"""The grouped matrix products' share of their roofline, with the work
reckoned from the rows that were there: the window's own change of the
program counter ``pairs`` (token-expert pairs that reached held experts, all
expert layers together) over the change of ``steps`` gives the rows a step,
``kernels/<kernel>.py`` the least time a step's forward and backward products
over that many rows can take, and the device time is that of the operations
matching ``pattern`` inside each counted traced step. A share reckoned from
the expected count could read over 100 % in a window that routed fewer.

The counters are the process's: where several groups share the traced chip
they hold every group's pairs, as the trace holds every group's operations.
A program without the counter or without such operations gives ``None``."""

from harness import spec
from harness.peaks import peaks_for


def _rows_a_step(run, args):
    c = run.get("counters", {})
    begin = c.get("begin.0")
    end = [c[k] for k in c if k.startswith("end.0.")]
    if begin is None or not end:
        return None
    try:
        pairs = float(end[0][args["pairs"]]) - float(
            begin.get(args["pairs"], 0.0))
        steps = float(end[0][args["steps"]]) - float(
            begin.get(args["steps"], 0.0))
    except KeyError:
        return None
    return pairs / steps if steps > 0 and pairs > 0 else None


def read(run, args):
    if run.get("device_trace") is None or run.get("device_kind") is None:
        return None
    rows = _rows_a_step(run, args)
    if rows is None:
        return None
    secs, count, steps = spec.module("readers", "op_ms").seconds_in_steps(
        run, args["pattern"])
    if not count:
        return None
    least = spec.module("kernels", args["kernel"]).least_seconds(
        run["cfg"], rows, peaks_for(run["device_kind"]))
    run.setdefault("notes", []).append(
        f"{args['pattern']}: {count} events in {steps} steps, "
        f"{secs:.6f} s; {rows:.1f} rows a step, {least['bound']}-bound, "
        f"least {least['seconds']:.6f} s a step")
    return 100.0 * least["seconds"] * steps / secs
