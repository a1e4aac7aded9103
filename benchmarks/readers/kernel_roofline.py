"""A kernel's share of its roofline: the least time the counted steps' calls
can take (``kernels/<kernel>.py``: max(operations/peak, bytes/peak) of one
forward and one backward, times the calls a step makes, for every group that
shares the traced chip) over the device time of the kernel's events, summed
inside each counted step's own interval. A recomputed forward adds to the
time and not to the work."""

from harness import spec, trace_reduce
from harness.peaks import peaks_for
from harness.readers import traced_steps


def read(run, args):
    if run.get("device_trace") is None:
        return None
    inside = traced_steps(run)
    secs = count = 0
    for s in inside:
        one, n = trace_reduce.kernel_seconds(
            run["device_trace"]["planes"], args["pattern"], s["t0"], s["t1"])
        secs, count = secs + one, count + n
    if not count:
        return None
    kernel = spec.module("kernels", args["kernel"])
    least = kernel.least_seconds(run["cfg"], run["batch"], run["seq"],
                                 peaks_for(run["device_kind"]))
    calls = (len(inside) * kernel.calls_per_step(run["cfg"])
             * run["groups_on_device"])
    run.setdefault("notes", []).append(
        f"{args['pattern']}: {count} events in {len(inside)} steps, "
        f"{secs:.6f} s; {calls} forward+backward calls, each "
        f"{least['bound']}-bound, least {least['seconds']:.6f} s")
    return 100.0 * least["seconds"] * calls / secs
