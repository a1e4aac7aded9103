"""Device time (ms a step) of the operations whose event name matches
``pattern``, summed inside each counted step's own interval. Nothing to
read (no trace, or a program whose operations carry no such name) gives
``None``."""

from harness import trace_reduce
from harness.readers import traced_steps


def seconds_in_steps(run, pattern):
    """``(seconds, events, steps)``: summed device time and count of the
    events matching ``pattern`` inside the counted traced steps, and how
    many such steps there are."""
    inside = traced_steps(run)
    secs = count = 0
    for s in inside:
        one, n = trace_reduce.kernel_seconds(
            run["device_trace"]["planes"], pattern, s["t0"], s["t1"])
        secs, count = secs + one, count + n
    return secs, count, len(inside)


def read(run, args):
    if run.get("device_trace") is None:
        return None
    secs, count, steps = seconds_in_steps(run, args["pattern"])
    if not count:
        return None
    run.setdefault("notes", []).append(
        f"{args['pattern']}: {count} events in {steps} steps, {secs:.6f} s")
    return 1e3 * secs / steps
