"""Device time (ms a step) of the XLA programs whose name matches
``pattern``, summed inside each counted step's own interval: what runs on the
device during a step that does not count (a solo or a heal step between two
counted ones) is left out."""

from harness import trace_reduce
from harness.readers import traced_steps


def read(run, args):
    if run.get("device_trace") is None:
        return None
    inside = traced_steps(run)
    secs = count = 0
    for s in inside:
        one, n = trace_reduce.kernel_seconds(
            run["device_trace"]["modules"], args["pattern"], s["t0"], s["t1"])
        secs, count = secs + one, count + n
    return 1e3 * secs / len(inside) if count else None
