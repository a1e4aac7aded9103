"""As ``op_ms``, for a cell whose device trace may end early: device time
(ms a step) of the operations matching ``pattern``, over the counted traced
steps **that the device trace itself shows whole**: those inside whose
interval a program matching ``module`` (the step's own XLA module) ran from
start to end. ``op_ms`` divides by every counted step between the
profiler's start and stop on the host's clock; where the profiler stopped
recording device events before that (a cell with tens of thousands of short
loop operations a second: ``qwen3-next-80b-a3b.steady-1g-8k`` lost 8 of 14
steps in one traced run of two, PERF.md PR 40) it under-reads, and a share
of a roofline reckoned that way over-reads. Nothing to read gives ``None``."""

from harness import trace_reduce
from harness.readers import traced_steps


def seen_steps(run, module):
    """The counted traced steps in which the device trace holds a whole run
    of a program matching ``module``."""
    modules = run["device_trace"].get("modules") or {}
    return [s for s in traced_steps(run)
            if trace_reduce.kernel_seconds(modules, module,
                                           s["t0"], s["t1"])[1]]


def seconds_in_seen_steps(run, pattern, module):
    """``(seconds, events, steps)`` of the events matching ``pattern``
    inside the steps the device trace shows whole."""
    seen = seen_steps(run, module)
    secs = count = 0
    for s in seen:
        one, n = trace_reduce.kernel_seconds(
            run["device_trace"]["planes"], pattern, s["t0"], s["t1"])
        secs, count = secs + one, count + n
    return secs, count, len(seen)


def read(run, args):
    if run.get("device_trace") is None:
        return None
    secs, count, steps = seconds_in_seen_steps(run, args["pattern"],
                                               args["module"])
    if not count:
        return None
    run.setdefault("notes", []).append(
        f"{args['pattern']}: {count} events in {steps} steps the device "
        f"trace shows whole, {secs:.6f} s")
    return 1e3 * secs / steps
