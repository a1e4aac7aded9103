"""As ``counter_delta``, for a counter that a program may not have: where the
window's last snapshot lacks ``key`` (a parent commit from before the counter)
there is nothing to read, and ``counter_delta`` would raise."""

from harness import spec


def read(run, args):
    base = spec.module("readers", "counter_delta")
    pair = base._counters(run, args.get("on", "leader"))
    if pair is None or pair[0] is None or args["key"] not in pair[1]:
        return None
    return base.read(run, args)
