"""The share of the traced window in which no operation ran on the device:
1 - union of the device's operation intervals over the window."""

from harness import trace_reduce


def read(run, args):
    dev = run.get("device_trace")
    if dev is None:
        return None
    lo, hi = dev["lo"], dev["hi"]
    busy = trace_reduce.busy_seconds(dev["planes"], lo, hi)
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))
