"""As ``kernel_roofline``, with the calls reckoned from the counted traced
steps that the device trace itself shows whole (``op_ms_seen.py`` says
which and why): the least time those steps' calls can take over the device
time of the kernel's events inside them. A trace that ends early then reads
the same share over fewer steps, and not a share over 100 %."""

from harness import spec
from harness.peaks import peaks_for


def read(run, args):
    if run.get("device_trace") is None:
        return None
    secs, count, steps = spec.module(
        "readers", "op_ms_seen").seconds_in_seen_steps(
            run, args["pattern"], args["module"])
    if not count:
        return None
    kernel = spec.module("kernels", args["kernel"])
    least = kernel.least_seconds(run["cfg"], run["batch"], run["seq"],
                                 peaks_for(run["device_kind"]))
    calls = steps * kernel.calls_per_step(run["cfg"]) \
        * run["groups_on_device"]
    run.setdefault("notes", []).append(
        f"{args['pattern']}: {count} events in {steps} steps the device "
        f"trace shows whole, {secs:.6f} s; {calls} forward+backward calls, "
        f"each {least['bound']}-bound, least {least['seconds']:.6f} s")
    return 100.0 * least["seconds"] * calls / secs
