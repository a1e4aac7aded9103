"""The builder's operations per token (recompute not counted) times the
traced run's own tokens per second per chip with fault tolerance on, over the
chip's peak."""

from harness import spec
from harness.peaks import peaks_for


def read(run, args):
    rate = run.get("tokens_per_s_untraced") or run.get("tokens_per_s")
    if rate is None or run["device_kind"] is None:
        return None
    per_token = getattr(spec.model_of(run["cfg"]), args["flops"])(
        run["cfg"], run["seq"])
    peak = float(peaks_for(run["device_kind"])["bf16_flops_per_s"])
    return 100.0 * per_token * rate / peak
