"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it. A kind that is not here is an error, never
a default: a roofline share against a guessed peak is worse than none."""

from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" system architecture page: 197 TFLOP/s
# bf16 and 393 TOP/s int8 per chip, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect. JAX names the chip "TPU v5 lite".
_V5E = {
    "bf16_flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "cloud.google.com/tpu/docs/v5e (system architecture table)",
}

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add a row with its source.") from None
