"""Kernel ``flash_attention`` with a window in some layers
(``torchft_tpu/ops/flash_attention.py``, ``window=``): the operations each
layer's forward and backward need over the part of the score matrix its kind
lets a query see (the causal triangle in a full layer, the band
``0 <= i - j < window`` in a sliding one), the bytes as the full kernel's
(every input read once), and so the least time a step's attention can take.
``kernels/flash_attention.py`` counts a full causal triangle in every layer
and would overstate a windowed layer's work."""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional

from harness import spec


def layer_windows(cfg: Mapping[str, Any]) -> List[Optional[int]]:
    """Each running layer's window, ``None`` for a full-attention layer."""
    kinds = [cfg["layer_types"][int(i)] for i in cfg["published_layers"]]
    return [int(cfg["sliding_window"]) if k == "sliding_attention" else None
            for k in kinds]


def visible_pairs(seq: int, window: Optional[int]) -> float:
    """Query-key pairs of a ``seq``-token sequence that the mask lets
    through."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2
    return window * (window + 1) / 2 + (seq - window) * window


def window_flops(batch: int, seq: int, heads: int, head_dim: int,
                 window: Optional[int]) -> Dict[str, float]:
    """Forward two matmuls, backward five, each 2*D an entry."""
    entry = 2.0 * batch * heads * head_dim * visible_pairs(seq, window)
    return {"fwd": 2 * entry, "bwd": 5 * entry}


# ---- what a kernel-roofline reader asks of a kernel's file

def least_seconds(cfg: Mapping[str, Any], batch: int, seq: int,
                  peaks: Mapping[str, Any]) -> Dict[str, Any]:
    """A step's attention: every running layer's forward plus backward."""
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    b = spec.module("kernels", "flash_attention").flash_bytes(
        batch, seq, heads, kv, d)
    seconds = flops = nbytes = 0.0
    bounds = set()
    for window in layer_windows(cfg):
        f = window_flops(batch, seq, heads, d, window)
        t_flops = (f["fwd"] + f["bwd"]) / float(peaks["bf16_flops_per_s"])
        t_bytes = (b["fwd"] + b["bwd"]) / float(peaks["hbm_bytes_per_s"])
        seconds += max(t_flops, t_bytes)
        bounds.add("compute" if t_flops >= t_bytes else "memory")
        flops += f["fwd"] + f["bwd"]
        nbytes += b["fwd"] + b["bwd"]
    return {"seconds": seconds, "bound": "/".join(sorted(bounds)),
            "flops": flops, "bytes": nbytes}


def calls_per_step(cfg: Mapping[str, Any]) -> int:
    """``least_seconds`` already holds every layer of a step."""
    return 1
