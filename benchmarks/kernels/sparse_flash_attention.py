"""Kernel ``sparse_flash_attention`` (``torchft_tpu/ops/flash_attention.py``,
the custom calls ``flash_fwd_sparse`` / ``flash_bwd_sparse``): attention of
each query over the keys a learned index SELECTED for it. The operations and
bytes its forward and backward need, from shapes alone: the SELECTED pairs,
``sum_t min(t + 1, topk)`` a head (not the causal triangle, of which a
selection of 2,048 keeps 43.7 % at 8,192 tokens), two matmuls a pair forward
and five backward as ``kernels/flash_attention.py`` counts them; the bytes
that file counts plus the least a selection costs, ``topk`` indices of 2 B a
query. The count reads the same whatever implements the kernel: one that
computes the whole triangle under a mask reads under 44 % by construction,
one that gathers its keys could approach 100 %. Recomputation inside the
backward is not counted."""

from __future__ import annotations

from typing import Any, Dict, Mapping

from harness import spec


def selected_pairs(seq: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)`` over a sequence's queries."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def sparse_flops(batch: int, seq: int, heads: int, head_dim: int,
                 topk: int) -> Dict[str, float]:
    """``2 (d + d)`` operations a selected pair forward (QK^T, PV) and
    ``2 (3d + 2d)`` backward (the scores again, dP, dV, dQ, dK)."""
    pair = 2.0 * batch * heads * selected_pairs(seq, topk) * head_dim
    return {"fwd": 2 * pair, "bwd": 5 * pair}


def least_seconds(cfg: Mapping[str, Any], batch: int, seq: int,
                  peaks: Mapping[str, Any]) -> Dict[str, Any]:
    """One call's forward plus backward at the configuration's heads."""
    heads, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    topk = int(cfg["sa_config"]["topk"])
    f = sparse_flops(batch, seq, heads, d, topk)
    b = spec.module("kernels", "flash_attention").flash_bytes(
        batch, seq, heads, int(cfg["num_key_value_heads"]), d)
    indices = 2.0 * batch * seq * min(topk, seq) * 2     # read twice
    t_flops = (f["fwd"] + f["bwd"]) / float(peaks["bf16_flops_per_s"])
    t_bytes = (b["fwd"] + b["bwd"] + indices) / float(
        peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "flops": f["fwd"] + f["bwd"],
            "bytes": b["fwd"] + b["bwd"] + indices}


def calls_per_step(cfg: Mapping[str, Any]) -> int:
    """Forward-plus-backward calls in one group's step: one a layer."""
    return int(cfg["num_hidden_layers"])
