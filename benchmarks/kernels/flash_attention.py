"""Kernel ``flash_attention`` (``torchft_tpu/ops/flash_attention.py``): the
operations and bytes its forward and backward need, from shapes, and so the
least time they can take on a chip. A kernel-roofline metric names this file
by ``"kernel"``. Recomputation inside the backward is work the implementation
chose and is not counted, so the share can only be understated."""

from __future__ import annotations

from typing import Any, Dict, Mapping


def flash_flops(batch: int, seq: int, heads: int, head_dim: int,
                causal: bool = True) -> Dict[str, float]:
    """Flash attention forward (QK^T, PV) and backward (the scores again,
    dP, dV, dQ, dK: five matmuls), each 2*B*H*S*S*D over the square."""
    square = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        square /= 2
    return {"fwd": 2 * square, "bwd": 5 * square}


def flash_bytes(batch: int, seq: int, heads: int, kv_heads: int,
                head_dim: int, itemsize: int = 2) -> Dict[str, float]:
    """Bytes the kernel must move if it reads every input once and writes
    every output once. GQA: K and V have ``kv_heads`` heads. The row
    statistics (log-sum-exp, and delta in the backward) are f32."""
    q = batch * seq * heads * head_dim * itemsize
    kv = batch * seq * kv_heads * head_dim * itemsize
    stat = batch * heads * seq * 4
    fwd = q + 2 * kv + q + stat                  # Q K V -> O, lse
    bwd = (q + 2 * kv + q + q + 2 * stat         # Q K V O dO lse delta
           + q + 2 * kv)                         # -> dQ dK dV
    return {"fwd": float(fwd), "bwd": float(bwd)}


def flash_roofline_seconds(batch: int, seq: int, heads: int, kv_heads: int,
                           head_dim: int, peaks: Mapping[str, Any]
                           ) -> Dict[str, Any]:
    """The least time one forward plus one backward of the kernel can take
    on a chip with ``peaks``, and which peak bounds it."""
    f = flash_flops(batch, seq, heads, head_dim)
    b = flash_bytes(batch, seq, heads, kv_heads, head_dim)
    t_flops = (f["fwd"] + f["bwd"]) / float(peaks["bf16_flops_per_s"])
    t_bytes = (b["fwd"] + b["bwd"]) / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "flops": f["fwd"] + f["bwd"], "bytes": b["fwd"] + b["bwd"]}


# ---- what a kernel-roofline reader asks of a kernel's file

def least_seconds(cfg: Mapping[str, Any], batch: int, seq: int,
                  peaks: Mapping[str, Any]) -> Dict[str, Any]:
    """One call's forward plus backward at the configuration's widths."""
    heads = int(cfg["num_attention_heads"])
    return flash_roofline_seconds(
        batch, seq, heads, int(cfg["num_key_value_heads"]),
        int(cfg["hidden_size"]) // heads, peaks)


def calls_per_step(cfg: Mapping[str, Any]) -> int:
    """Forward-plus-backward calls in one group's step: one a layer."""
    return int(cfg["num_hidden_layers"])
