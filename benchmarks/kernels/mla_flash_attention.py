"""Kernel ``flash_attention`` at two head sizes, as latent attention calls
it (``torchft_tpu/ops/flash_attention.py``: queries and keys ``d_qk`` wide,
values ``d_v``; the custom calls named ``flash_*_mla``): the operations its
forward and backward need over the causal triangle and the least bytes any
implementation has to move, and so the least time a step's attention can
take. ``kernels/flash_attention.py`` counts one head size for all five
matmuls and would overstate this kernel's work by a fifth.

Operations, a visible query-key pair a head: the forward's scores contract
over ``d_qk`` and its PV over ``d_v``: ``2 (d_qk + d_v)``; the backward's
scores again, dQ and dK over ``d_qk`` and dP and dV over ``d_v``:
``2 (3 d_qk + 2 d_v)``. Recomputation is not counted.

Bytes: what the algorithm needs and not what the program happens to move:
a head's query ``d_qk`` wide, its no-position key and its value
``d_nope`` / ``d_v`` wide, and the rotary key ONCE, since it is one head
shared by all (the program broadcasts it to every head before the kernel;
those bytes are the implementation's)."""

from __future__ import annotations

from typing import Any, Dict, Mapping


def _sizes(cfg: Mapping[str, Any]) -> Dict[str, int]:
    return dict(heads=int(cfg["num_attention_heads"]),
                nope=int(cfg["qk_nope_head_dim"]),
                rope=int(cfg["qk_rope_head_dim"]),
                d_v=int(cfg["v_head_dim"]))


def mla_flops(batch: int, seq: int, heads: int, d_qk: int, d_v: int
              ) -> Dict[str, float]:
    pairs = batch * heads * seq * (seq + 1) / 2
    return {"fwd": 2.0 * (d_qk + d_v) * pairs,
            "bwd": 2.0 * (3 * d_qk + 2 * d_v) * pairs}


def mla_bytes(batch: int, seq: int, heads: int, nope: int, rope: int,
              d_v: int, itemsize: int = 2) -> Dict[str, float]:
    rows = batch * seq
    q = rows * heads * (nope + rope) * itemsize
    k = rows * (heads * nope + rope) * itemsize     # the rotary key once
    v = rows * heads * d_v * itemsize
    stat = rows * heads * 4
    fwd = q + k + v + v + stat                   # Q K V -> O, lse
    bwd = (q + k + v + v + v + 2 * stat          # Q K V O dO lse delta
           + q + k + v)                          # -> dQ dK dV
    return {"fwd": float(fwd), "bwd": float(bwd)}


# ---- what a kernel-roofline reader asks of a kernel's file

def least_seconds(cfg: Mapping[str, Any], batch: int, seq: int,
                  peaks: Mapping[str, Any]) -> Dict[str, Any]:
    """One layer's forward plus backward at the configuration's widths."""
    s = _sizes(cfg)
    f = mla_flops(batch, seq, s["heads"], s["nope"] + s["rope"], s["d_v"])
    b = mla_bytes(batch, seq, s["heads"], s["nope"], s["rope"], s["d_v"])
    t_flops = (f["fwd"] + f["bwd"]) / float(peaks["bf16_flops_per_s"])
    t_bytes = (b["fwd"] + b["bwd"]) / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "flops": f["fwd"] + f["bwd"], "bytes": b["fwd"] + b["bwd"]}


def calls_per_step(cfg: Mapping[str, Any]) -> int:
    """Forward-plus-backward calls in one group's step: one a running
    published layer, the prediction module's among them."""
    return len(cfg["published_layers"])
