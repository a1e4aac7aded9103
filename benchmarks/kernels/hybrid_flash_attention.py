"""Kernel ``flash_attention`` in a model whose layers are not all
attention (``torchft_tpu/ops/flash_attention.py`` at a stated head size,
key/value heads shared through the index maps; the custom calls named
``attn``): the operations and bytes of the configuration's full-attention
layers only, found from ``published_layers`` and
``full_attention_interval``, at ``head_dim`` and not ``hidden_size /
num_attention_heads``. ``kernels/flash_attention.py`` would read a head of
2048 / 16 = 128 and a call in every layer.

Operations over the causal triangle (``S (S + 1) / 2`` visible pairs a
head): two matmuls forward, five backward (the scores again, dP, dV, dQ,
dK), each ``2 * head_dim`` a pair. Bytes as the full kernel's: every input
read once, K and V at the key/value heads' count."""

from __future__ import annotations

from typing import Any, Dict, Mapping

from harness import spec


def full_layers(cfg: Mapping[str, Any]) -> int:
    every = int(cfg["full_attention_interval"])
    return sum((int(i) + 1) % every == 0 for i in cfg["published_layers"])


def triangle_flops(batch: int, seq: int, heads: int, head_dim: int
                   ) -> Dict[str, float]:
    pair = 2.0 * batch * heads * head_dim * seq * (seq + 1) / 2
    return {"fwd": 2 * pair, "bwd": 5 * pair}


# ---- what a kernel-roofline reader asks of a kernel's file

def least_seconds(cfg: Mapping[str, Any], batch: int, seq: int,
                  peaks: Mapping[str, Any]) -> Dict[str, Any]:
    """One full-attention layer's forward plus backward."""
    heads = int(cfg["num_attention_heads"])
    kv, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    f = triangle_flops(batch, seq, heads, d)
    b = spec.module("kernels", "flash_attention").flash_bytes(
        batch, seq, heads, kv, d)
    t_flops = (f["fwd"] + f["bwd"]) / float(peaks["bf16_flops_per_s"])
    t_bytes = (b["fwd"] + b["bwd"]) / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "flops": f["fwd"] + f["bwd"], "bytes": b["fwd"] + b["bwd"]}


def calls_per_step(cfg: Mapping[str, Any]) -> int:
    """Forward-plus-backward calls in one group's step: one a running
    full-attention layer."""
    return full_layers(cfg)
