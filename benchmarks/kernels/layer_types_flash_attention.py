"""Kernel ``flash_attention`` in a model whose layers are named by
``layer_types`` and are not all attention
(``torchft_tpu/ops/flash_attention.py`` at a head of ``hidden_size /
num_attention_heads``, key/value heads shared through the index maps; the
custom calls named ``attn``): the operations and bytes of the
configuration's ``full_attention`` layers only, found from
``published_layers`` and ``layer_types``. ``kernels/flash_attention.py``
would read a call in every layer.

Operations over the causal triangle as ``kernels/hybrid_flash_attention.py``
counts them (``triangle_flops``: two matmuls forward, five backward, each
``2 * head_dim`` a visible pair a head); bytes as the full kernel's: every
input read once, K and V at the key/value heads' count."""

from __future__ import annotations

from typing import Any, Dict, Mapping

from harness import spec


def attention_layers(cfg: Mapping[str, Any]) -> int:
    return sum(cfg["layer_types"][int(i)] == "full_attention"
               for i in cfg["published_layers"])


# ---- what a kernel-roofline reader asks of a kernel's file

def least_seconds(cfg: Mapping[str, Any], batch: int, seq: int,
                  peaks: Mapping[str, Any]) -> Dict[str, Any]:
    """One attention layer's forward plus backward."""
    heads = int(cfg["num_attention_heads"])
    kv, d = int(cfg["num_key_value_heads"]), int(cfg["hidden_size"]) // heads
    f = spec.module("kernels", "hybrid_flash_attention").triangle_flops(
        batch, seq, heads, d)
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
    return attention_layers(cfg)
