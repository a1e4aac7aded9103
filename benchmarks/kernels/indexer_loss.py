"""Kernel ``indexer_loss`` (``torchft_tpu/ops/sparse_index.py``): the
indexer's Kullback-Leibler loss against the attention's own probabilities
and, in the same pass, its gradient through the index scores. What it needs,
from shapes alone: the backward of the index scores on the SELECTED pairs
(``dI`` is zero elsewhere), two products of ``2 J c`` operations a pair (to
the index queries, to the index key); the index operands and the attention's
queries, keys and logsumexp read once, the three gradients written in
float32. Not counted, as work the implementation chose: the second pass over
``q k^T`` that rebuilds the probabilities a tile (the attention's forward had
them), the index scores recomputed a tile, and everything on the vector unit.
The share is therefore low by construction; its device time is what the next
change to this kernel is measured by."""

from __future__ import annotations

from typing import Any, Dict, Mapping

from harness import spec


def least_seconds(cfg: Mapping[str, Any], batch: int, seq: int,
                  peaks: Mapping[str, Any]) -> Dict[str, Any]:
    """One call: the loss and its gradient come from one pass."""
    sa = cfg["sa_config"]
    heads, dim = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    pairs = spec.module("kernels", "sparse_flash_attention").selected_pairs(
        seq, int(sa["topk"]))
    flops = 2 * 2.0 * heads * dim * batch * pairs
    h, g = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    index_in = (heads * dim + dim) * 2 + heads * 4
    index_out = (heads * dim + dim + heads) * 4
    nbytes = batch * seq * ((h + g) * d * 2 + h * 4 + index_in + index_out
                            + min(int(sa["topk"]), seq) * 2)
    t_flops = flops / float(peaks["bf16_flops_per_s"])
    t_bytes = nbytes / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "flops": flops, "bytes": float(nbytes)}


def calls_per_step(cfg: Mapping[str, Any]) -> int:
    """Calls in one group's step: one a layer."""
    return int(cfg["num_hidden_layers"])
