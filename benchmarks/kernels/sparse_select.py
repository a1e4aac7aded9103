"""Kernel ``sparse_select`` (``torchft_tpu/ops/sparse_index.py``): the index
scores of a learned sparse attention and each query's ``topk`` largest. What
it needs, from shapes alone: the scores over the causal triangle, ``2 J c``
operations a pair (``J`` index heads of ``c`` on one key head; the ReLU, the
weighting and the selection's compares ride the vector unit and are not
counted), the index queries, key and weights read once and, as the least a
selection costs, ``topk`` indices of 2 B a query written. The kernel as built
scores every key of a row block (twice the triangle) and sweeps the block 45
times for its thresholds: both read as a share below what a sort-free
selection over the triangle could reach."""

from __future__ import annotations

from typing import Any, Dict, Mapping


def least_seconds(cfg: Mapping[str, Any], batch: int, seq: int,
                  peaks: Mapping[str, Any]) -> Dict[str, Any]:
    """One call (the kernel has no backward: the selection is a hard set)."""
    sa = cfg["sa_config"]
    heads, dim = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    flops = 2.0 * heads * dim * batch * seq * (seq + 1) / 2
    nbytes = batch * seq * ((heads * dim + dim) * 2 + heads * 4
                            + min(int(sa["topk"]), seq) * 2)
    t_flops = flops / float(peaks["bf16_flops_per_s"])
    t_bytes = nbytes / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "flops": flops, "bytes": float(nbytes)}


def calls_per_step(cfg: Mapping[str, Any]) -> int:
    """Calls in one group's step: one a layer."""
    return int(cfg["num_hidden_layers"])
