"""The routed experts' grouped matrix products where an expert is two
matrices and a squared ReLU (``torchft_tpu/models/moe.py``:
``grouped_matmul`` with ``form="relu2"``, two a layer: up, down): the
operations and bytes their forward and backward need for a given number of
rows (token-expert pairs on held experts, all expert blocks of a step
together), and so the least time they can take.
``kernels/grouped_matmul.py`` counts the three products of a SwiGLU expert
and every layer past the dense ones; here the expert blocks are the ``E`` of
``hybrid_override_pattern`` among ``published_layers``. The recomputed
forward under ``remat`` is work the implementation chose and is not counted,
nor are the rows the kernels pad a group's last tile with."""

from __future__ import annotations

from typing import Any, Dict, Mapping


def expert_layers(cfg: Mapping[str, Any]) -> int:
    pattern = str(cfg["hybrid_override_pattern"])
    return sum(pattern[int(i)] == "E" for i in cfg["published_layers"])


def grouped_flops(rows: float, hidden: int, width: int) -> Dict[str, float]:
    """Two products of [rows, hidden] x [hidden, width] size forward; the
    backward of each is two of that size (to the rows, to the weights)."""
    fwd = 2 * 2.0 * rows * hidden * width
    return {"fwd": fwd, "bwd": 2 * fwd}


def grouped_bytes(rows: float, layers: int, held: int, hidden: int,
                  width: int, itemsize: int = 2) -> Dict[str, float]:
    """Every input read once and every output written once. Forward: the
    rows in, up out, its squared ReLU in, the rows out; the held experts'
    two matrices in ``itemsize``. Backward: each product reads its input
    rows, its output's cotangent and the matrix, and writes the input's
    cotangent, and the matrices' gradients leave in float32."""
    weights = layers * held * 2 * hidden * width
    acts_fwd = rows * (hidden + width + width + hidden)
    fwd = (weights + acts_fwd) * itemsize
    bwd = (weights + 2 * acts_fwd) * itemsize + weights * 4
    return {"fwd": float(fwd), "bwd": float(bwd)}


def least_seconds(cfg: Mapping[str, Any], rows: float,
                  peaks: Mapping[str, Any]) -> Dict[str, Any]:
    """One step's forward plus backward products over ``rows`` rows."""
    hidden, width = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    f = grouped_flops(rows, hidden, width)
    b = grouped_bytes(rows, expert_layers(cfg), int(cfg["num_experts_held"]),
                      hidden, width)
    t_flops = (f["fwd"] + f["bwd"]) / float(peaks["bf16_flops_per_s"])
    t_bytes = (b["fwd"] + b["bwd"]) / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "flops": f["fwd"] + f["bwd"], "bytes": b["fwd"] + b["bwd"]}
