"""Kernel ``flash_attention`` in a looped stack
(``torchft_tpu/ops/flash_attention.py`` at the configuration's stated
``head_dim``, plain multi-head attention: as many key/value heads as query
heads; the custom calls named ``attn`` inside the pass scan): the operations
and bytes of one forward plus one backward call as
``kernels/flash_attention.py`` counts them, and a step's calls: one a layer
A PASS, ``num_hidden_layers * total_ut_steps``. ``kernels/flash_attention.py``
counts one a layer and would read ``total_ut_steps`` times the truth here.
The forward that the rematerialised layer runs again in the backward pass
adds to the time and not to the work."""

from __future__ import annotations

from typing import Any, Dict, Mapping

from harness import spec


# ---- what a kernel-roofline reader asks of a kernel's file

def least_seconds(cfg: Mapping[str, Any], batch: int, seq: int,
                  peaks: Mapping[str, Any]) -> Dict[str, Any]:
    """One call's forward plus backward at the configuration's heads."""
    return spec.module("kernels", "flash_attention").flash_roofline_seconds(
        batch, seq, int(cfg["num_attention_heads"]),
        int(cfg["num_key_value_heads"]), int(cfg["head_dim"]), peaks)


def calls_per_step(cfg: Mapping[str, Any]) -> int:
    """Forward-plus-backward calls in one group's step: one a layer a
    pass."""
    return int(cfg["num_hidden_layers"]) * int(cfg["total_ut_steps"])
