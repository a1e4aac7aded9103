"""Hardware check of the fused flash-attention backward.

The fused backward computes dq, dk and dv from one recompute a block and
holds one (batch, head)'s dq in a VMEM accumulator for the whole sweep of
its grid row (``flash_attention.py`` ``_bwd_fused_kernel``); the split
backward is two kernels that each recompute. Off a chip the fused kernel
runs only interpreted and steered (``tests/test_flash_band.py``): what
Mosaic makes of it, the dynamic row slice into the accumulator and the
VMEM the call asks for, only a chip shows. This module runs the SAME
backward twice on hardware, fused (``TORCHFT_FLASH_FUSED_BWD=1``) and
split (``=0``), and compares dq/dk/dv: both accumulate in f32 over the
same key-block order, so anything beyond last-ulp noise is a fault.

Exit codes of ``python -m torchft_tpu.ops.fused_bwd_check``: 0 = match,
75 = no TPU backend (the caller decides whether that is a skip or a
failure), 1 = MISMATCH (do not ship; set ``TORCHFT_FLASH_FUSED_BWD=0``
operationally until fixed).

Run nightly via ``tests/test_attention.py::TestFusedBwdHardware`` (marker
``nightly``), by ``chip_smoke.py`` on every chip run (which calls
:func:`fused_vs_split` directly and treats a missing TPU as a failure),
and manually after any jaxlib/libtpu upgrade or block-shape change.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Optional, Tuple

SKIP = 75


# Both paths accumulate dq in f32 over the same k-block order; a lost or
# doubled tile is rel ~ O(1). 1e-3 leaves room for bf16 recompute noise
# while catching any real corruption.
TOLERANCE = 1e-3


def _grads(q, k, v, use_fused: bool, block: int, interpret: Optional[bool]):
    import jax

    from torchft_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        # The block is pinned explicitly, so the q grid is eight deep at
        # the default shape (auto-pick would choose block_q=1024: four).
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block,
                               interpret=interpret).astype("float32").sum()

    # The env var is read at TRACE time inside _flash_bwd; each call here
    # builds a fresh closure, so jax.jit re-traces and the toggle takes
    # effect (a shared cached jit would silently reuse the first variant).
    prev = os.environ.get("TORCHFT_FLASH_FUSED_BWD")
    os.environ["TORCHFT_FLASH_FUSED_BWD"] = "1" if use_fused else "0"
    try:
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    finally:
        if prev is None:
            del os.environ["TORCHFT_FLASH_FUSED_BWD"]
        else:
            os.environ["TORCHFT_FLASH_FUSED_BWD"] = prev


def fused_vs_split(shape: Tuple[int, int, int, int] = (1, 4096, 8, 128),
                   block: int = 512,
                   interpret: Optional[bool] = None) -> Dict[str, float]:
    """Run the same backward fused and split on the current backend and
    return the relative max difference of dq/dk/dv (difference over the
    split path's max magnitude) plus ``worst``. The default shape has a
    deep q grid (nqb = 4096/512 = 8 >= 4) so the fused path is taken."""
    import jax
    import jax.numpy as jnp

    assert shape[1] // block >= 4, "q grid too shallow for the fused path"
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in ks)
    fused = _grads(q, k, v, True, block, interpret)
    split = _grads(q, k, v, False, block, interpret)
    out: Dict[str, float] = {}
    for name, a, bb in zip(("dq", "dk", "dv"), fused, split):
        diff = float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - bb.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(bb.astype(jnp.float32)))) or 1.0
        out[name] = diff / scale
    out["worst"] = max(out.values())
    return out


def main() -> int:
    import jax

    if jax.default_backend() != "tpu":
        print(f"fused_bwd_check: no TPU backend "
              f"({jax.default_backend()})", file=sys.stderr)
        return SKIP
    rel = fused_vs_split()
    for name in ("dq", "dk", "dv"):
        print(f"fused_bwd_check: {name} rel={rel[name]:.3e}")
    if rel["worst"] > TOLERANCE:
        print("fused_bwd_check: MISMATCH: the fused kernel's dq, dk or dv "
              "is not the split kernels'; set TORCHFT_FLASH_FUSED_BWD=0 "
              "and investigate", file=sys.stderr)
        return 1
    print("fused_bwd_check: OK (fused == split on hardware)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
