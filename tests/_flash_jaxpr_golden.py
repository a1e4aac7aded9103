"""What ``tests/golden_flash_jaxpr_pr57.json`` holds and how it is made: the
flash kernels' forward and gradient traced (``jax.make_jaxpr``, interpret
mode, nothing run) at query/key heads of at most one 128-lane tile (64,
96/64, 128, 128 under a window), and of each the sha256 of the jaxpr's
text. The file was written by running this module on PR 57's parent
(``python tests/_flash_jaxpr_golden.py <file>`` in a ``git archive`` of
``25549b4``); ``tests/test_flash_latent.py`` computes the same on the
tree: a change to what a head OVER one lane tile asks for (PR 57: its
scoped VMEM) that reaches a head it should leave alone fails there. A
change that means to alter those calls' programs takes the file again and
says so."""

import hashlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#           d_qk, d_v, window
ONE_LANE_TILE = {"64": (64, 64, None), "96_64": (96, 64, None),
                 "128": (128, 128, None), "128_window": (128, 128, 96)}


def grad_jaxpr_text(d_qk: int, d_v: int, window=None) -> str:
    """The jaxpr of forward and gradients of one causal call: four query
    heads on two key/value heads, 256 tokens."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    from torchft_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((2, 256, 4, d_qk), jnp.float32)
    k = jax.ShapeDtypeStruct((2, 256, 2, d_qk), jnp.float32)
    v = jax.ShapeDtypeStruct((2, 256, 2, d_v), jnp.float32)
    return str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True, interpret=True,
                                        window=window).sum(),
        argnums=(0, 1, 2)))(q, k, v))


def jaxpr_hash(case: str) -> str:
    return hashlib.sha256(
        grad_jaxpr_text(*ONE_LANE_TILE[case]).encode()).hexdigest()


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        json.dump({case: jaxpr_hash(case) for case in ONE_LANE_TILE}, f,
                  indent=1)
        f.write("\n")
