"""What ``tests/golden_gdn_pr49.json`` holds and how it is made: a small
``GatedDeltaNet`` (float32 and bfloat16) on seeded weights and inputs, its
output and every gradient as two wrapping 32-bit sums of the bit patterns.
The file was written by running this module on PR 49's tree
(``python tests/_gdn_golden.py <file>``), whose rule runs its recurrence
and its chunk inverse in Pallas kernels, interpreted on the CPU;
``tests/test_ssd.py`` computes the same with the tree's own layer. (Until
PR 49 the file was ``golden_gdn_pr44.json``, from the commit before PR 45:
the ``lax.scan`` and XLA's substitution, other roundings in the last bits.)"""

import json
import sys

import jax
import jax.numpy as jnp


def digests(dtype_name: str) -> dict:
    from torchft_tpu.models import GatedDeltaNet
    from torchft_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        embed_dim=64, num_heads=4, dtype=getattr(jnp, dtype_name),
        linear_key_heads=2, linear_key_dim=16, linear_value_heads=4,
        linear_value_dim=16)
    layer = GatedDeltaNet(cfg)
    x = jax.random.normal(jax.random.key(7), (2, 96, 64), jnp.float32)
    params = layer.init(jax.random.key(8), x)

    def f(p, x):
        out = layer.apply(p, x)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True))(params, x)
    named = {"out": out}
    for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
        named["grad" + jax.tree_util.keystr(path)] = leaf
    result = {}
    for name, leaf in named.items():
        bits = jax.lax.bitcast_convert_type(
            leaf.reshape(-1).astype(jnp.float32), jnp.uint32)
        idx = jnp.arange(bits.size, dtype=jnp.uint32)
        result[name] = [int(jnp.sum(bits)),
                        int(jnp.sum(bits * (2 * idx + 1)))]
    return result


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        json.dump({k: digests(k) for k in ("float32", "bfloat16")}, f,
                  indent=1)
        f.write("\n")
