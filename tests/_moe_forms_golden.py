"""What ``tests/golden_moe_forms_pr50.json`` holds and how it is made: a
small ``RoutedMoEMLP`` over a share of its experts with a shared expert, in
each form PR 50's tree had (``swiglu``, ``relu2``), float32 and bfloat16, on
seeded weights and inputs: its output, its first three stats (all it had
then) and every gradient
(the pass loops' hand-written backward) as two wrapping 32-bit sums of the
bit patterns. The file was written by running this module on PR 50's tree
(``python tests/_moe_forms_golden.py <file>``), the commit before the layer
took ``route_on`` and a third form; ``tests/test_moe_routed.py`` computes
the same with the tree's own layer, the router's input not named and named
as the layer's input."""

import json
import sys

import jax
import jax.numpy as jnp

FORMS_THEN = ("swiglu", "relu2")


def digests(form: str, dtype_name: str, **call) -> dict:
    """``call``: further arguments of the layer's call, as a function of
    its input (``route_on=lambda x: x``)."""
    from torchft_tpu.models.moe import RoutedMoEMLP

    layer = RoutedMoEMLP(num_experts=16, mlp_dim=32, top_k=4, held=(4, 8),
                         shared_dim=32, route_scale=2.826, form=form,
                         dtype=getattr(jnp, dtype_name), pass_rows=512,
                         interpret=True)
    x = jax.random.normal(jax.random.key(7), (2, 128, 64), jnp.float32)
    params = layer.init(jax.random.key(8), x)

    def f(p, x):
        out, stats = layer.apply(
            p, x, return_stats=True, **{k: v(x) for k, v in call.items()})
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), (out, stats)

    (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True))(params, x)
    # the three whole numbers PR 50's tree had (a fourth since PR 53)
    named = {"out": out, "stats": stats[:3]}
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
        json.dump({form: {k: digests(form, k)
                          for k in ("float32", "bfloat16")}
                   for form in FORMS_THEN}, f, indent=1)
        f.write("\n")
