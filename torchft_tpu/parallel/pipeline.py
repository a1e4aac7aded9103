"""Pipeline parallelism: GPipe microbatch schedule over the ``pp`` mesh axis.

New scope vs the reference (SURVEY.md §2: no PP). TPU-first shape: the
whole schedule is ONE jitted SPMD program under ``shard_map`` — every
stage executes the identical per-tick computation (no data-dependent
branching), activations hop stage→stage with ``lax.ppermute`` (ICI
neighbor traffic), and idle ticks are masked rather than skipped, which
is what keeps XLA's pipeline static. Differentiable end-to-end: the
backward schedule is the transpose XLA derives from ppermute/psum.

Layer weights live stacked as ``[pp, layers_per_stage, ...]`` with the
leading dim sharded over ``pp`` (:func:`stack_layer_params` builds this
from ordinary per-layer transformer params), so each stage holds only its
own layers — the memory win PP exists for.

Schedule: ticks ``t ∈ [0, n_micro + pp - 1)``; stage ``s`` processes
microbatch ``t - s`` when in range. Bubble fraction = (pp-1)/(n_micro+pp-1),
so use n_micro >= 4*pp in production.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_layer_params(params: Any, num_layers: int, pp: int,
                       prefix: str = "layer_") -> tuple[Any, Any]:
    """Split a flax Transformer param dict into (rest, stacked) where
    ``stacked`` carries the decoder layers as a ``[pp, L//pp, ...]`` pytree
    and ``rest`` is everything else (embed, final norm, head)."""
    inner = params["params"] if "params" in params else params
    layers = [inner[f"{prefix}{i}"] for i in range(num_layers)]
    assert num_layers % pp == 0, "num_layers must divide by pp stages"
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves).reshape(
            pp, num_layers // pp, *leaves[0].shape),
        *layers)
    rest = {k: v for k, v in inner.items() if not k.startswith(prefix)}
    return rest, stacked


def pipeline_spec(tree: Any, mesh: Mesh, axis: str = "pp") -> Any:
    """NamedShardings placing a stacked-layer pytree's leading dim on
    ``axis``."""
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(
            mesh, P(axis, *([None] * (leaf.ndim - 1)))),
        tree)


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    x: jnp.ndarray,
    n_micro: int,
    mesh: Mesh,
    axis: str = "pp",
    batch_axes=("dp", "fsdp"),
) -> jnp.ndarray:
    """Run ``x`` through the pipeline; returns the last stage's outputs.

    Args:
        stage_fn: ``(stage_params, activations) -> activations`` applying
            one stage's layers; ``stage_params`` is the ``[L//pp, ...]``
            slice owned by the stage.
        stacked_params: ``[pp, L//pp, ...]`` pytree (shard leading dim on
            ``axis`` — see :func:`pipeline_spec`).
        x: ``[B, ...]`` inputs; B must divide by ``n_micro`` (and by the
            product of present ``batch_axes`` sizes — the batch dim is
            sharded over those axes so pp composes with real data
            parallelism instead of replicating the schedule per dp slice).
    """
    pp = mesh.shape[axis]
    if pp == 1:
        return stage_fn(jax.tree_util.tree_map(lambda p: p[0],
                                               stacked_params), x)
    b = x.shape[0]
    assert b % n_micro == 0, "batch must divide into microbatches"
    micro = x.reshape(n_micro, b // n_micro, *x.shape[1:])

    def body(params_stacked, micro_local):
        # shard_map gives [1, L//pp, ...]; drop the stage dim.
        params_local = jax.tree_util.tree_map(lambda p: p[0],
                                              params_stacked)
        stage = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % pp) for i in range(pp)]
        n_ticks = n_micro + pp - 1

        received0 = jnp.zeros_like(micro_local[0])
        ys0 = jnp.zeros_like(micro_local)

        def tick(carry, t):
            received, ys = carry
            m0 = jnp.clip(t, 0, n_micro - 1)
            x_in = jax.lax.dynamic_index_in_dim(micro_local, m0, axis=0,
                                                keepdims=False)
            inp = jnp.where(stage == 0, x_in, received)
            out = stage_fn(params_local, inp)
            # Last stage banks microbatch t-(pp-1) when in range.
            m_last = t - (pp - 1)
            valid = jnp.logical_and(m_last >= 0, stage == pp - 1)
            banked = jax.lax.dynamic_update_index_in_dim(
                ys, out, jnp.clip(m_last, 0, n_micro - 1), axis=0)
            ys = jnp.where(valid, banked, ys)
            received = jax.lax.ppermute(out, axis, perm)
            return (received, ys), None

        (_, ys), _ = jax.lax.scan(tick, (received0, ys0),
                                  jnp.arange(n_ticks))
        # Only the last stage holds real outputs; psum-mask replicates them.
        ys = jnp.where(stage == pp - 1, ys, jnp.zeros_like(ys))
        return jax.lax.psum(ys, axis)

    present = tuple(a for a in batch_axes
                    if a in mesh.axis_names and mesh.shape[a] > 1)
    bspec = present if present else None
    micro_spec = P(None, bspec)  # [n_micro, B_m, ...]: batch over dp axes
    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stacked_params),
        micro_spec,
    )
    fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=micro_spec, check_vma=False)
    ys = fn(stacked_params, micro)
    return ys.reshape(b, *x.shape[1:])


# ---------------------------------------------------------------------------
# Pipelined transformer: reuses the flax DecoderLayer weights, stacked.
# ---------------------------------------------------------------------------


def transformer_pipeline_forward(
    cfg: Any,
    params: Any,
    tokens: jnp.ndarray,
    mesh: Mesh,
    n_micro: int = 4,
    axis: str = "pp",
    batch_axes=("dp", "fsdp"),
) -> jnp.ndarray:
    """Full forward of :class:`~torchft_tpu.models.transformer.Transformer`
    with the decoder layers pipelined over ``axis``.

    ``params`` is the ordinary ``Transformer.init`` dict; embed/norm/head
    stay replicated (they are small), layers run through the pipeline.
    """
    from torchft_tpu.models.transformer import DecoderLayer, RMSNorm

    rest, stacked = stack_layer_params(params, cfg.num_layers,
                                       mesh.shape[axis])

    emb = rest["embed"]["embedding"]
    x = emb[tokens].astype(cfg.dtype)

    layer = DecoderLayer(cfg)

    def stage_fn(stage_params, h):
        # positions rebuilt per microbatch (identical across batch rows)
        positions = jnp.broadcast_to(jnp.arange(h.shape[1]), h.shape[:2])

        def one_layer(h, lp):
            return layer.apply({"params": lp}, h, positions), None

        h, _ = jax.lax.scan(one_layer, h, stage_params)
        return h

    x = pipeline_apply(stage_fn, stacked, x, n_micro, mesh, axis,
                       batch_axes)

    x = RMSNorm().apply({"params": rest["final_norm"]}, x)
    return jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                      rest["lm_head"]["kernel"].astype(jnp.float32))
