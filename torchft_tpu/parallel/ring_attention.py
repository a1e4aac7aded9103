"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context scaling, first-class in the TPU build (new scope vs the
reference, which has no sequence parallelism — SURVEY.md §2). The sequence
dimension is sharded over the ``sp`` mesh axis; each device holds one
query block permanently and streams the K/V blocks around the ring with
``lax.ppermute`` (ICI neighbor traffic, bandwidth-optimal), accumulating
the softmax online — attention over sequence length S costs O(S/n) memory
per device and never materializes an [S, S] matrix, while the K/V transfer
overlaps the per-block compute under XLA's scheduler.

Pure lax ops inside ``shard_map`` → differentiable (shard_map transposes
ppermute), so this drops straight into training.

Use with the transformer::

    ring = make_ring_attention(mesh, axis="sp")
    cfg = TransformerConfig(..., attention_fn=ring)
    # shard tokens with batch_spec(mesh, seq_axis="sp"): [B, S] → (dp, sp)
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _ring_body(q, k, v, axis: str, causal: bool):
    """Local computation inside shard_map. q/k/v: [B, S_local, H, D].

    Each K/V block is processed by the Pallas flash kernel
    (:func:`~torchft_tpu.ops.flash_attention.flash_attention_block`) with
    a traced shift selecting the block's mask — full for past blocks,
    diagonal-causal for the resident block, fully-blocked for future ones
    — and the block-normalized outputs merge online-softmax style via
    their logsumexps. Per-device memory is O(tile), never
    O(s_local^2)."""
    from torchft_tpu.ops.flash_attention import flash_attention_block

    n = jax.lax.psum(1, axis)
    my = jax.lax.axis_index(axis)

    b, s_loc, h, d = q.shape
    m_run = jnp.full((b * h, s_loc), NEG_INF, jnp.float32)
    r = jnp.zeros((b * h, s_loc), jnp.float32)
    acc = jnp.zeros((b, s_loc, h, d), jnp.float32)

    # Block t holds K/V originating from device (my - t) mod n.
    perm = [(i, (i + 1) % n) for i in range(n)]

    def per_row(x):  # [b*h, s] -> [b, s, h, 1] aligned with outputs
        return x.reshape(b, h, s_loc).transpose(0, 2, 1)[..., None]

    def step(t, carry):
        k_t, v_t, m_run, r, acc = carry
        src = (my - t) % n
        if causal:
            # src < my → past block (full); src == my → diagonal
            # (causal within); src > my → future (blocked; its lse comes
            # back ~ -inf so it merges with weight 0).
            shift = jnp.where(src < my, s_loc,
                              jnp.where(src == my, 0, -s_loc))
        else:
            shift = jnp.int32(s_loc)
        out_t, lse_t = flash_attention_block(q, k_t, v_t, shift)
        # Online-softmax merge across blocks. t=0 is always the resident
        # (diagonal) block, so m_run is real before any blocked block's
        # ~-inf lse arrives — their weights underflow to exactly 0.
        m_new = jnp.maximum(m_run, lse_t)
        c = jnp.exp(m_run - m_new)
        w = jnp.exp(lse_t - m_new)
        r = r * c + w
        acc = acc * per_row(c) + per_row(w) * out_t.astype(jnp.float32)
        # Rotate K/V to the next device. (The final rotation restores the
        # original placement; keeping it unconditional avoids a collective
        # inside lax.cond, which XLA cannot partition correctly.)
        k_t = jax.lax.ppermute(k_t, axis, perm)
        v_t = jax.lax.ppermute(v_t, axis, perm)
        return k_t, v_t, m_new, r, acc

    _, _, m_run, r, acc = jax.lax.fori_loop(
        0, n, step, (k, v, m_run, r, acc), unroll=True)
    out = acc / per_row(jnp.maximum(r, 1e-30))
    return out.astype(q.dtype)


def make_ring_attention(
    mesh: Mesh,
    axis: str = "sp",
    batch_axes=("dp", "fsdp"),
) -> Callable:
    """Build a ring-attention callable matching the transformer's
    ``attention_fn`` signature: ``fn(q, k, v, causal) -> out`` with
    [B, S, H, D] tensors whose S dim is sharded over ``axis``."""
    present = tuple(a for a in batch_axes
                    if a in mesh.axis_names and mesh.shape[a] > 1)
    bspec = present if present else None
    spec = P(bspec, axis, None, None)

    def attention(q, k, v, causal: bool = True):
        if mesh.shape[axis] == 1:
            from torchft_tpu.models.transformer import plain_attention

            return plain_attention(q, k, v, causal)
        fn = shard_map(
            functools.partial(_ring_body, axis=axis, causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v)

    # Per-block compute is the GQA-capable flash kernel (and the sp=1
    # fallback repeats internally), so callers need not repeat kv heads —
    # the ring then rotates H/H_kv-times less K/V over the interconnect.
    attention.supports_gqa = True
    return attention
