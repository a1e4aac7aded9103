"""The indexer of a learned sparse attention (Pallas TPU kernels): which
keys each query attends to, and the loss that teaches the indexer.

An indexer is a second, small attention inside the layer: ``J`` query heads
``a`` of ``c`` dims on ONE shared key head ``b``, a ReLU where attention has
a softmax, and a learned weight ``u`` a head:

    I[t, s] = (J c)^-1/2 * sum_j u[t, j] * relu(a[t, j] . b[s]),   s <= t

Query ``t`` attends to the ``min(t + 1, topk)`` keys of largest ``I[t, .]``
(ties to the lower index, ``jax.lax.top_k``'s rule), and the indexer learns
from the attention it steers: with ``p[t, .]`` the attention's probabilities
on the selected set, averaged over its heads, and ``r[t, .]`` the softmax of
``I[t, .]`` over the same set, its loss is ``mean_t KL(p[t] || r[t])``.

Two kernels, neither of which writes an ``[S, S]`` float32 to HBM:

- ``sparse_select`` (:func:`select_keys`): a block of query rows at a time,
  the scores of the block against every key in VMEM, each row's
  ``topk``-th largest by BISECTION ON THE VALUE (32 compare-and-count
  sweeps over the scores' bit patterns, which order as the floats do; no
  sort), ties at the threshold settled by a second bisection on the key's
  index. Out: the selection as int8 ``[B, S, S]`` (what
  ``ops.flash_attention.sparse_flash_attention`` takes) and each row's
  logsumexp of ``I`` over its set.
- ``indexer_loss`` (:func:`indexer_kl`): grid ``(batch, q-block, k-block,
  attention head)``, the head innermost: a tile's ``p`` is the sum over
  heads of ``exp(q k^T / sqrt(d) - lse)``, recomputed from the attention's
  own ``lse`` and held in VMEM (never ``[H, S, S]``); at the last head the
  tile's ``I`` is recomputed, ``r``, the tile's part of the loss, ``dI = r
  - p`` and through the ReLU ``da``, ``db`` (one batch's whole ``[S, c]``
  resident, as the fused flash backward holds ``dq``) and ``du``. The
  forward yields the gradient; the backward rule only scales.

Index products take their operands as they come (bfloat16 in training) and
accumulate in float32; the ReLU, the weighting by ``u``, the comparison,
``r`` and the loss are float32. Off a TPU the kernels run interpreted.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.flash_attention import (
    NEG_INF, _LANES, _resolve_interpret, _to_bh)

_INT_MIN = -(1 << 31)
# Query rows a step of ``sparse_select``: the block's scores against EVERY
# key stay in VMEM through the sweeps, [rows, S] float32 and a few
# temporaries of that size (4 MiB each at 128 x 8192).
_SELECT_ROWS = 128
_SELECT_VMEM_BYTES = 96 << 20
# The loss kernel's tiles (the published q_chunk_size / kv_chunk_size):
# the tile's p, I and the J heads' ReLU products [J, bq, bk] float32 (16
# MiB at 16 x 512 x 512), one batch's db.
_LOSS_BLOCK = 512
_LOSS_VMEM_BYTES = 100 << 20


def _tiles(s: int, cap: int) -> Tuple[int, int]:
    """``(block, padded length)`` of a sequence of ``s`` under tiles of at
    most ``cap``: one sublane-aligned tile where it fits, else whole tiles
    of ``cap``."""
    if s <= cap:
        return -(-s // 8) * 8, -(-s // 8) * 8
    return cap, -(-s // cap) * cap


def _index_scale(heads: int, dim: int) -> float:
    return (heads * dim) ** -0.5


def _column(x, j: int):
    """Column ``j`` of ``x`` [rows, J] as [rows, 1]."""
    return x[:, j:j + 1]


def _relu_products(a_ref, b):
    """``relu(a_j b^T)`` for each index head ``j``: float32 [bq, bk]."""
    for j in range(a_ref.shape[0]):
        yield j, jnp.maximum(
            jnp.dot(a_ref[j], b.T, preferred_element_type=jnp.float32), 0.0)


def _count(mask):
    """Rows' counts of a [rows, S] mask, float32 (exact below 2**24)."""
    return jnp.sum(mask.astype(jnp.float32), axis=1, keepdims=True)


def _select_kernel(a_ref, b_ref, u_ref, sel_ref, lse_ref, *, topk: int,
                   index_bits: int):
    qi = pl.program_id(1)
    rows, s = sel_ref.shape[1:]
    heads, _, dim = a_ref.shape
    b = b_ref[0]
    u = u_ref[0].astype(jnp.float32)
    score = jnp.zeros((rows, s), jnp.float32)
    for j, z in _relu_products(a_ref, b):
        score += _column(u, j) * z
    score *= _index_scale(heads, dim)
    t = qi * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    causal = pos <= t
    # The floats' bit patterns as int32 keys that order as the floats do
    # (a negative float's magnitude bits flipped); a key right of the
    # diagonal is below every float's.
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = jnp.where(causal, key, _INT_MIN)
    want = jnp.float32(topk)

    def value_bit(i, thr):
        # int32 wraps: INT_MIN + (1 << 31) is 0, the first bit's step
        cand = thr + jax.lax.shift_left(jnp.int32(1), 31 - i)
        return jnp.where(_count(key >= cand) >= want, cand, thr)

    # the largest value that ``topk`` keys reach: the row's topk-th largest
    # (INT_MIN where the row has fewer keys: every key passes)
    thr = jax.lax.fori_loop(
        0, 32, value_bit, jnp.full((rows, 1), _INT_MIN, jnp.int32))
    above = jnp.logical_and(key > thr, causal)
    tie = jnp.logical_and(key == thr, causal)
    need = want - _count(above)        # ties to take, the lowest indices

    def index_bit(i, cut):
        cand = cut + jax.lax.shift_left(jnp.int32(1), index_bits - 1 - i)
        fewer = _count(jnp.logical_and(tie, pos < cand)) < need
        return jnp.where(fewer, cand, cut)

    # the largest index with fewer than ``need`` ties left of it
    cut = jax.lax.fori_loop(0, index_bits, index_bit,
                            jnp.zeros((rows, 1), jnp.int32))
    sel = jnp.logical_or(above, jnp.logical_and(tie, pos <= cut))
    sel_ref[0] = jnp.where(sel, 1, 0).astype(jnp.int8)
    top = jnp.max(jnp.where(sel, score, NEG_INF), axis=1, keepdims=True)
    mass = jnp.sum(jnp.where(sel, jnp.exp(score - top), 0.0), axis=1,
                   keepdims=True)
    lse = top + jnp.log(jnp.maximum(mass, 1e-30))
    lse_ref[0] = jax.lax.broadcast_in_dim(lse[:, 0], lse_ref.shape[1:], (0,))


def select_keys(a: jnp.ndarray, b: jnp.ndarray, u: jnp.ndarray, topk: int,
                interpret: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(selection, lse)``: int8 ``[B, S, S]``, 1 where key ``s`` is among
    the ``min(t + 1, topk)`` largest of ``I[t, 0..t]`` (the module
    docstring; ties to the lower index), and float32 ``[B, S]``, each row's
    logsumexp of ``I`` over its set. ``a``: [B, S, J, c] index queries,
    ``b``: [B, S, c] the one index key head, ``u``: [B, S, J] the heads'
    weights. Not differentiated: the selection is a hard set, and the
    indexer learns through :func:`indexer_kl`."""
    interpret = _resolve_interpret(interpret)
    bsz, s, heads, dim = a.shape
    a, b, u = jax.lax.stop_gradient((a, b, u))
    rows, sp = _tiles(s, _SELECT_ROWS)
    pad = sp - s
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
    sel, lse = pl.pallas_call(
        functools.partial(_select_kernel, topk=int(topk),
                          index_bits=max(sp - 1, 1).bit_length()),
        out_shape=[jax.ShapeDtypeStruct((bsz, sp, sp), jnp.int8),
                   jax.ShapeDtypeStruct((bsz, sp, _LANES), jnp.float32)],
        grid=(bsz, sp // rows),
        in_specs=[
            pl.BlockSpec((heads, rows, dim), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, sp, dim), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, rows, heads), lambda bi, i: (bi, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, sp), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, rows, _LANES), lambda bi, i: (bi, i, 0)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_SELECT_VMEM_BYTES),
        interpret=interpret,
        name="sparse_select",
    )(_to_bh(a), b, u)
    if pad:
        # a padded row selects nothing and no row selects a padded key
        # (they lie right of every real row's diagonal)
        sel = sel[:, :s, :s]
    return sel, lse[:, :s, 0]


# ------------------------------------------------------ the indexer's loss

def _loss_kernel(q_ref, k_ref, lse_ref, sel_ref, a_ref, b_ref, u_ref,
                 lsei_ref, kl_ref, da_ref, db_ref, du_ref, p_acc, z_ref, *,
                 scale: float, attn_heads: int):
    qi, ki, hi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    bq, bk = sel_ref.shape[1:]
    heads, _, dim = a_ref.shape
    first_head, last_head = hi == 0, hi == attn_heads - 1

    @pl.when(jnp.logical_and(ki == 0, first_head))
    def _init_rows():
        kl_ref[...] = jnp.zeros_like(kl_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        du_ref[...] = jnp.zeros_like(du_ref)

    @pl.when(jnp.logical_and(jnp.logical_and(qi == 0, ki == 0), first_head))
    def _init_batch():
        db_ref[...] = jnp.zeros_like(db_ref)

    # a tile wholly right of the diagonal holds no selected pair
    visible = ki * bk <= qi * bq + bq - 1

    @pl.when(jnp.logical_and(visible, first_head))
    def _init_tile():
        p_acc[:] = jnp.zeros_like(p_acc)

    @pl.when(visible)
    def _one_head():
        logits = jnp.dot(q_ref[0], k_ref[0].T,
                         preferred_element_type=jnp.float32) * scale
        lse = jnp.max(lse_ref[0], axis=1, keepdims=True)
        p_acc[:] += jnp.exp(logits - lse)

    @pl.when(jnp.logical_and(visible, last_head))
    def _tile():
        sel = sel_ref[0].astype(jnp.int32) != 0
        # an unselected pair's exp may be anything (its score is not under
        # the row's lse): selected out, never multiplied
        p = jnp.where(sel, p_acc[:] * (1.0 / attn_heads), 0.0)
        b = b_ref[0]
        u = u_ref[0].astype(jnp.float32)
        cscale = _index_scale(heads, dim)
        score = jnp.zeros((bq, bk), jnp.float32)
        for j, z in _relu_products(a_ref, b):
            z_ref[j] = z
            score += _column(u, j) * z
        log_r = score * cscale - jnp.max(lsei_ref[0], axis=1, keepdims=True)
        r = jnp.where(sel, jnp.exp(log_r), 0.0)
        kl = jnp.where(p > 0.0,
                       p * (jnp.log(jnp.maximum(p, 1e-37)) - log_r), 0.0)
        kl_ref[0] += jnp.broadcast_to(
            jnp.sum(kl, axis=1, keepdims=True), kl_ref.shape[1:])
        d_score = (r - p) * cscale
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, heads), 1)
        du = jnp.zeros((bq, heads), jnp.float32)
        keys = pl.ds(pl.multiple_of(ki * bk, bk), bk)
        for j in range(heads):
            z = z_ref[j]
            du += jnp.where(
                lane == j, jnp.sum(d_score * z, axis=1, keepdims=True), 0.0)
            dz = jnp.where(z > 0.0, d_score * _column(u, j), 0.0)
            da_ref[j] += jnp.dot(dz.astype(b.dtype), b,
                                 preferred_element_type=jnp.float32)
            db_ref[0, keys, :] += jnp.dot(
                dz.astype(b.dtype).T, a_ref[j],
                preferred_element_type=jnp.float32)
        du_ref[0] += du


def _indexer_kl_and_grads(a, b, u, q, k, lse, selection, index_lse,
                          interpret: bool):
    """``(kl, (da, db, du))``: the loss (a float32 scalar) and its
    gradients, in one pass."""
    bsz, s, heads, dim = a.shape
    h, d = q.shape[2], q.shape[3]
    g = k.shape[2]
    bq, sp = _tiles(s, _LOSS_BLOCK)
    bk, pad = bq, sp - s
    if pad:
        seq = ((0, 0), (0, pad))
        a, q, k = (jnp.pad(x, seq + ((0, 0), (0, 0))) for x in (a, q, k))
        b, u = jnp.pad(b, seq + ((0, 0),)), jnp.pad(u, seq + ((0, 0),))
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad)))
        index_lse = jnp.pad(index_lse, seq)
        selection = jnp.pad(selection, seq + ((0, pad),))
    nq = sp // bq

    def kcol(i, j):
        # a step right of the diagonal names the row's last visible tile
        # again and fetches nothing
        return jnp.minimum(j, (i * bq + bq - 1) // bk)

    lanes = lambda x: jnp.broadcast_to(  # noqa: E731
        x[..., None], x.shape + (_LANES,))
    row = lambda bi, i, j, hi: (bi, i, 0)  # noqa: E731
    kl, da, db, du = pl.pallas_call(
        functools.partial(_loss_kernel, scale=d ** -0.5, attn_heads=h),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, sp, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((bsz * heads, sp, dim), jnp.float32),
            jax.ShapeDtypeStruct((bsz, sp, dim), jnp.float32),
            jax.ShapeDtypeStruct((bsz, sp, heads), jnp.float32),
        ],
        grid=(bsz, nq, nq, h),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bi, i, j, hi: (bi * h + hi, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bi, i, j, hi: (
                bi * g + hi // (h // g), kcol(i, j), 0)),
            pl.BlockSpec((1, bq, _LANES),
                         lambda bi, i, j, hi: (bi * h + hi, i, 0)),
            pl.BlockSpec((1, bq, bk),
                         lambda bi, i, j, hi: (bi, i, kcol(i, j))),
            pl.BlockSpec((heads, bq, dim), row),
            pl.BlockSpec((1, bk, dim),
                         lambda bi, i, j, hi: (bi, kcol(i, j), 0)),
            pl.BlockSpec((1, bq, heads), row),
            pl.BlockSpec((1, bq, _LANES), row),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, _LANES), row),
            pl.BlockSpec((heads, bq, dim), row),
            pl.BlockSpec((1, sp, dim), lambda bi, i, j, hi: (bi, 0, 0)),
            pl.BlockSpec((1, bq, heads), row),
        ],
        scratch_shapes=[pltpu.VMEM((bq, bk), jnp.float32),
                        pltpu.VMEM((heads, bq, bk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LOSS_VMEM_BYTES),
        interpret=interpret,
        name="indexer_loss",
    )(_to_bh(q), _to_bh(k), lanes(lse.reshape(bsz * h, sp)), selection,
      _to_bh(a), b, u, lanes(index_lse))
    share = 1.0 / (bsz * s)      # the mean over the real rows
    da = da.reshape(bsz, heads, sp, dim).transpose(0, 2, 1, 3)
    return (jnp.sum(kl[:, :s, 0]) * share,
            (da[:, :s] * share, db[:, :s] * share, du[:, :s] * share))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _indexer_kl(a, b, u, q, k, lse, selection, index_lse, interpret):
    return _indexer_kl_and_grads(a, b, u, q, k, lse, selection, index_lse,
                                 interpret)[0]


def _indexer_kl_fwd(a, b, u, q, k, lse, selection, index_lse, interpret):
    kl, grads = _indexer_kl_and_grads(a, b, u, q, k, lse, selection,
                                      index_lse, interpret)
    return kl, tuple(x.astype(like.dtype)
                     for x, like in zip(grads, (a, b, u)))


def _indexer_kl_bwd(interpret, grads, g):
    # the attention's side and the selection take no cotangent
    return tuple((x.astype(jnp.float32) * g).astype(x.dtype)
                 for x in grads) + (None,) * 5


_indexer_kl.defvjp(_indexer_kl_fwd, _indexer_kl_bwd)


def indexer_kl(a: jnp.ndarray, b: jnp.ndarray, u: jnp.ndarray,
               q: jnp.ndarray, k: jnp.ndarray, lse: jnp.ndarray,
               selection: jnp.ndarray, index_lse: jnp.ndarray,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """The indexer's loss of one layer: the mean over queries of ``KL(p ||
    r)`` over each query's selected set (the module docstring). ``a``, ``b``,
    ``u``: the indexer's queries, key and weights (as :func:`select_keys`
    took them); ``q`` [B, S, H, D], ``k`` [B, S, H_kv, D] and ``lse``
    [B, H, S]: the attention's queries and keys as its kernel took them and
    the logsumexp it returned; ``selection`` and ``index_lse``:
    :func:`select_keys`'s. Differentiated in ``a``, ``b`` and ``u`` ONLY:
    the attention's side is a target (stopped here), so the loss moves the
    indexer's leaves and nothing else. One kernel pass gives the loss and
    its gradient; the backward rule scales it."""
    q, k, lse, index_lse = jax.lax.stop_gradient((q, k, lse, index_lse))
    return _indexer_kl(a, b, u, q, k, lse, selection, index_lse,
                       _resolve_interpret(interpret))
