"""Pallas flash attention (TPU kernel) — the hot op of the transformer.

Blockwise-online-softmax attention that never materializes the [S, S]
score matrix: O(block) VMEM instead of O(S^2) HBM, MXU-shaped matmuls, f32
accumulators with bf16 inputs. This is new scope relative to the reference
(which has no kernels at all — SURVEY.md §2 "no CUDA kernels"); it exists
because long-context is first-class in the TPU build and the plain
attention in :mod:`torchft_tpu.models.transformer` is HBM-bound at long S.

Three structural choices shape the kernels (timings: a TPU v5e, jax
0.9.0, libtpu 0.0.34, the kernels alone at the benchmark cells' shapes by
``scripts/flash_head64_check.py --shape all``; PERF.md, Findings, PR 48):

1. **Interior blocks skip the mask entirely.** The kernel is VPU-bound
   (per block the softmax's element passes outweigh the two matmuls' MXU
   time), and the causal mask's iota/compare/select passes are pure VPU
   work — yet below-diagonal blocks are fully visible. Each kernel has
   two pl.when instantiations of the same body (masked for
   diagonal-adjacent blocks, plain for interior), so only ~nqb of the
   ~nqb^2/2 computed blocks pay for masking. (Hoisting the mask behind a
   per-tile lax.cond *inside* one body serializes, and lost.)
2. **A masked block moves no bytes.** The grid is the whole
   (q-block, k-block) rectangle and a block right of the diagonal or left
   of the window computes nothing (:func:`_block_visibility`, on the grid
   index); the index map of every input such a step does not read stops
   at the row's visible band (:func:`_visible_band`, from the same
   inequalities), so a run of skipped steps names the neighbouring visible
   block again and Pallas, which copies a tile only when its block index
   changes, fetches nothing: 28 of 64 steps causal at 8,192 tokens, 43 of
   64 under a window of 2,048, 120 of 256 at 512-token tiles. Kernels
   under a traced ``shift`` (ring attention) keep the plain maps: their
   visibility is data.
3. **Fused backward** (_bwd_fused_kernel): dq does not run as a separate
   kernel recomputing (logits, p, dp, ds) — one kernel does 5 matmuls +
   1 exp per block instead of the split path's 7 + 2, with one
   (batch, head)'s whole dq accumulated in an f32 VMEM scratch across the
   outer k-grid and written out once, in q's dtype, at the grid row's
   last step (no HBM buffer read back, so nothing for the pipeline to
   race). Where that accumulator would not fit its VMEM budget the split
   kernels run. Checked against the split path on hardware by
   ``fused_bwd_check`` (run by ``chip_smoke.py``);
   TORCHFT_FLASH_FUSED_BWD=0 falls back.

Tiles default to the largest power of two <= 1024 dividing the sequence,
at every head size: a head over 128 lanes asks for more scoped VMEM
(:func:`_tile_vmem`), not for smaller tiles. At 512-token tiles the
192/128 forward took 11.66 ms for 32 heads x 8,192 tokens where it takes
7.85 at 1024 (half the key steps a query block, so half the rescales of
its accumulator, and a quarter of the grid steps; PERF.md, Findings,
PR 57). Head_dim matters too: d=128 fills the MXU contraction, d=64 halves
it, d=192 pads to 256 (and runs no faster when the program pads it to 256
lanes itself, in HBM or in VMEM: same place). An exp2-domain
rewrite (log2(e) folded into the logit scale) was tried and reverted:
Mosaic already lowers jnp.exp to the hardware exp2 with the multiply
fused.

Kernel structure: grid (batch*heads, q_blocks, k_blocks). The innermost
(k) grid dimension is sequential on a TPU core, so the running
(max, sum, acc) statistics live in VMEM scratch that persists across k
steps — each program instance sees one [block_q, d] q tile and one
[block_k, d] k/v tile, so VMEM usage is O(block) regardless of S and the
pipeline streams K/V tiles from HBM while the MXU works.

Forward and backward are both Pallas kernels. The forward additionally
saves the per-row logsumexp; the backward recomputes the probability tiles
blockwise from (q, k, lse) — the flash-style recompute that trades FLOPs
for the O(S^2) residuals — and accumulates dq (one kernel, k innermost)
and dk/dv (one kernel, q innermost) in VMEM scratch. Training memory is
O(S) residuals + O(block) workspace at any sequence length.
Layouts: q/k/v are [B, S, H, D]; causal masks are end-aligned (queries are
the last s_q key positions; s_k >= s_q enforced).

Which masks are known before a tile is fetched, and which is data:

- **Functions of position** (:func:`_visible`): causal, a causal window,
  and a ring's ``shift`` as far as its block bounds go. A block's
  visibility follows from the grid index (:func:`_block_visibility`), and
  under a STATIC mask the visible band can be solved for the block index
  (:func:`_visible_band`), which is what lets an index map stop at the
  band so that a skipped step moves no bytes (choice 2 above).
- **Data** (:func:`_selected`; ``sparse_flash_attention``): a learned
  sparse attention's selection, an int8 ``[B, S, S]`` operand read a tile,
  with one flag a tile (none / some / every pair selected) read from SMEM
  where the others compare block bounds. ``_visible`` and ``_visible_band``
  say nothing about it: no inequality in the block index holds a selected
  set, and no index map of a selected kernel is clamped.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30
_LANES = 128  # TPU vector lane count


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one place interpret mode is decided, once per public call.

    ``None`` means "compiled on a TPU backend, interpreted elsewhere" (the
    CPU test suite); a TPU backend therefore never gets interpret mode
    implicitly — only an explicit ``interpret=True`` does."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _block_visibility(qi, ki, bq, bk, offset, causal, shift_ref,
                      window=None):
    """Block-level mask bounds shared by every kernel (forward, split
    backward, fused backward) so their masking can never desynchronize.

    Returns ``(diag_ok, full_vis)``: the block has any visible entry /
    every entry visible. ``offset = s_k - s_q`` end-aligns queries; a
    traced ``shift_ref`` (ring attention) slides the boundary as data —
    the bounds stay scalar compares either way, so fully-masked blocks
    are skipped and fully-visible blocks take the unmasked path even
    when the mask VALUES are traced.

    ``window`` (static, causal only) adds the lower bound beside the
    causal upper one: key ``j`` is visible to query ``i`` iff
    ``0 <= i - j < window``. Over a block ``i - j`` takes every value from
    ``min_i - max_j`` to ``max_i - min_j``, so blocks wholly left of the
    window are skipped like blocks right of the diagonal."""
    if shift_ref is not None:
        shift = shift_ref[0, 0]
        diag_ok = (qi * bq + bq - 1 + offset + shift >= ki * bk)
        full_vis = (qi * bq + offset + shift >= ki * bk + bk - 1)
    elif causal:
        diag_ok = (qi * bq + bq - 1 + offset >= ki * bk)
        full_vis = (qi * bq + offset >= ki * bk + bk - 1)
        if window is not None:
            diag_ok = jnp.logical_and(
                diag_ok, qi * bq + offset - (ki * bk + bk - 1) < window)
            full_vis = jnp.logical_and(
                full_vis, qi * bq + bq - 1 + offset - ki * bk < window)
    else:
        diag_ok = True
        full_vis = True
    return diag_ok, full_vis


def _visible(q_pos, k_pos, window):
    """The element mask of the kernels whose mask is a FUNCTION OF POSITION:
    causal, and inside the window where there is one. Known from the grid
    index alone, so :func:`_block_visibility` skips a block and
    :func:`_visible_band` stops an index map before a tile is fetched. The
    selected kernels (``sparse_flash_attention``) do not come here: their
    mask is an operand (:func:`_selected`), read a tile."""
    if window is None:
        return q_pos >= k_pos
    return jnp.logical_and(q_pos >= k_pos, q_pos - k_pos < window)


# What a tile of a selection holds (``_with_tile_flags``), as the selected
# kernels read it from SMEM: no selected pair (the step is skipped as a
# masked block is), some, or every pair (the unmasked body).
_TILE_NONE, _TILE_SOME, _TILE_FULL = 0, 1, 2


def _selected(sel_ref):
    """The element mask of the selected kernels: DATA, the ``[bq, bk]``
    int8 tile of the selection that the step fetched."""
    return sel_ref[0].astype(jnp.int32) != 0


def _tile_visibility(flag_ref, bh, heads: int, qi, ki, nqb: int):
    """``(diag_ok, full_vis)`` of a selected kernel's step, as
    :func:`_block_visibility` gives them for a mask of positions: from the
    tile's flag, one int32 a ``(batch, q-block, k-block)``."""
    flag = flag_ref[(bh // heads) * nqb + qi, ki]
    return flag > _TILE_NONE, flag == _TILE_FULL


def _step_visibility(flag_ref, selected_heads: int, nqb, qi, ki, bq, bk,
                    offset, causal, shift_ref, window):
    """A grid step's ``(diag_ok, full_vis)`` in every kernel: the tile's
    flag under a selection (``nqb`` query blocks a batch), else
    :func:`_block_visibility`."""
    if selected_heads:
        return _tile_visibility(flag_ref, pl.program_id(0), selected_heads,
                                qi, ki, nqb)
    return _block_visibility(qi, ki, bq, bk, offset, causal, shift_ref,
                             window)


def _with_tile_flags(selection: jnp.ndarray, block_q: Optional[int],
                     block_k: Optional[int]
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """An int8 selection ``[B, S, S_k]`` with the ``[B * S/bq, S_k/bk]``
    int32 flags of what each ``[bq, bk]`` tile holds, at the blocks the
    forward and the backward both take: one reduction of the selection a
    call, kept with it in the residuals."""
    b, s, sk = selection.shape
    bq = min(block_q or _auto_block(s), s)
    bk = min(block_k or _auto_block(sk), sk)
    tiles = selection.reshape(b, s // bq, bq, sk // bk, bk).astype(
        jnp.int32).sum(axis=(2, 4))
    flags = jnp.where(tiles == 0, _TILE_NONE,
                      jnp.where(tiles == bq * bk, _TILE_FULL, _TILE_SOME))
    return selection, flags.reshape(b * (s // bq), sk // bk).astype(jnp.int32)


def _visible_band(idx, bq: int, bk: int, n: int, offset: int,
                  window: Optional[int], keys: bool):
    """First and last visible block along the inner grid axis under a
    STATIC causal/window mask: :func:`_block_visibility`'s ``diag_ok``
    solved for the inner index. ``keys=True``: ``idx`` is a query block and
    the range is over the ``n`` key blocks (forward, split dq);
    ``keys=False``: ``idx`` is a key block and the range is over the ``n``
    query blocks (dk/dv, fused backward). Visible blocks are exactly
    ``first..last``, contiguous; ``last < first`` says the row sees nothing
    (key blocks left of every query's window, only with ``s_k > s_q``),
    and ``first`` is a valid block index even then. Python ints or traced
    scalars (an index map's)."""
    if keys:
        b = bk
        lo = 0 if window is None else (idx * bq + offset - window + 1) // b
        hi = (idx * bq + bq - 1 + offset) // b
    else:
        b = bq
        lo = (idx * bk - offset) // b
        hi = (n - 1 if window is None
              else (window + idx * bk + bk - 2 - offset) // b)
    if isinstance(idx, int):
        return max(lo, 0), min(hi, n - 1)
    return jnp.maximum(lo, 0), jnp.minimum(hi, n - 1)


def _band_clamp(causal: bool, dynamic_shift: bool, bq: int, bk: int,
                n: int, offset: int, window: Optional[int], keys: bool):
    """``clamp(outer, inner)`` for the index map of an input that a wholly
    masked step does not read: the inner block index held inside the outer
    block's visible band, so a run of skipped steps names the neighbouring
    visible block again and Pallas, which fetches only when a block index
    changes, moves nothing. The kernels' bodies keep deciding by
    :func:`_block_visibility` on the GRID index. Without a static mask
    (non-causal; a traced shift, whose visibility is data) the index is
    the grid's own."""
    if not causal or dynamic_shift:
        return lambda outer, inner: inner

    def clamp(outer, inner):
        lo, hi = _visible_band(outer, bq, bk, n, offset, window, keys)
        return jnp.clip(inner, lo, jnp.maximum(hi, lo))
    return clamp


def _count_grid_steps(bh: int, n_outer: int, n_inner: int, causal: bool,
                      dynamic_shift: bool, bq: int, bk: int, offset: int,
                      window: Optional[int], keys: bool, **also: int) -> None:
    """Trace-time counters of one ``pallas_call``, added on the host as
    the head loss's are (``tracing.add_program_counters``): its grid steps,
    batch x heads included, and those of them wholly masked by a static
    mask, which move no bytes. Under a traced shift none is counted as
    skipped: which are is data. ``also``: counters of the same call
    (which backward it is)."""
    from torchft_tpu import tracing

    skipped = 0
    if causal and not dynamic_shift:
        for idx in range(n_outer):
            lo, hi = _visible_band(idx, bq, bk, n_inner, offset, window,
                                   keys)
            skipped += n_inner - max(hi - lo + 1, 0)
    tracing.add_program_counters(
        flash_grid_steps_traced_total=bh * n_outer * n_inner,
        flash_skipped_steps_traced_total=bh * skipped, **also)


def _dual_instantiate(compute, causal, shift_ref, diag_ok, full_vis):
    """Emit ``compute(apply_mask)`` twice behind complementary pl.when
    predicates: the masked body only for diagonal-adjacent blocks, the
    plain body for fully-visible ones (the mask's iota/compare/select
    passes measured 33% of per-block time — the kernels are VPU-bound).
    Non-causal static kernels have no mask and get one unguarded body."""
    if causal or shift_ref is not None:
        @pl.when(jnp.logical_and(diag_ok, jnp.logical_not(full_vis)))
        def _compute_masked():
            compute(True)

        @pl.when(jnp.logical_and(diag_ok, full_vis))
        def _compute_plain():
            compute(False)
    else:
        compute(False)


def _fwd_kernel(*refs, causal: bool, scale: float, nkb: int, offset: int,
                dynamic_shift: bool, window: Optional[int] = None,
                selected_heads: int = 0):
    shift_ref = sel_ref = flag_ref = None
    if dynamic_shift:
        q_ref, k_ref, v_ref, shift_ref, o_ref, lse_ref, \
            m_ref, l_ref, acc_ref = refs
    elif selected_heads:
        q_ref, k_ref, v_ref, sel_ref, flag_ref, o_ref, lse_ref, \
            m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # (a dense kernel's program asks for no grid size: it traces as before)
    nqb = pl.num_programs(1) if selected_heads else 0
    diag_ok, full_vis = _step_visibility(
        flag_ref, selected_heads, nqb, qi, ki, bq, bk, offset, causal,
        shift_ref, window)

    def _softmax_update(logits, v):
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    def _compute(apply_mask: bool):
        # Matmul inputs stay in the INPUT dtype (bf16 in training) with
        # f32 accumulation — upcasting q/k/v first would push the MXU off
        # its bf16 fast path and roughly halve kernel throughput at
        # moderate S (measured: the S=2048 fwd+bwd at ~17% of bf16 peak
        # with f32 operands). Softmax statistics stay f32 throughout.
        q = q_ref[0]                                      # [bq, d]
        k = k_ref[0]                                      # [bk, d]
        v = v_ref[0]                                      # [bk, d]
        logits = jnp.dot(q, k.T,
                         preferred_element_type=jnp.float32) * scale
        if apply_mask and selected_heads:
            logits = jnp.where(_selected(sel_ref), logits, NEG_INF)
        elif apply_mask:
            # Mask from two 1-D iotas and ONE broadcast compare: the mask
            # is pure VPU overhead on every diagonal-adjacent block, and
            # materializing two full [bq, bk] i32 iotas costs ~3x the
            # passes of a [bq,1] vs [1,bk] broadcast.
            q_pos = offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, 1), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (1, bk), 1)
            if dynamic_shift:
                # Traced mask selector (ring attention): q_pos + shift >=
                # k_pos. shift=0 → diagonal causal; shift >= s_k → full
                # attention; shift <= -s_q → fully blocked.
                q_pos = q_pos + shift_ref[0, 0]
            logits = jnp.where(_visible(q_pos, k_pos, window), logits,
                               NEG_INF)
        _softmax_update(logits, v)

    _dual_instantiate(_compute, causal or bool(selected_heads), shift_ref,
                      diag_ok, full_vis)

    @pl.when(ki == nkb - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] /
                    jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)
        # Per-row logsumexp of the scaled logits — the only residual the
        # backward needs beyond (q, k, v, o).
        lse = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))
        lse_ref[0] = jax.lax.broadcast_in_dim(
            lse[:, 0], lse_ref.shape[1:], (0,))


def _check_window(window: Optional[int], causal: bool,
                  dynamic_shift: bool) -> None:
    if window is None:
        return
    if not causal or dynamic_shift:
        raise ValueError("a window bounds the causal mask from below: it "
                         "needs causal=True and no traced shift")
    if int(window) < 1:
        raise ValueError(f"window must be at least 1, got {window}")


def _kernel_name(base: str, window: Optional[int],
                 latent: bool = False,
                 selected: bool = False) -> Optional[str]:
    """The windowed ``pallas_call``'s own name, by which a profile tells it
    from the full kernel, and the latent one's (a value head narrower than
    the query/key head: ``_mla``). The full kernel with one head size keeps
    none: XLA names a custom call after the innermost scope, a ``name`` is
    one more scope, and the benchmark finds the full kernel by its caller's
    (``%attn``). The selected kernels are ``flash_fwd_sparse``,
    ``flash_bwd_sparse`` and, split, ``flash_bwd_sparse_dq`` / ``_dkdv``."""
    if selected:
        stem, split, part = base.partition("_d")
        return stem + "_sparse" + split + part
    if window is None and not latent:
        return None
    return base + ("_window" if window is not None else "") \
        + ("_mla" if latent else "")


def _auto_block(seq: int, cap: int = 1024) -> int:
    """Largest power-of-two tile <= cap dividing ``seq`` (>= 128); short
    sequences get one whole-sequence tile. Measured on a v5e at S=16k:
    1024-tiles run the fwd+bwd 2.5x faster than 256-tiles (more MXU work
    per grid step, fewer HBM round-trips for the running stats).

    A LONG seq with no power-of-two divisor (e.g. 6000) returns 128 so
    the divisibility assert fires with a clear message — silently tiling
    the whole sequence would blow VMEM instead. Odd seqs up to ``cap``
    still get the whole-sequence tile (VMEM-safe)."""
    if seq <= 128:
        return seq
    b = cap
    while b >= 128:
        if seq % b == 0:
            return b
        b //= 2
    return seq if seq <= cap else 128


# Mosaic's own default scoped VMEM limit: what a kernel's tiles and score
# buffers fit at bf16 operands, 1024-token tiles and a head of one
# 128-lane tile.
_SCOPED_VMEM_BYTES = 16 << 20


def _lane_tiles(d: int) -> int:
    return -(-d // _LANES)


def _tile_vmem(d: int, itemsize: int,
               selected: bool = False) -> Optional[pltpu.CompilerParams]:
    """``compiler_params`` of the forward and of the split backward kernels:
    the [bq, bk] f32 score / probability buffers do not grow with the head,
    the [b*, d] operand tiles and accumulators do (a 1024-token tile at
    d=192 asked for 17.45 MB of the default's 16), so a query/key head of
    ``n`` 128-lane tiles asks for ``n`` times the default and keeps the
    1024-token tiles. ``None`` at one lane tile: Mosaic's default, and
    such calls trace as they always did. A selected kernel holds the
    selection's [bq, bk] int8 tile, twice, and its widened copy beside the
    scores: one default more."""
    if _lane_tiles(d) == 1 and not selected:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=_SCOPED_VMEM_BYTES * (_lane_tiles(d) + selected)
        * max(itemsize // 2, 1))


def _selection_specs(bq: int, bk: int, tile_of: Callable) -> list:
    """The two operands a selected kernel takes after its own: the
    selection's int8 tile (``tile_of`` maps the grid to ``(batch, q-block,
    k-block)``: one selection serves a batch's heads) and the tiles' flags,
    whole, in SMEM. A selected kernel takes no static mask and no shift,
    so its other index maps are the grid's own."""
    return [pl.BlockSpec((1, bq, bk), tile_of),
            pl.BlockSpec(memory_space=pltpu.SMEM)]


def _to_bh(x: jnp.ndarray) -> jnp.ndarray:
    """[B, S, H, D] -> [B*H, S, D], whatever the head size."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _flash_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               causal: bool, block_q: Optional[int], block_k: Optional[int],
               interpret: bool,
               shift: Optional[jnp.ndarray] = None,
               window: Optional[int] = None,
               selection: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
               ) -> jnp.ndarray:
    """``selection``: an int8 selection with its tiles' flags
    (:func:`_with_tile_flags`), where visibility is data."""
    b, s, h, d = q.shape
    d_v = v.shape[-1]     # the value head may be narrower (latent attention)
    h_kv = k.shape[2]
    assert h % h_kv == 0, f"num_heads {h} not a multiple of kv heads {h_kv}"
    assert k.shape[-1] == d and v.shape[2] == h_kv, (
        f"q/k head size {d} vs {k.shape[-1]}; k/v heads {h_kv} vs "
        f"{v.shape[2]}")
    rep = h // h_kv
    scale = d ** -0.5
    block_q = block_q or _auto_block(s)
    block_k = block_k or _auto_block(k.shape[1])
    dynamic_shift = shift is not None
    _check_window(window, causal, dynamic_shift)

    qh, kh, vh = _to_bh(q), _to_bh(k), _to_bh(v)
    sk = kh.shape[1]
    assert not causal or sk >= s, (
        "causal flash_attention requires s_k >= s_q (queries are the last "
        f"s_q positions, decode convention); got s_q={s}, s_k={sk}")
    block_q = min(block_q, s)
    block_k = min(block_k, sk)
    assert s % block_q == 0 and sk % block_k == 0, (
        "flash_attention requires seq divisible by block sizes; "
        f"got s={s}, sk={sk}, block_q={block_q}, block_k={block_k}")
    nkb = sk // block_k

    # GQA is an index-map concern, not a data one: query row bi*h + hi
    # reads K/V row bi*h_kv + hi//rep — no materialized jnp.repeat.
    def kv_row(bh):
        return (bh // h) * h_kv + (bh % h) // rep

    grid = (b * h, s // block_q, nkb)
    # K and V stop at the query block's visible band: a wholly masked
    # step re-names a neighbouring visible tile and fetches nothing.
    kj = _band_clamp(causal, dynamic_shift, block_q, block_k, nkb, sk - s,
                     window, keys=True)
    _count_grid_steps(*grid, causal, dynamic_shift, block_q, block_k,
                      sk - s, window, keys=True)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda bh, i, j: (kv_row(bh), kj(i, j), 0)),
        pl.BlockSpec((1, block_k, d_v),
                     lambda bh, i, j: (kv_row(bh), kj(i, j), 0)),
    ]
    inputs = [qh, kh, vh]
    if dynamic_shift:
        # Traced mask selector, one scalar riding a [1, LANES] i32 tile.
        in_specs.append(pl.BlockSpec((1, _LANES), lambda bh, i, j: (0, 0)))
        inputs.append(jnp.broadcast_to(
            jnp.asarray(shift, jnp.int32).reshape(1, 1), (1, _LANES)))
    selected = selection is not None
    if selected:
        in_specs += _selection_specs(
            block_q, block_k, lambda bh, i, j: (bh // h, i, j))
        inputs += list(selection)
    also = dict(selected_heads=h) if selected else {}
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale,
                          nkb=nkb, offset=sk - s,
                          dynamic_shift=dynamic_shift, window=window,
                          **also),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d_v), q.dtype),
            # Row stats ride in [bh, s, 128] with the value broadcast over
            # the 128 lanes — the TPU-friendly layout for per-row scalars
            # (same trick as jax.experimental.pallas.ops.tpu.flash_attention;
            # a [bh, s] block or a flat 1D array violates Mosaic tiling).
            jax.ShapeDtypeStruct((b * h, s, _LANES), jnp.float32),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, i, j: (bh, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d_v), jnp.float32),  # output accumulator
        ],
        compiler_params=_tile_vmem(d, q.dtype.itemsize, selected),
        interpret=interpret,
        name=_kernel_name("flash_fwd", window, d_v != d, selected),
    )(*inputs)
    lse = lse[:, :, 0]
    if d_v != d:
        # The kernel writes a row's logsumexp across all 128 lanes; one
        # value a row is what the backward needs. The barrier ties the
        # narrow [bh, s] copy to the output: it exists before anything
        # reads the output, so it, and not the 128-times wider kernel
        # output (128 MiB a layer at 32 heads x 8192), is what lives from a
        # layer's forward to its backward; left alone, XLA fuses the slice
        # into its consumer in the backward. Latent calls only: at one head
        # size the program stays the one the accepted cells were measured
        # on (there the barrier cost 0.25 % of a step and, by XLA's
        # rescheduling, 90 MB more temporaries in the routed-expert step,
        # which has none to spare; PERF.md, PR 33).
        out, lse = jax.lax.optimization_barrier((out, lse))
    return out.reshape(b, h, s, d_v).transpose(0, 2, 1, 3), lse


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    qi, ki, causal: bool, scale: float, offset: int,
                    shift_ref=None, apply_mask: bool = True,
                    window: Optional[int] = None, sel_ref=None):
    """Shared backward recompute: rebuild the probability tile from
    (q, k, lse) under the same end-aligned causal mask as the forward and
    form ds = p * (dp - delta). Used by both the dq and dk/dv kernels so
    their masking/scaling can never desynchronize. Returns (p, ds, q, k,
    do) as f32. ``delta`` may carry the lse cotangent folded in
    (delta - g_lse) — d(lse)/d(logits) is the softmax itself."""
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    # Native-dtype matmul inputs, f32 accumulation (see _fwd_kernel note).
    q = q_ref[0]                                      # [bq, d]
    k = k_ref[0]                                      # [bk, d]
    v = v_ref[0]                                      # [bk, d]
    do = do_ref[0]                                    # [bq, d]
    logits = jnp.dot(q, k.T,
                     preferred_element_type=jnp.float32) * scale
    if apply_mask and sel_ref is not None:
        logits = jnp.where(_selected(sel_ref), logits, NEG_INF)
    elif apply_mask and (causal or shift_ref is not None):
        # Same broadcast-compare mask as the forward (see _fwd_kernel).
        q_pos = offset + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, 1), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(
            jnp.int32, (1, bk), 1)
        if shift_ref is not None:
            q_pos = q_pos + shift_ref[0, 0]
        logits = jnp.where(_visible(q_pos, k_pos, window), logits, NEG_INF)
    lse_row = jnp.max(lse_ref[0], axis=1, keepdims=True)
    p = jnp.exp(logits - lse_row)                     # exact softmax
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    delta_row = jnp.max(delta_ref[0], axis=1, keepdims=True)
    ds = p * (dp - delta_row)
    return p, ds, q, k, do


def _bwd_refs(refs, dynamic_shift: bool, selected_heads: int):
    """A backward kernel's refs as ``(the six inputs, shift_ref, sel_ref,
    flag_ref, the rest)``: a traced shift or a selection and its flags
    follow the inputs."""
    extra = 1 if dynamic_shift else 2 if selected_heads else 0
    shift_ref = refs[6] if dynamic_shift else None
    sel_ref, flag_ref = refs[6:8] if selected_heads else (None, None)
    return refs[:6], shift_ref, sel_ref, flag_ref, refs[6 + extra:]


def _bwd_dq_kernel(*refs, causal: bool, scale: float, nkb: int,
                   offset: int, dynamic_shift: bool,
                   window: Optional[int] = None, selected_heads: int = 0):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), shift_ref, sel_ref, \
        flag_ref, (dq_ref, acc_ref) = _bwd_refs(refs, dynamic_shift,
                                                selected_heads)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # (a dense kernel's program asks for no grid size: it traces as before)
    nqb = pl.num_programs(1) if selected_heads else 0
    diag_ok, full_vis = _step_visibility(
        flag_ref, selected_heads, nqb, qi, ki, bq, bk, offset, causal,
        shift_ref, window)

    def _compute(apply_mask: bool):
        _, ds, _, k, _ = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            qi, ki, causal, scale, offset, shift_ref,
            apply_mask=apply_mask, window=window, sel_ref=sel_ref)
        acc_ref[:] += jnp.dot(ds.astype(k.dtype), k,
                              preferred_element_type=jnp.float32) * scale

    _dual_instantiate(_compute, causal or bool(selected_heads), shift_ref,
                      diag_ok, full_vis)

    @pl.when(ki == nkb - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkdv_kernel(*refs, causal: bool, scale: float, nqb: int,
                     offset: int, dynamic_shift: bool,
                     window: Optional[int] = None, selected_heads: int = 0):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), shift_ref, sel_ref, \
        flag_ref, (dk_ref, dv_ref, dk_acc, dv_acc) = _bwd_refs(
            refs, dynamic_shift, selected_heads)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    diag_ok, full_vis = _step_visibility(
        flag_ref, selected_heads, nqb, qi, ki, bq, bk, offset, causal,
        shift_ref, window)

    def _compute(apply_mask: bool):
        p, ds, q, _, do = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            qi, ki, causal, scale, offset, shift_ref,
            apply_mask=apply_mask, window=window, sel_ref=sel_ref)
        dv_acc[:] += jnp.dot(p.astype(do.dtype).T, do,
                             preferred_element_type=jnp.float32)
        dk_acc[:] += jnp.dot(ds.astype(q.dtype).T, q,
                             preferred_element_type=jnp.float32) * scale

    _dual_instantiate(_compute, causal or bool(selected_heads), shift_ref,
                      diag_ok, full_vis)

    @pl.when(qi == nqb - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(*refs, causal: bool, scale: float, nqb: int,
                      nkb: int, offset: int, dynamic_shift: bool,
                      window: Optional[int] = None, selected_heads: int = 0):
    """One backward kernel for dq, dk AND dv.

    The split kernels each recompute (logits, p, dp, ds) per block — the
    exp alone is ~a third of a block's VPU time, and the kernel is
    VPU-bound. Fusing computes them ONCE: per (k-block, q-block) step this
    does 5 matmuls + 1 exp instead of the split path's 7 matmuls + 2 exps.

    Grid (bh, ki, qi): dk/dv accumulate in VMEM scratch across the inner
    qi sweep; dq accumulates ACROSS the outer ki dimension in ``dq_acc``,
    an f32 ``[s, d]`` scratch that holds the whole sequence of one
    (batch, head) on the chip for that grid row's (ki, qi) sweep. A
    computed step adds into its ``bq`` rows (key blocks ascending: the
    split dq kernel's order), a skipped step touches nothing, and the
    row's last step writes dq out once, in q's dtype: the output block is
    the row's whole ``[s, d]``, whose index changes only with ``bh``, so
    it leaves VMEM once a row. No HBM buffer is read back, so there is
    nothing for the pipeline to race and no grid depth it needs.
    """
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), shift_ref, sel_ref, \
        flag_ref, (dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc) = \
        _bwd_refs(refs, dynamic_shift, selected_heads)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(jnp.logical_and(ki == 0, qi == 0))
    def _init_row():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    diag_ok, full_vis = _step_visibility(
        flag_ref, selected_heads, nqb, qi, ki, bq, bk, offset, causal,
        shift_ref, window)

    def _compute(apply_mask: bool):
        p, ds, q, k, do = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            qi, ki, causal, scale, offset, shift_ref,
            apply_mask=apply_mask, window=window, sel_ref=sel_ref)
        dv_acc[:] += jnp.dot(p.astype(do.dtype).T, do,
                             preferred_element_type=jnp.float32)
        dk_acc[:] += jnp.dot(ds.astype(q.dtype).T, q,
                             preferred_element_type=jnp.float32) * scale
        rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)
        dq_acc[rows, :] += jnp.dot(
            ds.astype(k.dtype), k,
            preferred_element_type=jnp.float32) * scale

    _dual_instantiate(_compute, causal or bool(selected_heads), shift_ref,
                      diag_ok, full_vis)

    @pl.when(qi == nqb - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ki == nkb - 1, qi == nqb - 1))
    def _finalize_row():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


# The fused backward holds one (batch, head)'s dq in VMEM: an f32 [s, d]
# accumulator and the double-buffered [s, d] output block in q's dtype,
# each row padded to whole 128-lane tiles (4 + 2 x 2 MiB at 8192 x 128 in
# bf16, the same at a head of 64, 8 + 2 x 4 at 192 and at 256) of the
# chip's 128 MiB. Over this many accumulator bytes the split kernels run.
_DQ_RESIDENT_BYTES = 16 << 20


def _dq_resident_bytes(s: int, d: int, itemsize: int = 0) -> int:
    """VMEM bytes of one row's f32 dq accumulator and, with an
    ``itemsize``, of its two output buffers as well."""
    return s * (-(-d // _LANES) * _LANES) * (4 + 2 * itemsize)


def _fused_vmem_limit(s: int, d: int, itemsize: int) -> int:
    """``vmem_limit_bytes`` of the fused backward: the scoped default for
    its tiles (as wide as the operands: f32 takes twice bf16's; a lane
    tile of the head, see :func:`_tile_vmem`) and the resident dq."""
    return (_SCOPED_VMEM_BYTES * max(itemsize // 2, 1) * _lane_tiles(d)
            + _dq_resident_bytes(s, d, itemsize))


def _flash_bwd(q, k, v, out, lse, g, causal: bool, block_q: Optional[int],
               block_k: Optional[int], interpret: bool, shift=None,
               g_lse=None, window: Optional[int] = None, selection=None):
    b, s, h, d = q.shape
    d_v = v.shape[-1]     # v, o, do and dv at the value head's size
    latent = d_v != d
    h_kv = k.shape[2]
    rep = h // h_kv
    scale = d ** -0.5

    def kv_row(bh):
        return (bh // h) * h_kv + (bh % h) // rep

    qh, kh, vh = _to_bh(q), _to_bh(k), _to_bh(v)
    doh, oh = _to_bh(g), _to_bh(out)
    sk = kh.shape[1]
    block_q = min(block_q or _auto_block(s), s)
    block_k = min(block_k or _auto_block(sk), sk)
    nqb = s // block_q
    nkb = sk // block_k
    offset = sk - s

    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian correction term;
    # O(S) like the lse, computed once outside the kernels.
    delta = jnp.sum(doh.astype(jnp.float32) * oh.astype(jnp.float32),
                    axis=-1)                               # [bh, s]
    if g_lse is not None:
        # lse cotangent (ring-block merges differentiate through lse):
        # d lse / d logits = softmax = p, so it folds into delta —
        # ds = p * (dp - (delta - g_lse)).
        delta = delta - g_lse.astype(jnp.float32)
    # Lane-broadcast layout for per-row scalars (see _flash_fwd).
    delta_l = jnp.broadcast_to(delta[:, :, None], (b * h, s, _LANES))
    lse_l = jnp.broadcast_to(lse[:, :, None], (b * h, s, _LANES))

    dynamic_shift = shift is not None
    _check_window(window, causal, dynamic_shift)
    band = (causal, dynamic_shift, block_q, block_k)
    # (bh, q-block, k-block) grid order, the split dq kernel's: K and V
    # stop at the query block's visible band (see _flash_fwd).
    kj = _band_clamp(*band, nkb, offset, window, keys=True)
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    do_spec = pl.BlockSpec((1, block_q, d_v), lambda bh, i, j: (bh, i, 0))
    k_spec = pl.BlockSpec((1, block_k, d),
                          lambda bh, i, j: (kv_row(bh), kj(i, j), 0))
    v_spec = pl.BlockSpec((1, block_k, d_v),
                          lambda bh, i, j: (kv_row(bh), kj(i, j), 0))
    row_spec = pl.BlockSpec((1, block_q, _LANES),
                            lambda bh, i, j: (bh, i, 0))

    in_specs = [q_spec, k_spec, v_spec, do_spec, row_spec, row_spec]
    inputs = [qh, kh, vh, doh, lse_l, delta_l]

    # Specs in (bh, k-block, q-block) grid order + output reshapers,
    # shared by the fused kernel and the split dk/dv kernel: q, do and the
    # two row statistics stop at the key block's visible band.
    qi_ = _band_clamp(*band, nqb, offset, window, keys=False)
    q_spec2 = pl.BlockSpec((1, block_q, d),
                           lambda bh, j, i: (bh, qi_(j, i), 0))
    do_spec2 = pl.BlockSpec((1, block_q, d_v),
                            lambda bh, j, i: (bh, qi_(j, i), 0))
    k_in_spec2 = pl.BlockSpec((1, block_k, d),
                              lambda bh, j, i: (kv_row(bh), j, 0))
    v_in_spec2 = pl.BlockSpec((1, block_k, d_v),
                              lambda bh, j, i: (kv_row(bh), j, 0))
    k_out_spec2 = pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0))
    v_out_spec2 = pl.BlockSpec((1, block_k, d_v),
                               lambda bh, j, i: (bh, j, 0))
    row_spec2 = pl.BlockSpec((1, block_q, _LANES),
                             lambda bh, j, i: (bh, qi_(j, i), 0))
    in_specs2 = [q_spec2, k_in_spec2, v_in_spec2, do_spec2, row_spec2,
                 row_spec2]
    if dynamic_shift:
        # Traced mask selector, one scalar riding a [1, LANES] i32 tile
        # (an index map ignores its argument names: one spec, both grids).
        shift_spec = pl.BlockSpec((1, _LANES), lambda bh, i, j: (0, 0))
        in_specs, in_specs2 = in_specs + [shift_spec], in_specs2 + [shift_spec]
        inputs.append(jnp.broadcast_to(
            jnp.asarray(shift, jnp.int32).reshape(1, 1), (1, _LANES)))
    selected = selection is not None
    also = dict(selected_heads=h) if selected else {}
    if selected:
        # the selection's tile by either grid order, the flags whole
        in_specs = in_specs + _selection_specs(
            block_q, block_k, lambda bh, i, j: (bh // h, i, j))
        in_specs2 = in_specs2 + _selection_specs(
            block_q, block_k, lambda bh, j, i: (bh // h, i, j))
        inputs += list(selection)

    def from_bh(x, seq):
        return x.reshape(b, h, seq, x.shape[-1]).transpose(0, 2, 1, 3)

    def kv_from_bh(x, seq):
        # [b*h, seq, d] per query head -> sum the rep heads sharing each
        # kv head -> [b, seq, h_kv, d]
        x = x.reshape(b, h_kv, rep, seq, x.shape[-1])
        x = x.astype(jnp.float32).sum(axis=2)
        return x.transpose(0, 2, 1, 3).astype(k.dtype)

    def pack(dq, dk, dv):
        if rep == 1:
            return from_bh(dq, s), from_bh(dk, sk), from_bh(dv, sk)
        return from_bh(dq, s), kv_from_bh(dk, sk), kv_from_bh(dv, sk)

    def count(n_outer, n_inner, keys, **also):
        _count_grid_steps(b * h, n_outer, n_inner, *band, offset, window,
                          keys, **also)

    # Which backward runs follows from the shapes. The fused kernel (dq,
    # dk and dv from one recompute a block) where the q grid is four or
    # more blocks deep (below that the split kernels are what the cells'
    # one-block programs were measured on) and one (batch, head)'s f32 dq
    # fits the VMEM set aside for it; the split kernels otherwise, and
    # under TORCHFT_FLASH_FUSED_BWD=0. Interpreted calls take the split
    # kernels too, so what the CPU computes stays what it was: the fused
    # kernel has nothing an interpreter cannot model, and
    # tests/test_flash_band.py runs it interpreted.
    import os
    fused_ok = os.environ.get("TORCHFT_FLASH_FUSED_BWD", "1") != "0"
    if (nqb >= 4 and fused_ok and not interpret
            and _dq_resident_bytes(s, d) <= _DQ_RESIDENT_BYTES):
        count(nkb, nqb, keys=False, flash_dq_resident_traces_total=1)
        dk, dv, dq = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, causal=causal,
                              scale=scale, nqb=nqb, nkb=nkb, offset=offset,
                              dynamic_shift=dynamic_shift, window=window,
                              **also),
            out_shape=[
                jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
                jax.ShapeDtypeStruct((b * h, sk, d_v), v.dtype),
                jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            ],
            grid=(b * h, nkb, nqb),
            in_specs=in_specs2,
            out_specs=[k_out_spec2, v_out_spec2,
                       pl.BlockSpec((1, s, d), lambda bh, j, i: (bh, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d_v), jnp.float32),
                pltpu.VMEM((s, d), jnp.float32),   # the row's whole dq
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_fused_vmem_limit(s, d, q.dtype.itemsize)
                + selected * _SCOPED_VMEM_BYTES),
            interpret=interpret,
            name=_kernel_name("flash_bwd", window, latent, selected),
        )(*inputs)
        return pack(dq, dk, dv)

    count(nqb, nkb, keys=True, flash_dq_split_traces_total=1)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          nkb=nkb, offset=offset,
                          dynamic_shift=dynamic_shift, window=window,
                          **also),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        grid=(b * h, nqb, nkb),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_tile_vmem(d, q.dtype.itemsize, selected),
        interpret=interpret,
        name=_kernel_name("flash_bwd_dq", window, latent, selected),
    )(*inputs)

    # dk/dv: k-block outer, q-block innermost (sequential accumulation).
    # Outputs are per QUERY head (each grid row writes its own block, no
    # cross-row accumulation hazards); GQA reduces over the rep query
    # heads sharing a kv head afterwards, outside the kernel.
    count(nkb, nqb, keys=False)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, causal=causal, scale=scale,
                          nqb=nqb, offset=offset,
                          dynamic_shift=dynamic_shift, window=window,
                          **also),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d_v), v.dtype),
        ],
        grid=(b * h, nkb, nqb),
        in_specs=in_specs2,
        out_specs=[k_out_spec2, v_out_spec2],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        compiler_params=_tile_vmem(d, q.dtype.itemsize, selected),
        interpret=interpret,
        name=_kernel_name("flash_bwd_dkdv", window, latent, selected),
    )(*inputs)

    return pack(dq, dk, dv)


def _reference(q, k, v, causal, window=None):
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), dtype=bool),
                              k=s_k - s_q - int(window))
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                causal: bool = True, block_q: Optional[int] = None,
                block_k: Optional[int] = None,
                interpret: bool = False,
                window: Optional[int] = None) -> jnp.ndarray:
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret,
                        window=window)
    return out


def _aligned_len(s: int) -> bool:
    """True when the auto tile for ``s`` divides it and is sublane-aligned
    (a multiple of 8) — the shapes the kernel lowers efficiently."""
    b = _auto_block(s)
    return s % b == 0 and b % 8 == 0


def _seq_pad(s_q: int, s_k: int) -> int:
    """Smallest pad (applied to BOTH q and k, keeping the end-aligned
    causal offset ``s_k - s_q`` intact) that makes both lengths aligned.
    Static Python over static shapes; the scan is bounded and trivially
    cheap next to tracing."""
    for delta in range(0, 2049):
        if _aligned_len(s_q + delta) and _aligned_len(s_k + delta):
            return delta
    raise ValueError(
        f"flash_attention: no common pad aligns s_q={s_q} and s_k={s_k} "
        f"(their residues are incompatible); pad/mask the inputs "
        f"externally or pass explicit block sizes")


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jnp.ndarray:
    """Flash attention. q: [B, S, H, D]; k: [B, S_k, H_kv, D] and v:
    [B, S_k, H_kv, D_v] with H_kv dividing H — GQA/MQA kv heads are shared
    via kernel index maps, never materialized with a repeat. ``D_v`` may
    differ from ``D`` (latent attention: 192-wide queries and keys, 128-wide
    values): the scores scale by ``D ** -0.5``, the output is
    [B, S, H, D_v], and v, o, do and dv move at their own width in every
    kernel, with no padding; such a call's kernels are named ``*_mla``.
    ``block_q/block_k=None`` auto-picks the
    largest power-of-two tile (<=1024) dividing the sequence;
    ``interpret=None`` compiles on a TPU backend and interprets elsewhere
    (:func:`_resolve_interpret`). A Mosaic kernel cannot be partitioned
    automatically: inside a jit whose arguments are sharded over several
    devices jax refuses it ("wrap the call in a shard_map"), and
    :func:`sharded_flash_attention` is that wrapping.

    Sequence lengths with no sublane-aligned dividing tile (e.g. S=999,
    which would otherwise get a whole-sequence tile whose sublane dim is
    not a multiple of 8, or S=6000, which has no power-of-two tile at all)
    are zero-padded at the end — q and k/v by the same amount, so the
    end-aligned causal mask is unchanged; padded keys sit after every real
    query's window and padded query rows are sliced off, making padding
    exact rather than relying on Mosaic's implicit handling. Only the
    causal path pads (padded keys would corrupt non-causal rows); passing
    EITHER block size explicitly bypasses padding, and the blocks must
    then divide the unpadded lengths.

    ``window`` (causal only): key ``j`` is visible to query ``i`` iff
    ``0 <= i - j < window`` (positions end-aligned as for the causal mask);
    blocks wholly outside the window are skipped in the forward and in both
    backward paths. ``None`` is full causal attention and traces the kernels
    exactly as before the argument existed."""
    interpret = _resolve_interpret(interpret)
    window = None if window is None else int(window)
    s, sk = q.shape[1], k.shape[1]
    if block_q is not None or block_k is not None:
        # Any explicit block bypasses padding entirely: the caller is
        # tiling by hand, and the kernel's divisibility assert should
        # speak about THEIR lengths, not internally padded ones.
        return _flash_core(q, k, v, causal, block_q, block_k, interpret,
                           window)
    delta = _seq_pad(s, sk)
    if delta == 0:
        return _flash_core(q, k, v, causal, block_q, block_k, interpret,
                           window)
    if not causal:
        # ValueError, not assert: under `python -O` an assert is stripped
        # and the zero-padding below would silently include padded keys in
        # every row's softmax — wrong numerics instead of an error.
        raise ValueError(
            f"flash_attention: non-causal attention requires aligned "
            f"sequence lengths (got s_q={s}, s_k={sk}); pad the sequence "
            f"to a multiple of 8 (<=1024) or 128 and mask externally")
    pad = ((0, 0), (0, delta), (0, 0), (0, 0))
    out = _flash_core(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                      causal, block_q, block_k, interpret, window)
    return out[:, :s]


# Names (``jax.ad_checkpoint.checkpoint_name``) of the two residuals only the
# kernel can make. A caller that rematerialises the attention's inputs
# (``jax.checkpoint`` with ``save_only_these_names(*SAVED_NAMES)``, as
# ``models/mla.py`` does for the keys and values it expands from a latent)
# keeps these and runs no second forward kernel.
SAVED_NAMES = ("flash_out", "flash_lse")


def _fwd_rule(q, k, v, causal, block_q, block_k, interpret, window=None):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret,
                          window=window)
    out = checkpoint_name(out, SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    return out, (q, k, v, out, lse)


def _bwd_rule(causal, block_q, block_k, interpret, window, res, g):
    # Blockwise Pallas backward: recompute p tiles from (q, k, lse), no
    # O(S^2) residuals or intermediates at any sequence length.
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, causal, block_q, block_k,
                      interpret, window=window)


_flash_core.defvjp(_fwd_rule, _bwd_rule)
# Consumers (models.transformer.Attention) check this to skip the GQA
# kv-head repeat — the kernel shares kv heads via its index maps.
flash_attention.supports_gqa = True


def sharded_flash_attention(mesh: Mesh,
                            data_axes: Sequence[str] = ("dp", "fsdp"),
                            head_axis: str = "tp",
                            interpret: Optional[bool] = None) -> Callable:
    """An ``attention_fn`` for a jit whose arguments are sharded over
    ``mesh``: :func:`flash_attention` inside a ``shard_map`` over the whole
    mesh, batch split over the ``data_axes`` the mesh has and heads (q and
    kv alike) over ``head_axis``. Each device runs the kernel on its own
    ``[B/data, S, H/tp, D]`` block with the full sequence, so there are no
    collectives inside. ``check_vma=False``: a pallas_call carries no
    replication rule.

    A call whose batch does not divide the data axes (``model.init`` on a
    batch of one) goes to the bare kernel, which is right outside a
    sharded jit and refused by jax inside one."""
    data = tuple(a for a in data_axes
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    heads = (head_axis if head_axis in mesh.axis_names
             and mesh.shape[head_axis] > 1 else None)
    n_data = math.prod(mesh.shape[a] for a in data)
    n_heads = mesh.shape[heads] if heads else 1
    spec = P(data or None, None, heads, None)

    def attention(q, k, v, causal=True, window=None):
        if q.shape[0] % n_data:
            return flash_attention(q, k, v, causal, interpret=interpret,
                                   window=window)
        if q.shape[2] % n_heads or k.shape[2] % n_heads:
            raise ValueError(
                f"sharded_flash_attention: {q.shape[2]} query / "
                f"{k.shape[2]} kv heads do not divide over "
                f"{head_axis}={n_heads}")
        return jax.shard_map(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, causal,
                                               interpret=interpret,
                                               window=window),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False)(q, k, v)

    attention.supports_gqa = True
    return attention


# ------------------------------------------------------ a selected set
#
# Attention over a set of keys that is DATA: query ``t`` sees key ``s`` iff
# ``selection[b, t, s]`` (a learned sparse attention's top-k of an index
# score). The same kernels as above, which take the selection's int8 tile
# as an operand where the others compare positions, and the tiles' flags
# (none / some / every pair selected) where the others solve the mask's
# inequalities for the block index: a tile with no selected pair computes
# nothing, a tile wholly selected takes the unmasked body. No band clamps
# an index map here (which tiles are empty is not known before the flags
# are read), so a skipped step still moves its tiles.

def sparse_flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           selection: jnp.ndarray,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           return_lse: bool = False):
    """``softmax`` attention of each query over ITS selected keys. q:
    [B, S, H, D]; k, v: [B, S, H_kv, D] (grouped heads shared through the
    index maps, as :func:`flash_attention`); ``selection``: [B, S, S], bool
    or int8, nonzero where query ``t`` attends to key ``s``, one set for
    all heads. Every row must select at least one key. No causal mask is
    added: a causal selection is the caller's. Exactly the selected pairs'
    softmax in the forward and in the backward (``flash_fwd_sparse``,
    ``flash_bwd_sparse``, or ``flash_bwd_sparse_dq`` / ``_dkdv`` where the
    dense kernels would split too); no gradient reaches ``selection``.
    With every causal pair selected the result is
    ``flash_attention(q, k, v)``'s.

    ``return_lse=True`` also returns the rows' logsumexp over the selected
    scaled scores, float32 ``[B, H, S]`` (differentiable: its cotangent
    folds into the backward's delta). Lengths with no aligned tile are
    padded at the end, the padded rows and keys unselected."""
    from torchft_tpu import tracing

    interpret = _resolve_interpret(interpret)
    b, s, h, _ = q.shape
    if k.shape[1] != s or selection.shape != (b, s, s):
        raise ValueError(
            f"sparse_flash_attention: q {q.shape}, k {k.shape} and "
            f"selection {selection.shape} must share one sequence length "
            f"(selection [B, S, S])")
    selection = selection.astype(jnp.int8)
    delta = 0 if block_q or block_k else _seq_pad(s, s)
    if delta:
        pad = ((0, 0), (0, delta), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
        selection = jnp.pad(selection, ((0, 0), (0, delta), (0, delta)))
    # counted on the host, when the call is traced: nothing in the step
    tracing.add_program_counters(sparse_attn_traces_total=1)
    out, lse = _sparse_core(q, k, v, selection, block_q, block_k, interpret)
    out, lse = out[:, :s], lse.reshape(b, h, -1)[:, :, :s]
    return (out, lse) if return_lse else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _sparse_core(q, k, v, selection, block_q, block_k, interpret):
    return _flash_fwd(
        q, k, v, False, block_q, block_k, interpret,
        selection=_with_tile_flags(selection, block_q, block_k))


def _sparse_fwd_rule(q, k, v, selection, block_q, block_k, interpret):
    flagged = _with_tile_flags(selection, block_q, block_k)
    out, lse = _flash_fwd(q, k, v, False, block_q, block_k, interpret,
                          selection=flagged)
    return (out, lse), (q, k, v, out, lse, flagged)


def _sparse_bwd_rule(block_q, block_k, interpret, res, g):
    q, k, v, out, lse, flagged = res
    g_out, g_lse = g
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g_out, False, block_q,
                            block_k, interpret, g_lse=g_lse,
                            selection=flagged)
    return dq, dk, dv, jnp.zeros(flagged[0].shape, dtype=jax.dtypes.float0)


_sparse_core.defvjp(_sparse_fwd_rule, _sparse_bwd_rule)


# ------------------------------------------------------------- ring block
#
# The composable primitive ring attention needs: one flash pass against a
# single K/V block with a TRACED mask selector, returning the
# block-normalized output AND its per-row logsumexp so blocks merge
# online-softmax style outside the kernel. ``shift`` (int32 scalar) picks
# the mask: 0 = diagonal-causal, >= s_k = full attention, <= -s_q = fully
# blocked (the block then carries lse ~ -inf and merges with zero
# weight). Differentiable: the lse cotangent folds into the backward's
# delta term (d lse / d logits is the softmax itself, so
# ds = p * (dp - (delta - g_lse))).

def flash_attention_block(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          shift: jnp.ndarray,
                          block_q: Optional[int] = None,
                          block_k: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """One flash pass with a traced shift mask; returns ``(out, lse)``
    with ``out`` [B, S, H, D] block-normalized and ``lse`` [B*H, S]."""
    return _flash_block_core(q, k, v, shift, block_q, block_k,
                             _resolve_interpret(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_block_core(q, k, v, shift, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, False, block_q, block_k, interpret,
                      shift=shift)


def _block_fwd_rule(q, k, v, shift, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, False, block_q, block_k, interpret,
                          shift=shift)
    return (out, lse), (q, k, v, out, lse, shift)


def _block_bwd_rule(block_q, block_k, interpret, res, g):
    q, k, v, out, lse, shift = res
    g_out, g_lse = g
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g_out, False, block_q,
                            block_k, interpret, shift=shift,
                            g_lse=g_lse)
    return dq, dk, dv, jnp.zeros(jnp.shape(shift),
                                 dtype=jax.dtypes.float0)


_flash_block_core.defvjp(_block_fwd_rule, _block_bwd_rule)
