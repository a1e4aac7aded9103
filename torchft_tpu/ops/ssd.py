"""The Mamba-2 selective state-space scan in its chunked (SSD) form.

Per head ``h`` of ``H``, with a state ``S`` of ``[P, N]`` (head size by
state size) that starts at zero, a step ``Delta_t > 0``, a rate ``A < 0``
and a skip ``D``, and with ``B_t``, ``C_t`` of ``[N]`` shared by the
``H / G`` heads of a group (head ``h`` reads group ``h // (H / G)``):

    S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T
    y_t = S_t C_t + D x_t

:func:`ssd_recurrent` is that recurrence token by token in float32 at the
highest precision: the oracle, and nothing the trainer runs.
:func:`ssd_scan` computes the same in chunks of ``CHUNK`` tokens. With
``gamma_i`` the running sum of ``Delta A`` inside a chunk (``<= 0``),
``gamma_Q`` its last value and ``S`` the state entering the chunk:

    Y = (tril(exp(gamma_i - gamma_j)) * (C B^T)) (Delta * X)
        + exp(gamma_i) C_i S + D X
    S <- exp(gamma_Q) S + sum_j exp(gamma_Q - gamma_j) Delta_j x_j B_j^T

``gamma`` is a running sum XLA takes on ``[B, T, H]`` float32; everything
else is two Pallas kernels under one ``jax.custom_vjp``
(:func:`_chunk_scan`), written as ``ops/gated_delta.py``'s are. ``ssd_fwd``
walks a grid of (batch, heads / ``hb``, chunks) with the chunks last, so in
order on a core. A step holds ``hb`` heads of ONE group
(:func:`_heads_a_step`: the whole group of 8 at the published sizes) and
reads its tiles where they lie: ``x`` as a ``[CHUNK, hb P]`` block of
``[B, T, H P]``, ``B`` and ``C`` as ``[CHUNK, N]`` blocks of ``[B, T, G N]``;
only what is a number a token a head (``Delta``, ``gamma``, ``gamma`` at its
chunk's end; float32 ``[B, T, H]``, 2 MiB each at 8,192 tokens) is laid out
for it, a head's tokens along the lanes. In VMEM it forms ``C B^T`` once for
the group, a head's decay triangle (masked before the ``exp``, so every
exponent taken is of a number ``<= 0``), a head's product with it, and for
all its heads at once the state's reading ``S C^T`` and writing, and carries
the heads' states, float32 ``[hb P, N]``, in scratch from chunk to chunk: no
triangle, no per-chunk state product and no transposed copy of ``x`` or
``y`` is ever in HBM. A differentiated call also writes the state each
chunk found (float32 ``[B, chunks, H P, N]``, 128 MiB a block at the
published sizes): all the backward keeps beside the inputs. ``ssd_bwd``
walks the same grid from the last chunk to the first with the state's
cotangent in that scratch, builds the chunk's triangle again and writes the
cotangents of ``x``, ``B`` and ``C`` in their own layouts (``B``'s and
``C``'s summed over the step's heads before their products with ``C`` and
``B``; a group wider than a step leaves a float32 partial sum a step, added
outside) and, a token a head, those of ``Delta``'s direct use, of ``gamma``
and of ``D``, which XLA reduces to ``dDelta``, ``dA`` and ``dD`` on
``[B, T, H]``.

``Delta``, ``gamma``, the decays and the states are float32; the products
take ``dtype`` inputs (bfloat16 in training) and accumulate in float32,
cotangents enter the backward's products in ``dtype`` too. Off a TPU the
kernels run interpreted. (A Mosaic kernel cannot be partitioned
automatically: under a jit sharded over several chips the scan wants a
``shard_map`` over the batch or the groups around it, as
``ops.sharded_flash_attention`` is around the flash kernels.)

A sequence that is not a whole number of chunks is padded at its end with
tokens of step zero, which neither decay nor write.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.gated_delta import (_LANES, _dot,
                                         _resolve_interpret)

CHUNK = 128   # tokens a chunk (the published kernel's): a chunk's triangle
#               is [128, 128] a head, the saved states [T / 128, H P, N]

# What a grid step's tiles may take of VMEM, both buffers of each input and
# output and the scratch counted: ``hb`` is the most heads that fit.
_TILE_BYTES = 12 << 20
# What a kernel asks Mosaic for: the tiles and the body's temporaries (a
# head's triangle and its cotangent are float32 [CHUNK, CHUNK] values).
_VMEM_LIMIT_BYTES = 24 << 20


def _grouped(h: int, g: int) -> int:
    if g <= 0 or h % g:
        raise ValueError(f"{h} heads over {g} groups")
    return h // g


def _heads_a_step(k: int, p: int, n: int, itemsize: int) -> int:
    """Heads a grid step: the largest divisor of the ``k`` heads a group
    whose backward tiles (the larger of the two kernels') fit
    ``_TILE_BYTES``. A head's: ``x`` and ``dx``, float32 ``dy``, the saved
    state, its row of ``keep`` and seven rows of the chunk; the step's:
    ``B``, ``C`` and their float32 cotangents, and ``gamma`` as a float32
    column a vector's lanes wide; two buffers each, and the float32
    scratch."""
    head = CHUNK * p * (2 * itemsize + 4) + (p + 1) * n * 4 + 7 * CHUNK * 4
    shared = 2 * CHUNK * n * (itemsize + 4) + CHUNK * _LANES * 4
    hb = max(min((_TILE_BYTES - 2 * shared) // (2 * head + p * n * 4), k), 1)
    while k % hb:
        hb -= 1
    return hb


# Inside a kernel the tokens lie along the LANES: a step transposes its tile
# of ``x`` once (``[CHUNK, hb P] -> [hb P, CHUNK]``) and its tile of ``y``
# back, so a head is a run of sublanes, whatever is a number a token
# (``Delta``, ``gamma``, the decays) is one vector, broadcast down the
# sublanes for nothing, and every product streams P or hb P rows through the
# MXU against a full [128, 128] tile. (The other way round, tokens down the
# sublanes, a head of 64 fills half of every vector, a number a token is a
# column of 16 vectors with one lane in use, and the products stream 128 rows
# for 64 columns: 2.4 times the time on a v5e; PERF.md, PR 54.)

def _triangle(g_row, gcol, upper):
    """``exp(gamma_i - gamma_j)`` at [j, i] for ``i >= j``, zero below the
    diagonal (where the difference is positive and may overflow: masked
    before the exp)."""
    return jnp.exp(jnp.where(upper, g_row - gcol, -jnp.inf))


def _upper(q):
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _fwd_kernel(x_ref, dt_ref, g_ref, total_ref, gcol_ref, keep_ref, d_ref,
                b_ref, c_ref, y_ref, *rest, hb: int, dtype: Any):
    """One chunk of ``hb`` heads of a group: ``y`` from the chunk's own
    tokens, from the state it found and from the skip, then the state it
    leaves. ``rest`` is the scratch (the heads' states, [hb P, N]) and, in
    the differentiated call, before it the output that takes the state as
    the chunk found it."""
    state = rest[-1]
    exact = jnp.dtype(dtype) == jnp.float32
    p = state.shape[0] // hb

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    b, c = b_ref[...].astype(dtype), c_ref[...].astype(dtype)
    cb = _dot(b, c, (1, 1), exact)                # (C B^T)^T, [j, i]
    upper = _upper(cb.shape[0])
    x_all = x_ref[...].astype(jnp.float32).T      # [hb P, Q]
    s_all = state[...]
    if len(rest) == 2:
        rest[0][...] = s_all
    read = _dot(s_all.astype(dtype), c, (1, 1), exact)        # S C^T
    ys, to_end, kept = [], [], []
    for j in range(hb):     # static: a head is a run of the tile's sublanes
        rows = slice(j * p, (j + 1) * p)
        g, dt = g_ref[j:j + 1, :], dt_ref[j:j + 1, :]
        x = x_all[rows]
        x_dt = x * dt
        m = cb * _triangle(g, gcol_ref[:, j:j + 1], upper)
        y = _dot(x_dt.astype(dtype), m.astype(dtype), (1, 0), exact)
        ys.append(y + read[rows] * jnp.exp(g) + d_ref[j:j + 1, :] * x)
        to_end.append(
            (x_dt * jnp.exp(total_ref[j:j + 1, :] - g)).astype(dtype))
        kept.append(keep_ref[j:j + 1, :] * s_all[rows])
    y_ref[...] = jnp.concatenate(ys, axis=0).T
    state[...] = jnp.concatenate(kept, axis=0) + _dot(
        jnp.concatenate(to_end, axis=0), b, (1, 0), exact)


def _bwd_kernel(x_ref, dt_ref, g_ref, total_ref, gcol_ref, keep_ref, d_ref,
                b_ref, c_ref, s_ref, dy_ref, dx_ref, ddt_ref, dg_ref, dd_ref,
                db_ref, dc_ref, dstate, *, hb: int, dtype: Any):
    """The same chunk from the other side. ``dstate`` holds the cotangent
    of the state as the chunk LEFT it and ends as that of the state it
    found (``s_ref``). With ``M`` the masked ``C B^T`` times the triangle
    and ``w = exp(gamma_Q - gamma)``:

        d(Delta x) = M^T dy + w (B dS^T)
        d(C B^T)   = (dy (Delta x)^T) * triangle, summed over the heads
        dgamma_i   = dy_i . (y_i - D x_i) - (Delta x)_i . d(Delta x)_i,
                     and at the chunk's last token + <dS, the state left>

    ``gamma_i`` stands in row i of the triangle and, against it, in column
    i: both sums are taken from ONE float32 ``dM * M`` (down its sublanes,
    and down its transpose's: a sum along the lanes is seven rotations a
    vector), or their roundings would not cancel in dA's long sum."""
    exact = jnp.dtype(dtype) == jnp.float32
    f32 = jnp.float32
    p = dstate.shape[0] // hb

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dstate[...] = jnp.zeros_like(dstate)

    b, c = b_ref[...].astype(dtype), c_ref[...].astype(dtype)
    cb = _dot(b, c, (1, 1), exact)
    q = cb.shape[0]
    upper = _upper(q)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    x_all = x_ref[...].astype(f32).T              # [hb P, Q]
    dy_all = dy_ref[...].T
    s_all, ds_all = s_ref[...], dstate[...]
    s_in, ds_in = s_all.astype(dtype), ds_all.astype(dtype)
    read = _dot(s_in, c, (1, 1), exact)           # S C^T, [hb P, i]
    off_all = _dot(ds_in, b, (1, 1), exact)       # dS B^T, [hb P, j]
    dcb = jnp.zeros((q, q), f32)
    dxs, gated, to_end, kept = [], [], [], []
    for j in range(hb):
        rows = slice(j * p, (j + 1) * p)
        g, dt = g_ref[j:j + 1, :], dt_ref[j:j + 1, :]
        e_gamma, w = jnp.exp(g), jnp.exp(total_ref[j:j + 1, :] - g)
        x, dy = x_all[rows], dy_all[rows]
        dy_in = dy.astype(dtype)
        x_dt = x * dt
        decay = _triangle(g, gcol_ref[:, j:j + 1], upper)
        m = cb * decay
        # through the products that read Delta x
        off = off_all[rows] * w
        dx_dt = _dot(dy_in, m.astype(dtype), (1, 1), exact) + off
        dxs.append(dx_dt * dt + d_ref[j:j + 1, :] * dy)
        ddt_ref[j:j + 1, :] = jnp.sum(dx_dt * x, axis=0, keepdims=True)
        dd_ref[j:j + 1, :] = jnp.sum(dy * x, axis=0, keepdims=True)
        # through the triangle, the state's reading and the state left
        dm = _dot(x_dt.astype(dtype), dy_in, (0, 0), exact)   # [j, i]
        dcb += dm * decay
        through = dm * m
        written = jnp.sum(x_dt * off, axis=0, keepdims=True)
        left = (jnp.sum(written, axis=1, keepdims=True)
                + keep_ref[j:j + 1, :1] * jnp.sum(
                    jnp.sum(ds_all[rows] * s_all[rows], axis=0,
                            keepdims=True), axis=1, keepdims=True))
        dg_ref[j:j + 1, :] = (
            jnp.sum(through, axis=0, keepdims=True)
            - jnp.sum(through.T, axis=0, keepdims=True)
            + jnp.sum(dy * read[rows], axis=0, keepdims=True) * e_gamma
            - written + jnp.where(at_end, left, 0.0))
        gated.append((dy * e_gamma).astype(dtype))
        to_end.append((x_dt * w).astype(dtype))
        kept.append(keep_ref[j:j + 1, :] * ds_all[rows])
    dx_ref[...] = jnp.concatenate(dxs, axis=0).T.astype(dx_ref.dtype)
    gated = jnp.concatenate(gated, axis=0)        # [hb P, Q]
    to_end = jnp.concatenate(to_end, axis=0)
    dstate[...] = jnp.concatenate(kept, axis=0) + _dot(gated, c, (1, 0),
                                                       exact)
    dcb = dcb.astype(dtype)
    db_ref[...] = (_dot(dcb, c, (1, 0), exact)
                   + _dot(to_end, ds_in, (0, 0), exact)).astype(db_ref.dtype)
    dc_ref[...] = (_dot(dcb, b, (0, 0), exact)
                   + _dot(gated, s_in, (0, 0), exact)).astype(dc_ref.dtype)


def _rows(v, hb):
    """[B, T, H] -> [B, H / hb, hb, T]: a head's tokens along the lanes."""
    bsz, t, h = v.shape
    return v.reshape(bsz, t, h // hb, hb).transpose(0, 2, 3, 1)


def _tokens_first(v):
    """:func:`_rows` undone."""
    bsz, steps, hb, t = v.shape
    return v.transpose(0, 3, 1, 2).reshape(bsz, t, steps * hb)


def _call(kernel, name, args, more, outs, hb, dtype, index, interpret, q):
    """One sweep over the chunks, a grid of (batch, heads / ``hb``, chunks):
    ``args`` as :func:`ssd_scan` has them after padding (``dt`` and
    ``gamma`` float32), ``more`` further inputs and ``outs`` the outputs'
    shapes, each beside the kind of its block: ``tile`` ([B, T, H P], a
    chunk of the step's heads), ``state`` ([B, chunks, H P, N]), ``rows``
    ([B, H / hb, hb, T]) or ``sum`` ([B, T, H / hb, N]: a chunk's
    [q, N] a step). The chunk a step works on is ``index`` of the grid's
    third, ``q`` tokens long."""
    x, dt, gamma, b_in, c_in, d = args
    bsz, t, h, p = x.shape
    g, n_state = b_in.shape[2:]
    n = t // q
    a_group = h // g // hb                        # steps a group
    rows = pl.BlockSpec((None, None, hb, q),
                        lambda i, j, c: (i, j, 0, index(c)))
    group = pl.BlockSpec((None, q, n_state),
                         lambda i, j, c: (i, index(c), j // a_group))
    spec = {
        "tile": pl.BlockSpec((None, q, hb * p),
                             lambda i, j, c: (i, index(c), j)),
        "state": pl.BlockSpec((None, None, hb * p, n_state),
                              lambda i, j, c: (i, index(c), j, 0)),
        "rows": rows,
        "sum": pl.BlockSpec((None, q, n_state),
                            lambda i, j, c: (i, index(c), j)),
    }
    gamma = _rows(gamma, hb)
    # gamma at its chunk's end, over the chunk's tokens and, as the chunk's
    # whole decay, over the state's lanes
    total = gamma.reshape(bsz, h // hb, hb, n, q)[..., -1:]
    keep = jnp.broadcast_to(jnp.exp(total).transpose(0, 1, 3, 2, 4),
                            (bsz, h // hb, n, hb, n_state))
    total = jnp.broadcast_to(total, (bsz, h // hb, hb, n, q))
    return pl.pallas_call(
        functools.partial(kernel, hb=hb, dtype=dtype),
        out_shape=[shape for shape, _ in outs],
        grid=(bsz, h // hb, n),
        in_specs=[spec["tile"], rows, rows, rows,
                  # gamma again, tokens down the sublanes (the triangle's
                  # other index)
                  pl.BlockSpec((None, None, q, hb),
                               lambda i, j, c: (i, j, index(c), 0)),
                  pl.BlockSpec((None, None, None, hb, n_state),
                               lambda i, j, c: (i, j, index(c), 0, 0)),
                  pl.BlockSpec((None, hb, q), lambda i, j, c: (j, 0, 0)),
                  group, group, *(spec[kind] for _, kind in more)],
        out_specs=[spec[kind] for _, kind in outs],
        scratch_shapes=[pltpu.VMEM((hb * p, n_state), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(x.reshape(bsz, t, h * p), _rows(dt, hb), gamma,
      total.reshape(gamma.shape), jnp.swapaxes(gamma, 2, 3), keep,
      jnp.broadcast_to(d.reshape(h // hb, hb, 1), (h // hb, hb, q)),
      b_in.reshape(bsz, t, g * n_state), c_in.reshape(bsz, t, g * n_state),
      *(v for v, _ in more))


# Both sweeps under ``jax.jit``: a model's mixers of one shape then trace and
# lower each kernel body once a program, not once a block (the unrolled
# bodies are 0.1 s of Python apiece, paid in every process's set-up).
# ``q`` is ``CHUNK``, an argument so that the traces are keyed by it.

@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _sweep_fwd(args, hb, dtype, interpret, q, states):
    bsz, t, h, p = args[0].shape
    f32 = jnp.float32
    outs = [(jax.ShapeDtypeStruct((bsz, t, h * p), f32), "tile")]
    if states:
        outs.append((jax.ShapeDtypeStruct(
            (bsz, t // q, h * p, args[3].shape[3]), f32), "state"))
    y, *kept = _call(_fwd_kernel, "ssd_fwd", args, (), outs, hb, dtype,
                     lambda c: c, interpret, q)
    return y.reshape(bsz, t, h, p), kept


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _chunk_scan(x, dt, gamma, b_in, c_in, d, hb, dtype, interpret, q):
    """The scan over whole chunks, ``gamma`` given: ``x`` [B, T, H, P],
    ``dt``, ``gamma`` float32 [B, T, H], ``b_in`` and ``c_in`` [B, T, G, N],
    ``d`` float32 [H]; ``hb`` heads a grid step, products in ``dtype``,
    chunks of ``q`` tokens.
    Returns float32 [B, T, H, P]. Its pullback takes ``dt`` for its direct
    use (``Delta x``) only: what reaches it through ``gamma`` is the
    caller's running sum's to pass on."""
    return _sweep_fwd((x, dt, gamma, b_in, c_in, d), hb, dtype, interpret, q,
                      False)[0]


def _scan_fwd(x, dt, gamma, b_in, c_in, d, hb, dtype, interpret, q):
    args = (x, dt, gamma, b_in, c_in, d)
    y, (states,) = _sweep_fwd(args, hb, dtype, interpret, q, True)
    return y, (args, states)


def _scan_bwd(hb, dtype, interpret, q, res, dy):
    return _sweep_bwd(*res, dy, hb, dtype, interpret, q)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _sweep_bwd(args, states, dy, hb, dtype, interpret, q):
    x, _, _, b_in, _, d = args
    bsz, t, h, p = x.shape
    g, n_state = b_in.shape[2:]
    f32 = jnp.float32
    n = t // q
    rows = (jax.ShapeDtypeStruct((bsz, h // hb, hb, t), f32), "rows")
    # a step that holds its whole group writes the group's cotangent as it
    # is; narrower steps leave float32 partial sums, added below
    whole = h // g == hb
    summed = (jax.ShapeDtypeStruct((bsz, t, h // hb * n_state),
                                   b_in.dtype if whole else f32), "sum")
    dx, ddt, dgamma, dd, db, dc = _call(
        _bwd_kernel, "ssd_bwd", args,
        ((states, "state"), (dy.reshape(bsz, t, h * p), "tile")),
        [(jax.ShapeDtypeStruct((bsz, t, h * p), x.dtype), "tile"),
         rows, rows, rows, summed, summed],
        hb, dtype, lambda c: n - 1 - c, interpret, q)
    db, dc = (v.reshape(bsz, t, g, -1, n_state).sum(3).astype(b_in.dtype)
              for v in (db, dc))
    return (dx.reshape(x.shape), _tokens_first(ddt), _tokens_first(dgamma),
            db, dc, _tokens_first(dd).sum((0, 1)).astype(d.dtype))


_chunk_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b_in: jnp.ndarray, c_in: jnp.ndarray, d: jnp.ndarray,
             dtype: Any = jnp.bfloat16) -> jnp.ndarray:
    """The chunked form (see the module docstring). ``x`` [B, T, H, P],
    ``dt`` [B, T, H] (the step ``Delta``, after its softplus), ``a`` [H]
    (negative), ``b_in`` and ``c_in`` [B, T, G, N], ``d`` [H]. Returns
    float32 [B, T, H, P]."""
    bsz, t, h, p = x.shape
    k = _grouped(h, b_in.shape[2])
    pad = -t % CHUNK
    if pad:
        x, b_in, c_in = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                         for v in (x, b_in, c_in))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    f32 = jnp.float32
    dt = dt.astype(f32)
    gamma = jnp.cumsum((dt * a.astype(f32)).reshape(bsz, -1, CHUNK, h),
                       axis=2).reshape(dt.shape)
    y = _chunk_scan(
        x, dt, gamma, b_in, c_in, d.astype(f32),
        _heads_a_step(k, p, b_in.shape[3], jnp.dtype(dtype).itemsize),
        dtype, _resolve_interpret(None), CHUNK)
    return y[:, :t]


def ssd_recurrent(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                  b_in: jnp.ndarray, c_in: jnp.ndarray, d: jnp.ndarray
                  ) -> jnp.ndarray:
    """The recurrence token by token, float32 at the highest precision: what
    :func:`ssd_scan` is held to. Same arguments and result."""
    f32 = jnp.float32
    bsz, _, h, p = x.shape
    k = _grouped(h, b_in.shape[2])
    a, d = a.astype(f32), d.astype(f32)

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs                   # [B,H,P] [B,H] [B,G,N]
        b_t, c_t = (jnp.repeat(v, k, axis=1) for v in (b_t, c_t))
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        y_t = jnp.einsum("bhps,bhs->bhp", state, c_t,
                         precision=jax.lax.Precision.HIGHEST)
        return state, y_t + d[:, None] * x_t

    _, out = jax.lax.scan(
        token, jnp.zeros((bsz, h, p, b_in.shape[3]), f32),
        tuple(jnp.moveaxis(v.astype(f32), 1, 0)
              for v in (x, dt, b_in, c_in)))
    return jnp.moveaxis(out, 0, 1)
