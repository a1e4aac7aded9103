"""The gated delta rule (Gated DeltaNet's linear attention) and the causal
short convolution in front of it.

Per head, with a state ``S`` of ``[d_k, d_v]`` that starts at zero, a decay
``alpha_t = exp(g_t)`` in (0, 1] and a write strength ``beta_t`` in [0, 1]:

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

:func:`gated_delta_recurrent` is that recurrence token by token in float32:
the oracle, and nothing the trainer runs. :func:`gated_delta_rule` computes
the same in chunks of ``CHUNK`` tokens. With ``gamma_i`` the running sum of
``g`` inside a chunk and ``S`` the state entering it:

    A_ij = beta_i (k_i . k_j) exp(gamma_i - gamma_j)   for i > j, else 0
    T = (I + A)^-1
    W = T (beta exp(gamma) * K);  U = T (beta * V)
    V' = U - W S
    O = (Q * exp(gamma)) S + tril(Q K^T * exp(gamma_i - gamma_j)) V'
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

Everything that does not read ``S`` (``A``, ``T``, ``W``, ``U``, the masked
``Q K^T``) is computed for all chunks at once in large batched products;
the last three lines are sequential, and run as two Pallas kernels under
one ``jax.custom_vjp`` (:func:`chunk_recurrence`). ``gdn_fwd`` walks a
grid of (batch x heads / ``hb``, chunks) with the chunks last, so in
order on a core: the ``hb`` heads' states, float32 ``[hb, d_k, d_v]``, stay
in VMEM scratch for the whole sweep, and a step reads one chunk of ``W``,
``U``, ``Q e^gamma``, the masked ``Q K^T`` and ``K e^(gamma_C - gamma)``,
does the three lines as four MXU products a head and writes ``O``; in a
differentiated call it also writes the state each chunk found (256 MiB a
layer at 32 heads of 128 x 128 over 128 chunks), which is all the backward
keeps beside the inputs. ``gdn_bwd`` walks the same grid from the last
chunk to the first with the state's cotangent in that scratch, computes
``V'`` again from the saved state and gives the six cotangents (eight
products a head). ``hb`` is the most heads whose tiles fit a VMEM budget
(:func:`_heads_a_step`: 16 at those sizes in bfloat16).

``T`` is forward substitution in a third kernel (``gdn_inv``,
:func:`unit_lower_inverse`): the matrices lie along the lanes, so a row's
update is one multiply-subtract for 128 matrices at once, exact in float32
on the VPU; its pullback is two float32 products. (The nilpotent product
``(I - A)(I + A^2)(I + A^4)...`` is exact only in exact arithmetic: where a
chunk's keys repeat, its terms grow like binomial coefficients before they
cancel, and float32 reads 24 where the inverse's largest entry is 1. Ten
float32 products at the highest precision also take twice the time of
XLA's own substitution on a v5e, which the kernel undercuts by six;
PERF.md, PR 49.)

Decays, ``gamma``, the inverse and the carried state are float32; the
products take ``dtype`` inputs (bfloat16 in training) and accumulate in
float32, in the kernels as in front of them; cotangents enter the
backward's products in ``dtype`` too. Off a TPU the kernels run
interpreted. A caller that lacks the room for a layer's saved states and
chunk products from forward to backward wraps the call in
``jax.checkpoint``.

A sequence that is not a whole number of chunks is padded at its end with
tokens that neither decay nor write (``g = 0``, ``beta = 0``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.flash_attention import _LANES, _resolve_interpret

CHUNK = 64   # tokens a chunk: the [C, C] inverse stays small, the products
#              between chunks stay MXU-sized


def causal_conv1d(x: jnp.ndarray, weight: jnp.ndarray,
                  bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Causal depthwise convolution along the sequence. ``x`` [B, T, Ch],
    ``weight`` [K, Ch], ``bias`` [Ch] or none (the delta rule's and the
    gated short convolution's have none, the state-space mixer's has one):
    ``y_t = sum_j weight[j] * x_{t-K+1+j} + bias`` with zeros left of the
    sequence. Float32 inside, ``x``'s type out. K shifted copies in one
    fused pass: at the K of 3 or 4 its callers have, a convolution primitive
    has nothing to add."""
    k = weight.shape[0]
    t = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    y = sum(w[j] * padded[:, j:j + t] for j in range(k))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _mm(eq: str, a: jnp.ndarray, b: jnp.ndarray, dtype: Any) -> jnp.ndarray:
    """A product with ``dtype`` inputs and a float32 result; float32 inputs
    are multiplied at the highest precision."""
    exact = jnp.dtype(dtype) == jnp.float32
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST if exact else None)


def _heads_first(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """[B, n * CHUNK, H, d] -> [B, H, n, CHUNK, d]."""
    b, _, h, d = x.shape
    return x.reshape(b, n, CHUNK, h, d).transpose(0, 3, 1, 2, 4)


# ------------------------------------------------------ the chunk inverse
#
# Forward substitution, row by row, on the VPU: the matrices lie along the
# lanes ([row, column, matrix]), so a row's update is one multiply-subtract
# a (row, earlier row) pair across 128 matrices at once, exact in float32
# at one operation where the MXU takes six passes of a quarter-filled
# array. A grid step takes one vector's lanes of matrices (``_LANES``): 64
# sequential rows a matrix, 32 grid steps for the cell's 4,096 matrices.

def _inverse_kernel(a_ref, t_ref):
    """``t = (I + a)^-1``, blocks [n, n, _LANES] float32 indexed
    [row, column, matrix], ``a`` strictly lower: row ``i`` of ``t`` is
    ``e_i - sum_{j < i} a[i, j] t[j]``. The rows not yet written are zero,
    so a group of eight ``j`` may reach past ``i``, where ``a`` is zero."""
    n = a_ref.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, a_ref.shape[1:], 0)
    t_ref[...] = jnp.zeros_like(t_ref)

    def row(i, _):
        def eight(jb, acc):
            start = pl.multiple_of(jb * 8, 8)
            a8 = a_ref[i, pl.ds(start, 8), :]
            for jj in range(8):
                acc = acc - a8[jj:jj + 1, :] * t_ref[start + jj]
            return acc

        t_ref[i] = jax.lax.fori_loop(0, (i + 7) // 8, eight,
                                     (col == i).astype(jnp.float32))
        return 0

    jax.lax.fori_loop(0, n, row, 0)


@jax.custom_vjp
def unit_lower_inverse(a: jnp.ndarray) -> jnp.ndarray:
    """``(I + a)^-1`` for strictly lower triangular float32 ``a``
    [..., n, n] (``n`` a multiple of 8), by substitution in one Pallas
    kernel; its pullback is two products, ``-T^T ct T^T``."""
    n = a.shape[-1]
    count = math.prod(a.shape[:-2])
    pad = -count % _LANES
    # [..., row, column] -> [row, column, matrix], whole vectors of matrices
    lanes = jnp.pad(a.reshape(count, n, n).transpose(1, 2, 0),
                    ((0, 0), (0, 0), (0, pad)))
    spec = pl.BlockSpec((n, n, _LANES), lambda m: (0, 0, m))
    inv = pl.pallas_call(
        _inverse_kernel,
        out_shape=jax.ShapeDtypeStruct(lanes.shape, jnp.float32),
        grid=((count + pad) // _LANES,),
        in_specs=[spec], out_specs=spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_resolve_interpret(None),
        name="gdn_inv",
    )(lanes)
    return inv[..., :count].transpose(2, 0, 1).reshape(a.shape)


def _inverse_fwd(a):
    inv = unit_lower_inverse(a)
    return inv, inv


def _inverse_bwd(inv, ct):
    # d (I + a)^-1 = -T da T
    t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.matmul(t, jnp.matmul(ct, t, precision="highest"),
                        precision="highest"),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# --------------------------------------------------- the chunk recurrence
#
# Two Pallas kernels over a grid (batch x heads / hb, chunks), the chunks
# last and so sequential on a core: a step holds ``hb`` heads' state
# (float32 [hb, d_k, d_v]) in VMEM scratch, reads one chunk of each input
# and does the chunk's products on the MXU. The backward walks the chunks
# from the last to the first with the state's cotangent in that scratch.

# What a grid step's tiles may take of VMEM, both buffers of each input and
# output and the scratch counted: ``hb`` is the most heads that fit.
_TILE_BYTES = 12 << 20
# What a kernel asks Mosaic for: the tiles and the body's temporaries (a
# head's products are float32 [CHUNK, d] values, a few alive at a time).
_VMEM_LIMIT_BYTES = 24 << 20


def _heads_a_step(bh: int, d_k: int, d_v: int, itemsize: int) -> int:
    """Value heads a grid step: the largest divisor of ``bh`` whose
    backward tiles (the larger of the two kernels': eight inputs, six
    outputs, two buffers each, and the float32 scratch) fit
    ``_TILE_BYTES``."""
    narrow = 6 * CHUNK * d_k + 2 * CHUNK * CHUNK      # w, q, k, their ct; qk
    wide = 3 * CHUNK * d_v + d_k * d_v + 2 * d_v      # u, do, du; state; keep
    head = 2 * (narrow * itemsize + wide * 4) + d_k * d_v * 4
    hb = max(min(_TILE_BYTES // head, bh), 1)
    while bh % hb:
        hb -= 1
    return hb


def _dot(a, b, contract, exact):
    """``a`` and ``b`` contracted over one axis each, float32 out."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if exact else None)


def _fwd_kernel(w_ref, u_ref, q_ref, qk_ref, k_ref, keep_ref, o_ref, *rest,
                hb: int, unroll: bool):
    """One chunk of ``hb`` heads: ``v = u - w S``, ``o = q S + qk v``,
    ``S <- keep S + k^T v``. ``rest`` is the scratch and, in the
    differentiated call, before it the output that takes the state as the
    chunk found it."""
    state = rest[-1]
    dtype = w_ref.dtype
    exact = dtype == jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    def head(j, _):
        s = state[j]
        if len(rest) == 2:
            rest[0][j] = s
        s_in = s.astype(dtype)
        v = (u_ref[j] - _dot(w_ref[j], s_in, (1, 0), exact)).astype(dtype)
        o_ref[j] = (_dot(q_ref[j], s_in, (1, 0), exact)
                    + _dot(qk_ref[j], v, (1, 0), exact))
        state[j] = keep_ref[j] * s + _dot(k_ref[j], v, (0, 0), exact)
        return 0

    jax.lax.fori_loop(0, hb, head, 0, unroll=unroll)


def _bwd_kernel(w_ref, u_ref, q_ref, qk_ref, k_ref, keep_ref, s_ref, do_ref,
                dw_ref, du_ref, dq_ref, dqk_ref, dk_ref, dkeep_ref, dstate,
                *, hb: int, unroll: bool):
    """The same chunk from the other side. ``dstate`` holds the cotangent
    of the state as the chunk LEFT it and ends as that of the state it
    found (``s_ref``); ``v`` is computed again from it."""
    dtype = w_ref.dtype
    exact = dtype == jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _zero():
        dstate[...] = jnp.zeros_like(dstate)

    def head(j, _):
        s, ds = s_ref[j], dstate[j]
        s_in, ds_in = s.astype(dtype), ds.astype(dtype)
        w, q, qk, k = w_ref[j], q_ref[j], qk_ref[j], k_ref[j]
        do = do_ref[j].astype(dtype)
        v = (u_ref[j] - _dot(w, s_in, (1, 0), exact)).astype(dtype)
        dv = (_dot(qk, do, (0, 0), exact)
              + _dot(k, ds_in, (1, 0), exact))
        dv_in = dv.astype(dtype)
        du_ref[j] = dv
        dw_ref[j] = (-_dot(dv_in, s_in, (1, 1), exact)).astype(dtype)
        dq_ref[j] = _dot(do, s_in, (1, 1), exact).astype(dtype)
        dqk_ref[j] = _dot(do, v, (1, 1), exact).astype(dtype)
        dk_ref[j] = _dot(v, ds_in, (1, 1), exact).astype(dtype)
        dkeep_ref[j] = jnp.sum(ds * s, axis=0, keepdims=True)
        dstate[j] = (keep_ref[j] * ds + _dot(q, do, (0, 0), exact)
                     - _dot(w, dv_in, (0, 0), exact))
        return 0

    jax.lax.fori_loop(0, hb, head, 0, unroll=unroll)


def _chunk_specs(hb: int, shapes, index):
    """A ``BlockSpec`` an array [BH, n, rows, cols]: ``hb`` heads of one
    chunk, the chunk from the grid's second index through ``index``."""
    return [pl.BlockSpec((hb, None) + tuple(shape[2:]),
                         lambda i, c: (i, index(c), 0, 0))
            for shape in shapes]


def _call(kernel, name, args, out_shapes, hb, index, scratch, interpret):
    """One sweep over the chunks: every argument and output
    [BH, n, rows, cols], blocked ``hb`` heads of a chunk a step."""
    from torchft_tpu import tracing

    bh, n = args[0].shape[:2]
    grid = (bh // hb, n)
    tracing.add_program_counters(
        gdn_kernel_traces_total=1,
        gdn_kernel_grid_steps_traced_total=grid[0] * grid[1])
    return pl.pallas_call(
        # the heads of a step are independent: unrolled, the chip's
        # scheduler interleaves their products; interpreted, the loop stays
        # a loop, which only spares the CPU's compiler
        functools.partial(kernel, hb=hb, unroll=not interpret),
        out_shape=out_shapes,
        grid=grid,
        in_specs=_chunk_specs(hb, [a.shape for a in args], index),
        out_specs=_chunk_specs(hb, [o.shape for o in out_shapes], index),
        scratch_shapes=[scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(*args)


def _sweep_fwd(w, u, q_in, qk, k_out, keep, hb, interpret, states):
    bh, n, _, d_k = w.shape
    d_v = u.shape[-1]
    f32 = jnp.float32
    outs = [jax.ShapeDtypeStruct(u.shape, f32)]
    if states:
        outs.append(jax.ShapeDtypeStruct((bh, n, d_k, d_v), f32))
    return _call(_fwd_kernel, "gdn_fwd", (w, u, q_in, qk, k_out, keep),
                 outs, hb, lambda c: c, pltpu.VMEM((hb, d_k, d_v), f32),
                 interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def chunk_recurrence(w, u, q_in, qk, k_out, keep, hb, interpret):
    """The part of the rule that reads the carried state, all chunks of
    all heads: ``w``, ``q_in``, ``k_out`` [BH, n, CHUNK, d_k] and ``qk``
    [BH, n, CHUNK, CHUNK] in the products' type, ``u`` [BH, n, CHUNK, d_v]
    and ``keep`` [BH, n, 1, d_v] (a chunk's whole decay, the same in every
    column) float32. Returns ``o`` float32 [BH, n, CHUNK, d_v]. ``hb``
    heads a grid step."""
    return _sweep_fwd(w, u, q_in, qk, k_out, keep, hb, interpret, False)[0]


def _recurrence_fwd(w, u, q_in, qk, k_out, keep, hb, interpret):
    out, states = _sweep_fwd(w, u, q_in, qk, k_out, keep, hb, interpret,
                             True)
    return out, (w, u, q_in, qk, k_out, keep, states)


def _recurrence_bwd(hb, interpret, res, do):
    states = res[-1]
    n = states.shape[1]
    outs = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in res[:6]]
    return tuple(_call(
        _bwd_kernel, "gdn_bwd", (*res, do), outs, hb,
        lambda c: n - 1 - c,
        pltpu.VMEM((hb,) + states.shape[2:], jnp.float32), interpret))


chunk_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def gated_delta_rule(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     g: jnp.ndarray, beta: jnp.ndarray,
                     dtype: Any = jnp.bfloat16) -> jnp.ndarray:
    """The chunked form (see the module docstring). ``q``, ``k``
    [B, T, H_k, d_k] (a key head serves ``H / H_k`` value heads), ``v``
    [B, T, H, d_v], ``g`` (log decay, <= 0) and ``beta`` [B, T, H]. Returns
    float32 [B, T, H, d_v]."""
    b, t, h, d_v = v.shape
    d_k = k.shape[-1]
    rep = h // k.shape[2]
    if rep * k.shape[2] != h:
        raise ValueError(f"{h} value heads over {k.shape[2]} key heads")
    if rep > 1:
        q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
    pad = -t % CHUNK
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (g, beta))
    n = (t + pad) // CHUNK
    f32 = jnp.float32
    q, k, v = (_heads_first(x, n) for x in (q, k, v))         # [B,H,n,C,d]
    g = _heads_first(g.astype(f32)[..., None], n)[..., 0]     # [B,H,n,C]
    beta = _heads_first(beta.astype(f32)[..., None], n)[..., 0]

    gamma = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    # exp(gamma_i - gamma_j) for i >= j, zero above the diagonal (where the
    # difference is positive and may overflow: masked before the exp)
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None]
                              - gamma[..., None, :], -jnp.inf))
    a = jnp.where(jnp.tril(lower, -1),
                  beta[..., None] * _mm("bhnid,bhnjd->bhnij", k, k, dtype)
                  * decay, 0.0)
    inv = unit_lower_inverse(a)
    k32, e_gamma = k.astype(f32), jnp.exp(gamma)
    w = _mm("bhnij,bhnjd->bhnid", inv, k32 * (beta * e_gamma)[..., None],
            dtype)
    u = _mm("bhnij,bhnjd->bhnid", inv, v.astype(f32) * beta[..., None],
            dtype)
    qk = _mm("bhnid,bhnjd->bhnij", q, k, dtype) * decay
    q_in = q.astype(f32) * e_gamma[..., None]
    k_out = k32 * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    keep = jnp.broadcast_to(jnp.exp(gamma[..., -1])[..., None, None],
                            (b, h, n, 1, d_v))
    # what the kernels only ever read as a product's input waits in ``dtype``
    w, q_in, qk, k_out = (x.astype(dtype) for x in (w, q_in, qk, k_out))

    def rows(x):                       # [B, H, n, ...] -> [B * H, n, ...]
        return x.reshape((b * h,) + x.shape[2:])

    out = chunk_recurrence(
        *map(rows, (w, u, q_in, qk, k_out, keep)),
        _heads_a_step(b * h, d_k, d_v, jnp.dtype(dtype).itemsize),
        _resolve_interpret(None))
    # [B * H, n, C, d_v] -> [B, T, H, d_v]
    out = out.reshape(b, h, n * CHUNK, d_v).transpose(0, 2, 1, 3)
    return out[:, :t]


def gated_delta_recurrent(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          g: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """The recurrence token by token, float32 at the highest precision: what
    :func:`gated_delta_rule` is held to. Same arguments and result."""
    f32 = jnp.float32
    rep = v.shape[2] // k.shape[2]
    if rep > 1:
        q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
    b, _, h, d_v = v.shape

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs                       # [B,H,...]
        state = jnp.exp(g_t)[..., None, None] * state
        seen = jnp.einsum("bhd,bhde->bhe", k_t, state,
                          precision=jax.lax.Precision.HIGHEST)
        write = beta_t[..., None] * (v_t - seen)
        state = state + k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhd,bhde->bhe", q_t, state,
                                 precision=jax.lax.Precision.HIGHEST)

    _, out = jax.lax.scan(
        token, jnp.zeros((b, h, k.shape[-1], d_v), f32),
        tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)
