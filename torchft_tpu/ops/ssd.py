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
``gamma_i`` the running sum of ``Delta A`` inside a chunk (``<= 0``) and
``Gamma_c`` the running sum of the chunks' totals:

    Y_diag   = (tril(exp(gamma_i - gamma_j)) * (C B^T)) (Delta * X)
    states_c = sum_j exp(gamma_Q - gamma_j) Delta_j x_j B_j^T
    S_c      = sum_{c' < c} exp(Gamma_{c-1} - Gamma_{c'}) states_{c'}
    Y_off    = exp(gamma_i) C_i S_c

The recurrence is linear in the state, so unlike the delta rule
(``ops/gated_delta.py``: a triangular inverse a chunk and one ``lax.scan``
over the chunks) nothing here is sequential: the pass between chunks is ONE
batched product of the ``[chunks, chunks]`` strictly lower-triangular decay
matrix with the chunks' states (64 x 64 at 8,192 tokens), and the whole
scan is a handful of large batched products, forward and backward. Every
exponent taken is of a number ``<= 0`` (the triangles are masked before the
``exp``). ``Delta``, ``gamma``, the decays and the states are float32 (the
product between chunks takes the float32 states at the highest precision);
the other products take ``dtype`` inputs (bfloat16 in training) and
accumulate in float32. The backward is the derivative of these products;
what a caller that lacks the room for their residuals does about it is in
``models/mamba2.py``.

A sequence that is not a whole number of chunks is padded at its end with
tokens of step zero, which neither decay nor write.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from torchft_tpu.ops.gated_delta import _mm

CHUNK = 128   # tokens a chunk (the published kernel's): per-chunk states
#               are [T / 128, H, P, N], the triangles [T / 128, H, 128, 128]


def _grouped(h: int, g: int) -> int:
    if g <= 0 or h % g:
        raise ValueError(f"{h} heads over {g} groups")
    return h // g


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b_in: jnp.ndarray, c_in: jnp.ndarray, d: jnp.ndarray,
             dtype: Any = jnp.bfloat16) -> jnp.ndarray:
    """The chunked form (see the module docstring). ``x`` [B, T, H, P],
    ``dt`` [B, T, H] (the step ``Delta``, after its softplus), ``a`` [H]
    (negative), ``b_in`` and ``c_in`` [B, T, G, N], ``d`` [H]. Returns
    float32 [B, T, H, P]."""
    bsz, t, h, p = x.shape
    g, n_state = b_in.shape[2], b_in.shape[3]
    k = _grouped(h, g)
    pad = -t % CHUNK
    if pad:
        x, b_in, c_in = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                         for v in (x, b_in, c_in))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    n = (t + pad) // CHUNK
    f32 = jnp.float32
    # heads as [group, head in group]; chunks as [chunk, token in chunk]
    xh = x.reshape(bsz, n, CHUNK, g, k, p).transpose(0, 3, 4, 1, 2, 5)
    dth = dt.astype(f32).reshape(bsz, n, CHUNK, g, k).transpose(0, 3, 4, 1, 2)
    bh = b_in.reshape(bsz, n, CHUNK, g, n_state).transpose(0, 3, 1, 2, 4)
    ch = c_in.reshape(bsz, n, CHUNK, g, n_state).transpose(0, 3, 1, 2, 4)
    a_gk = a.astype(f32).reshape(g, k)
    d_gk = d.astype(f32).reshape(g, k)

    gamma = jnp.cumsum(dth * a_gk[None, :, :, None, None], axis=-1)
    lower = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    # exp(gamma_i - gamma_j) for i >= j, zero above the diagonal (where the
    # difference is positive and may overflow: masked before the exp)
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None]
                              - gamma[..., None, :], -jnp.inf))
    cb = _mm("bgnis,bgnjs->bgnij", ch, bh, dtype)
    x_dt = xh.astype(f32) * dth[..., None]
    y = _mm("bgknij,bgknjp->bgknip", cb[:, :, None] * decay, x_dt, dtype)

    # what each chunk leaves behind, [B, G, K, n, P, N], and what enters
    # each: one product over the chunks' strictly lower triangle
    to_end = jnp.exp(gamma[..., -1:] - gamma)
    states = _mm("bgknqp,bgnqs->bgknps", x_dt * to_end[..., None], bh, dtype)
    total = gamma[..., -1]                                    # [B,G,K,n]
    upto = jnp.cumsum(total, axis=-1)
    carry = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((n, n), bool), -1),
        (upto - total)[..., :, None] - upto[..., None, :], -jnp.inf))
    entering = jnp.einsum("bgkcz,bgkzps->bgkcps", carry, states,
                          precision=jax.lax.Precision.HIGHEST)
    y = y + _mm("bgnqs,bgknps->bgknqp", ch, entering, dtype) \
        * jnp.exp(gamma)[..., None]
    y = y + d_gk[None, :, :, None, None, None] * xh.astype(f32)
    # [B, G, K, n, Q, P] -> [B, T, H, P]
    y = y.transpose(0, 3, 4, 1, 2, 5).reshape(bsz, n * CHUNK, h, p)
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
