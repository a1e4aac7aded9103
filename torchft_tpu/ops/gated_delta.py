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
only the last three lines are sequential, one ``lax.scan`` over the
``T / CHUNK`` chunks. Decays, ``gamma``, the inverse and the carried state
are float32; the products take ``dtype`` inputs (bfloat16 in training) and
accumulate in float32. The backward is the scan's own derivative: the
per-chunk states are its residuals (``[T / CHUNK, B, H, d_k, d_v]``
float32), which a caller that lacks the room recomputes by wrapping the
call in ``jax.checkpoint`` (``models/linear_attention.py`` does).

A sequence that is not a whole number of chunks is padded at its end with
tokens that neither decay nor write (``g = 0``, ``beta = 0``).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

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


def gated_delta_rule(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     g: jnp.ndarray, beta: jnp.ndarray,
                     dtype: Any = jnp.bfloat16) -> jnp.ndarray:
    """The chunked form (see the module docstring). ``q``, ``k``
    [B, T, H_k, d_k] (a key head serves ``H / H_k`` value heads), ``v``
    [B, T, H, d_v], ``g`` (log decay, <= 0) and ``beta`` [B, T, H]. Returns
    float32 [B, T, H, d_v]."""
    b, t, h, d_v = v.shape
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
    eye = jnp.eye(CHUNK, dtype=f32)
    inv = jax.lax.linalg.triangular_solve(
        eye + a, jnp.broadcast_to(eye, a.shape), left_side=True, lower=True,
        unit_diagonal=True)
    k32, e_gamma = k.astype(f32), jnp.exp(gamma)
    w = _mm("bhnij,bhnjd->bhnid", inv, k32 * (beta * e_gamma)[..., None],
            dtype)
    u = _mm("bhnij,bhnjd->bhnid", inv, v.astype(f32) * beta[..., None],
            dtype)
    qk = _mm("bhnid,bhnjd->bhnij", q, k, dtype) * decay
    q_in = q.astype(f32) * e_gamma[..., None]
    k_out = k32 * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    keep = jnp.exp(gamma[..., -1])                            # [B,H,n]
    # what the scan only ever reads as a product's input waits in ``dtype``
    w, q_in, qk, k_out = (x.astype(dtype) for x in (w, q_in, qk, k_out))

    def chunk(state, xs):
        w_c, u_c, q_c, qk_c, k_c, keep_c = xs
        v_new = u_c - _mm("bhcd,bhde->bhce", w_c, state, dtype)
        out = _mm("bhcd,bhde->bhce", q_c, state, dtype) \
            + _mm("bhij,bhje->bhie", qk_c, v_new, dtype)
        state = keep_c[..., None, None] * state \
            + _mm("bhcd,bhce->bhde", k_c, v_new, dtype)
        return state, out

    def chunks_first(x):
        return jnp.moveaxis(x, 2, 0)

    _, out = jax.lax.scan(
        chunk, jnp.zeros((b, h, k.shape[-1], d_v), f32),
        tuple(map(chunks_first, (w, u, q_in, qk, k_out, keep))))
    # [n, B, H, C, d_v] -> [B, T, H, d_v]
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, n * CHUNK, h, d_v)
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
