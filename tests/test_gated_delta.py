"""The gated delta rule (``ops/gated_delta.py``): the chunked form (its
recurrence two Pallas kernels, interpreted here) against the token-by-token
recurrence, outputs and every gradient, at lengths of 1, 1.5 and 4 chunks
with the decay near 1 and near 0; the bfloat16 recipe to a written
tolerance; key heads shared by value heads; the kernels against the
``lax.scan`` they replaced and its derivative; the chunk inverse's kernel
against ``triangular_solve``; the causal short convolution against an explicit
shifted sum."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu import tracing
from torchft_tpu.ops import (causal_conv1d, gated_delta_recurrent,
                             gated_delta_rule)
from torchft_tpu.ops.gated_delta import (CHUNK, _mm, chunk_recurrence,
                                         unit_lower_inverse)

NAMES = ("q", "k", "v", "g", "beta")
LENGTHS = {"1_chunk": CHUNK, "1.5_chunks": CHUNK + CHUNK // 2,
           "4_chunks": 4 * CHUNK}
# where the pre-softplus input of the decay sits: -5 gives alpha about
# 0.993 a token (the state carries across every chunk), +2 alpha about 0.12
# (the state has forgotten a chunk's start well before its end)
DECAYS = {"alpha_near_1": -5.0, "alpha_near_0": 2.0}


def inputs(t, decay, seed=0, b=2, hk=2, h=4, dk=16, dv=8):
    """What the layer hands the rule: unit keys, unit queries times
    ``dk^-1/2``, ``g <= 0``, ``beta`` in (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, t, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, hk, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)) + decay)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def chunked_f32(*args):
    return gated_delta_rule(*args, dtype=jnp.float32)


@pytest.mark.parametrize("decay", list(DECAYS), ids=list(DECAYS))
@pytest.mark.parametrize("length", list(LENGTHS), ids=list(LENGTHS))
def test_chunked_outputs_equal_the_recurrence_in_float32(length, decay):
    args = inputs(LENGTHS[length], DECAYS[decay])
    alpha = float(jnp.mean(jnp.exp(args[3])))
    assert alpha > 0.98 if decay == "alpha_near_1" else alpha < 0.2
    got, want = chunked_f32(*args), gated_delta_recurrent(*args)
    assert got.shape == want.shape == args[2].shape
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1     # not a comparison of zeros


@pytest.mark.parametrize("decay", list(DECAYS), ids=list(DECAYS))
@pytest.mark.parametrize("length", list(LENGTHS), ids=list(LENGTHS))
def test_chunked_gradients_equal_the_recurrences_in_float32(length, decay):
    """Every input's gradient under a random cotangent, to 1e-5 of the
    recurrence's own largest entry."""
    args = inputs(LENGTHS[length], DECAYS[decay], seed=1)
    ct = jax.random.normal(jax.random.key(9), args[2].shape)
    got = jax.grad(lambda *a: jnp.sum(chunked_f32(*a) * ct),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(gated_delta_recurrent(*a) * ct),
                    argnums=range(5))(*args)
    for name, a, b in zip(NAMES, got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        np.testing.assert_allclose(a / scale, b / scale, atol=1e-5,
                                   err_msg=name)


@pytest.mark.parametrize("decay", list(DECAYS), ids=list(DECAYS))
@pytest.mark.parametrize("length", list(LENGTHS), ids=list(LENGTHS))
def test_the_bfloat16_recipe_stays_within_its_tolerance(length, decay):
    """Product inputs bfloat16 (8 bits of mantissa: 2^-9 = 0.002 relative a
    rounding), accumulation, decays, inverse and state float32: outputs
    within 0.02 and gradients within 0.05 of the float32 recurrence's
    largest entry. The same inputs with the state's products in float32
    read 1e-6 (above), so the distance is the rounding of the inputs."""
    args = inputs(LENGTHS[length], DECAYS[decay], seed=2)
    want = gated_delta_recurrent(*args)
    got = gated_delta_rule(*args)
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got - want))) / scale
    assert 1e-5 < err < 0.02
    ct = jax.random.normal(jax.random.key(9), want.shape)
    g_got = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a) * ct),
                     argnums=range(5))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(gated_delta_recurrent(*a) * ct),
                      argnums=range(5))(*args)
    for name, a, b in zip(NAMES, g_got, g_want):
        assert float(jnp.max(jnp.abs(a - b))) \
            < 0.05 * float(jnp.max(jnp.abs(b))), name


def test_a_carried_state_is_what_joins_the_chunks():
    """With the decay near 1 the second chunk's outputs depend on the first
    chunk's keys and values; run alone, the second chunk gives another
    answer. (What the benchmark's ``no_carry`` control removes.)"""
    args = inputs(2 * CHUNK, DECAYS["alpha_near_1"], seed=3)
    whole = chunked_f32(*args)[:, CHUNK:]
    alone = chunked_f32(*(x[:, CHUNK:] for x in args))
    assert float(jnp.max(jnp.abs(whole - alone))) > 0.05
    # and with the decay near 0 it hardly does
    args = inputs(2 * CHUNK, 6.0, seed=3)
    whole = chunked_f32(*args)[:, CHUNK:]
    alone = chunked_f32(*(x[:, CHUNK:] for x in args))
    assert float(jnp.max(jnp.abs(whole[:, 8:] - alone[:, 8:]))) < 1e-5


def test_a_ragged_last_chunk_is_padded_with_tokens_that_do_nothing():
    """A length that is no multiple of the chunk gives the same rows as the
    longer sequence it is the start of (causal), in outputs and gradients."""
    args = inputs(2 * CHUNK, DECAYS["alpha_near_1"], seed=4)
    cut = CHUNK + 7
    short = tuple(x[:, :cut] for x in args)
    np.testing.assert_allclose(chunked_f32(*short),
                               chunked_f32(*args)[:, :cut], atol=1e-6)
    g = jax.grad(lambda v: jnp.sum(chunked_f32(*short[:2], v, *short[3:])))(
        short[2])
    assert g.shape == short[2].shape and bool(jnp.all(jnp.isfinite(g)))


def test_key_heads_are_shared_by_their_value_heads():
    q, k, v, g, beta = inputs(CHUNK, DECAYS["alpha_near_1"], seed=5)
    rep = v.shape[2] // k.shape[2]
    assert rep == 2
    got = chunked_f32(q, k, v, g, beta)
    want = chunked_f32(jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v, g,
                       beta)
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="value heads"):
        chunked_f32(q, k, v[:, :, :3], g[..., :3], beta[..., :3])


def test_no_overflow_where_the_decay_is_strong():
    """exp(gamma_i - gamma_j) is masked before the exp above the diagonal,
    where the difference is large and positive."""
    q, k, v, g, beta = inputs(2 * CHUNK, 0.0, seed=6)
    g = jnp.full_like(g, -3.6)                  # the harness's seeding
    out = gated_delta_rule(q, k, v, g, beta)
    grads = jax.grad(lambda g_: jnp.sum(gated_delta_rule(q, k, v, g_, beta)))(
        g)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert bool(jnp.all(jnp.isfinite(grads)))
    np.testing.assert_allclose(out, gated_delta_recurrent(q, k, v, g, beta),
                               atol=5e-3)


# ------------------------------------------------- the kernels alone

def scan_recurrence(w, u, q_in, qk, k_out, keep, dtype=jnp.float32):
    """The plain form of the rule's sequential part, as ``gated_delta_rule``
    ran it before the kernels: one ``lax.scan`` over the chunks, arguments
    [BH, n, CHUNK, ...] and ``keep`` [BH, n]."""
    def chunk(state, xs):
        w_c, u_c, q_c, qk_c, k_c, keep_c = xs
        v_new = u_c - _mm("hcd,hde->hce", w_c, state, dtype)
        out = _mm("hcd,hde->hce", q_c, state, dtype) \
            + _mm("hij,hje->hie", qk_c, v_new, dtype)
        state = keep_c[..., None, None] * state \
            + _mm("hcd,hce->hde", k_c, v_new, dtype)
        return state, out

    _, out = jax.lax.scan(
        chunk, jnp.zeros((w.shape[0], w.shape[-1], u.shape[-1])),
        tuple(jnp.moveaxis(x, 1, 0) for x in (w, u, q_in, qk, k_out, keep)))
    return jnp.moveaxis(out, 0, 1)


@pytest.mark.parametrize("chunks", [3, 5], ids=["3_chunks", "5_chunks"])
@pytest.mark.parametrize("hb", [1, 4], ids=["hb1", "hb4"])
def test_the_kernels_are_the_scan_and_its_derivative(hb, chunks):
    """``gdn_fwd`` against the scan body and ``gdn_bwd`` against
    ``jax.vjp`` of it, all six cotangents, in float32 on inputs of the
    sizes the chunk products hand over."""
    bh, d_k, d_v = 4, 16, 8
    ks = jax.random.split(jax.random.key(11), 7)
    w, q_in, k_out = (0.3 * jax.random.normal(k, (bh, chunks, CHUNK, d_k))
                      for k in ks[:3])
    u, do = (jax.random.normal(k, (bh, chunks, CHUNK, d_v))
             for k in ks[3:5])
    qk = 0.3 * jax.random.normal(ks[5], (bh, chunks, CHUNK, CHUNK))
    keep = jax.random.uniform(ks[6], (bh, chunks), minval=0.2, maxval=1.0)

    def kernels(w, u, q_in, qk, k_out, keep):
        wide = jnp.broadcast_to(keep[..., None, None],
                                (bh, chunks, 1, d_v))
        return chunk_recurrence(w, u, q_in, qk, k_out, wide, hb, True)

    args = (w, u, q_in, qk, k_out, keep)
    got, pull = jax.vjp(kernels, *args)
    want, want_pull = jax.vjp(scan_recurrence, *args)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.max(jnp.abs(want))) > 1
    for name, a, b in zip(("w", "u", "q_in", "qk", "k_out", "keep"),
                          pull(do), want_pull(do)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0.1, name
        np.testing.assert_allclose(a / scale, b / scale, atol=1e-5,
                                   err_msg=name)


def _keys_that_repeat(key):
    """beta k_i . k_j with one key all along the chunk, beta near 1: every
    entry near 1, the inverse near bidiagonal."""
    beta = jax.random.uniform(key, (8, CHUNK, 1), minval=0.9, maxval=1.0)
    return jnp.broadcast_to(beta, (8, CHUNK, CHUNK))


MATRICES = {
    "signed": lambda k: jax.random.uniform(k, (8, CHUNK, CHUNK), minval=-1,
                                           maxval=1),
    "positive": lambda k: jax.random.uniform(k, (8, CHUNK, CHUNK)),
    "keys_that_repeat": _keys_that_repeat,
}


@pytest.mark.parametrize("kind", list(MATRICES), ids=list(MATRICES))
def test_the_chunk_inverse_equals_substitution(kind):
    """``(I + A)^-1`` on seeded strictly lower ``A`` with entries up to 1,
    by the kernel that substitutes across the lanes, and its pullback by
    products: within 1e-5 of ``triangular_solve``'s relative to the largest
    entry. (The product ``(I - A)(I + A^2)(I + A^4)...`` reads 24 and 145
    on the last two kinds in float32: its terms grow like binomial
    coefficients before they cancel.)"""
    a = jnp.tril(MATRICES[kind](jax.random.key(21)), -1)
    assert 0.9 < float(jnp.max(jnp.abs(a))) <= 1
    eye = jnp.eye(CHUNK)

    def solve(a):
        return jax.lax.linalg.triangular_solve(
            eye + a, jnp.broadcast_to(eye, a.shape), left_side=True,
            lower=True, unit_diagonal=True)

    got, pull = jax.vjp(unit_lower_inverse, a)
    want, want_pull = jax.vjp(solve, a)
    scale = jnp.max(jnp.abs(want), axis=(-1, -2), keepdims=True)
    assert float(jnp.max(jnp.abs(got - want) / scale)) < 1e-5
    np.testing.assert_array_equal(jnp.triu(got, 1), 0)
    ct = jax.random.normal(jax.random.key(22), a.shape)
    d, d_want = jnp.tril(pull(ct)[0], -1), jnp.tril(want_pull(ct)[0], -1)
    scale = jnp.max(jnp.abs(d_want), axis=(-1, -2), keepdims=True)
    assert float(jnp.max(jnp.abs(d - d_want) / scale)) < 1e-5


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_a_traced_kernel_counts_itself_and_its_grid_steps(what):
    """The cell's call, traced and never run: 32 heads in steps of 16 over
    128 chunks are 256 grid steps a sweep; a gradient traces the forward
    that keeps the states and the backward."""
    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    args = (shaped(1, 8192, 16, 128), shaped(1, 8192, 16, 128),
            shaped(1, 8192, 32, 128), shaped(1, 8192, 32, dtype=jnp.float32),
            shaped(1, 8192, 32, dtype=jnp.float32))

    def loss(*a):
        return gated_delta_rule(*a).sum()

    def counters():
        c = tracing.program_counters()
        return [c.get("gdn_kernel_traces_total", 0),
                c.get("gdn_kernel_grid_steps_traced_total", 0)]

    before = counters()
    sweeps = 1
    if what == "forward":
        jax.eval_shape(loss, *args)
    else:
        jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *args)
        sweeps = 2
    assert [a - b for a, b in zip(counters(), before)] \
        == [sweeps, sweeps * 256]


# ------------------------------------------------- the short convolution

@pytest.mark.parametrize("kernel", [4, 1, 3], ids=["k4", "k1", "k3"])
def test_causal_convolution_is_the_explicit_shifted_sum(kernel):
    """y[t] = sum_j w[j] * x[t - K + 1 + j], zeros left of the sequence,
    written as loops."""
    b, t, ch = 2, 11, 6
    x = np.asarray(jax.random.normal(jax.random.key(0), (b, t, ch)))
    w = np.asarray(jax.random.normal(jax.random.key(1), (kernel, ch)))
    want = np.zeros_like(x)
    for step in range(t):
        for j in range(kernel):
            src = step - kernel + 1 + j
            if src >= 0:
                want[:, step] += w[j] * x[:, src]
    got = causal_conv1d(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(got, want, atol=1e-6)
    # causal: a later input changes no earlier output
    x2 = x.copy()
    x2[:, 7] += 1.0
    again = causal_conv1d(jnp.asarray(x2), jnp.asarray(w))
    np.testing.assert_array_equal(again[:, :7], got[:, :7])
    assert float(jnp.max(jnp.abs(again[:, 7] - got[:, 7]))) > 0


def test_causal_convolution_keeps_the_inputs_type_and_differentiates():
    x = jax.random.normal(jax.random.key(0), (1, 9, 4)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (4, 4))
    assert causal_conv1d(x, w).dtype == jnp.bfloat16
    x32 = x.astype(jnp.float32)
    dw = jax.grad(lambda w_: jnp.sum(causal_conv1d(x32, w_)))(w)
    # d/dw[j] of the sum is the sum of the inputs the tap j reads
    want = jnp.stack([jnp.sum(x32[:, : 9 - (3 - j)], axis=(0, 1))
                      for j in range(4)])
    np.testing.assert_allclose(dw, want, atol=1e-5)
