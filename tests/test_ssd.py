"""The chunked state-space scan (``ops/ssd.py: ssd_scan``) against the
token-by-token recurrence (``ssd_recurrent``): values and every gradient in
float32 and with bfloat16 products, a ragged length, a state that must be
carried over chunks, decays of -20 a token, groups shared by heads; and the
convolution that serves both scans (``causal_conv1d``): with a bias and,
without one, bit for bit what ``GatedDeltaNet`` computed before it had the
argument."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _gdn_golden
from torchft_tpu.ops import causal_conv1d, ssd_recurrent, ssd_scan
from torchft_tpu.ops import ssd as ssd_module

B, H, P, G, N = 2, 4, 8, 2, 16
NAMES = ("x", "dt", "a", "b", "c", "d")


@pytest.fixture(autouse=True)
def small_chunk(monkeypatch):
    """Chunks of 16 tokens: several chunks at a length a CPU test affords.
    Nothing in the scan depends on the chunk's size but its shapes."""
    monkeypatch.setattr(ssd_module, "CHUNK", 16)


def inputs(t, seed=0, dt_shift=-2.0, rate=0.3):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (B, t, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, t, H)) + dt_shift),
            -jnp.exp(jax.random.normal(k[2], (H,)) * rate),
            jax.random.normal(k[3], (B, t, G, N)),
            jax.random.normal(k[4], (B, t, G, N)),
            jax.random.normal(k[5], (H,)))


def grads(fn, args):
    return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                            argnums=tuple(range(6))))(*args)


@functools.lru_cache(maxsize=None)
def both_gradients():
    """Every input's gradient, chunked and token by token, computed once
    for the six cases that read them."""
    args = inputs(40, seed=1)
    with jax.default_matmul_precision("highest"):
        return (grads(lambda *a: ssd_scan(*a, jnp.float32), args),
                grads(ssd_recurrent, args))


def rel(got, want):
    """rms(got - want) / rms(want): an error against the value's own scale,
    as the benchmark's ``grad_distance`` reads a leaf."""
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                          / jnp.mean(want ** 2)))


# 64: whole chunks; 40: two and a half (padded with tokens of step zero);
# 7: less than one chunk
@pytest.mark.parametrize("t", [64, 40, 7], ids=["whole", "ragged", "short"])
def test_chunked_scan_against_the_recurrence_float32(t):
    """float32 products at the highest precision on both sides: the two
    differ only in the order of float32 sums (a chunk's triangle against a
    running state), 1e-5 of the value's scale here; 1e-4 leaves room for
    another seed and would not pass a dropped term (the carry alone is
    tens of percent, below)."""
    args = inputs(t)
    want = jax.jit(ssd_recurrent)(*args)
    got = jax.jit(lambda *a: ssd_scan(*a, jnp.float32))(*args)
    assert got.shape == want.shape == (B, t, H, P)
    assert got.dtype == jnp.float32
    assert rel(got, want) < 1e-4


@pytest.mark.parametrize("which", range(6), ids=NAMES)
def test_every_gradient_against_the_recurrence_float32(which):
    """The scan's derivative (of the batched products) against the
    derivative of the token loop, input by input; float32, so the same
    1e-4 of the gradient's own scale."""
    got, want = both_gradients()
    assert rel(got[which], want[which]) < 1e-4


def test_bfloat16_products_stay_near_the_recurrence():
    """Products with bfloat16 inputs (eight bits of mantissa: 0.4 % an
    input) and float32 sums, decays and states: the value within 1 % and
    every gradient within 3 % of the float32 recurrence's, by rms. Read
    here at 0.3 % and 0.3-1.0 %; a float8 input (3 bits) reads 6 % and
    more, a bfloat16 state or decay would show in ``dt`` and ``a`` first."""
    args = inputs(64, seed=2)
    # jitted: the CPU's eager dot has no bfloat16 x bfloat16 -> float32
    low = jax.jit(lambda *a: ssd_scan(*a, jnp.bfloat16))
    assert rel(low(*args), jax.jit(ssd_recurrent)(*args)) < 1e-2
    got = grads(low, args)
    want = grads(ssd_recurrent, args)
    for name, g, w in zip(NAMES, got, want):
        assert rel(g, w) < 3e-2, name


def test_the_state_is_carried_across_chunks():
    """Slow decay (a step of about 0.007, as the benchmark seeds it): what
    the first chunk wrote is most of what the fourth reads. The scan over
    the whole sequence is the recurrence; the scan run chunk by chunk with
    no state between them (a dropped carry) is far from it, so the first
    assertion cannot pass by the carry being small."""
    args = inputs(64, seed=3, dt_shift=-5.0, rate=0.0)
    x, dt, a, b, c, d = args
    scan = jax.jit(lambda *a_: ssd_scan(*a_, jnp.float32))
    want = jax.jit(ssd_recurrent)(*args)
    got = scan(*args)
    dropped = jnp.concatenate([
        scan(x[:, i:i + 16], dt[:, i:i + 16], a, b[:, i:i + 16],
             c[:, i:i + 16], d)
        for i in range(0, 64, 16)], axis=1)
    assert rel(got, want) < 1e-4
    np.testing.assert_allclose(dropped[:, :16], want[:, :16], atol=1e-4)
    assert rel(dropped[:, 16:], want[:, 16:]) > 0.3


def test_decays_of_minus_twenty_a_token_stay_finite():
    """``Delta A`` = -20 a token: gamma reaches -320 inside a chunk and
    -1280 over the sequence, the upper triangles' differences +300 and
    more. Every exponent taken is of a number <= 0, so nothing overflows,
    forward or backward, and the state is forgotten at once: each token
    reads its own write only."""
    x, _, _, b, c, d = inputs(64, seed=4)
    dt = jnp.full((B, 64, H), 20.0)
    a = -jnp.ones((H,))
    args = (x, dt, a, b, c, d)
    got = jax.jit(lambda *a_: ssd_scan(*a_, jnp.float32))(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    for g in grads(lambda *a_: ssd_scan(*a_, jnp.float32), args):
        assert bool(jnp.all(jnp.isfinite(g)))
    bc = jnp.repeat(jnp.sum(b * c, -1), H // G, axis=-1)     # [B, T, H]
    own = (dt * bc)[..., None] * x + d[:, None] * x
    np.testing.assert_allclose(got, own, rtol=1e-4, atol=1e-3)


def test_heads_of_a_group_share_b_and_c():
    """Head ``h`` reads group ``h // (H / G)``: the scan with B and C given
    a group is the scan with them repeated a head (G = H); another
    group's B moves only that group's heads."""
    args = inputs(40, seed=5)
    x, dt, a, b, c, d = args
    scan = jax.jit(lambda *a_: ssd_scan(*a_, jnp.float32))
    per_head = scan(x, dt, a, jnp.repeat(b, H // G, axis=2),
                    jnp.repeat(c, H // G, axis=2), d)
    got = scan(*args)
    np.testing.assert_allclose(got, per_head, atol=1e-5)
    moved = scan(x, dt, a, b.at[:, :, 1].add(1.0), c, d)
    np.testing.assert_array_equal(moved[:, :, :H // G], got[:, :, :H // G])
    assert rel(moved[:, :, H // G:], got[:, :, H // G:]) > 0.1
    with pytest.raises(ValueError, match="heads over"):
        ssd_scan(x, dt, a, b[:, :, :1].repeat(3, axis=2),
                 c[:, :, :1].repeat(3, axis=2), d)


def test_the_skip_term_and_the_zero_state():
    """With B = 0 nothing is ever written: the output is ``D x`` alone."""
    x, dt, a, b, c, d = inputs(40, seed=6)
    got = jax.jit(lambda *a_: ssd_scan(*a_, jnp.float32))(
        x, dt, a, jnp.zeros_like(b), c, d)
    np.testing.assert_allclose(got, d[:, None] * x, atol=1e-6)


# ------------------------------------------------------- the convolution

def test_convolution_with_a_bias():
    """``y_t = sum_j w_j x_{t-K+1+j} + bias``, zeros left of the sequence:
    against the sum written out, and the bias is added once a token (not a
    tap)."""
    k = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(k[0], (2, 10, 6))
    w = jax.random.normal(k[1], (4, 6))
    bias = jax.random.normal(k[2], (6,))
    want = np.zeros((2, 10, 6), np.float32)
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(w[j] * x[:, t - 3 + j])
    np.testing.assert_allclose(causal_conv1d(x, w), want, atol=1e-6)
    np.testing.assert_allclose(causal_conv1d(x, w, bias), want + bias,
                               atol=1e-6)
    got = causal_conv1d(x.astype(jnp.bfloat16), w, bias)
    assert got.dtype == jnp.bfloat16


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_deltanet_is_bitwise_what_it_was_before_the_bias(dtype):
    """``causal_conv1d`` serves both scans; called without a bias it is the
    same function of the same inputs: ``GatedDeltaNet``'s output and every
    gradient at a small size, bit for bit what PR 49's tree computed on the
    CPU (``tests/golden_gdn_pr49.json``, made by ``tests/_gdn_golden.py``
    there: the rule's kernels interpreted; the file before it,
    ``golden_gdn_pr44.json``, held the ``lax.scan`` the kernels replaced)."""
    with open(os.path.join(os.path.dirname(__file__),
                           "golden_gdn_pr49.json")) as f:
        golden = json.load(f)[dtype]
    assert _gdn_golden.digests(dtype) == golden
