"""The chunked state-space scan (``ops/ssd.py: ssd_scan``, its two Pallas
kernels interpreted here) against the token-by-token recurrence
(``ssd_recurrent``): values and every gradient in float32 and with bfloat16
products, a ragged length, a state that must be carried over chunks, decays
of -20 a token, groups shared by heads, grid steps narrower than a group,
and what the lowered program holds beside the kernels; and the convolution
that serves both scans (``causal_conv1d``): with a bias and, without one,
bit for bit what ``GatedDeltaNet`` computed before it had the argument."""

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


def inputs(t, seed=0, dt_shift=-2.0, rate=0.3, bsz=B, h=H, g=G):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (bsz, t, h, P)),
            jax.nn.softplus(jax.random.normal(k[1], (bsz, t, h)) + dt_shift),
            -jnp.exp(jax.random.normal(k[2], (h,)) * rate),
            jax.random.normal(k[3], (bsz, t, g, N)),
            jax.random.normal(k[4], (bsz, t, g, N)),
            jax.random.normal(k[5], (h,)))


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
    """The scan's written backward (``ssd_bwd``) against the derivative of
    the token loop, input by input; float32, so the same 1e-4 of the
    gradient's own scale."""
    got, want = both_gradients()
    assert rel(got[which], want[which]) < 1e-4


# name: batch, tokens, heads, groups, heads a grid step (None: what the
# shapes give, the whole group at these sizes). Chunks are 16 tokens.
LAYOUTS = {
    "batch-of-2": (2, 32, 4, 2, None),
    "batch-of-1": (1, 32, 4, 2, None),
    "ragged-backward": (2, 23, 4, 2, None),
    "two-groups-narrow-steps": (2, 40, 4, 2, 1),
    "one-group-of-4": (1, 40, 4, 1, None),
    "one-group-steps-of-2": (1, 40, 4, 1, 2),
    "one-group-steps-of-1": (1, 23, 4, 1, 1),
    "a-head-a-group": (1, 32, 4, 4, None),
}


def layout_inputs(name):
    bsz, t, h, g, _ = LAYOUTS[name]
    return inputs(t, seed=sum(map(ord, name)), bsz=bsz, h=h, g=g)


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_value_and_gradients_in_every_layout(name, monkeypatch):
    """The grid is (batch, heads / hb, chunks): a batch of 2 and of 1, a
    length that is no whole number of chunks THROUGH the backward, one group
    and a group a head, and steps narrower than a group, where ``dB`` and
    ``dC`` leave the kernel as float32 partial sums a step and are added
    outside. Value and all six gradients against the recurrence, float32."""
    hb = LAYOUTS[name][4]
    if hb is not None:
        monkeypatch.setattr(ssd_module, "_heads_a_step", lambda *_: hb)
    args = layout_inputs(name)
    with jax.default_matmul_precision("highest"):
        scan = lambda *a: ssd_scan(*a, jnp.float32)
        assert rel(jax.jit(scan)(*args), jax.jit(ssd_recurrent)(*args)) < 1e-4
        for n, got, want in zip(NAMES, grads(scan, args),
                                grads(ssd_recurrent, args)):
            assert got.shape == want.shape and got.dtype == want.dtype, n
            assert rel(got, want) < 1e-4, n


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_narrow_steps_against_the_whole_group(dtype, monkeypatch):
    """``hb`` is how the work is cut, not what is computed: steps of one
    head against the whole group of four, value and gradients. A head's own
    results see nothing of the others (equal to float32 rounding: the CPU
    fuses the two shapes differently); ``dB`` and ``dC`` are sums over the
    group's heads, which narrow steps leave as a partial sum a step, each
    product's cotangent rounded to ``dtype`` before it, where the whole
    group rounds their sum once."""
    args = layout_inputs("one-group-of-4")
    scan = lambda *a: ssd_scan(*a, dtype)
    assert ssd_module._heads_a_step(4, P, N, 2) == 4
    whole = (jax.jit(scan)(*args), *grads(scan, args))
    monkeypatch.setattr(ssd_module, "_heads_a_step", lambda *_: 1)
    narrow = (jax.jit(scan)(*args), *grads(scan, args))
    for n, a, b in zip(("y",) + NAMES, narrow, whole):
        shared = n in ("b", "c") and dtype == jnp.bfloat16
        assert rel(a, b) < (1e-2 if shared else 1e-5), n


def test_heads_a_step_is_a_divisor_that_fits(monkeypatch):
    """The whole group at the published sizes; under a tighter budget the
    largest divisor of the group whose tiles fit, and never less than one
    head."""
    monkeypatch.setattr(ssd_module, "CHUNK", 128)
    assert ssd_module._heads_a_step(8, 64, 128, 2) == 8
    monkeypatch.setattr(ssd_module, "_TILE_BYTES", 2 << 20)
    assert ssd_module._heads_a_step(8, 64, 128, 2) == 4
    assert ssd_module._heads_a_step(10, 64, 128, 2) == 5
    monkeypatch.setattr(ssd_module, "_TILE_BYTES", 1 << 10)
    assert ssd_module._heads_a_step(8, 64, 128, 2) == 1


def test_the_lowered_gradient_holds_two_kernels_and_no_triangle(monkeypatch):
    """The jitted gradient lowered for a TPU (nothing runs; chunks of 128,
    the mixer's layouts: ``x`` a ``[B, T, H P]`` array, ``B`` and ``C``
    ``[B, T, G N]``): two Mosaic calls, ``ssd_fwd`` and ``ssd_bwd``; outside
    them no ``[.., 128, 128]`` triangle, no per-chunk state but the one the
    forward hands the backward, and no transpose of anything larger than
    the ``[B, T, H]`` rows of ``Delta`` and ``gamma``: ``x``, ``y``, ``B``
    and ``C`` go in and out as they lie."""
    import re

    monkeypatch.setattr(ssd_module, "CHUNK", 128)
    monkeypatch.setattr(ssd_module, "_resolve_interpret", lambda _: False)
    bsz, t, h, p, g, n = 2, 256, 4, 64, 2, 128
    f32, bf16 = jnp.float32, jnp.bfloat16

    def loss(x, dt, a, b, c, d):
        return ssd_scan(x.reshape(bsz, t, h, p), dt, a,
                        b.reshape(bsz, t, g, n), c.reshape(bsz, t, g, n),
                        d).sum()

    shapes = [jax.ShapeDtypeStruct(s, dt) for s, dt in (
        ((bsz, t, h * p), bf16), ((bsz, t, h), f32), ((h,), f32),
        ((bsz, t, g * n), bf16), ((bsz, t, g * n), bf16), ((h,), f32))]
    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).trace(
        *shapes).lower(lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2
    assert "ssd_fwd" in calls[0] and "ssd_bwd" in calls[1]
    assert "x128x128x" not in text                    # no triangle
    states = f"tensor<{bsz}x{t // 128}x{h * p}x{n}xf32>"
    assert all(states in line for line in calls)      # written, then read
    # and nothing else computes on it: the other lines that name it hand it
    # from the forward sweep's function to the backward's
    for line in text.splitlines():
        if states in line and "tpu_custom_call" not in line:
            assert "stablehlo." not in line, line
    transposed = re.findall(r"stablehlo\.transpose.*: \(tensor<([\dx]+)xf32>\)",
                            text)
    assert transposed and len(transposed) == text.count("stablehlo.transpose")
    for shape in transposed:
        assert np.prod([int(s) for s in shape.split("x")]) <= bsz * t * h


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
