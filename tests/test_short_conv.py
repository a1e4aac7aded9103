"""The gated short convolution (``models/short_conv.py``, LFM2's "conv"
operator) against a token-by-token float32 loop written here: forward and
gradients at kernels of 3 and 4, a sequence shorter than the kernel, the
order of the three streams, and the two numbers it hands the program
counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import ShortConv
from torchft_tpu.models.short_conv import SCONV_COUNTERS
from torchft_tpu.models.transformer import TransformerConfig

E = 16


def _cfg(kernel, dtype=jnp.float32):
    return TransformerConfig(embed_dim=E, num_heads=2, dtype=dtype,
                             linear_conv_kernel=kernel)


def _params(kernel, seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    return {"in_proj": {"kernel": 0.3 * jax.random.normal(k[0], (E, 3 * E))},
            "conv": 0.5 * jax.random.normal(k[1], (kernel, E)),
            "out_proj": {"kernel": 0.3 * jax.random.normal(k[2], (E, E))}}


def gated_by_token(params, x):
    """``y_t = C_t * sum_j k_j h_{t-K+1+j}`` with ``h = B * z``, one token
    and one tap at a time; nothing before the sequence."""
    taps = params["conv"]
    kernel = taps.shape[0]
    with jax.default_matmul_precision("highest"):
        bcz = x @ params["in_proj"]["kernel"]
    b_gate, c_gate, z = bcz[..., :E], bcz[..., E:2 * E], bcz[..., 2 * E:]
    h = b_gate * z
    rows = []
    for t in range(x.shape[1]):
        c = jnp.zeros_like(h[:, 0])
        for j in range(kernel):
            src = t - kernel + 1 + j
            if src >= 0:
                c = c + taps[j] * h[:, src]
        rows.append(c_gate[:, t] * c)
    return jnp.stack(rows, axis=1)


def by_token(params, x):
    """The mixer's output by the loop: ``y W_out``."""
    with jax.default_matmul_precision("highest"):
        return gated_by_token(params, x) @ params["out_proj"]["kernel"]


@pytest.mark.parametrize("kernel,seq", [(3, 12), (4, 12), (3, 2), (4, 3),
                                        (3, 1)],
                         ids=["k3", "k4", "k3_seq2", "k4_seq3", "k3_seq1"])
def test_forward_and_gradients_against_the_loop(kernel, seq):
    params = _params(kernel)
    x = jax.random.normal(jax.random.key(5), (2, seq, E))
    layer = ShortConv(_cfg(kernel))
    target = jax.random.normal(jax.random.key(6), (2, seq, E))

    def mine(p, x_):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(layer.apply({"params": p}, x_) * target)

    def loop(p, x_):
        return jnp.sum(by_token(p, x_) * target)

    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, x)
    np.testing.assert_allclose(got, by_token(params, x), atol=1e-5)
    assert float(jnp.max(jnp.abs(got))) > 1e-3
    got_g = jax.grad(mine, argnums=(0, 1))(params, x)
    want_g = jax.grad(loop, argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(g, w, atol=2e-5)
    if seq < kernel:
        # the taps that would read before the sequence take no gradient
        assert float(jnp.max(jnp.abs(
            got_g[0]["conv"][:kernel - seq]))) == 0.0


def test_the_tree_and_the_order_of_the_three_streams():
    """``in_proj`` 3 E wide, the taps ``[K, E]``, ``out_proj`` square, no
    bias anywhere; the streams are ``[B | C | z]``: zeroing B's or z's
    columns zeroes the output, zeroing C's does too (the gate after), and
    swapping B with z changes nothing (their product)."""
    layer = ShortConv(_cfg(3))
    x = jax.random.normal(jax.random.key(0), (1, 8, E))
    params = layer.init(jax.random.key(1), x)["params"]
    shapes = jax.tree_util.tree_map(lambda v: tuple(v.shape), params)
    assert shapes == {"in_proj": {"kernel": (E, 3 * E)}, "conv": (3, E),
                      "out_proj": {"kernel": (E, E)}}
    w_in = params["in_proj"]["kernel"]
    out = layer.apply({"params": params}, x)
    assert float(jnp.max(jnp.abs(out))) > 1e-4
    for lo in (0, E, 2 * E):
        cut = {**params, "in_proj": {
            "kernel": w_in.at[:, lo:lo + E].set(0.0)}}
        assert float(jnp.max(jnp.abs(
            layer.apply({"params": cut}, x)))) == 0.0
    swapped = jnp.concatenate(
        [w_in[:, 2 * E:], w_in[:, E:2 * E], w_in[:, :E]], axis=1)
    np.testing.assert_allclose(
        layer.apply({"params": {**params, "in_proj": {"kernel": swapped}}},
                    x), out, atol=1e-6)
    moved = jnp.concatenate(
        [w_in[:, E:2 * E], w_in[:, :E], w_in[:, 2 * E:]], axis=1)
    assert float(jnp.max(jnp.abs(layer.apply(
        {"params": {**params, "in_proj": {"kernel": moved}}}, x) - out))) \
        > 1e-4


def test_it_reads_no_token_after_its_own_and_none_beyond_the_taps():
    """Token t's output depends on tokens t-K+1 .. t and no other."""
    layer = ShortConv(_cfg(3))
    params = _params(3)
    x = jax.random.normal(jax.random.key(2), (1, 10, E))
    jac = jax.jacobian(lambda x_: layer.apply({"params": params}, x_)[0, 6])(
        x)[:, 0]                                    # [E_out, T, E_in]
    reach = np.asarray(jnp.max(jnp.abs(jac), axis=(0, 2)))
    assert np.all(reach[[4, 5, 6]] > 0)
    assert np.all(reach[[0, 1, 2, 3, 7, 8, 9]] == 0)


def test_bfloat16_compute_stays_in_a_band_of_the_float32_layer():
    params = _params(3)
    x = jax.random.normal(jax.random.key(3), (2, 32, E))
    want = ShortConv(_cfg(3)).apply({"params": params}, x)
    got = ShortConv(_cfg(3, jnp.bfloat16)).apply({"params": params}, x)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.sqrt(jnp.mean(jnp.square(got.astype(jnp.float32) - want))
                         / jnp.mean(jnp.square(want))))
    assert err < 0.03, err


def test_the_counters_two_values():
    """``return_stats`` hands ``(tokens, rms(y))``: the tokens through the
    mixer and the root mean square of the gated convolution's output before
    the projection; with the taps at zero the second reads exactly 0."""
    assert SCONV_COUNTERS == ("shortconv_tokens_total",
                              "shortconv_out_rms_micro_total")
    layer = ShortConv(_cfg(3))
    params = _params(3)
    x = jax.random.normal(jax.random.key(4), (2, 12, E))
    out, stats = layer.apply({"params": params}, x, return_stats=True)
    np.testing.assert_allclose(out, layer.apply({"params": params}, x))
    assert stats.shape == (2,) and stats.dtype == jnp.float32
    assert float(stats[0]) == 2 * 12
    y = gated_by_token(params, x)
    np.testing.assert_allclose(float(stats[1]),
                               float(jnp.sqrt(jnp.mean(y * y))), rtol=1e-5)
    dead = {**params, "conv": jnp.zeros_like(params["conv"])}
    _, stats = layer.apply({"params": dead}, x, return_stats=True)
    assert float(stats[1]) == 0.0
    # the reading takes no gradient
    g = jax.grad(lambda p: layer.apply({"params": p}, x,
                                       return_stats=True)[1][1])(params)
    assert all(float(jnp.max(jnp.abs(v))) == 0.0
               for v in jax.tree_util.tree_leaves(g))
