"""The window in the flash kernels' mask (``ops/flash_attention.py``,
``window=``): forward and gradients against masked softmax for windows
under, at and over a block and over the sequence, with GQA and with more
keys than queries; ``window=None`` traces what it traced before the argument
existed; the plain attention and the sharded wrapper take the same
argument."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models.transformer import plain_attention
from torchft_tpu.ops import flash_attention, sharded_flash_attention

pytestmark = pytest.mark.heavy
BLOCK = 64


def masked_softmax(q, k, v, window):
    """Key j visible to query i iff 0 <= i - j < window, positions
    end-aligned; float32 throughout."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s_q, s_k = q.shape[1], k.shape[1]
    i = jnp.arange(s_q)[:, None] + (s_k - s_q)
    j = jnp.arange(s_k)[None, :]
    mask = (i >= j) & (i - j < window)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def inputs(s_q, s_k, h, h_kv, d=32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (2, s_q, h, d)),
            jax.random.normal(ks[1], (2, s_k, h_kv, d)),
            jax.random.normal(ks[2], (2, s_k, h_kv, d)),
            jax.random.normal(ks[3], (2, s_q, h, d)))


CASES = {
    "under_a_block": (256, 256, 2, 2, 24),
    "one": (256, 256, 2, 2, 1),
    "a_block": (256, 256, 2, 2, BLOCK),
    "a_block_and_one": (256, 256, 2, 2, BLOCK + 1),
    "two_and_a_half_blocks": (256, 256, 2, 2, 160),
    "over_the_sequence": (256, 256, 2, 2, 1000),
    "gqa": (256, 256, 4, 2, 100),
    "mqa": (256, 256, 4, 1, BLOCK),
    "more_keys_than_queries": (128, 256, 4, 2, 96),
    "more_keys_window_over_queries": (64, 256, 2, 1, 200),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_windowed_flash_against_masked_softmax(case):
    s_q, s_k, h, h_kv, window = CASES[case]
    q, k, v, g = inputs(s_q, s_k, h, h_kv)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, block_q=BLOCK, block_k=BLOCK,
                               interpret=True, window=window)

    out, vjp = jax.vjp(flash, q, k, v)
    want, vjp_ref = jax.vjp(
        lambda q, k, v: masked_softmax(q, k, v, window), q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-6)
    for a, b in zip(vjp(g), vjp_ref(g)):
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_window_none_traces_what_it_traced_before():
    q, k, v, _ = inputs(128, 128, 2, 1)

    def text(**kw):
        return str(jax.make_jaxpr(jax.grad(
            lambda q: flash_attention(q, k, v, True, interpret=True,
                                      **kw).sum()))(q))

    assert text() == text(window=None)
    assert "flash_fwd_window" not in text()
    assert "flash_fwd_window" in text(window=32)


def test_a_window_over_the_sequence_is_the_full_kernel_bitwise():
    q, k, v, g = inputs(256, 256, 4, 2, seed=3)
    run = lambda w: jax.vjp(lambda *a: flash_attention(  # noqa: E731
        *a, True, block_q=BLOCK, block_k=BLOCK, interpret=True, window=w),
        q, k, v)
    (out, vjp), (out_w, vjp_w) = run(None), run(256)
    assert (out == out_w).all()
    for a, b in zip(vjp(g), vjp_w(g)):
        assert (a == b).all()


@pytest.mark.parametrize("bad", [dict(causal=False, window=8),
                                 dict(causal=True, window=0)])
def test_a_window_needs_a_causal_mask_and_a_length(bad):
    q, k, v, _ = inputs(64, 64, 2, 2)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, interpret=True, **bad)


@pytest.mark.parametrize("window", [1, 40, 64, 500])
def test_plain_attention_takes_the_same_window(window):
    q, k, v, _ = inputs(64, 128, 4, 2, seed=1)
    np.testing.assert_allclose(plain_attention(q, k, v, True, window=window),
                               masked_softmax(q, k, v, window), atol=2e-6)


def test_sharded_flash_attention_takes_the_window():
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    attn = sharded_flash_attention(mesh, interpret=True)
    q, k, v, _ = inputs(128, 128, 4, 2, seed=2)
    out = jax.jit(lambda q, k, v: attn(q, k, v, True, window=48))(q, k, v)
    np.testing.assert_allclose(out, masked_softmax(q, k, v, 48), atol=2e-6)
