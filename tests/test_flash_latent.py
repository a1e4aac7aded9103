"""Two head sizes in the flash kernels (``ops/flash_attention.py``: queries
and keys ``d_qk`` wide, values ``d_v``, as latent attention has them):
forward and gradients against masked softmax at 192/128 and 96/64, with
shared key/value heads and with more keys than queries, through the split
backward and through the fused one (four and eight key blocks); one head size computes what the parent
commit computed, bit for bit; the kernels' names; the plain attention, the
sharded wrapper and the padding path take the two sizes.

Since PR 57 a query/key head over one 128-lane tile (192, 256) keeps the
1024-token tiles of every other head and asks for a lane tile's scoped
VMEM more instead: the tiles and the limits each traced kernel asks for,
and heads of one lane tile traced to the parent's jaxpr."""

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from _flash_jaxpr_golden import ONE_LANE_TILE, jaxpr_hash
from torchft_tpu.models.transformer import plain_attention
from torchft_tpu.ops import flash_attention, sharded_flash_attention

# the module: ``torchft_tpu.ops`` exports the function under the same name
fa = importlib.import_module("torchft_tpu.ops.flash_attention")
pytestmark = pytest.mark.heavy
BLOCK = 64
HERE = os.path.dirname(os.path.abspath(__file__))


def masked_softmax(q, k, v, window=None):
    """Causal, positions end-aligned, float32 throughout; the scores scale
    by the query/key head size."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s_q, s_k = q.shape[1], k.shape[1]
    i = jnp.arange(s_q)[:, None] + (s_k - s_q)
    j = jnp.arange(s_k)[None, :]
    mask = i >= j
    if window is not None:
        mask &= i - j < window
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def inputs(s_q, s_k, h, h_kv, d_qk, d_v, seed=0, batch=2):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (batch, s_q, h, d_qk)),
            jax.random.normal(ks[1], (batch, s_k, h_kv, d_qk)),
            jax.random.normal(ks[2], (batch, s_k, h_kv, d_v)),
            jax.random.normal(ks[3], (batch, s_q, h, d_v)))


#        s_q  s_k  h  h_kv d_qk d_v
CASES = {
    "192_128": (256, 256, 2, 2, 192, 128),
    "96_64": (256, 256, 2, 2, 96, 64),
    "192_128_more_keys": (128, 256, 2, 2, 192, 128),
    "96_64_more_keys": (64, 256, 2, 2, 96, 64),
    "96_64_gqa": (256, 256, 4, 2, 96, 64),
    "48_32_one_shared_head": (128, 128, 4, 1, 48, 32),
    "wider_values_64_96": (128, 128, 2, 2, 64, 96),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_two_head_sizes_against_masked_softmax(case):
    """Forward and the split backward (what interpret mode takes)."""
    q, k, v, g = inputs(*CASES[case])

    def flash(q, k, v):
        return flash_attention(q, k, v, True, block_q=BLOCK, block_k=BLOCK,
                               interpret=True)

    out, vjp = jax.vjp(flash, q, k, v)
    want, vjp_ref = jax.vjp(masked_softmax, q, k, v)
    assert out.shape == q.shape[:3] + (v.shape[-1],)
    np.testing.assert_allclose(out, want, atol=3e-6)
    got = vjp(g)
    assert [x.shape for x in got] == [q.shape, k.shape, v.shape]
    for a, b in zip(got, vjp_ref(g)):
        np.testing.assert_allclose(a, b, atol=1e-5)


FUSED = {"192_128": (256, 256, 2, 2, 192, 128),
         "96_64_gqa": (256, 256, 4, 2, 96, 64),
         "96_64_more_keys": (256, 512, 2, 2, 96, 64)}


@pytest.mark.parametrize("case", list(FUSED), ids=list(FUSED))
def test_fused_backward_at_two_head_sizes(case, monkeypatch):
    """The fused backward kernel, which off a chip only runs when steered:
    every ``pallas_call`` of the backward is made interpreted from here
    while ``_flash_bwd`` is told it compiles. Four and eight key blocks
    against four query blocks: dq stays in the kernel's VMEM accumulator
    from one key block's sweep to the next (no aliased buffer for the
    interpreter to get wrong), dk and dv accumulate in scratch."""
    q, k, v, g = inputs(*FUSED[case])
    real = fa.pl.pallas_call
    names = []

    def interpreted(*a, **kw):
        names.append(kw.get("name"))
        return real(*a, **{**kw, "interpret": True})

    out, lse = fa._flash_fwd(q, k, v, True, BLOCK, BLOCK, True)
    monkeypatch.setattr(fa.pl, "pallas_call", interpreted)
    monkeypatch.delenv("TORCHFT_FLASH_FUSED_BWD", raising=False)
    dq, dk, dv = fa._flash_bwd(q, k, v, out, lse, g, True, BLOCK, BLOCK,
                               interpret=False)
    assert names == ["flash_bwd_mla"]
    _, vjp_ref = jax.vjp(masked_softmax, q, k, v)
    for a, b in zip((dq, dk, dv), vjp_ref(g)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)


MIB = 1 << 20
#        d_qk d_v  heads kv: scoped VMEM asked, MiB (None: Mosaic's default)
TILES = {"192_128": (192, 128, 32, 32, 32),
         "256": (256, 256, 16, 2, 32),
         "160_128": (160, 128, 4, 4, 32),
         "320": (320, 320, 2, 2, 48),
         "128": (128, 128, 32, 4, None),
         "96_64": (96, 64, 8, 8, None)}


@pytest.mark.parametrize("fused", ["1", "0"], ids=["fused", "split"])
@pytest.mark.parametrize("case", list(TILES), ids=list(TILES))
def test_every_head_size_keeps_1024_token_tiles(case, fused, monkeypatch):
    """The 8k calls traced as the chip compiles them, never run: every
    kernel takes 1024-token tiles whatever the head (a head over 128 took
    512 before PR 57, four times the grid steps), and a query/key head of
    ``n`` lane tiles asks for ``n`` times Mosaic's default scoped VMEM,
    the fused backward for its resident dq beside that; one lane tile asks
    for nothing, as before."""
    d_qk, d_v, h, h_kv, scoped = TILES[case]
    monkeypatch.setenv("TORCHFT_FLASH_FUSED_BWD", fused)
    real, calls = fa.pl.pallas_call, []

    def recording(*a, **kw):
        calls.append((kw["grid"],
                      [spec.block_shape for spec in kw["in_specs"][:3]],
                      getattr(kw.get("compiler_params"), "vmem_limit_bytes",
                              None)))
        return real(*a, **kw)

    monkeypatch.setattr(fa.pl, "pallas_call", recording)
    q = jax.ShapeDtypeStruct((1, 8192, h, d_qk), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, h_kv, d_qk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 8192, h_kv, d_v), jnp.bfloat16)
    jax.eval_shape(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, True, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), q, k, v)
    assert len(calls) == (2 if fused == "1" else 3)  # forward first
    for i, (grid, blocks, limit) in enumerate(calls):
        assert grid == (h, 8, 8)
        assert blocks == [(1, 1024, d_qk), (1, 1024, d_qk), (1, 1024, d_v)]
        if fused == "1" and i == 1:
            assert limit == ((scoped or 16) * MIB
                             + fa._dq_resident_bytes(8192, d_qk, 2))
        else:
            assert limit == (scoped * MIB if scoped else None)
    if scoped:      # float32 operands: tiles twice as wide
        assert fa._tile_vmem(d_qk, 4).vmem_limit_bytes == 2 * scoped * MIB


@pytest.mark.parametrize("case", list(ONE_LANE_TILE), ids=list(ONE_LANE_TILE))
def test_heads_of_one_lane_tile_trace_to_the_parents_jaxpr(case):
    """Forward and gradients at a head of at most 128 trace to the text
    PR 57's parent traced (``tests/_flash_jaxpr_golden.py``): what a wider
    head asks for does not reach them."""
    with open(os.path.join(HERE, "golden_flash_jaxpr_pr57.json")) as f:
        assert jaxpr_hash(case) == json.load(f)[case]


def _digest(tree):
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        bits = jax.lax.bitcast_convert_type(
            x.reshape(-1).astype(jnp.float32), jnp.uint32)
        idx = jnp.arange(bits.size, dtype=jnp.uint32)
        out += [int(jnp.sum(bits)), int(jnp.sum(bits * (2 * idx + 1)))]
    return out


ONE_SIZE = {"mha": dict(s_q=256, s_k=256, h=2, h_kv=2, d=64, window=None),
            "gqa_more_keys": dict(s_q=128, s_k=256, h=4, h_kv=2, d=32,
                                  window=None),
            "mqa_window": dict(s_q=256, s_k=256, h=4, h_kv=1, d=32,
                               window=96)}


@pytest.mark.parametrize("case", list(ONE_SIZE), ids=list(ONE_SIZE))
def test_one_head_size_is_bitwise_what_the_parent_computed(case):
    """``tests/golden_latent_pr33.json`` was written by these lines on the
    parent commit (6195c1c), whose kernels read one ``d``."""
    with open(os.path.join(HERE, "golden_latent_pr33.json")) as f:
        golden = json.load(f)["flash"][case]
    c = ONE_SIZE[case]
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (2, c["s_q"], c["h"], c["d"]))
    k = jax.random.normal(ks[1], (2, c["s_k"], c["h_kv"], c["d"]))
    v = jax.random.normal(ks[2], (2, c["s_k"], c["h_kv"], c["d"]))
    g = jax.random.normal(ks[3], (2, c["s_q"], c["h"], c["d"]))
    out, vjp = jax.vjp(
        lambda q, k, v: flash_attention(
            q, k, v, True, block_q=64, block_k=64, interpret=True,
            window=c["window"]), q, k, v)
    assert _digest(out) == golden["out"]
    assert _digest(vjp(g)) == golden["grads"]


def _kernel_names(d_qk, d_v, window=None):
    q, k, v, _ = inputs(128, 128, 2, 1, d_qk, d_v)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q: flash_attention(q, k, v, True, interpret=True,
                                  window=window).sum()))(q))
    # "_" is a word character: flash_fwd_window does not match inside
    # flash_fwd_window_mla
    return {n for n in ("flash_fwd_mla", "flash_bwd_dq_mla",
                        "flash_bwd_dkdv_mla", "flash_fwd_window_mla",
                        "flash_fwd_window", "flash_bwd_dq_window")
            if re.search(rf"name={n}\b", text)}


@pytest.mark.parametrize("d_qk,d_v,window,want", [
    (96, 64, None, {"flash_fwd_mla", "flash_bwd_dq_mla",
                    "flash_bwd_dkdv_mla"}),
    (96, 64, 40, {"flash_fwd_window_mla"}),
    (64, 64, None, set()),
    (64, 64, 40, {"flash_fwd_window", "flash_bwd_dq_window"})],
    ids=["latent", "latent_window", "one_size", "one_size_window"])
def test_a_profile_tells_the_latent_kernels_by_name(d_qk, d_v, window, want):
    """``_mla`` is in a kernel's name exactly when the two sizes differ;
    one head size keeps the names (or none) it had."""
    assert fa._kernel_name("flash_fwd", window, d_qk != d_v) == (
        None if not want else
        "flash_fwd" + ("_window" if window else "")
        + ("_mla" if d_qk != d_v else ""))
    got = _kernel_names(d_qk, d_v, window)
    assert want <= got
    assert any(n.endswith("_mla") for n in got) == (d_qk != d_v)


@pytest.mark.parametrize("window", [None, 70], ids=["causal", "window"])
def test_plain_attention_and_the_kernels_reference_take_two_sizes(window):
    q, k, v, _ = inputs(96, 160, 4, 2, 48, 32)
    want = masked_softmax(q, k, v, window)
    np.testing.assert_allclose(plain_attention(q, k, v, True, window=window),
                               want, atol=3e-6)
    rep = q.shape[2] // k.shape[2]
    np.testing.assert_allclose(
        fa._reference(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2), True,
                      window), want, atol=3e-6)


def test_lengths_that_need_padding_at_two_sizes():
    q, k, v, g = inputs(100, 100, 2, 2, 48, 32)
    out, vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, True, interpret=True),
        q, k, v)
    want, vjp_ref = jax.vjp(masked_softmax, q, k, v)
    np.testing.assert_allclose(out, want, atol=3e-6)
    for a, b in zip(vjp(g), vjp_ref(g)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_sharded_wrapper_at_two_sizes():
    """Batch over fsdp, heads over tp, the value head narrower: each device
    runs the kernel on its block."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
    attention = sharded_flash_attention(mesh, interpret=True)
    q, k, v, _ = inputs(128, 128, 4, 2, 48, 32)
    out = jax.jit(lambda q, k, v: attention(q, k, v, True))(q, k, v)
    assert out.shape == (2, 128, 4, 32)
    np.testing.assert_allclose(out, masked_softmax(q, k, v), atol=3e-6)


def test_mismatched_key_size_is_refused():
    q, k, v, _ = inputs(64, 64, 2, 2, 48, 32)
    with pytest.raises(AssertionError, match="head size"):
        flash_attention(q, k[..., :32], v, True, interpret=True)
