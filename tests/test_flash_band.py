"""A masked block moves no bytes (``ops/flash_attention.py``): the visible
band the index maps stop at, against ``_block_visibility`` block by block;
the fused backward, whose dq stays in VMEM for a (batch, head)'s whole
sweep, interpreted at four and more key blocks against masked softmax and
against the split kernels; the index maps as the kernels get them; and the
trace-time counters of grid steps, skipped steps and the backward taken."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu import tracing

fa = importlib.import_module("torchft_tpu.ops.flash_attention")
pytestmark = pytest.mark.heavy

WINDOWS = (None, 1, 5, 16, 24, 32, 33, 48, 64, 100, 1000)
#          s_q  s_k  bq  bk
GRIDS = {
    "square": (128, 128, 16, 16),
    "wide_q_blocks": (128, 128, 32, 16),
    "wide_k_blocks": (128, 128, 16, 64),
    "more_keys": (64, 192, 16, 16),
    "more_keys_wide_q": (64, 256, 32, 16),
    "more_keys_wide_k": (96, 192, 16, 32),
    "one_q_block": (32, 128, 32, 16),
    "one_k_block": (128, 128, 16, 128),
}


def _seen(qi, ki, bq, bk, offset, window):
    return bool(fa._block_visibility(qi, ki, bq, bk, offset, True, None,
                                     window)[0])


@pytest.mark.parametrize("keys", [True, False], ids=["k_of_q", "q_of_k"])
@pytest.mark.parametrize("grid", list(GRIDS), ids=list(GRIDS))
def test_band_is_exactly_the_visible_blocks(grid, keys):
    """The visible steps of every row are ``first..last``, contiguous;
    a row with none reads ``last < first`` with ``first`` a valid index."""
    s_q, s_k, bq, bk = GRIDS[grid]
    nqb, nkb, offset = s_q // bq, s_k // bk, s_k - s_q
    n_outer, n_inner = (nqb, nkb) if keys else (nkb, nqb)
    empty = 0
    for window in WINDOWS:
        for outer in range(n_outer):
            lo, hi = fa._visible_band(outer, bq, bk, n_inner, offset,
                                      window, keys)
            want = [i for i in range(n_inner)
                    if _seen(*((outer, i) if keys else (i, outer)),
                             bq, bk, offset, window)]
            assert 0 <= lo < n_inner and hi < n_inner
            assert list(range(lo, hi + 1)) == want, (window, outer)
            empty += not want
    # every query sees itself; only key blocks can be left of every window
    assert empty == 0 or (not keys and s_k > s_q)


def test_band_has_rows_that_see_nothing():
    """Keys far left of every query's window (``s_k > s_q``): the dk/dv and
    fused grids have whole rows with no visible query block."""
    s_q, s_k, bq, bk = GRIDS["more_keys"]
    lo, hi = fa._visible_band(0, bq, bk, s_q // bq, s_k - s_q, 16, False)
    assert hi < lo and 0 <= lo < s_q // bq


@pytest.mark.parametrize("keys", [True, False], ids=["k_of_q", "q_of_k"])
@pytest.mark.parametrize("window", [None, 24, 64])
def test_clamped_index_names_a_visible_block_or_its_neighbour(window, keys):
    """What an index map gets: inside the band the grid's own index, outside
    it the nearest end, so consecutive skipped steps name one block; traced
    scalars as Pallas hands them."""
    s_q, s_k, bq, bk = GRIDS["more_keys_wide_k"]
    nqb, nkb, offset = s_q // bq, s_k // bk, s_k - s_q
    n_outer, n_inner = (nqb, nkb) if keys else (nkb, nqb)
    clamp = jax.jit(fa._band_clamp(True, False, bq, bk, n_inner, offset,
                                   window, keys))
    for outer in range(n_outer):
        lo, hi = fa._visible_band(outer, bq, bk, n_inner, offset, window,
                                  keys)
        got = [int(clamp(jnp.int32(outer), jnp.int32(i)))
               for i in range(n_inner)]
        assert got == [min(max(i, lo), max(hi, lo)) for i in range(n_inner)]


@pytest.mark.parametrize("causal,shift", [(False, False), (False, True)],
                         ids=["non_causal", "traced_shift"])
def test_no_static_mask_keeps_the_grid_index(causal, shift):
    clamp = fa._band_clamp(causal, shift, 16, 16, 8, 0, None, True)
    assert [clamp(3, i) for i in range(8)] == list(range(8))


def masked_softmax(q, k, v, window=None, shift=None):
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s_q, s_k = q.shape[1], k.shape[1]
    i = jnp.arange(s_q)[:, None] + (s_k - s_q)
    j = jnp.arange(s_k)[None, :]
    mask = i >= j if shift is None else i + shift >= j
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


#   s_q  s_k  h h_kv d_qk d_v  block_q block_k window shift
FUSED = {
    "causal": (256, 256, 2, 2, 32, 32, 64, 64, None, None),
    "causal_8_key_blocks": (256, 256, 2, 2, 32, 32, 64, 32, None, None),
    "window": (256, 256, 2, 2, 32, 32, 64, 64, 64, None),
    "window_not_a_block_multiple": (256, 256, 2, 2, 32, 32, 32, 64, 80, None),
    "latent_192_128": (256, 256, 2, 2, 192, 128, 64, 64, None, None),
    "gqa": (256, 256, 4, 2, 32, 32, 64, 64, None, None),
    "head_64_on_8_kv": (256, 256, 32, 8, 64, 64, 64, 64, None, None),
    "more_keys_window": (256, 512, 2, 2, 32, 32, 64, 64, 96, None),
    "traced_shift": (256, 256, 2, 2, 32, 32, 64, 64, None, 40),
}


@pytest.fixture
def interpreted(monkeypatch):
    """Every ``pallas_call`` made interpreted while ``_flash_bwd`` is told
    it compiles: how the fused kernel, a compiled call's, runs off a chip."""
    real = fa.pl.pallas_call
    names = []

    def call(*a, **kw):
        names.append(kw.get("name"))
        return real(*a, **{**kw, "interpret": True})

    monkeypatch.setattr(fa.pl, "pallas_call", call)
    monkeypatch.delenv("TORCHFT_FLASH_FUSED_BWD", raising=False)
    return names


@pytest.mark.parametrize("case", list(FUSED), ids=list(FUSED))
def test_fused_backward_holds_dq_on_the_chip(case, interpreted):
    """Four or more key blocks, so dq is whole only if the accumulator
    carries from one key block's sweep to the next: against masked softmax,
    and bit for bit against the split kernels (the same products, the same
    order of additions into dq, key blocks ascending)."""
    *shape, bq, bk, window, shift = FUSED[case]
    batch = 1 if shape[2] > 4 else 2
    q, k, v, g = inputs(*shape, batch=batch)
    assert k.shape[1] // bk >= 4 and q.shape[1] // bq >= 4
    causal = shift is None
    sh = None if shift is None else jnp.int32(shift)
    out, lse = fa._flash_fwd(q, k, v, causal, bq, bk, True, shift=sh,
                             window=window)
    del interpreted[:]
    fused = fa._flash_bwd(q, k, v, out, lse, g, causal, bq, bk,
                          interpret=False, shift=sh, window=window)
    assert len(interpreted) == 1          # one kernel: the fused one
    split = fa._flash_bwd(q, k, v, out, lse, g, causal, bq, bk,
                          interpret=True, shift=sh, window=window)
    assert len(interpreted) == 3
    _, vjp_ref = jax.vjp(
        lambda q, k, v: masked_softmax(q, k, v, window, shift), q, k, v)
    for a, b, want in zip(fused, split, vjp_ref(g)):
        assert a.shape == want.shape and a.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(a, want, atol=2e-5)


def test_fused_backward_writes_dq_in_the_queries_dtype(interpreted):
    q, k, v, g = (x.astype(jnp.bfloat16)
                  for x in inputs(256, 256, 2, 2, 32, 32))
    out, lse = fa._flash_fwd(q, k, v, True, 64, 64, True)
    dq, dk, dv = fa._flash_bwd(q, k, v, out, lse, g, True, 64, 64,
                               interpret=False)
    assert (dq.dtype, dk.dtype, dv.dtype) == (jnp.bfloat16,) * 3
    want = fa._flash_bwd(q, k, v, out, lse, g, True, 64, 64, interpret=True)
    np.testing.assert_array_equal(np.asarray(dq, np.float32),
                                  np.asarray(want[0], np.float32))


def test_a_dq_too_large_for_vmem_takes_the_split_kernels(interpreted,
                                                         monkeypatch):
    """Which backward runs follows from the shapes: over the accumulator's
    budget the function falls to the split kernels."""
    q, k, v, g = inputs(256, 256, 2, 2, 32, 32)
    out, lse = fa._flash_fwd(q, k, v, True, 64, 64, True)
    monkeypatch.setattr(fa, "_DQ_RESIDENT_BYTES",
                        fa._dq_resident_bytes(256, 32) - 1)
    del interpreted[:]
    fa._flash_bwd(q, k, v, out, lse, g, True, 64, 64, interpret=False)
    assert len(interpreted) == 2          # dq, then dk/dv


def test_resident_dq_bytes_count_whole_lane_tiles():
    # a head of 64 pads to 128 lanes; 192 to 256
    assert fa._dq_resident_bytes(8192, 64) == 4 << 20
    assert fa._dq_resident_bytes(8192, 128) == 4 << 20
    assert fa._dq_resident_bytes(8192, 192) == 8 << 20
    assert fa._dq_resident_bytes(8192, 128, 2) == 8 << 20
    assert fa._dq_resident_bytes(8192, 256) <= fa._DQ_RESIDENT_BYTES


KEYS = ("flash_grid_steps_traced_total", "flash_skipped_steps_traced_total",
        "flash_dq_resident_traces_total", "flash_dq_split_traces_total")
#            q heads, kv heads, d_qk, d_v, window: skipped, grid a (b, h)
TRACED = {"causal": (32, 4, 128, 128, None, 28, 64),
          "window": (32, 4, 128, 128, 2048, 43, 64),
          "latent": (32, 32, 192, 128, None, 28, 64)}


def _counters():
    c = tracing.program_counters()
    return [c.get(key, 0) for key in KEYS]


@pytest.mark.parametrize("fused", ["1", "0"], ids=["fused", "split"])
@pytest.mark.parametrize("case", list(TRACED), ids=list(TRACED))
def test_counters_are_counted_when_a_kernel_is_traced(case, fused,
                                                     monkeypatch):
    """The 8k calls of the cells, traced and never run: forward and
    backward count their grid steps, batch x heads included, and those a
    static mask skips (28/64 causal, 43/64 under a window of 2,048; the
    latent kernels' as the causal ones' since PR 57 gave a head over 128
    the 1024-token tiles too: 120/256 before), and which backward was
    taken."""
    h, h_kv, d, d_v, window, skipped, grid = TRACED[case]
    monkeypatch.setenv("TORCHFT_FLASH_FUSED_BWD", fused)
    q = jax.ShapeDtypeStruct((1, 8192, h, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, h_kv, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 8192, h_kv, d_v), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, True, interpret=False,
                                  window=window).astype(jnp.float32).sum()

    before = _counters()
    jax.eval_shape(loss, q, k, v)
    forward = [a - b for a, b in zip(_counters(), before)]
    assert forward == [h * grid, h * skipped, 0, 0]
    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    both = [a - b for a, b in zip(_counters(), before)]
    calls = 1 + 1 + (1 if fused == "1" else 2)   # forward twice
    assert both[:2] == [calls * h * grid, calls * h * skipped]
    assert both[2:] == ([1, 0] if fused == "1" else [0, 1])
    assert both[1] * grid == both[0] * skipped


def test_a_traced_shift_counts_no_step_as_skipped():
    q = jax.ShapeDtypeStruct((1, 512, 2, 32), jnp.float32)
    before = _counters()
    jax.eval_shape(lambda q, s: fa.flash_attention_block(
        q, q, q, s, block_q=128, block_k=128, interpret=False),
        q, jax.ShapeDtypeStruct((), jnp.int32))
    assert [a - b for a, b in zip(_counters(), before)] == [2 * 16, 0, 0, 0]
