"""The kernels of a learned sparse attention alone (``ops/sparse_index.py``;
the selected kernels of ``ops/flash_attention.py``): forward, split and fused
backward on a given random selection against a masked softmax, the
selection against ``jax.lax.top_k`` on random scores and on planted ties,
and the indexer's loss rule against autodiff of the divergence written out.
The whole model against the benchmark builder's reference is
``tests/test_sparse_attention_model.py``'s."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from harness import spec  # noqa: E402

import torchft_tpu.ops.flash_attention  # noqa: E402,F401
from torchft_tpu.models.transformer import plain_attention  # noqa: E402
from torchft_tpu.ops import sparse_index as si  # noqa: E402

fa = sys.modules["torchft_tpu.ops.flash_attention"]
pytestmark = pytest.mark.heavy
TOPK = 16


@pytest.fixture(scope="module")
def builder():
    spec.configure(REPO)
    return spec.module("models", "dsa_moe_decoder")


@pytest.fixture(autouse=True, scope="module")
def leave_no_programs_behind():
    yield
    jax.clear_caches()


# ------------------------------------------------- the kernels on a given set

def _given(seq, seed=0, heads=4, groups=2, d=16, batch=2, keep=8):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (batch, seq, heads, d))
    k = jax.random.normal(ks[1], (batch, seq, groups, d))
    v = jax.random.normal(ks[2], (batch, seq, groups, d))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    score = jnp.where(causal, jax.random.normal(ks[3], (batch, seq, seq)),
                      -jnp.inf)
    kth = jnp.sort(score, axis=-1)[..., -keep][..., None]
    sel = jnp.logical_and(score >= kth, causal)   # a row has min(t+1, keep)
    return q, k, v, sel, jax.random.normal(ks[4], q.shape)


def _masked_plain(q, k, v, sel):
    rep = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * q.shape[-1] ** -0.5
    logits = jnp.where(sel[:, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return (jnp.einsum("bhqk,bkhd->bqhd", p, vv),
            jax.nn.logsumexp(logits, axis=-1))


CASES = {"blocks_16": (64, 16, False), "one_tile": (48, None, False),
         "padded": (50, None, False), "empty_tile": (64, 16, True)}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_selected_kernels_against_masked_plain_attention(case):
    """Forward and the split backward on a given random selection (rows
    with fewer than ``keep`` keys at the top, a tile with no selected pair,
    a length that is padded) against a masked softmax; the lse too."""
    seq, block, empty = CASES[case]
    q, k, v, sel, w = _given(seq)
    if empty:
        sel = sel.at[:, 32:48, 0:16].set(False)
        sel = jnp.logical_or(sel, jnp.eye(seq, dtype=bool))
        _, flags = fa._with_tile_flags(sel.astype(jnp.int8), 16, 16)
        assert int(flags.reshape(2, 4, 4)[0, 2, 0]) == fa._TILE_NONE

    def mine(q, k, v):
        return fa.sparse_flash_attention(q, k, v, sel, block_q=block,
                                         block_k=block, return_lse=True)

    with jax.default_matmul_precision("highest"):
        (out, lse), (want, want_lse) = mine(q, k, v), _masked_plain(
            q, k, v, sel)
        np.testing.assert_allclose(out, want, atol=2e-6)
        np.testing.assert_allclose(lse, want_lse, atol=2e-6)
        got = jax.grad(lambda *a: jnp.sum(mine(*a)[0] * w)
                       + jnp.sum(mine(*a)[1]), (0, 1, 2))(q, k, v)
        ref = jax.grad(lambda *a: jnp.sum(_masked_plain(*a, sel)[0] * w)
                       + jnp.sum(_masked_plain(*a, sel)[1]),
                       (0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_fused_selected_backward_is_the_split_one(monkeypatch):
    """The fused backward (a compiled call's) run interpreted, as
    ``tests/test_flash_band.py`` steers it: one kernel, the split kernels'
    numbers."""
    real, made = fa.pl.pallas_call, []

    def call(*a, **kw):
        made.append(kw.get("name"))
        return real(*a, **{**kw, "interpret": True})

    monkeypatch.setattr(fa.pl, "pallas_call", call)
    monkeypatch.delenv("TORCHFT_FLASH_FUSED_BWD", raising=False)
    q, k, v, sel, g = _given(64, seed=3)
    sel8 = fa._with_tile_flags(sel.astype(jnp.int8), 16, 16)
    out, lse = fa._flash_fwd(q, k, v, False, 16, 16, True, selection=sel8)
    fused = fa._flash_bwd(q, k, v, out, lse, g, False, 16, 16,
                          interpret=False, selection=sel8)
    split = fa._flash_bwd(q, k, v, out, lse, g, False, 16, 16,
                          interpret=True, selection=sel8)
    assert made == ["flash_fwd_sparse", "flash_bwd_sparse",
                    "flash_bwd_sparse_dq", "flash_bwd_sparse_dkdv"]
    for a, b in zip(fused, split):
        np.testing.assert_allclose(a, b, atol=2e-6)


@pytest.mark.parametrize("seq,block", [(64, 16), (50, None)])
def test_every_causal_key_selected_is_flash_attention(seq, block):
    q, k, v, _, _ = _given(seq)
    every = jnp.broadcast_to(jnp.tril(jnp.ones((seq, seq), bool)),
                             (2, seq, seq))
    got = fa.sparse_flash_attention(q, k, v, every, block_q=block,
                                    block_k=block)
    want = fa.flash_attention(q, k, v, True, block_q=block, block_k=block)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(got, plain_attention(q, k, v), atol=2e-6)


# ----------------------------------------------------------- the selection

def _index_inputs(seq, seed, heads=2, dim=8, batch=2):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (batch, seq, heads, dim)),
            jax.random.normal(ks[1], (batch, seq, dim)),
            jax.random.normal(ks[2], (batch, seq, heads)))


def _scores(a, b, u):
    z = jnp.maximum(jnp.einsum("btjc,bsc->btjs", a, b), 0.0)
    return jnp.einsum("btjs,btj->bts", z, u) \
        * (a.shape[2] * a.shape[3]) ** -0.5


@pytest.mark.parametrize("seq,ties", [(48, False), (50, True), (300, False)],
                         ids=["48", "50_ties", "300"])
def test_the_selection_is_top_k_a_row(builder, seq, ties):
    """The kernel's set equals ``jax.lax.top_k``'s, on random scores and on
    scores with planted ties (small whole numbers: many equal scores, which
    go to the lower index); rows ``t < K`` select ``0..t``; the rows'
    logsumexp over their sets."""
    a, b, u = _index_inputs(seq, 1)
    if ties:
        a, b, u = jnp.round(a), jnp.round(b), jnp.round(2 * u) / 2
    with jax.default_matmul_precision("highest"):
        sel, lse = si.select_keys(a, b, u, TOPK)
        scores = _scores(a, b, u)
    want = builder.reference_selection(scores, TOPK)
    if ties:
        assert int(jnp.sum(scores[0, -1, :-1] == scores[0, -1, 1:])) > 0
    np.testing.assert_array_equal(np.asarray(sel != 0), np.asarray(want))
    rows = np.asarray(jnp.sum(sel, axis=-1))
    np.testing.assert_array_equal(
        rows, np.broadcast_to(np.minimum(np.arange(seq) + 1, TOPK),
                              rows.shape))
    np.testing.assert_array_equal(
        np.asarray(sel[:, :TOPK, :TOPK] != 0),
        np.broadcast_to(np.tril(np.ones((TOPK, TOPK), bool)),
                        (2, TOPK, TOPK)))
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1),
        atol=2e-6)


# ------------------------------------------------------------ the loss rule

def _written_out_kl(a, b, u, q, k, sel):
    scores = _scores(a, b, u)
    p = jnp.mean(jax.nn.softmax(jnp.where(
        sel[:, None], jnp.einsum(
            "bqhd,bkhd->bhqk", q, jnp.repeat(k, q.shape[2] // k.shape[2], 2))
        * q.shape[-1] ** -0.5, -jnp.inf), axis=-1), axis=1)
    log_r = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
    kl = jnp.where(sel, p * (jnp.log(jnp.where(sel, p, 1.0))
                             - jnp.where(sel, log_r, 0.0)), 0.0)
    return jnp.sum(kl) / (a.shape[0] * a.shape[1])


@pytest.mark.parametrize("seq", [48, 50])
def test_the_loss_rule_against_autodiff_of_the_written_out_divergence(seq):
    """``indexer_kl``'s value and its ``da``, ``db``, ``du`` (one kernel
    pass: ``dI = (r - p) / S`` through the ReLU) against ``jax.grad`` of
    the divergence written out over ``[B, H, S, S]``."""
    a, b, u = _index_inputs(seq, 2)
    q, k, v, _, _ = _given(seq, seed=4)
    with jax.default_matmul_precision("highest"):
        sel, index_lse = si.select_keys(a, b, u, TOPK)
        _, lse = fa.sparse_flash_attention(q, k, v, sel, return_lse=True)
        mine = lambda a, b, u: si.indexer_kl(  # noqa: E731
            a, b, u, q, k, lse, sel, index_lse)
        got, g_got = jax.value_and_grad(mine, (0, 1, 2))(a, b, u)
        want, g_want = jax.value_and_grad(
            lambda a, b, u: _written_out_kl(a, b, u, q, k, sel != 0),
            (0, 1, 2))(a, b, u)
        # the attention's side is a target: no gradient reaches it
        g_q = jax.grad(lambda q: si.indexer_kl(a, b, u, q, k, lse, sel,
                                               index_lse))(q)
    assert float(want) > 1e-2 and abs(float(got) - float(want)) < 1e-6
    for x, y in zip(g_got, g_want):
        np.testing.assert_allclose(x, y, atol=1e-6 + 1e-5 * float(
            jnp.max(jnp.abs(y))))
    assert float(jnp.max(jnp.abs(g_q))) == 0.0


def test_the_loss_is_zero_where_the_index_scores_are_log_p():
    """One index head whose key is a one-hot of its position and whose
    query is ``log p + shift`` (positive, so the ReLU passes it): ``I`` is
    ``log p`` up to a row's constant, ``r = p``, and the loss and its
    gradient vanish."""
    seq = 16
    q, k, v, sel, _ = _given(seq, seed=6, batch=1, keep=6)
    _, lse = fa.sparse_flash_attention(q, k, v, sel, return_lse=True)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, 2)) * 0.25
    p = jnp.mean(jax.nn.softmax(jnp.where(sel[:, None], logits, -jnp.inf),
                                axis=-1), axis=1)
    a = jnp.where(sel, jnp.log(jnp.where(sel, p, 1.0)) + 40.0, 0.0)
    b = jnp.eye(seq)[None]
    u = jnp.full((1, seq, 1), float(seq) ** 0.5)     # undoes (J c)^-1/2
    index_lse = jax.nn.logsumexp(jnp.where(sel, a, -jnp.inf), axis=-1)
    with jax.default_matmul_precision("highest"):
        kl, grads = jax.value_and_grad(
            lambda a, b, u: si.indexer_kl(a, b, u, q, k, lse,
                                          sel.astype(jnp.int8), index_lse),
            (0, 1, 2))(a[:, :, None, :], b, u)
    assert abs(float(kl)) < 1e-6
    assert float(jnp.max(jnp.abs(grads[0]))) < 1e-6
