"""A learned sparse attention (``ops/sparse_index.py``, the selected kernels
of ``ops/flash_attention.py``, ``models/transformer.py``'s ``Indexer`` and
``sparse_lm_loss``) against the benchmark builder's plain reference
(``dsa_moe_decoder``): the whole small model in float32 and in bfloat16, the
two objectives' disjoint gradients, the expert shares' sum, the controls and
the counters. The kernels alone (on a given selection, the selection against
``jax.lax.top_k``, the loss rule against autodiff of the written-out
divergence) are ``tests/test_sparse_attention_kernels.py``'s: two files, so
that two of the suite's workers share them."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from harness import reference as R  # noqa: E402
from harness import spec  # noqa: E402

from torchft_tpu import tracing  # noqa: E402
from torchft_tpu.models import sparse_lm_losses  # noqa: E402
from torchft_tpu.models.moe import RoutedMoEMLP  # noqa: E402

pytestmark = pytest.mark.heavy
TOPK = 16


@pytest.fixture(scope="module")
def builder():
    spec.configure(REPO)
    return spec.module("models", "dsa_moe_decoder")


@pytest.fixture(autouse=True, scope="module")
def leave_no_programs_behind():
    # an interpreted kernel's program is hundreds of memory maps on the CPU
    # (PERF.md section 7): drop this module's when it is done
    yield
    jax.clear_caches()


def small(**over):
    """The configuration's file at small widths: hidden 64, 2 layers, 4
    query heads on 2 key/value heads of 16, an indexer of 2 heads of 8
    selecting 16 keys, 8 experts of which 4 are held, 2 a token, a
    vocabulary of 256."""
    with open(os.path.join(
            REPO, "benchmarks/configs/keye-vl-2.0-30b-a3b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, moe_intermediate_size=32, num_experts=8,
               num_local_experts=8, num_experts_per_tok=2, num_experts_held=4,
               vocab_size=256, published_layers=[0, 1], num_hidden_layers=2,
               sa_config=dict(cfg["sa_config"], indexer_num_heads=2,
                              indexer_head_dim=8, topk=TOPK))
    cfg.update(over)
    return cfg


def _leaf_distances(got, want):
    out = {}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 0, jax.tree_util.keystr(path)
        out[jax.tree_util.keystr(path)] = float(
            jnp.sqrt(jnp.mean(jnp.square(g.astype(jnp.float32) - w))
                     / jnp.mean(jnp.square(w))))
    return out


_SIDES = {}


def _both_sides(builder, seed, seq, dtype):
    """The program's and the reference's loss and gradients on one seeded
    tree and sequence (kept: the tests below share them)."""
    key = (seed, seq, jnp.dtype(dtype).name)
    if key not in _SIDES:
        cfg = small()
        params = R.init_params(builder, cfg, seed)
        toks = R.make_tokens(cfg, seed, 0, 0, 1, seq)
        got = jax.jit(jax.value_and_grad(builder.make_loss_fn(
            cfg, seq, interpret=True, dtype=dtype)))(params, {"tokens": toks})
        want = R.loss_and_grads(builder, cfg)(params, toks)
        _SIDES[key] = cfg, params, toks, got, want
    return _SIDES[key]


def _program_losses(builder, cfg, seq):
    """``params -> (L_lm, [L_I])`` of the program in float32."""
    model = builder._make_model(cfg, seq, True, dtype=jnp.float32)
    return lambda p, toks: sparse_lm_losses(model, p, toks)


def _flipped_share(builder, cfg, params, toks, seq, dtype):
    got = jax.jit(builder.program_key_selections(
        cfg, seq, True, dtype=dtype))(params, toks)
    want = jax.jit(lambda p, t: builder.reference_key_selections(p, t, cfg))(
        params, toks)
    flips = [float(jnp.sum((g != 0) != w) / (2 * jnp.sum(w)))
             for g, w in zip(got, want)]
    return flips


# ---------------------------------------------------------- whole model

@pytest.mark.parametrize("seq", [48, 50], ids=["aligned", "padded"])
def test_program_against_reference_whole_float32(builder, seq):
    """float32 compute on both sides: the select kernel's bisection, the
    selected flash kernels and the loss kernel against ``jax.lax.top_k``, a
    masked softmax and the divergence written out: the loss, ``L_lm``, every
    ``L_I`` and every gradient leaf agree to float32's own error (1e-5; 1e-4
    of a leaf's rms), and the selected sets are equal row by row."""
    cfg, params, toks, (got_loss, got), (want_loss, want) = _both_sides(
        builder, 11, seq, jnp.float32)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    for name, dist in _leaf_distances(got, want).items():
        assert dist < 1e-4, (name, dist)
    losses = _program_losses(builder, cfg, seq)
    lm, kls = jax.jit(lambda p: losses(p, toks))(params)
    want_lm, want_kls = jax.jit(
        lambda p: builder.reference_losses(p, toks, cfg))(params)
    assert abs(float(lm) - float(want_lm)) < 1e-5
    assert len(kls) == len(want_kls) == 2
    for a, b in zip(kls, want_kls):
        assert float(b) > 1e-3 and abs(float(a) - float(b)) < 1e-5
    assert _flipped_share(builder, cfg, params, toks, seq,
                          jnp.float32) == [0.0, 0.0]


def test_program_in_bfloat16_stays_in_a_band_of_the_reference(builder):
    """bfloat16 compute against the float32 reference: the loss to 1e-3 and
    every gradient leaf within 0.5 of its rms, the limit the sparse
    configurations hold ``grad_vs_reference`` to on the chip and not the
    0.1 of the dense models' tests: over 48 tokens ONE key or expert that
    bfloat16 flips moves a leaf by tenths (read on four seeds: 0.07-0.22,
    with 0-0.5 % of a layer's selected keys flipped; the float32 test above
    holds the same program to 1e-4 with no flip)."""
    cfg, params, toks, (got_loss, got), (want_loss, want) = _both_sides(
        builder, 5, 48, jnp.bfloat16)
    assert abs(float(got_loss) - float(want_loss)) < 1e-3 * float(want_loss)
    flips = _flipped_share(builder, cfg, params, toks, 48, jnp.bfloat16)
    dists = _leaf_distances(got, want)
    worst = max(dists, key=dists.get)
    print(f"share of selected keys that flip, a layer: {flips}; worst leaf "
          f"{worst} {dists[worst]:.4f}")
    assert dists[worst] < 0.5, (worst, dists[worst])


@pytest.mark.parametrize("control", ["dense_attention", "no_indexer_loss",
                                     "topk_half"])
def test_the_reference_tells_a_program_without_the_selection(builder,
                                                             control):
    """Each switch of the reference reads over 0.8 of the worst leaf's rms:
    at least ten times what the sound float32 program reads against the
    sound reference (2e-6) and three times what the bfloat16 one does (0.07
    at 48 tokens on this seed, most of it a flipped key or expert; on the
    chip at 8,192 tokens the configuration's limit is set from
    ``control.py``'s readings); without the indexer's loss its leaves take
    no gradient at all (exactly 1)."""
    cfg, params, toks, (_, got), (_, want) = _both_sides(
        builder, 5, 48, jnp.bfloat16)
    _, _, _, (_, got32), _ = _both_sides(builder, 5, 48, jnp.float32)
    _, ctl = R.loss_and_grads(builder, cfg, builder.CONTROLS[control])(
        params, toks)
    reads = R.grad_distance(ctl, want)
    assert reads >= 0.8
    assert reads >= 10 * R.grad_distance(got32, want)
    assert reads >= 3 * R.grad_distance(got, want)
    if control == "no_indexer_loss":
        for leaf in jax.tree_util.tree_leaves(
                ctl["params"]["layer_0"]["attn"]["indexer"]):
            assert float(jnp.max(jnp.abs(leaf))) == 0.0


def test_the_tree_is_the_builders_tree(builder):
    cfg = small()
    model = builder._make_model(cfg, 48, True, dtype=jnp.float32)
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    mine = {jax.tree_util.keystr(k): tuple(v.shape) for k, v in
            jax.tree_util.tree_leaves_with_path(shapes)}
    theirs = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_leaves_with_path(
                  builder.param_shapes(cfg)["params"],
                  is_leaf=lambda x: isinstance(x, tuple))}
    assert mine == theirs
    assert builder.param_count(cfg) == sum(
        int(np.prod(s)) for s in theirs.values())


# ------------------------------------------- two objectives, disjoint leaves

def test_the_two_losses_have_disjoint_leaves_bit_for_bit(builder):
    """The indexers' leaves have zero gradient under ``L_lm`` alone, every
    other leaf zero under ``sum L_I`` alone, and the gradient of the sum is
    the two laid side by side, bit for bit."""
    cfg, params, toks, (_, total), _ = _both_sides(builder, 11, 48,
                                                   jnp.float32)
    losses = _program_losses(builder, cfg, 48)
    g_lm = jax.jit(jax.grad(lambda p: losses(p, toks)[0]))(params)
    g_kl = jax.jit(jax.grad(lambda p: sum(losses(p, toks)[1])))(params)
    seen = {True: 0, False: 0}
    for (path, t), a, b in zip(jax.tree_util.tree_leaves_with_path(total),
                               jax.tree_util.tree_leaves(g_lm),
                               jax.tree_util.tree_leaves(g_kl)):
        name = jax.tree_util.keystr(path)
        mine, other = (b, a) if "indexer" in name else (a, b)
        seen["indexer" in name] += 1
        assert float(jnp.max(jnp.abs(other))) == 0.0, name
        assert float(jnp.max(jnp.abs(mine))) > 0.0, name
        np.testing.assert_array_equal(np.asarray(t), np.asarray(mine), name)
    assert seen == {True: 2 * 5, False: len(jax.tree_util.tree_leaves(total))
                    - 10}


# ------------------------------------------------------ the experts' shares

def test_the_two_shares_of_four_experts_add_up_to_the_uncut_layer(builder):
    """At the small size: the program's layer over experts 0-3 plus its
    layer over experts 4-7 is the reference's expert layer holding all 8,
    under softmax routing with renormalised weights and no shared expert."""
    cfg = small(num_experts_held=8)
    w = builder._w(cfg)
    p = R.init_params(builder, cfg, 9)["params"]["layer_0"]["moe"]
    x = jax.random.normal(jax.random.key(1), (2, 24, w["E"]))

    def share(first):
        layer = RoutedMoEMLP(
            num_experts=8, top_k=2, mlp_dim=w["Fm"], held=(first, 4),
            score="softmax", route_norm=True, dtype=jnp.float32,
            interpret=True)
        mine = {"router": p["router"],
                **{n: p[n][first:first + 4]
                   for n in ("wi_gate", "wi_up", "wo")}}
        return layer.apply({"params": mine}, x)

    with jax.default_matmul_precision("highest"):
        whole = builder._experts(x, p, w, builder._same, builder._same)
        np.testing.assert_allclose(share(0) + share(4), whole, atol=2e-5)
    assert float(jnp.max(jnp.abs(whole))) > 1e-3


# ---------------------------------------------------------------- counters

def test_counters_go_up_once_a_step(builder):
    """One output a step carries the routed layers' counts and, beside
    them, the mean keys a query (x 1000) and the mean ``L_I`` (x 1e6);
    tracing a selected attention is counted on the host."""
    cfg = small()
    seq = 48
    params = R.init_params(builder, cfg, 13)
    toks = R.make_tokens(cfg, 13, 0, 0, 1, seq)
    traces = tracing.program_counters().get("sparse_attn_traces_total", 0)
    step = jax.jit(jax.value_and_grad(tracing.collect_counts(
        builder.make_loss_fn(cfg, seq, interpret=True, dtype=jnp.float32)),
        has_aux=True))
    jax.block_until_ready(step(params, {"tokens": toks}))     # compiled
    before = tracing.program_counters()
    assert before["sparse_attn_traces_total"] - traces == 2   # two layers
    (_, counts), _ = step(params, {"tokens": toks})
    assert [len(names) for names in counts.keys] == [4, 2]
    tracing.defer_program_counts(counts)
    tracing.settle_program_counts(wait=True)
    after = tracing.program_counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    keys = sum(min(t + 1, TOPK) for t in range(seq)) / seq
    assert delta["sparse_selected_keys_milli_total"] == pytest.approx(
        1e3 * keys, rel=1e-6)
    _, kls = _program_losses(builder, cfg, seq)(params, toks)
    assert delta["indexer_kl_micro_total"] == pytest.approx(
        1e6 * float(sum(kls)) / 2, rel=1e-5)
    assert delta["moe_pairs_routed_total"] == 2 * seq * 2
    assert delta["sparse_attn_traces_total"] == 0


def test_manager_metrics_report_the_sparse_counters():
    """``Manager.metrics()`` merges the program counters whatever their
    names."""
    import inspect

    from torchft_tpu import manager

    assert "program_counters()" in inspect.getsource(manager.Manager.metrics)


def test_sparse_lm_loss_needs_a_model_that_selects(builder):
    """A model whose layers sow no indexer loss (``sparse_topk=0``, or a
    rematerialised layer) is refused by name, not summed as zero."""
    import dataclasses

    from torchft_tpu.models import Transformer, sparse_lm_loss

    cfg = builder._make_model(small(), 48, True, dtype=jnp.float32).cfg
    model = Transformer(dataclasses.replace(cfg, sparse_topk=0))
    toks = jnp.zeros((1, 8), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), toks)
    with pytest.raises(ValueError, match="indexer losses"):
        jax.eval_shape(lambda p: sparse_lm_loss(model, p, toks), params)
