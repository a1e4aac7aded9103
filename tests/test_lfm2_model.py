"""The LFM2 decoder of PR 47 (``models/short_conv.py``, the two-norm layer
kind ``"conv"``, ``tie_embeddings``) against the benchmark builder's plain
reference (``lfm2_moe_decoder``): the whole small model, loss and every
gradient leaf, in float32 and in bfloat16; the tied leaf's gradient as the
sum of the gather's and the head's parts; the tree's names; the four shares
of 32 experts adding up to the uncut layer; the reference telling a
convolution that lost its history; a step through ``FTTrainer`` and a
``Manager``, fused and split; and the other configurations' trees with the
new options off."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from harness import reference as R  # noqa: E402
from harness import spec  # noqa: E402
from mockplane import make_manager, quorum_result  # noqa: E402

from torchft_tpu import tracing  # noqa: E402
from torchft_tpu.models import (  # noqa: E402
    Transformer, causal_lm_loss, chunked_causal_lm_loss, head_kernel,
    moe_lm_loss, tiny_config)
from torchft_tpu.models.moe import RoutedMoEMLP  # noqa: E402
from torchft_tpu.models.transformer import TransformerConfig  # noqa: E402

pytestmark = pytest.mark.heavy
SEQ = 64
CONFIG = os.path.join(REPO, "benchmarks/configs/lfm2-8b-a1b.json")


@pytest.fixture(scope="module")
def builder():
    spec.configure(REPO)
    return spec.module("models", "lfm2_moe_decoder")


def small(builder, layers=(0, 2, 3, 4, 5), **over):
    """The configuration's file at the rehearsal's widths, with a real
    selection (3 of 8, 4 held from the second on)."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(builder.REHEARSE)
    cfg.update(num_experts=8, num_experts_per_tok=3, num_experts_held=4,
               first_expert_held=1, published_layers=list(layers),
               num_hidden_layers=len(layers))
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


# ---------------------------------------------------------- whole model

# published layers: 0, 2-5 is the cell's cut (a leading dense conv layer and
# one period: attention, conv, conv, conv); 1, 5, 6 a dense conv layer and
# the turn into the next period (conv, attention); the kinds alone
PATTERNS = {"cut": (0, 2, 3, 4, 5), "next_period": (1, 5, 6),
            "conv_only": (0, 3), "attention_only": (2,)}


@pytest.mark.parametrize("which", list(PATTERNS), ids=list(PATTERNS))
def test_program_against_reference_whole_float32(builder, which):
    """float32 compute on both sides, the program's fused convolution,
    flash kernel at its head size, routed passes and tied chunked head
    against the reference's shifted copies, plain softmax, loop over experts
    and ``x E^T``: the loss and every gradient leaf agree to float32's own
    error (1e-5 on the loss; 1e-4 of a leaf's rms)."""
    cfg = small(builder, PATTERNS[which])
    w = builder._w(cfg)
    if which == "cut":
        assert w["kinds"] == ["conv", "full_attention", "conv", "conv",
                              "conv"]
        assert w["dense"] == [True, False, False, False, False]
    params = R.init_params(builder, cfg, 11)
    toks = R.make_tokens(cfg, 11, 0, 0, 1, SEQ)
    loss_fn = builder.make_loss_fn(cfg, SEQ, interpret=True,
                                   dtype=jnp.float32)
    got_loss, got = jax.jit(jax.value_and_grad(loss_fn))(
        params, {"tokens": toks})
    want_loss, want = R.loss_and_grads(builder, cfg)(params, toks)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    for name, dist in _leaf_distances(got, want).items():
        assert dist < 1e-4, (name, dist)


def test_program_in_bfloat16_stays_in_a_band_of_the_reference(builder):
    """bfloat16 compute against the float32 reference with every expert
    selected (the rehearsal's sizes: a flipped selection would swamp 64
    tokens): the loss to 1e-3 and every gradient leaf within 0.1 of its rms
    (read at 0.02 and below)."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(builder.REHEARSE)
    params = R.init_params(builder, cfg, 5)
    toks = R.make_tokens(cfg, 5, 0, 0, 1, SEQ)
    got_loss, got = jax.jit(jax.value_and_grad(
        builder.make_loss_fn(cfg, SEQ, interpret=True)))(
            params, {"tokens": toks})
    want_loss, want = R.loss_and_grads(builder, cfg)(params, toks)
    assert abs(float(got_loss) - float(want_loss)) < 1e-3 * float(want_loss)
    worst = max(_leaf_distances(got, want).values())
    assert worst < 0.1, worst


def test_the_reference_tells_a_convolution_that_lost_its_history(builder):
    """``drop_taps`` (the reference's convolution reading the current token
    only) is far from the sound reference at the harness's own seeding: the
    convolution's own leaf alone reads over 1 (two of its three taps take
    no gradient there), and no precision does that."""
    cfg = small(builder)
    params = R.init_params(builder, cfg, 7)
    toks = R.make_tokens(cfg, 7, 0, 0, 1, SEQ)
    _, want = R.loss_and_grads(builder, cfg)(params, toks)
    _, got = R.loss_and_grads(builder, cfg, builder.CONTROLS["drop_taps"])(
        params, toks)
    assert R.grad_distance(want, got) > 1.0
    taps = got["params"]["layer_0"]["attn"]["conv"]
    assert float(jnp.max(jnp.abs(taps[:2]))) == 0.0
    assert float(jnp.max(jnp.abs(taps[2]))) > 0.0


# ------------------------------------------------------------ the tree

def test_the_tree_is_the_builders_and_has_no_lm_head(builder):
    """The program's tree is the builder's, name for name: two norms a
    layer, the mixer under ``attn`` (three leaves for a convolution, six
    for attention with its head norms), ``mlp`` in the dense layer and
    ``moe`` (router and three stacks, no shared expert) after it, and no
    ``lm_head``."""
    cfg = small(builder)
    model = builder._make_model(cfg, SEQ, True)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))["params"]
    mine = {jax.tree_util.keystr(k): tuple(v.shape) for k, v in
            jax.tree_util.tree_leaves_with_path({"params": shapes})}
    theirs = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_leaves_with_path(
                  builder.param_shapes(cfg),
                  is_leaf=lambda x: isinstance(x, tuple))}
    assert mine == theirs
    assert sorted(shapes) == ["embed", "final_norm"] + [
        f"layer_{i}" for i in range(5)]
    assert sorted(shapes["layer_0"]) == ["attn", "attn_norm", "mlp",
                                         "mlp_norm"]
    assert sorted(shapes["layer_0"]["attn"]) == ["conv", "in_proj",
                                                 "out_proj"]
    assert sorted(shapes["layer_1"]) == ["attn", "attn_norm", "mlp_norm",
                                         "moe"]
    assert sorted(shapes["layer_1"]["attn"]) == ["k", "k_norm", "o", "q",
                                                 "q_norm", "v"]
    assert sorted(shapes["layer_2"]["moe"]) == ["router", "wi_gate",
                                                "wi_up", "wo"]
    assert len(mine) == 49
    assert sum(int(np.prod(s)) for s in mine.values()) \
        == builder.param_count(cfg)


def test_the_published_cut_has_its_parameter_count(builder):
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert builder.param_count(cfg) == 507_820_160
    leaves = jax.tree_util.tree_leaves(
        builder.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in leaves) == 507_820_160
    assert len(leaves) == 49


# ------------------------------------------------------- the tied leaf

def _tied(**over):
    kw = dict(vocab_size=96, num_layers=2, embed_dim=32, num_heads=4,
              num_kv_heads=2, max_seq_len=SEQ, dtype=jnp.float32,
              layer_types=("conv", "full_attention"), linear_conv_kernel=3,
              qk_norm=True, tie_embeddings=True)
    kw.update(over)
    return TransformerConfig(**kw)


def test_the_tied_leafs_gradient_is_the_gathers_part_plus_the_heads():
    """One ``[vocab, hidden]`` leaf, two sites: with the table handed to
    the gather and to the head as two arguments, the gradient of the tied
    model's loss is the sum of the two arguments' gradients, and neither
    part is zero."""
    model = Transformer(_tied())
    toks = jax.random.randint(jax.random.key(1), (2, 24), 0, 96)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}
    assert "lm_head" not in params["params"]

    def loss(p):
        hidden = model.apply(p, toks, return_hidden=True)
        return chunked_causal_lm_loss(hidden, head_kernel(p), toks)

    def two_sites(gathered, read):
        p = {"params": {**params["params"],
                        "embed": {"embedding": gathered}}}
        hidden = model.apply(p, toks, return_hidden=True)
        return chunked_causal_lm_loss(hidden, read.T, toks)

    table = params["params"]["embed"]["embedding"]
    tied = jax.grad(loss)(params)["params"]["embed"]["embedding"]
    gather, head = jax.grad(two_sites, argnums=(0, 1))(table, table)
    assert float(jnp.max(jnp.abs(gather))) > 1e-6
    assert float(jnp.max(jnp.abs(head))) > 1e-6
    np.testing.assert_allclose(tied, gather + head, atol=1e-7)
    # a row no token selects takes the head's part alone
    unseen = np.setdiff1d(np.arange(96), np.asarray(toks[:, :-1]))
    assert unseen.size
    np.testing.assert_array_equal(np.asarray(gather)[unseen], 0.0)


@pytest.mark.parametrize("path", ["logits", "chunked", "moe_lm_loss"])
def test_every_loss_of_a_tied_model_reads_the_table(path):
    """The full-logits call, the chunked loss over :func:`head_kernel` and
    ``moe_lm_loss`` agree on a tied model, loss and gradients."""
    model = Transformer(_tied())
    toks = jax.random.randint(jax.random.key(2), (2, 24), 0, 96)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}
    table = params["params"]["embed"]["embedding"]

    def want(p):
        hidden = model.apply(p, toks, return_hidden=True)
        logits = hidden.astype(jnp.float32) @ p["params"]["embed"][
            "embedding"].T
        return causal_lm_loss(logits, toks)

    fns = {"logits": lambda p: causal_lm_loss(model.apply(p, toks), toks),
           "chunked": lambda p: chunked_causal_lm_loss(
               model.apply(p, toks, return_hidden=True), head_kernel(p),
               toks, chunk_size=8),
           "moe_lm_loss": lambda p: moe_lm_loss(model, p, toks)}
    assert model.apply(params, toks).shape == (2, 24, 96)
    np.testing.assert_array_equal(head_kernel(params), table.T)
    np.testing.assert_array_equal(head_kernel(params["params"]), table.T)
    got_loss, got = jax.value_and_grad(fns[path])(params)
    want_loss, want_g = jax.value_and_grad(want)(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(g, w, atol=2e-6)


def test_an_untied_models_head_kernel_is_its_lm_head():
    model = Transformer(_tied(tie_embeddings=False))
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), toks)
    assert head_kernel(params) is params["params"]["lm_head"]["kernel"]
    assert head_kernel(params).shape == (32, 96)


# ------------------------------------------------------ the shares add up

def test_the_four_shares_of_32_experts_add_up_to_the_uncut_layer(builder):
    """32 routed SwiGLU experts of width 8 (4 a token, sigmoid scores,
    normalised, scale 1, no shared expert) cut into 4 shares of 8, as the
    deployment cuts them over its chips: the parts the program's layer
    gives for the four shares add up to the reference's uncut layer.
    float32 at the highest precision: 2e-5 is the sums' order."""
    n, k, d, width, shares = 32, 4, 16, 8, 4
    held = n // shares
    key = jax.random.split(jax.random.key(6), 5)

    def mat(i, *shape):
        return 0.2 * jax.random.normal(key[i], shape)

    full = {"router": {"kernel": 0.5 * jax.random.normal(key[0], (d, n))},
            "wi_gate": mat(1, n, d, width), "wi_up": mat(2, n, d, width),
            "wo": mat(3, n, width, d)}
    u = jax.random.normal(key[4], (1, 24, d))
    w = dict(K=k, route_norm=True, scale=1.0, first=0, held=n)
    with jax.default_matmul_precision("highest"):
        whole = builder.experts_share(u, full, w)
    assert float(jnp.max(jnp.abs(whole))) > 1e-3

    def part(first):
        layer = RoutedMoEMLP(num_experts=n, mlp_dim=width, top_k=k,
                             held=(first, held), shared_dim=0,
                             score="sigmoid", route_scale=1.0,
                             dtype=jnp.float32, interpret=True)
        mine = {**full, **{name: full[name][first:first + held]
                           for name in ("wi_gate", "wi_up", "wo")}}
        with jax.default_matmul_precision("highest"):
            return layer.apply({"params": mine}, u)

    parts = [part(s * held) for s in range(shares)]
    assert all(float(jnp.max(jnp.abs(p))) > 1e-3 for p in parts)
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
    # and the reference's own shares do
    with jax.default_matmul_precision("highest"):
        theirs = sum(builder.experts_share(
            u, {**full, **{name: full[name][s * held:(s + 1) * held]
                           for name in ("wi_gate", "wi_up", "wo")}},
            {**w, "first": s * held, "held": held}) for s in range(shares))
    np.testing.assert_allclose(theirs, whole, atol=2e-5)


# -------------------------------------------------------------- counters

@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_counters_go_up_once_a_step(builder, remat):
    """One output a step carries the conv mixers' two numbers and the
    routed layers' counts out of a collecting program, under remat too."""
    cfg = small(builder)
    params = R.init_params(builder, cfg, 13)
    toks = R.make_tokens(cfg, 13, 0, 0, 2, SEQ)
    step = jax.jit(jax.value_and_grad(tracing.collect_counts(
        builder.make_loss_fn(cfg, SEQ, interpret=True, dtype=jnp.float32,
                             remat=remat)), has_aux=True))
    jax.block_until_ready(step(params, {"tokens": toks}))     # compiled
    before = tracing.program_counters()
    (_, counts), _ = step(params, {"tokens": toks})
    tracing.defer_program_counts(counts)
    tracing.settle_program_counts(wait=True)
    after = tracing.program_counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    # four conv layers x 2 sequences x 64 tokens
    assert delta["shortconv_tokens_total"] == 4 * 2 * SEQ
    # rms of the gated convolution's output at the harness's seeding, the
    # step's mean over the four mixers, in millionths: three streams of
    # sqrt(128) x 0.02 and three taps of 0.02, about 4e-4
    assert 100 < delta["shortconv_out_rms_micro_total"] < 1e4
    assert delta["moe_pairs_routed_total"] == 4 * 2 * SEQ * 3
    assert 0 < delta["moe_pairs_local_total"] < delta["moe_pairs_routed_total"]


# ------------------------------------- through FTTrainer and a Manager

def _lfm2(**over):
    kw = dict(vocab_size=256, num_layers=3, embed_dim=64, num_heads=4,
              num_kv_heads=2, hidden_dim=128, max_seq_len=SEQ,
              dtype=jnp.float32, rope_theta=1e6,
              layer_types=("conv", "full_attention", "conv"),
              linear_conv_kernel=3, qk_norm=True, tie_embeddings=True,
              moe_experts=8, moe_top_k=2, moe_dispatch="routed", moe_dim=32,
              moe_held=(0, 4), moe_score="sigmoid", moe_dense_layers=1,
              moe_interpret=True)
    kw.update(over)
    return TransformerConfig(**kw)


def _lm_loss(model):
    def loss_fn(p, batch):
        hidden = model.apply(p, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(hidden, head_kernel(p),
                                      batch["tokens"])

    return loss_fn


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
def test_an_lfm2_model_trains_through_fttrainer_and_a_manager(fused):
    """``TransformerConfig(layer_types=("conv", "full_attention", ...),
    tie_embeddings=True)`` on the normal path, by configuration alone: a
    quorum, a step (the one-group fused program, or forward/backward and
    the update apart) and a commit on the mocked control plane; every new
    leaf moves, the table among them, and the counters reach
    ``Manager.metrics()``."""
    from torchft_tpu.parallel import FTTrainer

    model = Transformer(_lfm2())
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}
    assert "lm_head" not in params["params"]
    quorum = quorum_result(max_world_size=1 if fused else 2,
                           replica_world_size=1 if fused else 2)
    counted = tracing.program_counters()     # process-wide totals
    trainer = FTTrainer(
        loss_fn=_lm_loss(model), tx=optax.adamw(3e-4), params=params,
        manager_factory=lambda load, save: make_manager(
            quorum=quorum, load_state_dict=load, state_dict=save,
            min_replica_size=1))
    try:
        before = jax.tree_util.tree_map(np.asarray, trainer.params)
        loss, committed = trainer.train_step({"tokens": toks})
        jax.block_until_ready(trainer.params)
        assert committed and np.isfinite(float(loss))
        assert abs(float(loss) - np.log(256)) < 1.0
        # which program ran: the fused one with one group, else the split
        assert trainer._predict_single is fused
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), before,
            trainer.params)
        assert all(v > 0 for v in jax.tree_util.tree_leaves(moved))
        metrics = trainer.manager.metrics()
        delta = {k: metrics[k] - counted.get(k, 0.0) for k in (
            "shortconv_tokens_total", "shortconv_out_rms_micro_total",
            "moe_pairs_routed_total")}
        assert delta["shortconv_tokens_total"] == 2 * 2 * SEQ
        assert delta["shortconv_out_rms_micro_total"] > 0
        assert delta["moe_pairs_routed_total"] == 2 * 2 * SEQ * 2
        trainer.manager._client.quorum.assert_called()
        trainer.manager._client.should_commit.assert_called()
    finally:
        trainer.shutdown()


# ---- the other configurations' trees, with the new options off

def test_with_the_new_options_off_the_tree_has_its_lm_head():
    """``tie_embeddings=False`` and no ``"conv"`` layer: the tree every
    accepted configuration builds (``tests/golden_*.json`` hold its bits in
    ``test_mamba2_model.py`` and ``test_gdn_golden``), an ``lm_head`` of
    ``[hidden, vocab]`` among its leaves."""
    cfg = tiny_config(tie_embeddings=False)
    toks = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.key(0),
                            toks)["params"]
    assert shapes["lm_head"]["kernel"].shape == (cfg.embed_dim,
                                                 cfg.vocab_size)
    tied = jax.eval_shape(Transformer(tiny_config(tie_embeddings=True)).init,
                          jax.random.key(0), toks)["params"]
    assert sorted(set(shapes) - set(tied)) == ["lm_head"]
    assert TransformerConfig().tie_embeddings is False
