"""The SmallThinker decoder of PR 51 (``moe_route_input="mixer"``: a routed
layer's router reads the attention's normed input; ``moe_form="reglu"``;
28/4-style grouped heads at rep 7 under a window beside full layers without
rotary) against the benchmark builder's plain reference
(``smallthinker_decoder``): the whole small model, loss and every gradient
leaf, in float32 and in bfloat16; the reference telling a router that reads
the experts' input; the selections standing still when the attention's
weights move; the tree's names; the four shares of 64 experts adding up to
the uncut layer; the two counters; a step through ``FTTrainer`` and a
``Manager``, fused and split; what the field refuses; and the configuration
file's derived names."""

import dataclasses
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
    Transformer, chunked_causal_lm_loss, head_kernel, tiny_config)
from torchft_tpu.models.moe import (  # noqa: E402
    REGLU_COUNTER, ROUTE_AHEAD_COUNTER, RoutedMoEMLP)
from torchft_tpu.models.transformer import (  # noqa: E402
    DecoderLayer, TransformerConfig)

pytestmark = pytest.mark.heavy
SEQ = 64
CONFIG = os.path.join(REPO, "benchmarks/configs/smallthinker-21b-a3b.json")


@pytest.fixture(scope="module")
def builder():
    spec.configure(REPO)
    return spec.module("models", "smallthinker_decoder")


def small(builder, layers=(0, 1, 2, 3), **over):
    """The configuration's file at the rehearsal's widths (seven query
    heads on one key/value head, a window of 16 under 64 tokens), with a
    real selection (3 of 8, 4 held from the second on)."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(builder.REHEARSE)
    cfg.update(moe_num_primary_experts=8, moe_num_active_primary_experts=3,
               num_experts_held=4, first_expert_held=1,
               published_layers=list(layers), num_hidden_layers=len(layers))
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

# published layers: 0-3 is the cell's cut, one whole period (full without
# rotary, then three windowed with rotary); 3, 4 the turn into the next
# period; the kinds alone; and the period at 14 query heads on 2 key/value
# heads, the other way to a rep of 7
PATTERNS = {"period": ((0, 1, 2, 3), {}), "next_period": ((3, 4), {}),
            "full_only": ((0,), {}), "window_only": ((2,), {}),
            "period_14_on_2": ((0, 1, 2, 3), dict(
                num_attention_heads=14, num_key_value_heads=2, head_dim=16))}


@pytest.mark.parametrize("which", list(PATTERNS), ids=list(PATTERNS))
def test_program_against_reference_whole_float32(builder, which):
    """float32 compute on both sides, the program's flash kernels at rep 7,
    its softmax over all experts divided by the selection's sum, routed
    passes in the ReGLU form and chunked head against the reference's plain
    softmax under its mask, top-k-then-softmax, loop over experts and full
    logits: the loss and every gradient leaf agree to float32's own error
    (1e-5 on the loss; 1e-4 of a leaf's rms), the router's kernel and the
    input norm, which take the router's gradient, among them."""
    layers, over = PATTERNS[which]
    cfg = small(builder, layers, **over)
    w = builder._w(cfg)
    assert w["H"] // w["Hkv"] == 7 and w["window"] < SEQ
    if which == "period":
        assert w["kinds"] == ["full_attention"] + ["sliding_attention"] * 3
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
    tokens): the loss to 1e-3 and every gradient leaf within 0.15 of its
    rms (read at 0.08 and below: a ReLU gate flips where a SiLU bends)."""
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
    assert worst < 0.15, worst


def test_the_reference_tells_a_router_that_reads_the_experts_input(builder):
    """``route_late`` (the reference's router reading ``u``, the stream
    after attention, where the model's reads ``h``) is far from the sound
    reference: other tokens reach other experts, and no precision does
    that. A program that ignored ``moe_route_input`` matches it instead."""
    cfg = small(builder)
    params = R.init_params(builder, cfg, 7)
    toks = R.make_tokens(cfg, 7, 0, 0, 1, SEQ)
    _, want = R.loss_and_grads(builder, cfg)(params, toks)
    _, late = R.loss_and_grads(builder, cfg,
                               builder.CONTROLS["route_late"])(params, toks)
    assert R.grad_distance(late, want) > 0.5
    sound = builder.reference_selections(params, toks, cfg)
    moved = builder.reference_selections(
        params, toks, cfg, {"route_late": lambda x: x})
    assert len(sound) == 4
    assert all((np.asarray(a) != np.asarray(b)).mean() > 0.2
               for a, b in zip(sound, moved))
    # the program with the field at its default IS the late router
    model = builder._make_model(cfg, SEQ, True, dtype=jnp.float32)
    default = Transformer(dataclasses.replace(model.cfg,
                                              moe_route_input="mlp"))

    def loss(p, batch):
        hidden = default.apply(p, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(hidden, head_kernel(p),
                                      batch["tokens"])

    _, got = jax.jit(jax.value_and_grad(loss))(params, {"tokens": toks})
    assert R.grad_distance(got, late) < 1e-4
    assert R.grad_distance(got, want) > 0.5


def test_the_programs_selections_are_the_references(builder):
    cfg = small(builder)
    params = R.init_params(builder, cfg, 9)
    toks = R.make_tokens(cfg, 9, 0, 0, 1, SEQ)
    mine = builder.program_selections(cfg, SEQ, True)(params, toks)
    theirs = builder.reference_selections(params, toks, cfg)
    # bfloat16 activations above a float32 router: all but a few pairs
    same = np.mean([np.mean(np.sort(np.asarray(a), -1)
                            == np.sort(np.asarray(b), -1))
                    for a, b in zip(mine, theirs)])
    assert same > 0.9, same


# ------------------------------ the routing does not wait for the mixer

def _layer(route_input, **over):
    kw = dict(vocab_size=64, num_layers=1, embed_dim=32, num_heads=7,
              num_kv_heads=1, attn_head_dim=8, max_seq_len=SEQ,
              dtype=jnp.float32, moe_experts=8, moe_top_k=3,
              moe_dispatch="routed", moe_dim=16, moe_held=(1, 4),
              moe_score="softmax", moe_form="reglu", moe_interpret=True,
              moe_route_input=route_input)
    kw.update(over)
    return DecoderLayer(TransformerConfig(**kw), moe=True)


@pytest.mark.parametrize("route_input", ["mixer", "mlp"])
def test_selections_stand_still_when_the_attention_moves(route_input):
    """Routed on the mixer's input, the selection is a function of the
    layer's input and the input norm alone: other attention weights, same
    experts. Routed on the MLP's input (every other configuration) they
    move with the attention's output."""
    layer = _layer(route_input)
    x = jax.random.normal(jax.random.key(1), (2, SEQ, 32))
    pos = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    params = layer.init(jax.random.key(0), x, pos)["params"]

    def picks(p):
        _, state = layer.apply({"params": p}, x, pos,
                               mutable=["intermediates"])
        return np.asarray(state["intermediates"]["moe"]["experts"][0])

    other = {**params, "attn": jax.tree_util.tree_map(
        lambda a: 3.0 * a[::-1], params["attn"])}
    moved = (picks(params) != picks(other)).mean()
    if route_input == "mixer":
        assert moved == 0.0
    else:
        assert moved > 0.05
    out, counts = layer.apply({"params": params}, x, pos)
    assert (ROUTE_AHEAD_COUNTER in counts) == (route_input == "mixer")
    assert 0.0 < float(counts[REGLU_COUNTER]) < 1e6


def test_the_routers_gradient_reaches_the_mixers_input_not_the_mlps():
    """The layer is, gradient for gradient, a layer put together by hand
    that hands ``h`` (the attention's normed input) to the expert layer as
    what its router reads; handing it ``u`` gives other gradients on both
    norms' gains: the router's part lands on the input norm, not on the
    MLP's."""
    from torchft_tpu.models.transformer import Attention, RMSNorm

    layer = _layer("mixer")
    x = jax.random.normal(jax.random.key(2), (1, SEQ, 32))
    pos = jnp.broadcast_to(jnp.arange(SEQ), (1, SEQ))
    params = layer.init(jax.random.key(0), x, pos)["params"]

    def by_hand(p, read_h):
        eps = layer.cfg.rms_norm_eps
        h = RMSNorm(eps).apply({"params": p["attn_norm"]}, x)
        x1 = x + Attention(layer.cfg).apply({"params": p["attn"]}, h, pos)
        u = RMSNorm(eps).apply({"params": p["mlp_norm"]}, x1)
        m = RoutedMoEMLP(num_experts=8, mlp_dim=16, top_k=3, held=(1, 4),
                         score="softmax", form="reglu", dtype=jnp.float32,
                         interpret=True).apply(
                             {"params": p["moe"]}, u,
                             route_on=h if read_h else u)
        return x1 + m

    def grads(fn):
        return jax.grad(lambda p: jnp.sum(jnp.sin(fn(p))))(params)

    got = grads(lambda p: layer.apply({"params": p}, x, pos)[0])
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(
                        grads(lambda p: by_hand(p, True)))):
        np.testing.assert_allclose(a, b, atol=1e-6)
    late = grads(lambda p: by_hand(p, False))
    for norm in ("attn_norm", "mlp_norm"):
        assert float(jnp.max(jnp.abs(
            got[norm]["scale"] - late[norm]["scale"]))) > 1e-4


# ------------------------------------------------------------ the tree

def test_the_tree_is_the_builders(builder):
    """The program's tree is the builder's, name for name: two norms a
    layer, four attention leaves (no head norms, no gate), ``moe`` with the
    router and three stacks (no shared expert) in every layer, an
    ``lm_head`` of its own."""
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
        f"layer_{i}" for i in range(4)] + ["lm_head"]
    for i in range(4):
        assert sorted(shapes[f"layer_{i}"]) == ["attn", "attn_norm",
                                                "mlp_norm", "moe"]
        assert sorted(shapes[f"layer_{i}"]["attn"]) == ["k", "o", "q", "v"]
        assert sorted(shapes[f"layer_{i}"]["moe"]) == [
            "router", "wi_gate", "wi_up", "wo"]
    assert len(mine) == 4 * 10 + 3
    assert sum(int(np.prod(s)) for s in mine.values()) \
        == builder.param_count(cfg)


@pytest.mark.parametrize("held,count", [(16, 559_290_880),
                                        (8, 370_547_200)])
def test_the_published_cut_has_its_parameter_count(builder, held, count):
    """16 of 64 held as the cell runs it; 8 is ISSUE 51's fallback."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["num_experts_held"] == 16
    cfg["num_experts_held"] = held
    assert builder.param_count(cfg) == count
    leaves = jax.tree_util.tree_leaves(
        builder.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in leaves) == count
    assert len(leaves) == 43


def test_the_files_derived_names_are_their_published_keys(builder):
    """What the accepted kernel files read (``layer_types``,
    ``sliding_window``, ``moe_intermediate_size``, ``num_dense_layers``) is
    in the file beside the published key it comes from, and the builder
    refuses a file in which the two differ."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    for key, value in builder.derived(cfg).items():
        assert cfg[key] == value, key
        assert key in cfg["derived"]
    assert cfg["layer_types"] == (["full_attention"]
                                  + ["sliding_attention"] * 3) * 13
    assert cfg["sliding_window_layout"] == cfg["rope_layout"]
    assert cfg["published_layers"] == [0, 1, 2, 3]
    with pytest.raises(ValueError, match="sliding_window"):
        builder._w({**cfg, "sliding_window": 2048})
    with pytest.raises(ValueError, match="rotary"):
        builder._w({**cfg, "rope_layout": [1] * 52})
    with pytest.raises(ValueError, match="softmax"):
        builder._w({**cfg, "norm_topk_prob": False})
    # by shapes at the cell's 8,192 tokens (ISSUE 51's arithmetic)
    assert round(builder.forward_flops_per_token(cfg, 8192)) == 527_959_552
    parts = builder.layer_forward_flops(cfg, 8192)
    assert round(sum(p["routed"] for p in parts)) == 4 * 17_694_720


# ------------------------------------------------------ the shares add up

def test_the_four_shares_of_64_experts_add_up_to_the_uncut_layer(builder):
    """64 routed ReGLU experts of width 8 (6 a token, softmax over the
    selected, no shared expert) cut into 4 shares of 16, as the deployment
    cuts them over its chips, routed on one stream and computed on another:
    the parts the program's layer gives for the four shares add up to the
    reference's uncut layer. float32 at the highest precision: 2e-5 is the
    sums' order."""
    n, k, d, width, shares = 64, 6, 16, 8, 4
    held = n // shares
    key = jax.random.split(jax.random.key(6), 6)

    def mat(i, *shape):
        return 0.3 * jax.random.normal(key[i], shape)

    full = {"router": {"kernel": 0.5 * jax.random.normal(key[0], (d, n))},
            "wi_gate": mat(1, n, d, width), "wi_up": mat(2, n, d, width),
            "wo": mat(3, n, width, d)}
    u = jax.random.normal(key[4], (1, 24, d))
    h = jax.random.normal(key[5], (1, 24, d))
    w = dict(K=k, first=0, held=n)
    with jax.default_matmul_precision("highest"):
        whole = builder.experts_share(u, h, full, w)
        late = builder.experts_share(u, u, full, w)
    assert float(jnp.max(jnp.abs(whole))) > 1e-3
    assert float(jnp.max(jnp.abs(whole - late))) > 1e-3

    def share(first):
        return {**full, **{name: full[name][first:first + held]
                           for name in ("wi_gate", "wi_up", "wo")}}

    def part(first):
        layer = RoutedMoEMLP(num_experts=n, mlp_dim=width, top_k=k,
                             held=(first, held), shared_dim=0,
                             score="softmax", form="reglu",
                             dtype=jnp.float32, interpret=True)
        with jax.default_matmul_precision("highest"):
            return layer.apply({"params": share(first)}, u, route_on=h)

    parts = [part(s * held) for s in range(shares)]
    assert all(float(jnp.max(jnp.abs(p))) > 1e-3 for p in parts)
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
    # and the reference's own shares do
    with jax.default_matmul_precision("highest"):
        theirs = sum(builder.experts_share(
            u, h, share(s * held), {**w, "first": s * held, "held": held})
            for s in range(shares))
    np.testing.assert_allclose(theirs, whole, atol=2e-5)


# -------------------------------------------------------------- counters

@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_counters_go_up_once_a_step(builder, remat):
    """One output a step carries the routed layers' counts, the layers
    routed ahead and the ReGLU's active share out of a collecting program,
    under remat too."""
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
    assert delta[ROUTE_AHEAD_COUNTER] == 4
    # the step's mean over four layers of a share near a half, in millionths
    assert 400_000 < delta[REGLU_COUNTER] < 600_000
    assert delta["moe_pairs_routed_total"] == 4 * 2 * SEQ * 3
    assert 0 < delta["moe_pairs_local_total"] < delta["moe_pairs_routed_total"]


# ------------------------------------- through FTTrainer and a Manager

def _smallthinker(**over):
    kw = dict(vocab_size=256, num_layers=2, embed_dim=64, num_heads=7,
              num_kv_heads=1, attn_head_dim=16, hidden_dim=32,
              max_seq_len=SEQ, dtype=jnp.float32, rope_theta=1.5e6,
              layer_types=("full_attention", "sliding_attention"),
              sliding_window=16, rope_full_layers=False,
              moe_experts=8, moe_top_k=3, moe_dispatch="routed", moe_dim=32,
              moe_held=(0, 4), moe_score="softmax", moe_form="reglu",
              moe_route_input="mixer", moe_interpret=True)
    kw.update(over)
    return TransformerConfig(**kw)


def _lm_loss(model):
    def loss_fn(p, batch):
        hidden = model.apply(p, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(hidden, head_kernel(p),
                                      batch["tokens"])

    return loss_fn


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
def test_a_smallthinker_model_trains_through_fttrainer_and_a_manager(fused):
    """``TransformerConfig(moe_form="reglu", moe_route_input="mixer")`` on
    the normal path, by configuration alone: a quorum, a step (the
    one-group fused program, or forward/backward and the update apart) and
    a commit on the mocked control plane; every leaf moves, the routers
    among them, and the two counters reach ``Manager.metrics()``."""
    from torchft_tpu.parallel import FTTrainer

    model = Transformer(_smallthinker())
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}
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
        assert trainer._predict_single is fused
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), before,
            trainer.params)
        assert all(v > 0 for v in jax.tree_util.tree_leaves(moved))
        metrics = trainer.manager.metrics()
        delta = {k: metrics[k] - counted.get(k, 0.0) for k in (
            ROUTE_AHEAD_COUNTER, REGLU_COUNTER, "moe_pairs_routed_total")}
        assert delta[ROUTE_AHEAD_COUNTER] == 2
        assert 0 < delta[REGLU_COUNTER] < 1e6
        assert delta["moe_pairs_routed_total"] == 2 * 2 * SEQ * 3
        trainer.manager._client.quorum.assert_called()
        trainer.manager._client.should_commit.assert_called()
    finally:
        trainer.shutdown()


# ------------------------------------------------- what the field refuses

def test_the_field_is_the_two_norm_routed_layers():
    toks = jnp.zeros((1, 8), jnp.int32)

    def init(**kw):
        return jax.eval_shape(Transformer(tiny_config(**kw)).init,
                              jax.random.key(0), toks)

    moe = dict(moe_experts=4, moe_top_k=2, moe_interpret=True)
    with pytest.raises(ValueError, match="moe_route_input"):
        init(moe_route_input="attention", moe_dispatch="routed", **moe)
    # a block of one norm and the experts alone has one input
    with pytest.raises(ValueError, match="no mixer"):
        init(moe_route_input="mixer", moe_dispatch="routed",
             layer_types=("attention", "moe"), **moe)
    # the dense-dispatch layer has no such argument
    with pytest.raises(ValueError, match="routed expert layer"):
        init(moe_route_input="mixer", moe_dispatch="dense", **moe)
    with pytest.raises(ValueError, match="routed expert"):
        init(moe_form="reglu")
    # leading dense layers pass; the default is today's
    init(moe_route_input="mixer", moe_dispatch="routed", moe_dense_layers=1,
         **moe)
    assert TransformerConfig().moe_route_input == "mlp"


def test_a_prediction_modules_layer_routes_ahead_too():
    """``MTPModule`` builds its layer from the same class: with the field
    set its expert layer reads the mixer's input and is counted, and the
    active share stays a mean over the three expert layers."""
    cfg = tiny_config(num_layers=2, moe_experts=4, moe_top_k=2,
                      moe_dispatch="routed", moe_dim=32, moe_form="reglu",
                      moe_score="softmax", moe_route_input="mixer",
                      moe_interpret=True, mtp_layers=1, dtype=jnp.float32)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.key(1), (1, 32), 0, cfg.vocab_size)
    params = model.init(jax.random.key(0), toks, return_mtp=True)
    _, _, counts = model.apply(params, toks, return_mtp=True)
    assert int(counts[ROUTE_AHEAD_COUNTER]) == 3
    assert 0 < float(counts[REGLU_COUNTER]) < 1e6
    assert cfg.moe_layers == 3
