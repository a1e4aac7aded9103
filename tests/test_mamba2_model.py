"""The single-sub-block decoder of PR 45 (``models/mamba2.py``, the layer
kinds ``"mamba"``, ``"moe"`` and ``"attention"`` with one norm each, relu^2
experts) against the benchmark builder's plain reference
(``mamba2_moe_decoder``), whole and by pattern; the 16 shares of a
128-expert relu^2 block adding up to the uncut block; the tree's names;
the counters once a step; a step through ``FTTrainer`` and a ``Manager``;
the tensor-parallel rules for the mixer's leaves; and the blocks the other
configurations run, bitwise what they were with the new options off."""

import functools
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
    Mamba2Mixer, Transformer, chunked_causal_lm_loss, tiny_config, tp_rules)
from torchft_tpu.models.moe import RoutedMoEMLP  # noqa: E402
from torchft_tpu.models.transformer import TransformerConfig  # noqa: E402
from torchft_tpu.ops import flash_attention  # noqa: E402

pytestmark = pytest.mark.heavy
SEQ = 256          # two chunks of the scan: a state is carried


@pytest.fixture(scope="module")
def builder():
    spec.configure(REPO)
    return spec.module("models", "mamba2_moe_decoder")


def small(builder, layers=tuple(range(7)), **over):
    """The configuration's file at the rehearsal's widths, with a real
    selection (3 of 16, 5 held from the second on)."""
    with open(os.path.join(
            REPO, "benchmarks/configs/nemotron-3-nano-30b-a3b.json")) as f:
        cfg = json.load(f)
    cfg.update(builder.REHEARSE)
    cfg.update(n_routed_experts=16, num_experts_per_tok=3,
               num_experts_held=5, first_expert_held=1,
               published_layers=list(layers), num_hidden_layers=len(layers))
    cfg.update(over)
    return cfg


# ---------------------------------------------------------- whole model

# published blocks: 0-6 is the cell's period MEMEM*E; 5-8 crosses into the
# next (*EME); the single kinds alone
PATTERNS = {"period": tuple(range(7)), "next_period": (5, 6, 7, 8),
            "mamba_only": (0, 2), "experts_only": (1,),
            "attention_only": (5,)}


@pytest.mark.parametrize("which", list(PATTERNS), ids=list(PATTERNS))
def test_program_against_reference_whole(builder, which):
    """float32 compute on both sides, the program's chunked scan, flash
    kernel and routed passes against the reference's token-by-token
    recurrence, plain softmax and loop over experts: the loss and every
    gradient leaf agree to float32's own error (1e-4 of a leaf's rms; read
    at 3e-6 and below)."""
    cfg = small(builder, PATTERNS[which])
    kinds = builder._w(cfg)["kinds"]
    if which == "period":
        assert kinds == ["mamba", "moe", "mamba", "moe", "mamba",
                         "attention", "moe"]
    params = R.init_params(builder, cfg, 11)
    toks = R.make_tokens(cfg, 11, 0, 0, 1, SEQ)
    loss_fn = builder.make_loss_fn(cfg, SEQ, interpret=True,
                                   dtype=jnp.float32)
    got_loss, got = jax.jit(jax.value_and_grad(loss_fn))(
        params, {"tokens": toks})
    want_loss, want = R.loss_and_grads(builder, cfg)(params, toks)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 0, jax.tree_util.keystr(path)
        dist = float(jnp.sqrt(jnp.mean(jnp.square(g - w))
                              / jnp.mean(jnp.square(w))))
        assert dist < 1e-4, (jax.tree_util.keystr(path), dist)


def test_the_reference_tells_a_scan_that_drops_its_carry(builder):
    """With ``dt_bias_shift`` the state carries across chunks, so the
    reference with the state zeroed every 128 tokens (the ``no_carry``
    control) is far from the sound one; at the harness's own seeding (a
    decay of 0.03 a token) only a boundary's next token or two can tell."""
    cfg = small(builder, (0, 1, 2))
    params = R.init_params(builder, cfg, 7)
    toks = R.make_tokens(cfg, 7, 0, 0, 1, SEQ)

    def dropped(c):
        _, want = R.loss_and_grads(builder, c)(params, toks)
        control = R.loss_and_grads(builder, c, builder.CONTROLS["no_carry"])
        return R.grad_distance(control(params, toks)[1], want)

    shifted, flat = dropped(cfg), dropped({**cfg, "dt_bias_shift": 0.0})
    assert cfg["dt_bias_shift"] == -6.0
    assert shifted > 0.8 and flat < 0.25 * shifted


def test_blocks_of_all_three_kinds_in_order_with_one_norm_each(builder):
    """The program's tree is the builder's, name for name: every block has
    exactly ``norm`` and its one sub-block (``attn`` for the mixer or the
    attention, ``moe`` for the experts), in the pattern's order; relu^2
    experts hold two stacks and the shared expert two matrices."""
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
    sub = ["attn", "moe", "attn", "moe", "attn", "attn", "moe"]
    for i, name in enumerate(sub):
        assert sorted(shapes[f"layer_{i}"]) == sorted(["norm", name])
    assert sorted(shapes["layer_0"]["attn"]) == [
        "A_log", "D", "conv", "conv_bias", "dt_bias", "in_proj", "norm",
        "out_proj"]
    assert sorted(shapes["layer_1"]["moe"]) == ["router", "shared", "wi_up",
                                                "wo"]
    assert sorted(shapes["layer_1"]["moe"]["shared"]) == ["down", "up"]
    assert sorted(shapes["layer_5"]["attn"]) == ["k", "o", "q", "v"]
    total = sum(int(np.prod(s)) for s in mine.values())
    assert total == builder.param_count(cfg)


def test_the_published_cut_has_its_parameter_count(builder):
    with open(os.path.join(
            REPO, "benchmarks/configs/nemotron-3-nano-30b-a3b.json")) as f:
        cfg = json.load(f)
    assert builder.param_count(cfg) == 528_092_736
    leaves = jax.tree_util.tree_leaves(
        builder.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in leaves) == 528_092_736
    assert len(leaves) == 53


# ------------------------------------------------------ the shares add up

def test_the_16_shares_of_128_experts_add_up_to_the_uncut_block(builder):
    """128 routed relu^2 experts of width 8 (6 a token, sigmoid scores,
    normalised, scale 2.5, one shared expert of width 16) cut into 16 shares
    of 8, as the deployment cuts them over its chips: the parts the shares
    give, with the shared expert counted once, equal the reference's uncut
    block. float32 at the highest precision: 2e-5 is the sums' order."""
    n, k, d, width, shares = 128, 6, 16, 8, 16
    held = n // shares
    key = jax.random.split(jax.random.key(6), 6)

    def mat(i, *shape):
        return 0.2 * jax.random.normal(key[i], shape)

    full = {"router": {"kernel": 0.5 * jax.random.normal(key[0], (d, n))},
            "wi_up": mat(1, n, d, width), "wo": mat(2, n, width, d),
            "shared": {"up": {"kernel": mat(3, d, 2 * width)},
                       "down": {"kernel": mat(4, 2 * width, d)}}}
    u = jax.random.normal(key[5], (1, 24, d))
    w = dict(K=k, route_norm=True, route_scale=2.5, first=0, held=n)
    with jax.default_matmul_precision("highest"):
        whole = builder._experts(u, full, w, builder._same, builder._same)
        only_shared = builder._experts(u, full, {**w, "held": 0},
                                       builder._same, builder._same)
    assert float(jnp.max(jnp.abs(only_shared))) > 1e-3
    assert float(jnp.max(jnp.abs(whole - only_shared))) > 1e-3

    def part(first):
        layer = RoutedMoEMLP(num_experts=n, mlp_dim=width, top_k=k,
                             held=(first, held), shared_dim=2 * width,
                             score="sigmoid", route_scale=2.5, form="relu2",
                             dtype=jnp.float32, interpret=True)
        mine = {**full, **{name: full[name][first:first + held]
                           for name in ("wi_up", "wo")}}
        with jax.default_matmul_precision("highest"):
            return layer.apply({"params": mine}, u)

    total = sum(part(s * held) - only_shared for s in range(shares))
    np.testing.assert_allclose(total + only_shared, whole, atol=2e-5)


# ------------------------------------------------------------- the mixer

def _mixer_cfg(**over):
    kw = dict(embed_dim=64, num_heads=4, dtype=jnp.float32, ssm_heads=4,
              ssm_head_dim=8, ssm_groups=2, ssm_state=16)
    kw.update(over)
    return TransformerConfig(**kw)


def test_the_gate_is_applied_before_a_norm_over_groups():
    """``N_group(y * SiLU(z)) * w_n``: scaling one group's gate input
    changes nothing (its own norm divides it out), which a norm over the
    whole width or a gate after the norm would not give."""
    # eps far below y's mean square, so that the norm is a pure division
    layer = Mamba2Mixer(_mixer_cfg(rms_norm_eps=1e-12))
    x = jax.random.normal(jax.random.key(0), (1, 32, 64))
    params = layer.init(jax.random.key(1), x)["params"]
    # inner = 32 channels in 2 groups of 16; the convolution's channels are
    # [x 32 | B 32 | C 32]. SiLU is not homogeneous, so scale y and not z:
    # with B = 0 (its taps and bias zeroed) y = D x exactly.
    conv = params["conv"].at[:, 32:64].set(0.0)
    bias = params["conv_bias"].at[32:64].set(0.0)
    base = {**params, "conv": conv, "conv_bias": bias}
    scaled = {**base, "D": base["D"].at[:2].multiply(3.0)}   # group 0
    out = layer.apply({"params": base}, x)
    np.testing.assert_allclose(layer.apply({"params": scaled}, x), out,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(out))) > 1e-3
    half = {**base, "D": base["D"].at[:1].multiply(3.0)}     # one head of 2
    assert float(jnp.max(jnp.abs(
        layer.apply({"params": half}, x) - out))) > 1e-3


@pytest.mark.parametrize("missing", ["ssm_heads", "ssm_state", "ssm_groups"])
def test_a_mamba_layer_without_its_sizes_says_so(missing):
    cfg = _mixer_cfg(**{missing: 0})
    with pytest.raises(ValueError, match="a mamba layer needs ssm_heads"):
        Mamba2Mixer(cfg).init(jax.random.key(0), jnp.zeros((1, 8, 64)))


def test_an_expert_form_outside_the_routed_layer_says_so():
    cfg = tiny_config(moe_experts=4, moe_form="relu2")     # dense dispatch
    with pytest.raises(ValueError, match="moe_form 'relu2' is the routed"):
        Transformer(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="unknown expert form"):
        RoutedMoEMLP(num_experts=4, mlp_dim=8, form="gelu", interpret=True
                     ).init(jax.random.key(0), jnp.zeros((1, 8, 16)))
    with pytest.raises(ValueError, match='a "moe" layer needs moe_experts'):
        Transformer(tiny_config(num_layers=1, layer_types=("moe",))).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


# -------------------------------------------------------------- counters

@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_counters_go_up_once_a_step(builder, remat):
    """One output a step carries the mamba blocks' two numbers and the
    routed blocks' counts out of a collecting program, under remat too."""
    cfg = small(builder, (0, 1, 2))
    params = R.init_params(builder, cfg, 13)
    toks = R.make_tokens(cfg, 13, 0, 0, 2, SEQ)
    step = jax.jit(jax.value_and_grad(tracing.collect_counts(
        builder.make_loss_fn(cfg, SEQ, interpret=True, dtype=jnp.float32,
                             remat=remat)), has_aux=True))
    jax.block_until_ready(step(params, {"tokens": toks}))     # compiled
    before = tracing.program_counters()
    (_, counts), _ = step(params, {"tokens": toks})
    # whole numbers (the four moe_*) and the mamba blocks' float32 pair
    assert [len(names) for names in counts.keys] == [4, 2]
    tracing.defer_program_counts(counts)
    tracing.settle_program_counts(wait=True)
    after = tracing.program_counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    # two mamba blocks x 2 sequences x 256 / 128 chunks
    assert delta["ssd_chunks_total"] == 2 * 2 * 2
    # seeded at one and shifted by -6: softplus(dt - 5) about 0.007-0.011,
    # times -e: the step's mean log decay in millionths
    assert -0.05e6 < delta["ssd_log_decay_micro_total"] < -0.01e6
    assert delta["moe_pairs_routed_total"] == 1 * 2 * SEQ * 3
    assert 0 < delta["moe_pairs_local_total"] < delta["moe_pairs_routed_total"]


# ------------------------------------- through FTTrainer and a Manager

def _hybrid(**over):
    kw = dict(vocab_size=256, num_layers=3, embed_dim=64, num_heads=4,
              num_kv_heads=2, attn_head_dim=16, rope_full_layers=False,
              max_seq_len=SEQ, dtype=jnp.float32,
              layer_types=("mamba", "moe", "attention"),
              ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=16,
              moe_experts=8, moe_top_k=2, moe_dispatch="routed", moe_dim=32,
              moe_held=(0, 4), moe_shared_dim=64, moe_form="relu2",
              moe_score="sigmoid", moe_route_scale=2.5, moe_interpret=True,
              remat=True)
    kw.update(over)
    return TransformerConfig(**kw)


def _lm_loss(model):
    def loss_fn(p, batch):
        hidden = model.apply(p, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, p["params"]["lm_head"]["kernel"], batch["tokens"])

    return loss_fn


def test_a_mamba_hybrid_trains_through_fttrainer_and_a_manager():
    """``TransformerConfig(layer_types=("mamba", "moe", ..., "attention",
    ...))`` on the normal path, by configuration alone: a quorum, a fused
    step and a commit on the mocked control plane; every new leaf moves and
    the counters reach ``Manager.metrics()``."""
    from torchft_tpu.parallel import FTTrainer

    model = Transformer(_hybrid())
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}
    alone = quorum_result(max_world_size=1, replica_world_size=1)
    trainer = FTTrainer(
        loss_fn=_lm_loss(model), tx=optax.adamw(3e-4), params=params,
        manager_factory=lambda load, save: make_manager(
            quorum=alone, load_state_dict=load, state_dict=save,
            min_replica_size=1))
    try:
        before = jax.tree_util.tree_map(np.asarray, trainer.params)
        loss, committed = trainer.train_step({"tokens": toks})
        jax.block_until_ready(trainer.params)
        assert committed and np.isfinite(float(loss))
        assert abs(float(loss) - np.log(256)) < 1.0
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), before,
            trainer.params)
        mixer = moved["params"]["layer_0"]["attn"]
        assert all(mixer[name] > 0 for name in (
            "A_log", "dt_bias", "D", "conv", "conv_bias", "norm"))
        assert moved["params"]["layer_1"]["moe"]["wi_up"] > 0
        assert moved["params"]["layer_2"]["attn"]["k"]["kernel"] > 0
        metrics = trainer.manager.metrics()
        assert metrics["ssd_chunks_total"] >= 1 * 2 * 2
        assert metrics["ssd_log_decay_micro_total"] < 0
        trainer.manager._client.quorum.assert_called()
        trainer.manager._client.should_commit.assert_called()
    finally:
        trainer.shutdown()


def test_the_tensor_parallel_rules_name_the_mixers_leaves():
    """``tp_rules`` splits the mixer's two projections (columns in, rows
    out); the sharded model's loss is the unsharded one's: XLA moves what
    the scan needs."""
    from jax.sharding import PartitionSpec as P

    from torchft_tpu.parallel import apply_rules, make_mesh

    model = Transformer(_hybrid(remat=False, num_layers=2,
                                layer_types=("mamba", "attention")))
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}
    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    sh = apply_rules(params, mesh, tp_rules())
    mixer = sh["params"]["layer_0"]["attn"]
    assert mixer["in_proj"]["kernel"].spec == P(None, "tp")
    assert mixer["out_proj"]["kernel"].spec == P("tp", None)
    assert sh["params"]["layer_1"]["attn"]["q"]["kernel"].spec \
        == P(None, "tp", None)
    loss = jax.jit(_lm_loss(model))
    want = loss(params, {"tokens": toks})
    got = loss(jax.device_put(params, sh), {"tokens": toks})
    np.testing.assert_allclose(got, want, rtol=1e-5)


# ---- the blocks the other configurations run, with the new options off

def _digest(tree):
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        bits = jax.lax.bitcast_convert_type(
            x.reshape(-1).astype(jnp.float32), jnp.uint32)
        idx = jnp.arange(bits.size, dtype=jnp.uint32)
        out += [int(jnp.sum(bits)), int(jnp.sum(bits * (2 * idx + 1)))]
    return out


NEW_OFF = dict(ssm_heads=0, ssm_head_dim=0, ssm_groups=0, ssm_state=0,
               moe_form="swiglu")
GOLDEN = {
    "dense_moe": ("golden_transformer.json", dict(
        moe_experts=4, moe_top_k=2, num_kv_heads=2)),
    "routed_sandwich": ("golden_latent_pr33.json", dict(
        num_kv_heads=2, hidden_dim=256, remat=True, attention_fn="flash",
        moe_experts=8, moe_top_k=2, moe_dispatch="routed", moe_dim=64,
        moe_held=(1, 3), moe_shared_dim=64, moe_route_scale=2.826,
        moe_dense_layers=1, moe_interpret=True,
        layer_types=("sliding_attention", "full_attention"),
        sliding_window=16, rope_full_layers=False, attn_head_dim=32,
        qk_norm=True, attn_gate=True, sandwich_norm=True,
        embed_scale=True)),
}


@pytest.mark.parametrize("which", list(GOLDEN), ids=list(GOLDEN))
def test_todays_layer_types_build_the_parents_tree_bitwise(which):
    """Tree, loss and gradients of the blocks the other five configurations
    run, with PR 45's options stated at their off values, as the commits
    before them computed them on the CPU (``tests/golden_transformer.json``,
    ``tests/golden_latent_pr33.json``): the two-norm layer, its mixers and
    the SwiGLU experts (the pass loops and their hand-written backward
    among them) are what they were."""
    file, kw = GOLDEN[which]
    with open(os.path.join(REPO, "tests", file)) as f:
        golden = json.load(f)
    golden = golden["transformer"][which] if "transformer" in golden \
        else golden[which]
    kw = {**kw, **NEW_OFF}
    if kw.get("attention_fn") == "flash":
        kw["attention_fn"] = functools.partial(flash_attention,
                                               interpret=True)
    cfg = tiny_config(**kw)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}
    loss, grads = jax.jit(jax.value_and_grad(_lm_loss(model)))(
        params, {"tokens": toks})
    names = [jax.tree_util.keystr(k) + str(tuple(v.shape)) for k, v in
             jax.tree_util.tree_leaves_with_path(params)]
    assert names == golden["tree"]
    assert _digest(params) == golden["params"]
    assert _digest([loss]) == golden["loss_bits"]
    assert _digest(grads) == golden["grads"]
