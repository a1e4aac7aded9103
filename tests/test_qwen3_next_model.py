"""The hybrid decoder of PR 40 (``models/linear_attention.py``, the
``linear_attention`` layer kind, partial rotary, the gated shared expert and
softmax routing at 512 columns) against the benchmark builder's plain
reference (``gdn_moe_decoder``), part by part and whole; the flash kernel at
head size 256 on 2 key/value heads; the 32 shares of a 512-expert layer
adding up to the uncut layer; the counters once a step; a step through
``FTTrainer`` and a ``Manager``; and the options the other configurations
run, bitwise what they were with the new options off."""

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
    GatedDeltaNet, Transformer, chunked_causal_lm_loss, tiny_config)
from torchft_tpu.models.moe import RoutedMoEMLP, route  # noqa: E402
from torchft_tpu.models.transformer import (  # noqa: E402
    Attention, TransformerConfig, plain_attention, rotary)
from torchft_tpu.ops import flash_attention  # noqa: E402

pytestmark = pytest.mark.heavy
SEQ = 128          # two chunks of the scan: a state is carried


@pytest.fixture(scope="module")
def builder():
    spec.configure(REPO)
    return spec.module("models", "gdn_moe_decoder")


def small(builder, layers=(0, 1, 2, 3), **over):
    """The configuration's file at the rehearsal's widths, with a real
    selection (3 of 16, 5 held from the second on)."""
    with open(os.path.join(
            REPO, "benchmarks/configs/qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    cfg.update(builder.REHEARSE)
    cfg.update(num_experts=16, num_experts_per_tok=3, num_experts_held=5,
               first_expert_held=1, published_layers=list(layers),
               num_hidden_layers=len(layers))
    cfg.update(over)
    return cfg


# ---------------------------------------------------------- whole model

PERIODS = {"two_periods": tuple(range(8)), "one_period": (0, 1, 2, 3),
           "linear_only": (0, 1), "full_only": (3,)}


@pytest.mark.parametrize("which", list(PERIODS), ids=list(PERIODS))
def test_program_against_reference_whole(builder, which):
    """float32 compute on both sides, the program's chunked scan against the
    reference's token-by-token recurrence: the loss and every gradient leaf
    agree to float32's own error."""
    cfg = small(builder, PERIODS[which])
    kinds = builder._w(cfg)["kinds"]
    if which == "two_periods":
        assert kinds == (["linear_attention"] * 3 + ["full_attention"]) * 2
    params = R.init_params(builder, cfg, 11)
    toks = R.make_tokens(cfg, 11, 0, 0, 2, SEQ)
    loss_fn = builder.make_loss_fn(cfg, SEQ, interpret=True,
                                   dtype=jnp.float32)
    got_loss, got = jax.jit(jax.value_and_grad(loss_fn))(
        params, {"tokens": toks})
    want_loss, want = R.loss_and_grads(builder, cfg)(params, toks)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    leaves = jax.tree_util.tree_leaves_with_path(want)
    for (path, w), g in zip(leaves, jax.tree_util.tree_leaves(got)):
        assert float(jnp.max(jnp.abs(w))) > 0, jax.tree_util.keystr(path)
        dist = float(jnp.sqrt(jnp.mean(jnp.square(g - w))
                              / jnp.mean(jnp.square(w))))
        assert dist < 1e-4, (jax.tree_util.keystr(path), dist)


@pytest.mark.parametrize("held", [(0, 16), (13, 3)], ids=["all", "last_3"])
def test_program_against_reference_for_other_shares(builder, held):
    cfg = small(builder, (2, 3), first_expert_held=held[0],
                num_experts_held=held[1])
    params = R.init_params(builder, cfg, 5)
    toks = R.make_tokens(cfg, 5, 0, 0, 1, SEQ)
    loss_fn = builder.make_loss_fn(cfg, SEQ, interpret=True,
                                   dtype=jnp.float32, remat=False)
    _, got = jax.jit(jax.value_and_grad(loss_fn))(params, {"tokens": toks})
    _, want = R.loss_and_grads(builder, cfg)(params, toks)
    assert R.grad_distance(got, want) < 1e-4


def test_the_reference_tells_a_scan_that_drops_its_carry(builder):
    """With ``dt_bias_shift`` the state carries across chunks, so the
    reference with the state zeroed every 64 tokens (the ``no_carry``
    control) is far from the sound one; at the harness's own seeding (alpha
    0.03 a token) only a boundary's next token or two can tell, and the
    same control reads a fraction of that."""
    cfg = small(builder, (0, 1, 2, 3))
    params = R.init_params(builder, cfg, 7)
    toks = R.make_tokens(cfg, 7, 0, 0, 1, SEQ)

    def dropped(c):
        _, want = R.loss_and_grads(builder, c)(params, toks)
        control = R.loss_and_grads(builder, c, builder.CONTROLS["no_carry"])
        return R.grad_distance(control(params, toks)[1], want)

    shifted, flat = dropped(cfg), dropped({**cfg, "dt_bias_shift": 0.0})
    assert cfg["dt_bias_shift"] == -6.0
    assert shifted > 0.8 and flat < 0.25 * shifted


def test_the_tree_is_the_builders_tree(builder):
    cfg = small(builder)
    model = builder._make_model(cfg, SEQ, True)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))["params"]
    mine = {jax.tree_util.keystr(k): tuple(v.shape) for k, v in
            jax.tree_util.tree_leaves_with_path(shapes)}
    theirs = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_leaves_with_path(
                  builder.param_shapes(cfg)["params"],
                  is_leaf=lambda x: isinstance(x, tuple))}
    assert mine == theirs


def test_a_linear_layer_needs_its_sizes():
    cfg = tiny_config(layer_types=("linear_attention", "full_attention"))
    with pytest.raises(ValueError, match="linear_key_heads"):
        Transformer(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="unknown layer type"):
        Transformer(tiny_config(
            layer_types=("retention", "full_attention"))).init(
                jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


# ------------------------------------------------------ the mixer alone

@pytest.mark.parametrize("seq", [SEQ, 80], ids=["whole_chunks", "ragged"])
def test_gated_deltanet_against_the_reference_mixer(builder, seq):
    cfg = small(builder, (0,), dt_bias_shift=0.0)
    w = builder._w(cfg)
    model = builder._make_model(cfg, seq, True, dtype=jnp.float32)
    params = R.init_params(builder, cfg, 3)["params"]["layer_0"]["attn"]
    # a decay near one, so that the carried state matters
    params = {**params, "dt_bias": params["dt_bias"] - 5.0}
    h = jax.random.normal(jax.random.key(4), (2, seq, w["E"]))
    got, stats = GatedDeltaNet(model.cfg).apply({"params": params}, h,
                                                return_stats=True)
    with jax.default_matmul_precision("highest"):
        want = builder._linear_mixer(h, params, w, {})
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.max(jnp.abs(want))) > 1e-4
    assert float(stats[0]) == 2 * -(-seq // 64)
    assert -0.1 < float(stats[1]) < 0.0


# -------------------------------------------------------- partial rotary

def test_partial_rotary_leaves_the_dims_past_rotary_dim_untouched(builder):
    """The first ``rotary_dim`` dims turn as a head of that size would
    (halves paired: i with i + rotary_dim / 2), the rest pass bit for bit;
    the same on the reference's side."""
    from torchft_tpu.models.transformer import _rotary_leading

    x = jax.random.normal(jax.random.key(0), (2, 24, 3, 32))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    got = _rotary_leading(x, pos, 1e7, 8)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(got[..., :8], rotary(x[..., :8], pos, 1e7))
    assert float(jnp.max(jnp.abs(got[:, 1:, :, :8] - x[:, 1:, :, :8]))) > 0.1
    np.testing.assert_allclose(builder._rope_leading(x, 1e7, 8), got,
                               atol=1e-6)
    # no rotary_dim, or the whole head: the rotary there was
    for whole in (None, 32):
        np.testing.assert_array_equal(_rotary_leading(x, pos, 1e7, whole),
                                      rotary(x, pos, 1e7))


def test_attention_with_partial_rotary_against_the_reference(builder):
    cfg = small(builder, (3,))
    w = builder._w(cfg)
    assert w["rot"] == 8 and w["D"] == 32
    model = builder._make_model(cfg, SEQ, True, dtype=jnp.float32)
    params = R.init_params(builder, cfg, 3)["params"]["layer_0"]["attn"]
    h = jax.random.normal(jax.random.key(4), (2, SEQ, w["E"]))
    pos = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    got = Attention(model.cfg).apply({"params": params}, h, pos)
    with jax.default_matmul_precision("highest"):
        want = builder._full_mixer(h, params, w, {})
    np.testing.assert_allclose(got, want, atol=2e-6)
    whole = Attention(TransformerConfig(**{
        **model.cfg.__dict__, "rotary_dim": None})).apply(
            {"params": params}, h, pos)
    assert float(jnp.max(jnp.abs(whole - got))) > 1e-4


# ------------------------------- the flash kernel at the full layers' size

@pytest.mark.parametrize("seq", [128, 1024], ids=["one_tile", "two_tiles"])
def test_flash_kernel_at_head_256_on_2_kv_heads(seq):
    """16 query heads of 256 on 2 key/value heads (shared through the index
    maps, not repeated), interpreted, against ``plain_attention``: outputs
    and the three gradients. At 1,024 tokens the tiles are 512, the cap
    above a head of 128."""
    heads = 16 if seq == 128 else 4
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (1, seq, heads, 256))
    k = jax.random.normal(ks[1], (1, seq, 2, 256))
    v = jax.random.normal(ks[2], (1, seq, 2, 256))
    ct = jax.random.normal(ks[3], q.shape)
    flash = functools.partial(flash_attention, interpret=True)
    np.testing.assert_allclose(flash(q, k, v, True),
                               plain_attention(q, k, v, True), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a, True) * ct),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain_attention(*a, True) * ct),
                    argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=2e-4, err_msg=name)


# ----------------------------------------------------------- the router

def test_softmax_router_selects_what_the_reference_selects(builder):
    """512 columns, 10 a token, float32: the program's ``route`` and the
    reference's routing pick the same experts with the same weights, and
    the weights of a token add up to one (``norm_topk_prob``, no scale)."""
    w = dict(K=10, route_norm=True)
    u = jax.random.normal(jax.random.key(0), (1, 64, 32))
    kernel = 0.3 * jax.random.normal(jax.random.key(1), (32, 512))
    with jax.default_matmul_precision("highest"):
        want_w, want_idx = builder.reference_routing(u, kernel, w)
        got_w, got_idx, scores = route(u @ kernel, 10, "softmax", True, 1.0)
    np.testing.assert_array_equal(got_idx, want_idx)
    np.testing.assert_allclose(got_w, want_w, atol=1e-7)
    np.testing.assert_allclose(jnp.sum(got_w, -1), 1.0, atol=1e-6)
    np.testing.assert_allclose(jnp.sum(scores, -1), 1.0, atol=1e-5)


def test_the_models_selections_are_the_references(builder):
    cfg = small(builder)
    params = R.init_params(builder, cfg, 9)
    toks = R.make_tokens(cfg, 9, 0, 0, 1, SEQ)
    model_kw = dict(dtype=jnp.float32)
    model = builder._make_model(cfg, SEQ, True, **model_kw)
    _, state = model.apply(builder._shifted(params, builder._w(cfg)), toks,
                           return_hidden=True, mutable=["intermediates"])
    want = builder.reference_selections(params, toks, cfg)
    assert len(want) == 4
    for i, ref in enumerate(want):
        got = state["intermediates"][f"layer_{i}"]["moe"]["experts"][0]
        assert float(jnp.mean(jnp.sort(got, -1) == jnp.sort(ref, -1))) > 0.995


# ------------------------------------------------------ the shares add up

@pytest.mark.parametrize("shares", [32, 64], ids=["32_of_16", "64_of_8"])
def test_the_shares_of_512_experts_add_up_to_the_uncut_layer(builder,
                                                             shares):
    """512 routed experts of width 8 (10 a token, softmax, no scale, one
    shared expert behind its sigmoid gate) cut into equal shares, as the
    deployment cuts them over its chips (and as the fallback cut would): the
    parts the shares give, with the gated shared expert counted once, equal
    the reference's uncut layer."""
    n, k, d, width = 512, 10, 16, 8
    held = n // shares
    key = jax.random.split(jax.random.key(6), 9)

    def mat(i, *shape):
        return 0.2 * jax.random.normal(key[i], shape)

    full = {"router": {"kernel": 0.5 * jax.random.normal(key[0], (d, n))},
            "wi_gate": mat(1, n, d, width), "wi_up": mat(2, n, d, width),
            "wo": mat(3, n, width, d),
            "shared": {"gate": {"kernel": mat(4, d, width)},
                       "up": {"kernel": mat(5, d, width)},
                       "down": {"kernel": mat(6, width, d)}},
            "shared_gate": {"kernel": mat(8, d, 1)}}
    u = jax.random.normal(key[7], (1, 24, d))
    w = dict(K=k, route_norm=True, Fs=width, first=0, held=n)
    with jax.default_matmul_precision("highest"):
        whole = builder._experts(u, full, w, builder._same, builder._same)
        only_shared = builder._experts(u, full, {**w, "held": 0},
                                       builder._same, builder._same)
    assert float(jnp.max(jnp.abs(only_shared))) > 1e-3

    def part(first):
        layer = RoutedMoEMLP(num_experts=n, mlp_dim=width, top_k=k,
                             held=(first, held), shared_dim=width,
                             shared_gate=True, score="softmax",
                             dtype=jnp.float32, interpret=True)
        mine = {**full, **{name: full[name][first:first + held]
                           for name in ("wi_gate", "wi_up", "wo")}}
        with jax.default_matmul_precision("highest"):
            return layer.apply({"params": mine}, u)

    total = sum(part(s * held) - only_shared for s in range(shares))
    np.testing.assert_allclose(total + only_shared, whole, atol=2e-5)


def test_the_shared_experts_gate_is_off_unless_asked():
    layer = RoutedMoEMLP(num_experts=4, mlp_dim=8, top_k=2, shared_dim=8,
                         dtype=jnp.float32, interpret=True)
    x = jnp.ones((1, 4, 16))
    assert "shared_gate" not in layer.init(jax.random.key(0), x)["params"]
    gated = RoutedMoEMLP(num_experts=4, mlp_dim=8, top_k=2, shared_dim=8,
                         shared_gate=True, dtype=jnp.float32, interpret=True)
    shape = jax.tree_util.tree_map(
        jnp.shape, gated.init(jax.random.key(0), x)["params"]["shared_gate"])
    assert shape == {"kernel": (16, 1)}


# -------------------------------------------------------------- counters

@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_counters_go_up_once_a_step(builder, remat):
    """One output a step carries the linear layers' two numbers and the
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
    # whole numbers (the four moe_*) and the linear layers' float32 pair
    assert [len(names) for names in counts.keys] == [4, 2]
    tracing.defer_program_counts(counts)
    tracing.settle_program_counts(wait=True)
    after = tracing.program_counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    # three linear layers x 2 sequences x 128 / 64 chunks
    assert delta["gdn_chunks_total"] == 3 * 2 * 2
    # seeded at one and shifted by -6: softplus(a - 5) about 0.0067, times
    # e: the step's mean log decay in millionths
    assert -0.03e6 < delta["gdn_log_decay_micro_total"] < -0.01e6
    assert delta["moe_pairs_routed_total"] == 4 * 2 * SEQ * 3
    assert 0 < delta["moe_pairs_local_total"] < delta["moe_pairs_routed_total"]


# ------------------------------------- through FTTrainer and a Manager

def test_a_hybrid_model_trains_through_fttrainer_and_a_manager(builder):
    """``TransformerConfig(layer_types=(..., "linear_attention", ...))`` on
    the normal path: a quorum, a fused step and a commit on the mocked
    control plane; the weights move and the counters reach
    ``Manager.metrics()``."""
    from torchft_tpu.parallel import FTTrainer

    cfg = TransformerConfig(
        vocab_size=256, num_layers=4, embed_dim=64, num_heads=4,
        num_kv_heads=2, attn_head_dim=16, rotary_dim=4, qk_norm=True,
        attn_gate=True, max_seq_len=SEQ, dtype=jnp.float32,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        linear_key_heads=2, linear_key_dim=16, linear_value_heads=4,
        linear_value_dim=16, moe_experts=8, moe_top_k=2,
        moe_dispatch="routed", moe_dim=32, moe_held=(0, 4),
        moe_shared_dim=32, moe_shared_gate=True, moe_score="softmax",
        moe_interpret=True, remat=True)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}

    def loss_fn(p, batch):
        hidden = model.apply(p, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, p["params"]["lm_head"]["kernel"], batch["tokens"])

    alone = quorum_result(max_world_size=1, replica_world_size=1)
    trainer = FTTrainer(
        loss_fn=loss_fn, tx=optax.adamw(3e-4), params=params,
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
        attn = moved["params"]["layer_0"]["attn"]
        assert all(attn[name] > 0 for name in ("A_log", "dt_bias", "conv",
                                               "norm"))
        assert moved["params"]["layer_3"]["moe"]["shared_gate"]["kernel"] > 0
        metrics = trainer.manager.metrics()
        assert metrics["gdn_chunks_total"] >= 3 * 2 * 2
        assert metrics["gdn_log_decay_micro_total"] < 0
        trainer.manager._client.quorum.assert_called()
        trainer.manager._client.should_commit.assert_called()
    finally:
        trainer.shutdown()


# ---- the options the other configurations run, with the new options off

def _digest(tree):
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        bits = jax.lax.bitcast_convert_type(
            x.reshape(-1).astype(jnp.float32), jnp.uint32)
        idx = jnp.arange(bits.size, dtype=jnp.uint32)
        out += [int(jnp.sum(bits)), int(jnp.sum(bits * (2 * idx + 1)))]
    return out


NEW_OFF = dict(rotary_dim=None, moe_shared_gate=False, linear_key_heads=0,
               linear_key_dim=0, linear_value_heads=0, linear_value_dim=0)
GOLDEN = {
    "plain_mha": ("golden_transformer.json", dict()),
    "gqa_flash_remat": ("golden_transformer.json", dict(
        num_kv_heads=2, hidden_dim=256, remat=True, attention_fn="flash")),
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
def test_new_options_off_leave_the_goldens_bitwise(which):
    """Tree, loss and gradients of the blocks the other four configurations
    run, with PR 40's options stated at their off values, as the commits
    before them computed them on the CPU (``tests/golden_transformer.json``,
    ``tests/golden_latent_pr33.json``; the ``lm_head`` gradient's numbers
    re-taken at PR 41, see ``test_afmoe_model.py``)."""
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

    def loss_fn(p):
        h = model.apply(p, toks, return_hidden=True)
        return chunked_causal_lm_loss(
            h, p["params"]["lm_head"]["kernel"], toks)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    names = [jax.tree_util.keystr(k) + str(tuple(v.shape)) for k, v in
             jax.tree_util.tree_leaves_with_path(params)]
    assert names == golden["tree"]
    assert _digest(params) == golden["params"]
    assert _digest([loss]) == golden["loss_bits"]
    assert _digest(grads) == golden["grads"]
