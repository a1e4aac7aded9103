"""Latent attention, interleaved rotary, the multi-token-prediction module
and the two-loss objective (``models/mla.py``, ``models/transformer.py``)
against the benchmark builder's plain reference (``mla_moe_decoder``), part
by part and whole; the head's gradient as the sum of the two losses' parts;
the shares of a 256-expert layer adding up to the uncut layer; the counters
once a step; and the options ``trinity-mini`` runs, bitwise what the parent
commit computed."""

import functools
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
from torchft_tpu.models import (  # noqa: E402
    Transformer, chunked_causal_lm_loss, tiny_config)
from torchft_tpu.models.moe import RoutedMoEMLP  # noqa: E402
from torchft_tpu.models.transformer import (  # noqa: E402
    RMSNorm, TransformerConfig, rotary, rotary_interleaved)
from torchft_tpu.ops import flash_attention  # noqa: E402

pytestmark = pytest.mark.heavy
SEQ = 64


@pytest.fixture(scope="module")
def builder():
    spec.configure(REPO)
    return spec.module("models", "mla_moe_decoder")


def small(builder, layers, **over):
    """The configuration's file at the rehearsal's widths, with a real
    selection (2 of 8, 3 held from the second on)."""
    with open(os.path.join(REPO,
                           "benchmarks/configs/joyai-llm-flash.json")) as f:
        cfg = json.load(f)
    cfg.update(builder.REHEARSE)
    cfg.update(n_routed_experts=8, num_experts_per_tok=2, num_experts_held=3,
               first_expert_held=1, published_layers=list(layers) + [40],
               num_hidden_layers=len(layers))
    cfg.update(over)
    return cfg


def program_model(builder, cfg, **kw):
    kw = {"dtype": jnp.float32, **kw}
    return builder._make_model(cfg, SEQ, True, **kw)


# ---------------------------------------------------------------- rotary

@pytest.mark.parametrize("d,offset,atol", [
    (16, 0, 2e-5), (64, 0, 2e-5), (64, 1000, 1e-3)],
    ids=["16", "64", "64_far"])
def test_interleaved_rotary_is_the_complex_rotation(d, offset, atol):
    """Pair (x[2i], x[2i+1]) as a complex number times
    exp(i pos theta^(-2i/d)), computed in float64; the program's angles are
    float32, whose last bit at position 1000 is 6e-5 of a turn."""
    theta = 32_000_000.0
    x = jax.random.normal(jax.random.key(0), (2, 24, 3, d))
    pos = jnp.broadcast_to(jnp.arange(24) + offset, (2, 24))
    z = np.asarray(x[..., 0::2], np.float64) \
        + 1j * np.asarray(x[..., 1::2], np.float64)
    ang = np.asarray(pos, np.float64)[..., None] \
        * theta ** (-np.arange(0, d, 2) / d)
    turned = z * np.exp(1j * ang)[:, :, None, :]
    want = np.stack([turned.real, turned.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(rotary_interleaved(x, pos, theta), want,
                               atol=atol)


def test_interleaved_rotary_is_the_half_split_one_on_permuted_dims():
    """The two conventions differ by where a pair's halves sit."""
    x = jax.random.normal(jax.random.key(1), (1, 16, 2, 32))
    pos = jnp.broadcast_to(jnp.arange(16), (1, 16))
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    got = rotary_interleaved(x, pos, 10_000.0)
    want = rotary(halves, pos, 10_000.0)
    np.testing.assert_allclose(got[..., 0::2], want[..., :16], atol=1e-6)
    np.testing.assert_allclose(got[..., 1::2], want[..., 16:], atol=1e-6)
    assert not np.allclose(got, rotary(x, pos, 10_000.0), atol=1e-3)


# ------------------------------------------------- the attention module

@pytest.mark.parametrize("interleave", [True, False],
                         ids=["interleaved", "half_split"])
def test_latent_attention_against_the_reference(builder, interleave):
    from torchft_tpu.models.mla import LatentAttention

    cfg = small(builder, (1,), rope_interleave=interleave)
    w = builder._w(cfg)
    model = program_model(builder, cfg)
    params = R.init_params(builder, cfg, 3)["params"]["layer_0"]["attn"]
    h = jax.random.normal(jax.random.key(4), (2, SEQ, w["E"]))
    pos = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    got = LatentAttention(model.cfg).apply({"params": params}, h, pos)
    with jax.default_matmul_precision("highest"):
        want = builder._latent_attention(
            h, params, w, builder._same, builder._same, builder._same)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # 2 x 64 x 4 heads: queries and keys 48 wide, values 32
    assert got.shape == (2, SEQ, w["E"])


def test_the_backward_recomputes_the_expansion_and_no_forward_kernel(builder):
    """What lives from the forward to the backward is the latent: the
    gradient's program expands keys and values from it a second time (two
    ``W_kvb`` products with the latent on the left) and holds ONE forward
    flash kernel, whose output and logsumexp were kept by name."""
    from torchft_tpu.models.mla import LatentAttention

    cfg = small(builder, (1,))
    w = builder._w(cfg)
    model = builder._make_model(cfg, SEQ, True, dtype=jnp.float32)
    params = R.init_params(builder, cfg, 3)["params"]["layer_0"]["attn"]
    h = jax.random.normal(jax.random.key(4), (1, SEQ, w["E"]))
    pos = jnp.broadcast_to(jnp.arange(SEQ), (1, SEQ))
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: LatentAttention(model.cfg).apply(
            {"params": p}, h, pos).sum()))(params))
    assert text.count("name=flash_fwd_mla") == 1
    assert text.count("name=flash_bwd_dq_mla") == 1
    assert "flash_out" in text and "flash_lse" in text


def test_latent_attention_needs_all_its_sizes():
    cfg = tiny_config(kv_lora_rank=32)
    with pytest.raises(ValueError, match="q_lora_rank"):
        Transformer(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


# ------------------------------------- the module, the model, the losses

def both_losses(builder, cfg, params, toks, **kw):
    """``(L_main, L_mtp)`` as the program computes them."""
    model = program_model(builder, cfg, **kw)
    hidden, mtp_hidden, _ = model.apply(params, toks, return_mtp=True)
    head = params["params"]["lm_head"]["kernel"]
    return (chunked_causal_lm_loss(hidden, head, toks),
            chunked_causal_lm_loss(mtp_hidden[:, :-1], head, toks[:, 1:]))


@pytest.mark.parametrize("which", ["dense_trunk", "expert_trunk"])
def test_prediction_module_against_the_reference(builder, which):
    """The module's loss alone, and its gradient into the module's own
    leaves, the trunk under it and the shared embedding and head."""
    cfg = small(builder, (0,) if which == "dense_trunk" else (2,))
    params = R.init_params(builder, cfg, 21)
    toks = R.make_tokens(cfg, 21, 0, 0, 2, SEQ)
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: both_losses(builder, cfg, p, toks)[1]))(params)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: builder.reference_losses(p, toks, cfg)[1]))(params)
    assert abs(float(got) - float(want)) < 1e-5
    assert R.grad_distance(g_got, g_want) < 1e-4
    moved = g_got["params"]["mtp"]["proj"]["kernel"]
    assert float(jnp.max(jnp.abs(moved))) > 0


LAYERS = {"dense_and_module": (0,), "expert_and_module": (3,),
          "the_cells_four_and_module": (0, 1, 2, 3)}


@pytest.mark.parametrize("which", list(LAYERS), ids=list(LAYERS))
def test_program_against_reference_whole(builder, which):
    """float32 compute on both sides: the loss L_main + 0.3 L_mtp and every
    gradient leaf agree to float32's own error."""
    cfg = small(builder, LAYERS[which])
    params = R.init_params(builder, cfg, 11)
    toks = R.make_tokens(cfg, 11, 0, 0, 2, SEQ)
    loss_fn = builder.make_loss_fn(cfg, SEQ, interpret=True,
                                   dtype=jnp.float32)
    got_loss, got = jax.jit(jax.value_and_grad(loss_fn))(
        params, {"tokens": toks})
    want_loss, want = R.loss_and_grads(builder, cfg)(params, toks)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    assert R.grad_distance(got, want) < 1e-4


@pytest.mark.parametrize("held", [(0, 8), (6, 2)], ids=["all", "last_two"])
def test_program_against_reference_for_other_shares(builder, held):
    cfg = small(builder, (1, 2), first_expert_held=held[0],
                num_experts_held=held[1])
    params = R.init_params(builder, cfg, 5)
    toks = R.make_tokens(cfg, 5, 0, 0, 1, SEQ)
    loss_fn = builder.make_loss_fn(cfg, SEQ, interpret=True,
                                   dtype=jnp.float32, remat=False)
    _, got = jax.jit(jax.value_and_grad(loss_fn))(params, {"tokens": toks})
    _, want = R.loss_and_grads(builder, cfg)(params, toks)
    assert R.grad_distance(got, want) < 1e-4


def test_shared_leaves_get_the_sum_of_the_two_losses_parts(builder):
    """The head and the embedding are read by both losses: their gradient
    under L_main + 0.3 L_mtp is the main loss's part plus 0.3 times the
    module's, and each part alone is not zero."""
    cfg = small(builder, (1,))
    params = R.init_params(builder, cfg, 8)
    toks = R.make_tokens(cfg, 8, 0, 0, 2, SEQ)
    lam = cfg["mtp_loss_weight"]
    assert lam == 0.3
    total = jax.jit(jax.grad(builder.make_loss_fn(
        cfg, SEQ, interpret=True, dtype=jnp.float32)))(
            params, {"tokens": toks})
    # one backward pass with two cotangents: both losses' gradients
    both = jax.jit(jax.jacrev(
        lambda p: jnp.stack(both_losses(builder, cfg, p, toks))))(params)
    main, mtp = (jax.tree_util.tree_map(lambda x: x[i], both)
                 for i in (0, 1))
    for leaf in (("lm_head", "kernel"), ("embed", "embedding"),
                 ("final_norm", "scale")):
        t, a, b = (g["params"][leaf[0]][leaf[1]] for g in (total, main, mtp))
        assert float(jnp.max(jnp.abs(a))) > 0
        assert float(jnp.max(jnp.abs(b))) > 0
        np.testing.assert_allclose(t, a + lam * b, atol=1e-6, rtol=1e-5)
    # the module's own leaves hear only of the module's loss
    own = main["params"]["mtp"]["proj"]["kernel"]
    assert float(jnp.max(jnp.abs(own))) == 0.0


def test_the_tree_is_the_builders_tree(builder):
    """The program's own init names and shapes every leaf as
    ``param_shapes`` does."""
    cfg = small(builder, (0, 1, 2))
    model = program_model(builder, cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    # the module's leaves come with return_mtp, the head's with the logits
    shapes = {**jax.eval_shape(model.init, jax.random.key(0), toks)["params"],
              **jax.eval_shape(functools.partial(model.init, return_mtp=True),
                               jax.random.key(0), toks)["params"]}
    mine = {jax.tree_util.keystr(k): tuple(v.shape) for k, v in
            jax.tree_util.tree_leaves_with_path(shapes)}
    theirs = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_leaves_with_path(
                  builder.param_shapes(cfg)["params"],
                  is_leaf=lambda x: isinstance(x, tuple))}
    assert mine == theirs


def test_return_mtp_needs_a_module():
    with pytest.raises(ValueError, match="mtp_layers"):
        Transformer(tiny_config()).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32), return_mtp=True)


def test_the_stated_norm_eps_is_the_one_used():
    x = 1e-3 * jax.random.normal(jax.random.key(2), (4, 32))
    a = RMSNorm(eps=1e-6).apply({"params": {"scale": jnp.ones(32)}}, x)
    b = RMSNorm().apply({"params": {"scale": jnp.ones(32)}}, x)
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(a, want, rtol=1e-6)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-2   # 1e-5 matters at 1e-3
    assert TransformerConfig().rms_norm_eps == 1e-5


# ------------------------------------------------------ the shares add up

@pytest.mark.parametrize("shares", [32, 16], ids=["32_of_8", "16_of_16"])
def test_the_shares_of_256_experts_add_up_to_the_uncut_layer(builder,
                                                             shares):
    """256 routed experts of width 8 (8 a token, scale 2.5, one shared
    expert) cut into equal shares, as the deployment cuts them over its
    chips: the parts the shares give, with the shared expert counted once,
    equal the reference's uncut layer."""
    n, k, d, width = 256, 8, 16, 8
    held = n // shares
    key = jax.random.split(jax.random.key(6), 8)
    full = {"router": {"kernel": 0.5 * jax.random.normal(key[0], (d, n))},
            "wi_gate": 0.2 * jax.random.normal(key[1], (n, d, width)),
            "wi_up": 0.2 * jax.random.normal(key[2], (n, d, width)),
            "wo": 0.2 * jax.random.normal(key[3], (n, width, d)),
            "shared": {"gate": {"kernel": 0.2 * jax.random.normal(
                           key[4], (d, width))},
                       "up": {"kernel": 0.2 * jax.random.normal(
                           key[5], (d, width))},
                       "down": {"kernel": 0.2 * jax.random.normal(
                           key[6], (width, d))}}}
    u = jax.random.normal(key[7], (1, 24, d))
    w = dict(K=k, route_norm=True, scale=2.5, Fs=width, first=0, held=n)
    with jax.default_matmul_precision("highest"):
        whole = builder._experts(u, full, w, builder._same, builder._same)
        only_shared = builder._experts(u, full, {**w, "held": 0},
                                       builder._same, builder._same)

    def part(first):
        layer = RoutedMoEMLP(num_experts=n, mlp_dim=width, top_k=k,
                             held=(first, held), shared_dim=width,
                             route_scale=2.5, dtype=jnp.float32,
                             interpret=True)
        mine = {**full, **{name: full[name][first:first + held]
                           for name in ("wi_gate", "wi_up", "wo")}}
        with jax.default_matmul_precision("highest"):
            return layer.apply({"params": mine}, u)

    total = sum(part(s * held) - only_shared for s in range(shares))
    np.testing.assert_allclose(total + only_shared, whole, atol=2e-5)


# -------------------------------------------------------------- counters

@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_counters_go_up_once_a_step(builder, remat):
    """One output a step carries the routed layers' counts (the module's
    layer among them) and the two losses out of a collecting program, under
    remat too."""
    cfg = small(builder, (1,))
    params = R.init_params(builder, cfg, 13)
    toks = R.make_tokens(cfg, 13, 0, 0, 2, SEQ)
    step = jax.jit(jax.value_and_grad(tracing.collect_counts(
        builder.make_loss_fn(cfg, SEQ, interpret=True, dtype=jnp.float32,
                             remat=remat)), has_aux=True))
    jax.block_until_ready(step(params, {"tokens": toks}))     # compiled
    before = tracing.program_counters()
    (loss, counts), _ = step(params, {"tokens": toks})
    # whole numbers (the four moe_*) and the two losses, side by side
    assert [len(names) for names in counts.keys] == [4, 2]
    tracing.defer_program_counts(counts)
    tracing.settle_program_counts(wait=True)
    after = tracing.program_counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    main, mtp = both_losses(builder, cfg, params, toks)
    assert delta["loss_main_micro_total"] == pytest.approx(
        1e6 * float(main), rel=1e-5)
    assert delta["loss_mtp_micro_total"] == pytest.approx(
        1e6 * float(mtp), rel=1e-5)
    assert float(loss) == pytest.approx(float(main) + 0.3 * float(mtp),
                                        rel=1e-5)
    # two expert layers (the trunk's, the module's) x 128 tokens x 2
    assert delta["moe_pairs_routed_total"] == 2 * 2 * SEQ * 2
    assert 0 < delta["moe_pairs_local_total"] < delta["moe_pairs_routed_total"]


def test_manager_metrics_report_the_loss_counters():
    """``Manager.metrics()`` merges the program counters whatever their
    names: the two new ones reach it with no line of ``manager.py``."""
    import inspect

    from torchft_tpu import manager

    assert "program_counters()" in inspect.getsource(manager.Manager.metrics)


# ---- the options trinity-mini runs: what the parent's tree gave, bitwise

def _digest(tree):
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        bits = jax.lax.bitcast_convert_type(
            x.reshape(-1).astype(jnp.float32), jnp.uint32)
        idx = jnp.arange(bits.size, dtype=jnp.uint32)
        out += [int(jnp.sum(bits)), int(jnp.sum(bits * (2 * idx + 1)))]
    return out


def test_routed_sandwich_options_are_bitwise_what_they_were():
    """Layers of two kinds, head norms, the gate, four norms a layer, the
    scaled embedding, a leading dense layer and routed experts over a share
    (``trinity-mini``'s options at a tiny size), through the flash kernel and
    remat: tree, loss and gradients as commit 6195c1c computed them on the
    CPU (``tests/golden_latent_pr33.json``; the Llama-style block's are in
    ``tests/golden_transformer.json``, ``test_afmoe_model.py``, which also
    says what PR 41 re-took: the ``lm_head`` gradient's numbers)."""
    with open(os.path.join(REPO, "tests/golden_latent_pr33.json")) as f:
        golden = json.load(f)["transformer"]["routed_sandwich"]
    cfg = tiny_config(
        num_kv_heads=2, hidden_dim=256, remat=True,
        attention_fn=functools.partial(flash_attention, interpret=True),
        moe_experts=8, moe_top_k=2, moe_dispatch="routed", moe_dim=64,
        moe_held=(1, 3), moe_shared_dim=64, moe_route_scale=2.826,
        moe_dense_layers=1, moe_interpret=True,
        layer_types=("sliding_attention", "full_attention"),
        sliding_window=16, rope_full_layers=False, attn_head_dim=32,
        qk_norm=True, attn_gate=True, sandwich_norm=True, embed_scale=True)
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
