"""The afmoe options of ``Transformer`` (layers of two attention kinds, head
norms, the attention gate, four norms a layer, the scaled embedding, leading
dense layers, routed experts over a share) against the benchmark builder's
plain reference, layer kind by layer kind and whole; and the old options,
bitwise what they were."""

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

from torchft_tpu.models import (Transformer, chunked_causal_lm_loss,  # noqa: E402
                                tiny_config)
from torchft_tpu.ops import flash_attention  # noqa: E402

pytestmark = pytest.mark.heavy
SEQ = 64


@pytest.fixture(scope="module")
def builder():
    spec.configure(REPO)
    return spec.module("models", "afmoe_decoder")


def small(builder, layers, **over):
    with open(os.path.join(REPO, "benchmarks/configs/trinity-mini.json")) as f:
        cfg = json.load(f)
    cfg.update(builder.REHEARSE)
    # a real selection (2 of 8, 3 held from the second on), which the
    # rehearsal's sizes leave out
    cfg.update(num_experts=8, num_experts_per_tok=2, num_experts_held=3,
               first_expert_held=1, published_layers=list(layers),
               num_hidden_layers=len(layers))
    cfg.update(over)
    return cfg


LAYERS = {"dense_sliding": (0,), "experts_sliding": (2,),
          "experts_full": (3,), "the_cells_five": (0, 2, 3, 4, 5)}


@pytest.mark.parametrize("which", list(LAYERS), ids=list(LAYERS))
def test_program_against_reference_by_layer_kind(builder, which):
    """float32 compute on both sides: loss and every gradient leaf agree to
    float32's own error, so the equations are the same equations."""
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
    cfg = small(builder, (2, 3), first_expert_held=held[0],
                num_experts_held=held[1])
    params = R.init_params(builder, cfg, 5)
    toks = R.make_tokens(cfg, 5, 0, 0, 1, SEQ)
    loss_fn = builder.make_loss_fn(cfg, SEQ, interpret=True,
                                   dtype=jnp.float32, remat=False)
    _, got = jax.jit(jax.value_and_grad(loss_fn))(params, {"tokens": toks})
    _, want = R.loss_and_grads(builder, cfg)(params, toks)
    assert R.grad_distance(got, want) < 1e-4


def test_the_tree_is_the_builders_tree(builder):
    """The program's own init names and shapes every leaf as
    ``param_shapes`` does: one seeded tree serves both sides."""
    from torchft_tpu.models.transformer import TransformerConfig

    cfg = small(builder, (0, 2, 3))
    w = builder._w(cfg)
    model = Transformer(TransformerConfig(
        vocab_size=w["V"], num_layers=3, embed_dim=w["E"], num_heads=w["H"],
        num_kv_heads=w["Hkv"], hidden_dim=w["F"], attn_head_dim=w["D"],
        moe_experts=w["Ne"], moe_top_k=w["K"], moe_dispatch="routed",
        moe_dim=w["Fm"], moe_held=(w["first"], w["held"]),
        moe_shared_dim=w["Fs"], moe_dense_layers=1, moe_interpret=True,
        layer_types=tuple(w["kinds"]), sliding_window=w["window"],
        rope_full_layers=False, qk_norm=True, attn_gate=True,
        sandwich_norm=True, embed_scale=True))
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


def test_layer_types_must_match_the_depth():
    cfg = tiny_config(layer_types=("full_attention",), num_layers=2)
    with pytest.raises(ValueError, match="layer_types"):
        Transformer(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    cfg = tiny_config(layer_types=("sliding_attention",) * 2)
    with pytest.raises(ValueError, match="sliding_window"):
        Transformer(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


# ---- the old options: what PR 28's tree gave, bit for bit (CPU)

def _digest(tree):
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        bits = jax.lax.bitcast_convert_type(
            x.reshape(-1).astype(jnp.float32), jnp.uint32)
        idx = jnp.arange(bits.size, dtype=jnp.uint32)
        out += [int(jnp.sum(bits)), int(jnp.sum(bits * (2 * idx + 1)))]
    return out


OLD = {"plain_mha": dict(),
       "gqa_flash_remat": dict(num_kv_heads=2, hidden_dim=256, remat=True,
                               attention_fn="flash"),
       "dense_moe": dict(moe_experts=4, moe_top_k=2, num_kv_heads=2)}


@pytest.mark.parametrize("which", list(OLD), ids=list(OLD))
def test_old_options_are_bitwise_what_they_were(which):
    """Tree, loss and gradients of the Llama-style block (the one
    ``mistral-7b`` and ``internlm2-1.8b`` run) and of the dense-dispatch
    expert layer, as the parent commit computed them here on the CPU
    (``tests/golden_transformer.json``, written by the same lines run on
    PR 28's tree; the ``lm_head`` gradient's two numbers re-taken at PR 41,
    whose one-scan loss rounds the head's gradient differently: 1.7e-7 rms,
    every other leaf and the loss bitwise as before)."""
    with open(os.path.join(REPO, "tests/golden_transformer.json")) as f:
        golden = json.load(f)[which]
    kw = dict(OLD[which])
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
