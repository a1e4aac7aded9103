"""The looped stack of PR 56 (``TransformerConfig.loop_steps`` /
``exit_gate``, ``chunked_weighted_nll``, ``looped_causal_lm_loss``) against
the benchmark builder's plain reference (``looped_dense_decoder``): the
whole small model, loss and every gradient leaf, in float32 and in bfloat16;
one set of leaves (the looped model against an unrolled one of tied copies);
the weighted head rule against ``jax.grad`` of a plain weighted
cross-entropy; the gate's algebra; the reference's controls; the counters;
the program's size; and the plain stack's program with the new fields at
their defaults."""

import hashlib
import json
import os
import re
import sys

import flax.linen as nn
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
    Transformer, causal_lm_loss, chunked_causal_lm_loss,
    chunked_weighted_nll, head_kernel, looped_causal_lm_loss)
from torchft_tpu.models.transformer import (  # noqa: E402
    DecoderLayer, RMSNorm, TransformerConfig, exit_distribution)

pytestmark = pytest.mark.heavy
SEQ = 48
T = 4
CONFIG = os.path.join(REPO, "benchmarks/configs/ouro-2.6b.json")


@pytest.fixture(scope="module")
def builder():
    spec.configure(REPO)
    yield spec.module("models", "looped_dense_decoder")
    # interpreted kernels cost memory maps (PERF.md section 7): the
    # module's programs go with it
    jax.clear_caches()


def small(**over):
    """The configuration's file at hidden 64, 2 layers, 4 heads of 16,
    vocabulary 256; 4 passes as published."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               head_dim=16, intermediate_size=128, vocab_size=256,
               num_hidden_layers=2)
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


def _both_sides(builder, seed, **model_kw):
    cfg = small()
    params = R.init_params(builder, cfg, seed)
    toks = R.make_tokens(cfg, seed, 0, 0, 1, SEQ)
    got = jax.jit(jax.value_and_grad(builder.make_loss_fn(
        cfg, SEQ, interpret=True, **model_kw)))(params, {"tokens": toks})
    want = R.loss_and_grads(builder, cfg)(params, toks)
    return cfg, params, toks, got, want


# ---------------------------------------------------------- whole model

def test_program_against_reference_whole_float32(builder):
    """float32 compute on both sides: the pass scan with its rematerialised
    layers, the flash kernel, the exits as batch through the weighted head
    rule and the log-space exit distribution, against the reference's Python
    loop over passes, plain softmax, four cross-entropies and the gate
    written out position by position: the loss and every gradient leaf
    agree to float32's own error (1e-5 on the loss; 1e-4 of a leaf's rms,
    read at 1.3e-6 and below)."""
    _, _, _, (got_loss, got), (want_loss, want) = _both_sides(
        builder, 11, dtype=jnp.float32)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    for name, dist in _leaf_distances(got, want).items():
        assert dist < 1e-4, (name, dist)


def test_program_in_bfloat16_stays_in_a_band_of_the_reference(builder):
    """bfloat16 compute against the float32 reference: the loss to 1e-3 and
    every gradient leaf within 0.1 of its rms, the bound the other
    configurations' tests use (read at 0.035 and below: four passes of two
    layers round as eight layers do)."""
    _, _, _, (got_loss, got), (want_loss, want) = _both_sides(builder, 5)
    assert abs(float(got_loss) - float(want_loss)) < 1e-3 * float(want_loss)
    worst = max(_leaf_distances(got, want).values())
    assert worst < 0.1, worst


@pytest.mark.parametrize("control", ["one_pass", "uniform_exits"])
def test_the_reference_tells_a_program_without_the_loop_or_the_gate(
        builder, control):
    """``one_pass`` (the reference runs one pass and puts the whole loss on
    it) and ``uniform_exits`` (it weights the four exits a quarter each and
    ignores the gate, whose leaves then take no gradient: exactly 1) read at
    least ten times what the sound bfloat16 program reads against the sound
    reference."""
    cfg, params, toks, (_, got), (_, want) = _both_sides(builder, 7)
    sound = R.grad_distance(got, want)
    _, ctl = R.loss_and_grads(builder, cfg, builder.CONTROLS[control])(
        params, toks)
    assert R.grad_distance(ctl, want) >= max(10 * sound, 1.0)
    if control == "uniform_exits":
        gate = ctl["params"]["exit_gate"]
        assert float(jnp.max(jnp.abs(gate["kernel"]))) == 0.0


# ------------------------------------------------------ one set of leaves

def _looped(**over):
    kw = dict(vocab_size=256, num_layers=2, embed_dim=64, num_heads=4,
              hidden_dim=128, max_seq_len=SEQ, dtype=jnp.float32,
              rope_theta=1e6, rms_norm_eps=1e-6, sandwich_norm=True,
              loop_steps=T, exit_gate=True, remat=True)
    kw.update(over)
    return TransformerConfig(**kw)


class _Unrolled(nn.Module):
    """``T * L`` layers and ``T`` final norms with leaves of their own."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        exits = []
        for t in range(T):
            for i in range(cfg.num_layers):
                x = DecoderLayer(cfg, name=f"pass_{t}_layer_{i}")(
                    x, positions)
            x = RMSNorm(eps=cfg.rms_norm_eps, name=f"pass_{t}_final_norm")(x)
            exits.append(x)
        return jnp.stack(exits)


def test_the_looped_stack_is_an_unrolled_one_of_tied_copies():
    """The tree has ONE set of layers and no pass axis (the leaves of a
    two-layer dense model plus the gate's one); the exits equal those of a
    ``4 L``-layer model whose layers and final norms are copies of them,
    and a looped leaf's gradient is the sum of its four copies' (float32:
    1e-5 of the leaf's largest element, a sum of four in another order)."""
    cfg = _looped()
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    params = model.init(jax.random.key(0), toks)["params"]
    plain = Transformer(_looped(loop_steps=1, exit_gate=False)).init(
        jax.random.key(0), toks)["params"]
    paths = lambda tree: {jax.tree_util.keystr(p) for p, _ in  # noqa: E731
                          jax.tree_util.tree_leaves_with_path(tree)}
    assert paths(params) == paths(plain) | {"['exit_gate']['kernel']"}
    assert params["exit_gate"]["kernel"].shape == (64 + 1, 1)
    for (_, a), b in zip(jax.tree_util.tree_leaves_with_path(plain),
                         jax.tree_util.tree_leaves(
                             {k: v for k, v in params.items()
                              if k != "exit_gate"})):
        assert a.shape == b.shape

    looped_names = [f"layer_{i}" for i in range(cfg.num_layers)] \
        + ["final_norm"]
    copies = {f"pass_{t}_{name}": params[name]
              for t in range(T) for name in looped_names}
    x = params["embed"]["embedding"][toks]
    mix = jax.random.normal(jax.random.key(2), (T, 2, SEQ, 64))

    def through_loop(stack):
        exits, _ = model.apply({"params": {**params, **stack}}, toks,
                               return_exits=True)
        return jnp.sum(exits * mix), exits

    def through_copies(c):
        exits = _Unrolled(cfg).apply({"params": c}, x)
        return jnp.sum(exits * mix), exits

    (_, got), g_loop = jax.value_and_grad(through_loop, has_aux=True)(
        {name: params[name] for name in looped_names})
    (_, want), g_copies = jax.value_and_grad(through_copies, has_aux=True)(
        copies)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name in looped_names:
        summed = jax.tree_util.tree_map(
            lambda *leaves: sum(leaves),
            *[g_copies[f"pass_{t}_{name}"] for t in range(T)])
        for a, b in zip(jax.tree_util.tree_leaves(g_loop[name]),
                        jax.tree_util.tree_leaves(summed)):
            assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(
                jnp.max(jnp.abs(b))), name


def test_return_modes_of_a_looped_stack():
    """``return_hidden`` and the logits of a looped stack are the last
    pass's; a model without the gate hands no gate logits; a looped stack
    refuses a prediction module."""
    model = Transformer(_looped())
    toks = jax.random.randint(jax.random.key(1), (1, SEQ), 0, 256)
    params = model.init(jax.random.key(0), toks)
    exits, gate = model.apply(params, toks, return_exits=True)
    assert exits.shape == (T, 1, SEQ, 64) and gate.shape == (T - 1, 1, SEQ)
    assert gate.dtype == jnp.float32
    hidden = model.apply(params, toks, return_hidden=True)
    np.testing.assert_array_equal(hidden, exits[-1])
    logits = model.apply(params, toks)
    np.testing.assert_allclose(
        logits, exits[-1] @ params["params"]["lm_head"]["kernel"],
        rtol=1e-5, atol=1e-5)
    bare = Transformer(_looped(exit_gate=False))
    bare_params = bare.init(jax.random.key(0), toks)
    assert "exit_gate" not in bare_params["params"]
    assert bare.apply(bare_params, toks, return_exits=True)[1] is None
    with pytest.raises(ValueError, match="exit_gate"):
        looped_causal_lm_loss(bare, bare_params, toks, 0.1)
    with pytest.raises(ValueError, match="prediction module"):
        Transformer(_looped(mtp_layers=1)).init(
            jax.random.key(0), toks, return_mtp=True)


# --------------------------------------------------- the weighted rule

def _plain_weighted(h, w_head, tokens, weights):
    logp = jax.nn.log_softmax(h[:, :-1] @ w_head, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(weights * nll), nll


@pytest.mark.parametrize("batch,seq,chunk", [(3, 20, 8), (T * 2, 33, 16),
                                             (2, 17, 16)],
                         ids=["ragged", "exits_as_batch", "whole_chunks"])
def test_the_weighted_rule_against_a_plain_weighted_cross_entropy(
        batch, seq, chunk):
    """Value, per-position loss, ``dh``, ``dW`` and ``dweights`` of the one
    fused scan against ``jax.grad`` of the loss written out, float32 (1e-5
    of the largest element): with ``S - 1`` no multiple of the chunk, with
    ``T * B`` rows as the batch, and with whole chunks."""
    keys = jax.random.split(jax.random.key(3), 4)
    h = jax.random.normal(keys[0], (batch, seq, 64))
    w_head = 0.1 * jax.random.normal(keys[1], (64, 256))
    tokens = jax.random.randint(keys[2], (batch, seq), 0, 256)
    weights = jax.random.uniform(keys[3], (batch, seq - 1))

    def rule(h, w_head, weights):
        return chunked_weighted_nll(h, w_head, tokens, weights,
                                    chunk_size=chunk)

    def plain(h, w_head, weights):
        return _plain_weighted(h, w_head, tokens, weights)

    (got, got_nll), got_g = jax.value_and_grad(
        rule, argnums=(0, 1, 2), has_aux=True)(h, w_head, weights)
    (want, want_nll), want_g = jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True)(h, w_head, weights)
    # undifferentiated, the same numbers from the loss-only scan
    alone, alone_nll = rule(h, w_head, weights)
    for a, b in ((got, want), (got_nll, want_nll), (alone, want),
                 (alone_nll, want_nll), *zip(got_g, want_g)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(
            jnp.max(jnp.abs(b)))
    # the per-position loss is handed out with its gradient stopped
    through_nll = jax.grad(lambda h: jnp.sum(rule(h, w_head, weights)[1]))(h)
    assert float(jnp.max(jnp.abs(through_nll))) == 0.0


def test_the_weighted_rule_at_uniform_weights_is_the_chunked_loss():
    """At ``weights = 1 / (b * s1)`` the sum is ``chunked_causal_lm_loss``
    to float32 rounding (1e-6 relative: the two sum in another order), and
    so are both head gradients (1e-5)."""
    keys = jax.random.split(jax.random.key(4), 3)
    h = jax.random.normal(keys[0], (3, 40, 64))
    w_head = 0.1 * jax.random.normal(keys[1], (64, 256))
    tokens = jax.random.randint(keys[2], (3, 40), 0, 256)
    weights = jnp.full((3, 39), 1.0 / (3 * 39), jnp.float32)
    got, got_g = jax.value_and_grad(
        lambda h, w: chunked_weighted_nll(h, w, tokens, weights, 16)[0],
        argnums=(0, 1))(h, w_head)
    want, want_g = jax.value_and_grad(
        lambda h, w: chunked_causal_lm_loss(h, w, tokens, 16),
        argnums=(0, 1))(h, w_head)
    assert abs(float(got) - float(want)) <= 1e-6 * float(want)
    for a, b in zip(got_g, want_g):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(
            jnp.max(jnp.abs(b)))


# ------------------------------------------------------ the gate's algebra

def test_the_exit_distribution_sums_to_one_everywhere():
    """``sum_t q_t = 1`` at every position (float32: 1e-6), for logits
    from -30 to 30, where a product of sigmoids would underflow: ``log q``
    stays finite."""
    z = jnp.concatenate([
        jax.random.normal(jax.random.key(5), (T - 1, 2, 30)) * 3,
        jnp.full((T - 1, 2, 2), 30.0), jnp.full((T - 1, 2, 2), -30.0)],
        axis=-1)
    q, log_q = exit_distribution(z)
    assert q.shape == (T, 2, 34)
    np.testing.assert_allclose(jnp.sum(q, axis=0), 1.0, atol=1e-6)
    assert bool(jnp.all(jnp.isfinite(log_q)))
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(q[1], lam[1] * (1 - lam[0]), atol=1e-6)
    np.testing.assert_allclose(q[-1], jnp.prod(1 - lam, axis=0), atol=1e-6)


def test_one_pass_is_the_plain_loss_and_has_no_entropy():
    """``loop_steps=1`` under the looped loss: ``q = 1``, no entropy, the
    plain chunked loss of the plain stack over the same leaves."""
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    model = Transformer(_looped(loop_steps=1))
    params = model.init(jax.random.key(0), toks)
    got = looped_causal_lm_loss(model, params, toks, beta=0.7,
                                gate_bias_shift=3.0, chunk_size=16)
    plain = Transformer(_looped(loop_steps=1, exit_gate=False))
    stack = {"params": {k: v for k, v in params["params"].items()
                        if k != "exit_gate"}}
    want = chunked_causal_lm_loss(
        plain.apply(stack, toks, return_hidden=True), head_kernel(stack),
        toks, 16)
    assert abs(float(got) - float(want)) <= 1e-6 * float(want)


def test_a_gate_forced_open_at_the_first_pass_gives_its_loss_alone():
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    model = Transformer(_looped())
    params = model.init(jax.random.key(0), toks)
    got = looped_causal_lm_loss(model, params, toks, beta=0.1,
                                gate_bias_shift=40.0, chunk_size=16)
    exits, _ = model.apply(params, toks, return_exits=True)
    first = chunked_causal_lm_loss(exits[0], head_kernel(params), toks, 16)
    assert abs(float(got) - float(first)) <= 1e-6 * float(first)


def test_the_bias_shift_moves_the_loss_and_not_the_bias_gradient():
    """``gate_bias_shift`` is a constant beside the learned bias: the loss
    at ``(b, shift)`` is the loss at ``(b + shift, 0)``, another number than
    at ``(b, 0)``, and every leaf's gradient is the same at both (the
    bias's, the last row of the gate's leaf, among them)."""
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    model = Transformer(_looped())
    params = model.init(jax.random.key(0), toks)
    moved = jax.tree_util.tree_map(lambda x: x, params)
    gate = params["params"]["exit_gate"]["kernel"]
    moved["params"]["exit_gate"] = {"kernel": gate.at[-1, 0].add(-1.1)}

    def loss(p, shift):
        return looped_causal_lm_loss(model, p, toks, 0.1, shift, 16)

    shifted, g_shifted = jax.value_and_grad(loss)(params, -1.1)
    in_bias, g_in_bias = jax.value_and_grad(loss)(moved, 0.0)
    assert abs(float(shifted) - float(in_bias)) <= 1e-6 * float(in_bias)
    assert abs(float(shifted) - float(loss(params, 0.0))) > 1e-3
    for a, b in zip(jax.tree_util.tree_leaves(g_shifted),
                    jax.tree_util.tree_leaves(g_in_bias)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(
            jnp.max(jnp.abs(b)))
    assert float(jnp.abs(
        g_shifted["params"]["exit_gate"]["kernel"][-1, 0])) > 0


# ------------------------------------- through FTTrainer and a Manager

def test_a_looped_model_trains_through_fttrainer_and_counts():
    """The looped stack on the normal path, by configuration alone: a
    quorum, the one-group fused step and a commit on the mocked control
    plane, twice; every leaf moves, the gate's among them;
    ``loop_passes_total`` rises by 4 a step and
    ``loop_expected_exit_milli_total`` by a value strictly between 1000 and
    4000; the weighted rule was traced once, at its three chunks."""
    from torchft_tpu.parallel import FTTrainer

    model = Transformer(_looped())
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}
    counted = tracing.program_counters()     # process-wide totals
    trainer = FTTrainer(
        loss_fn=lambda p, batch: looped_causal_lm_loss(
            model, p, batch["tokens"], 0.1, -1.1, chunk_size=16),
        tx=optax.adamw(3e-4), params=params,
        manager_factory=lambda load, save: make_manager(
            quorum=quorum_result(max_world_size=1, replica_world_size=1),
            load_state_dict=load, state_dict=save, min_replica_size=1))
    try:
        before = jax.tree_util.tree_map(np.asarray, trainer.params)
        for _ in range(2):
            loss, committed = trainer.train_step({"tokens": toks})
            jax.block_until_ready(trainer.params)
            assert committed and np.isfinite(float(loss))
        assert trainer._predict_single
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), before,
            trainer.params)
        assert all(v > 0 for v in jax.tree_util.tree_leaves(moved))
        metrics = trainer.manager.metrics()
        delta = {k: metrics[k] - counted.get(k, 0.0) for k in (
            "loop_passes_total", "loop_expected_exit_milli_total",
            "loop_exit_entropy_micro_total", "loss_exit_first_micro_total",
            "loss_exit_last_micro_total",
            "head_loss_weighted_traces_total",
            "head_loss_weighted_chunks_traced_total")}
        assert delta["loop_passes_total"] == 2 * T
        assert 1000 < delta["loop_expected_exit_milli_total"] / 2 < 4000
        assert 0 < delta["loop_exit_entropy_micro_total"] / 2 < np.log(T) * 1e6
        for exit_loss in ("loss_exit_first_micro_total",
                          "loss_exit_last_micro_total"):
            assert abs(delta[exit_loss] / 2e6 - np.log(256)) < 1.0
        assert delta["head_loss_weighted_traces_total"] == 1
        assert delta["head_loss_weighted_chunks_traced_total"] == 3
    finally:
        trainer.shutdown()


# ---------------------------------------------------- the program's size

def _flash_calls(loop_steps, layers=2):
    """Flash kernels in the lowered text of the differentiated step,
    lowered for a TPU (no chip needed to lower): the Mosaic custom calls."""
    import functools

    from torchft_tpu.ops import flash_attention

    cfg = _looped(num_layers=layers, loop_steps=loop_steps,
                  embed_dim=512, num_heads=4, hidden_dim=512,
                  max_seq_len=512, dtype=jnp.bfloat16,
                  attention_fn=functools.partial(flash_attention,
                                                 interpret=False))
    model = Transformer(cfg)
    toks = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), toks)
    step = jax.jit(jax.value_and_grad(
        lambda p, t: looped_causal_lm_loss(model, p, t, 0.1, -1.1)))
    text = step.trace(params, toks).lower(
        lowering_platforms=("tpu",)).as_text()
    return (len(re.findall(r"tpu_custom_call", text)),
            text.count("stablehlo.while"), len(text))


def test_the_looped_step_holds_one_copy_of_a_pass():
    """What PR 52 was refused for, held here for the pass scan: the lowered
    step at four passes holds ONE copy of a layer's body. Its flash kernels
    number what one pass needs (a forward, the rematerialised forward and
    the backward a layer: ``3 L`` with the fused backward, ``4 L`` with the
    split one that a lowering on this host takes), not four passes' (``12
    L`` and more), and the same at two passes as at four; the scan over
    passes shows as loops (its forward and its backward) beside the loss's;
    the text is of one length to 1 %."""
    layers = 2
    calls_four, loops_four, length_four = _flash_calls(4, layers)
    calls_two, loops_two, length_two = _flash_calls(2, layers)
    assert calls_four == calls_two
    assert 3 * layers <= calls_four <= 4 * layers
    assert loops_four == loops_two == 3
    assert length_four <= 1.01 * length_two


# ------------------------------------------- the plain stack's program

# sha256 of the lowered text of ``TransformerConfig()``'s differentiated
# loss on PR 56's parent (``1c1944a``), taken by running
# ``_default_lowerings`` in a ``git archive`` of it
PARENT_DEFAULTS = {
    "hidden": "74604a7a670db747b9382b057645e3e7caeb21b371a2982919b3a0fd590f09db",
    "logits": "af950e0ddd92912129a43e71a6a6fb41140830957fc992c169ed4f054ea3b64f",
}


def _default_lowerings():
    model = Transformer(TransformerConfig())
    toks = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), toks)

    def hidden(p, t):
        return chunked_causal_lm_loss(
            model.apply(p, t, return_hidden=True),
            p["params"]["lm_head"]["kernel"], t)

    def logits(p, t):
        return causal_lm_loss(model.apply(p, t), t)

    return {name: hashlib.sha256(jax.jit(jax.value_and_grad(f)).lower(
        params, toks).as_text().encode()).hexdigest()
        for name, f in (("hidden", hidden), ("logits", logits))}


def test_the_defaults_lower_as_before():
    """``loop_steps=1`` without a gate is the parent's program: no scan, the
    same names, the same lowered text, through ``return_hidden`` and through
    the logits (the benchmark's dense cells are held by
    ``tests/test_dense_lowering.py``)."""
    assert _default_lowerings() == PARENT_DEFAULTS
