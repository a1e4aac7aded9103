"""A program's counts leave it as an output (docs/design/observability.md,
"How a count leaves a program"; tier-1).

(a) the collector alone: what ``tracing.collect_counts`` returns, what
``count_in_program`` does outside one, where a count cannot be taken;
(b) the host's queue: a vector is added once its program has finished and
not before; (c) the trainers: every loop of this package that
differentiates a user's loss returns the counts from its program and adds
them, a dense model's programs keep their outputs, a discarded speculative
step counts nothing, and no program holds a host callback.
"""

from unittest.mock import MagicMock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mockplane import make_manager, quorum_result
from torchft_tpu import tracing
from torchft_tpu.local_sgd import DiLoCoTrainer, StreamingDiLoCoTrainer
from torchft_tpu.models import Transformer, tiny_config
from torchft_tpu.parallel import FTTrainer
from torchft_tpu.policy import AdaptiveTrainer

# Spelled out, not read from the program: ``Manager.metrics()`` and
# ``benchmarks/metrics/`` (``moe_pairs_local``, ``moe_passes``) read these
# keys, so one renamed or dropped has to fail here.
MOE_COUNTERS = ("moe_pairs_routed_total", "moe_pairs_local_total",
                "moe_expert_load_max_total", "moe_passes_total")

pytestmark = pytest.mark.obs

ROWS = 8


def _delta(before, key):
    return tracing.program_counters().get(key, 0.0) - before.get(key, 0.0)


def counting_loss(params, batch):
    """Counts its rows and (in millionths) the sum of what it was fed."""
    tracing.count_in_program(
        test_rows_total=batch["x"].shape[0],
        test_sum_micro_total=jnp.sum(batch["x"]) * 1e6)
    return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)


def dense_loss(params, batch):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)


def _batch(k):
    rng = np.random.default_rng(k)
    return {"x": jnp.asarray(rng.integers(0, 4, size=(ROWS, 4)), jnp.float32),
            "y": jnp.asarray(rng.normal(size=(ROWS,)), jnp.float32)}


def _params():
    return {"w": jnp.zeros((4,), jnp.float32)}


def _client(worlds):
    """Round k answers a quorum of ``worlds[k]`` groups (the last one from
    then on); every vote commits."""
    client = MagicMock()
    left = list(worlds)

    def quorum(**_):
        world = left.pop(0) if len(left) > 1 else left[0]
        return quorum_result(max_rank=0, max_world_size=world,
                             replica_rank=0, replica_world_size=world)

    client.quorum.side_effect = quorum
    client.should_commit.return_value = True
    return client


def _trainer(loss_fn, worlds=(1,), digest=True, **kwargs):
    """An FTTrainer on the mocked control plane. ``digest``: the boundary
    reads the state's digest as it does beside a real manager server (the
    mocked plane has none, and then pushes no digest), which is the read
    that ends after the step's program."""
    manager_kwargs = {k: kwargs.pop(k) for k in ("overlap_steps",)
                      if k in kwargs}
    trainer = FTTrainer(
        loss_fn=loss_fn, tx=optax.sgd(0.1), params=_params(),
        manager_factory=lambda load, save: make_manager(
            _client(worlds), load_state_dict=load, state_dict=save,
            min_replica_size=1, **manager_kwargs),
        **kwargs)
    if digest:
        trainer.manager._manager_server = MagicMock()
    return trainer


# ----------------------------------------------------- (a) the collector

class TestCollector:
    def test_counts_are_a_vector_a_kind_in_key_order(self):
        def loss(params, batch):
            tracing.count_in_program(test_b_total=jnp.sum(batch["x"] > 1),
                                     test_flag_total=True)
            return counting_loss(params, batch)

        out, counts = jax.jit(tracing.collect_counts(loss))(
            _params(), _batch(0))
        assert isinstance(counts, tracing.ProgramCounts)
        # Whole numbers as int32, the others as f32, each in key order.
        assert counts.keys == (
            ("test_b_total", "test_flag_total", "test_rows_total"),
            ("test_sum_micro_total",))
        assert [(v.shape, v.dtype) for v in counts.values] \
            == [((3,), jnp.int32), ((1,), jnp.float32)]
        assert jax.tree_util.tree_leaves((out, counts)) \
            == [out, *counts.values]
        x = np.asarray(_batch(0)["x"])
        assert counts.ready()
        assert counts.totals() == {
            "test_b_total": float((x > 1).sum()), "test_flag_total": 1.0,
            "test_rows_total": float(ROWS),
            "test_sum_micro_total": pytest.approx(1e6 * x.sum())}

    def test_nothing_counted_is_no_output(self):
        out, counts = jax.jit(tracing.collect_counts(dense_loss))(
            _params(), _batch(0))
        assert counts is None
        assert out.shape == ()

    def test_has_aux_nests_the_counts_beside_the_aux(self):
        def with_state(params, state, batch):
            return counting_loss(params, batch), state + 1

        (loss, (state, counts)), grads = jax.jit(jax.value_and_grad(
            tracing.collect_counts(with_state, has_aux=True),
            has_aux=True))(_params(), jnp.zeros(()), _batch(0))
        assert float(state) == 1.0
        assert counts.totals()["test_rows_total"] == ROWS
        want = jax.grad(dense_loss)(_params(), _batch(0))
        np.testing.assert_array_equal(grads["w"], want["w"])

    def test_a_key_counted_twice_is_summed(self):
        def twice(x):
            tracing.count_in_program(test_rows_total=x.shape[0])
            tracing.count_in_program(test_rows_total=jnp.sum(x))
            return x

        _, counts = jax.jit(tracing.collect_counts(twice))(jnp.ones(4))
        assert counts.totals() == {"test_rows_total": 8.0}

    def test_the_innermost_collector_takes_the_count(self):
        inner = tracing.collect_counts(counting_loss)

        def outer(params, batch):
            tracing.count_in_program(test_outer_total=1)
            return inner(params, batch)

        (_, taken), mine = tracing.collect_counts(outer)(_params(),
                                                         _batch(0))
        assert mine.keys == (("test_outer_total",),)
        assert taken.keys == (("test_rows_total",),
                              ("test_sum_micro_total",))

    def test_outside_a_collector_nothing_is_counted_or_raised(self):
        before = tracing.program_counters()
        tracing.count_in_program(test_rows_total=3)         # no trace at all
        step = jax.jit(jax.value_and_grad(counting_loss))
        loss, _ = step(_params(), _batch(0))
        assert "callback" not in step.lower(_params(), _batch(0)).as_text()
        assert np.isfinite(float(loss))
        tracing.settle_program_counts(wait=True)
        assert tracing.program_counters() == before

    def test_a_collector_closes_when_its_function_raises(self):
        def broken(x):
            tracing.count_in_program(test_rows_total=1)
            raise RuntimeError("no")

        with pytest.raises(RuntimeError):
            tracing.collect_counts(broken)(1.0)
        before = tracing.program_counters()
        tracing.count_in_program(test_rows_total=1)         # no collector
        assert tracing.program_counters() == before

    @pytest.mark.parametrize("region", ["scan", "checkpoint_grad", "jit"])
    def test_a_count_inside_a_traced_region_is_refused(self, region):
        """Such a count is a tracer of the region, not of the collecting
        function: jax names the leak instead of a wrong number arriving."""
        def body(carry, x):
            tracing.count_in_program(test_rows_total=jnp.sum(x))
            return carry + jnp.sum(x), None

        def loss(x):
            if region == "scan":
                return jax.lax.scan(body, 0.0, x)[0]
            if region == "jit":
                return jax.jit(lambda v: body(0.0, v)[0])(x)
            return jax.checkpoint(lambda v: body(0.0, v)[0])(x)

        fn = tracing.collect_counts(loss)
        if region == "checkpoint_grad":
            fn = jax.grad(fn, has_aux=True)
        with pytest.raises(jax.errors.UnexpectedTracerError):
            jax.jit(fn)(jnp.ones((2, 3)))


# ----------------------------------------------------- (b) the host's queue

def _one(key, vector):
    """The counts of a program that counted one number."""
    return tracing.ProgramCounts(((key,),), (vector,))


class _Unfinished:
    """A program's output that is not there yet."""

    def __init__(self, values, fails=False):
        self.values, self.ready, self.fails = values, False, fails

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        if self.fails:
            raise RuntimeError("the program failed")
        return np.asarray(self.values, dtype)


class TestQueue:
    def test_a_vector_is_added_once_its_program_has_finished(self):
        before = tracing.program_counters()
        slow = _Unfinished([5.0])
        tracing.defer_program_counts(
            _one("test_queue_total", slow))
        tracing.defer_program_counts(
            _one("test_queue_total", jnp.ones(1)))
        tracing.defer_program_counts(None)
        # The finished one is in, whatever its place in the queue; the
        # other is neither waited for nor lost.
        assert _delta(before, "test_queue_total") == 1.0
        tracing.settle_program_counts()
        assert _delta(before, "test_queue_total") == 1.0
        slow.ready = True
        assert _delta(before, "test_queue_total") == 6.0
        assert _delta(before, "test_queue_total") == 6.0

    def test_wait_takes_the_unfinished_too(self):
        before = tracing.program_counters()
        tracing.defer_program_counts(
            _one("test_queue_total", _Unfinished([2.0])))
        tracing.settle_program_counts(wait=True)
        assert _delta(before, "test_queue_total") == 2.0

    def test_a_failed_programs_vector_is_dropped(self):
        before = tracing.program_counters()
        tracing.defer_program_counts(
            _one("test_queue_total", _Unfinished([2.0], fails=True)))
        tracing.defer_program_counts(
            _one("test_queue_total", jnp.full(1, 3.0)))
        tracing.settle_program_counts(wait=True)
        assert _delta(before, "test_queue_total") == 3.0
        tracing.settle_program_counts(wait=True)
        assert _delta(before, "test_queue_total") == 3.0

    def test_threads_that_queue_and_settle_lose_nothing(self):
        """Trainers of several groups queue from their own threads while a
        metrics reader settles from another: every vector is added once."""
        import sys
        import threading

        before = tracing.program_counters()
        workers, each = 16, 200

        def work():
            for _ in range(each):
                tracing.defer_program_counts(
                    _one("test_stress_total", np.ones(1, np.float32)))
                tracing.settle_program_counts()
                tracing.program_counters()

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work) for _ in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert _delta(before, "test_stress_total") == workers * each

    def test_manager_metrics_ask_without_waiting(self):
        m = make_manager()
        try:
            before = m.metrics()
            slow = _Unfinished([4.0])
            tracing.defer_program_counts(
                _one("test_queue_total", slow))
            assert m.metrics().get("test_queue_total", 0.0) \
                == before.get("test_queue_total", 0.0)
            slow.ready = True
            assert m.metrics()["test_queue_total"] \
                == before.get("test_queue_total", 0.0) + 4.0
        finally:
            m.shutdown()


# ----------------------------------------------------- (c) the trainers

LAYERS, SEQ, TOP_K = 3, 32, 2


def _routed(remat):
    cfg = tiny_config(num_layers=LAYERS + 1, moe_experts=8, moe_top_k=TOP_K,
                      moe_dispatch="routed", moe_held=(2, 4), moe_dim=32,
                      moe_dense_layers=1, moe_interpret=True, remat=remat,
                      embed_dim=32, dtype=jnp.float32)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, cfg.vocab_size)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}

    def loss_fn(p, batch):
        return jnp.mean(model.apply(p, batch["tokens"]) ** 2)

    return loss_fn, params, {"tokens": toks}


def _mamba(remat):
    """Two mamba blocks and a relu^2 expert block between them, 2 x 256
    tokens: two chunks a sequence a block."""
    from torchft_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, num_layers=3, embed_dim=32, num_heads=2,
        max_seq_len=256, dtype=jnp.float32, remat=remat,
        layer_types=("mamba", "moe", "mamba"), ssm_heads=2, ssm_head_dim=8,
        ssm_groups=1, ssm_state=8, moe_experts=4, moe_top_k=TOP_K,
        moe_dispatch="routed", moe_held=(0, 2), moe_dim=16,
        moe_form="relu2", moe_interpret=True)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 256), 0, cfg.vocab_size)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}

    def loss_fn(p, batch):
        return jnp.mean(model.apply(p, batch["tokens"]) ** 2)

    return loss_fn, params, {"tokens": toks}


def _conv(remat):
    """Two gated short convolutions around an attention layer, a dense MLP
    then routed experts, a tied head: 2 x 32 tokens."""
    from torchft_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, num_layers=3, embed_dim=32, num_heads=2,
        max_seq_len=SEQ, dtype=jnp.float32, remat=remat,
        layer_types=("conv", "full_attention", "conv"),
        linear_conv_kernel=3, tie_embeddings=True, moe_experts=4,
        moe_top_k=TOP_K, moe_dispatch="routed", moe_held=(0, 2), moe_dim=16,
        moe_dense_layers=1, moe_interpret=True)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, cfg.vocab_size)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}

    def loss_fn(p, batch):
        return jnp.mean(model.apply(p, batch["tokens"]) ** 2)

    return loss_fn, params, {"tokens": toks}


def _routed_ahead(remat):
    """A full and a windowed layer at seven query heads on one key/value
    head, both with ReGLU experts routed on the mixer's input: 2 x 32
    tokens."""
    from torchft_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, embed_dim=32, num_heads=7,
        num_kv_heads=1, attn_head_dim=8, max_seq_len=SEQ, dtype=jnp.float32,
        remat=remat, layer_types=("full_attention", "sliding_attention"),
        sliding_window=16, rope_full_layers=False, moe_experts=4,
        moe_top_k=TOP_K, moe_dispatch="routed", moe_held=(0, 2), moe_dim=16,
        moe_score="softmax", moe_form="reglu", moe_route_input="mixer",
        moe_interpret=True)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, SEQ), 0, cfg.vocab_size)
    params = {"params": model.init(jax.random.key(0), toks)["params"]}

    def loss_fn(p, batch):
        return jnp.mean(model.apply(p, batch["tokens"]) ** 2)

    return loss_fn, params, {"tokens": toks}


class TestTrainers:
    @pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
    def test_a_model_routed_ahead_counts_layers_and_active_units(
            self, remat):
        """``moe_route_ahead_layers_total`` (a whole number, beside the
        routed layers' three) and ``moe_reglu_active_micro_total`` (the pass
        loop's carry, a float) leave the fused step, rematerialised or not,
        as values of its counts output: once a step, no host callback."""
        loss_fn, params, batch = _routed_ahead(remat)
        before = tracing.program_counters()
        trainer = FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.01), params=params,
            manager_factory=lambda load, save: make_manager(
                _client([1]), load_state_dict=load, state_dict=save,
                min_replica_size=1))
        try:
            for _ in range(2):
                _, committed = trainer.train_step(batch)
                assert committed
            jax.block_until_ready(trainer.params)
            metrics = trainer.manager.metrics()
            args = (trainer.params, None, trainer.opt_state, batch)
            lowered = trainer._fused.lower(*args).as_text()
            counts = jax.eval_shape(trainer._fused, *args)[-1]
        finally:
            trainer.shutdown()
        # 2 steps x 2 expert layers routed on the mixer's input
        assert metrics["moe_route_ahead_layers_total"] \
            - before.get("moe_route_ahead_layers_total", 0.0) == 2 * 2
        # a share in (0, 1e6) a step: near a half at fresh weights
        active = metrics["moe_reglu_active_micro_total"] \
            - before.get("moe_reglu_active_micro_total", 0.0)
        assert 2 * 300_000 < active < 2 * 700_000
        assert "callback" not in lowered
        assert counts.keys == (
            tuple(sorted((*MOE_COUNTERS, "moe_route_ahead_layers_total"))),
            ("moe_reglu_active_micro_total",))
        assert [(v.shape, v.dtype) for v in counts.values] \
            == [((5,), jnp.int32), ((1,), jnp.float32)]

    @pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
    def test_a_conv_model_counts_tokens_and_rms_as_output_values(
            self, remat):
        """``shortconv_tokens_total`` and ``shortconv_out_rms_micro_total``
        leave the fused step as values of its counts output, beside the
        routed layers' three whole numbers: once a step, no host
        callback."""
        loss_fn, params, batch = _conv(remat)
        before = tracing.program_counters()
        trainer = FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.01), params=params,
            manager_factory=lambda load, save: make_manager(
                _client([1]), load_state_dict=load, state_dict=save,
                min_replica_size=1))
        try:
            for _ in range(2):
                _, committed = trainer.train_step(batch)
                assert committed
            jax.block_until_ready(trainer.params)
            metrics = trainer.manager.metrics()
            args = (trainer.params, None, trainer.opt_state, batch)
            lowered = trainer._fused.lower(*args).as_text()
            counts = jax.eval_shape(trainer._fused, *args)[-1]
        finally:
            trainer.shutdown()
        # 2 steps x 2 conv layers x 2 sequences x 32 tokens
        assert metrics["shortconv_tokens_total"] \
            - before.get("shortconv_tokens_total", 0.0) == 2 * 2 * 2 * SEQ
        # a fresh layer's output carries something: its rms, the step's
        # mean over the two mixers, in millionths
        rms = metrics["shortconv_out_rms_micro_total"] \
            - before.get("shortconv_out_rms_micro_total", 0.0)
        assert 0 < rms < 2 * 10e6
        assert "callback" not in lowered
        assert counts.keys == (
            tuple(sorted(MOE_COUNTERS)),
            ("shortconv_out_rms_micro_total", "shortconv_tokens_total"))
        assert [(v.shape, v.dtype) for v in counts.values] \
            == [((4,), jnp.int32), ((2,), jnp.float32)]

    @pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
    def test_a_mamba_model_counts_chunks_and_decay_as_output_values(
            self, remat):
        """``ssd_chunks_total`` and ``ssd_log_decay_micro_total`` leave the
        fused step as values of its counts output, beside the routed
        block's three whole numbers: once a step, and no host callback."""
        loss_fn, params, batch = _mamba(remat)
        before = tracing.program_counters()
        trainer = FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.01), params=params,
            manager_factory=lambda load, save: make_manager(
                _client([1]), load_state_dict=load, state_dict=save,
                min_replica_size=1))
        try:
            for _ in range(2):
                _, committed = trainer.train_step(batch)
                assert committed
            jax.block_until_ready(trainer.params)
            metrics = trainer.manager.metrics()
            args = (trainer.params, None, trainer.opt_state, batch)
            lowered = trainer._fused.lower(*args).as_text()
            counts = jax.eval_shape(trainer._fused, *args)[-1]
        finally:
            trainer.shutdown()
        # 2 steps x 2 mamba blocks x 2 sequences x 256 / 128 chunks
        assert metrics["ssd_chunks_total"] \
            - before.get("ssd_chunks_total", 0.0) == 2 * 2 * 2 * 2
        # a fresh layer's step is drawn from 0.001-0.1 and its rate from
        # -16..-0.001: the mean log decay a token is negative, in millionths
        decay = metrics["ssd_log_decay_micro_total"] \
            - before.get("ssd_log_decay_micro_total", 0.0)
        assert -2 * 2e6 < decay < 0
        assert "callback" not in lowered
        assert counts.keys == (
            tuple(sorted(MOE_COUNTERS)),
            ("ssd_chunks_total", "ssd_log_decay_micro_total"))
        assert [(v.shape, v.dtype) for v in counts.values] \
            == [((4,), jnp.int32), ((2,), jnp.float32)]

    @pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
    def test_a_routed_model_counts_once_a_step_and_holds_no_callback(
            self, remat):
        loss_fn, params, batch = _routed(remat)
        before = tracing.program_counters()
        trainer = FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.01), params=params,
            manager_factory=lambda load, save: make_manager(
                _client([1]), load_state_dict=load, state_dict=save,
                min_replica_size=1))
        try:
            for _ in range(3):
                _, committed = trainer.train_step(batch)
                assert committed
            jax.block_until_ready(trainer.params)
            metrics = trainer.manager.metrics()
            args = (trainer.params, None, trainer.opt_state, batch)
            lowered = trainer._fused.lower(*args).as_text()
            jaxpr = str(jax.make_jaxpr(trainer._fused)(*args))
            counts = jax.eval_shape(trainer._fused, *args)[-1]
        finally:
            trainer.shutdown()
        assert metrics["moe_pairs_routed_total"] \
            - before.get("moe_pairs_routed_total", 0.0) \
            == 3 * LAYERS * 2 * SEQ * TOP_K
        assert "callback" not in lowered and "callback" not in jaxpr
        assert counts.keys == (tuple(sorted(MOE_COUNTERS)),)
        assert [(v.shape, v.dtype) for v in counts.values] \
            == [((4,), jnp.int32)]

    @pytest.mark.parametrize("stateful", [False, True],
                             ids=["stateless", "model_state"])
    def test_a_dense_models_programs_keep_their_outputs(self, stateful):
        if stateful:
            def loss_fn(params, state, batch):
                return dense_loss(params, batch), state
            kwargs = dict(model_state={"n": jnp.zeros(())})
        else:
            loss_fn, kwargs = dense_loss, {}
        trainer = _trainer(loss_fn, **kwargs)
        try:
            p, st, o = trainer.params, trainer.model_state, trainer.opt_state
            fused = jax.eval_shape(trainer._fused, p, st, o, _batch(0))
            split = jax.eval_shape(trainer._fwd_bwd, p, st, _batch(0))
            trainer.train_step(_batch(0))
        finally:
            trainer.shutdown()
        shape = jax.ShapeDtypeStruct((), jnp.float32)
        as_shapes = lambda tree: jax.tree_util.tree_map(       # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        # No leaf more than the loss, the state, the trees: the last place
        # holds None.
        assert jax.tree_util.tree_structure(fused) \
            == jax.tree_util.tree_structure((shape, st, p, o, None))
        assert fused == (shape, as_shapes(st), as_shapes(p), as_shapes(o),
                         None)
        assert split == (shape, as_shapes(st), as_shapes(p), None)

    @pytest.mark.parametrize("stateful", [False, True],
                             ids=["stateless", "model_state"])
    def test_fused_steps_count(self, stateful):
        if stateful:
            def loss_fn(params, state, batch):
                return counting_loss(params, batch), state
            kwargs = dict(model_state={"n": jnp.zeros(())})
        else:
            loss_fn, kwargs = counting_loss, {}
        before = tracing.program_counters()
        trainer = _trainer(loss_fn, **kwargs)
        try:
            total = 0.0
            for k in range(3):
                trainer.train_step(_batch(k))
                total += float(jnp.sum(_batch(k)["x"]))
                # Each step's counts are in by the time it returns (the
                # boundary read the digest behind the step's program).
                assert _delta(before, "test_rows_total") == (k + 1) * ROWS
        finally:
            trainer.shutdown()
        assert _delta(before, "test_sum_micro_total") \
            == pytest.approx(1e6 * total)

    def test_a_discarded_speculative_step_adds_nothing(self):
        before = tracing.program_counters()
        trainer = _trainer(counting_loss, worlds=[1, 1, 2])
        try:
            for k in range(4):
                trainer.train_step(_batch(k))
            jax.block_until_ready(trainer.params)
            programs = [s["program"]
                        for s in trainer.manager.tracer().spans()
                        if s["stage"] == "dispatch"]
        finally:
            trainer.shutdown()
        # Step 3's quorum grew under its fused program, which was thrown
        # away and run again split: five programs ran, four steps count.
        assert programs == ["fused", "fused", "fused", "fwd_bwd", "fwd_bwd"]
        assert _delta(before, "test_rows_total") == 4 * ROWS

    def test_split_steps_count(self):
        before = tracing.program_counters()
        trainer = _trainer(counting_loss, worlds=[2])
        try:
            for k in range(3):
                trainer.train_step(_batch(k))
                assert _delta(before, "test_rows_total") == (k + 1) * ROWS
        finally:
            trainer.shutdown()

    def test_the_overlap_loop_counts_each_step_once(self):
        before = tracing.program_counters()
        trainer = _trainer(counting_loss, worlds=[2], overlap_steps=1)
        try:
            for k in range(4):
                trainer.train_step(_batch(k))
            assert trainer.flush() is True
        finally:
            trainer.shutdown()
        assert _delta(before, "test_rows_total") == 4 * ROWS

    def test_without_a_digest_the_counts_arrive_by_the_flush(self):
        before = tracing.program_counters()
        trainer = _trainer(counting_loss, digest=False)
        try:
            for k in range(3):
                trainer.train_step(_batch(k))
            assert [s for s in trainer.manager.tracer().spans()
                    if s["stage"] == "state_digest"] == []
            assert trainer.flush() is None
            assert _delta(before, "test_rows_total") == 3 * ROWS
        finally:
            trainer.shutdown()

    def test_an_unjitted_trainer_counts(self):
        before = tracing.program_counters()
        trainer = _trainer(counting_loss, digest=False, jit_fwd=False)
        try:
            trainer.train_step(_batch(0))
        finally:
            trainer.shutdown()
        assert _delta(before, "test_rows_total") == ROWS

    @pytest.mark.parametrize("jit", [True, False], ids=["jit", "eager"])
    @pytest.mark.parametrize("cls,kwargs", [
        (DiLoCoTrainer, {}), (StreamingDiLoCoTrainer, {"fragments": 1})],
        ids=["diloco", "streaming"])
    def test_local_sgd_counts_its_inner_steps(self, cls, kwargs, jit):
        before = tracing.program_counters()
        trainer = cls(
            loss_fn=counting_loss, inner_tx=optax.sgd(0.1),
            params=_params(), sync_every=2, jit=jit, **kwargs,
            manager_factory=lambda load, save: make_manager(
                _client([1]), load_state_dict=load, state_dict=save,
                min_replica_size=1))
        try:
            for k in range(4):
                loss, _ = trainer.train_step(_batch(k))
            assert np.isfinite(float(loss))
            jax.block_until_ready(trainer.params)
            assert _delta(before, "test_rows_total") == 4 * ROWS
        finally:
            trainer.manager.shutdown()

    def test_the_adaptive_trainer_counts(self):
        before = tracing.program_counters()
        trainer = AdaptiveTrainer(
            loss_fn=counting_loss, tx=optax.sgd(0.1), params=_params(),
            manager_factory=lambda load, save: make_manager(
                _client([1]), load_state_dict=load, state_dict=save,
                min_replica_size=1))
        try:
            for k in range(3):
                trainer.train_step(_batch(k))
            jax.block_until_ready(trainer.params)
            assert _delta(before, "test_rows_total") == 3 * ROWS
        finally:
            trainer.manager.shutdown()
