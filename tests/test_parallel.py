"""Mesh/sharding/model tests on the 8-device virtual CPU mesh
(conftest.py sets xla_force_host_platform_device_count=8)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchft_tpu.models import (
    MLP,
    ResNet18,
    Transformer,
    TransformerConfig,
    causal_lm_loss,
    tp_rules,
)
from torchft_tpu.parallel import (
    apply_rules,
    batch_spec,
    infer_fsdp_sharding,
    make_mesh,
    shard_tree,
)

# Compile-heavy tier: pallas interpret mode + sharded jit dominate suite
# wall-clock; scripts/test.sh runs these after the fast unit tier.
pytestmark = pytest.mark.heavy


class TestMesh:
    def test_default_1d(self):
        mesh = make_mesh()
        assert mesh.axis_names == ("dp",)
        assert mesh.shape["dp"] == 8

    def test_2d_with_inference(self):
        mesh = make_mesh({"fsdp": -1, "tp": 2})
        assert mesh.shape == {"fsdp": 4, "tp": 2}

    def test_bad_shape_raises(self):
        with pytest.raises(ValueError):
            make_mesh({"dp": 3})


class TestSharding:
    def test_infer_fsdp(self):
        mesh = make_mesh({"fsdp": 8})
        params = {"big": jnp.zeros((256, 64)), "bias": jnp.zeros(64)}
        sh = infer_fsdp_sharding(params, mesh, min_size=128)
        assert sh["big"].spec == P("fsdp", None)
        assert sh["bias"].spec == P()  # too small, replicated
        placed = shard_tree(params, sh)
        assert placed["big"].sharding.spec == P("fsdp", None)

    def test_apply_rules_and_divisibility(self):
        mesh = make_mesh({"tp": 8})
        params = {"attn": {"q": {"kernel": jnp.zeros((64, 8, 16))}},
                  "other": jnp.zeros(4)}
        sh = apply_rules(params, mesh, [(r"attn/q/kernel",
                                         P(None, "tp", None))])
        assert sh["attn"]["q"]["kernel"].spec == P(None, "tp", None)
        assert sh["other"].spec == P()
        with pytest.raises(ValueError):
            apply_rules({"attn": {"q": {"kernel": jnp.zeros((64, 6, 16))}}},
                        mesh, [(r"attn/q/kernel", P(None, "tp", None))])

    def test_batch_spec(self):
        mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
        assert batch_spec(mesh) == P(("dp", "fsdp"))
        assert batch_spec(mesh, seq_axis="sp") == P(("dp", "fsdp"))
        mesh2 = make_mesh({"dp": 4, "sp": 2})
        assert batch_spec(mesh2, seq_axis="sp") == P(("dp",), "sp")


class TestModels:
    def test_mlp_forward(self):
        model = MLP(features=(32,), num_classes=10)
        params = model.init(jax.random.key(0), jnp.zeros((2, 8, 8, 3)))
        out = model.apply(params, jnp.zeros((2, 8, 8, 3)))
        assert out.shape == (2, 10)

    def test_resnet18_forward(self):
        model = ResNet18(num_classes=10)
        x = jnp.zeros((2, 32, 32, 3))
        vars_ = model.init(jax.random.key(0), x, train=False)
        out = model.apply(vars_, x, train=False)
        assert out.shape == (2, 10)
        assert out.dtype == jnp.float32

    def test_transformer_forward_and_loss(self):
        cfg = TransformerConfig(vocab_size=128, num_layers=2, embed_dim=64,
                                num_heads=4, max_seq_len=32)
        model = Transformer(cfg)
        tokens = jnp.ones((2, 16), dtype=jnp.int32)
        params = model.init(jax.random.key(0), tokens)
        logits = model.apply(params, tokens)
        assert logits.shape == (2, 16, 128)
        loss = causal_lm_loss(logits, tokens)
        assert np.isfinite(float(loss))

    def test_transformer_gqa(self):
        cfg = TransformerConfig(vocab_size=64, num_layers=1, embed_dim=64,
                                num_heads=8, num_kv_heads=2)
        model = Transformer(cfg)
        tokens = jnp.ones((1, 8), dtype=jnp.int32)
        params = model.init(jax.random.key(0), tokens)
        assert model.apply(params, tokens).shape == (1, 8, 64)

    def test_causal_masking(self):
        """Future tokens must not influence earlier logits."""
        cfg = TransformerConfig(vocab_size=64, num_layers=1, embed_dim=64,
                                num_heads=4, dtype=jnp.float32)
        model = Transformer(cfg)
        t1 = jnp.array([[1, 2, 3, 4]], dtype=jnp.int32)
        t2 = jnp.array([[1, 2, 9, 9]], dtype=jnp.int32)
        params = model.init(jax.random.key(0), t1)
        l1 = model.apply(params, t1)
        l2 = model.apply(params, t2)
        np.testing.assert_allclose(l1[0, :2], l2[0, :2], atol=1e-5)


class TestPresets:
    """Named model configurations (BASELINE.md config 3 family)."""

    def test_llama2_7b_param_count(self):
        """eval_shape materializes nothing — the full 7B architecture is
        verified by arithmetic: published Llama-2 7B is 6.74e9 params."""
        from torchft_tpu.models import Transformer, llama2_7b_config

        cfg = llama2_7b_config()
        model = Transformer(cfg)
        shapes = jax.eval_shape(
            lambda rng: model.init(rng, jnp.zeros((1, 8), jnp.int32)),
            jax.random.key(0))
        n = sum(int(np.prod(s.shape))
                for s in jax.tree_util.tree_leaves(shapes))
        assert 6.7e9 < n < 6.8e9, n

    def test_llama2_70b_gqa(self):
        from torchft_tpu.models import llama2_70b_config

        cfg = llama2_70b_config()
        assert cfg.kv_heads == 8 and cfg.num_heads == 64
        assert cfg.head_dim == 128  # MXU-tile friendly

    def test_chunked_lm_loss_matches_full(self):
        """chunked_causal_lm_loss never materializes [B, S, vocab] (the
        biggest allocation in LM training) yet must match the full loss
        and gradients — including a non-chunk-divisible sequence, which
        exercises the masked padding path."""
        from torchft_tpu.models import (Transformer, causal_lm_loss,
                                        chunked_causal_lm_loss, tiny_config)

        model = Transformer(tiny_config())
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 256, (2, 50)), jnp.int32)
        params = model.init(jax.random.key(0), tokens)

        def loss_full(p):
            return causal_lm_loss(model.apply(p, tokens), tokens)

        def loss_chunked(p):
            hid = model.apply(p, tokens, return_hidden=True)
            return chunked_causal_lm_loss(
                hid, p["params"]["lm_head"]["kernel"], tokens,
                chunk_size=16)

        lf, gf = jax.jit(jax.value_and_grad(loss_full))(params)
        lc, gc = jax.jit(jax.value_and_grad(loss_chunked))(params)
        np.testing.assert_allclose(float(lf), float(lc), rtol=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-5, atol=1e-6),
            gf, gc)

    @pytest.mark.parametrize("matmul_dtype", [None, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("chunk_size", [None, 16], ids=["rule", "16"])
    @pytest.mark.parametrize("batch,seq", [(2, 50), (1, 33), (3, 64),
                                           (4, 300)])
    def test_fused_head_loss_matches_full(self, batch, seq, chunk_size,
                                          matmul_dtype):
        """The one-scan rule (a custom_vjp: loss, dh and dW from the same
        pass) against ``causal_lm_loss`` on full logits: the loss and both
        gradients under a cotangent of 2.5, over sequences that do not
        divide the chunk ((4, 300) is two chunks of 256 by the rule)."""
        from torchft_tpu.models import chunked_causal_lm_loss

        e, v = 32, 96
        k1, k2, k3 = jax.random.split(jax.random.key(batch * seq), 3)
        hidden = jax.random.normal(k1, (batch, seq, e))
        head = 0.3 * jax.random.normal(k2, (e, v))
        tokens = jax.random.randint(k3, (batch, seq), 0, v)
        mm = jnp.float32 if matmul_dtype is None else matmul_dtype

        def full(h, w):
            logits = jnp.einsum("bse,ev->bsv", h.astype(mm), w.astype(mm),
                                preferred_element_type=jnp.float32)
            return 2.5 * causal_lm_loss(logits, tokens)

        def fused(h, w):
            return 2.5 * chunked_causal_lm_loss(
                h, w, tokens, chunk_size=chunk_size,
                matmul_dtype=matmul_dtype)

        lf, gf = jax.jit(jax.value_and_grad(full, argnums=(0, 1)))(
            hidden, head)
        lc, gc = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(
            hidden, head)
        np.testing.assert_allclose(float(lf), float(lc), rtol=2e-6)
        # bf16 inputs: the full path's autodiff rounds dW to bf16, the fused
        # rule keeps it f32
        tol = 1e-5 if matmul_dtype is None else 5e-3
        for want, got in zip(gf, gc):
            assert got.shape == want.shape and got.dtype == want.dtype
            assert float(jnp.linalg.norm(got - want)) \
                < tol * float(jnp.linalg.norm(want))
        # the undifferentiated call is the same number
        np.testing.assert_allclose(float(jax.jit(fused)(hidden, head)),
                                   float(lc), rtol=1e-6)

    def test_mtp_head_gradient_is_the_sum_of_the_two_losses(self):
        """``mtp_causal_lm_loss`` runs the fused rule twice over one head
        kernel: its gradient is the main loss's plus ``mtp_weight`` times
        the module's, each taken apart."""
        from torchft_tpu.models import (chunked_causal_lm_loss,
                                        mtp_causal_lm_loss, tiny_config)

        model = Transformer(tiny_config(mtp_layers=1))
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(0, 256, (2, 40)), jnp.int32)
        params = {"params": {
            **model.init(jax.random.key(0), tokens)["params"],
            **model.init(jax.random.key(0), tokens,
                         return_mtp=True)["params"]}}

        def part(which):
            def loss(p):
                hidden, mtp_hidden, _ = model.apply(p, tokens,
                                                    return_mtp=True)
                head = p["params"]["lm_head"]["kernel"]
                if which == "main":
                    return chunked_causal_lm_loss(hidden, head, tokens)
                return chunked_causal_lm_loss(mtp_hidden[:, :-1], head,
                                              tokens[:, 1:])
            return jax.jit(jax.grad(loss))(params)["params"]["lm_head"][
                "kernel"]

        total = jax.jit(jax.grad(
            lambda p: mtp_causal_lm_loss(model, p, tokens, 0.3)))(
                params)["params"]["lm_head"]["kernel"]
        main, mtp = part("main"), part("mtp")
        assert float(jnp.max(jnp.abs(mtp))) > 0
        np.testing.assert_allclose(total, main + 0.3 * mtp, rtol=1e-5,
                                   atol=1e-7)

    def test_fused_head_loss_sharded_matches_one_device(self):
        """Batch over ``fsdp`` and the head's vocabulary over ``tp``: the
        partitioner splits the fused scan as it split the differentiated
        one, and loss and gradients are the one-device ones."""
        from torchft_tpu.models import chunked_causal_lm_loss

        mesh = make_mesh({"fsdp": 4, "tp": 2})
        k1, k2, k3 = jax.random.split(jax.random.key(5), 3)
        hidden = jax.random.normal(k1, (4, 70, 32))
        head = 0.3 * jax.random.normal(k2, (32, 128))
        tokens = jax.random.randint(k3, (4, 70), 0, 128)

        def loss(h, w, t):
            return chunked_causal_lm_loss(h, w, t, chunk_size=16)

        want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
            hidden, head, tokens)
        put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
        got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
            put(hidden, P("fsdp")), put(head, P(None, "tp")),
            put(tokens, P("fsdp")))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-7), want, got)

    @pytest.mark.parametrize("differentiated", [True, False],
                             ids=["value_and_grad", "loss_only"])
    def test_head_loss_program_is_one_scan(self, differentiated):
        """The compiled program (optimised HLO, here on the CPU): ONE
        ``while``; differentiated it holds three head products a chunk
        (logits, dh, dW), alone one; and no array as large as
        ``[b, s, vocab]`` in either."""
        import re

        from torchft_tpu.models import chunked_causal_lm_loss

        b, s, e, v, chunk = 2, 100, 32, 96, 8
        hidden = jnp.ones((b, s, e), jnp.bfloat16)
        head = jnp.ones((e, v), jnp.float32)
        tokens = jnp.zeros((b, s), jnp.int32)

        def loss(h, w):
            return chunked_causal_lm_loss(h, w, tokens, chunk_size=chunk)

        fn = (jax.value_and_grad(loss, argnums=(0, 1)) if differentiated
              else loss)
        text = jax.jit(fn).lower(hidden, head).compile().as_text()
        assert len(re.findall(r" while\(", text)) == 1
        dots = [line for line in text.splitlines()
                if re.search(r" (dot|convolution)\(", line)]
        shapes = sorted(re.search(r"= \w+\[([\d,]+)\]", d).group(1)
                        for d in dots)
        rows = b * chunk
        want = [f"{rows},{v}"] + ([f"{rows},{e}", f"{e},{v}"]
                                  if differentiated else [])
        assert shapes == sorted(want), dots
        largest = max(
            int(np.prod([int(n) for n in dims.split(",")]))
            for dims in re.findall(r"(?:f32|bf16|s32|pred)\[([\d,]+)\]",
                                   text))
        assert largest < b * (s - 1) * v, largest

    @pytest.mark.parametrize("shape", [
        (4, 4096, 32000), (1, 8192, 16160), (1, 8192, 18992),
        (1, 8192, 25024), (1, 4096, 32000), (1, 1024, 92544), (2, 64, 256)],
        ids=lambda sh: "x".join(map(str, sh)))
    def test_head_loss_chunk_follows_the_shapes(self, shape):
        """The chunk rule alone: the benchmark cells' shapes get at least
        the dW ridge's tokens a chunk (and no more than one multiple of 256
        over it), InternLM2's vocabulary stays under the tile's byte
        budget, a short sequence gets one chunk of 256."""
        from torchft_tpu.models import transformer as T

        batch, seq, vocab = shape
        chunk = T.head_loss_chunk(batch, seq, vocab)
        ridge = T._DW_RIDGE_TOKENS
        assert 900 < ridge < 1024          # 4 x 197e12 / 819e9
        assert chunk % 256 == 0 and chunk >= 256
        if shape == (2, 64, 256):
            assert chunk == 256
        elif vocab == 92544:
            assert batch * chunk * vocab * 4 <= T._LOGITS_TILE_BYTES
            assert chunk == 512
        else:
            assert ridge <= batch * chunk < ridge + 256 * batch
            assert batch * chunk * vocab * 4 <= T._LOGITS_TILE_BYTES

    def test_head_loss_counters_are_counted_at_trace_time(self):
        """``head_loss_fused_traces_total`` / ``head_loss_chunks_traced_total``
        are added on the host when the fused rule is traced for a gradient
        (their ratio is the chunks a loss, as the rule chose them); the
        undifferentiated call adds nothing, and neither program holds a
        host callback."""
        from torchft_tpu import tracing
        from torchft_tpu.models import chunked_causal_lm_loss
        from torchft_tpu.models.transformer import head_loss_chunk

        b, s, e, v = 4, 600, 16, 64
        hidden = jnp.ones((b, s, e), jnp.bfloat16)
        head = jnp.ones((e, v), jnp.float32)
        tokens = jnp.zeros((b, s), jnp.int32)

        def loss(h, w):
            return chunked_causal_lm_loss(h, w, tokens)

        def counters():
            c = tracing.program_counters()
            return (c.get("head_loss_fused_traces_total", 0),
                    c.get("head_loss_chunks_traced_total", 0))

        before = counters()
        alone = str(jax.make_jaxpr(loss)(hidden, head))
        assert counters() == before
        grad = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
            hidden, head))
        after = counters()
        chunks = -(-(s - 1) // head_loss_chunk(b, s, v))
        assert chunks == 3
        assert after == (before[0] + 1, before[1] + chunks)
        assert "callback" not in alone and "callback" not in grad

    def test_remat_matches_plain_gradients(self):
        """cfg.remat trades backward FLOPs for activation memory; values
        and gradients must be bitwise-stable vs the plain path."""
        from torchft_tpu.models import (Transformer, causal_lm_loss,
                                        tiny_config)

        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 256, size=(2, 32)),
            jnp.int32)

        def loss_and_grad(remat):
            model = Transformer(tiny_config(remat=remat))
            params = model.init(jax.random.key(0), tokens)

            def loss_fn(p):
                return causal_lm_loss(model.apply(p, tokens), tokens)

            return jax.jit(jax.value_and_grad(loss_fn))(params)

        (l0, g0), (l1, g1) = loss_and_grad(False), loss_and_grad(True)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-5, atol=1e-6),
            g0, g1)

    def test_7b_sharding_rules_cover_all_params(self):
        """Every 7B parameter gets a sharding from the tp+fsdp rule set
        on a dp×fsdp×tp mesh, and each spec divides the dims — the HSDP
        layout of BASELINE config 3, checked shape-only."""
        from torchft_tpu.models import (Transformer, llama2_7b_config,
                                        tp_rules)
        from torchft_tpu.parallel.sharding import combined_shardings

        cfg = llama2_7b_config(num_layers=2)  # layers are homogeneous
        model = Transformer(cfg)
        shapes = jax.eval_shape(
            lambda rng: model.init(rng, jnp.zeros((1, 8), jnp.int32)),
            jax.random.key(0))
        mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
        shardings = combined_shardings(shapes, mesh, tp_rules())
        specs = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda s: s.spec, shardings))
        # TP must actually engage (attention/mlp projections) and FSDP
        # must pick up the rest — no fully-replicated large leaves.
        assert any("tp" in str(s) for s in specs)
        big = [
            (np.prod(sh.shape), sp.spec)
            for sh, sp in zip(jax.tree_util.tree_leaves(shapes),
                              jax.tree_util.tree_leaves(shardings))
            if np.prod(sh.shape) > 1e6
        ]
        assert big and all(sp != jax.sharding.PartitionSpec()
                           for _, sp in big)


class TestShardedTraining:
    def test_tp_sharded_transformer_step(self):
        """Full jitted train step with megatron TP specs on 8 devices."""
        mesh = make_mesh({"dp": 2, "tp": 4})
        cfg = TransformerConfig(vocab_size=128, num_layers=2, embed_dim=64,
                                num_heads=4, dtype=jnp.float32)
        model = Transformer(cfg)
        tokens = jnp.ones((4, 16), dtype=jnp.int32)
        params = model.init(jax.random.key(0), tokens)
        shardings = apply_rules(params, mesh, tp_rules())
        params = shard_tree(params, shardings)
        bsharding = NamedSharding(mesh, batch_spec(mesh))
        tokens = jax.device_put(tokens, bsharding)

        tx = optax.sgd(0.1)
        opt_state = tx.init(params)

        @jax.jit
        def step(p, o, t):
            loss, grads = jax.value_and_grad(
                lambda pp: causal_lm_loss(model.apply(pp, t), t))(p)
            updates, o = tx.update(grads, o, p)
            return optax.apply_updates(p, updates), o, loss

        p1, o1, loss1 = step(params, opt_state, tokens)
        p2, _, loss2 = step(p1, o1, tokens)
        assert float(loss2) < float(loss1)
        # TP layout preserved through the update
        leaf = p2["params"]["layer_0"]["attn"]["q"]["kernel"]
        # XLA normalizes away trailing Nones in the spec
        assert leaf.sharding.spec in (P(None, "tp"), P(None, "tp", None))

    def test_fsdp_sharded_mlp_step(self):
        mesh = make_mesh({"fsdp": 8})
        model = MLP(features=(256,), num_classes=10)
        x = jnp.ones((8, 4, 4, 3))
        y = jnp.zeros(8, dtype=jnp.int32)
        params = model.init(jax.random.key(0), x)
        sh = infer_fsdp_sharding(params, mesh, min_size=256)
        params = shard_tree(params, sh)

        def loss_fn(p, xx, yy):
            logits = model.apply(p, xx)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, yy).mean()

        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, x, y)
        assert np.isfinite(float(loss))
        # grads inherit the fsdp layout
        gleaf = grads["params"]["Dense_0"]["kernel"]
        assert "fsdp" in str(gleaf.sharding.spec)


class TestFTTrainerModelState:
    def test_batch_stats_advance_on_commit(self):
        """Mutable collections (BN stats) must be adopted on committed
        steps (regression: stats were computed and silently discarded)."""
        from concurrent.futures import Future
        from unittest.mock import MagicMock

        import flax.linen as nn
        import optax

        from torchft_tpu.parallel.step import FTTrainer
        from torchft_tpu.tracing import Tracer

        class BNModel(nn.Module):
            @nn.compact
            def __call__(self, x, train=True):
                x = nn.BatchNorm(use_running_average=not train,
                                 momentum=0.5)(x)
                return nn.Dense(1)(x)

        model = BNModel()
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(8, 4)) * 5 + 3, jnp.float32)
        variables = model.init(jax.random.key(0), x)

        def loss_fn(params, model_state, batch):
            out, new_state = model.apply(
                {"params": params, **model_state}, batch,
                mutable=["batch_stats"])
            return jnp.mean(out ** 2), new_state

        manager = MagicMock()
        manager.tracer.return_value = Tracer(enabled=False)
        manager.should_commit.return_value = True
        manager.is_healing.return_value = False

        def fake_allreduce(tree):
            f = Future()
            f.set_result(tree)
            return f

        manager.allreduce.side_effect = fake_allreduce

        trainer = FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.01),
            params=variables["params"],
            model_state={"batch_stats": variables["batch_stats"]},
            manager_factory=lambda load, save: manager,
            jit_fwd=False,
        )
        before = jax.device_get(
            trainer.model_state["batch_stats"]["BatchNorm_0"]["mean"])
        trainer.train_step(x)
        after = jax.device_get(
            trainer.model_state["batch_stats"]["BatchNorm_0"]["mean"])
        assert not np.allclose(before, after), "BN stats did not advance"
        # state_dict round-trips the mutable collection
        sd = trainer.state_dict()
        assert "model_state" in sd
        trainer.load_state_dict(sd)

    def test_abort_keeps_old_stats(self):
        from concurrent.futures import Future
        from unittest.mock import MagicMock

        import optax

        from torchft_tpu.parallel.step import FTTrainer
        from torchft_tpu.tracing import Tracer

        def loss_fn(params, model_state, batch):
            return jnp.sum(params["w"] * batch), {"s": model_state["s"] + 1}

        manager = MagicMock()
        manager.tracer.return_value = Tracer(enabled=False)
        manager.should_commit.return_value = False
        manager.is_healing.return_value = False
        f = Future()

        def fake_allreduce(tree):
            f2 = Future()
            f2.set_result(tree)
            return f2

        manager.allreduce.side_effect = fake_allreduce
        trainer = FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.1),
            params={"w": jnp.ones(2)},
            model_state={"s": jnp.zeros(())},
            manager_factory=lambda load, save: manager,
            jit_fwd=False,
        )
        _, committed = trainer.train_step(jnp.ones(2))
        assert not committed
        assert float(trainer.model_state["s"]) == 0.0
