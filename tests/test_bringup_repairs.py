"""The repairs PR 25 made so that nothing hides a missing or misbehaving
chip: one test per repair, all on the CPU."""

from __future__ import annotations

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from torchft_tpu.models import Transformer, llama2_7b_config, tp_rules
from torchft_tpu.models.transformer import plain_attention
from torchft_tpu.ops import flash_attention, sharded_flash_attention
from torchft_tpu.parallel import combined_shardings, make_mesh


def _qkv(shape, kv_heads=None):
    ks = jax.random.split(jax.random.key(0), 3)
    kv_shape = shape if kv_heads is None else (*shape[:2], kv_heads,
                                               shape[3])
    return (jax.random.normal(ks[0], shape, jnp.float32),
            jax.random.normal(ks[1], kv_shape, jnp.float32),
            jax.random.normal(ks[2], kv_shape, jnp.float32))


@pytest.mark.parametrize("mesh_shape,kv_heads", [
    ({"fsdp": 2, "tp": 2}, None),
    ({"fsdp": 2, "tp": 2}, 2),       # GQA: kv heads split over tp too
    ({"dp": 2, "fsdp": 2}, None),    # batch over both data axes, no tp
    ({"tp": 4}, None),               # heads only
])
def test_sharded_flash_attention_matches_plain(mesh_shape, kv_heads):
    n = int(np.prod(list(mesh_shape.values())))
    mesh = make_mesh(mesh_shape, jax.devices()[:n])
    q, k, v = _qkv((4, 32, 4, 16), kv_heads)
    attn = sharded_flash_attention(mesh)
    assert attn.supports_gqa
    out = jax.jit(attn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(plain_attention(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)


def test_sharded_flash_attention_small_batch_goes_to_the_bare_kernel():
    mesh = make_mesh({"fsdp": 2, "tp": 2}, jax.devices()[:4])
    q, k, v = _qkv((1, 32, 4, 16))  # model.init's batch of one
    out = sharded_flash_attention(mesh)(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(flash_attention(q, k, v)),
                               atol=1e-6)


def test_sharded_flash_attention_refuses_heads_that_do_not_divide():
    mesh = make_mesh({"tp": 4}, jax.devices()[:4])
    q, k, v = _qkv((4, 32, 6, 16))
    with pytest.raises(ValueError, match="do not divide"):
        sharded_flash_attention(mesh)(q, k, v)


@pytest.mark.parametrize("explicit,want", [(None, True), (True, True),
                                           (False, False)])
def test_interpret_is_decided_in_one_place(explicit, want):
    from torchft_tpu.ops.flash_attention import _resolve_interpret

    # this suite's backend is the CPU: implicit means interpreted here
    assert _resolve_interpret(explicit) is want


def test_interpreted_backward_is_right_on_a_deep_q_grid():
    """nqb >= 4 is where a compiled kernel takes the fused backward; an
    interpreted call keeps to the split kernels, so what the CPU computes
    is what it always was (``tests/test_flash_band.py`` interprets the
    fused kernel, steered)."""
    from torchft_tpu.ops.fused_bwd_check import fused_vs_split

    q, k, v = _qkv((1, 64, 2, 16))

    def flash(q, k, v):
        return flash_attention(q, k, v, True, block_q=16,
                               block_k=16).sum()

    def plain(q, k, v):
        return plain_attention(q, k, v, True).sum()

    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-4, rtol=2e-4)
    assert fused_vs_split((1, 64, 2, 16), block=16)["worst"] == 0.0


@pytest.mark.parametrize("path,spec", [
    ("attn/q/kernel", P("fsdp", "tp", None)),
    ("attn/o/kernel", P("tp", "fsdp")),
    ("mlp/down/kernel", P("tp", "fsdp")),
    ("embed/embedding", P("fsdp", "tp")),
    ("lm_head/kernel", P("fsdp", "tp")),
])
def test_combined_shardings_splits_ruled_leaves_over_fsdp_too(path, spec):
    """On fsdp x tp every chip holds 1/(fsdp*tp) of a projection."""
    cfg = llama2_7b_config(num_layers=1)
    shapes = jax.eval_shape(
        lambda r: Transformer(cfg).init(r, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))
    mesh = make_mesh({"fsdp": 2, "tp": 2}, jax.devices()[:4])
    sh = combined_shardings(shapes, mesh, tp_rules())
    flat = {"/".join(str(getattr(p, "key", p)) for p in kp): s.spec
            for kp, s in jax.tree_util.tree_flatten_with_path(sh)[0]}
    (got,) = [s for name, s in flat.items() if name.endswith(path)]
    assert got == spec


def test_compile_cache_is_left_to_the_environment(monkeypatch, tmp_path):
    from torchft_tpu import utils

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert utils.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # not set in code


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from torchft_tpu import utils

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        want = str(Path(__file__).resolve().parent.parent / ".jax_cache")
        assert utils.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_a_failed_rebuild_in_a_writable_checkout_is_an_error(monkeypatch):
    import subprocess

    from torchft_tpu import _native

    def no_toolchain():
        raise subprocess.CalledProcessError(1, "cmake", stderr=b"boom")

    monkeypatch.setattr(_native, "_stale", lambda: True)
    monkeypatch.setattr(_native, "_build_native", no_toolchain)
    with pytest.raises(RuntimeError, match="rebuilding it failed.*boom"):
        _native._load()


def test_a_swallowed_digest_failure_is_counted(monkeypatch):
    from unittest.mock import MagicMock

    from torchft_tpu import manager as manager_mod
    from torchft_tpu.communicator import DummyCommunicator

    state = {"w": jnp.ones((4,), jnp.float32)}
    m = manager_mod.Manager(
        comm=DummyCommunicator(), load_state_dict=lambda s: None,
        state_dict=lambda: state, min_replica_size=1, rank=0, world_size=1,
        replica_id="digest", _manager_client=MagicMock())
    try:
        assert m._compute_state_digest() != ""
        assert m.metrics()["sdc_digest_failures"] == 0.0

        def broken(leaves):
            raise RuntimeError("device said no")

        monkeypatch.setattr(manager_mod, "_attest_device_words", broken)
        assert m._compute_state_digest() == ""
        assert m.metrics()["sdc_digest_failures"] == 1.0
    finally:
        m.shutdown()


def test_bench_refuses_to_measure_without_a_tpu():
    import bench

    with pytest.raises(SystemExit, match="needs a TPU"):
        bench.main()
    with pytest.raises(ValueError, match="no bf16 peak on record"):
        bench._peak_tflops()


def test_fused_bwd_check_leaves_the_skip_to_its_caller(capsys):
    from torchft_tpu.ops import fused_bwd_check

    assert fused_bwd_check.main() == fused_bwd_check.SKIP
    assert "no TPU backend" in capsys.readouterr().err


def _two_lockstep_groups(fast_path: bool, steps: int = 4):
    """Two FTTrainer groups as threads against the native lighthouse,
    ``steps`` lockstep steps on different batches; returns both groups'
    final params and the participant counts each saw."""
    import threading

    import optax

    from torchft_tpu import HostCommunicator, Lighthouse, Manager
    from torchft_tpu.parallel import FTTrainer

    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=2,
                    join_timeout_ms=2000, quorum_tick_ms=10,
                    fast_path=fast_path)
    params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) / 7}
    out, errors = {}, []

    def group(gi: int) -> None:
        trainer = None
        try:
            trainer = FTTrainer(
                loss_fn=lambda p, b: jnp.sum((b @ p["w"]) ** 2),
                tx=optax.sgd(1e-2), params=params,
                manager_factory=lambda load, save: Manager(
                    comm=HostCommunicator(timeout_sec=3),
                    load_state_dict=load, state_dict=save,
                    min_replica_size=2, replica_id=f"fp_{gi}",
                    lighthouse_addr=lh.address(), rank=0, world_size=1,
                    timeout_ms=3_000, quorum_timeout_ms=3_000))
            batch = jnp.full((2, 3), 1.0 + gi)
            worlds = []
            for _ in range(steps):
                _, committed = trainer.train_step(batch)
                assert committed
                worlds.append(trainer.manager.num_participants())
            out[gi] = (np.asarray(trainer.params["w"]), worlds)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)
        finally:
            if trainer is not None:
                trainer.shutdown()

    threads = [threading.Thread(target=group, args=(gi,)) for gi in (0, 1)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(40)
    finally:
        lh.shutdown()
    if errors:
        raise errors[0]
    return out


def test_lockstep_groups_stay_bitwise_equal_on_the_default_lighthouse():
    out = _two_lockstep_groups(fast_path=False)
    assert out[0][1] == out[1][1] == [2] * 4
    np.testing.assert_array_equal(out[0][0], out[1][0])


@pytest.mark.xfail(strict=True, reason=(
    "ROADMAP D5: the quorum fast path serves the first requester of a "
    "round its peers' steps from the round before, so it counts itself "
    "the only participant and divides the ring's sum by 1"))
def test_lockstep_groups_stay_bitwise_equal_on_the_fast_path():
    out = _two_lockstep_groups(fast_path=True)
    np.testing.assert_array_equal(out[0][0], out[1][0])


def test_lighthouse_cli_has_no_switch_that_turns_the_fast_path_on(
        monkeypatch):
    from torchft_tpu import lighthouse

    seen = {}
    monkeypatch.setattr(
        lighthouse, "Lighthouse",
        lambda **kw: seen.update(kw) or (_ for _ in ()).throw(SystemExit))
    with pytest.raises(SystemExit):
        lighthouse.main(["--no-fast-path"])   # old launch scripts still parse
    assert "fast_path" not in seen
    with pytest.raises(SystemExit):
        lighthouse.main(["--fast-path"])


def test_a_restarted_trainer_adopts_healed_state_before_it_computes():
    """Its first step waits for the quorum (and the heal inside it) before
    dispatching anything; the staged restore is applied there, so forward
    and backward run on the healed weights and the weights at init are
    gone before the gradients exist."""
    from concurrent.futures import Future
    from unittest.mock import MagicMock

    import optax

    from torchft_tpu.parallel.step import FTTrainer
    from torchft_tpu.tracing import Tracer

    def loss_fn(params, batch):
        return jnp.sum(params["w"] * batch)

    manager = MagicMock()
    manager.tracer.return_value = Tracer(enabled=False)
    manager.is_healing.return_value = True
    manager.single_group_step.return_value = False
    manager.should_commit.return_value = True

    def instant(tree):
        f = Future()
        f.set_result(tree)
        return f

    manager.allreduce.side_effect = instant
    trainer = FTTrainer(
        loss_fn=loss_fn, tx=optax.sgd(0.0), params={"w": jnp.zeros(2)},
        manager_factory=lambda load, save: manager, jit_fwd=False)
    healed = {"params": {"w": jnp.full(2, 7.0)},
              "opt_state": trainer.opt_state}
    manager.prepare_commit.side_effect = (
        lambda: trainer.load_state_dict(healed))
    loss, _ = trainer.train_step(jnp.ones(2))
    assert float(loss) == 14.0   # 0.0 on the weights at init
    manager.prepare_commit.assert_called_once()


def test_a_donor_lets_its_heal_snapshot_go_when_the_last_stream_ends():
    """Not at its commit: a donor waiting in the ring for the healer would
    carry a dead copy of its state. A later GET of the same step snapshots
    the same bytes again."""
    import urllib.request

    from torchft_tpu.checkpointing import CheckpointServer

    state = {"w": jnp.arange(1024, dtype=jnp.float32)}
    server = CheckpointServer(lambda: state, bind_host="127.0.0.1")
    try:
        server.allow_checkpoint(3)
        bodies = []
        for _ in range(2):
            with urllib.request.urlopen(server.address(), timeout=10) as r:
                bodies.append(r.read())
            deadline = time.monotonic() + 5
            while server._snap is not None and time.monotonic() < deadline:
                time.sleep(0.01)   # the handler's finally runs after EOF
            assert server._snap is None
        assert bodies[0] == bodies[1]
        # A manifest alone keeps the snapshot: its body requests follow.
        with urllib.request.urlopen(server.address() + "/manifest",
                                    timeout=10) as r:
            r.read()
        time.sleep(0.1)
        assert server._snap is not None
    finally:
        server.shutdown()
