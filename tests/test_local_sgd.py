"""DiLoCo local-SGD tests: unit (mocked manager) + 2-group integration."""

from concurrent.futures import Future, ThreadPoolExecutor
from unittest.mock import MagicMock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu import HostCommunicator, Lighthouse, Manager
from torchft_tpu.local_sgd import DiLoCoTrainer, StreamingDiLoCoTrainer


class FakeManager:
    """Stateful stand-in for the streaming schedule tests: echo allreduce,
    always-commit, commit-gated step counter like the real Manager."""

    def __init__(self):
        self.step_calls = 0
        self.allreduce_calls = 0
        self._step = 0
        self._should_step = True
        self.commit_result = True

    def step(self):
        self.step_calls += 1
        if self._should_step:
            self._step += 1

    def wait_quorum(self):
        pass

    def current_step(self):
        return self._step

    def allreduce(self, tree):
        self.allreduce_calls += 1
        return echo_allreduce(tree)

    def should_commit(self):
        self._should_step = self.commit_result
        return self.commit_result

    def is_healing(self):
        return False

    def shutdown(self):
        pass


def echo_allreduce(tree):
    f: Future = Future()
    f.set_result(tree)
    return f


def make_trainer(manager, sync_every=4):
    def loss_fn(params, batch):
        return jnp.mean((params["w"] - batch) ** 2)

    return DiLoCoTrainer(
        loss_fn=loss_fn,
        inner_tx=optax.sgd(0.1),
        params={"w": jnp.zeros(4)},
        manager_factory=lambda load, save: manager,
        sync_every=sync_every,
        jit=False,
    )


def make_streaming(manager, sync_every=4, fragments=2):
    def loss_fn(params, batch):
        return (jnp.mean((params["w"] - batch) ** 2)
                + jnp.mean((params["b"] - batch[:2]) ** 2))

    return StreamingDiLoCoTrainer(
        loss_fn=loss_fn,
        inner_tx=optax.sgd(0.1),
        params={"b": jnp.zeros(2), "w": jnp.zeros(4)},
        manager_factory=lambda load, save: manager,
        sync_every=sync_every,
        fragments=fragments,
        jit=False,
    )


class TestStreamingUnit:
    def test_schedule_launch_collect_overlap(self):
        """Every interval: collect the in-flight fragment (None on the
        first), launch the next. Rounds = launches; commits lag launches
        by one interval — the overlap."""
        fm = FakeManager()
        t = make_streaming(fm, sync_every=4, fragments=2)  # interval 2
        target = jnp.full(4, 1.0)
        seen = [t.train_step(target)[1] for _ in range(8)]
        assert seen == [None, None, None, True, None, True, None, True]
        assert fm.step_calls == 4  # launches at steps 2, 4, 6, 8
        assert fm.allreduce_calls == 4
        assert t._pending is not None  # one round always in flight
        assert t.flush() is True
        assert t._pending is None

    def test_fragments_rotate_with_round_counter(self):
        fm = FakeManager()
        t = make_streaming(fm, sync_every=4, fragments=2)
        target = jnp.full(4, 1.0)
        frags = []
        for _ in range(4):
            t.train_step(target)
            t.train_step(target)
            frags.append(t._pending[0])
        assert frags == [1, 0, 1, 0]  # round % fragments

    def test_only_synced_fragment_anchor_moves(self):
        fm = FakeManager()
        t = make_streaming(fm, sync_every=4, fragments=2)
        target = jnp.full(4, 1.0)
        for _ in range(2):
            t.train_step(target)   # launch frag 1 (round 1)
        frag = t._pending[0]
        anchor_before = jax.device_get(t.anchor)
        for _ in range(2):
            t.train_step(target)   # collect frag `frag`, launch next
        anchor_after = jax.device_get(t.anchor)
        # leaves of the synced fragment moved, the others did not
        leaves_b, _ = jax.tree_util.tree_flatten(anchor_before)
        leaves_a, _ = jax.tree_util.tree_flatten(anchor_after)
        moved = [not np.allclose(x, y) for x, y in zip(leaves_b, leaves_a)]
        for i in range(len(moved)):
            assert moved[i] == (i in t._frag_idx[frag])

    def test_aborted_round_retries_same_fragment(self):
        fm = FakeManager()
        fm.commit_result = False
        t = make_streaming(fm, sync_every=4, fragments=2)
        target = jnp.full(4, 1.0)
        for _ in range(2):
            t.train_step(target)
        first_frag = t._pending[0]
        anchor_before = jax.device_get(t.anchor)
        _, committed = t.train_step(target) or (None, None)
        _, committed = t.train_step(target)
        assert committed is False
        np.testing.assert_allclose(
            jax.tree_util.tree_leaves(jax.device_get(t.anchor))[0],
            jax.tree_util.tree_leaves(anchor_before)[0])
        # the retry launches the SAME fragment (step did not bump)
        assert t._pending[0] == first_frag
        # recovery: next round commits and the anchor moves
        fm.commit_result = True
        for _ in range(2):
            t.train_step(target)
        assert t.flush() is True

    def test_fragment_split_balanced_nonempty(self):
        from torchft_tpu.local_sgd import _fragment_leaves
        leaves = [np.zeros(2), np.zeros(4)]
        assert _fragment_leaves(leaves, 2) == [[0], [1]]
        leaves = [np.zeros(100), np.zeros(1), np.zeros(1), np.zeros(1)]
        groups = _fragment_leaves(leaves, 3)
        assert [i for g in groups for i in g] == [0, 1, 2, 3]
        assert all(g for g in groups)
        assert _fragment_leaves([np.zeros(1)], 3) == [[0], [], []]


class TestDiLoCoUnit:
    def test_outer_round_cadence(self):
        manager = MagicMock()
        manager.should_commit.return_value = True
        manager.allreduce.side_effect = echo_allreduce
        t = make_trainer(manager, sync_every=4)
        target = jnp.full(4, 1.0)
        for i in range(4):
            _, committed = t.train_step(target)
            assert committed is (True if (i + 1) % 4 == 0 else None)
        assert manager.step.call_count == 1
        # right after the round, local params reset to the new anchor
        np.testing.assert_allclose(np.asarray(t.params["w"]),
                                   np.asarray(t.anchor["w"]))
        for i in range(3):
            _, committed = t.train_step(target)
            assert committed is None
        assert manager.step.call_count == 1  # still one outer round
        # inner steps moved local params off the anchor
        assert not np.allclose(np.asarray(t.params["w"]),
                               np.asarray(t.anchor["w"]))

    def test_inner_steps_do_not_communicate(self):
        manager = MagicMock()
        manager.allreduce.side_effect = echo_allreduce
        manager.should_commit.return_value = True
        t = make_trainer(manager, sync_every=100)
        for _ in range(50):
            t.train_step(jnp.ones(4))
        manager.step.assert_not_called()
        manager.allreduce.assert_not_called()

    def test_failed_round_keeps_local_progress(self):
        manager = MagicMock()
        manager.allreduce.side_effect = echo_allreduce
        manager.should_commit.return_value = False
        t = make_trainer(manager, sync_every=2)
        t.train_step(jnp.ones(4))
        params_before = np.asarray(t.params["w"])
        anchor_before = np.asarray(t.anchor["w"])
        _, committed = t.train_step(jnp.ones(4))
        assert committed is False
        # anchor untouched; local params kept training (≠ reset)
        np.testing.assert_allclose(np.asarray(t.anchor["w"]), anchor_before)
        assert not np.allclose(np.asarray(t.params["w"]), anchor_before)
        assert not np.allclose(np.asarray(t.params["w"]), params_before)

    def test_outer_applies_averaged_delta(self):
        manager = MagicMock()
        manager.should_commit.return_value = True
        # pretend the other group moved twice as far: average given back
        manager.allreduce.side_effect = echo_allreduce
        t = make_trainer(manager, sync_every=1)
        _, committed = t.train_step(jnp.full(4, 10.0))
        assert committed
        # outer sgd(0.7, nesterov 0.9): anchor moved toward params
        assert 0 < float(np.asarray(t.anchor["w"]).mean())


@pytest.mark.integration
class TestDiLoCoIntegration:
    def test_streaming_two_groups_anchors_identical(self):
        """Streaming DiLoCo: params drift locally by design, but every
        committed fragment round must land the same anchor on every group
        (the fragment schedule derives from the shared round counter)."""
        lh = Lighthouse(bind="127.0.0.1:0", min_replicas=2,
                        join_timeout_ms=1000, quorum_tick_ms=50)

        def run_group(group):
            def loss_fn(params, batch):
                return jnp.mean((params["w"] - batch) ** 2
                                ) + jnp.mean((params["b"] - batch[:2]) ** 2)

            t = StreamingDiLoCoTrainer(
                loss_fn=loss_fn,
                inner_tx=optax.sgd(0.05),
                params={"w": jnp.zeros(4), "b": jnp.zeros(2)},
                manager_factory=lambda load, save: Manager(
                    comm=HostCommunicator(timeout_sec=15),
                    load_state_dict=load,
                    state_dict=save,
                    min_replica_size=2,
                    replica_id=f"sdiloco{group}",
                    lighthouse_addr=lh.address(),
                    rank=0, world_size=1,
                    timeout_ms=15_000, quorum_timeout_ms=15_000,
                ),
                sync_every=4,
                fragments=2,
            )
            target = jnp.full(4, float(group + 1))
            try:
                while t.manager.current_step() < 4:  # 4 fragment rounds
                    t.train_step(target)
                t.flush()
                return jax.device_get(t.anchor)
            finally:
                t.shutdown()

        try:
            with ThreadPoolExecutor(max_workers=2) as pool:
                futs = [pool.submit(run_group, g) for g in range(2)]
                results = [f.result(timeout=120) for f in futs]
        finally:
            lh.shutdown()
        np.testing.assert_array_equal(results[0]["w"], results[1]["w"])
        np.testing.assert_array_equal(results[0]["b"], results[1]["b"])
        assert float(np.abs(results[0]["w"]).mean()) > 0

    def test_streaming_death_and_heal_keeps_anchors_identical(self):
        """Kill+restart a group mid-stream: the rejoiner must pick the
        quorum-agreed fragment (not one derived from its stale local
        step), heal, and land bit-identical anchors. Guards the
        fragment-id-from-pre-quorum-step bug."""
        joint_rounds = 6  # after the rejoiner has healed
        lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                        join_timeout_ms=500, quorum_tick_ms=20)

        def make(group):
            def loss_fn(params, batch):
                return (jnp.mean((params["w"] - batch) ** 2)
                        + jnp.mean((params["b"] - batch[:2]) ** 2))

            return StreamingDiLoCoTrainer(
                loss_fn=loss_fn,
                inner_tx=optax.sgd(0.05),
                params={"w": jnp.zeros(4), "b": jnp.zeros(2)},
                manager_factory=lambda load, save: Manager(
                    comm=HostCommunicator(timeout_sec=15),
                    load_state_dict=load,
                    state_dict=save,
                    min_replica_size=1,
                    replica_id=f"shl{group}",
                    lighthouse_addr=lh.address(),
                    rank=0, world_size=1,
                    timeout_ms=15_000, quorum_timeout_ms=15_000,
                ),
                sync_every=4,
                fragments=2,
            )

        # The last round, named by the rejoiner once it has healed. A
        # survivor that stopped at a round fixed beforehand could be
        # through with it before the rejoiner is back (its restart
        # compiles), and leave it nobody to heal from.
        stop_at = {"round": None}

        def running(t):
            last = stop_at["round"]
            return last is None or t.manager.current_step() < last

        def survivor():
            t = make(0)
            target = jnp.full(4, 1.0)
            try:
                while running(t):
                    assert t.manager.current_step() < 20_000
                    t.train_step(target)
                t.flush()
                return jax.device_get(t.anchor)
            finally:
                t.shutdown()

        def victim():
            t = make(1)
            target = jnp.full(4, 2.0)
            try:
                while t.manager.current_step() < 2:
                    t.train_step(target)
            finally:
                t.shutdown()  # dies
            t = make(1)  # restart: fresh params, must rejoin + heal
            try:
                while running(t):
                    t.train_step(target)
                    if (stop_at["round"] is None
                            and t.manager.metrics()["heal_count"] >= 1):
                        # healed, so in lockstep with the survivor from
                        # here on: both end a few joint rounds later
                        stop_at["round"] = \
                            t.manager.current_step() + joint_rounds
                t.flush()
                return jax.device_get(t.anchor)
            finally:
                t.shutdown()

        try:
            with ThreadPoolExecutor(max_workers=2) as pool:
                fa, fb = pool.submit(survivor), pool.submit(victim)
                a, b_res = fa.result(timeout=180), fb.result(timeout=180)
        finally:
            lh.shutdown()
        np.testing.assert_array_equal(a["w"], b_res["w"])
        np.testing.assert_array_equal(a["b"], b_res["b"])

    def test_two_groups_converge_identically(self):
        lh = Lighthouse(bind="127.0.0.1:0", min_replicas=2,
                        join_timeout_ms=1000, quorum_tick_ms=50)

        def run_group(group):
            def loss_fn(params, batch):
                return jnp.mean((params["w"] - batch) ** 2)

            t = DiLoCoTrainer(
                loss_fn=loss_fn,
                inner_tx=optax.sgd(0.05),
                params={"w": jnp.zeros(4)},
                manager_factory=lambda load, save: Manager(
                    comm=HostCommunicator(timeout_sec=15),
                    load_state_dict=load,
                    state_dict=save,
                    min_replica_size=2,
                    replica_id=f"diloco{group}",
                    lighthouse_addr=lh.address(),
                    rank=0, world_size=1,
                    timeout_ms=15_000, quorum_timeout_ms=15_000,
                ),
                sync_every=3,
            )
            # groups chase different targets; outer rounds reconcile
            target = jnp.full(4, float(group + 1))
            try:
                while t.manager.current_step() < 3:  # 3 outer rounds
                    t.train_step(target)
                return jax.device_get(t.anchor)
            finally:
                t.shutdown()

        try:
            with ThreadPoolExecutor(max_workers=2) as pool:
                futs = [pool.submit(run_group, g) for g in range(2)]
                results = [f.result(timeout=120) for f in futs]
        finally:
            lh.shutdown()
        np.testing.assert_array_equal(results[0]["w"], results[1]["w"])
        assert float(results[0]["w"].mean()) > 0  # moved off init
