"""Manager state-machine unit tests.

Mirrors the reference's mocked-client test strategy
(/root/reference/torchft/manager_test.py): a real :class:`Manager` with the
native ``ManagerClient`` replaced by a mock and the communicator replaced by
:class:`DummyCommunicator`, making every protocol branch testable in one
process — happy path, sync/async healing, error latching + next-step
recovery, spares participation, and 1/n numerics.
"""

from unittest.mock import MagicMock, patch

import jax
import numpy as np
import pytest

import conftest
from mockplane import make_manager, quorum_result
from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.exchange import _derive_schedule
from torchft_tpu.manager import Manager, WorldSizeMode

requires_native = conftest.requires_native()


class TestManagerHappyPath:
    """reference manager_test.py:81-113"""

    def test_step_commit(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result(max_step=1)
        client.should_commit.return_value = True
        comm = DummyCommunicator()
        m = make_manager(client, comm)
        try:
            assert m.current_step() == 0
            m.step()
            fut = m.allreduce({"g": np.array([2.0, 4.0])})
            out = fut.result()
            # DummyCommunicator returns input unchanged; n=2 → halved.
            np.testing.assert_allclose(out["g"], [1.0, 2.0])
            assert m.should_commit()
            assert m.current_step() == 1
            assert m.num_participants() == 2
            assert comm.configure_count == 1  # quorum_id -1 → 1
            m.step()
            assert m.current_step() == 2
            assert m.batches_committed() == 2
            # same quorum id → no reconfigure
            assert comm.configure_count == 1
        finally:
            m.shutdown()

    def test_quorum_id_change_reconfigures(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result(quorum_id=1)
        client.should_commit.return_value = True
        comm = DummyCommunicator()
        m = make_manager(client, comm)
        try:
            m.step()
            m.should_commit()
            client.quorum.return_value = quorum_result(quorum_id=2)
            m.step()
            m.allreduce({"g": np.zeros(1)}).result()
            assert comm.configure_count == 2
        finally:
            m.shutdown()


class TestManagerHealing:
    """reference manager_test.py:116-257"""

    def _heal_quorum(self, max_step=20):
        return quorum_result(
            quorum_id=1, max_step=max_step, max_rank=None, max_world_size=1,
            replica_rank=1, replica_world_size=2, heal=True,
        )

    def _patch_heal(self, state):
        checkpoint = patch(
            "torchft_tpu.manager.CheckpointServer.load_from_address",
            return_value=state,
        )
        primary = patch("torchft_tpu.manager.ManagerClient")
        return checkpoint, primary

    def test_async_heal(self):
        client = MagicMock()
        client.quorum.return_value = self._heal_quorum(max_step=20)
        client.should_commit.return_value = True
        loaded = MagicMock()
        m = make_manager(client, use_async_quorum=True,
                         load_state_dict=loaded, min_replica_size=1)
        state = {"user": {"w": np.full(2, 7.0)},
                 "torchft": {"step": 20, "batches_committed": 40}}
        cp, pc = self._patch_heal(state)
        try:
            with cp, pc:
                m.step()
                # healer zeroes its contribution
                fut = m.allreduce({"g": np.array([8.0])})
                np.testing.assert_allclose(fut.result()["g"], [0.0])
                assert m.is_healing()
                assert not m.is_participating()
                assert m.num_participants() == 1
                assert m.should_commit()
            # user state applied on the main thread at commit
            loaded.assert_called_once()
            assert loaded.call_args[0][0] == state["user"]
            # manager metadata restored: step jumped to max_step
            assert m.current_step() == 20
            # next step participates normally
            client.quorum.return_value = quorum_result(
                quorum_id=1, max_step=21, max_rank=1, max_world_size=2,
                replica_rank=1, replica_world_size=2)
            m.step()
            m._quorum_future.result()  # deterministic: join quorum thread
            assert m.current_step() == 21
            assert not m.is_healing()
            assert m.is_participating()
        finally:
            m.shutdown()

    def test_round_never_joined_answers_into_its_own_future(self):
        """A step thread may call step() again with the last round still
        in flight (a train_step that raised between step() and its
        join): that round's answer goes into the future step() made for
        IT, and round_heals() says what the newest round answered."""
        import threading
        client = MagicMock()
        held = threading.Event()
        later = quorum_result(
            quorum_id=1, max_step=21, max_rank=1, max_world_size=2,
            replica_rank=1, replica_world_size=2)

        def quorum(**_kw):
            if client.quorum.call_count == 1:
                assert held.wait(30)
                return self._heal_quorum(max_step=20)
            return later

        client.quorum.side_effect = quorum
        client.should_commit.return_value = True
        m = make_manager(client, use_async_quorum=True,
                         load_state_dict=MagicMock(), min_replica_size=1)
        state = {"user": {"w": np.full(2, 7.0)},
                 "torchft": {"step": 20, "batches_committed": 40}}
        cp, pc = self._patch_heal(state)
        try:
            with cp, pc:
                m.step()
                first = m._round_answer
                m.step()                    # the first round never joined
                assert m._round_answer is not first
                held.set()
                assert first.result(30) is True
                assert m.round_heals() is False
                m._quorum_future.result(30)   # no InvalidStateError
        finally:
            held.set()
            m.shutdown()

    def test_sync_heal_participates_immediately(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            quorum_id=1, max_step=5, max_rank=None, max_world_size=1,
            replica_rank=1, replica_world_size=2, heal=True)
        client.should_commit.return_value = True
        loaded = MagicMock()
        m = make_manager(client, use_async_quorum=False,
                         load_state_dict=loaded, min_replica_size=1)
        state = {"user": {"w": np.zeros(1)},
                 "torchft": {"step": 5, "batches_committed": 10}}
        cp, pc = self._patch_heal(state)
        try:
            with cp, pc:
                m.step()
            # sync mode: state restored before compute, participates now
            loaded.assert_called_once()
            assert m.is_participating()
            assert m.num_participants() == 2
            assert m.current_step() == 5
        finally:
            m.shutdown()

    def test_async_heal_too_few_participants_aborts_commit(self):
        client = MagicMock()
        client.quorum.return_value = self._heal_quorum()
        client.should_commit.return_value = False
        m = make_manager(client, min_replica_size=2)  # only 1 at max step
        state = {"user": {}, "torchft": {"step": 20, "batches_committed": 0}}
        cp, pc = self._patch_heal(state)
        try:
            with cp, pc:
                m.step()
                assert not m.should_commit()
            # local vote must have been False (not enough participants)
            assert client.should_commit.call_args.kwargs["should_commit"] is False
        finally:
            m.shutdown()


class TestManagerHealFailover:
    """ISSUE 3 acceptance, manager level, pure Python (no native lib):
    the donor dies at >=50% heal-transfer progress; the Manager
    re-resolves a fresh donor via re-quorum and the SAME resumable
    transfer completes from the second donor with bitwise-identical
    state, re-sending strictly less than the full payload."""

    def test_donor_death_mid_heal_fails_over_via_requorum(self):
        import urllib.parse

        from torchft_tpu import chaos as chaos_mod
        from torchft_tpu.chaos import ChaosSchedule, EndpointChaos
        from torchft_tpu.checkpointing import CheckpointServer
        from torchft_tpu.serialization import plan_pytree

        rng = np.random.RandomState(3)
        user_state = {f"w{i}": rng.rand(4096).astype(np.float32)
                      for i in range(8)}
        donor_state = {"user": user_state,
                       "torchft": {"step": 20, "batches_committed": 40}}
        donor_a = CheckpointServer(lambda: donor_state,
                                   bind_host="127.0.0.1")
        donor_b = CheckpointServer(lambda: donor_state,
                                   bind_host="127.0.0.1")
        donor_a.allow_checkpoint(20)
        donor_b.allow_checkpoint(20)
        payload = plan_pytree(donor_state).total_len
        netloc_a = urllib.parse.urlparse(donor_a.address()).netloc
        # donor A's stream dies deterministically at ~60% of the payload
        chaos_mod.install(ChaosSchedule(seed=0, endpoints={
            f"heal:{netloc_a}": EndpointChaos(
                kill_after_bytes=int(payload * 0.6)),
        }))

        def heal_quorum(recover):
            return quorum_result(
                quorum_id=1, max_step=20, max_rank=None, max_world_size=1,
                replica_rank=1, replica_world_size=2, heal=True,
                recover_manager_address=recover)

        client = MagicMock()
        # initial quorum names donor A; the mid-heal re-quorum (after A's
        # death) names donor B
        client.quorum.side_effect = [heal_quorum("managerA"),
                                     heal_quorum("managerB")]
        client.should_commit.return_value = True
        ckpt_addrs = {"managerA": donor_a.address(),
                      "managerB": donor_b.address()}

        def make_client(addr, **kwargs):
            mc = MagicMock()
            mc.checkpoint_address.return_value = ckpt_addrs[addr]
            return mc

        loaded = MagicMock()
        pc = patch("torchft_tpu.manager.ManagerClient",
                   side_effect=make_client)
        m = make_manager(
            client, use_async_quorum=True, load_state_dict=loaded,
            min_replica_size=1,
            state_dict=lambda: {f"w{i}": np.zeros(4096, np.float32)
                                for i in range(8)})
        try:
            with pc:
                m.step()
                assert m.should_commit()
        finally:
            m.shutdown()
            chaos_mod.uninstall()
            donor_a.shutdown()
            donor_b.shutdown()

        # healed user state applied at commit, bitwise identical
        loaded.assert_called_once()
        healed = loaded.call_args[0][0]
        for key, arr in user_state.items():
            assert healed[key].tobytes() == arr.tobytes()
        assert m.current_step() == 20  # manager metadata restored

        mx = m.metrics()
        assert mx["heal_count"] == 1
        assert mx["heal_donor_failovers"] == 1
        assert mx["heal_attempts_total"] >= 2
        # the resumed leg re-sent strictly less than the full payload
        assert 0 < mx["heal_bytes_resumed_total"] < payload
        # >=50% of the transfer survived the donor's death
        assert mx["heal_bytes_resumed_total"] <= payload * 0.5
        assert mx["heal_bytes_total"] > 0
        # live progress gauge landed on a completed transfer
        assert mx["heal_last_payload_bytes"] == payload
        assert mx["heal_last_bytes_committed"] > 0
        # both quorum joins happened (initial + mid-heal re-resolution)
        assert client.quorum.call_count == 2
        events = [e["event"] for e in m.history()]
        assert "heal_failover" in events
        assert "heal" in events

    def test_requorum_moved_on_aborts_failover(self):
        """When the mid-heal re-quorum no longer heals at the same
        max_step (the world moved on), the failover is abandoned and the
        heal fails cleanly — the next step starts a fresh heal."""
        import urllib.parse

        from torchft_tpu import chaos as chaos_mod
        from torchft_tpu.chaos import ChaosSchedule, EndpointChaos
        from torchft_tpu.checkpointing import CheckpointServer
        from torchft_tpu.serialization import plan_pytree

        user_state = {"w": np.arange(8192, dtype=np.float32)}
        donor_state = {"user": user_state,
                       "torchft": {"step": 20, "batches_committed": 40}}
        donor_a = CheckpointServer(lambda: donor_state,
                                   bind_host="127.0.0.1")
        donor_a.allow_checkpoint(20)
        payload = plan_pytree(donor_state).total_len
        netloc_a = urllib.parse.urlparse(donor_a.address()).netloc
        chaos_mod.install(ChaosSchedule(seed=0, endpoints={
            f"heal:{netloc_a}": EndpointChaos(
                kill_after_bytes=int(payload * 0.5)),
        }))

        client = MagicMock()
        client.quorum.side_effect = [
            quorum_result(quorum_id=1, max_step=20, max_rank=None,
                          max_world_size=1, replica_rank=1,
                          replica_world_size=2, heal=True,
                          recover_manager_address="managerA"),
            # re-quorum: everyone advanced, no heal offered at step 20
            quorum_result(quorum_id=1, max_step=25, max_rank=1,
                          max_world_size=2, replica_rank=1,
                          replica_world_size=2, heal=False),
        ]
        client.should_commit.return_value = False
        loaded = MagicMock()

        def make_client(addr, **kwargs):
            mc = MagicMock()
            mc.checkpoint_address.return_value = donor_a.address()
            return mc

        m = make_manager(
            client, use_async_quorum=True, load_state_dict=loaded,
            min_replica_size=1,
            state_dict=lambda: {"w": np.zeros(8192, np.float32)})
        try:
            with patch("torchft_tpu.manager.ManagerClient",
                       side_effect=make_client):
                m.step()
                # heal failed (donor dead, no replacement): the step
                # aborts instead of wedging
                assert not m.should_commit()
        finally:
            m.shutdown()
            chaos_mod.uninstall()
            donor_a.shutdown()
        loaded.assert_not_called()
        assert m.errored() is not None
        mx = m.metrics()
        assert mx["heal_donor_failovers"] == 0
        assert mx["heal_count"] == 1
        # failed heals still record their wire cost + attempt history
        assert mx["heal_attempts_total"] >= 1
        assert mx["heal_bytes_total"] > 0


class TestManagerErrors:
    """reference manager_test.py:260-342"""

    def test_allreduce_error_latches_and_recovers(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.side_effect = [False, True]
        comm = DummyCommunicator()
        m = make_manager(client, comm)
        try:
            m.step()
            comm.allreduce = MagicMock(side_effect=RuntimeError("boom"))
            tree = {"g": np.array([3.0])}
            out = m.allreduce(tree).result()
            np.testing.assert_allclose(out["g"], [3.0])  # fallback: unchanged
            assert m.errored() is not None
            # further collectives no-op instantly
            out2 = m.allreduce({"g": np.array([5.0])}).result()
            np.testing.assert_allclose(out2["g"], [5.0])
            assert not m.should_commit()
            assert client.should_commit.call_args.kwargs["should_commit"] is False

            # next step: error cleared, step NOT bumped (no commit)
            comm.allreduce = DummyCommunicator.allreduce.__get__(comm)
            m.step()
            assert m.errored() is None
            assert m.current_step() == 1
            m.allreduce({"g": np.array([4.0])}).result()
            assert m.should_commit()
        finally:
            m.shutdown()

    def test_poisoned_future_swallowed(self):
        from concurrent.futures import Future

        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = False
        comm = DummyCommunicator()
        poisoned: Future = Future()
        poisoned.set_exception(RuntimeError("late failure"))
        comm.allreduce = MagicMock(return_value=poisoned)
        m = make_manager(client, comm)
        try:
            m.step()
            out = m.allreduce({"g": np.array([1.0, 2.0])}).result()
            np.testing.assert_allclose(out["g"], [1.0, 2.0])
            assert m.errored() is not None
            assert not m.should_commit()
        finally:
            m.shutdown()

    def test_quorum_error_latches(self):
        client = MagicMock()
        client.quorum.side_effect = RuntimeError("lighthouse down")
        client.should_commit.return_value = False
        m = make_manager(client)
        try:
            m.step()
            out = m.allreduce({"g": np.array([9.0])}).result()
            np.testing.assert_allclose(out["g"], [9.0])
            assert m.errored() is not None
        finally:
            m.shutdown()


class TestMetrics:
    """Observability surface beyond the reference's
    current_step/batches_committed (manager.py:484-506)."""

    def test_counters_and_timings_update(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result(max_step=1)
        client.should_commit.return_value = True
        m = make_manager(client)
        try:
            m.step()
            m.allreduce({"g": np.array([2.0, 4.0])}).result()
            assert m.should_commit()
            metrics = m.metrics()
            assert metrics["quorum_count"] == 1
            assert metrics["quorum_ms_total"] >= 0.0
            assert metrics["reconfigure_count"] == 1  # quorum_id -1 -> 1
            assert metrics["allreduce_count"] == 1
            assert metrics["commit_count"] == 1
            assert metrics["committed_steps"] == 1
            assert metrics["aborted_steps"] == 0
            assert metrics["heal_count"] == 0
        finally:
            m.shutdown()

    def test_aborted_step_counted(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result(max_step=1)
        client.should_commit.return_value = False
        m = make_manager(client)
        try:
            m.step()
            assert not m.should_commit()
            metrics = m.metrics()
            assert metrics["aborted_steps"] == 1
            assert metrics["committed_steps"] == 0
        finally:
            m.shutdown()


class TestFailFast:
    """Persistent control-plane failure must surface to the caller instead
    of livelocking the training loop (round-1 VERDICT weak #8)."""

    def test_raises_after_consecutive_quorum_failures(self):
        client = MagicMock()
        client.quorum.side_effect = RuntimeError("lighthouse down")
        client.should_commit.return_value = False
        m = Manager(
            comm=DummyCommunicator(),
            load_state_dict=MagicMock(),
            state_dict=lambda: {},
            min_replica_size=2,
            rank=0,
            world_size=1,
            replica_id="testgroup",
            max_consecutive_failures=3,
            _manager_client=client,
        )
        try:
            with pytest.raises(RuntimeError, match="consecutive quorum"):
                for _ in range(10):
                    m.step()
                    assert not m.should_commit()
            # It took exactly max_consecutive_failures failed rounds.
            assert client.quorum.call_count == 3
        finally:
            m.shutdown()

    def test_streak_resets_on_success(self):
        client = MagicMock()
        client.quorum.side_effect = [
            RuntimeError("blip"),
            quorum_result(max_step=1),
            quorum_result(max_step=2),
        ]
        client.should_commit.side_effect = [False, True, True]
        m = Manager(
            comm=DummyCommunicator(),
            load_state_dict=MagicMock(),
            state_dict=lambda: {},
            min_replica_size=2,
            rank=0,
            world_size=1,
            replica_id="testgroup",
            max_consecutive_failures=2,
            _manager_client=client,
        )
        try:
            m.step()
            assert not m.should_commit()
            m.step()  # succeeds, resets the streak
            assert m.should_commit()
            m.step()  # must NOT raise even though one failure happened
            assert m.should_commit()
        finally:
            m.shutdown()


class TestSpares:
    """reference manager_test.py:345-379"""

    def test_spare_is_benched(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            max_rank=2, max_world_size=3, replica_rank=2,
            replica_world_size=3)
        client.should_commit.return_value = True
        m = make_manager(client, min_replica_size=2,
                         world_size_mode=WorldSizeMode.FIXED_WITH_SPARES)
        try:
            m.step()
            out = m.allreduce({"g": np.array([6.0])}).result()
            # benched: zero contribution, world clamped to 2 → 0/2
            np.testing.assert_allclose(out["g"], [0.0])
            assert not m.is_participating()
            assert m.num_participants() == 2
        finally:
            m.shutdown()

    def test_non_spare_clamped_world(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            max_rank=1, max_world_size=3, replica_rank=1,
            replica_world_size=3)
        client.should_commit.return_value = True
        m = make_manager(client, min_replica_size=2,
                         world_size_mode=WorldSizeMode.FIXED_WITH_SPARES)
        try:
            m.step()
            out = m.allreduce({"g": np.array([6.0])}).result()
            np.testing.assert_allclose(out["g"], [3.0])  # 1/2 not 1/3
            assert m.is_participating()
        finally:
            m.shutdown()


class TestNumerics:
    """reference manager_test.py:405-427"""

    @pytest.mark.parametrize("world", [1, 2, 4, 7])
    def test_one_over_n(self, world):
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            max_rank=0, max_world_size=world, replica_rank=0,
            replica_world_size=world)
        client.should_commit.return_value = True
        m = make_manager(client, min_replica_size=1)
        try:
            m.step()
            out = m.allreduce({"g": np.full(3, float(world))}).result()
            np.testing.assert_allclose(out["g"], np.ones(3))
        finally:
            m.shutdown()

    def test_int_grads_floor_divide(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        m = make_manager(client)
        try:
            m.step()
            out = m.allreduce({"g": np.array([5], dtype=np.int64)}).result()
            assert out["g"][0] == 2
        finally:
            m.shutdown()

    @requires_native
    @pytest.mark.parametrize("bucket_bytes", [1, 64, 1 << 20])
    def test_bucketed_matches_single(self, bucket_bytes):
        """The pipelined bucketed host allreduce is numerically identical
        to the single-shot path at world=2, where two-term sums are
        order-insensitive (at world>=3 ring chunk boundaries shift with
        bucketing, allowing last-ulp reorder differences — see
        _host_allreduce_pipelined's docstring). bucket_bytes=1 forces one
        bucket per leaf; 1MB collapses to a single bucket (the old
        behavior). Cross-rank bitwise agreement is asserted at any world
        by comparing both ranks' results below."""
        import threading as _t

        from torchft_tpu._native import Store
        from torchft_tpu.backends.host import HostCommunicator

        store = Store(bind="127.0.0.1:0")
        world = 2
        rng = np.random.default_rng(0)
        tree = {
            "a": rng.normal(size=(17, 3)).astype(np.float32),
            "b": rng.normal(size=(130,)).astype(np.float32),
            "c": {"d": rng.normal(size=(5,)).astype(np.float64),
                  "e": np.arange(6, dtype=np.int64)},
        }
        expected = {  # mean of (tree, 2*tree) = 1.5*tree; int floor-divides
            "a": tree["a"] * 1.5,
            "b": tree["b"] * 1.5,
            "c": {"d": tree["c"]["d"] * 1.5,
                  "e": (tree["c"]["e"] * 3) // 2},
        }
        results = [None] * world
        errors = []

        def run(rank):
            client = MagicMock()
            client.quorum.return_value = quorum_result(
                store_address=store.address(),
                max_rank=rank, max_world_size=world,
                replica_rank=rank, replica_world_size=world)
            client.should_commit.return_value = True
            m = make_manager(
                client, comm=HostCommunicator(timeout_sec=30),
                allreduce_bucket_bytes=bucket_bytes)
            try:
                m.step()
                scaled = jax.tree_util.tree_map(
                    lambda a: a * (rank + 1), tree)
                results[rank] = m.allreduce(scaled).result(timeout=30)
                assert m.should_commit()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                m.shutdown()

        threads = [_t.Thread(target=run, args=(r,)) for r in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        alive = [t for t in threads if t.is_alive()]
        store.shutdown()
        assert not alive, "pipelined allreduce deadlocked"
        assert not errors, errors
        for out in results:
            assert out is not None, "worker produced no result"
            flat_out = jax.tree_util.tree_leaves(out)
            flat_exp = jax.tree_util.tree_leaves(expected)
            assert len(flat_out) == len(flat_exp)
            for o, e in zip(flat_out, flat_exp):
                np.testing.assert_array_equal(np.asarray(o), e)

    @requires_native
    def test_zero_element_leaf_and_host_leaves_under_wire(self):
        """Two packing edge cases: (1) a 0-element leaf must contribute 0
        to the packed payload geometry (an off-by-one would wedge the
        ring / break the split); (2) the wire dtype is END-TO-END (the
        TCP ring carries it too, not just the D2H leg), so host-native
        float leaves are quantized exactly once like every other
        contribution — bounded by one bf16 quantization each, and
        bitwise identical across ranks."""
        import threading as _t

        import jax.numpy as jnp

        from torchft_tpu._native import Store
        from torchft_tpu.backends.host import HostCommunicator

        store = Store(bind="127.0.0.1:0")
        world = 2
        rng = np.random.default_rng(2)
        host_leaf = rng.normal(size=(33,)).astype(np.float32)
        tree = {
            "empty": np.zeros((0, 5), np.float32),
            "host": host_leaf,                      # numpy: stays exact
            "dev": jnp.asarray(rng.normal(size=(40,)).astype(np.float32)),
        }
        results = [None] * world
        errors = []

        def run(rank):
            client = MagicMock()
            client.quorum.return_value = quorum_result(
                store_address=store.address(),
                max_rank=rank, max_world_size=world,
                replica_rank=rank, replica_world_size=world)
            client.should_commit.return_value = True
            m = make_manager(
                client, comm=HostCommunicator(timeout_sec=30),
                allreduce_bucket_bytes=64,  # force multi-bucket
                allreduce_wire_dtype=jnp.bfloat16)
            try:
                m.step()
                scaled = jax.tree_util.tree_map(
                    lambda a: a * (rank + 1), tree)
                results[rank] = m.allreduce(scaled).result(timeout=30)
                assert m.should_commit()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                m.shutdown()

        threads = [_t.Thread(target=run, args=(r,)) for r in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        alive = [t for t in threads if t.is_alive()]
        store.shutdown()
        assert not alive, "packed allreduce deadlocked on empty leaf"
        assert not errors, errors
        # One bf16 quantization of each local contribution bounds the
        # error of the mean: |got - exact| <= (|q(x1)-x1| + |q(x2)-x2|)/2
        # (evaluated in f64, with an ulp cushion for the f32 fold).
        x64 = host_leaf.astype(np.float64)
        q1 = host_leaf.astype(jnp.bfloat16).astype(np.float64)
        q2 = (host_leaf * 2).astype(jnp.bfloat16).astype(np.float64)
        bound = (np.abs(q1 - x64) + np.abs(q2 - 2 * x64)) / 2
        cushion = 1e-6 * (1.0 + np.abs(1.5 * x64))
        for out in results:
            assert out["empty"].shape == (0, 5)
            got = np.asarray(out["host"]).astype(np.float64)
            assert np.all(np.abs(got - 1.5 * x64) <= bound + cushion)
        # Cross-rank bitwise agreement (canonical-order f32 fold).
        np.testing.assert_array_equal(np.asarray(results[0]["host"]),
                                      np.asarray(results[1]["host"]))
        np.testing.assert_array_equal(np.asarray(results[0]["dev"]),
                                      np.asarray(results[1]["dev"]))

    @requires_native
    def test_bf16_wire_compression_close_to_exact(self):
        """allreduce_wire_dtype=bfloat16 quantizes each local contribution
        once; the sum/scale stay f32, so the result tracks the exact mean
        within bf16 rounding (~3 decimal digits)."""
        import threading as _t

        import jax.numpy as jnp

        from torchft_tpu._native import Store
        from torchft_tpu.backends.host import HostCommunicator

        store = Store(bind="127.0.0.1:0")
        world = 2
        rng = np.random.default_rng(1)
        base = rng.normal(size=(257,)).astype(np.float32)
        results = [None] * world
        errors = []

        def run(rank):
            client = MagicMock()
            client.quorum.return_value = quorum_result(
                store_address=store.address(),
                max_rank=rank, max_world_size=world,
                replica_rank=rank, replica_world_size=world)
            client.should_commit.return_value = True
            m = Manager(
                comm=HostCommunicator(timeout_sec=30),
                load_state_dict=MagicMock(),
                state_dict=lambda: {},
                min_replica_size=2, rank=0, world_size=1,
                replica_id=f"wire{rank}",
                allreduce_wire_dtype=jnp.bfloat16,
                _manager_client=client,
            )
            try:
                m.step()
                tree = {"g": jnp.asarray(base * (rank + 1))}
                results[rank] = m.allreduce(tree).result(timeout=30)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                m.shutdown()

        threads = [_t.Thread(target=run, args=(r,)) for r in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        alive = [t for t in threads if t.is_alive()]
        store.shutdown()
        assert not alive, "wire-compressed allreduce deadlocked"
        assert not errors, errors
        for out in results:
            assert out is not None, "worker produced no result"
            # Callers must get their original dtype back, not the wire one.
            assert np.dtype(out["g"].dtype) == np.float32
            got = np.asarray(out["g"])
            np.testing.assert_allclose(got, base * 1.5, rtol=1e-2, atol=1e-2)

    @requires_native
    def test_wire_ring_matches_upcast_before_ring(self):
        """The wire-dtype ring must match the upcast-before-ring path it
        replaced within one bf16 quantization of each local contribution.
        At world 2 the match is exact: raw bf16 contributions cross the
        wire once and fold into an f32 accumulator — the same values,
        sum, and 1/n the old path computed after upcasting on the host —
        so the results are bitwise identical."""
        import threading as _t

        import jax.numpy as jnp

        from torchft_tpu._native import Store
        from torchft_tpu.backends.host import HostCommunicator

        store = Store(bind="127.0.0.1:0")
        world = 2
        rng = np.random.default_rng(3)
        base = rng.normal(size=(513,)).astype(np.float32)
        results = [None] * world
        errors = []

        def run(rank):
            client = MagicMock()
            client.quorum.return_value = quorum_result(
                store_address=store.address(),
                max_rank=rank, max_world_size=world,
                replica_rank=rank, replica_world_size=world)
            client.should_commit.return_value = True
            m = make_manager(
                client, comm=HostCommunicator(timeout_sec=30),
                allreduce_wire_dtype=jnp.bfloat16)
            try:
                m.step()
                tree = {"g": jnp.asarray(base * (rank + 1))}
                results[rank] = m.allreduce(tree).result(timeout=30)
                assert m.should_commit()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                m.shutdown()

        threads = [_t.Thread(target=run, args=(r,)) for r in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        alive = [t for t in threads if t.is_alive()]
        store.shutdown()
        assert not alive, "wire ring deadlocked"
        assert not errors, errors
        # Upcast-before-ring expectation: quantize each contribution
        # once (the device pack's bf16 cast), sum + 1/n in f32.
        q = [np.asarray(jnp.asarray(base * (r + 1))
                        .astype(jnp.bfloat16).astype(jnp.float32))
             for r in range(world)]
        expected = (q[0] + q[1]) / 2
        x64 = base.astype(np.float64)
        exact = 1.5 * x64
        bound = (np.abs(q[0].astype(np.float64) - x64)
                 + np.abs(q[1].astype(np.float64) - 2 * x64)) / 2
        cushion = 1e-6 * (1.0 + np.abs(exact))
        for out in results:
            got = np.asarray(out["g"])
            assert np.dtype(got.dtype) == np.float32
            np.testing.assert_array_equal(got, expected)
            diff = np.abs(got.astype(np.float64) - exact)
            assert np.all(diff <= bound + cushion)
        np.testing.assert_array_equal(
            np.asarray(results[0]["g"]), np.asarray(results[1]["g"]))

    def test_state_dict_roundtrip(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        m = make_manager(client)
        try:
            m.step()
            m.should_commit()
            sd = m.state_dict()
            assert sd == {"step": 1, "batches_committed": 0}
            m.load_state_dict({"step": 42, "batches_committed": 84})
            assert m.current_step() == 42
            assert m.batches_committed() == 84
        finally:
            m.shutdown()


class TestSchedule:
    """The memoized bucket/chunk schedule: metadata-only derivation (so
    participant, healer, and spare ranks agree byte-for-byte) and
    steady-state caching (so later steps skip the Python re-derivation)."""

    METAS = (
        ((17, 3), "float32"),
        ((130,), "float32"),
        ((0, 5), "float32"),
        ((5,), "float64"),
        ((6,), "int64"),
    )

    def test_cross_rank_fingerprint_identical(self):
        import jax.numpy as jnp

        a = _derive_schedule(self.METAS, 256, jnp.bfloat16)
        b = _derive_schedule(self.METAS, 256, jnp.bfloat16)
        assert a.fingerprint == b.fingerprint
        assert a.buckets == b.buckets
        for cs_a, cs_b in zip(a.chunks, b.chunks):
            for ca, cb in zip(cs_a, cs_b):
                assert (ca.orig, ca.wire, ca.idx, ca.offs, ca.sizes,
                        ca.shapes, ca.total, ca.rows) == (
                            cb.orig, cb.wire, cb.idx, cb.offs, cb.sizes,
                            cb.shapes, cb.total, cb.rows)
        # Geometry invariants: every leaf appears exactly once (none is
        # wider than a slice here: tests/test_slices.py has those), as
        # the entry (leaf, offset 0, its whole size); 0-size leaves
        # contribute 0 elements; chunk totals match their sizes.
        seen = sorted(i for cs in a.chunks for c in cs for i in c.idx)
        assert seen == list(range(len(self.METAS)))
        assert not a.slices
        for cs in a.chunks:
            for c in cs:
                assert c.total == sum(c.sizes)
                assert c.rows is None and not any(c.offs)
                assert c.sizes == [int(np.prod(s)) for s in c.shapes]
        flat_sizes = {i: s for cs in a.chunks for c in cs
                      for i, s in zip(c.idx, c.sizes)}
        assert flat_sizes[2] == 0  # the (0, 5) leaf

    def test_wire_fields_change_fingerprint(self):
        import jax.numpy as jnp

        exact = _derive_schedule(self.METAS, 256, None)
        wire = _derive_schedule(self.METAS, 256, jnp.bfloat16)
        assert exact.fingerprint != wire.fingerprint
        # Wire compression narrows float chunks but never int chunks.
        wire_dtypes = {str(c.wire) for cs in wire.chunks for c in cs}
        assert "bfloat16" in wire_dtypes
        assert any(str(c.wire) == "int64" for cs in wire.chunks
                   for c in cs)

    def test_schedule_cached_across_participant_and_healer_views(
            self, exchange_rig):
        """Participant (device leaves), healer, and spare (host zero
        leaves) ranks must land on ONE cached schedule: the cache key is
        metadata-only, so the same object — hence byte-identical chunk
        geometry — serves all three roles."""
        import jax.numpy as jnp

        from torchft_tpu.manager import _zero_like

        x = exchange_rig(bucket_bytes=64, wire_dtype=jnp.bfloat16).x
        tree = {"a": jnp.ones((9, 3), jnp.float32),
                "b": jnp.zeros((40,), jnp.float32)}
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        healer_leaves = [_zero_like(a) for a in leaves]
        s_part = x.schedule(treedef, leaves)
        s_heal = x.schedule(treedef, healer_leaves)
        assert s_part is s_heal  # one cache entry, identical geometry
        assert x.schedule(treedef, leaves) is s_part  # steady state


def _make_test_rings(world):
    """Socketpair ring for world thread-ranks: pair[i] connects rank i's
    next-hop to rank (i+1)%world's prev-hop. No store rendezvous, no
    native control plane — the real ring transport over real sockets."""
    import socket as _socket

    from torchft_tpu.backends.host import _Ring

    pairs = [_socket.socketpair() for _ in range(world)]
    return [_Ring(pairs[r][0], pairs[(r - 1) % world][1], _socket.socket())
            for r in range(world)]


def _wired_comm(ring, rank, world):
    """HostCommunicator with the store rendezvous replaced by a
    pre-wired ring, so the full pipelined allreduce — pack, async D2H,
    wire ring, device unpack — runs without the native library."""
    from unittest.mock import patch

    from torchft_tpu.backends import host as host_mod

    class WiredComm(host_mod.HostCommunicator):
        def configure(self, store_addr, rank, world_size):
            pass  # pre-wired

    # one ring, or a list of them: the epoch's lanes, with a worker each
    rings = list(ring) if isinstance(ring, list) else [ring]
    with patch.object(host_mod, "_RING_LANES", max(len(rings), 1)):
        c = WiredComm(timeout_sec=15)
    c._rings, c._rank, c._world = rings, rank, world
    return c


class TestWireRingPipelined:
    """End-to-end pipelined allreduce over real ring sockets (socketpair
    transport, mocked control plane): the tier-1 spelling of the
    numerics guarantees that don't need the native store."""

    def _run_steps(self, world, trees, lanes=1, **mkw):
        """One Manager a rank over pre-wired rings (``lanes`` of them a
        rank); ``trees[step](rank)`` is each step's gradient tree.
        Returns, per rank and step, the result (kept alive to the end)
        and ``Manager.metrics()``."""
        import threading as _t

        lane_rings = [_make_test_rings(world) for _ in range(lanes)]
        rings = [[lane[r] for lane in lane_rings] for r in range(world)]
        results = [[] for _ in range(world)]
        metrics = [[] for _ in range(world)]
        errors = []

        def run(rank):
            client = MagicMock()
            client.quorum.return_value = quorum_result(
                max_rank=rank, max_world_size=world,
                replica_rank=rank, replica_world_size=world)
            client.should_commit.return_value = True
            m = make_manager(client,
                             comm=_wired_comm(rings[rank], rank, world),
                             min_replica_size=world, **mkw)
            try:
                for tree_fn in trees:
                    m.step()
                    results[rank].append(
                        m.allreduce(tree_fn(rank)).result(timeout=30))
                    err = m.errored()
                    assert err is None, err
                    assert m.should_commit()
                    metrics[rank].append(m.metrics())
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                m.shutdown()

        threads = [_t.Thread(target=run, args=(r,)) for r in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90)
        alive = [t for t in threads if t.is_alive()]
        for lane in lane_rings:
            for r in lane:
                r.close()
        assert not alive, "pipelined allreduce deadlocked"
        assert not errors, errors
        return results, metrics

    def _run(self, world, tree_fn, **mkw):
        results, metrics = self._run_steps(world, [tree_fn], **mkw)
        return [r[0] for r in results], [m[0] for m in metrics]

    BASE = {
        "a": np.random.default_rng(0).normal(size=(257, 3)).astype(
            np.float32),
        "b": np.random.default_rng(1).normal(size=(1000,)).astype(
            np.float32),
        "empty": np.zeros((0, 5), np.float32),
        "i": np.arange(6, dtype=np.int32),
    }

    def test_exact_mode_bitwise(self):
        import jax.numpy as jnp

        def tf(rank):
            return jax.tree_util.tree_map(
                lambda a: jnp.asarray(a) * (rank + 1), self.BASE)

        results, metrics = self._run(2, tf, allreduce_bucket_bytes=1024)
        for out in results:
            for k in ("a", "b"):
                np.testing.assert_array_equal(
                    np.asarray(out[k]),
                    (self.BASE[k] * 1.5).astype(np.float32))
            assert out["empty"].shape == (0, 5)
            np.testing.assert_array_equal(np.asarray(out["i"]),
                                          (self.BASE["i"] * 3) // 2)
        mx = metrics[0]
        # Fetch split populated; exact mode moves identical bytes on
        # both legs (D2H and ring) at world 2.
        assert mx["allreduce_fetch_dispatch_ms_total"] > 0
        assert mx["allreduce_fetch_wait_ms_total"] > 0
        assert mx["allreduce_ring_wire_bytes_total"] == \
            mx["allreduce_wire_bytes_total"] > 0

    def test_bf16_wire_matches_upcast_path_bitwise_at_world2(self):
        import jax.numpy as jnp

        def tf(rank):
            return jax.tree_util.tree_map(
                lambda a: jnp.asarray(a) * (rank + 1), self.BASE)

        results, metrics = self._run(
            2, tf, allreduce_bucket_bytes=1024,
            allreduce_wire_dtype=jnp.bfloat16)
        for k in ("a", "b"):
            q = [np.asarray(
                jnp.ravel(jnp.asarray(self.BASE[k] * (r + 1)))
                .astype(jnp.bfloat16).astype(jnp.float32))
                .reshape(self.BASE[k].shape) for r in range(2)]
            expected = (q[0] + q[1]) / 2
            # Quantization bound evaluated in f64 (f32 evaluation of the
            # bound itself would flake on ulps — diff and bound are
            # mathematically EQUAL here), with an ulp cushion for the
            # f32 rounding of the accumulator sum.
            x64 = self.BASE[k].astype(np.float64)
            exact = 1.5 * x64
            bound = (np.abs(q[0].astype(np.float64) - x64)
                     + np.abs(q[1].astype(np.float64) - 2 * x64)) / 2
            cushion = 1e-6 * (1.0 + np.abs(exact))
            for out in results:
                got = np.asarray(out[k])
                assert np.dtype(got.dtype) == np.float32
                # Bitwise the upcast-before-ring result, and within one
                # bf16 quantization per contribution of the exact mean.
                np.testing.assert_array_equal(got, expected)
                diff = np.abs(got.astype(np.float64) - exact)
                assert np.all(diff <= bound + cushion)
        mx = metrics[0]
        # Float payload halves on BOTH legs; the int chunk stays wide.
        float_bytes = sum(self.BASE[k].size * 4 for k in ("a", "b"))
        int_bytes = self.BASE["i"].size * 4
        assert mx["allreduce_wire_bytes_total"] == \
            float_bytes / 2 + int_bytes
        assert mx["allreduce_ring_wire_bytes_total"] == \
            float_bytes / 2 + int_bytes

    def test_world3_wire_cross_rank_bitwise(self):
        import jax.numpy as jnp

        def tf(rank):
            return {"g": jnp.asarray(self.BASE["b"] * (rank + 1))}

        results, _ = self._run(3, tf, allreduce_wire_dtype=jnp.bfloat16)
        # Canonical-rank-order fold: all three ranks bitwise identical.
        g0 = np.asarray(results[0]["g"])
        np.testing.assert_array_equal(g0, np.asarray(results[1]["g"]))
        np.testing.assert_array_equal(g0, np.asarray(results[2]["g"]))
        q = [np.asarray(jnp.asarray(self.BASE["b"] * (r + 1))
                        .astype(jnp.bfloat16).astype(jnp.float32))
             for r in range(3)]
        np.testing.assert_array_equal(g0, ((q[0] + q[1]) + q[2]) / 3)

    def test_healer_gets_averaged_grads_without_contributing(self):
        import jax.numpy as jnp

        def tf(rank):
            return {"g": jnp.asarray(self.BASE["b"] * (rank + 1))}

        # Rank 1 is a healer (max_rank None): zero contribution, but it
        # still receives the participants' average.
        import threading as _t

        rings = _make_test_rings(2)
        results = [None] * 2
        errors = []

        def run(rank):
            client = MagicMock()
            client.quorum.return_value = quorum_result(
                max_rank=(0 if rank == 0 else None), max_world_size=1,
                replica_rank=rank, replica_world_size=2,
                heal=(rank == 1))
            client.should_commit.return_value = True
            m = make_manager(client,
                             comm=_wired_comm(rings[rank], rank, 2),
                             min_replica_size=1)
            try:
                m.step()
                results[rank] = m.allreduce(tf(rank)).result(
                    timeout=30)
                err = m.errored()
                assert err is None, err
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                m.shutdown()

        state = {"user": {}, "torchft": {"step": 1,
                                         "batches_committed": 0}}
        # Patch ONCE on the main thread around both workers: mock.patch
        # mutates the class attribute, so nested per-thread patching
        # races on unpatch and can leave the mock installed globally.
        cp = patch(
            "torchft_tpu.manager.CheckpointServer.load_from_address",
            return_value=state)
        pc = patch("torchft_tpu.manager.ManagerClient")
        with cp, pc:
            threads = [_t.Thread(target=run, args=(r,))
                       for r in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        for r in rings:
            r.close()
        assert not errors, errors
        # Participant world is 1; healer contributed zeros. Both see the
        # participant's grads unscaled (sum/1).
        np.testing.assert_array_equal(np.asarray(results[0]["g"]),
                                      self.BASE["b"])
        np.testing.assert_array_equal(np.asarray(results[1]["g"]),
                                      self.BASE["b"])

    # ---- the ring's accumulators live across steps (release after put)

    STEP_KEYS = ("allreduce_host_copy_bytes_total",
                 "allreduce_accum_reuse_total",
                 "allreduce_accum_alloc_total")

    def _accum_steps(self, world, trees, **mkw):
        """:meth:`_run_steps`, with each step's metrics cut to
        ``STEP_KEYS``."""
        results, metrics = self._run_steps(world, trees, **mkw)
        return results, [[tuple(mx[k] for k in self.STEP_KEYS)
                          for mx in steps] for steps in metrics]

    @pytest.mark.parametrize("world", [2, 3])
    def test_steady_steps_reuse_and_keep_earlier_results(self, world):
        """Device leaves, four buckets a step. From the second step of a
        signature on nothing is allocated and nothing copied; every
        step's result, held while later steps fold into the same
        accumulators, stays bitwise what the exact ring gives."""
        import jax.numpy as jnp
        from torchft_tpu.backends.host import _fold_exact_ring_order

        # distinct sizes: two chunks of one size may share a buffer
        # within a step, when the first's put ends before the second's
        # ring begins
        shapes = {"a": (257, 3), "b": (1000,), "c": (1001,), "d": (40, 9)}

        def host(step, rank):
            return {k: np.random.default_rng([step, rank, i]).normal(
                size=s).astype(np.float32)
                for i, (k, s) in enumerate(shapes.items())}

        def tree(step):
            return lambda rank: jax.tree_util.tree_map(
                jnp.asarray, host(step, rank))

        n_steps = 4
        results, counters = self._accum_steps(
            world, [tree(s) for s in range(n_steps)],
            allreduce_bucket_bytes=1024)
        ops = len(shapes)  # a bucket a leaf at this bucket size
        for rank in range(world):
            assert counters[rank] == [
                (0.0, float(ops * s), float(ops)) for s in range(n_steps)]
            for step in range(n_steps):
                for k, shape in shapes.items():
                    want = _fold_exact_ring_order(
                        [host(step, q)[k].ravel() for q in range(world)],
                        np.dtype(np.float32), world)
                    want = (want / world).astype(np.float32).reshape(shape)
                    np.testing.assert_array_equal(
                        np.asarray(results[rank][step][k]), want)

    @pytest.mark.parametrize("world", [2, 3])
    def test_ring_steps_are_published_by_executor(self, world):
        """``allreduce_ring_native_steps_total`` /
        ``allreduce_ring_python_steps_total``: 2·(world−1) inbound steps
        a ring buffer, all of them the native core's over plain sockets
        in f32 (all of them Python's where no core could be built)."""
        import jax.numpy as jnp
        from torchft_tpu import _native

        def tree(rank):
            return {k: jnp.full((n,), rank + 1.0, jnp.float32)
                    for k, n in (("a", 700), ("b", 1000), ("c", 1001))}

        _, metrics = self._run_steps(world, [tree] * 3,
                                     allreduce_bucket_bytes=1024)
        native = _native.ring_core() is not None
        for rank in range(world):
            for step, mx in enumerate(metrics[rank], 1):
                steps = float(mx["allreduce_ring_ops_total"]
                              * 2 * (world - 1))
                assert mx["allreduce_ring_ops_total"] == 3 * step
                assert (mx["allreduce_ring_native_steps_total"],
                        mx["allreduce_ring_python_steps_total"]) == (
                    (steps, 0.0) if native else (0.0, steps))

    @pytest.mark.parametrize("lanes", [1, 2, 3])
    def test_ring_lanes_are_published(self, lanes):
        """``allreduce_ring_lanes`` is the ring's lane count and
        ``allreduce_ring_overlapped_ops_total`` counts the wire ops that
        began beside another lane's (none can with one lane); the
        averaged tree is one lane's to the bit, split leaves and all."""
        import jax.numpy as jnp

        def tree(rank):
            return {k: jnp.asarray(np.random.default_rng(
                [rank, n]).normal(size=n).astype(np.float32))
                for k, n in (("a", 700), ("b", 1000), ("c", 1001),
                             ("d", 5000), ("e", 40))}

        results, metrics = self._run_steps(2, [tree] * 3, lanes=lanes,
                                           allreduce_bucket_bytes=1024)
        want = jax.tree_util.tree_map(
            lambda a, b: (np.asarray(a) + np.asarray(b)) / 2,
            tree(0), tree(1))
        for rank in range(2):
            for step, mx in enumerate(metrics[rank], 1):
                assert mx["allreduce_ring_lanes"] == lanes
                ops = mx["allreduce_ring_ops_total"]
                assert ops == 5 * step
                over = mx["allreduce_ring_overlapped_ops_total"]
                assert 0 <= over < ops and (lanes > 1 or over == 0)
                assert mx["allreduce_ring_native_steps_total"] \
                    + mx["allreduce_ring_python_steps_total"] == 2 * ops
                for k, w in want.items():
                    np.testing.assert_array_equal(
                        np.asarray(results[rank][step - 1][k]), w)

    def test_world_one_reports_no_lane(self):
        m = make_manager(MagicMock(), comm=_wired_comm([], 0, 1))
        try:
            mx = m.metrics()
            assert mx["allreduce_ring_lanes"] == 0
            assert mx["allreduce_ring_overlapped_ops_total"] == 0
        finally:
            m.shutdown()

    def test_changed_gradient_signature_drops_the_buffers(self):
        """A, A, B, B, A: each change of signature starts from nothing
        kept (its chunks have other sizes), and stays bitwise right."""
        import jax.numpy as jnp

        def tree(step, n):
            def fn(rank):
                return {"g": jnp.asarray(np.random.default_rng(
                    [step, rank]).normal(size=n).astype(np.float32))}
            return fn

        sizes = [5000, 5000, 7001, 7001, 5000]
        results, counters = self._accum_steps(
            2, [tree(s, n) for s, n in enumerate(sizes)])
        for rank in range(2):
            assert counters[rank] == [(0.0, 0.0, 1.0), (0.0, 1.0, 1.0),
                                      (0.0, 1.0, 2.0), (0.0, 2.0, 2.0),
                                      (0.0, 2.0, 3.0)]
            for step, n in enumerate(sizes):
                g = [np.random.default_rng([step, q]).normal(
                    size=n).astype(np.float32) for q in range(2)]
                np.testing.assert_array_equal(
                    np.asarray(results[rank][step]["g"]),
                    ((g[0] + g[1]) / 2).astype(np.float32))

    def test_host_leaves_count_their_assembly_copy(self):
        """A chunk of host-native leaves is assembled into one ring
        buffer on the host: the one host-to-host copy this path still
        makes, counted in bytes."""
        def tree(rank):
            return {"h": np.full(3000, rank + 1.0, np.float32)}

        results, counters = self._accum_steps(2, [tree, tree])
        for rank in range(2):
            assert [c[0] for c in counters[rank]] == [12_000.0, 24_000.0]
            np.testing.assert_array_equal(
                results[rank][1]["h"], np.full(3000, 1.5, np.float32))
