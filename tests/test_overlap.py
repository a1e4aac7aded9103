"""Cross-step overlap engine tests (docs/design/overlap.md).

The delayed-gradient-application mode (``Manager(overlap_steps=1)`` +
:class:`~torchft_tpu.optim.DelayedOptimizer`): step N's cross-group
allreduce stays in flight across the step boundary, draining under step
N+1's compute, with the commit vote and optimizer update deferred to the
N+1 boundary. Four properties are pinned here, all tier-1 (no native
control plane — mocked clients, DummyCommunicator, and the socketpair
ring trick from test_manager):

* **State machine** — stage/settle ordering enforced, votes gate the
  step counter exactly as in sync mode, stale grads DROP on vote aborts
  and latched comm errors, ``save_durable`` refuses mid-flight
  snapshots, ``flush`` applies the final step.
* **Bitwise equivalence** — overlap-mode params after K steps equal the
  one-step-shifted schedule's (``θ_{k+1} = θ_k - u(avg ∇L(θ_{k-1},
  b_k))``) computed serially with the same jitted executables, for a
  single group and for two groups over a real socketpair ring — and
  through a mid-run heal (real HTTP checkpoint fetch), where the healer
  must land bitwise on the donor.
* **Failure paths** — a replica death mid-transfer latches, the vote
  aborts, and the survivor keeps exactly the last settled params.
* **Performance** — with comm time ~= compute time, overlap mode beats
  sync mode >= 1.5x on steps/s, and ``allreduce_hidden_ms_total``
  accounts for the gain (the acceptance A/B, run with a deterministic
  slowed ring so the assertion doesn't ride rig noise).

Plus the bf16 fetch-path regression guards: the cached jitted pack must
compile once per grad signature (``allreduce_pack_cache_misses`` frozen
after the first step) and non-native wire dtypes must cross D2H as
canonical uint bits (the BENCH_r05 regression fix).
"""

import threading
import time
from unittest.mock import MagicMock, patch

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import conftest  # noqa: F401  (forces the CPU platform)
from mockplane import make_manager, quorum_result
from test_manager import _make_test_rings, _wired_comm
from torchft_tpu.backends.host import HostCommunicator
from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.exchange import _pack_leaves, _transfer_dtype
from torchft_tpu.manager import Manager
from torchft_tpu.optim import DelayedOptimizer
from torchft_tpu.parallel import FTTrainer

pytestmark = pytest.mark.overlap


def participant_client(world=2, **overrides):
    client = MagicMock()
    client.quorum.return_value = quorum_result(
        max_rank=overrides.pop("rank", 0), max_world_size=world,
        replica_rank=overrides.pop("replica_rank", 0),
        replica_world_size=world, **overrides)
    client.should_commit.return_value = True
    return client


class _Holder:
    def __init__(self, params, opt_state):
        self.params = params
        self.opt_state = opt_state


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


class TestDeferredStateMachine:
    """The deferred-commit protocol at Manager + DelayedOptimizer level
    (mocked control plane, DummyCommunicator)."""

    def _setup(self, client=None, lr=1.0):
        client = client or participant_client()
        m = make_manager(client, overlap_steps=1)
        tx = optax.sgd(lr)
        opt = DelayedOptimizer(m, tx)
        params = {"g": jnp.asarray([2.0, 4.0], jnp.float32)}
        holder = _Holder(params, opt.init(params))
        return m, opt, holder, client

    def test_settle_applies_at_next_boundary(self):
        m, opt, holder, _ = self._setup()
        try:
            opt.begin_step()
            grads = {"g": np.asarray([2.0, 4.0], np.float32)}
            fut = m.allreduce(grads)
            opt.stage(holder, fut)
            assert opt.pending() and m.deferred_pending()
            assert m.deferred_step() == 1
            # Not applied yet: the update waits for the next boundary.
            np.testing.assert_array_equal(np.asarray(holder.params["g"]),
                                          [2.0, 4.0])
            assert opt.settle() is True
            # DummyComm returns the input; n=2 -> avg = [1, 2]; sgd(1.0).
            np.testing.assert_array_equal(np.asarray(holder.params["g"]),
                                          [1.0, 2.0])
            assert not opt.pending() and not m.deferred_pending()
            # The vote gated the NEXT advance, not the staged one.
            opt.begin_step()
            assert m.current_step() == 2
        finally:
            m.shutdown()

    def test_step_refuses_to_advance_over_unsettled_deferred(self):
        m, opt, holder, _ = self._setup()
        try:
            opt.begin_step()
            opt.stage(holder, m.allreduce({"g": np.zeros(2, np.float32)}))
            with pytest.raises(RuntimeError, match="deferred"):
                m.step()
            opt.settle()
            m.step()  # settled: advances normally
            assert m.current_step() == 2
        finally:
            m.shutdown()

    def test_vote_abort_drops_stale_grads(self):
        client = participant_client()
        client.should_commit.return_value = False
        m, opt, holder, _ = self._setup(client)
        try:
            opt.begin_step()
            before = np.asarray(holder.params["g"]).copy()
            opt.stage(holder, m.allreduce({"g": np.ones(2, np.float32)}))
            assert opt.settle() is False
            np.testing.assert_array_equal(np.asarray(holder.params["g"]),
                                          before)  # dropped, not applied
            mx = m.metrics()
            assert mx["overlap_grads_dropped"] == 1
            assert mx["aborted_steps"] == 1
            # Abort: the step counter must not advance.
            client.should_commit.return_value = True
            opt.begin_step()
            assert m.current_step() == 1
        finally:
            m.shutdown()

    def test_latched_comm_error_drops_stale_grads(self):
        client = participant_client()
        client.should_commit.return_value = False
        comm = DummyCommunicator()
        m = make_manager(client, comm, overlap_steps=1)
        opt = DelayedOptimizer(m, optax.sgd(1.0))
        params = {"g": jnp.ones(2, jnp.float32)}
        holder = _Holder(params, opt.init(params))
        try:
            opt.begin_step()
            comm.allreduce = MagicMock(side_effect=RuntimeError("boom"))
            before = np.asarray(holder.params["g"]).copy()
            opt.stage(holder, m.allreduce({"g": np.ones(2, np.float32)}))
            assert m.errored() is not None  # latched while in flight
            assert opt.settle() is False
            np.testing.assert_array_equal(np.asarray(holder.params["g"]),
                                          before)
            assert m.metrics()["overlap_grads_dropped"] == 1
        finally:
            m.shutdown()

    def test_save_durable_refuses_mid_flight_then_saves_after_flush(self):
        m, opt, holder, _ = self._setup()
        writer = MagicMock()
        writer.save_async.return_value = "fut"
        try:
            opt.begin_step()
            opt.stage(holder, m.allreduce({"g": np.zeros(2, np.float32)}))
            # Mid-flight: manager metadata (step advanced) and params
            # (update unapplied) describe different steps — refused.
            assert m.save_durable(writer, "/tmp/nowhere") is None
            assert m.metrics()["ckpt_save_skipped"] == 1
            writer.save_async.assert_not_called()
            assert opt.flush() is True
            assert m.save_durable(writer, "/tmp/nowhere") == "fut"
            writer.save_async.assert_called_once()
        finally:
            m.shutdown()

    def test_flush_none_when_nothing_pending(self):
        m, opt, holder, _ = self._setup()
        try:
            assert opt.flush() is None
        finally:
            m.shutdown()

    def test_overlap_metrics_populate_and_inflight_drains(self):
        m, opt, holder, _ = self._setup()
        try:
            for _ in range(3):
                opt.flush()
                opt.begin_step()
                opt.stage(holder,
                          m.allreduce({"g": np.ones(2, np.float32)}))
            opt.flush()
            mx = m.metrics()
            assert mx["overlap_steps_deferred"] == 3
            assert mx["allreduce_hidden_ms_total"] >= 0.0
            assert mx["allreduce_drain_wait_ms_total"] >= 0.0
            assert mx["allreduce_inflight"] == 0  # all drained
        finally:
            m.shutdown()

    def test_overlap_steps_validation(self):
        with pytest.raises(ValueError, match="overlap_steps"):
            make_manager(participant_client(), overlap_steps=2)


class TestOverlapEquivalence:
    """Bitwise equivalence with the one-step-shifted schedule: the
    overlap engine's params after K steps must equal the serial oracle
    θ_{k+1} = θ_k - u(avg_g ∇L_g(θ_{k-1}, b_{g,k})) computed with the
    SAME jitted executables (grads evaluated one update behind — the
    documented staleness)."""

    K = 6

    @staticmethod
    def _loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    @classmethod
    def _params0(cls):
        return {"w": jnp.zeros((4,), jnp.float32)}

    @classmethod
    def _batches(cls, group, k):
        rng = np.random.default_rng(100 * group + k)
        return {"x": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
                "y": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}

    def _trainer(self, client, comm, overlap):
        return FTTrainer(
            loss_fn=self._loss_fn, tx=optax.sgd(0.1),
            params=self._params0(),
            manager_factory=lambda load, save: Manager(
                comm=comm, load_state_dict=load, state_dict=save,
                min_replica_size=1, rank=0, world_size=1,
                replica_id="eq", overlap_steps=overlap,
                _manager_client=client),
        )

    def test_single_group_matches_shifted_oracle(self):
        client = participant_client(world=1)
        tr = self._trainer(client, DummyCommunicator(), overlap=1)
        try:
            for k in range(self.K):
                tr.train_step(self._batches(0, k))
            assert tr.flush() is True
            got = np.asarray(tr.params["w"])
            fwd, upd = tr._fwd_bwd, tr._opt._ft._update
        finally:
            tr.shutdown()

        # Serial oracle of the delayed schedule, same executables.
        P, O = self._params0(), optax.sgd(0.1).init(self._params0())
        staged = None
        for k in range(self.K):
            _, _, g, _ = fwd(P, None, self._batches(0, k))  # stale point
            if staged is not None:
                P, O = upd(_copy(P), _copy(O), staged)
            staged = g
        P, O = upd(_copy(P), _copy(O), staged)
        assert np.asarray(P["w"]).tobytes() == got.tobytes()

        # Sanity: the shifted schedule genuinely differs from sync mode.
        tr2 = self._trainer(participant_client(world=1),
                            DummyCommunicator(), overlap=0)
        try:
            for k in range(self.K):
                tr2.train_step(self._batches(0, k))
            assert np.asarray(tr2.params["w"]).tobytes() != got.tobytes()
        finally:
            tr2.shutdown()

    def test_two_groups_ring_bitwise_vs_shifted_oracle(self):
        """Two groups over a REAL socketpair ring. Single-threaded
        alternation is deliberate: within one iteration A's settle
        drains step k-1 (B contributed last iteration) and B's settle
        drains after A already contributed this iteration — the
        deferred engine never blocks inside an iteration, which is
        itself a property under test."""
        rings = _make_test_rings(2)
        trainers = [
            self._trainer(
                participant_client(world=2, rank=r, replica_rank=r),
                _wired_comm(rings[r], r, 2), overlap=1)
            for r in range(2)
        ]
        try:
            for k in range(self.K):
                for r in (0, 1):
                    trainers[r].train_step(self._batches(r, k))
            for r in (0, 1):
                assert trainers[r].flush() is True
            got = [np.asarray(t.params["w"]) for t in trainers]
            fwd, upd = trainers[0]._fwd_bwd, trainers[0]._opt._ft._update
            mx = trainers[0].manager.metrics()
        finally:
            for t in trainers:
                t.shutdown()
            for ring in rings:
                ring.close()

        # Lockstep across groups first.
        assert got[0].tobytes() == got[1].tobytes()
        # Deferred accounting populated on the real ring.
        assert mx["overlap_steps_deferred"] == self.K
        assert mx["overlap_grads_dropped"] == 0

        # Serial shifted-schedule oracle; the exact-mode world-2 ring is
        # bitwise a two-term sum, and /2 is exact in f32.
        P, O = self._params0(), optax.sgd(0.1).init(self._params0())
        staged = None
        for k in range(self.K):
            gs = [fwd(P, None, self._batches(r, k))[2] for r in (0, 1)]
            if staged is not None:
                P, O = upd(_copy(P), _copy(O), staged)
            staged = jax.tree_util.tree_map(
                lambda a, b: (a + b) / 2, *gs)
        P, O = upd(_copy(P), _copy(O), staged)
        assert np.asarray(P["w"]).tobytes() == got[0].tobytes()

    def test_bitwise_through_midrun_heal(self):
        """Mid-run heal under overlap: group B's params are scrambled,
        its next quorum marks it a healer, and the REAL checkpoint
        transport (HTTP fetch from A's live state, served during A's
        open heal window) restores it; B then applies the RECEIVED
        average to the restored state at its settle — landing bitwise on
        A. Also exercises the engine's recompute path: B's speculative
        forward/backward at pre-heal params is discarded."""
        heal_at = 3  # 1-indexed step at which B heals
        K = 6

        def b_quorum(step):
            if step == heal_at:
                return quorum_result(
                    max_rank=None, max_world_size=1, replica_rank=1,
                    replica_world_size=2, heal=True, max_step=heal_at,
                    recover_manager_address="managerA")
            world = 1 if step == heal_at else 2
            # After the heal step both participate again.
            return quorum_result(
                max_rank=1, max_world_size=2, replica_rank=1,
                replica_world_size=2)

        def a_quorum(step):
            if step == heal_at:
                # B is healing: A is the only participant this step.
                return quorum_result(max_rank=0, max_world_size=1,
                                     replica_rank=0,
                                     replica_world_size=2)
            return quorum_result(max_rank=0, max_world_size=2,
                                 replica_rank=0, replica_world_size=2)

        client_a, client_b = MagicMock(), MagicMock()
        client_a.quorum.side_effect = [a_quorum(s)
                                       for s in range(1, K + 1)]
        client_b.quorum.side_effect = [b_quorum(s)
                                       for s in range(1, K + 1)]
        client_a.should_commit.return_value = True
        client_b.should_commit.return_value = True

        rings = _make_test_rings(2)
        tr_a = self._trainer(client_a, _wired_comm(rings[0], 0, 2), 1)
        tr_b = self._trainer(client_b, _wired_comm(rings[1], 1, 2), 1)

        def make_primary(addr, **kwargs):
            mc = MagicMock()
            mc.checkpoint_address.return_value = \
                tr_a.manager._ckpt_server.address()
            return mc

        try:
            with patch("torchft_tpu.manager.ManagerClient",
                       side_effect=make_primary):
                for k in range(K):
                    if k + 1 == heal_at:
                        # Scramble B: the heal must restore it.
                        tr_b.params = jax.tree_util.tree_map(
                            lambda a: a * 0 - 3.0, tr_b.params)
                    tr_a.train_step(self._batches(0, k))
                    tr_b.train_step(self._batches(1, k))
                assert tr_a.flush() is True
                assert tr_b.flush() is True
            pa = np.asarray(tr_a.params["w"])
            pb = np.asarray(tr_b.params["w"])
            mb = tr_b.manager.metrics()
        finally:
            tr_a.shutdown()
            tr_b.shutdown()
            for ring in rings:
                ring.close()

        assert mb["heal_count"] == 1
        assert mb["heal_bytes_total"] > 0  # real HTTP transfer happened
        assert pa.tobytes() == pb.tobytes()

    def test_sync_quorum_heal_recomputes_at_restored_params(self):
        """use_async_quorum=False heals restore INSIDE ``step()`` (and
        clear the healing flag there), after the overlap loop's
        speculative dispatch: the params-identity guard must detect the
        restore and recompute, or the healer would contribute grads
        computed at its pre-heal garbage params as a full participant."""
        heal_at, K = 3, 5

        def quorums(rank):
            out = []
            for s in range(1, K + 1):
                if s == heal_at and rank == 1:
                    out.append(quorum_result(
                        max_rank=1, max_world_size=2, replica_rank=1,
                        replica_world_size=2, heal=True, max_step=s,
                        recover_manager_address="managerA"))
                else:
                    out.append(quorum_result(
                        max_rank=rank, max_world_size=2,
                        replica_rank=rank, replica_world_size=2))
            return out

        rings = _make_test_rings(2)
        trainers = []
        for r in (0, 1):
            client = MagicMock()
            client.quorum.side_effect = quorums(r)
            client.should_commit.return_value = True
            trainers.append(FTTrainer(
                loss_fn=self._loss_fn, tx=optax.sgd(0.1),
                params=self._params0(),
                manager_factory=lambda load, save, r=r, c=client: Manager(
                    comm=_wired_comm(rings[r], r, 2), load_state_dict=load,
                    state_dict=save, min_replica_size=1, rank=0,
                    world_size=1, replica_id=f"sq{r}", overlap_steps=1,
                    use_async_quorum=False, _manager_client=c)))
        tr_a, tr_b = trainers

        # Spy on B's forward/backward: record (iteration, param sum) so
        # the recompute at restored params is directly observable.
        calls = []
        iter_cell = {"k": -1}
        orig_fwd = tr_b._fwd_bwd

        def spy(p, st, b):
            calls.append((iter_cell["k"], float(jnp.sum(p["w"]))))
            return orig_fwd(p, st, b)

        tr_b._fwd_bwd = spy

        def make_primary(addr, **kwargs):
            mc = MagicMock()
            mc.checkpoint_address.return_value = \
                tr_a.manager._ckpt_server.address()
            return mc

        SCRAMBLE = -9000.0
        try:
            with patch("torchft_tpu.manager.ManagerClient",
                       side_effect=make_primary):
                for k in range(K):
                    iter_cell["k"] = k
                    if k + 1 == heal_at:
                        tr_b.params = jax.tree_util.tree_map(
                            lambda a: a * 0 + SCRAMBLE, tr_b.params)
                    tr_a.train_step(self._batches(0, k))
                    tr_b.train_step(self._batches(1, k))
                assert tr_a.flush() is True
                assert tr_b.flush() is True
            pa = np.asarray(tr_a.params["w"])
            pb = np.asarray(tr_b.params["w"])
            assert tr_b.manager.metrics()["heal_count"] == 1
        finally:
            tr_a.shutdown()
            tr_b.shutdown()
            for ring in rings:
                ring.close()

        assert pa.tobytes() == pb.tobytes()
        heal_iter = [s for it, s in calls if it == heal_at - 1]
        # Speculative dispatch saw the scrambled params...
        assert abs(heal_iter[0]) > 1000, calls
        # ...and the post-restore recompute (the grads actually
        # contributed) ran at the RESTORED params, not the garbage.
        assert len(heal_iter) >= 2, calls
        assert abs(heal_iter[-1]) < 100, calls


class TestReplicaDeathMidFlight:
    """In-flight deferred allreduce + replica death: the transfer
    errors, the error latches, the deferred vote aborts, and the
    survivor's params stay EXACTLY at the last settled state (the same
    state sync mode recovers to — dropped, never half-applied)."""

    def test_survivor_drops_stale_grads_and_keeps_last_state(self):
        loss_fn = TestOverlapEquivalence._loss_fn
        params0 = TestOverlapEquivalence._params0()
        batches = TestOverlapEquivalence._batches
        rings = _make_test_rings(2)

        def trainer(r, client):
            return FTTrainer(
                loss_fn=loss_fn, tx=optax.sgd(0.1), params=params0,
                manager_factory=lambda load, save: Manager(
                    comm=_wired_comm(rings[r], r, 2), load_state_dict=load,
                    state_dict=save, min_replica_size=1, rank=0,
                    world_size=1, replica_id=f"death{r}", overlap_steps=1,
                    _manager_client=client),
            )

        client_a = participant_client(world=2, rank=0, replica_rank=0)
        client_b = participant_client(world=2, rank=1, replica_rank=1)
        tr_a = trainer(0, client_a)
        tr_b = trainer(1, client_b)
        try:
            for k in range(2):
                tr_a.train_step(batches(0, k))
                tr_b.train_step(batches(1, k))
            # Iteration 3: A settles step 2 and stages step 3...
            tr_a.train_step(batches(0, 2))
            settled = np.asarray(tr_a.params["w"]).copy()
            # ...then B dies mid-transfer (never contributes step 3).
            tr_b.manager.shutdown()
            # The step-3 vote must abort (a real barrier would return
            # False; the mock mirrors that).
            client_a.should_commit.return_value = False
            assert tr_a.flush() is False
            assert tr_a.manager.errored() is not None
            # Stale grads dropped: params are exactly the last settled
            # state, bitwise.
            assert np.asarray(tr_a.params["w"]).tobytes() \
                == settled.tobytes()
            mx = tr_a.manager.metrics()
            assert mx["overlap_grads_dropped"] == 1
            assert mx["aborted_steps"] == 1
            # Abort semantics unchanged: the survivor holds at step 3
            # (the aborted step), poised to retry it.
            assert tr_a.manager.current_step() == 3
        finally:
            tr_a.shutdown()
            for ring in rings:
                ring.close()


class _SlowWiredComm(HostCommunicator):
    """Socketpair-wired host communicator whose wire collective costs a
    deterministic extra delay on the op worker — comm-bound conditions
    without rig-dependent payloads."""

    def __init__(self, ring, rank, world, delay):
        super().__init__(timeout_sec=30)
        self._rings, self._rank, self._world = [ring], rank, world
        self._delay = delay

    def configure(self, store_addr, rank, world_size):
        pass  # pre-wired

    def _do_allreduce_wire(self, *args, **kwargs):
        time.sleep(self._delay)
        return super()._do_allreduce_wire(*args, **kwargs)


class TestOverlapPerfAB:
    """The acceptance A/B: with comm ~= compute, deferring the drain
    must buy >= 1.5x steps/s over the sync protocol, and
    ``allreduce_hidden_ms_total`` must account for the gain. The ring
    is slowed deterministically (sleep on the comm worker) so the
    assertion tests the ENGINE, not the rig."""

    COMPUTE_S = 0.15
    COMM_S = 0.15
    STEPS = 6

    def _run(self, overlap: bool) -> dict:
        rings = _make_test_rings(2)
        walls = [None] * 2
        hidden = [0.0] * 2
        errors = []
        tree = {"g": np.ones(1024, np.float32)}
        tx = optax.sgd(0.0)

        def run(rank):
            client = participant_client(world=2, rank=rank,
                                        replica_rank=rank)
            m = make_manager(
                client,
                comm=_SlowWiredComm(rings[rank], rank, 2, self.COMM_S),
                overlap_steps=1 if overlap else 0)
            from torchft_tpu.optim import FTOptimizer

            params = {"g": jnp.ones(1024, jnp.float32)}
            try:
                if overlap:
                    opt = DelayedOptimizer(m, tx)
                    holder = _Holder(params, opt.init(params))
                    t0 = None
                    for k in range(self.STEPS + 1):
                        time.sleep(self.COMPUTE_S)  # "compute"
                        if opt.pending():
                            assert opt.settle()
                        if k == 1:
                            t0 = time.perf_counter()  # past compiles
                        opt.begin_step()
                        opt.stage(holder, m.allreduce(dict(tree)))
                    assert opt.flush()
                else:
                    opt = FTOptimizer(m, tx)
                    holder = _Holder(params, opt.init(params))
                    t0 = None
                    for k in range(self.STEPS + 1):
                        if k == 1:
                            t0 = time.perf_counter()
                        m.step()
                        time.sleep(self.COMPUTE_S)
                        avg = m.allreduce(dict(tree)).result()
                        assert opt.apply(holder, avg)
                walls[rank] = time.perf_counter() - t0
                hidden[rank] = m.metrics()["allreduce_hidden_ms_total"]
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                m.shutdown()

        threads = [threading.Thread(target=run, args=(r,))
                   for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for ring in rings:
            ring.close()
        assert not errors, errors
        assert all(w is not None for w in walls)
        return {"steps_per_s": self.STEPS / max(walls),
                "wall": max(walls), "hidden_ms": max(hidden)}

    def test_overlap_beats_sync_1p5x_and_hidden_accounts(self):
        sync = self._run(overlap=False)
        ov = self._run(overlap=True)
        speedup = ov["steps_per_s"] / sync["steps_per_s"]
        assert speedup >= 1.5, (sync, ov)
        # The gain is the hidden comm: the hidden counter must cover
        # most of the wall-clock saved (slack for scheduling jitter).
        saved_ms = (sync["wall"] - ov["wall"]) * 1e3
        assert ov["hidden_ms"] >= 0.6 * saved_ms, (ov, saved_ms)
        assert sync["hidden_ms"] == 0.0  # sync mode never defers


class TestPackFetchPath:
    """bf16 wire fetch regression guards (BENCH_r05: 12.9s vs 2.9s
    fetch at HALF the bytes): the pack executable must compile once per
    grad signature, and non-native wire dtypes must cross D2H as
    canonical uint bits (custom ml_dtypes buffers can fall off the
    runtime's raw-bytes transfer fast path onto a per-element
    conversion path)."""

    def test_pack_bitcasts_custom_wire_dtype_to_canonical_carrier(self):
        assert _transfer_dtype(np.float32) is None
        assert _transfer_dtype(np.float64) is None
        assert _transfer_dtype(jnp.bfloat16) == np.dtype(np.uint16)

        x = jnp.asarray(
            np.random.default_rng(0).normal(size=(37,)), jnp.float32)
        packed = _pack_leaves([x], "bfloat16")
        # Canonical carrier on the wire-transfer leg...
        assert packed.dtype == jnp.uint16
        got = np.asarray(jax.device_get(packed)).view(
            np.dtype(jnp.bfloat16))
        want = np.asarray(jax.device_get(x.astype(jnp.bfloat16)))
        # ...and a bitwise-identical payload after the host-side view.
        assert got.tobytes() == want.tobytes()
        # Native dtypes are untouched.
        assert _pack_leaves([x], "float32").dtype == jnp.float32

    def test_zero_pack_cache_misses_after_first_step(self):
        """Three pipelined bf16-wire steps over a real ring: the pack
        (and schedule) caches must make steps 2..3 compile-free —
        ``allreduce_pack_cache_misses`` frozen after step 1. A per-step
        retrace here is the silent 10x fetch collapse failure mode."""
        world, steps = 2, 3
        rings = _make_test_rings(world)
        miss_log: list = []
        barrier = threading.Barrier(world)
        errors = []
        base = np.random.default_rng(0).normal(size=(600,)).astype(
            np.float32)

        def run(rank):
            client = participant_client(world=world, rank=rank,
                                        replica_rank=rank)
            m = make_manager(client,
                             comm=_wired_comm(rings[rank], rank, world),
                             allreduce_bucket_bytes=512,
                             allreduce_wire_dtype=jnp.bfloat16)
            try:
                for s in range(steps):
                    m.step()
                    tree = {"g": jnp.asarray(base * (rank + 1 + s))}
                    m.allreduce(tree).result(timeout=30)
                    assert m.errored() is None, m.errored()
                    assert m.should_commit()
                    barrier.wait(timeout=30)
                    if rank == 0:
                        miss_log.append(
                            m.metrics()["allreduce_pack_cache_misses"])
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                m.shutdown()

        threads = [threading.Thread(target=run, args=(r,))
                   for r in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        for ring in rings:
            ring.close()
        assert not errors, errors
        assert len(miss_log) == steps
        # Whatever compiled on step 1, steps 2..N must add NOTHING.
        assert miss_log[0] == miss_log[-1], miss_log
