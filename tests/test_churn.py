"""Spot-instance churn tests (ISSUE 14, docs/design/churn.md).

Tier-1 (marker ``churn``, ``scripts/test.sh churn``): the seeded
:class:`~torchft_tpu.chaos.ChurnOrchestrator` event stream, the
Manager's graceful-preemption drain state machine (notice → clean
commit boundary → farewell → final durable save → advertisement
withdrawal → :class:`~torchft_tpu.manager.PreemptedExit`; deferral
mid-heal / mid-deferred / errored / aborted; deadline expiry with a
flight dump), the SIGTERM handler, manager-side join-coalescing and
reconfigures-per-minute accounting, the pre-join heal (join
backpressure over the REAL checkpoint HTTP transport), chaos
kill-latch rebirth for address-reusing replacements, and the 2-group
graceful-vs-SIGKILL A/B drive over a real socketpair ring (the
acceptance oracle: the graceful leg's survivor commits every step with
zero vote aborts and zero ring-reset latches; the SIGKILL control leg
shows at least one abort).

The lighthouse-side join-coalescing window and the farewell-races-
fast-path regression run in the C++ core tier (core_test.cc); the
Poisson churn soak (``bench_churn_goodput`` gates: >= 0.8x zero-churn
goodput at graceful churn, bitwise convergence through membership
drift) is native-gated and rides nightly.
"""

import functools
import os
import signal
import threading
import time
from unittest.mock import MagicMock

import numpy as np
import pytest

import conftest
import mockplane
from mockplane import (FAKE_STORE_ADDR, FakeStore, boundary, mock_client,
                       quorum_result)
from torchft_tpu import chaos
from torchft_tpu.chaos import ChaosSchedule, ChurnOrchestrator, EndpointChaos
from torchft_tpu.checkpointing import CheckpointServer
from torchft_tpu.manager import PreemptedExit

requires_native = conftest.requires_native()

pytestmark = pytest.mark.churn


make_manager = functools.partial(
    mockplane.make_manager, min_replica_size=1,
    state_dict=lambda: {"w": np.ones(4, np.float32)})


# ------------------------------------------------------ ChurnOrchestrator


class TestChurnOrchestrator:
    def _drive(self, o, seconds, dt=0.5):
        acts = []
        t = 0.0
        while t <= seconds:
            acts += o.tick(t)
            t += dt
        return acts

    def test_same_seed_same_event_stream(self):
        mk = lambda: ChurnOrchestrator(  # noqa: E731
            seed=7, groups=["a", "b", "c", "d"], rate_per_min=20,
            graceful_frac=0.5, replace_delay_s=1.0)
        a, b = mk(), mk()
        assert self._drive(a, 300) == self._drive(b, 300)
        assert a.notices == b.notices and a.kills == b.kills
        assert a.notices > 0 and a.kills > 0

    def test_different_seed_different_stream(self):
        a = ChurnOrchestrator(seed=1, groups=["a", "b"], rate_per_min=30)
        b = ChurnOrchestrator(seed=2, groups=["a", "b"], rate_per_min=30)
        assert self._drive(a, 300) != self._drive(b, 300)

    def test_zero_rate_is_silent(self):
        o = ChurnOrchestrator(seed=1, groups=["a", "b"], rate_per_min=0.0)
        assert self._drive(o, 600) == []
        assert o.notices == o.kills == 0

    def test_rate_scales_event_count(self):
        slow = ChurnOrchestrator(seed=3, groups=list(range(8)),
                                 rate_per_min=6, replace_delay_s=0.0)
        fast = ChurnOrchestrator(seed=3, groups=list(range(8)),
                                 rate_per_min=60, replace_delay_s=0.0)
        self._drive(slow, 600)
        self._drive(fast, 600)
        assert fast.notices + fast.kills > 3 * (slow.notices + slow.kills)

    def test_graceful_frac_extremes(self):
        g = ChurnOrchestrator(seed=5, groups=["a", "b", "c"],
                              rate_per_min=30, graceful_frac=1.0)
        k = ChurnOrchestrator(seed=5, groups=["a", "b", "c"],
                              rate_per_min=30, graceful_frac=0.0)
        self._drive(g, 300)
        self._drive(k, 300)
        assert g.kills == 0 and g.notices > 0
        assert k.notices == 0 and k.kills > 0
        # Same seed, same victims/times: only the notice/kill flavor
        # differs — the A/B legs of the bench see the identical storm.
        assert [(t, gid) for t, _, gid in g.events] \
            == [(t, gid) for t, _, gid in k.events]

    def test_min_live_floor_holds(self):
        fired = []
        o = ChurnOrchestrator(seed=9, groups=["a", "b"], rate_per_min=120,
                              graceful_frac=0.0,
                              kill=fired.append,
                              replace_delay_s=-1.0,  # never respawn
                              min_live=1)
        self._drive(o, 600)
        assert len(o.live) == 1
        assert len(fired) == 1  # one kill allowed, then the floor holds
        assert o.skipped_min_live > 0

    def test_replacement_scheduling_and_callback(self):
        replaced = []
        o = ChurnOrchestrator(seed=11, groups=["a", "b", "c"],
                              rate_per_min=60, graceful_frac=0.0,
                              replace=replaced.append,
                              replace_delay_s=5.0, min_live=1)
        acts = self._drive(o, 120)
        kills = [a for a in acts if a[1] == "kill"]
        repl = [a for a in acts if a[1] == "replace"]
        assert kills and repl
        assert o.replacements == len(replaced) == len(repl)
        # Every replacement respawned >= replace_delay_s after its kill.
        kill_t = {}
        for t, kind, gid in acts:
            if kind == "kill":
                kill_t[gid] = t
            elif kind == "replace":
                assert t - kill_t[gid] >= 5.0

    def test_set_rate_moves_intensity_live(self):
        o = ChurnOrchestrator(seed=13, groups=list(range(4)),
                              rate_per_min=0.0, replace_delay_s=0.0)
        assert self._drive(o, 300) == []
        o.set_rate(60.0)
        assert len(self._drive(o, 300)) > 0


# --------------------------------------------------- drain state machine


class TestPreemptionDrain:
    def participant_client(self, **kw):
        return mock_client(quorum_result(**kw))

    def test_happy_path_drain_sequence(self, tmp_path):
        from torchft_tpu import checkpoint_io
        from torchft_tpu.checkpoint_io import AsyncCheckpointer

        client = self.participant_client(
            store_address=FAKE_STORE_ADDR, replica_rank=1, max_rank=1)
        store = FakeStore()
        m = make_manager(client, store=store)
        writer = AsyncCheckpointer()
        m.set_durable_target(writer, str(tmp_path))
        pub = MagicMock()
        m._publisher = pub

        assert boundary(m)
        # Healset advertised (rank 1, step "1:<addr>" prefix).
        assert store.kv["torchft/healset/1"].startswith(b"1:")

        remaining = m.request_preemption(60.0, reason="reclaim-test")
        assert 0 < remaining <= 60.0
        assert m.preemption_pending()
        assert not m.drained()

        # The last boundary was clean: the drain lands at the next
        # step() — its post-apply edge, where the caller has applied
        # the committed update — and that same call raises.
        with pytest.raises(PreemptedExit):
            m.step()
        assert m.drained()
        assert not m.preemption_pending()
        # (1) farewell went out via the duck-typed client hook.
        assert client.farewell.called
        # (2) final durable save landed at the drained step.
        rec = checkpoint_io.recover(str(tmp_path))
        assert rec is not None
        _user, mgr_state = checkpoint_io.load(
            rec, target={"w": np.ones(4, np.float32)})
        assert mgr_state["step"] == 1  # the committed boundary's step
        # (3) healset advertisement tombstoned (step -1 never matches a
        # heal's max_step, so _healset_donors filters it out).
        assert store.kv["torchft/healset/1"] == b"-1:"
        mx = m.metrics()
        assert mx["preempt_notices_total"] == 1
        assert mx["graceful_exits_total"] == 1
        assert mx["preempt_deadline_expired_total"] == 0
        events = [e["event"] for e in m.history()]
        assert "preempt_notice" in events
        assert "farewell" in events
        assert "graceful_exit" in events
        # (4) the loop stays out: every later step() refuses too.
        with pytest.raises(PreemptedExit):
            m.step()

    def test_drain_without_durable_target_still_exits(self):
        client = self.participant_client()
        m = make_manager(client)
        assert boundary(m)
        m.request_preemption(60.0)
        with pytest.raises(PreemptedExit):
            m.step()
        assert m.drained()
        assert m.metrics()["graceful_exits_total"] == 1

    def test_tombstoned_healset_entry_is_filtered_from_donor_sets(self):
        client = self.participant_client()
        store = FakeStore()
        store.set("torchft/healset/1", b"-1:")
        store.set("torchft/healset/2", b"3:http://live:1/checkpoint/3")
        m = make_manager(client, store=store)
        q = quorum_result(store_address=FAKE_STORE_ADDR, max_step=3,
                          max_world_size=3)
        donors = m._healset_donors(q, "http://primary:1/checkpoint/3")
        assert donors == ["http://primary:1/checkpoint/3",
                          "http://live:1/checkpoint/3"]
        m.shutdown()

    def test_vote_abort_defers_drain_to_next_boundary(self):
        client = self.participant_client()
        client.should_commit.side_effect = [False, True]
        m = make_manager(client)
        try:
            assert not boundary(m)  # aborted boundary
            m.request_preemption(60.0)
            # The next step sees an aborted last boundary: drain defers
            # and the step RETRIES normally.
            assert boundary(m)
            assert not m.drained()
            assert m.preemption_pending()
            mx = m.metrics()
            assert mx["preempt_drain_deferrals_total"] == 1
            evs = [e for e in m.history() if e["event"] == "preempt_deferred"]
            assert evs and "vote aborted" in evs[0]["why"]
            with pytest.raises(PreemptedExit):
                m.step()  # clean boundary behind us: drain lands
            assert m.drained()
        finally:
            if not m.drained():
                m.shutdown()

    def test_errored_boundary_defers_drain(self):
        client = self.participant_client()
        client.should_commit.side_effect = \
            lambda rank, step, should_commit, timeout_ms=None: should_commit
        m = make_manager(client)
        try:
            m.step()
            m.report_error(RuntimeError("injected"))
            assert not m.should_commit()
            m.request_preemption(60.0)
            # Next step: the latched error (and aborted vote) defer the
            # drain; the step itself retries normally and commits.
            assert boundary(m)
            assert not m.drained()
            assert m.metrics()["preempt_drain_deferrals_total"] == 1
            evs = [e for e in m.history() if e["event"] == "preempt_deferred"]
            assert "errored" in evs[0]["why"]
            with pytest.raises(PreemptedExit):
                m.step()
            assert m.drained()
        finally:
            if not m.drained():
                m.shutdown()

    def test_sigterm_mid_heal_defers_cleanly(self):
        """SIGTERM satellite: a notice landing while a heal is staged
        must defer the drain — a final save then would persist the
        inconsistent mid-heal state — and land cleanly at the next
        boundary once the heal settled."""
        client = self.participant_client()
        m = make_manager(client)
        try:
            assert boundary(m)
            # Simulate the quorum thread having marked a heal in flight
            # (the staged-restore window save_durable also refuses in).
            with m._metrics_lock:
                m._healing = True
            m.request_preemption(60.0)
            # The notice lands mid-heal: the drain defers and the step
            # proceeds normally (step() clears the heal flag itself as
            # the heal settles).
            assert boundary(m)
            assert not m.drained()
            mx = m.metrics()
            assert mx["preempt_drain_deferrals_total"] == 1
            evs = [e for e in m.history() if e["event"] == "preempt_deferred"]
            assert "healing" in evs[0]["why"]
            # Heal settled + clean boundary behind us: the drain lands.
            with pytest.raises(PreemptedExit):
                m.step()
            assert m.drained()
        finally:
            if not m.drained():
                m.shutdown()

    def test_sigterm_mid_deferred_overlap_defers_cleanly(self):
        """SIGTERM satellite: with a deferred allreduce still in flight
        (overlap mode), the boundary must NOT tear the drain through it
        — the deferral waits for the settle, then the next boundary
        drains."""
        client = self.participant_client()
        m = make_manager(client, overlap_steps=1)
        try:
            m.step()
            fut = m.allreduce({"g": np.ones(4, np.float32)})
            m.stage_deferred(fut)
            m.request_preemption(60.0)
            # Nothing may tear the staged step: a premature step() is
            # refused by the overlap guard AND the drain defers first
            # (never fires through an in-flight deferred commit).
            with pytest.raises(RuntimeError, match="deferred"):
                m.step()
            assert not m.drained()
            assert m.deferred_pending()
            evs = [e for e in m.history() if e["event"] == "preempt_deferred"]
            assert evs and "deferred in flight" in evs[0]["why"]
            # The settle (DelayedOptimizer's job) clears the staged
            # step; the drain then lands at the post-apply edge.
            assert m.drain_deferred() is not None
            assert m.should_commit()
            assert not m.drained()
            with pytest.raises(PreemptedExit):
                m.step()
            assert m.drained()
        finally:
            if not m.drained():
                m.shutdown()

    def test_deadline_expiry_degrades_to_hard_kill_with_flight_dump(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        client = self.participant_client()
        client.should_commit.return_value = False  # forever blocked
        m = make_manager(client)
        try:
            assert not boundary(m)  # vote aborted
            m.request_preemption(0.2)
            assert not boundary(m)  # blocked, inside deadline: deferred
            assert m.preemption_pending()
            time.sleep(0.25)
            assert not boundary(m)  # past deadline: expire, not drain
            assert not m.drained()
            assert not m.preemption_pending()  # expired = no longer armed
            mx = m.metrics()
            assert mx["preempt_deadline_expired_total"] == 1
            assert mx["graceful_exits_total"] == 0
            assert [e for e in m.history()
                    if e["event"] == "preempt_deadline_expired"]
            # The flight recorder dumped the postmortem.
            assert mx["flight_dumps_total"] >= 1
            assert any(f.endswith(".json") for f in os.listdir(tmp_path))
            # Later boundaries are undisturbed (hard-kill behavior:
            # keep running until the SIGKILL lands).
            client.should_commit.return_value = True
            assert boundary(m)
            assert not m.drained()
        finally:
            m.shutdown()

    def test_save_durable_with_user_state_is_not_auto_remembered(self, tmp_path):
        """A cadence save passing an explicit user_state must NOT arm
        the drain's auto-remembered target: the drain would write the
        manager-registered tree while every cadence file holds the
        caller's richer one — the newest checkpoint would then break
        cold-start resume on the structure mismatch. Such callers
        register via set_durable_target(user_state_fn=...)."""
        from torchft_tpu.checkpoint_io import AsyncCheckpointer

        client = self.participant_client()
        m = make_manager(client)
        try:
            writer = AsyncCheckpointer()
            assert boundary(m)
            fut = m.save_durable(writer, str(tmp_path),
                                 user_state={"rich": {"w": np.ones(2)}})
            assert fut is not None
            fut.result(timeout=30)
            assert m._drain.target is None  # no mismatched drain save
            # A plain save (manager-registered tree) IS remembered.
            fut = m.save_durable(writer, str(tmp_path))
            fut.result(timeout=30)
            assert m._drain.target is not None
        finally:
            m.shutdown()

    def test_fresh_notice_rearms_after_expiry(self):
        """Spot reprieve then re-reclaim: a notice arriving AFTER an
        earlier notice expired must re-arm the drain with the NEW
        deadline (not min() against the long-dead one, which would
        leave the drain inert forever)."""
        client = self.participant_client()
        client.should_commit.return_value = False
        m = make_manager(client)
        try:
            assert not boundary(m)
            m.request_preemption(0.2)
            assert not boundary(m)  # deferred (vote aborted)
            time.sleep(0.25)
            assert not boundary(m)  # expired
            assert not m.preemption_pending()
            # The reclaim was cancelled; a fresh one arrives later.
            remaining = m.request_preemption(60.0, reason="re-reclaim")
            assert remaining > 50.0  # re-armed, not a negative stale min
            assert m.preemption_pending()
            client.should_commit.return_value = True
            assert boundary(m)  # deferred once more (last vote aborted)
            with pytest.raises(PreemptedExit):
                m.step()
            assert m.drained()
            mx = m.metrics()
            assert mx["preempt_deadline_expired_total"] == 1
            assert mx["graceful_exits_total"] == 1
        finally:
            if not m.drained():
                m.shutdown()

    def test_refused_final_save_degrades_instead_of_lying(self):
        """A final save that save_durable REFUSES (state turned unclean
        between the drain's check and the save) must degrade to the
        hard-kill path — never complete the drain claiming a final
        save that was not written."""
        client = self.participant_client()
        m = make_manager(client)
        try:
            m.set_durable_target(MagicMock(), "/nonexistent")
            assert boundary(m)
            m.request_preemption(60.0)
            m.save_durable = MagicMock(return_value=None)  # refusal
            m.step()  # drain attempt: save refused -> degrade, no raise
            assert not m.drained()
            mx = m.metrics()
            assert mx["preempt_deadline_expired_total"] == 1
            assert mx["graceful_exits_total"] == 0
            assert any("refused" in str(e.get("why", ""))
                       for e in m.history()
                       if e["event"] == "preempt_deadline_expired")
        finally:
            m.shutdown()

    def test_repeated_notices_count_and_keep_earliest_deadline(self):
        client = self.participant_client()
        m = make_manager(client)
        try:
            m.request_preemption(120.0)
            remaining = m.request_preemption(60.0)
            # (now + 60.0) - now is 60.0 to a rounding of the clock's sum:
            # where now + 60 crosses a power of two the sum loses a bit and
            # the difference reads just over 60.0 a quarter of the time (the
            # monotonic clock 4,036-4,096 s or 8,132-8,192 s after boot:
            # the driver's run of 7c58aa3 failed `<= 60.0` there).
            assert remaining <= 60.0 + 1e-6
            # A later, LONGER notice must not extend the armed deadline.
            remaining = m.request_preemption(300.0)
            assert remaining <= 60.0 + 1e-6
            assert m.metrics()["preempt_notices_total"] == 3
        finally:
            m.shutdown()

    def test_reclaim_sec_env_default(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_RECLAIM_SEC", "42")
        client = self.participant_client()
        m = make_manager(client)
        try:
            assert m.request_preemption() == pytest.approx(42.0, abs=1.0)
        finally:
            m.shutdown()

    def test_sigterm_handler_requests_preemption(self):
        client = self.participant_client()
        m = make_manager(client)
        prev = None
        try:
            prev = m.install_preemption_handler(deadline_s=30.0)
            os.kill(os.getpid(), signal.SIGTERM)
            # Python delivers the signal on the main thread at the next
            # bytecode boundary; give it one.
            for _ in range(100):
                if m.preemption_pending():
                    break
                time.sleep(0.01)
            assert m.preemption_pending()
            # The handler is lock-free (a signal can interrupt a frame
            # HOLDING _metrics_lock — taking it again would deadlock
            # the drain): the counter lands at the next boundary's
            # flush, not inside the handler.
            assert m.metrics()["preempt_notices_total"] == 0
            with pytest.raises(PreemptedExit):
                m.step()  # clean init boundary: flush + drain
            assert m.metrics()["preempt_notices_total"] == 1
        finally:
            if prev is not None:
                signal.signal(signal.SIGTERM, prev)
            m.shutdown()

    def test_publication_detaches_on_drain(self):
        from torchft_tpu.serving import WeightPublisher

        client = self.participant_client()
        m = make_manager(client)
        pub = WeightPublisher()
        assert boundary(m)
        assert m.publish(pub) is not None
        assert m._ckpt_server._publication is pub
        m.request_preemption(60.0)
        with pytest.raises(PreemptedExit):
            m.step()
        assert m.drained()
        # Withdrawn: the next /publish head poll 404s and subscribers
        # rotate away (checkpointing.detach_publication).
        assert m._ckpt_server._publication is None


# ------------------------------------- join/churn accounting (manager)


class TestJoinChurnAccounting:
    def test_joins_coalesced_counts_multi_member_growth(self):
        client = MagicMock()
        client.quorum.side_effect = [
            quorum_result(quorum_id=1, replica_world_size=2),
            # One reconfigure admits THREE joiners at once (world 2->5):
            # two of them rode an already-open coalescing window.
            quorum_result(quorum_id=2, replica_world_size=5,
                          max_world_size=5),
            # Shrink: never counted.
            quorum_result(quorum_id=3, replica_world_size=3,
                          max_world_size=3),
            # Single joiner: nothing coalesced.
            quorum_result(quorum_id=4, replica_world_size=4,
                          max_world_size=4),
        ]
        client.should_commit.return_value = True
        m = make_manager(client)
        try:
            for _ in range(4):
                assert boundary(m)
            mx = m.metrics()
            assert mx["joins_coalesced_total"] == 2
            assert mx["reconfigure_count"] == 4
            assert mx["reconfigures_per_min"] == 4.0
        finally:
            m.shutdown()

    def test_own_first_join_is_not_coalescing(self):
        client = MagicMock()
        # Our first round lands in a 5-group fleet: the world "jump"
        # from 0 is just us discovering it, not a coalesced admission.
        client.quorum.return_value = quorum_result(
            quorum_id=9, replica_world_size=5, max_world_size=5)
        client.should_commit.return_value = True
        m = make_manager(client)
        try:
            assert boundary(m)
            assert m.metrics()["joins_coalesced_total"] == 0
        finally:
            m.shutdown()

    def test_churn_rate_feeds_policy_signals(self):
        from torchft_tpu.policy import PolicyController

        c = PolicyController(window=4, escalate_failures=2,
                             relax_after=3, cooldown=1)
        c.note_boundary(True, churn_rate=7.0)
        assert c.last_signals.churn_rate == 7.0
        assert c.last_signals.as_dict()["churn_rate"] == 7.0


# ----------------------------------------- pre-join heal (backpressure)


class TestPrejoinHeal:
    def _fleet_state(self):
        return {
            "user": {"w": np.arange(8, dtype=np.float32) * 3.0},
            "torchft": {"step": 7, "batches_committed": 21},
        }

    def test_prejoin_adopts_fleet_state_over_real_http(self):
        donor_state = self._fleet_state()
        srv = CheckpointServer(lambda: donor_state)
        srv.allow_checkpoint(7)
        holder = {}
        client = MagicMock()
        m = make_manager(client,
                         load_state_dict=lambda s: holder.update(p=s),
                         state_dict=lambda: {"w": np.zeros(8, np.float32)})
        try:
            status = {"members": [
                {"replica_id": "donor", "address": "mgr:1", "step": 7},
            ]}
            ok = m.prejoin_heal(lambda: status,
                                resolve=lambda addr: srv.address())
            assert ok is True
            assert m.current_step() == 7
            assert m.batches_committed() == 21
            got = np.asarray(holder["p"]["w"])
            assert got.tobytes() == donor_state["user"]["w"].tobytes()
            mx = m.metrics()
            assert mx["prejoin_heals_total"] == 1
            assert mx["heal_bytes_total"] > 0
            assert [e for e in m.history() if e["event"] == "prejoin_heal"]
        finally:
            m.shutdown()
            srv.shutdown()

    def test_prejoin_stripes_across_max_step_members(self):
        donor_state = self._fleet_state()
        srvs = [CheckpointServer(lambda: donor_state) for _ in range(2)]
        for s in srvs:
            s.allow_checkpoint(7)
        holder = {}
        m = make_manager(MagicMock(),
                         load_state_dict=lambda s: holder.update(p=s),
                         state_dict=lambda: {"w": np.zeros(8, np.float32)})
        try:
            status = {"members": [
                {"replica_id": "d0", "address": "m0:1", "step": 7},
                {"replica_id": "d1", "address": "m1:1", "step": 7},
                {"replica_id": "lag", "address": "m2:1", "step": 5},
            ]}
            addrs = {"m0:1": srvs[0].address(), "m1:1": srvs[1].address()}
            ok = m.prejoin_heal(lambda: status,
                                resolve=lambda addr: addrs[addr])
            assert ok is True
            assert m.current_step() == 7
            got = np.asarray(holder["p"]["w"])
            assert got.tobytes() == donor_state["user"]["w"].tobytes()
        finally:
            m.shutdown()
            for s in srvs:
                s.shutdown()

    def test_prejoin_noop_when_already_current_or_no_fleet(self):
        m = make_manager(MagicMock())
        try:
            assert m.prejoin_heal(lambda: {"members": []}) is False
            # Fleet at our step: nothing to adopt.
            assert m.prejoin_heal(lambda: {"members": [
                {"replica_id": "d", "address": "m:1", "step": 0}]}) is False
            assert m.metrics()["prejoin_heals_total"] == 0
        finally:
            m.shutdown()

    def test_prejoin_failure_is_best_effort(self):
        m = make_manager(MagicMock())
        try:
            status = {"members": [
                {"replica_id": "d", "address": "m:1", "step": 9}]}

            def bad_resolve(addr):
                raise ConnectionRefusedError("donor gone")

            assert m.prejoin_heal(lambda: status,
                                  resolve=bad_resolve) is False
            assert m.current_step() == 0  # untouched; in-quorum heal covers
        finally:
            m.shutdown()

    def test_prejoin_refused_after_first_quorum_join(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        m = make_manager(client)
        try:
            assert boundary(m)
            with pytest.raises(RuntimeError, match="BEFORE the first"):
                m.prejoin_heal(lambda: {"members": []})
        finally:
            m.shutdown()


# ------------------------------------------------- kill-latch rebirth


class TestKillLatchRebirth:
    def test_endpoint_reborn_clears_latch_and_byte_account(self):
        sched = ChaosSchedule(seed=1, endpoints={
            "heal": EndpointChaos(kill_after_bytes=100)})
        chaos.install(sched)
        try:
            sched.kill_endpoint("heal:h:1")
            sched.note_bytes("heal:h:1", 100)
            assert sched.is_dead("heal:h:1")
            chaos.endpoint_reborn("heal:h:1", "serve:h:1")
            assert not sched.is_dead("heal:h:1")
            # The byte account reset with the latch: the replacement
            # gets the full kill_after_bytes allowance, not instant
            # re-death on its first byte.
            assert sched.kill_allowance("heal:h:1") == 100
        finally:
            chaos.uninstall()

    def test_endpoint_reborn_noop_without_schedule(self):
        chaos.uninstall()
        chaos.endpoint_reborn("heal:x:1")  # must not raise

    def test_replacement_checkpoint_server_revives_inherited_latch(self):
        """The soak-blocking bug: a replacement binding a dead member's
        host:port inherited the corpse's kill latch — every dial
        refused forever. A fresh server at the address must revive it."""
        sched = ChaosSchedule(seed=1, endpoints={})
        chaos.install(sched)
        try:
            state = {"w": np.ones(4, np.float32)}
            first = CheckpointServer(lambda: state, bind_host="127.0.0.1")
            import urllib.parse

            netloc = urllib.parse.urlparse(first.address()).netloc
            port = int(netloc.rsplit(":", 1)[1])
            # The member dies; chaos latches its endpoints dead.
            first.shutdown()
            sched.kill_endpoint(f"heal:{netloc}")
            sched.kill_endpoint(f"serve:{netloc}")
            # The replacement reuses the address: bind revives both.
            second = CheckpointServer(lambda: state,
                                      bind_host="127.0.0.1",
                                      bind_port=port)
            try:
                assert not sched.is_dead(f"heal:{netloc}")
                assert not sched.is_dead(f"serve:{netloc}")
            finally:
                second.shutdown()
        finally:
            chaos.uninstall()

    def test_replacement_publication_server_revives_latch(self):
        from torchft_tpu.serving import PublicationServer, WeightPublisher

        sched = ChaosSchedule(seed=1, endpoints={})
        chaos.install(sched)
        try:
            pub = WeightPublisher()
            first = PublicationServer(pub, bind_host="127.0.0.1")
            import urllib.parse

            netloc = urllib.parse.urlparse(first.address()).netloc
            port = int(netloc.rsplit(":", 1)[1])
            first.shutdown()
            sched.kill_endpoint(f"serve:{netloc}")
            second = PublicationServer(pub, bind_host="127.0.0.1",
                                       port=port)
            try:
                assert not sched.is_dead(f"serve:{netloc}")
            finally:
                second.shutdown()
        finally:
            chaos.uninstall()


# --------------------------------- 2-group graceful-vs-SIGKILL A/B drive


class TestGracefulReclaimDrive:
    """The acceptance oracle (ISSUE 14): two groups over a REAL
    socketpair ring (the data plane is real sockets; the control plane
    is scripted). Graceful leg: B gets a reclaim notice, drains at its
    commit boundary (farewell first), and A — whose next quorum round
    reflects the farewell-driven membership cut — commits EVERY step
    with zero vote aborts and zero ring-reset latches. SIGKILL control
    leg: B vanishes without a farewell, A's next round still names B
    (staleness not yet proven), its ring op hits dead sockets, and the
    step aborts — the cost the graceful protocol exists to avoid."""

    K_TOGETHER = 3   # steps both groups run
    K_AFTER = 3      # survivor-only steps after B leaves

    def _survivor_client(self, stale_rounds=0):
        """A's scripted control plane: world 2 while B lives, then —
        after `stale_rounds` rounds that still name B (the SIGKILL
        staleness window) — world 1 under a bumped quorum id."""
        client = MagicMock()
        seq = []
        for s in range(1, self.K_TOGETHER + 1):
            seq.append(quorum_result(
                quorum_id=1, max_rank=0, max_world_size=2,
                replica_rank=0, replica_world_size=2, max_step=s))
        for _ in range(stale_rounds):
            seq.append(quorum_result(
                quorum_id=1, max_rank=0, max_world_size=2,
                replica_rank=0, replica_world_size=2))
        for _ in range(self.K_AFTER + 2):
            seq.append(quorum_result(
                quorum_id=2, max_rank=0, max_world_size=1,
                replica_rank=0, replica_world_size=1))
        client.quorum.side_effect = seq
        client.should_commit.side_effect = \
            lambda rank, step, should_commit, timeout_ms=None: should_commit
        return client

    def _leaver_client(self):
        client = MagicMock()
        client.quorum.side_effect = [
            quorum_result(store_address=FAKE_STORE_ADDR, quorum_id=1,
                          max_rank=1, max_world_size=2, replica_rank=1,
                          replica_world_size=2, max_step=s)
            for s in range(1, self.K_TOGETHER + 1)
        ]
        client.should_commit.side_effect = \
            lambda rank, step, should_commit, timeout_ms=None: should_commit
        return client

    def _grads(self, rank, step):
        rng = np.random.default_rng(100 * rank + step)
        return {"g": np.asarray(rng.normal(size=(64,)), np.float32)}

    def _run_leg(self, graceful, tmp_path):
        from test_manager import _make_test_rings, _wired_comm

        rings = _make_test_rings(2)
        store = FakeStore()
        client_a = self._survivor_client(
            stale_rounds=0 if graceful else 1)
        client_b = self._leaver_client()
        comm_a = _wired_comm(rings[0], 0, 2)
        comm_b = _wired_comm(rings[1], 1, 2)

        # The survivor's world genuinely shrinks at the membership cut:
        # the scripted configure mirrors what the real rendezvous does.
        def configure_a(store_addr, rank, world_size):
            comm_a._rank, comm_a._world = rank, world_size
        comm_a.configure = configure_a

        m_a = make_manager(client_a, comm=comm_a, replica_id="groupA")
        m_b = make_manager(client_b, comm=comm_b, replica_id="groupB",
                           store=store)
        from torchft_tpu.checkpoint_io import AsyncCheckpointer

        m_b.set_durable_target(AsyncCheckpointer(), str(tmp_path))

        committed_a = []
        b_outcome = {}

        def run_b():
            try:
                for k in range(self.K_TOGETHER):
                    m_b.step()
                    m_b.allreduce(self._grads(1, k)).result()
                    if graceful and k == self.K_TOGETHER - 1:
                        # The cloud's reclaim notice lands mid-step:
                        # the boundary below still commits; the drain
                        # fires at the next step()'s post-apply edge.
                        m_b.request_preemption(30.0, reason="reclaim")
                    m_b.should_commit()
                if graceful:
                    try:
                        m_b.step()
                        b_outcome["exit"] = "kept-running"
                    except PreemptedExit:
                        b_outcome["exit"] = "preempted"
                else:
                    # SIGKILL: vanish without farewell/shutdown — the
                    # ring sockets are slammed shut by the main thread.
                    b_outcome["exit"] = "killed"
            except Exception as e:  # noqa: BLE001
                b_outcome["exit"] = f"error: {e!r}"

        tb = threading.Thread(target=run_b, name="groupB")
        tb.start()
        try:
            for k in range(self.K_TOGETHER):
                m_a.step()
                avg = m_a.allreduce(self._grads(0, k)).result()
                assert avg is not None
                committed_a.append(m_a.should_commit())
            tb.join(timeout=30)
            assert not tb.is_alive()
            if not graceful:
                # B's process is gone: its sockets slam shut.
                rings[1].close()
                # Simulate the teardown a dead process gets.
                comm_b.shutdown()
            for k in range(self.K_AFTER + (0 if graceful else 1)):
                m_a.step()
                m_a.allreduce(self._grads(0, 100 + k)).result()
                committed_a.append(m_a.should_commit())
            mx_a = m_a.metrics()
            mx_b = m_b.metrics()
            poisoned = m_a._comm_poisoned
            events_a = m_a.history()
        finally:
            m_a.shutdown()
            if not graceful:
                # B never shut down (it "SIGKILL'd"): reap its threads.
                m_b._executor.shutdown(wait=False, cancel_futures=True)
                m_b._put_executor.shutdown(wait=False)
                m_b._ckpt_server.shutdown()
            for ring in rings:
                try:
                    ring.close()
                except Exception:  # noqa: BLE001
                    pass
        return {"committed_a": committed_a, "mx_a": mx_a, "mx_b": mx_b,
                "store": store, "poisoned": poisoned,
                "events_a": events_a, "b_outcome": b_outcome}

    def test_graceful_leg_zero_aborts_zero_ring_resets(self, tmp_path):
        r = self._run_leg(graceful=True, tmp_path=tmp_path)
        # The survivor committed EVERY step across B's exit.
        assert r["committed_a"] == [True] * len(r["committed_a"])
        assert r["mx_a"]["aborted_steps"] == 0
        # Zero ring-reset latches: no poison, no recovery rendezvous.
        assert r["poisoned"] is False
        assert not [e for e in r["events_a"]
                    if e["event"] == "reconfigure" and e.get("recovery")]
        assert not [e for e in r["events_a"] if e["event"] == "abort"]
        # B drained the full protocol: farewell + final save + tombstone.
        assert r["mx_b"]["graceful_exits_total"] == 1
        assert r["store"].kv["torchft/healset/1"] == b"-1:"
        from torchft_tpu import checkpoint_io

        assert checkpoint_io.recover(str(tmp_path)) is not None
        assert r["b_outcome"]["exit"] == "preempted"

    def test_sigkill_control_leg_costs_at_least_one_abort(self, tmp_path):
        r = self._run_leg(graceful=False, tmp_path=tmp_path)
        # The control leg: >= 1 abort proves the graceful protocol
        # earns its keep (identical storm, only the farewell differs).
        assert r["mx_a"]["aborted_steps"] >= 1
        assert False in r["committed_a"]
        # And the survivor RECOVERS: the last steps commit again.
        assert r["committed_a"][-1] is True
        assert r["mx_b"]["graceful_exits_total"] == 0


# ---------------------------------------------------- bench plumbing


class TestChurnBenchPlumbing:
    def test_hard_kill_helper_tears_down_without_farewell(self):
        """The SIGKILL leg's teardown: sockets/servers die, but NO
        farewell goes out — survivors must observe a crash, or the
        control leg silently measures the graceful protocol twice."""
        import bench

        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        m = make_manager(client)
        assert boundary(m)
        bench._hard_kill_manager(m)
        assert not client.farewell.called
        assert m.metrics()["graceful_exits_total"] == 0

    def test_churn_goodput_row_carries_churn_rate(self):
        """Every bench_churn_goodput result must carry the churn rate
        its row is stamped with (the satellite contract); frozen here
        so a refactor cannot drop it silently."""
        import inspect

        import bench

        src = inspect.getsource(bench.bench_churn_goodput)
        assert '"churn_pct_per_min": churn_pct_per_min' in src
        # And main() stamps churn_rate on every emitted churn row.
        main_src = inspect.getsource(bench.main)
        assert main_src.count('"churn_rate"') >= 2


# ------------------------------------------- join-storm admission (native)


@requires_native
class TestJoinStormAdmission:
    """The ISSUE-14 join-storm acceptance, against the REAL control
    plane: >= 8 joiners landing inside one coalescing window must be
    admitted as ONE membership delta, and a second wave costs exactly
    one more — reconfigure count grows with WINDOWS, not joiners."""

    def _mk_group(self, lh_addr, name, servers, clients):
        from torchft_tpu import _native
        from torchft_tpu.retry import RetryPolicy

        s = _native.ManagerServer(name, lh_addr, store_addr=f"st-{name}",
                                  bind="127.0.0.1:0", world_size=1,
                                  heartbeat_ms=50)
        c = _native.ManagerClient(s.address(), connect_timeout_ms=10_000,
                                  retry_policy=RetryPolicy(max_attempts=1))
        servers.append(s)
        clients.append(c)
        return c

    def test_two_waves_two_deltas(self):
        from torchft_tpu import _native

        lh = _native.Lighthouse(
            bind="127.0.0.1:0", min_replicas=1,
            join_timeout_ms=150,  # a window-less cut per joiner's pace
            quorum_tick_ms=10, heartbeat_fresh_ms=400,
            # The window holds every arrival of a wave (0.5 s of
            # staggered starts) with a second to spare on a loaded host.
            eviction_staleness_factor=3, join_window_ms=1500)
        servers, clients = [], []
        try:
            seed = self._mk_group(lh.address(), "seed", servers, clients)
            q0 = seed.quorum(rank=0, step=1,
                             checkpoint_server_addr="ckpt-seed",
                             timeout_ms=60_000)
            assert q0.replica_world_size == 1

            def wave(tag, k, step):
                members = list(clients)  # everyone admitted so far
                results = [None] * (k + len(members))

                def rejoin(c, idx):
                    results[idx] = c.quorum(
                        rank=0, step=step,
                        checkpoint_server_addr=f"ckpt-{idx}",
                        timeout_ms=60_000)

                def joiner(i, idx):
                    c = self._mk_group(lh.address(), f"{tag}{i:02d}",
                                       servers, clients)
                    results[idx] = c.quorum(
                        rank=0, step=1,
                        checkpoint_server_addr=f"ckpt-{tag}{i}",
                        timeout_ms=60_000)

                threads = [threading.Thread(target=joiner, args=(i, i))
                           for i in range(k)]
                for t in threads[:3]:
                    t.start()
                    # Staggered past join_timeout_ms in total: without
                    # the window these arrivals would cut several rounds.
                    time.sleep(0.06)
                # The members' re-joins (a live group asks every step)
                # start AFTER a few joiners are in flight: before the
                # first joiner the unchanged membership would cut at
                # once, and the storm would meet a round of its own.
                rejoins = [threading.Thread(target=rejoin, args=(c, k + j))
                           for j, c in enumerate(members)]
                for t in rejoins:
                    t.start()
                for t in threads[3:]:
                    t.start()
                    time.sleep(0.06)
                for t in threads + rejoins:
                    t.join(timeout=60)
                    assert not t.is_alive()
                return results

            world0 = 1
            r1 = wave("a", 8, step=2)
            assert all(r is not None for r in r1)
            assert {r.quorum_id for r in r1} == {q0.quorum_id + 1}
            assert {r.replica_world_size for r in r1} == {world0 + 8}

            r2 = wave("b", 8, step=3)
            assert all(r is not None for r in r2)
            assert {r.quorum_id for r in r2} == {q0.quorum_id + 2}
            assert {r.replica_world_size for r in r2} == {world0 + 16}

            st = lh.status()
            # 8 joiners per wave -> 7 coalesced beyond the first, twice.
            assert st["joins_coalesced"] >= 14
        finally:
            for s in servers:
                s.shutdown()
            lh.shutdown()


@requires_native
@pytest.mark.slow
@pytest.mark.nightly
class TestControlPlaneChurn256:
    """The title-scale soak: a 256-group fleet on the REAL control
    plane (thin manager/client pairs — the data-plane goodput soak
    runs at bench scale) churns through farewell-leaves + silent kills
    + replacement waves. Gates: the quorum keeps cutting, membership
    tracks the live set, and the membership-delta count grows with
    churn WAVES (leaves coalesce per round, joins per window), not
    with individual members."""

    N = 256
    WAVES = 3
    PER_WAVE = 8

    def test_fleet_survives_wave_churn(self):
        from torchft_tpu import _native
        from torchft_tpu.retry import RetryPolicy

        lh = _native.Lighthouse(
            bind="127.0.0.1:0", min_replicas=1,
            join_timeout_ms=60_000, quorum_tick_ms=5,
            heartbeat_fresh_ms=500, eviction_staleness_factor=6,
            join_window_ms=300)
        groups = {}  # name -> (server, client)
        try:
            def spawn(name):
                s = _native.ManagerServer(
                    name, lh.address(), store_addr=f"st-{name}",
                    bind="127.0.0.1:0", world_size=1, heartbeat_ms=100)
                c = _native.ManagerClient(
                    s.address(), connect_timeout_ms=10_000,
                    retry_policy=RetryPolicy(max_attempts=1))
                groups[name] = (s, c)

            def quorum_all(step, early=()):
                """One quorum round for the whole fleet. ``early``
                names start (and announce) first — replacement waves
                must open the slow round before a survivor's request
                can sneak a fast-path serve of the stale membership."""
                out = {}
                errs = []

                def one(name, c):
                    try:
                        out[name] = c.quorum(
                            rank=0, step=step,
                            checkpoint_server_addr=f"ck-{name}",
                            timeout_ms=120_000)
                    except Exception as e:  # noqa: BLE001
                        errs.append((name, repr(e)))

                ts_early = [threading.Thread(target=one, args=(n, c))
                            for n, (_s, c) in groups.items()
                            if n in early]
                ts = [threading.Thread(target=one, args=(n, c))
                      for n, (_s, c) in groups.items()
                      if n not in early]
                for t in ts_early:
                    t.start()
                if ts_early:
                    time.sleep(0.5)  # announces landed; round is open
                for t in ts:
                    t.start()
                for t in ts_early + ts:
                    t.join(timeout=180)
                assert not errs, errs[:3]
                return out

            for i in range(self.N):
                spawn(f"g{i:03d}")
            r = quorum_all(1)
            qid0 = next(iter(r.values())).quorum_id
            assert {v.replica_world_size for v in r.values()} == {self.N}

            rng = np.random.default_rng(42)
            step = 2
            for wave in range(self.WAVES):
                victims = rng.choice(sorted(groups), size=self.PER_WAVE,
                                     replace=False)
                for j, name in enumerate(victims):
                    s, _c = groups.pop(name)
                    if j % 2 == 0:
                        s.shutdown()   # clean leave: farewell
                    else:
                        s.hard_stop()  # SIGKILL: silence, staleness
                # Survivors cut the shrunken quorum; the farewell'd
                # half is provably gone, the killed half ages out
                # within the staleness bound.
                r = quorum_all(step)
                assert {v.replica_world_size for v in r.values()} \
                    == {self.N - self.PER_WAVE}
                step += 1
                # Replacement wave: fresh ids join inside one window.
                new_names = set()
                for i in range(self.PER_WAVE):
                    spawn(f"r{wave}{i:02d}")
                    new_names.add(f"r{wave}{i:02d}")
                r = quorum_all(step, early=new_names)
                assert {v.replica_world_size for v in r.values()} \
                    == {self.N}
                step += 1

            # Membership-delta accounting: each wave costs O(1) deltas
            # (one shrink cut + one coalesced join round, plus at most
            # one straggler round) — NOT one per preempted/joined
            # member.
            qid_delta = next(iter(r.values())).quorum_id - qid0
            assert qid_delta <= 3 * self.WAVES
            st = lh.status()
            assert st["joins_coalesced"] >= self.WAVES * (self.PER_WAVE // 2)
        finally:
            for s, _c in groups.values():
                s.shutdown()
            lh.shutdown()


# ------------------------------------------------- nightly churn soak


@requires_native
@pytest.mark.slow
@pytest.mark.nightly
class TestChurnSoak:
    """The Poisson churn soak (nightly): seeded graceful+SIGKILL churn
    with cold replacements at accelerated rates, gated on the ISSUE-14
    acceptance — graceful-leg goodput >= 0.8x the zero-churn baseline,
    and bitwise convergence through unbounded membership drift."""

    def test_churn_goodput_curve_and_bitwise_convergence(self):
        import bench

        base = bench.bench_churn_goodput(churn_pct_per_min=0.0,
                                         duration_s=20.0, seed=1234)
        assert base["bitwise_identical"]
        base_rate = base["committed_batches_per_s"]
        assert base_rate > 0

        # Graceful leg walks stable -> storm -> stable (PhasedChaos
        # shape) so the gate covers the regime transition, not just a
        # constant rate.
        graceful = bench.bench_churn_goodput(
            leg="graceful", reclaim_s=8.0, seed=1234,
            phases=((8.0, 0.0), (16.0, 200.0), (8.0, 0.0)))
        assert graceful["notices"] >= 1
        assert graceful["bitwise_identical"]
        assert graceful["committed_batches_per_s"] >= 0.8 * base_rate

        sigkill = bench.bench_churn_goodput(
            churn_pct_per_min=150.0, leg="sigkill", duration_s=30.0,
            seed=1234)
        assert sigkill["kills"] >= 1
        assert sigkill["bitwise_identical"]

    def test_ram_tier_churn_goodput_ab(self):
        """RAM-tier A/B under sustained churn (docs/design/memory_tier.md,
        ISSUE-16 acceptance): the 20%/min leg must hold goodput with the
        RAM tier armed — cross-replication at every commit boundary and
        RAM-preferring cold starts must not cost throughput vs the
        disk-only control, and the bitwise oracle must stay exact."""
        import bench

        off = bench.bench_churn_goodput(
            churn_pct_per_min=20.0, leg="sigkill", duration_s=30.0,
            seed=4321, replace_delay_s=1.0, ram_tier=False)
        assert off["bitwise_identical"]
        assert off["committed_batches_per_s"] > 0

        on = bench.bench_churn_goodput(
            churn_pct_per_min=20.0, leg="sigkill", duration_s=30.0,
            seed=4321, replace_delay_s=1.0, ram_tier=True)
        assert on["ram_tier"]
        assert on["bitwise_identical"]
        # Replication rides the commit boundary on every group, so it
        # must be happening even when churn never fires a kill.
        assert on["ram_replications"] >= 1
        # Goodput gate: RAM-on holds >= 0.9x the disk-only control
        # (replication is async off the step path; the tier may only
        # ever make replacement FASTER, never training slower).
        assert on["committed_batches_per_s"] >= (
            0.9 * off["committed_batches_per_s"])
