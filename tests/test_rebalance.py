"""Straggler-aware fleet rebalancing tests (ISSUE 19,
docs/design/fleet_rebalance.md).

Tier-1 (marker ``rebalance``, ``scripts/test.sh rebalance``), all
native-free: the fraction-table wire format, the pure-Python
Rebalancer ladder frozen boundary-for-boundary against the C++ mirror
(core_test.cc carries the SAME snapshot literals — a drift on either
side fails one of the two), the ladder's edge cases (duplicate-step
replay, sticky ineligible rows, farewell, small-fleet median
behavior, the boost cap's unallocated remainder), the Manager's
commit-boundary adoption protocol (uncoordinated hint fallback,
decider-publishes/all-adopt over a fake quorum store, refusal
classes, bounds clamping, the digest's in-force fraction stamp and
its TypeError compatibility ladder), the ElasticSampler's fractional
draws + fold-weight reporting, the chaos ``slow:`` band, and the
composed-fraction bitwise weighted fold over real socketpair rings.

The PhasedChaos stable -> storm -> stable shrink-then-restore soak
(the zero-flap acceptance gate) rides ``nightly``+``slow``.
"""

import functools
import threading
import time
from unittest.mock import MagicMock

import numpy as np
import pytest

import conftest  # noqa: F401 — repo-standard path/env setup
import mockplane
from mockplane import (FAKE_STORE_ADDR, FakeStore, boundary, mock_client,
                       quorum_result)
from torchft_tpu import chaos, fleet
from torchft_tpu.backends.host import HostCommunicator, _Ring
from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.data import ElasticSampler, _reports_samples
from torchft_tpu.degraded import _REBALANCE_KEY

pytestmark = pytest.mark.rebalance


# --------------------------------------------------------------- helpers


make_manager = functools.partial(
    mockplane.make_manager, min_replica_size=1, replica_id="rebaltest",
    rebalance=True)


class BrokenStore(FakeStore):
    """Publishes fine, every read fails — the torn-control-plane case:
    adoption must fall back to 'adopt nothing this boundary'."""

    def get(self, key, timeout_ms=0):
        raise RuntimeError("store read lost")


def weighted_oracle(xs, weights, dtype=np.float32):
    """The documented weighted-fold contract, spelled in single-process
    numpy: sum of w_r * x_r in rank order (zero-weight contributions
    EXCLUDED, not multiplied by zero), true-divided by the total."""
    dt = np.dtype(dtype)
    acc = np.zeros(np.ravel(xs[0]).size, dt)
    for w, x in zip(weights, xs):
        if w:
            acc += np.ravel(x).astype(dt) * dt.type(w)
    total = sum(weights)
    if total:
        acc /= dt.type(total)
    return acc


def _socketpair_rings(world):
    import socket as _socket

    pairs = [_socket.socketpair() for _ in range(world)]
    return [_Ring(pairs[r][0], pairs[(r - 1) % world][1],
                  _socket.socket())
            for r in range(world)]


def _run_ring(world, fn):
    rings = _socketpair_rings(world)
    comms = []
    for r in range(world):
        c = HostCommunicator(timeout_sec=15)
        c._rank, c._world = r, world
        comms.append(c)
    out = [None] * world
    errors = []

    def w(r):
        try:
            out[r] = fn(comms[r], rings[r], r)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=w, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    alive = [t for t in ts if t.is_alive()]
    for ring in rings:
        ring.close()
    for c in comms:
        c.shutdown()
    assert not alive, "weighted ring deadlocked"
    return out, errors


# ----------------------------------------------------- table wire format


class TestRebalanceTable:
    def test_roundtrip_and_sorted_canonical_order(self):
        fr = {"zeta": 0.5, "alpha": 1.1667, "mid": 0.875}
        table = fleet.format_rebalance_table(fr)
        assert table == "alpha=1.1667,mid=0.8750,zeta=0.5000"
        back = fleet.parse_rebalance_table(table)
        assert back == {"alpha": 1.1667, "mid": 0.875, "zeta": 0.5}

    def test_uniform_entries_omitted_empty_means_uniform(self):
        assert fleet.format_rebalance_table({"a": 1.0, "b": 1.0}) == ""
        assert fleet.parse_rebalance_table("") == {}

    def test_malformed_entries_dropped_not_fatal(self):
        got = fleet.parse_rebalance_table(
            "a=0.7500,garbage,=0.5,b=notanumber,c=0.6250")
        assert got == {"a": 0.75, "c": 0.625}

    def test_out_of_ladder_fractions_dropped(self):
        # An old/corrupt table must never adopt past the skew bounds:
        # entries outside [FLOOR, CEIL] read as absent (-> 1.0).
        got = fleet.parse_rebalance_table("a=0.2500,b=2.0000,c=0.5000")
        assert got == {"c": 0.5}

    def test_rids_with_equals_sign_roundtrip(self):
        # rpartition: the LAST '=' splits, so exotic replica ids keep
        # working.
        table = fleet.format_rebalance_table({"grp=east": 0.75})
        assert fleet.parse_rebalance_table(table) == {"grp=east": 0.75}


# ------------------------------------------------- Rebalancer (mirror)


class TestRebalancerLadderParity:
    """The frozen shrink -> recover trace. core_test.cc's
    test_rebalancer_ladder_parity carries these EXACT snapshot
    literals: the C++ rebalancer and this pure-Python mirror must walk
    the same ladder boundary-for-boundary, or one of the two suites
    fails — the mirror-parity contract of the fleet plane."""

    # (boundary k, table, seq, shrinks_total, restores_total)
    SNAPS = [
        (1, "", 0, 0, 0),
        (3, "a=1.0417,b=1.0417,c=0.8750,d=1.0417", 1, 1, 0),
        (7, "a=1.0833,b=1.0833,c=0.7500,d=1.0833", 2, 2, 0),
        (11, "a=1.1250,b=1.1250,c=0.6250,d=1.1250", 3, 3, 0),
        (15, "a=1.1667,b=1.1667,c=0.5000,d=1.1667", 4, 4, 0),
        (21, "a=1.1250,b=1.1250,c=0.6250,d=1.1250", 5, 4, 1),
        (27, "a=1.0833,b=1.0833,c=0.7500,d=1.0833", 6, 4, 2),
        (33, "a=1.0417,b=1.0417,c=0.8750,d=1.0417", 7, 4, 3),
        (39, "", 8, 4, 4),
    ]

    def test_shrink_then_recover_trace_matches_cpp_mirror(self):
        rb = fleet.Rebalancer()
        base = {"a": 100.0, "b": 100.0, "c": 200.0, "d": 100.0}
        # reported_fraction trails the assigned table by one boundary
        # (the adoption lag real managers have) and the wall scales
        # with it (a shrunken batch finishes proportionally faster).
        prev = {rid: 1.0 for rid in base}
        snaps = iter(self.SNAPS)
        expect = next(snaps)
        for k in range(1, 40):
            if k == 16:
                base["c"] = 100.0  # the straggler recovers
            prev = rb.observe(
                [(rid, k, base[rid] * prev[rid], prev[rid], True)
                 for rid in sorted(base)])
            if expect is not None and expect[0] == k:
                assert (rb.table, rb.seq, rb.shrinks_total,
                        rb.restores_total) == expect[1:], f"k={k}"
                expect = next(snaps, None)
        assert expect is None  # every snapshot visited
        assert all(f == 1.0 for f in rb.fractions().values())

    def test_fleet_total_conserved_at_the_floor(self):
        """At the deepest snapshot (c at the 0.5 floor) the trimmed
        half-slice is exactly absorbed by the three headroom groups:
        the fleet sample total is conserved."""
        rb = fleet.Rebalancer()
        base = {"a": 100.0, "b": 100.0, "c": 200.0, "d": 100.0}
        prev = {rid: 1.0 for rid in base}
        for k in range(1, 16):
            prev = rb.observe(
                [(rid, k, base[rid] * prev[rid], prev[rid], True)
                 for rid in sorted(base)])
        fr = rb.fractions()
        assert fr["c"] == 0.5
        assert sum(fr.values()) == pytest.approx(4.0)
        assert all(f <= fleet.REBALANCE_CEIL + 1e-9
                   for f in fr.values())

    def test_floor_is_terminal_no_further_shrink(self):
        rb = fleet.Rebalancer()
        base = {"a": 100.0, "b": 100.0, "c": 200.0, "d": 100.0}
        prev = {rid: 1.0 for rid in base}
        for k in range(1, 40):  # never recovers
            prev = rb.observe(
                [(rid, k, base[rid] * prev[rid], prev[rid], True)
                 for rid in sorted(base)])
        assert rb.fractions()["c"] == fleet.REBALANCE_FLOOR
        assert rb.shrinks_total == 4  # 1.0 -> 0.5 in eighths, then stop
        # Still loud every boundary, but the floor latches: no flap.
        assert rb.restores_total == 0


class TestRebalancerEdges:
    def _rows(self, walls, step, elig=None):
        elig = elig or {}
        return [(rid, step, w, 1.0, elig.get(rid, True))
                for rid, w in sorted(walls.items())]

    def test_duplicate_step_replay_takes_no_observation(self):
        """Aggregate-recompute cadence (the 200 ms lighthouse cache, a
        dashboard poller) must not inflate the ladder clock: the same
        boundary replayed 10x never accumulates persistence."""
        rb = fleet.Rebalancer()
        walls = {"a": 100, "b": 100, "c": 400, "d": 100}
        for _ in range(10):
            rb.observe(self._rows(walls, step=1))
        assert rb.shrinks_total == 0 and rb.table == ""

    def test_ineligible_straggler_sticky_no_shrink_no_boost(self):
        """A healer/degraded row is legitimately slow: its slowness is
        explained, so the ladder freezes (sticky fraction) instead of
        shrinking it — and it never receives boost either."""
        rb = fleet.Rebalancer()
        walls = {"a": 100, "b": 100, "c": 400, "d": 100}
        for k in range(1, 9):
            rb.observe(self._rows(walls, step=k, elig={"c": False}))
        assert rb.shrinks_total == 0 and rb.table == ""
        assert rb.fractions()["c"] == 1.0

    def test_forget_drops_group_and_its_deficit(self):
        rb = fleet.Rebalancer()
        walls = {"a": 100, "b": 100, "c": 400, "d": 100}
        for k in range(1, 4):
            rb.observe(self._rows(walls, step=k))
        assert rb.shrinks_total == 1
        rb.forget("c")
        assert fleet.format_rebalance_table(rb.fractions()) == ""

    def test_departed_group_dropped_from_observation(self):
        """Absent from rows == departed: same as forget, driven by the
        aggregate view instead of the farewell RPC."""
        rb = fleet.Rebalancer()
        walls = {"a": 100, "b": 100, "c": 400, "d": 100}
        for k in range(1, 4):
            rb.observe(self._rows(walls, step=k))
        assert rb.shrinks_total == 1
        rb.observe(self._rows({"a": 100, "b": 100, "d": 100}, step=4))
        assert fleet.format_rebalance_table(rb.fractions()) == ""

    def test_two_group_fleet_median_absorbs_a_2x_outlier(self):
        """Pinned so nobody 'fixes' the median into a mean and changes
        small-fleet behavior silently: with 2 groups the outlier drags
        the median up (med 150, ratio 1.33 < HI), so a 2x straggler
        never shrinks — only past 3x does a 2-group outlier go loud."""
        rb = fleet.Rebalancer()
        for k in range(1, 13):
            rb.observe(self._rows({"a": 100, "b": 200}, step=k))
        assert rb.shrinks_total == 0 and rb.table == ""

    def test_two_group_fleet_4x_outlier_does_shrink(self):
        rb = fleet.Rebalancer()
        prev = {"a": 1.0, "b": 1.0}
        for k in range(1, 13):
            prev = rb.observe(
                [(rid, k, w * prev[rid], prev[rid], True)
                 for rid, w in (("a", 100.0), ("b", 400.0))])
        assert rb.shrinks_total >= 1
        assert rb.fractions()["b"] < 1.0
        assert rb.fractions()["a"] > 1.0  # the survivor absorbs

    def test_boost_cap_leaves_remainder_unallocated(self):
        """Two groups at the floor with a single headroom group: the
        1.0 deficit would boost it to 2.0, but the CEIL caps it at 1.5
        and the remainder goes UNALLOCATED — the fleet total shrinks
        rather than overloading the one fast group into the next
        straggler."""
        rb = fleet.Rebalancer()
        for rid in ("a", "b", "c"):
            st = rb._st(rid)
            st["eligible"] = True
        rb._st("a")["fraction"] = 0.5
        rb._st("b")["fraction"] = 0.5
        fr = rb.fractions()
        assert fr == {"a": 0.5, "b": 0.5, "c": 1.5}
        assert sum(fr.values()) == pytest.approx(2.5)  # not 3.0

    def test_seq_counts_table_changes_only(self):
        """seq is the flap counter: identical recomputes never bump."""
        rb = fleet.Rebalancer()
        walls = {"a": 100, "b": 100, "c": 400, "d": 100}
        for k in range(1, 3):
            rb.observe(self._rows(walls, step=k))
        assert rb.seq == 0  # loud but below persistence: no change yet
        rb.observe(self._rows(walls, step=3))
        assert rb.seq == 1  # the shrink landed
        rb.observe(self._rows(walls, step=4))
        assert rb.seq == 1  # cooldown: same table, no bump


# --------------------------------------------- Manager adoption protocol


class TestManagerAdoption:
    def test_disabled_by_default_fraction_inert(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            rebalance_table="off=0.5000")
        client.should_commit.return_value = True
        m = make_manager(client, rebalance=False, replica_id="off")
        try:
            boundary(m)
            assert not m.rebalance_enabled()
            assert m.rebalance_fraction() == 1.0
            mx = m.metrics()
            assert mx["rebalance_fraction"] == 1.0
            assert mx["rebalance_adoptions_total"] == 0
        finally:
            m.shutdown()

    def test_device_array_comm_rejected_at_build(self):
        class _DeviceComm(DummyCommunicator):
            wants_device_arrays = True

        with pytest.raises(ValueError, match="host-path"):
            make_manager(MagicMock(), comm=_DeviceComm())

    def test_uncoordinated_hint_adoption_and_restore(self):
        """Single-group / storeless runs adopt straight from their own
        FleetHint table copy; an entry vanishing from the table is the
        restore-to-uniform spelling."""
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            rebalance_table="rebaltest=0.7500")
        client.should_commit.return_value = True
        m = make_manager(client)
        try:
            boundary(m)
            assert m.rebalance_fraction() == 0.75
            assert m.participant_slot()[2] == 0.75
            mx = m.metrics()
            assert mx["rebalance_fraction"] == 0.75
            assert mx["rebalance_adoptions_total"] == 1
            # Absent from the table -> back to the uniform share.
            client.quorum.return_value = quorum_result(
                rebalance_table="")
            boundary(m)
            assert m.rebalance_fraction() == 1.0
            assert m.metrics()["rebalance_adoptions_total"] == 2
            events = [e["event"] for e in m.history()]
            assert events.count("rebalance_adopt") == 2
        finally:
            m.shutdown()

    def test_absent_table_field_is_inert_not_a_restore(self):
        """Tri-state hint: a pre-rebalance lighthouse (no table
        attribute at all) must never read as a restore-everyone order —
        the stored table only refreshes on a STRING."""
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            rebalance_table="inert=0.7500")
        client.should_commit.return_value = True
        m = make_manager(client, replica_id="inert")
        try:
            boundary(m)
            assert m.rebalance_fraction() == 0.75
            q = quorum_result()
            q.rebalance_table = None  # duck-typed old control plane
            client.quorum.return_value = q
            boundary(m)
            assert m.rebalance_fraction() == 0.75  # sticky, no restore
        finally:
            m.shutdown()

    def test_refusal_defers_then_lands_next_boundary(self):
        """save_durable's refusal classes apply: an errored boundary
        counts rebalance_deferred_total and the retry lands at the next
        clean boundary (the table re-reads every round — nothing is
        lost)."""
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            rebalance_table="defer=0.6250")
        client.should_commit.return_value = False
        m = make_manager(client, replica_id="defer")
        try:
            m.step()
            m.allreduce({"g": np.ones(4, np.float32)}).result()
            m.report_error(RuntimeError("injected step error"))
            m.should_commit()
            assert m.rebalance_fraction() == 1.0
            mx = m.metrics()
            assert mx["rebalance_deferred_total"] == 1
            assert mx["rebalance_adoptions_total"] == 0
            # The error clears at the next step(); adoption retries.
            client.should_commit.return_value = True
            boundary(m)
            assert m.rebalance_fraction() == 0.625
            assert m.metrics()["rebalance_adoptions_total"] == 1
            events = [e["event"] for e in m.history()]
            assert "rebalance_deferred" in events
        finally:
            m.shutdown()

    def test_out_of_bounds_entries_never_adopt(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            rebalance_table="clamp=0.2500")  # below the floor
        client.should_commit.return_value = True
        m = make_manager(client, replica_id="clamp")
        try:
            boundary(m)
            assert m.rebalance_fraction() == 1.0
            assert m.metrics()["rebalance_adoptions_total"] == 0
        finally:
            m.shutdown()

    def _pair(self, store, decider_table):
        """Two coordinated managers over a fake quorum store — the
        policy-coordination harness with the rebalance key."""
        ms = []
        for rank in range(2):
            client = mock_client(quorum_result(
                store_address=FAKE_STORE_ADDR, max_rank=rank,
                replica_rank=rank,
                rebalance_table=decider_table if rank == 0 else ""))
            m = make_manager(client,
                             comm=DummyCommunicator(world_size=2),
                             replica_id=f"reb{rank}", store=store)
            ms.append(m)
        return ms

    def test_decider_publishes_follower_adopts(self):
        """The decider-publishes/all-adopt protocol: only the decider's
        lighthouse hint carries the table, yet the follower lands its
        own entry via the store read — cross-group lockstep without a
        new RPC."""
        store = FakeStore()
        ms = self._pair(store, decider_table="reb1=0.8750")
        try:
            for m in ms:
                boundary(m)
            assert store.kv[_REBALANCE_KEY] == b"1:reb1=0.8750"
            assert ms[1].rebalance_fraction() == 0.875
            # The decider itself is absent from the table: stays 1.0.
            assert ms[0].rebalance_fraction() == 1.0
        finally:
            for m in ms:
                m.shutdown()

    def test_follower_never_publishes(self):
        store = FakeStore()
        ms = self._pair(store, decider_table="reb1=0.8750")
        try:
            boundary(ms[1])  # follower first: nothing published yet
            assert _REBALANCE_KEY not in store.kv
            assert ms[1].rebalance_fraction() == 1.0  # read had no key
        finally:
            for m in ms:
                m.shutdown()

    def test_failed_read_adopts_nothing(self):
        """Stale-but-consistent beats a torn default: when the
        coordinated read fails, the boundary adopts NOTHING — not the
        local hint copy, not 1.0."""
        store = BrokenStore()
        ms = self._pair(store, decider_table="")
        try:
            # The follower's own hint says shrink; the coordinated read
            # is authoritative and it failed -> no adoption either way.
            ms[1]._client.quorum.return_value = quorum_result(
                store_address=FAKE_STORE_ADDR, max_rank=1, replica_rank=1,
                rebalance_table="reb1=0.5000")
            for m in ms:
                boundary(m)
            assert ms[1].rebalance_fraction() == 1.0
            assert ms[1].metrics()["rebalance_adoptions_total"] == 0
        finally:
            for m in ms:
                m.shutdown()

    def test_composed_capacity_times_rebalance(self):
        """Degraded capacity and the rebalance share compose
        multiplicatively in the ONE atomic snapshot the sampler draws
        by, and the fallback wire weight encodes the same product."""
        client = MagicMock()
        client.quorum.return_value = quorum_result(
            rebalance_table="compose=0.7500")
        client.should_commit.return_value = True
        m = make_manager(client, replica_id="compose",
                         degraded_mode=True)
        try:
            boundary(m)
            assert m.request_degrade(0.5, reason="test")
            rank, _committed, frac = m.participant_slot()
            assert rank == 0
            assert frac == pytest.approx(0.375)
            assert m._share.wire_weight() == round(0.375 * 10_000)
            m.set_step_samples(24)  # the sampler's exact draw wins
            assert m._share.wire_weight() == 24
        finally:
            m.shutdown()

    def test_digest_stamps_in_force_fraction_one_boundary_lag(self):
        """The digest's rebalance_fraction is the fraction the measured
        step actually RAN under: an adoption at boundary k is stamped
        from boundary k+1 on — stamping the live value would
        mis-normalize the just-measured wall and flap the ladder."""

        class _Capture:
            def __init__(self):
                self.calls = []

            def set_status(self, *a, **k):
                pass

            def set_digest(self, **kw):
                self.calls.append(kw)

            def lighthouse_redials(self):  # metrics() reads this
                return 0

            def shutdown(self):
                pass

        client = MagicMock()
        client.quorum.return_value = quorum_result(rebalance_table="")
        client.should_commit.return_value = True
        m = make_manager(client, replica_id="digest",
                         fleet_telemetry=True)
        cap = _Capture()
        m._manager_server = cap
        try:
            boundary(m)  # first boundary: no wall to report yet
            client.quorum.return_value = quorum_result(
                rebalance_table="digest=0.7500")
            boundary(m)  # adoption lands AFTER this boundary's wall
            boundary(m)
            assert [c["rebalance_fraction"] for c in cap.calls] \
                == [1.0, 0.75]
            assert all(c["step"] >= 1 for c in cap.calls)
        finally:
            m.shutdown()

    def test_digest_typeerror_ladder_keeps_older_servers_working(self):
        """Control planes predating each digest field generation keep
        receiving digests: the TypeError retry ladder drops ram_peers
        first (still unplumbed in the C bridge), then the rebalance
        fraction, then attestation."""

        class _PreRam:
            def __init__(self):
                self.calls = []

            def set_status(self, *a, **k):
                pass

            def set_digest(self, **kw):
                if "ram_peers" in kw:
                    raise TypeError("unexpected ram_peers")
                self.calls.append(kw)

            def lighthouse_redials(self):  # metrics() reads this
                return 0

            def shutdown(self):
                pass

        class _PreRebalance(_PreRam):
            def set_digest(self, **kw):
                if "ram_peers" in kw or "rebalance_fraction" in kw:
                    raise TypeError("pre-rebalance server")
                self.calls.append(kw)

        for server, has_frac in ((_PreRam(), True),
                                 (_PreRebalance(), False)):
            client = MagicMock()
            client.quorum.return_value = quorum_result()
            client.should_commit.return_value = True
            m = make_manager(client, replica_id="ladder",
                             fleet_telemetry=True)
            m._manager_server = server
            try:
                boundary(m)
                boundary(m)
                assert server.calls, type(server).__name__
                assert ("rebalance_fraction" in server.calls[0]) \
                    == has_frac
                assert "state_digest" in server.calls[0]
            finally:
                m.shutdown()


# ------------------------------------------------ ElasticSampler draws


class _FakeSlot:
    """Duck-typed manager for the sampler: one atomic slot snapshot,
    recording every reported fold weight."""

    def __init__(self, rank=0, committed=0, frac=1.0, degraded=False):
        self.rank, self.committed, self.frac = rank, committed, frac
        self._degraded = degraded
        self.reported = []

    def participant_slot(self):
        return (self.rank, self.committed, self.frac)

    def set_step_samples(self, n):
        self.reported.append(n)

    def degraded_mode(self):
        return self._degraded


class TestSamplerFractions:
    def test_shrunken_draw_reports_weight_without_degraded_mode(self):
        """The ISSUE's decouple regression: a rebalance-shrunken draw
        (fraction < 1, degraded mode OFF) must still report its exact
        sample count — gating on the degraded probe alone would leave
        the fold weight silently at full batch."""
        mgr = _FakeSlot(frac=0.75, degraded=False)
        s = ElasticSampler(64, mgr, batch_size=8, seed=3)
        idx = s.next_indices()
        assert len(idx) == 6  # round(8 * 0.75)
        assert mgr.reported == [6]

    def test_full_fraction_outside_degraded_mode_skips_report(self):
        mgr = _FakeSlot(frac=1.0, degraded=False)
        s = ElasticSampler(64, mgr, batch_size=8)
        assert len(s.next_indices()) == 8
        assert mgr.reported == []

    def test_degraded_mode_full_draw_still_reports(self):
        mgr = _FakeSlot(frac=1.0, degraded=True)
        s = ElasticSampler(64, mgr, batch_size=8)
        s.next_indices()
        assert mgr.reported == [8]

    def test_boost_draws_into_neighbor_slot_prefix(self):
        """A boosted group (fraction > 1) absorbs the straggler's
        trimmed slice by drawing past its slot boundary: the overflow
        is exactly the NEXT slot's prefix, so the fleet sample total
        is conserved (the neighbor re-visits those few samples — the
        documented with-replacement perturbation)."""
        mgr = _FakeSlot(frac=1.25)
        s = ElasticSampler(64, mgr, batch_size=8, seed=5)
        idx = s.next_indices()
        assert len(idx) == 10
        perm = s._perm(0)
        np.testing.assert_array_equal(idx, perm[:10])
        neighbor = s.indices_for_slot(1)
        np.testing.assert_array_equal(idx[8:], neighbor[:2])
        assert mgr.reported == [10]

    def test_draw_truncates_at_epoch_edge(self):
        s = ElasticSampler(64, _FakeSlot(), batch_size=8)
        # Last slot of the epoch: the boost has nowhere to overflow.
        assert len(s.indices_for_slot(7, 1.25)) == 8
        assert len(s.indices_for_slot(7, 0.5)) == 4

    def test_reports_samples_truth_table(self):
        class NoReport:
            pass

        class NoProbe:
            set_step_samples = staticmethod(lambda n: None)

        assert not _reports_samples(NoReport(), 0.5)
        assert _reports_samples(NoProbe(), 1.0)  # test doubles: always
        mgr = _FakeSlot(degraded=False)
        assert _reports_samples(mgr, 0.75)
        assert _reports_samples(mgr, 1.1667)  # boost reports too
        assert not _reports_samples(mgr, 1.0)
        mgr_deg = _FakeSlot(degraded=True)
        assert _reports_samples(mgr_deg, 1.0)


# --------------------------------------------------- chaos `slow:` band


class TestChaosSlowBand:
    def teardown_method(self):
        chaos.reset()

    def test_spec_parses_slow_fields(self):
        sched = chaos.parse_spec(
            "seed=7;slow:slow_rate=1.0,slow_factor=3.0")
        cfg = sched.config_for("slow:anygroup")
        assert cfg.slow_rate == 1.0 and cfg.slow_factor == 3.0

    def test_no_config_no_decision_draw_stream_purity(self):
        """Like the sdc band: with no `slow` channel configured the
        hook returns 1.0 WITHOUT drawing a decision, so existing
        channels' traces are byte-identical whether or not the caller
        polls the slow band."""
        sched = chaos.parse_spec("seed=1;serve:reset_rate=0.5")
        assert chaos.slow_fault("slow:g0", sched) == 1.0
        assert "slow" not in sched._counts
        assert chaos.slow_fault("slow:g0") == 1.0  # nothing installed

    def test_persistent_straggler_every_boundary(self):
        sched = chaos.parse_spec(
            "seed=2;slow:slow_rate=1.0,slow_factor=2.5")
        got = [chaos.slow_fault("slow:g0", sched) for _ in range(8)]
        assert got == [2.5] * 8

    def test_deterministic_per_seed(self):
        mk = lambda: chaos.parse_spec(  # noqa: E731
            "seed=9;slow:slow_rate=0.5,slow_factor=2.0")
        a, b = mk(), mk()
        seq_a = [chaos.slow_fault("slow:g0", a) for _ in range(40)]
        seq_b = [chaos.slow_fault("slow:g0", b) for _ in range(40)]
        assert seq_a == seq_b
        assert set(seq_a) == {1.0, 2.0}

    def test_intensity_scales_rate_not_factor(self):
        """The PhasedChaos knob: intensity 0 mints no stretch (the
        stable phases), intensity 1 restores the configured rate —
        while slow_factor is a multiplier and never scales."""
        sched = chaos.parse_spec(
            "seed=3;slow:slow_rate=1.0,slow_factor=2.0")
        sched.set_intensity(0.0)
        assert all(chaos.slow_fault("slow:g0", sched) == 1.0
                   for _ in range(10))
        sched.set_intensity(1.0)
        assert chaos.slow_fault("slow:g0", sched) == 2.0

    def test_factor_below_one_clamps_to_no_stretch(self):
        sched = chaos.parse_spec(
            "seed=4;slow:slow_rate=1.0,slow_factor=0.25")
        assert chaos.slow_fault("slow:g0", sched) == 1.0

    def test_manager_hook_stretches_natural_wall(self):
        """step()'s injection point: a participant under a slow_rate=1
        schedule sleeps (factor-1) x the natural boundary wall — and
        subtracts its OWN prior injection from the measured wall, so
        the stretch converges instead of compounding (at factor >= 2
        the naive spelling diverges)."""
        chaos.install(chaos.parse_spec(
            "seed=5;slow:slow_rate=1.0,slow_factor=3.0"))
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        m = make_manager(client, replica_id="slowmgr")
        try:
            boundary(m)  # establishes participation + the prev stamp
            time.sleep(0.05)
            t0 = time.monotonic()
            m._chaos_slow.at_step_edge(True)
            slept = time.monotonic() - t0
            first = m._chaos_slow.injected
            assert first >= 0.05  # ~2x the ~0.05 s natural wall
            assert slept >= first * 0.9
            # Immediately again: the wall is almost all injected sleep,
            # so the natural remainder — and the new injection — is
            # tiny (convergence, not compounding).
            m._chaos_slow.at_step_edge(True)
            assert m._chaos_slow.injected < first * 0.5
        finally:
            m.shutdown()
            chaos.reset()

    def test_manager_hook_participants_only_no_draw(self):
        """A healer/spare contributes no wall the Rebalancer reads, so
        it must not sleep — and must not draw either (stream purity
        for the shared channel)."""
        sched = chaos.parse_spec(
            "seed=6;slow:slow_rate=1.0,slow_factor=4.0")
        chaos.install(sched)
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        m = make_manager(client, replica_id="benched")
        try:
            boundary(m)
            with m._metrics_lock:
                m._healing = False
            m._participating_rank = None  # benched spare
            draws_before = sched._counts.get("slow", 0)
            time.sleep(0.02)
            m._chaos_slow.at_step_edge(True)
            assert m._chaos_slow.injected == 0.0
            assert sched._counts.get("slow", 0) == draws_before
        finally:
            m.shutdown()
            chaos.reset()


# ------------------------------------- composed-fraction weighted fold


class TestComposedFractionFold:
    """The acceptance gate's numeric half: the wire-v4 weighted fold
    at rebalance-composed weights is BITWISE identical on every rank
    to the single-process oracle — zero new wire format, the same
    ring, just the draws as weights."""

    @pytest.mark.parametrize("world,fracs", [
        (2, [1.1667, 0.375]),        # boost x (degrade 0.5 x reb 0.75)
        (3, [1.1667, 0.5, 1.0]),     # floor straggler + boost + plain
        (4, [1.1667, 1.1667, 0.5, 1.1667]),  # the parity-trace fleet
    ])
    def test_bitwise_matches_oracle_at_composed_weights(self, world,
                                                        fracs):
        batch = 48
        weights = [int(round(batch * f)) for f in fracs]
        rng = np.random.default_rng(world)
        xs = [rng.normal(size=10_007).astype(np.float32)
              for _ in range(world)]
        out, errors = _run_ring(
            world, lambda c, ring, r: c._do_allreduce_wire(
                ring, [xs[r].copy()], [np.dtype(np.float32)], "sum",
                "step", weights[r]))
        assert not errors, errors
        expected = weighted_oracle(xs, weights)
        for o in out:
            np.testing.assert_array_equal(o[0], expected)


# ------------------------------------------------- aggregator coupling


class TestFleetAggregatorRebalance:
    def _drive(self, agg, walls, step):
        for rid in sorted(walls):
            agg.ingest(fleet.StepDigest(replica_id=rid, step=step,
                                        step_wall_ms=walls[rid]))
        return agg.aggregate()

    def test_aggregate_drives_ladder_and_exposes_table(self):
        agg = fleet.FleetAggregator()
        walls = {"a": 100.0, "b": 100.0, "c": 400.0, "d": 100.0}
        for k in range(1, 4):
            out = self._drive(agg, walls, step=k)
        fl = out["fleet"]
        assert fl["rebalance_fractions"]["c"] == 0.875
        assert fl["rebalance_seq"] == 1
        assert fl["rebalance_shrinks_total"] == 1
        assert "c=0.8750" in fl["rebalance_table"]
        by_id = {g["replica_id"]: g for g in out["groups"]}
        assert by_id["c"]["rebalance_fraction"] == 0.875
        assert by_id["a"]["rebalance_fraction"] > 1.0

    def test_healing_digest_ineligible_for_ladder(self):
        agg = fleet.FleetAggregator()
        for k in range(1, 6):
            for rid, wall in (("a", 100.0), ("b", 100.0),
                              ("d", 100.0)):
                agg.ingest(fleet.StepDigest(replica_id=rid, step=k,
                                            step_wall_ms=wall))
            agg.ingest(fleet.StepDigest(replica_id="c", step=k,
                                        step_wall_ms=400.0,
                                        healing=True))
            out = agg.aggregate()
        assert out["fleet"]["rebalance_shrinks_total"] == 0
        assert out["fleet"]["rebalance_table"] == ""

    def test_remove_forgets_fraction_immediately(self):
        """The farewell path: a departed group's slice is gone the same
        round — no ghost deficit keeps boosting the survivors."""
        agg = fleet.FleetAggregator()
        walls = {"a": 100.0, "b": 100.0, "c": 400.0, "d": 100.0}
        for k in range(1, 4):
            self._drive(agg, walls, step=k)
        assert agg.rebalancer.shrinks_total == 1
        agg.remove("c")
        out = self._drive(agg, {"a": 100.0, "b": 100.0, "d": 100.0},
                          step=4)
        assert out["fleet"]["rebalance_fractions"] == {}
        assert out["fleet"]["rebalance_table"] == ""

    def test_reported_fraction_normalizes_the_wall(self):
        """The anti-flap half: once shrunk, the digest reports its
        fraction and the ladder judges wall/fraction — a straggler
        whose RAW wall normalized back to the fleet's stays shrunk
        (no shrink -> restore -> shrink oscillation)."""
        agg = fleet.FleetAggregator()
        walls = {"a": 100.0, "b": 100.0, "c": 400.0, "d": 100.0}
        for k in range(1, 4):
            self._drive(agg, walls, step=k)
        assert agg.rebalancer.fractions()["c"] == 0.875
        seq_after_shrink = agg.rebalancer.seq
        # c now reports 0.875 and its raw wall shrank proportionally:
        # normalized it is still 400 — loud, not quiet. 6+ boundaries
        # at the would-be-restore cadence must NOT restore it.
        for k in range(4, 12):
            for rid in ("a", "b", "d"):
                agg.ingest(fleet.StepDigest(replica_id=rid, step=k,
                                            step_wall_ms=100.0))
            agg.ingest(fleet.StepDigest(
                replica_id="c", step=k, step_wall_ms=400.0 * 0.875,
                rebalance_fraction=0.875))
            agg.aggregate()
        assert agg.rebalancer.restores_total == 0
        assert agg.rebalancer.fractions()["c"] < 0.875  # kept sinking
        assert agg.rebalancer.seq > seq_after_shrink


# ----------------------------------------------- nightly shrink/restore


@pytest.mark.slow
@pytest.mark.nightly
class TestRebalanceSoak:
    """The seeded stable -> storm -> stable acceptance soak, pure
    Python end-to-end: the chaos ``slow:`` band mints a persistent 4x
    straggler for the storm phase (intensity is the PhasedChaos knob,
    driven here by boundary count so the soak is deterministic), the
    real FleetAggregator + Rebalancer walk the ladder down to the
    floor and symmetrically back, with ZERO table changes inside the
    settled stable windows — and the final fold at the storm-peak
    fractions is bitwise against the oracle."""

    def test_storm_shrinks_stable_restores_zero_flap(self):
        sched = chaos.parse_spec(
            "seed=11;slow:slow_rate=1.0,slow_factor=4.0")
        agg = fleet.FleetAggregator()
        base = {"a": 100.0, "b": 100.0, "c": 100.0, "d": 100.0}
        assigned = {rid: 1.0 for rid in base}
        seq_at = {}
        frac_c = {}
        for k in range(1, 121):
            # stable(20) -> storm(40) -> stable(60), by boundary count.
            sched.set_intensity(1.0 if 21 <= k <= 60 else 0.0)
            factor = chaos.slow_fault("slow:c", sched)
            reported = dict(assigned)  # adopted at the last boundary
            for rid in sorted(base):
                stretch = factor if rid == "c" else 1.0
                agg.ingest(fleet.StepDigest(
                    replica_id=rid, step=k,
                    step_wall_ms=base[rid] * reported[rid] * stretch,
                    rebalance_fraction=reported[rid]))
            out = agg.aggregate()
            assigned = {rid: out_g["rebalance_fraction"]
                        for out_g in out["groups"]
                        for rid in [out_g["replica_id"]]}
            seq_at[k] = agg.rebalancer.seq
            frac_c[k] = agg.rebalancer.fractions()["c"]

        # Initial stable phase: a uniform fleet, untouched table.
        assert seq_at[20] == 0 and frac_c[20] == 1.0
        # Storm: c walked to the floor and LATCHED there — no flap in
        # the storm's settled tail.
        assert frac_c[60] == fleet.REBALANCE_FLOOR
        assert seq_at[60] == seq_at[45], "table flapped at the floor"
        assert agg.rebalancer.shrinks_total == 4
        # Final stable phase: symmetric restore, then a settled window
        # with zero table changes, ending uniform.
        assert frac_c[120] == 1.0
        assert agg.rebalancer.restores_total == 4
        assert agg.rebalancer.table == ""
        assert seq_at[120] == seq_at[105], "table flapped after restore"
        # 4 shrinks down + 4 restores up, each a table change, plus the
        # final change back to the empty table: the whole 120-boundary
        # soak moved the fleet exactly 8 times.
        assert seq_at[120] == 8

        # Bitwise fold at the storm-peak fractions (floor + boosts).
        batch = 64
        fracs = [1.1667, 1.1667, 0.5, 1.1667]
        weights = [int(round(batch * f)) for f in fracs]
        rng = np.random.default_rng(11)
        xs = [rng.normal(size=4_099).astype(np.float32)
              for _ in range(4)]
        out, errors = _run_ring(
            4, lambda c, ring, r: c._do_allreduce_wire(
                ring, [xs[r].copy()], [np.dtype(np.float32)], "sum",
                "step", weights[r]))
        assert not errors, errors
        expected = weighted_oracle(xs, weights)
        for o in out:
            np.testing.assert_array_equal(o[0], expected)
