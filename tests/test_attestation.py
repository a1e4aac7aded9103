"""State-attestation tests (docs/design/state_attestation.md).

Tier-1 and native-free (marker ``sdc``, ``scripts/test.sh sdc``): the
device digest kernel frozen against the NumPy reference
(:func:`torchft_tpu.serialization.attest_fingerprint`) across dtypes
plus its trace-time cache tripwire, the pure-Python
:class:`~torchft_tpu.fleet.FleetAggregator` vote (strict majority,
healer/absent/foreign-quorum abstention, sticky latch, the non-voter
clear-on-match, farewell-vs-prune clearing), the satellite-1
read-time staleness bound (a SIGKILLed group ages out of baselines
AND ballots), the ONE shared donor-admission predicate across all
three resolvers (in-quorum healset, pre-join, RAM replication
targets), the Manager quarantine ladder (latch, refusal classes,
serve-gate 503, withdrawn advertisements, verdict-clear rules), the
chaos ``sdc`` band (spec parse, stream purity, intensity/PhasedChaos
composition, determinism, the participants-only injection contract),
and the seeded 3-group soak: one bit flip -> verdict within one
commit boundary -> auto-heal from the attested majority -> bitwise
fleet convergence and a clean latch.

The C++ lighthouse runs the same vote (lighthouse.cc — the mirror
contract); its unit matrix lives in ``_core/core_test.cc`` and the
native parity round rides nightly.
"""

import functools
import urllib.error
import urllib.request
from unittest.mock import MagicMock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mockplane
from mockplane import FAKE_STORE_ADDR, FakeStore
from torchft_tpu import chaos, fleet, serialization
from torchft_tpu.chaos import ChaosSchedule, EndpointChaos
from torchft_tpu.checkpointing import CheckpointServer
from torchft_tpu.fleet import FleetAggregator, StepDigest
from torchft_tpu.manager import (_ATTEST_STATS, _addr_base,
                                 _attest_device_words)
from torchft_tpu.policy import PhasedChaos

pytestmark = pytest.mark.sdc

NOW = 1_000_000  # fixed aggregator clock base (ms)


def mk_digest(rid, step=5, wall=100.0, healing=False, capacity=1.0,
              quorum_id=1, state_digest="", trace_addr=""):
    return StepDigest(replica_id=rid, step=step, step_wall_ms=wall,
                      healing=healing, capacity_fraction=capacity,
                      quorum_id=quorum_id, state_digest=state_digest,
                      trace_addr=trace_addr)


quorum_result = functools.partial(
    mockplane.quorum_result, max_world_size=3, replica_world_size=3)
make_manager = functools.partial(
    mockplane.make_manager, quorum=quorum_result(), min_replica_size=1,
    use_async_quorum=False, replica_id="sdc0",
    state_dict=lambda: {"w": np.arange(8, dtype=np.float32)})


# ------------------------------------------------------- digest kernel


class TestDigestKernel:
    """The jitted device fingerprint is FROZEN against the NumPy
    reference: u32 wraparound arithmetic is associative, so the
    device's per-add wrap and the reference's u64-sum-then-mask must
    agree bit-for-bit on the same bytes."""

    CASES = [
        np.arange(37, dtype=np.float32) * 0.7,
        np.arange(-8, 8, dtype=np.int32),
        np.arange(256, dtype=np.uint8),
        np.array([True, False, True, True]),
    ]

    def _device_digest(self, arrays):
        leaves = [jax.device_put(a) for a in arrays]
        words = np.asarray(_attest_device_words(leaves), dtype=np.uint32)
        return serialization.attest_combine([int(w) for w in words])

    def test_device_matches_numpy_reference(self):
        for a in self.CASES:
            dev = jax.device_put(a)
            # Hash the SAME bytes the device holds (device_put may
            # legitimately canonicalize dtypes, e.g. f64 -> f32).
            ref = serialization.attest_fingerprint([np.asarray(dev)])
            assert self._device_digest([a]) == ref, a.dtype

    def test_bfloat16_leaf(self):
        dev = jnp.arange(33, dtype=jnp.bfloat16) * jnp.bfloat16(0.5)
        words = np.asarray(_attest_device_words([dev]), dtype=np.uint32)
        got = serialization.attest_combine([int(w) for w in words])
        assert got == serialization.attest_fingerprint([np.asarray(dev)])

    def test_multi_leaf_fold_and_order_sensitivity(self):
        a = np.arange(16, dtype=np.float32)
        b = np.arange(9, dtype=np.int32)
        assert self._device_digest([a, b]) == \
            serialization.attest_fingerprint([a, b])
        # Pytree order is part of the fingerprint: swapped leaves must
        # NOT collide (the fold is non-commutative by construction).
        assert self._device_digest([a, b]) != self._device_digest([b, a])

    def test_single_bit_flip_changes_digest(self):
        a = np.arange(64, dtype=np.float32)
        clean = self._device_digest([a])
        for byte, bit in ((0, 0), (17, 3), (255, 7)):
            c = a.copy()
            c.view(np.uint8)[byte] ^= np.uint8(1 << bit)
            assert self._device_digest([c]) != clean, (byte, bit)

    def test_digest_is_32_hex_chars(self):
        d = self._device_digest([np.ones(4, np.float32)])
        assert len(d) == 32
        int(d, 16)  # must parse as hex

    def test_trace_time_cache_tripwire(self):
        """The kernel caches per leaf-signature jit functions; a
        recompile storm (shape-unstable state trees) must show up in
        the sdc_digest_cache_misses counter, which counts COMPILES
        (trace-time bumps), not calls."""
        leaves = [jax.device_put(np.arange(11, dtype=np.float32))]
        _attest_device_words(leaves)  # warm (may or may not compile)
        before = _ATTEST_STATS["sdc_digest_cache_misses"]
        for _ in range(5):
            _attest_device_words(leaves)  # cached: no new trace
        assert _ATTEST_STATS["sdc_digest_cache_misses"] == before
        fresh = [jax.device_put(np.arange(13, dtype=np.float32))]
        _attest_device_words(fresh)  # new signature: exactly one trace
        assert _ATTEST_STATS["sdc_digest_cache_misses"] == before + 1

    def test_manager_digest_host_fallback_matches_reference(self):
        m = make_manager(
            state_dict=lambda: {"w": np.arange(8, dtype=np.float32),
                                "meta": "not-an-array"})
        try:
            got = m._compute_state_digest()
            assert got == serialization.attest_fingerprint(
                [np.arange(8, dtype=np.float32)])
            assert m.metrics()["sdc_digests_total"] == 1.0
            assert m._last_state_digest == got
        finally:
            m.shutdown()

    def test_manager_digest_device_path_matches_host_path(self):
        arr = np.arange(24, dtype=np.float32) * 3.0
        dev = make_manager(state_dict=lambda: {"w": jax.device_put(arr)},
                           replica_id="sdc-dev")
        host = make_manager(state_dict=lambda: {"w": arr},
                            replica_id="sdc-host")
        try:
            assert dev._compute_state_digest() == \
                host._compute_state_digest()
        finally:
            dev.shutdown()
            host.shutdown()

    def test_attestation_off_yields_empty_digest(self):
        m = make_manager(attestation=False)
        try:
            assert m._compute_state_digest() == ""
            assert m.metrics()["sdc_digests_total"] == 0.0
        finally:
            m.shutdown()


# ------------------------------------------------------------ the vote


class TestAttestationVote:
    def _feed(self, agg, rows, now=NOW):
        for rid, kw in rows:
            agg.ingest(mk_digest(rid, **kw), now_ms=now)
        return agg.aggregate(now_ms=now + 10)

    def test_majority_quarantines_the_minority(self):
        agg = FleetAggregator()
        res = self._feed(agg, [
            ("a", dict(state_digest="aaaa")),
            ("b", dict(state_digest="aaaa")),
            ("c", dict(state_digest="cccc",
                       trace_addr="http://c:1/checkpoint/5")),
        ])
        assert sorted(agg.quarantined()) == ["c"]
        rec = agg.quarantined()["c"]
        assert rec["digest"] == "cccc"
        assert rec["majority_digest"] == "aaaa"
        assert rec["quorum_id"] == 1 and rec["step"] == 5
        f = res["fleet"]
        assert f["sdc_quarantined"] == ["c"]
        assert f["sdc_quarantined_addrs"] == ["http://c:1/checkpoint/5"]
        assert f["sdc_verdicts_total"] == 1
        by_id = {g["replica_id"]: g for g in res["groups"]}
        assert by_id["c"]["sdc_diverged"] and not by_id["a"]["sdc_diverged"]
        assert by_id["a"]["attested"]

    def test_fifty_fifty_split_fails_open(self):
        agg = FleetAggregator()
        self._feed(agg, [("a", dict(state_digest="aaaa")),
                         ("b", dict(state_digest="bbbb"))])
        assert agg.quarantined() == {}

    def test_two_vs_two_tie_fails_open(self):
        agg = FleetAggregator()
        self._feed(agg, [("a", dict(state_digest="aaaa")),
                         ("b", dict(state_digest="aaaa")),
                         ("c", dict(state_digest="cccc")),
                         ("d", dict(state_digest="cccc"))])
        assert agg.quarantined() == {}

    def test_healers_never_vote(self):
        """A mid-restore group's transient bytes are legitimately
        different; with the healer abstaining the remaining 2-1 vote
        still convicts the real minority — and a 1-1 remainder fails
        open."""
        agg = FleetAggregator()
        self._feed(agg, [
            ("a", dict(state_digest="aaaa")),
            ("b", dict(state_digest="aaaa")),
            ("h", dict(state_digest="hhhh", healing=True)),
            ("c", dict(state_digest="cccc")),
        ])
        assert sorted(agg.quarantined()) == ["c"]

    def test_absent_digest_and_foreign_quorum_abstain(self):
        agg = FleetAggregator()
        self._feed(agg, [
            ("a", dict(state_digest="aaaa")),
            ("b", dict(state_digest="aaaa")),
            ("n", dict(state_digest="")),           # pre-attestation
            ("q", dict(state_digest="qqqq", quorum_id=-1)),
        ])
        assert agg.quarantined() == {}  # 2 voters agree: no minority

    def test_different_steps_ballot_separately(self):
        """Ballots key on (quorum_id, step): a group one boundary
        behind must not be convicted against a different step's
        digests."""
        agg = FleetAggregator()
        self._feed(agg, [
            ("a", dict(step=5, state_digest="aaaa")),
            ("b", dict(step=5, state_digest="aaaa")),
            ("c", dict(step=4, state_digest="cccc")),
        ])
        assert agg.quarantined() == {}

    def test_verdict_is_sticky_and_counted_once(self):
        agg = FleetAggregator()
        rows = [("a", dict(state_digest="aaaa")),
                ("b", dict(state_digest="aaaa")),
                ("c", dict(state_digest="cccc"))]
        self._feed(agg, rows)
        # Same ballot re-aggregated: latched, not re-counted.
        for _ in range(3):
            self._feed(agg, rows)
        assert sorted(agg.quarantined()) == ["c"]
        assert agg.aggregate(now_ms=NOW + 50)["fleet"][
            "sdc_verdicts_total"] == 1

    def test_nonvoter_clear_on_match(self):
        """THE deadlock fix: a quarantined group reports
        ``healing=True`` (its own latch benched it), so its re-attested
        digest is never a ballot entry — but a fresh digest MATCHING
        the winner for the same ballot must clear it anyway, or the
        quarantine could never end."""
        agg = FleetAggregator()
        self._feed(agg, [("a", dict(state_digest="aaaa")),
                         ("b", dict(state_digest="aaaa")),
                         ("c", dict(state_digest="cccc"))])
        assert sorted(agg.quarantined()) == ["c"]
        res = self._feed(agg, [
            ("a", dict(step=6, state_digest="ffff")),
            ("b", dict(step=6, state_digest="ffff")),
            ("c", dict(step=6, state_digest="ffff", healing=True)),
        ], now=NOW + 1000)
        assert agg.quarantined() == {}
        assert res["fleet"]["sdc_clears_total"] == 1

    def test_still_divergent_reheal_stays_latched(self):
        agg = FleetAggregator()
        self._feed(agg, [("a", dict(state_digest="aaaa")),
                         ("b", dict(state_digest="aaaa")),
                         ("c", dict(state_digest="cccc"))])
        self._feed(agg, [
            ("a", dict(step=6, state_digest="ffff")),
            ("b", dict(step=6, state_digest="ffff")),
            ("c", dict(step=6, state_digest="0bad", healing=True)),
        ], now=NOW + 1000)
        assert sorted(agg.quarantined()) == ["c"]

    def test_farewell_clears_but_prune_does_not(self):
        agg = FleetAggregator()
        self._feed(agg, [("a", dict(state_digest="aaaa")),
                         ("b", dict(state_digest="aaaa")),
                         ("c", dict(state_digest="cccc"))])
        # Dead-without-farewell: rows age past stale_ms and prune out,
        # but the verdict stays — the corpse's last attested state is
        # still the corrupt one, and donor filters must keep excluding
        # its address if a cached copy resurfaces.
        agg.prune(now_ms=NOW + 10_000_000)
        assert sorted(agg.quarantined()) == ["c"]
        # A clean farewell DOES clear: the replacement rejoins behind
        # max_step and heals before it can attest anything.
        agg.remove("c")
        assert agg.quarantined() == {}

    def test_prometheus_exposition_names(self):
        agg = FleetAggregator()
        res = self._feed(agg, [("a", dict(state_digest="aaaa")),
                               ("b", dict(state_digest="aaaa")),
                               ("c", dict(state_digest="cccc"))])
        text = fleet.status_prometheus(res)
        assert "torchft_fleet_sdc_quarantined 1.0" in text
        assert "torchft_fleet_sdc_verdicts_total 1.0" in text


# ------------------------------------- satellite 1: read-time staleness


class TestReadTimeStaleness:
    def _cadenced(self, agg, rid, n, period_ms, t0=NOW, wall=100.0,
                  digest="aaaa", step0=0):
        for i in range(n):
            agg.ingest(mk_digest(rid, step=step0 + i, wall=wall,
                                 state_digest=digest),
                       now_ms=t0 + i * period_ms)
        return t0 + (n - 1) * period_ms

    def test_sigkilled_group_leaves_the_baseline(self):
        """The regression this satellite exists for: a SIGKILLed group
        (no farewell) kept feeding the straggler baseline with its last
        digest for the whole 60 s retention window. With the read-time
        bound (~2.5 median intervals, 2 s floor) it drops out of the
        baseline after ~2 missed boundaries while staying VISIBLE as
        ``stale``."""
        agg = FleetAggregator()
        t_dead = self._cadenced(agg, "dead", 8, 1000, wall=5000.0)
        # The live groups keep stepping well past the dead group.
        for rid in ("a", "b"):
            self._cadenced(agg, rid, 14, 1000, wall=100.0)
        now = t_dead + 6000  # 6 missed 1 s boundaries, well under 60 s
        res = agg.aggregate(now_ms=now)
        by_id = {g["replica_id"]: g for g in res["groups"]}
        assert by_id["dead"]["straggler_stage"] == "stale"
        assert not by_id["dead"]["baseline"]
        assert res["fleet"]["baseline_groups"] == 2
        # The huge dead wall must not crown the straggler.
        assert res["straggler"]["replica_id"] != "dead"

    def test_sparse_ring_falls_back_to_stale_ms(self):
        """Fewer than 2 observed intervals = no cadence estimate: the
        row stays baseline-eligible up to the hard stale_ms cut."""
        agg = FleetAggregator()
        agg.ingest(mk_digest("one", state_digest="aaaa"), now_ms=NOW)
        res = agg.aggregate(now_ms=NOW + 30_000)  # old, but < stale_ms
        assert res["groups"][0]["baseline"]

    def test_stale_rows_do_not_vote(self):
        """A dead group's divergent last digest must not convict it (or
        anyone): votes draw from FRESH rows only."""
        agg = FleetAggregator()
        self._cadenced(agg, "dead", 8, 1000, digest="dddd")
        t = self._cadenced(agg, "a", 14, 1000, digest="aaaa", step0=0)
        self._cadenced(agg, "b", 14, 1000, digest="aaaa", step0=0)
        # At now, dead's step-7 row is stale; a/b's step-13 rows are
        # fresh and unanimous. No ballot convicts dead.
        agg.aggregate(now_ms=t + 500)
        assert agg.quarantined() == {}

    def test_attested_flag_drops_with_freshness(self):
        agg = FleetAggregator()
        self._cadenced(agg, "dead", 8, 1000)
        for rid in ("a", "b"):
            self._cadenced(agg, rid, 14, 1000)
        res = agg.aggregate(now_ms=NOW + 13_500)
        by_id = {g["replica_id"]: g for g in res["groups"]}
        assert not by_id["dead"]["attested"]
        assert by_id["a"]["attested"]


# --------------------------- satellite 2: the shared donor predicate


class TestDonorAdmission:
    def _quarantine_bases(self, m, *bases):
        with m._metrics_lock:
            m._sdc_quarantined_bases = {_addr_base(b) for b in bases}

    def test_predicate_rules(self):
        m = make_manager()
        try:
            ok = "http://live:1/checkpoint/3"
            assert m._donor_admissible(ok)
            assert not m._donor_admissible("")
            assert not m._donor_admissible("", step_s="-1")
            assert not m._donor_admissible(ok, step_s="-1")
            assert not m._donor_admissible(ok, step_s="")
            assert not m._donor_admissible(ok, step_s="2", max_step=3)
            assert m._donor_admissible(ok, step_s="3", max_step=3)
            self._quarantine_bases(m, "http://live:1/checkpoint/9")
            # Base matching: ANY step suffix of a quarantined server is
            # inadmissible, and the ramckpt spelling too.
            assert not m._donor_admissible(ok, step_s="3", max_step=3)
            assert not m._donor_admissible("http://live:1/ramckpt/img")
            assert m._donor_admissible("http://other:1/checkpoint/3",
                                       step_s="3", max_step=3)
        finally:
            m.shutdown()

    def test_healset_donors_filter_quarantined(self):
        store = FakeStore()
        store.set("torchft/healset/1", b"3:http://bad:1/checkpoint/3")
        store.set("torchft/healset/2", b"3:http://live:1/checkpoint/3")
        m = make_manager(store=store)
        self._quarantine_bases(m, "http://bad:1")
        try:
            q = quorum_result(store_address=FAKE_STORE_ADDR, max_step=3)
            donors = m._healset_donors(q, "http://primary:1/checkpoint/3")
            assert donors == ["http://primary:1/checkpoint/3",
                              "http://live:1/checkpoint/3"]
        finally:
            m.shutdown()

    def test_ram_peer_bases_filter_quarantined_and_tombstoned(self):
        store = FakeStore()
        store.set("torchft/healset/1", b"-1:")  # withdrawn
        store.set("torchft/healset/2", b"4:http://bad:1/checkpoint/4")
        store.set("torchft/healset/3", b"4:http://live:1/checkpoint/4")
        m = make_manager(store=store)
        m._last_round_facts = (FAKE_STORE_ADDR, 0, 4, 4)
        self._quarantine_bases(m, "http://bad:1")
        try:
            assert m._ram_peer_bases() == ["http://live:1"]
        finally:
            m.shutdown()

    def test_resolve_checkpoint_addr_raises_on_quarantined_donor(self):
        m = make_manager()
        self._quarantine_bases(m, "http://bad:1")
        try:
            with MagicMock() as _:
                pass
            import torchft_tpu.manager as manager_mod
            real_client = manager_mod.ManagerClient
            fake = MagicMock()
            fake.return_value.checkpoint_address.return_value = \
                "http://bad:1/checkpoint/7"
            manager_mod.ManagerClient = fake
            try:
                with pytest.raises(RuntimeError, match="quarantined"):
                    m._resolve_checkpoint_addr("bad-manager:1")
            finally:
                manager_mod.ManagerClient = real_client
        finally:
            m.shutdown()


# -------------------------------------------- Manager quarantine ladder


class TestQuarantineLadder:
    def _verdict(self, sd, rids="", addrs=""):
        return quorum_result(sdc_diverged=sd, sdc_quarantined=rids,
                             sdc_quarantined_addrs=addrs)

    def test_latch_enters_the_full_ladder(self):
        store = FakeStore()
        store.set("torchft/healset/0", b"1:http://me:1/checkpoint/1")
        m = make_manager(store=store)
        m._last_round_facts = (FAKE_STORE_ADDR, 0, 3, 3)
        m._flight = MagicMock()
        try:
            m._consume_fleet_hint(self._verdict(
                True, rids="sdc0",
                addrs="http://me:1/checkpoint/1"))
            assert m._sdc_quarantined
            assert not m.is_participating()  # zero-weight fold
            assert m._share.wire_weight() == 0
            # Advertisement withdrawn with the PR 14 tombstone.
            assert store.kv["torchft/healset/0"] == b"-1:"
            mx = m.metrics()
            assert mx["sdc_quarantined"] == 1.0
            assert mx["sdc_quarantines_total"] == 1.0
            assert m._flight.dump.call_args[0][0] == "sdc_divergence"
            events = [e["event"] for e in m.history()]
            assert "sdc_divergence" in events
            # The fleet lists landed for the donor filters.
            assert "sdc0" in m._sdc_quarantined_peers
            assert "http://me:1" in m._sdc_quarantined_bases
        finally:
            m.shutdown()

    def test_checkpoint_serve_gate_503(self):
        srv = CheckpointServer(
            lambda: {"user": {"w": np.ones(4, np.float32)},
                     "torchft": {"step": 1}},
            bind_host="127.0.0.1")
        try:
            srv.allow_checkpoint(1)
            addr = srv.address()
            assert urllib.request.urlopen(addr, timeout=10).status == 200
            srv.set_quarantined(True)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(addr, timeout=10)
            assert ei.value.code == 503
            srv.set_quarantined(False)
            assert urllib.request.urlopen(addr, timeout=10).status == 200
        finally:
            srv.shutdown()

    def test_absent_verdict_field_is_inert(self):
        """Duck-typed / pre-attestation control planes carry NO
        sdc_diverged attribute: neither a latch nor an all-clear."""
        m = make_manager()
        try:
            class Bare:
                pass

            m._consume_sdc_verdict(Bare())
            assert not m._sdc_quarantined
            with m._metrics_lock:
                m._sdc_quarantined = True
            m._consume_sdc_verdict(Bare())
            assert m._sdc_quarantined  # an old lighthouse never clears
        finally:
            m.shutdown()

    def test_clear_deferred_while_heal_in_flight(self):
        m = make_manager()
        try:
            m._consume_fleet_hint(self._verdict(True, rids="sdc0"))
            assert m._sdc_quarantined
            with m._metrics_lock:
                m._healing = True
            m._consume_fleet_hint(self._verdict(False))
            assert m._sdc_quarantined  # mid-heal all-clear must wait
            with m._metrics_lock:
                m._healing = False
            m._consume_fleet_hint(self._verdict(False))
            assert not m._sdc_quarantined
            assert m.is_participating()
            assert m.metrics()["sdc_quarantine_clears_total"] == 1.0
            events = [e["event"] for e in m.history()]
            assert "sdc_quarantine_clear" in events
        finally:
            m.shutdown()

    def test_reheal_with_no_admissible_donor_stays_latched(self):
        """Every advertised donor quarantined/tombstoned and no
        resolvable primary: stay zero-weighted and retry next boundary
        — healing from nothing beats healing from divergent bytes."""
        store = FakeStore()
        store.set("torchft/healset/1", b"-1:")
        store.set("torchft/healset/2", b"1:http://bad:1/checkpoint/1")
        m = make_manager(store=store)
        with m._metrics_lock:
            m._sdc_quarantined = True
            m._sdc_quarantined_bases = {"http://bad:1"}
        try:
            m._sdc_reheal(quorum_result(
                recover_manager_address="",
                store_address=FAKE_STORE_ADDR))
            assert m._sdc_quarantined
            assert m._pending_state_dict is None
            assert m.metrics()["sdc_reheals_total"] == 1.0
            assert m.metrics()["heal_count"] == 0.0  # no fetch started
        finally:
            m.shutdown()


# ----------------------------------- satellite 3: the chaos ``sdc`` band


class TestChaosSdcBand:
    def test_spec_parses_via_torchft_chaos_grammar(self):
        sched = chaos.parse_spec(
            "seed=7;sdc:sdc_flip_rate=0.25,max_faults=3")
        cfg = sched.config_for("sdc:g0")
        assert cfg is not None and cfg.sdc_flip_rate == 0.25
        assert cfg.max_faults == 3
        assert sched.config_for("ring:0") is None

    def test_env_activation(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_CHAOS", "seed=3;sdc:sdc_flip_rate=1.0")
        chaos.reset()
        try:
            d = chaos.sdc_fault("sdc:g0")
            assert d is not None and d.fault == "sdc_flip"
        finally:
            chaos.reset()

    def test_stream_purity_without_config(self):
        """No config for the sdc channel = NO decision draw: every
        other channel's fault sequence stays byte-identical with the
        band absent."""
        sched = ChaosSchedule(seed=1, endpoints={
            "ring": EndpointChaos(reset_rate=0.5)})
        assert chaos.sdc_fault("sdc:g0", schedule=sched) is None
        assert "sdc" not in sched._counts  # no stream was even opened

    def test_decision_determinism(self):
        mk = lambda: ChaosSchedule(seed=11, endpoints={  # noqa: E731
            "sdc": EndpointChaos(sdc_flip_rate=0.5)})
        a, b = mk(), mk()
        seq_a = [chaos.sdc_fault("sdc:g0", schedule=a) for _ in range(40)]
        seq_b = [chaos.sdc_fault("sdc:g0", schedule=b) for _ in range(40)]
        assert [(d.n, d.frac) if d else None for d in seq_a] == \
            [(d.n, d.frac) if d else None for d in seq_b]
        assert any(seq_a) and not all(seq_a)  # 0.5: mixed outcomes

    def test_intensity_scales_and_phased_chaos_composes(self):
        sched = ChaosSchedule(seed=5, endpoints={
            "sdc": EndpointChaos(sdc_flip_rate=1.0)})
        sched.set_intensity(0.0)  # storm over: rate 1.0 never fires
        assert all(chaos.sdc_fault("sdc:g0", schedule=sched) is None
                   for _ in range(20))
        # PhasedChaos drives the same knob: a terminal storm phase of
        # intensity 1.0 re-arms the band with no sdc-specific plumbing.
        PhasedChaos(sched, ((0.0, 0.0), (1000.0, 1.0))).tick()
        assert sched.intensity() == 1.0
        assert any(chaos.sdc_fault("sdc:g0", schedule=sched)
                   for _ in range(20))

    def test_max_faults_caps_the_band(self):
        sched = ChaosSchedule(seed=9, endpoints={
            "sdc": EndpointChaos(sdc_flip_rate=1.0, max_faults=1)})
        fired = [chaos.sdc_fault("sdc:g0", schedule=sched)
                 for _ in range(10)]
        assert sum(1 for d in fired if d) == 1

    def test_never_fires_on_a_healer_or_quarantined_group(self):
        """The injection contract: post-commit state, participants
        only. The Manager hook must not even DRAW while healing or
        latched — a flip there would corrupt a transient restore and
        model a fault the vote deliberately abstains on."""
        sched = ChaosSchedule(seed=1, endpoints={
            "sdc": EndpointChaos(sdc_flip_rate=1.0)})
        chaos.install(sched)
        m = make_manager()
        try:
            with m._metrics_lock:
                m._healing = True
            m._chaos_sdc.at_step_edge(True)
            assert m.metrics()["sdc_chaos_flips_total"] == 0.0
            with m._metrics_lock:
                m._healing = False
                m._sdc_quarantined = True
            m._chaos_sdc.at_step_edge(True)
            assert m.metrics()["sdc_chaos_flips_total"] == 0.0
            assert "sdc" not in sched._counts  # guarded before the draw
            with m._metrics_lock:
                m._sdc_quarantined = False
            m._chaos_sdc.at_step_edge(True)  # a participant DOES flip
            assert m.metrics()["sdc_chaos_flips_total"] == 1.0
        finally:
            chaos.uninstall()
            m.shutdown()

    def test_flip_is_deterministic_and_single_bit(self):
        cell = {"w": np.arange(64, dtype=np.float32)}
        m = make_manager(state_dict=lambda: cell)
        m._user_load_state_dict = lambda s: (cell.clear(), cell.update(s))
        try:
            clean = cell["w"].copy()
            m._chaos_sdc.flip(0.37)
            diff = cell["w"].view(np.uint8) ^ clean.view(np.uint8)
            changed = np.nonzero(diff)[0]
            assert changed.size == 1  # exactly one byte...
            assert bin(int(diff[changed[0]])).count("1") == 1  # ...one bit
            # Pure function of frac: the same draw reproduces the flip.
            cell["w"] = clean.copy()
            m._chaos_sdc.flip(0.37)
            assert np.array_equal(cell["w"].view(np.uint8) ^
                                  clean.view(np.uint8), diff)
        finally:
            m.shutdown()

    def test_flip_changes_the_digest(self):
        cell = {"w": np.arange(64, dtype=np.float32)}
        m = make_manager(state_dict=lambda: cell)
        m._user_load_state_dict = lambda s: (cell.clear(), cell.update(s))
        try:
            clean = m._compute_state_digest()
            m._chaos_sdc.flip(0.5)
            assert m._compute_state_digest() != clean
        finally:
            m.shutdown()


# ------------------------------------------------- the 3-group sdc soak


class SdcSoakHarness:
    """Three sync-mode Managers against a pure-Python lighthouse
    (:class:`FleetAggregator`): every round each group steps, then its
    committed-state digest is ingested exactly as the piggyback would
    carry it, and the NEXT round's quorum hints echo the aggregate's
    verdict lists — the full detection -> quarantine -> auto-heal ->
    clear loop with the real Manager and real checkpoint HTTP donors,
    no native toolchain."""

    RIDS = ("g0", "g1", "g2")

    def __init__(self):
        self.store = FakeStore()
        self.agg = FleetAggregator()
        self.now = NOW
        self.cells, self.mgrs, self.clients = {}, {}, {}
        self.verdicts = {}
        for i, rid in enumerate(self.RIDS):
            cell = {"w": np.arange(64, dtype=np.float32).copy(),
                    "b": np.ones(7, dtype=np.float32)}
            self.cells[rid] = cell
            client = MagicMock()
            client.quorum.return_value = self._qr(i, 1)
            client.should_commit.return_value = True
            self.clients[rid] = client
            m = make_manager(client=client, replica_id=rid,
                             state_dict=lambda _c=cell: _c,
                             store=self.store)
            m._user_load_state_dict = \
                lambda s, _c=cell: (_c.clear(), _c.update(s))
            self.mgrs[rid] = m

    def _qr(self, rank, step, **kw):
        return quorum_result(store_address=FAKE_STORE_ADDR, max_step=step,
                             max_rank=2, replica_rank=rank, **kw)

    def round(self, r):
        """One commit boundary across the fleet; returns the aggregate."""
        rids = ",".join(sorted(self.verdicts))
        addrs = ",".join(sorted(
            {rec.get("trace_addr", "")
             for rec in self.verdicts.values() if rec.get("trace_addr")}))
        for i, rid in enumerate(self.RIDS):
            self.clients[rid].quorum.return_value = self._qr(
                i, r, sdc_diverged=rid in self.verdicts,
                sdc_quarantined=rids, sdc_quarantined_addrs=addrs)
        # step() first for ALL groups: a quarantined group's re-heal
        # fetches from peers whose serve windows are open mid-step —
        # the same concurrency the async fleet has.
        for rid in self.RIDS:
            self.mgrs[rid].step()
        for rid in self.RIDS:
            m = self.mgrs[rid]
            if m.is_participating():
                m.allreduce({"g": np.ones(4, np.float32)}).result()
                m.should_commit()
        for rid in self.RIDS:
            m = self.mgrs[rid]
            self.agg.ingest(
                mk_digest(rid, step=r, state_digest=m._compute_state_digest(),
                          healing=bool(m._healing
                                       or not m.is_participating()),
                          trace_addr=m._ckpt_server.address()),
                now_ms=self.now)
        self.now += 1000
        res = self.agg.aggregate(now_ms=self.now)
        self.verdicts = self.agg.quarantined()
        return res

    def metrics(self, rid):
        return self.mgrs[rid].metrics()

    def bitwise_converged(self):
        ref = self.cells[self.RIDS[0]]
        return all(
            np.array_equal(ref[k], self.cells[rid][k])
            for rid in self.RIDS[1:] for k in ref)

    def shutdown(self):
        for m in self.mgrs.values():
            m.shutdown()


class TestSdcSoak:
    def _run(self, rounds, seed=42, max_faults=1, rate=1.0):
        h = SdcSoakHarness()
        sched = ChaosSchedule(seed=seed, endpoints={
            "sdc:g2": EndpointChaos(sdc_flip_rate=rate,
                                    max_faults=max_faults)})
        chaos.install(sched)
        timeline = []
        try:
            for r in range(1, rounds + 1):
                h.round(r)
                timeline.append(dict(
                    round=r,
                    flips=h.metrics("g2")["sdc_chaos_flips_total"],
                    verdicts=sorted(h.verdicts),
                    latched=h.mgrs["g2"]._sdc_quarantined,
                    reheals=h.metrics("g2")["sdc_reheals_total"],
                    clears=h.metrics("g2")["sdc_quarantine_clears_total"],
                ))
        finally:
            chaos.uninstall()
        return h, timeline

    def test_detect_quarantine_heal_converge(self):
        h, tl = self._run(6)
        try:
            # (1) Detection within ONE commit boundary of the flip.
            flip_round = next(t["round"] for t in tl if t["flips"])
            detect_round = next(t["round"] for t in tl if t["verdicts"])
            assert detect_round - flip_round <= 1
            assert tl[detect_round - 1]["verdicts"] == ["g2"]
            # (2) The ladder ran: latch + exactly one auto-reheal.
            assert any(t["latched"] for t in tl)
            assert tl[-1]["reheals"] == 1.0
            mx = h.metrics("g2")
            assert mx["sdc_quarantines_total"] == 1.0
            assert mx["heal_count"] == 1.0
            # (3) Quarantine fully cleared on both sides.
            assert tl[-1]["clears"] == 1.0
            assert not tl[-1]["latched"] and not tl[-1]["verdicts"]
            assert h.mgrs["g2"].is_participating()
            # (4) Bitwise fleet convergence.
            assert h.bitwise_converged()
            # (5) The healthy groups never latched.
            for rid in ("g0", "g1"):
                assert h.metrics(rid)["sdc_quarantines_total"] == 0.0
        finally:
            h.shutdown()

    def test_quarantined_round_refuses_persistence(self, tmp_path):
        h, tl = self._run(2)
        try:
            assert h.mgrs["g2"]._sdc_quarantined or \
                h.metrics("g2")["sdc_quarantine_clears_total"] >= 1.0
            # Re-latch deterministically to probe the refusal surface.
            with h.mgrs["g2"]._metrics_lock:
                h.mgrs["g2"]._sdc_quarantined = True
            h.mgrs["g2"]._should_step = True
            writer = MagicMock()
            assert h.mgrs["g2"].save_durable(writer, str(tmp_path)) is None
            assert not writer.save_async.called
        finally:
            h.shutdown()

    def test_clean_fleet_never_quarantines(self):
        h = SdcSoakHarness()
        try:
            for r in range(1, 5):
                h.round(r)
            assert h.agg.quarantined() == {}
            for rid in h.RIDS:
                assert h.metrics(rid)["sdc_quarantines_total"] == 0.0
            assert h.agg._sdc_verdicts_total == 0
        finally:
            h.shutdown()

    @pytest.mark.slow
    @pytest.mark.nightly
    def test_nightly_storm_soak(self):
        """Longer seeded round with a PhasedChaos-driven storm, then a
        chaos-free drain: repeated flips across phases, every verdict
        must heal and clear, and the fleet must end bitwise-converged
        with zero standing verdicts."""
        h = SdcSoakHarness()
        sched = ChaosSchedule(seed=1234, endpoints={
            "sdc:g2": EndpointChaos(sdc_flip_rate=0.6)})
        chaos.install(sched)
        phases = PhasedChaos(sched, ((0.0, 1.0), (3600.0, 1.0)))
        try:
            for r in range(1, 21):
                phases.tick()
                h.round(r)
        finally:
            chaos.uninstall()
        # Drain: with the storm over, the last verdict's reheal
        # re-attests clean and the non-voter clear-on-match fires.
        for r in range(21, 26):
            h.round(r)
        try:
            mx = h.metrics("g2")
            assert mx["sdc_chaos_flips_total"] >= 2.0
            assert mx["sdc_quarantines_total"] >= 1.0
            assert mx["sdc_quarantines_total"] == \
                mx["sdc_quarantine_clears_total"]
            # Drain: no flip fires while latched, so the last rounds
            # re-attest and the fleet settles clean.
            assert h.agg.quarantined() == {}
            assert not h.mgrs["g2"]._sdc_quarantined
            assert h.bitwise_converged()
            for rid in ("g0", "g1"):
                assert h.metrics(rid)["sdc_quarantines_total"] == 0.0
        finally:
            h.shutdown()
