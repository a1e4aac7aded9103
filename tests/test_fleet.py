"""Fleet health plane tests (:mod:`torchft_tpu.fleet`,
docs/design/fleet_health.md).

Tier-1 and native-free via the pure-Python aggregator mirror: the
straggler-score battery (known-skew fleets, single-group no-NaN,
healer/degraded exclusion), slowest-stage attribution, staleness /
farewell pruning, the SLO engine's thresholds + (slo, group, step)
dedup, the frozen ``/fleet/metrics`` exposition names, the dashboard
table, ``scripts/tracefleet.py --fleet`` address resolution over a live
stub, ``scripts/benchdiff.py``'s direction vocabulary and gating, and
the Manager-side halves (digest push deltas, hint consumption, the
SLO-breach flight dump).

The native rounds (4-group piggyback drive with an artificially slowed
group, the Python-vs-C++ aggregator parity check, the churn-coherence
soak) are gated on the toolchain and ride nightly — the C++ unit
matrix itself lives in ``_core/core_test.cc``.
"""

import functools
import json
import os
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from unittest.mock import MagicMock

import numpy as np
import pytest

import conftest
import mockplane
from mockplane import quorum_result
from torchft_tpu import fleet, tracing
from torchft_tpu.fleet import (FleetAggregator, SLOConfig, SLOEngine,
                               StepDigest, attribute_stage,
                               format_fleet_table, resolve_trace_addrs,
                               robust_zscores, status_prometheus)

pytestmark = pytest.mark.fleet

requires_native = conftest.requires_native()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mk_digest(rid, wall, step=5, fetch=0.0, ring=0.0, put=0.0,
              vote=0.0, healing=False, capacity=1.0, **kw):
    return StepDigest(replica_id=rid, step=step, step_wall_ms=wall,
                      fetch_ms=fetch, ring_ms=ring, put_ms=put,
                      vote_ms=vote, healing=healing,
                      capacity_fraction=capacity, **kw)


def hint(**fleet_fields):
    """A one-group quorum carrying only the fleet-hint fields the
    consumption path reads."""
    return quorum_result(max_world_size=1, replica_world_size=1,
                         **fleet_fields)


make_manager = functools.partial(
    mockplane.make_manager, quorum=hint(), min_replica_size=1,
    use_async_quorum=False,
    state_dict=lambda: {"w": np.arange(8, dtype=np.float32)})


# ------------------------------------------------------- straggler math


class TestRobustZ:
    def test_empty_and_single(self):
        assert robust_zscores([]) == []
        # A single-group fleet has no dispersion: score 0.0, never NaN.
        assert robust_zscores([123.4]) == [0.0]

    def test_uniform_fleet_all_zero(self):
        scores = robust_zscores([100.0] * 8)
        assert scores == [0.0] * 8
        assert all(np.isfinite(scores))

    def test_known_skew_fleet_ranks_the_outlier(self):
        walls = [100.0, 101.0, 99.0, 100.5, 3000.0]
        scores = robust_zscores(walls)
        assert all(np.isfinite(scores))
        assert max(scores) == scores[4]
        assert scores[4] > 10.0  # wildly out vs a tight baseline
        assert all(abs(s) < 3.0 for s in scores[:4])

    def test_zero_mad_with_one_outlier_stays_finite(self):
        # Majority identical -> MAD 0 -> guarded to all-zero, not inf.
        assert robust_zscores([100.0, 100.0, 100.0, 900.0]) == [0.0] * 4

    def test_symmetric_negative_scores(self):
        scores = robust_zscores([50.0, 100.0, 150.0])
        assert scores[0] < 0 < scores[2]
        assert scores[1] == 0.0


class TestAttribution:
    MED = {"fetch": 10.0, "ring": 10.0, "put": 10.0, "vote": 10.0}

    def test_largest_excess_wins(self):
        stage = attribute_stage(
            {"fetch": 12.0, "ring": 500.0, "put": 11.0, "vote": 9.0},
            self.MED)
        assert stage == "ring"

    def test_tie_breaks_in_protocol_order(self):
        stage = attribute_stage(
            {"fetch": 50.0, "ring": 50.0, "put": 10.0, "vote": 10.0},
            self.MED)
        assert stage == "fetch"  # DIGEST_STAGES order wins ties

    def test_all_under_median_falls_back_to_own_biggest(self):
        stage = attribute_stage(
            {"fetch": 1.0, "ring": 5.0, "put": 2.0, "vote": 1.0},
            self.MED)
        assert stage == "ring"

    def test_all_zero_stages_unattributed(self):
        assert attribute_stage(
            {"fetch": 0.0, "ring": 0.0, "put": 0.0, "vote": 0.0},
            self.MED) == ""


class TestAggregator:
    def test_known_skew_fleet_ranking_and_attribution(self):
        agg = FleetAggregator()
        now = 1_000_000
        for i in range(3):
            agg.ingest(mk_digest(f"g{i}", 100.0 + i, fetch=25.0,
                                 ring=10.0, put=5.0, vote=2.0),
                       now_ms=now)
        agg.ingest(mk_digest("g3", 3000.0, fetch=25.0, ring=2500.0,
                             put=5.0, vote=2.0), now_ms=now)
        st = agg.aggregate(now_ms=now)
        assert st["fleet"]["groups"] == 4
        assert st["fleet"]["baseline_groups"] == 4
        assert st["fleet"]["p95_ms"] == 3000.0
        assert st["fleet"]["max_ms"] == 3000.0
        assert st["straggler"]["replica_id"] == "g3"
        assert st["straggler"]["stage"] == "ring"
        assert st["straggler"]["score"] > 10.0
        # worst-first ordering, and every group carries its own score
        assert [g["replica_id"] for g in st["groups"]][0] == "g3"
        by_id = {g["replica_id"]: g for g in st["groups"]}
        assert all(abs(by_id[f"g{i}"]["straggler_score"]) < 3.0
                   for i in range(3))
        # per-stage fleet medians come from the baseline
        assert st["fleet"]["stage_median_ms"]["fetch"] == 25.0

    def test_single_group_fleet_no_nan(self):
        agg = FleetAggregator()
        agg.ingest(mk_digest("only", 250.0, ring=100.0), now_ms=0)
        st = agg.aggregate(now_ms=1)
        g = st["groups"][0]
        assert g["straggler_score"] == 0.0
        assert np.isfinite(g["straggler_score"])
        assert st["fleet"]["p50_ms"] == 250.0
        assert json.loads(json.dumps(st))  # JSON-safe end to end

    def test_healer_excluded_from_baseline_and_ranking(self):
        agg = FleetAggregator()
        for i in range(3):
            agg.ingest(mk_digest(f"g{i}", 100.0, ring=10.0), now_ms=0)
        # The healer is 50x slower — legitimately: it is healing.
        agg.ingest(mk_digest("healer", 5000.0, ring=10.0,
                             healing=True), now_ms=0)
        st = agg.aggregate(now_ms=1)
        assert st["fleet"]["groups"] == 4
        assert st["fleet"]["baseline_groups"] == 3
        by_id = {g["replica_id"]: g for g in st["groups"]}
        assert by_id["healer"]["baseline"] is False
        assert by_id["healer"]["straggler_score"] == 0.0
        assert by_id["healer"]["straggler_stage"] == "heal"
        # ...and it can never be named THE straggler
        assert st["straggler"]["replica_id"] != "healer"
        # the baseline quantiles ignore it
        assert st["fleet"]["max_ms"] == 100.0

    def test_degraded_group_excluded_with_reason(self):
        agg = FleetAggregator()
        agg.ingest(mk_digest("ok", 100.0), now_ms=0)
        agg.ingest(mk_digest("deg", 900.0, capacity=0.75), now_ms=0)
        st = agg.aggregate(now_ms=1)
        by_id = {g["replica_id"]: g for g in st["groups"]}
        assert by_id["deg"]["straggler_stage"] == "degraded"
        assert by_id["deg"]["baseline"] is False
        assert st["fleet"]["baseline_groups"] == 1

    def test_stale_group_drops_out_of_aggregates(self):
        agg = FleetAggregator(stale_ms=1000)
        agg.ingest(mk_digest("fresh", 100.0), now_ms=5000)
        agg.ingest(mk_digest("silent", 100.0), now_ms=0)
        st = agg.aggregate(now_ms=5100)
        assert [g["replica_id"] for g in st["groups"]] == ["fresh"]
        # prune() also reclaims the ring memory
        agg.prune(now_ms=5100)
        assert agg.group_ids() == ["fresh"]

    def test_remove_is_immediate(self):
        agg = FleetAggregator()
        agg.ingest(mk_digest("a", 100.0), now_ms=0)
        agg.ingest(mk_digest("b", 100.0), now_ms=0)
        agg.note_commit_counts("b", 5, 0)
        agg.remove("b")
        st = agg.aggregate(now_ms=1)
        assert [g["replica_id"] for g in st["groups"]] == ["a"]
        assert "b" not in agg.commit_counts()

    def test_ring_bounded_latest_wins(self):
        agg = FleetAggregator(ring=4)
        for step in range(10):
            agg.ingest(mk_digest("a", 100.0 + step, step=step),
                       now_ms=step)
        st = agg.aggregate(now_ms=10)
        assert st["groups"][0]["step"] == 9
        assert st["groups"][0]["step_wall_ms"] == 109.0

    def test_uniform_fleet_straggler_matches_table_order(self):
        """Tied scores (uniform fleet -> all 0.0) must name the SAME
        group as the table's first row — smallest id, the native
        aggregator's tie-break. A max()-style pick of the LARGEST id
        here once diverged from both."""
        agg = FleetAggregator()
        for rid in ("c", "a", "b"):
            agg.ingest(mk_digest(rid, 100.0), now_ms=0)
        st = agg.aggregate(now_ms=1)
        assert st["straggler"]["replica_id"] == "a"
        assert st["straggler"]["replica_id"] == \
            st["groups"][0]["replica_id"]

    def test_staleness_slo_widens_retention(self):
        """A staleness threshold at/past the retention window could
        never breach (the group is dropped from the aggregate before
        the check sees it) — constructing the aggregator WITH the SLO
        config widens retention to 2x the threshold, mirroring the
        native lighthouse constructor."""
        cfg = SLOConfig(staleness_ms=120_000.0)
        agg = FleetAggregator(stale_ms=60_000, slo=cfg)
        agg.ingest(mk_digest("quiet", 100.0), now_ms=0)
        # 150s silent: past the default 60s retention, but visible
        # under the widened window — and breaching.
        st = agg.aggregate(now_ms=150_000)
        assert [g["replica_id"] for g in st["groups"]] == ["quiet"]
        eng = SLOEngine(cfg)
        assert [b["slo"] for b in eng.evaluate(st)] == ["staleness"]
        # ...and past 2x the threshold the group finally ages out.
        assert agg.aggregate(now_ms=260_000)["groups"] == []

    def test_empty_fleet_aggregate_is_sane(self):
        st = FleetAggregator().aggregate(now_ms=1)
        assert st["fleet"]["groups"] == 0
        assert st["fleet"]["p95_ms"] == 0.0
        assert st["straggler"]["replica_id"] == ""
        assert st["groups"] == []


# ---------------------------------------------------------------- SLOs


class TestSLOConfig:
    def test_spec_roundtrip_and_separators(self):
        cfg = SLOConfig.from_spec(
            "step_p95_ms=2500, commit_rate=0.95; heal_ms=60000")
        assert cfg.step_p95_ms == 2500.0
        assert cfg.commit_rate == 0.95
        assert cfg.heal_ms == 60000.0
        assert cfg.publish_lag_ms is None
        assert cfg.enabled()
        assert SLOConfig.from_spec(cfg.spec()).spec() == cfg.spec()

    def test_unknown_key_raises(self):
        with pytest.raises(ValueError, match="bad SLO spec"):
            SLOConfig.from_spec("step_p95ms=100")  # typo'd key
        with pytest.raises(ValueError):
            SLOConfig.from_spec("nonsense")

    def test_non_decimal_threshold_raises(self):
        """float() accepts spellings ("2_500", "nan") the C++ atof
        parses DIFFERENTLY — the strict gate rejects anything the two
        sides could disagree on."""
        for bad in ("step_p95_ms=2_500", "heal_ms=nan",
                    "commit_rate=", "staleness_ms=10s",
                    # negative = "disabled" to the C++ parser but a
                    # live always-breaching bound to the Python
                    # engine — rejected so they can't disagree
                    "step_p95_ms=-1"):
            with pytest.raises(ValueError):
                SLOConfig.from_spec(bad)
        # plain decimals, signs, and exponents still parse
        assert SLOConfig.from_spec(
            "step_p95_ms=2.5e3").step_p95_ms == 2500.0

    def test_empty_spec_disabled(self):
        cfg = SLOConfig.from_spec("")
        assert not cfg.enabled()
        assert cfg.spec() == ""

    def test_from_env(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_SLO", "staleness_ms=30000")
        assert SLOConfig.from_env().staleness_ms == 30000.0


class TestSLOEngine:
    def _status(self, **over):
        agg = FleetAggregator()
        agg.ingest(mk_digest("fast", 100.0, ring=10.0), now_ms=0)
        agg.ingest(mk_digest("slow", 4000.0, ring=3500.0, step=7,
                             **over.pop("slow_kw", {})), now_ms=0)
        return agg.aggregate(now_ms=1)

    def test_step_p95_breach_lands_on_the_straggler(self):
        eng = SLOEngine(SLOConfig(step_p95_ms=1000.0))
        fresh = eng.evaluate(self._status())
        assert len(fresh) == 1
        b = fresh[0]
        assert b["slo"] == "step_p95"
        assert b["replica_id"] == "slow"
        assert b["step"] == 7
        assert b["value"] == 4000.0
        assert eng.breaches_for("slow") == ["step_p95"]
        assert eng.breaches_for("fast") == []

    def test_dedup_per_slo_group_step(self):
        eng = SLOEngine(SLOConfig(step_p95_ms=1000.0))
        st = self._status()
        assert len(eng.evaluate(st)) == 1
        # same (slo, group, step) persisting -> no NEW breach...
        assert eng.evaluate(st) == []
        assert eng.breaches_total == 1
        # ...but it is still ACTIVE (the slo_breach gauge stays up)
        assert eng.breaches_for("slow") == ["step_p95"]
        # a new step re-arms the event
        agg = FleetAggregator()
        agg.ingest(mk_digest("fast", 100.0), now_ms=0)
        agg.ingest(mk_digest("slow", 4000.0, step=8), now_ms=0)
        assert len(eng.evaluate(agg.aggregate(now_ms=1))) == 1
        assert eng.breaches_total == 2

    def test_heal_publish_staleness_thresholds(self):
        agg = FleetAggregator(stale_ms=120_000)
        agg.ingest(mk_digest("a", 100.0, heal_last_ms=90_000.0),
                   now_ms=60_000)
        agg.ingest(mk_digest("b", 100.0, publish_last_ms=9_000.0),
                   now_ms=60_000)
        agg.ingest(mk_digest("c", 100.0), now_ms=0)  # silent 60s
        st = agg.aggregate(now_ms=60_000)
        eng = SLOEngine(SLOConfig(heal_ms=60_000.0,
                                  publish_lag_ms=5_000.0,
                                  staleness_ms=30_000.0))
        fresh = eng.evaluate(st)
        got = {(b["slo"], b["replica_id"]) for b in fresh}
        assert got == {("heal", "a"), ("publish_lag", "b"),
                       ("staleness", "c")}

    def test_commit_rate_needs_min_samples(self):
        agg = FleetAggregator()
        agg.ingest(mk_digest("a", 100.0), now_ms=0)
        st = agg.aggregate(now_ms=1)
        eng = SLOEngine(SLOConfig(commit_rate=0.9,
                                  min_commit_samples=8))
        # 3 commits, 4 aborts: terrible rate but under the sample floor
        assert eng.evaluate(st, {"a": (3, 4)}) == []
        fresh = eng.evaluate(st, {"a": (5, 5)})
        assert [b["slo"] for b in fresh] == ["commit_rate"]
        assert fresh[0]["value"] == 0.5

    def test_no_slos_no_breaches(self):
        eng = SLOEngine(SLOConfig())
        assert eng.evaluate(self._status()) == []
        assert eng.active == []


# ----------------------------------------------------------- renderers


# The /fleet/metrics exposition names, frozen: lighthouse.cc's
# fleet_metrics_text emits the SAME set — a drift between the two
# spellings breaks scrape configs silently.
FLEET_METRIC_NAMES = frozenset([
    "torchft_fleet_groups", "torchft_fleet_step_ms",
    "torchft_fleet_step_ms_max", "torchft_fleet_slo_breach",
    "torchft_fleet_slo_breaches_total",
    "torchft_fleet_sdc_quarantined",
    "torchft_fleet_sdc_verdicts_total",
    "torchft_fleet_rebalance_groups",
    "torchft_fleet_rebalance_seq",
    "torchft_fleet_rebalance_fraction",
    "torchft_fleet_stage_median_ms",
    "torchft_fleet_straggler_score", "torchft_fleet_group_step_ms",
    # publication relay tier (docs/design/serving.md)
    "torchft_fleet_relays", "torchft_fleet_relay_children",
    "torchft_fleet_relay_lag_gens_max",
    "torchft_fleet_relay_child_count", "torchft_fleet_relay_lag_gens",
])


def _exposition_names(text):
    names = set()
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            names.add(line.split()[2])
    return names


class TestRenderers:
    def _status(self):
        agg = FleetAggregator()
        agg.ingest(mk_digest("g0", 100.0, ring=10.0,
                             trace_addr="http://a:1"), now_ms=0)
        agg.ingest(mk_digest("g1", 900.0, ring=800.0,
                             trace_addr="http://b:2"), now_ms=0)
        agg.ingest(mk_digest("h", 5000.0, healing=True,
                             trace_addr="http://a:1"), now_ms=0)
        return agg.aggregate(now_ms=1)

    def test_prometheus_names_frozen(self):
        text = status_prometheus(self._status(), slo_active=1,
                                 slo_breaches_total=3)
        assert _exposition_names(text) == FLEET_METRIC_NAMES
        assert 'torchft_fleet_straggler_score{replica_id="g1"}' in text
        assert 'torchft_fleet_step_ms{quantile="0.95"}' in text
        assert "torchft_fleet_slo_breach 1.0" in text
        assert "torchft_fleet_slo_breaches_total 3.0" in text
        # every family carries HELP + TYPE
        helps = {l.split()[2] for l in text.splitlines()
                 if l.startswith("# HELP ")}
        assert helps == FLEET_METRIC_NAMES

    def test_prometheus_label_escaping(self):
        agg = FleetAggregator()
        agg.ingest(mk_digest('g"q\\z', 100.0), now_ms=0)
        # a raw newline in a replica_id must not split the sample line
        agg.ingest(mk_digest("g\nnl", 100.0), now_ms=0)
        text = status_prometheus(agg.aggregate(now_ms=1))
        assert 'replica_id="g\\"q\\\\z"' in text
        assert 'replica_id="g\\nnl"' in text
        assert "\ng\nnl" not in text

    def test_relay_tier_rides_aggregate_and_exposition(self):
        agg = FleetAggregator()
        agg.ingest(mk_digest("g0", 100.0), now_ms=0)
        agg.note_relays([
            {"id": "r1", "addr": "http://r1/publish", "children": 3,
             "lag_gens": 0, "age_s": 0.1},
            {"id": "r2", "addr": "http://r2/publish", "children": 1,
             "lag_gens": 2, "age_s": 0.4},
        ])
        st = agg.aggregate(now_ms=1)
        assert st["fleet"]["relays"] == 2
        assert st["fleet"]["relay_children"] == 4
        assert st["fleet"]["relay_lag_gens_max"] == 2
        assert [r["id"] for r in st["relays"]] == ["r1", "r2"]
        text = status_prometheus(st)
        assert _exposition_names(text) == FLEET_METRIC_NAMES
        assert "torchft_fleet_relays 2.0" in text
        assert "torchft_fleet_relay_children 4.0" in text
        assert "torchft_fleet_relay_lag_gens_max 2.0" in text
        assert 'torchft_fleet_relay_child_count{relay_id="r1"} 3.0' \
            in text
        assert 'torchft_fleet_relay_lag_gens{relay_id="r2"} 2.0' \
            in text

    def test_fleet_table_renders_ranked_rows(self):
        st = self._status()
        table = format_fleet_table(
            st, breaches=[{"slo": "step_p95", "replica_id": "g1",
                           "value": 900.0, "threshold": 500.0,
                           "step": 5}])
        lines = table.splitlines()
        assert "straggler: g1" in table
        assert "SLO BREACH: step_p95 on g1" in table
        # worst-first rows; the healer is flagged
        g1_row = next(i for i, l in enumerate(lines)
                      if l.startswith("g1"))
        g0_row = next(i for i, l in enumerate(lines)
                      if l.startswith("g0"))
        assert g1_row < g0_row
        assert any(l.endswith("HEAL") for l in lines)

    def test_resolve_trace_addrs_dedups(self):
        addrs = resolve_trace_addrs(self._status())
        assert addrs == ["http://b:2", "http://a:1"] or \
            set(addrs) == {"http://a:1", "http://b:2"}
        assert len(addrs) == 2
        assert resolve_trace_addrs({"groups": []}) == []


# ----------------------------------------------- tracer stage totals


class TestStageTotals:
    def test_sums_per_stage_for_newest_step(self):
        tr = tracing.Tracer(steps=4, enabled=True)
        tr.set_context(step=3)
        with tr.span("ring"):
            time.sleep(0.002)
        with tr.span("ring"):
            pass
        with tr.span("vote"):
            pass
        tr.set_context(step=4)
        with tr.span("put"):
            pass
        newest = tr.stage_totals()
        assert set(newest) == {"put"}
        old = tr.stage_totals(step=3)
        assert set(old) == {"ring", "vote"}
        assert old["ring"] >= 2.0  # two spans, one slept 2ms

    def test_empty_or_disabled_ring(self):
        assert tracing.Tracer(steps=4, enabled=True).stage_totals() == {}
        tr = tracing.Tracer(steps=4, enabled=False)
        with tr.span("ring"):
            pass
        assert tr.stage_totals() == {}


# ------------------------------------------------- manager-side halves


class _DigestServer:
    """Captures the manager's set_status/set_digest pushes."""

    def __init__(self):
        self.digests = []

    def set_status(self, *a, **k):
        pass

    def set_digest(self, **kw):
        self.digests.append(kw)

    def lighthouse_redials(self):  # metrics() reads this
        return 0


class TestDigestPush:
    def test_first_boundary_skipped_then_wall_reported(self):
        m = make_manager()
        try:
            srv = _DigestServer()
            m._manager_server = srv
            m._publish_status()
            assert srv.digests == []  # no previous boundary: no wall
            time.sleep(0.01)
            m._publish_status()
            assert len(srv.digests) == 1
            d = srv.digests[0]
            assert d["step_wall_ms"] >= 10.0
            assert d["trace_addr"] == m._ckpt_server.address()
            assert d["capacity_fraction"] == 1.0
            assert d["healing"] is False
            assert d["heal_last_ms"] == 0.0
        finally:
            m._manager_server = None
            m.shutdown()

    def test_heal_delta_gated_on_count(self):
        m = make_manager()
        try:
            srv = _DigestServer()
            m._manager_server = srv
            m._publish_status()
            # A heal completed this boundary: count bumped, ms accrued.
            with m._metrics_lock:
                m._metrics["heal_count"] += 1
                m._metrics["heal_ms_total"] += 2500.0
            m._publish_status()
            assert srv.digests[-1]["heal_last_ms"] == 2500.0
            # ms drift WITHOUT a completed heal must not mint one.
            with m._metrics_lock:
                m._metrics["heal_ms_total"] += 400.0
            m._publish_status()
            assert srv.digests[-1]["heal_last_ms"] == 0.0
        finally:
            m._manager_server = None
            m.shutdown()

    def test_stage_splits_come_from_tracer(self):
        m = make_manager(tracing=True)
        try:
            srv = _DigestServer()
            m._manager_server = srv
            m._publish_status()
            m._tracer.set_context(step=m._step)
            with m._tracer.span("ring"):
                time.sleep(0.002)
            with m._tracer.span("fetch_wait"):
                time.sleep(0.001)
            m._publish_status()
            d = srv.digests[-1]
            assert d["ring_ms"] >= 2.0
            assert d["fetch_ms"] >= 1.0  # dispatch + wait folded
            assert d["put_ms"] == 0.0
        finally:
            m._manager_server = None
            m.shutdown()

    def test_fleet_telemetry_off_pushes_nothing(self):
        m = make_manager(fleet_telemetry=False)
        try:
            srv = _DigestServer()
            m._manager_server = srv
            m._publish_status()
            m._publish_status()
            assert srv.digests == []
        finally:
            m._manager_server = None
            m.shutdown()

    def test_env_default_knob(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_FLEET_TELEMETRY", "0")
        m = make_manager()
        try:
            assert m._fleet_telemetry is False
        finally:
            m.shutdown()
        monkeypatch.delenv("TORCHFT_FLEET_TELEMETRY")
        m = make_manager()
        try:
            assert m._fleet_telemetry is True  # default ON
        finally:
            m.shutdown()

    def test_duck_typed_server_without_set_digest_is_fine(self):
        m = make_manager()
        try:
            m._manager_server = object()  # no set_digest, no set_status
            m._publish_status()  # must not raise
        finally:
            m._manager_server = None
            m.shutdown()


class TestFleetHintConsumption:
    def test_gauges_refresh_every_round(self):
        m = make_manager()
        try:
            m._consume_fleet_hint(hint(fleet_p95_ms=850.0,
                                       straggler_score=-0.4,
                                       fleet_groups=16,
                                       straggler_stage="fetch",
                                       straggler_id="g9"))
            mx = m.metrics()
            assert mx["fleet_p95_ms"] == 850.0
            assert mx["straggler_score"] == -0.4
            assert mx["fleet_groups"] == 16.0
            assert mx["slo_breach"] == 0.0
            assert mx["slo_breaches_total"] == 0.0
            assert m.metrics_info()["straggler_stage"] == "fetch"
            # a later hint-less round zeroes the gauges back
            m._consume_fleet_hint(hint())
            assert m.metrics()["fleet_p95_ms"] == 0.0
            assert m.metrics_info()["straggler_stage"] == ""
        finally:
            m.shutdown()

    def test_slo_breach_dumps_flight_once_per_step(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        m = make_manager()
        try:
            h = hint(fleet_p95_ms=4000.0, straggler_score=11.0,
                     straggler_stage="ring", slo_breach="step_p95")
            m._consume_fleet_hint(h)
            mx = m.metrics()
            assert mx["slo_breach"] == 1.0
            assert mx["slo_breaches_total"] == 1.0
            dumps = list(tmp_path.glob("*.json"))
            assert len(dumps) == 1
            side = json.loads(dumps[0].read_text())["torchft"]
            assert side["reason"] == "slo_breach_step_p95"
            assert side["extra"]["stage"] == "ring"
            assert side["extra"]["fleet_p95_ms"] == 4000.0
            # the breach persists across rounds of the same step: the
            # counter, event log, and dump must NOT repeat
            m._consume_fleet_hint(h)
            assert m.metrics()["slo_breaches_total"] == 1.0
            assert len(list(tmp_path.glob("*.json"))) == 1
            events = [e for e in m.history()
                      if e.get("event") == "slo_breach"]
            assert len(events) == 1
            # ...but a new step re-arms it (the real flow bumps both
            # in step(): the counter and the tracer's context)
            m._step += 1
            m._tracer.set_context(step=m._step)
            m._consume_fleet_hint(h)
            assert m.metrics()["slo_breaches_total"] == 2.0
            assert len(list(tmp_path.glob("*.json"))) == 2
        finally:
            m.shutdown()

    def test_multi_breach_hint_counts_each_slo(self):
        m = make_manager()
        try:
            m._consume_fleet_hint(
                hint(slo_breach="step_p95,staleness"))
            assert m.metrics()["slo_breaches_total"] == 2.0
        finally:
            m.shutdown()

    def test_duck_typed_quorum_is_hintless(self):
        m = make_manager()
        try:
            m._consume_fleet_hint(MagicMock())  # attrs are all Mocks
            mx = m.metrics()
            assert mx["fleet_p95_ms"] == 0.0
            assert mx["slo_breach"] == 0.0
            assert m.metrics_info()["straggler_stage"] == ""
        finally:
            m.shutdown()


# ------------------------------------------ tracefleet --fleet resolver


class _FleetStub:
    """A stub lighthouse serving ONLY /fleet/status.json."""

    def __init__(self, status):
        stub = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                if self.path != "/fleet/status.json":
                    self.send_error(404)
                    return
                body = json.dumps(stub.status).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        self.status = status
        self.srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.thread = threading.Thread(target=self.srv.serve_forever,
                                       daemon=True)
        self.thread.start()

    @property
    def address(self):
        return f"127.0.0.1:{self.srv.server_address[1]}"

    def close(self):
        self.srv.shutdown()
        self.srv.server_close()


class TestTracefleetFleetResolution:
    def _import_tracefleet(self):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import tracefleet
        finally:
            sys.path.pop(0)
        return tracefleet

    def test_resolves_and_merges_from_fleet_status(self, tmp_path):
        tracefleet = self._import_tracefleet()
        m = make_manager(replica_id="fla0")
        stub = None
        try:
            m.step()
            m.should_commit()
            agg = FleetAggregator()
            agg.ingest(mk_digest(
                "fla0", 100.0,
                trace_addr=m._ckpt_server.address()), now_ms=0)
            stub = _FleetStub(agg.aggregate(now_ms=1))
            out = tmp_path / "fleet.json"
            rc = tracefleet.main(["--fleet", stub.address,
                                  "--out", str(out)])
            assert rc == 0
            merged = json.loads(out.read_text())
            names = {ev["args"]["name"] for ev in merged["traceEvents"]
                     if ev.get("ph") == "M"
                     and ev.get("name") == "process_name"}
            assert names == {"fla0"}
        finally:
            if stub is not None:
                stub.close()
            m.shutdown()

    def test_fleet_resolution_failure_is_not_fatal_with_args(
            self, tmp_path):
        tracefleet = self._import_tracefleet()
        m = make_manager(replica_id="fla1")
        try:
            m.step()
            m.should_commit()
            out = tmp_path / "fleet.json"
            # unreachable --fleet + a good explicit address: merge wins
            rc = tracefleet.main(["--fleet", "127.0.0.1:1",
                                  m._ckpt_server.address(),
                                  "--out", str(out), "--timeout", "2"])
            assert rc == 0
            assert json.loads(out.read_text())["traceEvents"]
        finally:
            m.shutdown()

    def test_resolve_helper_reads_trace_addrs(self):
        tracefleet = self._import_tracefleet()
        agg = FleetAggregator()
        agg.ingest(mk_digest("a", 100.0, trace_addr="http://x:1"),
                   now_ms=0)
        stub = _FleetStub(agg.aggregate(now_ms=1))
        try:
            got = tracefleet.resolve_from_fleet(stub.address)
            assert got == ["http://x:1"]
        finally:
            stub.close()


# ------------------------------------------------------ benchdiff units


class TestBenchdiff:
    def _bd(self):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import benchdiff
        finally:
            sys.path.pop(0)
        return benchdiff

    def test_direction_vocabulary(self):
        bd = self._bd()
        assert bd.direction_of("steps_per_s") == 1
        assert bd.direction_of("speedup_vs_exact") == 1
        assert bd.direction_of("achieved_tflops") == 1
        assert bd.direction_of("allreduce_ms_avg") == -1
        assert bd.direction_of("stages_ms.ring") == -1
        assert bd.direction_of("recovery_wall_clock_s") == -1
        assert bd.direction_of("n_groups") is None
        assert bd.direction_of("seq_len") is None
        assert bd.direction_of("value", unit="steps/s") == 1
        assert bd.direction_of("value", unit="GB") == -1

    def test_driver_wrapper_and_jsonl_both_parse(self, tmp_path):
        bd = self._bd()
        row = {"metric": "m", "value": 1.0, "unit": "steps/s"}
        wrapped = tmp_path / "BENCH_r01.json"
        wrapped.write_text(json.dumps(
            {"n": 1, "cmd": "x", "rc": 0,
             "tail": "noise\n" + json.dumps(row) + "\n"}))
        raw = tmp_path / "rows.jsonl"
        raw.write_text(json.dumps(row) + "\n")
        assert bd.parse_bench_file(str(wrapped)) == {"m": row}
        assert bd.parse_bench_file(str(raw)) == {"m": row}

    def test_regression_direction_aware(self, tmp_path):
        bd = self._bd()
        old = {"m": {"metric": "m", "steps_per_s": 1.0,
                     "ring_ms": 100.0}}
        # throughput down 50% AND latency up 50%: two regressions
        new = {"m": {"metric": "m", "steps_per_s": 0.5,
                     "ring_ms": 150.0}}
        d = bd.diff_rows(old, new, threshold=0.10)
        assert {e["key"] for e in d["regressions"]} == \
            {"steps_per_s", "ring_ms"}
        # both moving the GOOD way: improvements, never fatal
        better = {"m": {"metric": "m", "steps_per_s": 2.0,
                        "ring_ms": 50.0}}
        d = bd.diff_rows(old, better, threshold=0.10)
        assert not d["regressions"]
        assert len(d["improvements"]) == 2

    def test_provenance_mismatch_skips_not_gates(self):
        """A rig/schema change or an error stub must read as skipped,
        never as a regression: a TPU round followed by a CPU-only rig
        would otherwise permanently fail the trajectory gate."""
        bd = self._bd()
        tpu = {"m": {"metric": "m", "steps_per_s": 100.0,
                     "schema": "tft-bench-2", "platform": "tpu"}}
        cpu = {"m": {"metric": "m", "steps_per_s": 1.0,
                     "schema": "tft-bench-2", "platform": "cpu"}}
        d = bd.diff_rows(tpu, cpu, threshold=0.10)
        assert not d["regressions"]
        assert d["skipped"] and "rig changed" in d["skipped"][0]["reason"]
        # rows predating the provenance stamp are schema v1
        v1 = {"m": {"metric": "m", "steps_per_s": 100.0}}
        d = bd.diff_rows(v1, cpu, threshold=0.10)
        assert not d["regressions"]
        assert "schema changed" in d["skipped"][0]["reason"]
        # an error stub is a placeholder, not a measurement
        err = {"m": {"metric": "m", "steps_per_s": -1.0,
                     "schema": "tft-bench-2", "platform": "cpu",
                     "error": "native control plane unavailable"}}
        d = bd.diff_rows(cpu, err, threshold=0.10)
        assert not d["regressions"]
        assert d["skipped"][0]["reason"] == "error row"
        # same rig, same schema, no error: still gates normally
        slow = {"m": {"metric": "m", "steps_per_s": 10.0,
                      "schema": "tft-bench-2", "platform": "cpu"}}
        d = bd.diff_rows(cpu, slow, threshold=0.10)
        assert not d["skipped"]
        assert len(d["improvements"]) == 1

    def test_host_shape_change_skips_not_gates(self):
        """Same "cpu" platform string on a different machine shape is
        still a rig change: a 1-core container cannot reproduce a
        16-core round's throughput rows. Strict like schema — an
        unstamped row's host is unknown, so stamped-vs-unstamped also
        skips rather than manufacturing a permanent regression."""
        bd = self._bd()

        def row(v, cpus=None):
            r = {"metric": "m", "steps_per_s": v,
                 "schema": "tft-bench-2", "platform": "cpu"}
            if cpus is not None:
                r["host_cpus"] = cpus
            return {"m": r}

        # both stamped, shapes differ -> skipped
        d = bd.diff_rows(row(100.0, cpus=16), row(10.0, cpus=1), 0.10)
        assert not d["regressions"]
        assert "host shape changed: 16 -> 1 cpus" == \
            d["skipped"][0]["reason"]
        # unstamped old vs stamped new (rows predate the stamp) ->
        # skipped, never a regression
        d = bd.diff_rows(row(100.0), row(10.0, cpus=1), 0.10)
        assert not d["regressions"]
        assert "unstamped -> 1 cpus" in d["skipped"][0]["reason"]
        # both stamped, same shape -> gates normally
        d = bd.diff_rows(row(100.0, cpus=1), row(10.0, cpus=1), 0.10)
        assert not d["skipped"]
        assert len(d["regressions"]) == 1

    def test_trajectory_gates_newest_pair_only(self, tmp_path):
        bd = self._bd()

        def write(n, v):
            (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(
                {"tail": json.dumps(
                    {"metric": "m", "value": v,
                     "unit": "steps/s"})}))

        # old regression (r1->r2), then recovery (r2->r3): gate passes
        write(1, 1.0)
        write(2, 0.4)
        write(3, 1.1)
        assert bd.main([str(tmp_path)]) == 0
        assert bd.main([str(tmp_path), "--all"]) == 1
        # newest pair regressing fails either way
        write(4, 0.2)
        assert bd.main([str(tmp_path)]) == 1

    def test_file_plus_directory_is_a_cli_error(self, tmp_path):
        """A file+directory pair must die as an argparse error, not an
        IsADirectoryError traceback from open('.')."""
        bd = self._bd()
        f = tmp_path / "a.json"
        f.write_text(json.dumps({"metric": "m", "value": 1.0}))
        with pytest.raises(SystemExit) as exc:
            bd.main([str(f), str(tmp_path)])
        assert exc.value.code == 2  # argparse usage error

    def test_added_removed_metrics_not_fatal(self, tmp_path):
        bd = self._bd()
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps({"metric": "gone", "value": 1.0}))
        b.write_text(json.dumps({"metric": "born", "value": 1.0}))
        assert bd.main([str(a), str(b)]) == 0


# ------------------------------------------------------- native rounds


@requires_native
@pytest.mark.integration
@pytest.mark.nightly
@pytest.mark.slow
class TestNativeFleetDrive:
    """The ISSUE-15 acceptance drive at the control-plane level: 4
    groups piggyback digests on real quorum RPC beats, one is
    artificially slowed (a fat ring stage), and the lighthouse must
    rank it, attribute it, echo the step-p95 breach to IT alone, and
    serve the same numbers over /fleet/status.json + /fleet/metrics
    that the pure-Python mirror computes from the same digests."""

    def _drive_round(self, servers, step, walls, rings):
        from torchft_tpu._native import ManagerClient

        results = {}

        def run(gid, srv):
            srv.set_digest(step=step, step_wall_ms=walls[gid],
                           fetch_ms=25.0, ring_ms=rings[gid],
                           put_ms=5.0, vote_ms=2.0,
                           trace_addr=f"http://{gid}:1")
            client = ManagerClient(srv.address())
            results[gid] = client.quorum(
                rank=0, step=step,
                checkpoint_server_addr=f"ckpt_{gid}",
                timeout_ms=20_000)

        ts = [threading.Thread(target=run, args=(gid, srv))
              for gid, srv in servers.items()]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return results

    def test_four_group_straggler_attribution_and_slo_echo(self):
        from torchft_tpu._native import Lighthouse, ManagerServer

        lh = Lighthouse(bind="127.0.0.1:0", min_replicas=4,
                        join_timeout_ms=2000, quorum_tick_ms=10,
                        slo="step_p95_ms=1000")
        servers = {}
        try:
            for i in range(4):
                gid = f"g{i}"
                servers[gid] = ManagerServer(
                    gid, lh.address(), store_addr=f"store_{gid}",
                    bind="127.0.0.1:0", world_size=1)
            walls = {"g0": 100.0, "g1": 110.0, "g2": 105.0,
                     "g3": 3000.0}
            rings = {"g0": 10.0, "g1": 12.0, "g2": 11.0, "g3": 2500.0}
            self._drive_round(servers, 1, walls, rings)
            time.sleep(0.3)  # let the 200ms aggregate cache expire
            res = self._drive_round(servers, 2, walls, rings)

            # every group sees the same fleet quantiles in its hint
            for gid, r in res.items():
                assert r.fleet_groups == 4, gid
                assert r.fleet_p95_ms == 3000.0, gid
                assert r.straggler_id == "g3", gid
            # the slowed group leads the ranking, attributed to ring,
            # and the step-p95 breach is echoed to IT alone
            assert res["g3"].straggler_score > 10.0
            assert res["g3"].straggler_stage == "ring"
            assert "step_p95" in res["g3"].slo_breach
            for gid in ("g0", "g1", "g2"):
                assert res[gid].slo_breach == "", gid
                assert abs(res[gid].straggler_score) < 3.0, gid

            # /fleet/status.json agrees, and matches the Python mirror
            # fed the same digests (the two implementations must rank
            # identically)
            with urllib.request.urlopen(
                    f"http://{lh.address()}/fleet/status.json",
                    timeout=10) as resp:
                native = json.loads(resp.read())
            assert native["straggler"]["replica_id"] == "g3"
            assert native["straggler"]["stage"] == "ring"
            assert [g["replica_id"] for g in native["groups"]][0] \
                == "g3"
            mirror = FleetAggregator()
            for gid in servers:
                mirror.ingest(mk_digest(gid, walls[gid], fetch=25.0,
                                        ring=rings[gid], put=5.0,
                                        vote=2.0, step=2), now_ms=0)
            st = mirror.aggregate(now_ms=1)
            for ng, pg in zip(native["groups"], st["groups"]):
                assert ng["replica_id"] == pg["replica_id"]
                assert ng["straggler_score"] == pytest.approx(
                    pg["straggler_score"], abs=1e-3)
                assert ng["straggler_stage"] == pg["straggler_stage"]
            assert native["fleet"]["p95_ms"] == st["fleet"]["p95_ms"]
            assert native["slo"]["breaches_total"] >= 1

            # /fleet/metrics serves the frozen exposition names
            with urllib.request.urlopen(
                    f"http://{lh.address()}/fleet/metrics",
                    timeout=10) as resp:
                text = resp.read().decode()
            assert _exposition_names(text) == FLEET_METRIC_NAMES
        finally:
            for srv in servers.values():
                srv.shutdown()
            lh.shutdown()

    def test_churn_soak_keeps_fleet_status_coherent(self):
        """Graceful churn (the ChurnOrchestrator's notice leg) must
        withdraw departed groups from /fleet/status.json immediately —
        no phantom straggler — while survivors keep aggregating."""
        from torchft_tpu._native import Lighthouse, ManagerServer
        from torchft_tpu.chaos import ChurnOrchestrator

        lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                        join_timeout_ms=300, quorum_tick_ms=10)
        gids = [f"c{i}" for i in range(4)]
        servers = {}

        def status_ids():
            with urllib.request.urlopen(
                    f"http://{lh.address()}/fleet/status.json",
                    timeout=10) as resp:
                st = json.loads(resp.read())
            return {g["replica_id"] for g in st["groups"]}

        def spawn(gid):
            servers[gid] = ManagerServer(
                gid, lh.address(), store_addr=f"store_{gid}",
                bind="127.0.0.1:0", world_size=1)
            servers[gid].set_digest(step=1, step_wall_ms=100.0,
                                    ring_ms=10.0,
                                    trace_addr=f"http://{gid}:1")

        def drain(gid):
            srv = servers.pop(gid, None)
            if srv is not None:
                srv.farewell()
                srv.shutdown()

        try:
            for gid in gids:
                spawn(gid)
            time.sleep(0.8)  # beats deliver the digests
            assert status_ids() == set(gids)

            orch = ChurnOrchestrator(
                seed=77, groups=gids, rate_per_min=600.0,
                graceful_frac=1.0, notify=drain, replace=spawn,
                replace_delay_s=0.3, min_live=2)
            t0 = time.monotonic()
            while time.monotonic() - t0 < 6.0:
                orch.tick(time.monotonic() - t0)
                time.sleep(0.05)
                # Coherence invariant: a farewelled group is withdrawn
                # IMMEDIATELY; live groups may lag one beat, so only
                # the no-phantom direction is exact.
                assert status_ids() <= set(servers), (
                    "departed group lingering in /fleet/status.json")
            assert orch.notices >= 2, "soak drove no churn"
            time.sleep(0.8)
            assert status_ids() == set(servers)
        finally:
            for srv in servers.values():
                srv.shutdown()
            lh.shutdown()
