"""Schema-stability snapshots of the observability surfaces (tier-1).

Every name below is documented behavior: dashboards, the
``/metrics.json`` endpoint, the Prometheus ``/metrics`` exposition, the
``/trace.json`` Chrome-trace export, the pod runbook's diagnosis
recipes, and the bench emitters all read these by name. A refactor that
renames or drops one silently breaks them long after the refactor's own
tests went green — these tests are the tripwire: a key may be ADDED
freely (add it here), but an existing key disappearing fails loudly.

Three frozen surfaces:
* ``Manager.metrics()`` — numeric-only (every value int/float, no
  per-key carve-outs: string diagnostics moved to ``metrics_info()``);
* the Prometheus text exposition rendered from it
  (``torchft_<key>`` samples + one ``torchft_info`` label set);
* the trace-event JSON schema (phases ``B``/``E``/``X`` (+``M``
  metadata), required context tags on every span).
"""

import pytest

from mockplane import make_manager
from torchft_tpu import tracing

pytestmark = pytest.mark.obs

# The documented metrics() schema, by subsystem. Append when a PR adds a
# counter; never remove without a deliberate deprecation (and a grep for
# every reader: docs/*, bench.py, dashboards).
DOCUMENTED_KEYS = frozenset([
    # quorum / control plane
    "quorum_count", "quorum_ms_total", "quorum_ms_last",
    "quorum_fast_path_hits", "quorum_slow_path_rounds",
    "quorum_epoch_last", "quorum_ms_p50", "quorum_ms_p95",
    "quorum_ms_max", "lighthouse_redials",
    "reconfigure_count", "reconfigure_ms_total",
    # a recovery, second by second (PR 58): rounds that changed the
    # quorum, the healed tree's adoption, a Manager's birth to its first
    # commit, the dispatches that traced their program, and what the
    # process spent building programs
    "quorum_changed_count", "quorum_changed_ms_total",
    "heal_adopt_ms_total", "join_first_commit_ms",
    "dispatch_traced_ms_total",
    "program_build_ms_total", "program_cache_read_ms_total",
    # a healer's step program built beside its heal (PR 59): how often,
    # and the wall of those ``dispatch`` spans (tagged ``ahead=True``)
    "dispatch_ahead_count", "dispatch_ahead_ms_total",
    # healing
    "heal_count", "heal_ms_total", "heal_bytes_total",
    "heal_bytes_resumed_total", "heal_donor_failovers",
    "heal_leaf_digest_mismatches", "heal_attempts_total",
    "heal_last_bytes_committed", "heal_last_payload_bytes",
    "heal_striped_donors", "heal_redials_avoided",
    # the heal transfer's stages, busy ms (docs/design/healing.md): the
    # healer's wait for the manifest, socket reads, crc32, placement;
    # the donor's D2H and socket writes
    "heal_manifest_ms_total", "heal_recv_ms_total",
    "heal_verify_ms_total", "heal_place_ms_total",
    "heal_serve_fetch_ms_total", "heal_serve_send_ms_total",
    # allreduce pipeline
    "allreduce_count", "allreduce_ms_total",
    "allreduce_fetch_ms_total", "allreduce_fetch_dispatch_ms_total",
    "allreduce_fetch_wait_ms_total", "allreduce_ring_ms_total",
    "allreduce_put_ms_total", "allreduce_wire_bytes_total",
    "allreduce_ring_wire_bytes_total",
    "allreduce_pack_cache_misses", "allreduce_d2h_async_fallbacks",
    # D2H fetch accounting + hierarchical transport legs
    # (docs/design/hier_transport.md)
    "allreduce_d2h_wire_bytes_total",
    "hier_intra_bytes_total", "hier_leader",
    # the exact ring's accumulators across steps
    # (docs/design/allreduce_pipeline.md): bytes copied host-to-host
    # between the fetch and the ring [bytes]; accumulators taken from
    # the kept set / freshly allocated [count, one per exact chunk of a
    # wire op]
    "allreduce_host_copy_bytes_total",
    "allreduce_accum_reuse_total", "allreduce_accum_alloc_total",
    # wire ops handed to the ring [count, one a bucket], and those of
    # them that were one slice of a leaf wider than a slice [count]
    "allreduce_ring_ops_total", "allreduce_split_slices_total",
    # inbound steps of the exact ring [count, one a chunk received:
    # 2*(world-1) an allreduce buffer], by executor: one GIL-free call
    # of the native core / the Python segment loop
    "allreduce_ring_native_steps_total",
    "allreduce_ring_python_steps_total",
    # the ring's lanes [gauge: socket pairs of the ring in force, 1
    # under the hierarchical transport, 0 at world 1], and the wire ops
    # that began while another lane's op was on the wire [count]
    "allreduce_ring_lanes", "allreduce_ring_overlapped_ops_total",
    # cross-step overlap engine
    "allreduce_hidden_ms_total", "allreduce_drain_wait_ms_total",
    "allreduce_inflight", "overlap_steps_deferred",
    "overlap_grads_dropped",
    # sharded update
    "reduce_scatter_count", "update_count", "update_ms_total",
    "shard_state_bytes", "shard_state_resets",
    # commit votes
    "commit_count", "commit_ms_total", "committed_steps",
    "aborted_steps",
    # durable checkpoints
    "ckpt_corrupt_quarantined", "ckpt_recover_fallbacks",
    "ckpt_recover_legacy", "ckpt_cold_starts", "ckpt_save_skipped",
    # live publication (serving tier)
    "publish_count", "publish_skipped", "publish_ms_total",
    "publish_last_generation",
    # transport retries
    "retry_count", "retry_ms_total", "retry_giveups",
    # degraded-mode groups (docs/design/degraded_mode.md)
    "degraded_capacity_fraction", "degrade_events_total",
    "restore_events_total",
    # adaptive FT policy (docs/design/adaptive_policy.md)
    "policy_current", "policy_switches_total",
    "policy_switch_refusals", "policy_switch_deferrals",
    "failure_rate", "wire_quant_residual_bytes",
    "allreduce_int8_ring_bytes_total",
    # observability tier (docs/design/observability.md)
    "trace_spans_total", "trace_spans_dropped", "flight_dumps_total",
    # spot-instance churn (docs/design/churn.md)
    "preempt_notices_total", "preempt_drain_deferrals_total",
    "preempt_deadline_expired_total", "graceful_exits_total",
    "prejoin_heals_total", "joins_coalesced_total",
    "reconfigures_per_min",
    # fleet health plane (docs/design/fleet_health.md): the
    # lighthouse's per-requester hint, refreshed every quorum round
    "fleet_p95_ms", "straggler_score", "fleet_groups",
    "slo_breach", "slo_breaches_total",
    # straggler-aware rebalance (docs/design/fleet_rebalance.md): the
    # fraction in force plus commit-boundary adoption accounting —
    # unconditional, like the degraded-mode trio above
    "rebalance_fraction", "rebalance_adoptions_total",
    "rebalance_deferred_total",
    # RAM checkpoint tier (docs/design/memory_tier.md) — the Manager
    # half only; the store/replicator counters merge in when the tier
    # is armed (see test_ram_tier_merges_keys)
    "ram_ckpt_heals_total", "ram_replicate_skipped",
    "ram_replicate_errors_total", "ram_replica_collapses_total",
    # transport substrate (docs/design/transport_substrate.md):
    # per-QoS-class byte volume, scheduler waits (grants that queued
    # behind another class), async-core connection/request totals, and
    # the sendfile fast-path volume — merged unconditionally (the
    # substrate is process-wide, like the jit-cache stats)
    "transport_qos_ring_bytes_total",
    "transport_qos_heal_bytes_total",
    "transport_qos_publication_bytes_total",
    "transport_qos_demotion_bytes_total",
    "transport_qos_waits_total", "transport_conns_total",
    "transport_requests_total", "transport_sendfile_bytes_total",
    # state attestation (docs/design/state_attestation.md): commit-
    # boundary digest accounting, the quarantine latch + ladder
    # counters, and the digest kernel's trace-time tripwire
    "sdc_digests_total", "sdc_digest_failures", "sdc_digest_ms_total",
    "sdc_quarantined",
    "sdc_quarantines_total", "sdc_quarantine_clears_total",
    "sdc_reheals_total", "sdc_refusals_total", "sdc_chaos_flips_total",
    "sdc_digest_cache_misses",
])

# Merged into metrics() only while the RAM tier is armed
# (Manager.enable_ram_tier) — same conditional-merge contract as the
# serving keys in test_attached_publisher_merges_serving_keys.
RAM_TIER_KEYS = frozenset([
    # RamCheckpointStore (peer-push acceptance side)
    "ram_ckpt_images", "ram_ckpt_stored_bytes",
    "ram_ckpt_accepts_total", "ram_ckpt_rejects_total",
    "ram_ckpt_evictions_total", "ram_ckpt_losses_total",
    # RamReplicator (push + demotion side)
    "ram_ckpt_replications_total", "ram_ckpt_bytes_replicated_total",
    "ram_ckpt_push_failures_total", "ram_ckpt_peers",
    "ram_demote_errors", "ram_demote_fatal", "ram_demote_stalls",
    "demote_stage_ms_total", "demote_encode_ms", "demote_ram_ms",
    "demote_replicate_ms", "demote_disk_ms", "demote_durable_ms",
])

# Latency-reservoir quantile keys rendered as ONE Prometheus summary
# family (torchft_quorum_ms{quantile="..."} + _sum/_count) instead of
# bare torchft_<key> gauges — tracing.SUMMARY_SPECS. They stay plain
# numeric keys in Manager.metrics() (the JSON surface is unchanged);
# only the text exposition differs. quorum_ms_max keeps its own gauge
# (summaries have no max slot).
SUMMARY_CONSUMED_KEYS = frozenset(["quorum_ms_p50", "quorum_ms_p95"])

# String-valued diagnostics, SPLIT from the numeric dict at the source
# (Manager.metrics_info): the Prometheus /metrics endpoint renders them
# as one torchft_info label set and the numeric invariant below needs
# no per-key carve-outs.
DOCUMENTED_INFO_KEYS = frozenset([
    "policy_name", "policy_last_reason", "ckpt_last_error",
    "flight_last_path", "ring_topology", "straggler_stage",
])

# Span context tags every exported trace event must carry (the fleet
# merger aligns on quorum_id/epoch/step; dashboards group by the rest).
REQUIRED_TRACE_TAGS = frozenset(tracing.CONTEXT_TAGS)

# Who recorded a span and under what: on every span dict and, as args, on
# every exported event (the benchmark's readers and self time go by them).
REQUIRED_SPAN_FIELDS = frozenset(["thread", "thread_id", "id", "parent"])

# The documented stages (docs/design/observability.md's taxonomy): one
# Perfetto track each, in protocol order. Add, never drop.
DOCUMENTED_STAGES = (
    "step_begin", "dispatch", "wait_quorum",
    "quorum", "reconfigure", "heal", "heal_stripe", "heal_adopt",
    "fetch_dispatch", "fetch_wait",
    "ring", "ring_preamble", "hier_intra", "hier_leader", "put",
    "exchange_wait",
    "overlap_drain", "drain", "pre_vote", "vote", "post_vote",
    "publish_status", "state_digest", "update", "ckpt_save", "publish",
    "heal_manifest", "heal_recv", "heal_verify", "heal_place",
)


class TestMetricsSchema:
    def test_every_documented_key_present(self):
        m = make_manager()
        try:
            got = set(m.metrics())
            missing = DOCUMENTED_KEYS - got
            assert not missing, (
                f"Manager.metrics() lost documented counter key(s): "
                f"{sorted(missing)} — dashboards/runbook/bench readers "
                "depend on these by name. If this is a deliberate "
                "rename, update every reader AND this snapshot.")
        finally:
            m.shutdown()

    def test_all_values_are_numeric(self):
        """EVERY metrics() value must be JSON-safe numeric — not just
        the documented set, and with no per-key carve-outs: string
        diagnostics live in metrics_info(), and the Prometheus
        exposition renders metrics() samples unconditionally."""
        m = make_manager()
        try:
            for key, val in m.metrics().items():
                assert isinstance(val, (int, float)) and \
                    not isinstance(val, bool), (
                        f"{key} is {type(val).__name__}, expected "
                        "int/float — string diagnostics belong in "
                        "metrics_info()")
        finally:
            m.shutdown()

    def test_info_split_from_numeric(self):
        """metrics_info() carries the documented string diagnostics —
        all str — and none of them leak back into metrics()."""
        m = make_manager()
        try:
            info = m.metrics_info()
            missing = DOCUMENTED_INFO_KEYS - set(info)
            assert not missing, sorted(missing)
            for key, val in info.items():
                assert isinstance(val, str), key
            assert info["policy_name"]
            overlap = DOCUMENTED_INFO_KEYS & set(m.metrics())
            assert not overlap, (
                f"string diagnostic key(s) {sorted(overlap)} leaked "
                "into the numeric metrics() dict")
        finally:
            m.shutdown()

    def test_attached_publisher_merges_serving_keys(self):
        """Attaching a WeightPublisher via publish() must surface the
        serving tier's counters in the same snapshot."""
        from torchft_tpu.serving import WeightPublisher

        m = make_manager()
        try:
            pub = WeightPublisher()
            gen = m.publish(pub)
            assert gen == 1
            mx = m.metrics()
            for key in ("publish_generations", "publish_delta_ratio_last",
                        "publish_payload_bytes_last", "serve_requests",
                        "serve_bytes_sent", "publish_generation_last",
                        "publish_step_last",
                        # quantized delta publication (ISSUE 20)
                        "publish_delta_leaves_last",
                        "publish_delta_fallback_leaves_last",
                        "publish_delta_wire_bytes_last",
                        "publish_delta_encode_ms_total",
                        "publish_delta_sets",
                        "serve_delta_requests", "serve_delta_bytes_sent",
                        # self-organizing relay tier
                        "relay_beats", "relay_steers", "relays_live",
                        "relay_children_total", "relay_lag_gens_max",
                        "serve_children"):
                assert key in mx, key
            assert mx["publish_count"] == 1
            assert mx["publish_last_generation"] == 1
        finally:
            m.shutdown()

    def test_ram_tier_merges_keys(self):
        """Arming the RAM checkpoint tier must surface the store and
        replicator counters in the same metrics() snapshot — and they
        must be absent while the tier is off (the Manager half of the
        schema stays unconditional either way)."""
        m = make_manager()
        try:
            off = set(m.metrics())
            leaked = RAM_TIER_KEYS & off
            assert not leaked, (
                f"RAM-tier key(s) {sorted(leaked)} present with the "
                "tier disarmed — these are documented as merge-on-arm")
            m.enable_ram_tier(peers=1)
            mx = m.metrics()
            missing = RAM_TIER_KEYS - set(mx)
            assert not missing, sorted(missing)
            for key in RAM_TIER_KEYS:
                val = mx[key]
                assert isinstance(val, (int, float)) and \
                    not isinstance(val, bool), key
        finally:
            m.shutdown()


class TestPrometheusExposition:
    """Freeze the /metrics exposition names: every documented counter
    renders as torchft_<key> with the repo's counter/gauge typing rule,
    and the string diagnostics render as ONE torchft_info sample."""

    def test_documented_names_render(self):
        m = make_manager(replica_id="metrics-schema")
        try:
            text = tracing.prometheus_text(
                m.metrics(), m.metrics_info(),
                labels={"replica_id": m.replica_id()})
        finally:
            m.shutdown()
        for key in DOCUMENTED_KEYS - SUMMARY_CONSUMED_KEYS:
            assert f"torchft_{key}{{" in text, (
                f"/metrics lost sample torchft_{key}")
        assert 'torchft_info{' in text
        for key in DOCUMENTED_INFO_KEYS:
            assert f'{key}="' in text, (
                f"torchft_info lost label {key}")
        assert 'replica_id="metrics-schema"' in text
        # The reservoir quantiles render as ONE summary family now.
        assert "# TYPE torchft_quorum_ms summary" in text
        assert 'quantile="0.5"' in text and 'quantile="0.95"' in text
        assert "torchft_quorum_ms_sum{" in text
        assert "torchft_quorum_ms_count{" in text
        # ...while the exact max stays its own gauge, and the bare
        # quantile gauges are GONE (consumed, not duplicated).
        assert "torchft_quorum_ms_max{" in text
        assert "torchft_quorum_ms_p50{" not in text
        assert "torchft_quorum_ms_p95{" not in text

    def test_counter_vs_gauge_rule(self):
        text = tracing.prometheus_text(
            {"x_total": 1, "y_count": 2.0, "z_ms_last": 3.0})
        assert "# TYPE torchft_x_total counter" in text
        assert "# TYPE torchft_y_count counter" in text
        assert "# TYPE torchft_z_ms_last gauge" in text

    def test_help_and_type_on_every_family(self):
        """Prometheus exposition-format conformance: every sample line
        belongs to a family that was preceded by # HELP and # TYPE
        lines (scrapers surface HELP text; some strict parsers reject
        TYPE-less families)."""
        m = make_manager()
        try:
            text = tracing.prometheus_text(
                m.metrics(), m.metrics_info(),
                labels={"replica_id": m.replica_id()})
        finally:
            m.shutdown()
        helped, typed = set(), set()
        for line in text.splitlines():
            if line.startswith("# HELP "):
                helped.add(line.split()[2])
            elif line.startswith("# TYPE "):
                typed.add(line.split()[2])
            elif line and not line.startswith("#"):
                name = line.split("{", 1)[0].split(" ", 1)[0]
                base = name
                # summary sub-samples belong to the base family
                for suffix in ("_sum", "_count"):
                    if name.endswith(suffix) and \
                            name[: -len(suffix)] in typed:
                        base = name[: -len(suffix)]
                assert base in typed, f"{name} has no # TYPE"
                assert base in helped, f"{name} has no # HELP"

    def test_summary_quantile_values_match_metrics(self):
        """The summary's quantile samples carry the reservoir's p50/p95
        values verbatim — renamed, not recomputed."""
        text = tracing.prometheus_text(
            {"quorum_ms_p50": 12.5, "quorum_ms_p95": 99.25,
             "quorum_ms_total": 250.0, "quorum_count": 20})
        assert 'torchft_quorum_ms{quantile="0.5"} 12.5' in text
        assert 'torchft_quorum_ms{quantile="0.95"} 99.25' in text
        assert "torchft_quorum_ms_sum 250.0" in text
        assert "torchft_quorum_ms_count 20.0" in text

    def test_large_counters_keep_full_precision(self):
        """A %g-style 6-sig-digit render freezes counters past 1e6
        (1000000 and 1000001 both print '1e+06'), zeroing Prometheus
        rate() exactly where byte counters live — values must render
        with full float precision."""
        a = tracing.prometheus_text({"x_total": 1_000_000.0})
        b = tracing.prometheus_text({"x_total": 1_000_001.0})
        assert a != b
        assert "1000001" in b

    def test_label_escaping(self):
        text = tracing.prometheus_text(
            {"a": 1}, {"weird": 'x"y\\z\n'}, labels={"replica_id": "r"})
        assert 'weird="x\\"y\\\\z\\n"' in text


class TestFleetExpositionSchema:
    """Freeze the fleet-side /fleet/metrics names the rebalance plane
    added (docs/design/fleet_rebalance.md): the aggregate gauges and the
    per-group fraction gauge — mirrored family-for-family by the C++
    lighthouse's fleet_metrics_text, so a rename here silently forks the
    two expositions."""

    def test_rebalance_families_render(self):
        from torchft_tpu import fleet

        agg = fleet.FleetAggregator()
        agg.ingest(fleet.StepDigest(replica_id="g0", step=1,
                                    step_wall_ms=100.0))
        text = fleet.status_prometheus(agg.aggregate())
        for family, typ in (
                ("torchft_fleet_rebalance_groups", "gauge"),
                ("torchft_fleet_rebalance_seq", "counter"),
                ("torchft_fleet_rebalance_fraction", "gauge")):
            assert f"# TYPE {family} {typ}" in text, family
        assert 'torchft_fleet_rebalance_fraction{replica_id="g0"} 1.0' \
            in text


class TestTraceEventSchema:
    """Freeze the /trace.json schema: Chrome trace-event JSON whose
    span phases are X (complete) and B/E (still-open at export), plus M
    metadata naming the process and one track per stage; every span
    carries the alignment/context tags."""

    def test_phases_and_required_tags(self):
        tr = tracing.Tracer(steps=4, enabled=True)
        tr.set_context(replica_id="g0", quorum_id=3, epoch=7, step=11,
                       policy_name="sync-f32")
        with tr.span("quorum", fast=True):
            pass
        with tr.span("vote", decision=True):
            pass
        open_span = tr.span("ring", kind="allreduce_wire")  # stays open
        trace = tr.chrome_trace()
        events = trace["traceEvents"]
        assert events, "empty trace"
        phases = {ev["ph"] for ev in events}
        assert phases <= {"X", "B", "E", "M"}, phases
        assert "X" in phases and "B" in phases and "E" in phases
        spans = [ev for ev in events if ev["ph"] in ("X", "B")]
        for ev in spans:
            missing = (REQUIRED_TRACE_TAGS | REQUIRED_SPAN_FIELDS) \
                - set(ev["args"])
            assert not missing, (ev["name"], sorted(missing))
            assert ev["args"]["step"] == 11
            assert ev["args"]["quorum_id"] == 3
            assert ev["args"]["epoch"] == 7
        # One track per stage: distinct stages -> distinct tids, named
        # by thread_name metadata.
        tid_of = {ev["name"]: ev["tid"] for ev in spans}
        assert len(set(tid_of.values())) == len(tid_of)
        named = {ev["args"]["name"] for ev in events
                 if ev["ph"] == "M" and ev["name"] == "thread_name"}
        assert named == set(tid_of)
        proc = [ev for ev in events
                if ev["ph"] == "M" and ev["name"] == "process_name"]
        assert proc and proc[0]["args"]["name"] == "g0"
        open_span.__exit__(None, None, None)

    def test_span_fields_and_stages(self):
        tr = tracing.Tracer(steps=4, enabled=True)
        with tr.span("publish_status"):
            with tr.span("state_digest", device=True):
                pass
        for rec in tr.spans():
            assert REQUIRED_SPAN_FIELDS <= set(rec), rec
            assert {"stage", "t0_ns", "dur_ns"} <= set(rec)
        assert tracing.STAGES == DOCUMENTED_STAGES
        # A known stage's track number is its place in the taxonomy.
        tid_of = {ev["name"]: ev["tid"]
                  for ev in tr.chrome_trace()["traceEvents"]
                  if ev["ph"] == "X"}
        assert tid_of == {
            "publish_status": DOCUMENTED_STAGES.index("publish_status") + 1,
            "state_digest": DOCUMENTED_STAGES.index("state_digest") + 1}

    def test_dispatch_tags(self):
        """A ``dispatch`` span says which program, whether the call was
        speculative and whether it traced; one that built a healer's
        program beside its heal says ``ahead`` and none of the other two
        (no call was made). The runbook's recovery recipe and the
        counters ``dispatch_traced_ms_total`` / ``dispatch_ahead_ms_total``
        go by these names."""
        import jax.numpy as jnp
        import optax

        from torchft_tpu.parallel import FTTrainer

        trainer = FTTrainer(
            loss_fn=lambda p, b: jnp.sum(p["w"] * b), tx=optax.sgd(0.1),
            params={"w": jnp.zeros(2)},
            manager_factory=lambda load, save: make_manager(
                load_state_dict=load, state_dict=save))
        try:
            batch = jnp.ones(2)
            trainer.train_step(batch)
            trainer._build_ahead(None, batch)
            mx = trainer.manager.metrics()
            events = [ev for ev in
                      trainer.manager.tracer().chrome_trace()["traceEvents"]
                      if ev["ph"] == "X" and ev["name"] == "dispatch"]
        finally:
            trainer.shutdown()
        known = REQUIRED_TRACE_TAGS | REQUIRED_SPAN_FIELDS
        call, ahead = ({k: v for k, v in ev["args"].items()
                        if k not in known} for ev in events)
        assert call == {"program": "fwd_bwd", "speculative": False,
                        "traced": True}
        assert ahead == {"program": "fwd_bwd", "ahead": True}
        assert mx["dispatch_ahead_count"] == 1
        assert mx["dispatch_ahead_ms_total"] > 0

    def test_open_spans_marked(self):
        tr = tracing.Tracer(steps=4, enabled=True)
        sp = tr.span("heal", donor="d:1")
        trace = tr.chrome_trace()
        begins = [ev for ev in trace["traceEvents"] if ev["ph"] == "B"]
        assert len(begins) == 1
        assert begins[0]["args"]["open"] is True
        sp.__exit__(None, None, None)
