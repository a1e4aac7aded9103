#!/usr/bin/env bash
# Local test runner, mirroring CI (reference scripts/test.sh: cargo test +
# pytest; here: cmake/ninja C++ tests + tiered pytest).
#
# Tiers, each with its wall clock printed. Measured (PR 27, 8 CPU
# cores): all of tests/ with -m 'not slow' on six xdist workers takes
# 156-167 s of wall and about 750 s of summed test time; the tiers
# below run in one process each and were not timed:
#   core   — C++ control-plane tests
#   unit   — protocol/state-machine/IO tests, no heavy compiles
#   heavy  — pallas-interpret kernels + sharded-jit parallelism tests
#   integ  — multi-replica-group scenarios (threads + real TCP)
# Nightly soaks (markers `nightly`/`slow`) are excluded from the
# per-commit tiers; run them on a schedule with
#   scripts/test.sh nightly
# which executes the failure-churn soaks AND the transport chaos soak
# (tests/test_chaos.py — seeded resets/latency/short-writes injected
# into store, manager RPC, heal, and ring; see
# docs/design/chaos_and_retry.md). Chaos can also be layered onto any
# tier ad hoc via TORCHFT_CHAOS="seed=...;ring:reset_rate=0.01,...".
set -euo pipefail
cd "$(dirname "$0")/.."

stage() {
    local name=$1; shift
    local t0=$SECONDS
    "$@"
    echo "== ${name} tier: $((SECONDS - t0))s"
}

# Nightly tier: long soaks only (failure churn + transport chaos).
if [[ "${1:-}" == "nightly" ]]; then
    stage nightly python -m pytest tests/ -q -m "nightly or slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Bench-smoke tier: the bench's allreduce A/B scenarios at tiny sizes as
# a fast regression gate for the pipelined host allreduce — single-shot
# vs bucketed, bf16 wire byte halving on both legs, and a chaos-enabled
# variant (TORCHFT_CHAOS short reads through the wire ring's segment
# upcast). bench_smoke tests are also marked `slow`, so tier-1 per-commit
# time is unaffected; run this tier on allreduce/bench changes.
if [[ "${1:-}" == "bench-smoke" ]]; then
    stage bench-smoke env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_bench_smoke.py -q -m bench_smoke
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Overlap tier: the cross-step overlap engine's focused gate — the
# deferred-commit state machine, bitwise equivalence with the one-step-
# shifted schedule (single group, two-group socketpair ring, and
# through a mid-run heal), stale-grad drop on replica death, the
# deterministic sync-vs-overlap >=1.5x A/B, and the bf16 pack/fetch
# regression guards (see docs/design/overlap.md). These tests are
# tier-1 too (not marked slow); this tier reruns just them on
# overlap/optim/manager changes. The overlap CHAOS soak
# (tests/test_chaos.py, overlap_steps=1 rounds) is marked
# nightly+slow and rides the nightly tier.
if [[ "${1:-}" == "overlap" ]]; then
    stage overlap env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_overlap.py -q -m overlap
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Shard tier: the cross-replica sharding layer's focused gate
# (docs/design/sharded_update.md) — reduce-scatter-vs-allreduce bitwise
# identity at worlds 2/3/5 (exact + bf16 wire), the sharded optimizer's
# stripe update + allgather E2E equivalence, healer-flow and latched-
# error drop semantics, the torrent-striped multi-donor heal (donor
# death mid-stripe, seed-shuffled load spread, shared serve-window
# plan), and the sharded durable checkpoint format (set condemnation,
# fallback, pruning). Tier-1 too (not marked slow); this tier reruns
# just them on communicator/optim/heal/checkpoint changes. The striped
# round of the heal soak (tests/test_chaos.py) is nightly.
if [[ "${1:-}" == "shard" ]]; then
    stage shard env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_shard.py -q -m shard
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Control-plane tier: the quorum fast path / coalesced heartbeats /
# warm-standby failover gate (docs/design/control_plane.md) — manager-side
# fast/slow round accounting + latency reservoir (no native needed), the
# piggybacked-beat freshness and fast-path hit/epoch protocol tests, and
# the standby SIGKILL failover acceptance (bitwise params, frozen
# reconfigure_count, observable redials). The C++ invalidation matrix runs
# in the `core` tier (core_test.cc). The SIGSTOP black-hole chaos round
# and the 64-client latency A/B are nightly+slow and ride the nightly
# tier; run this tier on lighthouse/manager/rpc changes.
if [[ "${1:-}" == "control-plane" ]]; then
    stage control-plane env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_control_plane.py -q -m control_plane
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Serve tier: the weight-distribution tier's focused gate
# (docs/design/serving.md) — the delta-publication protocol (head /
# manifest / ranged generations, eviction, long-poll), delta minimality
# byte accounting, the crc-verified atomic swap under TORCHFT_CHAOS net
# faults (torn-read guarantee, publisher restart, relay death
# failover), the relay tree, staleness bounds, Manager.publish commit
# coupling, and ranged-fetch connection reuse. Tier-1 too (not marked
# slow); this tier reruns just them on serving/checkpointing/manager
# changes. The seeded subscriber-churn soak (kill/revive of subscribers
# and a relay mid-publish) is marked nightly+slow and rides the nightly
# tier.
if [[ "${1:-}" == "serve" ]]; then
    stage serve env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_serving.py -q -m serve
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Relay tier: the CDN-scale serving gate (docs/design/serving.md) —
# quantized delta publication (the tft-publish-delta-1 doc/data routes,
# per-leaf wire+recon crc verification with automatic exact-f32
# fallback, verbatim relay adoption so grandchildren get bitwise the
# root's reconstruction), the lock-striped relay beat table (TTL prune,
# least-loaded pick, between-beat assignment spreading), steering
# (head hints, subscriber re-parenting, dead-hint cooldown), and relay
# registration/death re-parenting. Tier-1 and native-free; this tier
# reruns just them on serving/bench changes. The steered-delta churn
# soak is marked nightly+slow and rides the nightly tier.
if [[ "${1:-}" == "relay" ]]; then
    stage relay env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_serving.py -q -m relay
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Policy tier: the adaptive FT policy layer's focused gate
# (docs/design/adaptive_policy.md) — FTPolicy/PolicyController
# ladder+hysteresis units, the Manager's commit-boundary switch
# machinery (refusal mid-heal/mid-deferred, state-dict adoption,
# fake-store decider/follower coordination incl. the
# switch-racing-a-heal deferral), the int8+error-feedback wire rung
# (socketpair-ring bitwise identity at worlds 2/3/5, ~1/4 ring bytes,
# EF drift A/B, wire-format-skew detection), DiLoCo set_sync_every,
# and AdaptiveTrainer mode transitions. Tier-1 too (not marked slow);
# run this tier on policy/manager/communicator/host changes. The
# phase-varying adaptive-vs-fixed chaos soak is nightly+slow and rides
# the nightly tier.
if [[ "${1:-}" == "policy" ]]; then
    stage policy env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_policy.py -q -m "policy and not slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Degrade tier: the degraded-mode groups' focused gate
# (docs/design/degraded_mode.md) — surviving-submesh derivation +
# sharding fallback re-derivation, the weighted canonical-order fold
# over socketpair rings (bitwise vs the numpy oracle at worlds 2/3,
# int8 rung, reduce-scatter stripes, weight-mode/geometry skew aborts),
# the chaos `device` channel, the Manager's degrade/restore lifecycle
# (boundary refusals, flight dumps, the atomic capacity-bearing
# participant_slot snapshot), ElasticSampler capacity draws, and the
# DegradedModeDriver re-pjit lifecycle. Tier-1 too (not marked slow);
# run this tier on degraded/manager/host/data/parallel changes. The
# 2-group chip-loss goodput soak (>= 70%-of-healthy gate, bench row
# degraded_goodput_ab) is nightly+slow and rides the nightly tier.
if [[ "${1:-}" == "degrade" ]]; then
    stage degrade env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_degraded.py -q -m "degrade and not slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Transport tier: the data-plane transport's focused gate
# (docs/design/hier_transport.md) — the power-of-two int8 quantizer's
# device/host bitwise parity (payloads + error-feedback residual
# trajectories), the Manager-level device-vs-host quantize A/B (~1/4
# D2H bytes, identical results), the schedule-fingerprint residual-
# migration guard, and the hierarchical two-level ring's socketpair
# battery (exact/bf16/int8/weighted bitwise vs the flat ring,
# leader-death latch, skew aborts, leader-leg byte scaling). Tier-1
# too (not marked slow); run this tier on host/communicator/manager
# fetch-path changes. The 4-group hier chaos soak (leader kill mid-op
# must recover like a ring reset) is marked nightly+slow and rides
# the nightly tier.
if [[ "${1:-}" == "transport" ]]; then
    stage transport env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_transport.py -q -m "transport and not slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Churn tier: the spot-instance churn arc's focused gate
# (docs/design/churn.md) — the seeded ChurnOrchestrator event stream,
# the graceful preemption drain state machine (notice/SIGTERM ->
# boundary drain -> farewell -> final sharded save -> advertisement
# withdrawal -> PreemptedExit; deferral mid-heal/mid-deferred/errored/
# aborted; deadline expiry + flight dump), manager-side join-coalescing
# and reconfigures-per-minute accounting, the pre-join heal
# (join backpressure over real checkpoint HTTP), chaos kill-latch
# rebirth for address-reusing replacements, and the 2-group
# graceful-vs-SIGKILL A/B drive over a real socketpair ring. Tier-1 too
# (not marked slow); run this tier on manager/chaos/lighthouse changes.
# The lighthouse-side join window + farewell-race regression run in the
# `core` tier (core_test.cc); the Poisson churn soak
# (bench_churn_goodput goodput + bitwise gates) is native-gated and
# rides the nightly tier.
if [[ "${1:-}" == "churn" ]]; then
    stage churn env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_churn.py -q -m "churn and not slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# RAM checkpoint tier: the memory-tier arc's focused gate
# (docs/design/memory_tier.md) — the in-memory v2 image codec (bitwise
# vs the disk spelling, crc verify/reject), staged ranged peer pushes
# over the heal transport, the RamReplicator demotion pipeline
# (encode -> RAM -> K peers -> disk -> durable) with its stall
# watchdog + fatal classification + sticky error latch, the chaos RAM
# band (peer-RAM loss / replication blackhole / correlated K-peer
# death latches), Manager coupling (commit-coupled dispatch + refusal
# classes, healset peer discovery with tombstone filtering,
# RAM-preferring prejoin/cold-start rungs, replication-set collapse
# one-shot + flight dump), and the recovery-ladder bench gate
# (bench_recovery_tiers ram_speedup >= 2x at tiny scale). Tier-1 and
# native-free (FakeStore peers over local HTTP; not marked slow); run
# this tier on ram_ckpt/checkpoint_io/checkpointing/manager/chaos
# changes. The RAM-on/off churn-goodput soak is native-gated and rides
# the nightly tier (tests/test_churn.py::TestChurnSoak).
if [[ "${1:-}" == "ramckpt" ]]; then
    stage ramckpt env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_ram_ckpt.py -q \
        -m "ramckpt and not slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Fleet tier: the fleet health plane's focused gate
# (docs/design/fleet_health.md) — the straggler-score/attribution
# battery against the pure-Python aggregator mirror (known-skew fleets,
# single-group no-NaN, healer/degraded exclusion, staleness/farewell
# pruning), the SLO engine's thresholds and (slo, group, step) dedup,
# the frozen /fleet/metrics exposition names, the Manager's digest-push
# deltas + hint consumption + SLO-breach flight dump, tracefleet's
# --fleet resolution over a live stub, and benchdiff's regression
# gating. Tier-1 and native-free (not marked slow); run this tier on
# fleet/lighthouse/manager/tracing changes. The native 4-group
# piggyback drive (slowed group leads the ranking, ring attributed,
# breach echoed to it alone, C++-vs-Python aggregator parity) and the
# churn-coherence soak are nightly+slow and ride the nightly tier.
if [[ "${1:-}" == "fleet" ]]; then
    stage fleet env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_fleet.py -q -m "fleet and not slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Obs tier: the observability tier's focused gate
# (docs/design/observability.md) — span-ring bounds/context, the
# flight recorder's triggers (vote abort, latched comm error, heal
# failover, policy escalation, crash exit) and dump shape, the
# /trace.json + /metrics endpoints over real HTTP, the Prometheus /
# trace-event schema freezes, event-log monotonic ordering, and the
# tracefleet merge. Tier-1 too (not marked slow); run this tier on
# tracing/manager/checkpointing changes. The 2-group injected-ring-
# reset chaos round (a flight dump must be produced, parseable, and
# fleet-mergeable) is marked nightly+slow and rides the nightly tier.
if [[ "${1:-}" == "obs" ]]; then
    stage obs env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_tracing.py tests/test_metrics_schema.py \
        -q -m "obs and not slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Cold-start tier: seeded kill-all → cold-restart soak — every round a
# 2-group job checkpoints under disk chaos (torn writes, silent
# bit-flips, ENOSPC), the whole fleet "dies", and recovery must come
# back from the newest verified committed snapshot: never loading
# unverified bytes, never regressing past the newest clean save (see
# docs/design/durable_checkpoints.md). cold_start tests are also marked
# `slow`+`nightly`, so they ride the nightly tier too; run this tier on
# checkpoint_io / recovery changes.
if [[ "${1:-}" == "cold-start" ]]; then
    stage cold-start env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_cold_start.py -q -m cold_start
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Substrate tier: the shared transport plane's focused gate
# (docs/design/transport_substrate.md) — pooled ranged fetch client
# (reuse, redial-on-stale), the one ranged/bearer server core
# (200/206/416, 401, sendfile path), chunk_spans == shard_bounds
# geometry, the retry classification table, QoS weighted fairness under
# contention, and the chaos serve:/heal: channels injected at the
# substrate seam. Tier-1 and native-free; run this tier on
# transport/checkpointing/serving/ram_ckpt changes. Note the heal-soak
# and serve-churn nightly rounds now also ride the substrate: both
# tiers' byte paths (striped heal, publication fetch) are hosted by
# torchft_tpu/transport.py, so their chaos soaks are the substrate's
# endurance gate.
if [[ "${1:-}" == "substrate" ]]; then
    stage substrate env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_transport_substrate.py -q \
        -m "substrate and not slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Sdc tier: the silent-divergence arc's focused gate
# (docs/design/state_attestation.md) — the device digest kernel frozen
# against the NumPy reference across dtypes (plus the trace-time
# cache-miss tripwire), the pure-Python FleetAggregator attestation
# vote (strict majority, healer/absent/foreign-quorum abstention,
# sticky latch, the non-voter clear-on-match, farewell-clears vs
# prune-keeps), the read-time staleness bound (a SIGKILLed group ages
# out of baselines AND ballots), the ONE shared donor-admission
# predicate across all three resolvers, the Manager quarantine ladder
# (latch, refusal classes, checkpoint-server 503 gate, withdrawn
# advertisements, deferred clears), the chaos sdc: band (spec parse,
# stream purity, intensity/PhasedChaos, participants-only injection),
# and the seeded 3-group flip -> verdict -> auto-heal -> bitwise-
# converge soak. Tier-1 and native-free (not marked slow); run this
# tier on fleet/manager/chaos/serialization/checkpointing changes. The
# C++ lighthouse runs the same vote (the mirror contract) — its matrix
# is in the `core` tier; the PhasedChaos storm soak rides nightly.
if [[ "${1:-}" == "sdc" ]]; then
    stage sdc env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_attestation.py -q -m "sdc and not slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Rebalance tier: the straggler-aware fleet-rebalancing arc's focused
# gate (docs/design/fleet_rebalance.md) — the pure-Python Rebalancer
# ladder frozen against the C++ lighthouse mirror (the same snapshot
# literals core_test.cc pins), the fraction-table wire format, the
# Manager's decider-publishes/all-adopt commit-boundary protocol with
# save_durable's refusal classes, the composed capacity x rebalance
# effective fraction through participant_slot, ElasticSampler
# fractional/boost draws reporting exact fold weights, the chaos
# `slow:` band (spec parse, stream purity, natural-wall stretch), and
# the composed-fraction bitwise weighted-fold oracle over socketpair
# rings. Tier-1 and native-free (not marked slow); run this tier on
# fleet/manager/data/chaos changes. The C++ Rebalancer parity matrix
# is in the `core` tier; the PhasedChaos shrink -> restore zero-flap
# soak rides nightly.
if [[ "${1:-}" == "rebalance" ]]; then
    stage rebalance env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_rebalance.py -q \
        -m "rebalance and not slow"
    echo "== total: ${SECONDS}s"
    exit 0
fi

# Heal-soak tier: seeded chaos soak of repeated heals with donor churn —
# every round the primary donor is killed mid-stream while resets/short
# reads pepper the heal channel; each heal must complete bitwise-
# identical by failing over + resuming, with resumed bytes staying well
# under restart-from-zero cost (see docs/design/healing.md). heal_soak
# tests are also marked `slow`+`nightly`, so they ride the nightly tier
# too; run this tier on heal/checkpointing changes.
if [[ "${1:-}" == "heal-soak" ]]; then
    stage heal-soak env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_chaos.py -q -m heal_soak
    echo "== total: ${SECONDS}s"
    exit 0
fi

stage core bash -c '
    cmake -B torchft_tpu/_core/build -S torchft_tpu/_core -G Ninja \
        -DCMAKE_BUILD_TYPE=Release >/dev/null
    ninja -C torchft_tpu/_core/build
    ./torchft_tpu/_core/build/core_test'

stage unit  python -m pytest tests/ -q -m "not integration and not heavy and not nightly and not slow"
stage heavy python -m pytest tests/ -q -m "heavy and not nightly and not slow"
stage integ python -m pytest tests/ -q -m "integration and not nightly and not slow"

echo "== total: ${SECONDS}s"
