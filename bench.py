"""Benchmarks: FT efficiency, absolute throughput/MFU, multi-group traffic,
and recovery latency.

The reference publishes no numbers (BASELINE.md), so the headline metric is
the one its design claims and the north star targets: **FT efficiency** —
steps/sec with the full per-step fault-tolerance protocol (lighthouse
quorum, commit vote, checkpoint window, cross-group communicator) as a
fraction of raw jitted steps/sec on the same chip. North star: >= 0.90.

Prints ONE JSON line on stdout:
    {"metric": "ft_efficiency", "value": <ft steps/s>, "unit": "steps/s",
     "vs_baseline": <ft/raw efficiency vs the 0.90 target>}

Everything else (absolute img/s, achieved TFLOP/s + MFU, 2-replica-group
throughput with real cross-group HostCommunicator traffic, recovery steps
lost and wall-clock-to-heal — BASELINE.md's stated metrics) goes to stderr
as secondary JSON lines.

The scenario functions are importable; tests/test_bench_scenarios.py runs
them at tiny scale and asserts the recovery guarantees (<1 step lost).
"""

from __future__ import annotations

import json
import logging
import os
import statistics
import sys
import threading
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax


def _materialize(tree) -> float:
    """Force execution: fetch one scalar derived from the tree, so the
    timed region ends with a value that had to be computed."""
    leaf = jax.tree_util.tree_leaves(tree)[0]
    return float(jnp.sum(leaf))


_BENCH_SCHEMA = "tft-bench-2"
_PROVENANCE: Dict[str, Any] = {}


def _tracing_default() -> bool:
    from torchft_tpu import tracing as _tracing

    return _tracing.default_enabled()


def _provenance() -> Dict[str, Any]:
    """Environment stamp carried by every emitted row, so BENCH_r* files
    are comparable across rigs: the jax platform actually used, the jax
    version, a schema tag readers can dispatch on (rows predating the
    stamp are schema v1), the PROCESS-WIDE tracing default (rows whose
    scenario overrides it per-run — e.g. the trace A/B's legs — carry
    the truth in their own fields, which win over this stamp in
    _emit), the host CPU count (a "cpu" platform row from a 16-core
    box and one from a 1-core box are different rigs for every
    throughput metric — benchdiff treats a host-shape change like a
    platform change, skipped-not-gated), and the flight-recorder dump
    directory in force ("" = flight recording off) so an incident row
    points at its postmortem artifacts."""
    if not _PROVENANCE:
        _PROVENANCE.update({
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "host_cpus": os.cpu_count() or 1,
            "jax": jax.__version__,
            "schema": _BENCH_SCHEMA,
            "tracing_enabled": _tracing_default(),
            "flight_dir": os.environ.get("TORCHFT_FLIGHT_DIR", ""),
        })
    return dict(_PROVENANCE)


def _ab_server_cores(fn, **kw):
    """Run ``fn`` under both transport hosting cores — the legacy
    threaded server first (``TORCHFT_ASYNC_SERVER=0``, read per server
    start) and then the default async event loop — returning
    ``(threaded, async_)`` results. The cut-over A/B of ISSUE 17: the
    async leg must hold or beat the threaded leg on the same rig."""
    prev = os.environ.get("TORCHFT_ASYNC_SERVER")
    os.environ["TORCHFT_ASYNC_SERVER"] = "0"
    try:
        threaded = fn(**kw)
    finally:
        if prev is None:
            os.environ.pop("TORCHFT_ASYNC_SERVER", None)
        else:
            os.environ["TORCHFT_ASYNC_SERVER"] = prev
    return threaded, fn(**kw)


def _emit(obj: Dict[str, Any]) -> None:
    # Provenance first: a row's OWN fields win, so scenarios that
    # override an ambient knob per-run (tracing_enabled in the trace
    # A/B) report what was actually measured.
    print(json.dumps({**_provenance(), **obj}), file=sys.stderr)


# Peak dense matmul throughput per chip, bf16 (f32 is ~half). Sources:
# public TPU spec sheets. Used only for the advisory MFU line.
_PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5e": 197.0,
    "TPU v5 lite": 197.0,
    "TPU v5p": 459.0,
    "TPU v6e": 918.0,
}


def _peak_tflops() -> float:
    kind = jax.devices()[0].device_kind
    for name, peak in _PEAK_BF16_TFLOPS.items():
        if name.lower() in kind.lower():
            return peak
    raise ValueError(
        f"no bf16 peak on record for device_kind {kind!r}; add it to "
        f"_PEAK_BF16_TFLOPS with its source")


# --------------------------------------------------------------- scenario 0

def bench_rig_probes(mbytes: float = 4.0, reps: int = 3) -> Dict[str, float]:
    """Rig-drift probes, emitted with every run (a host-path swing must be
    attributable to the rig or to the code). Three numbers bound every
    host-path result:

    * ``d2h_mb_s`` / ``h2d_mb_s``: device<->host bandwidth on a ~4MB
      buffer — the legs the cross-group host allreduce rides; no allreduce
      design change can show below the time the gradient bytes take at
      that rate (not measured on an attached chip).
    * ``dispatch_ms``: one round trip of an already-compiled no-op —
      the per-dispatch floor every device_put/get pays on top of bytes.

    Read BENCH_rNN comparisons against these: if steps/s moved but the
    probes moved proportionally, it's the rig; if the probes held and
    steps/s moved, it's the code."""
    n = int(mbytes * 1e6 / 4)
    host = np.random.default_rng(0).normal(size=(n,)).astype(np.float32)
    bump = jax.jit(lambda a: a + 1)
    probe = jax.jit(lambda a: a + 1)
    _materialize(probe(jnp.zeros(())))
    base = jax.device_put(host)
    _materialize(bump(base))  # compile outside the timed region

    d2h, h2d, disp = [], [], []
    for _ in range(reps):
        # The fetched buffer must be a FRESH device computation every rep:
        # jax caches the host copy on the Array after the first fetch
        # (and device_put results retain theirs), so re-fetching the same
        # array reads host RAM and reports a host-memory rate as "D2H".
        dev = bump(base)
        dev.block_until_ready()
        t0 = time.perf_counter()
        np.asarray(jax.device_get(dev))
        d2h.append(mbytes / (time.perf_counter() - t0))
        t0 = time.perf_counter()
        jax.device_put(host).block_until_ready()
        h2d.append(mbytes / (time.perf_counter() - t0))
        t0 = time.perf_counter()
        _materialize(probe(jnp.zeros(())))
        disp.append((time.perf_counter() - t0) * 1e3)
    return {
        "d2h_mb_s": statistics.median(d2h),
        "h2d_mb_s": statistics.median(h2d),
        "dispatch_ms": statistics.median(disp),
        "probe_mbytes": mbytes,
    }


# --------------------------------------------------------------- scenario 1

def bench_single_group(steps: int = 20, segments: int = 3,
                       batch: int = 1024) -> Dict[str, float]:
    """Raw fused step vs full-FT step on one replica group (BASELINE.md
    config 1 shape: ResNet-18/CIFAR-10). Alternates raw/FT measurement
    segments and takes medians — a one-chip machine shares its host's CPU
    cores, and interleaving cancels slow drift out of the ratio."""
    from torchft_tpu import HostCommunicator, Lighthouse, Manager
    from torchft_tpu.models import ResNet18
    from torchft_tpu.parallel import FTTrainer

    # Per-chip batch 1024 by default: CIFAR-sized convs only fill the MXU
    # with a deep batch dimension (the early 3x3x64 layers are
    # matmul-shallow otherwise).
    model = ResNet18(num_classes=10)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 32, 32, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(batch,)), jnp.int32)
    b = {"x": x, "y": y}

    def loss_fn(params, model_state, batch_):
        logits, new_state = model.apply(
            {"params": params, **model_state}, batch_["x"], train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch_["y"]).mean()
        return loss, new_state

    variables = model.init(jax.random.key(0), x, train=True)
    params = variables["params"]
    bn_state = {"batch_stats": variables["batch_stats"]}
    tx = optax.sgd(0.1, momentum=0.9)

    def raw_step(p, st, o, b):
        (loss, st), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, st, b)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), st, o, loss

    raw = jax.jit(raw_step, donate_argnums=(0, 1, 2))
    p = jax.tree_util.tree_map(jnp.copy, params)
    st = jax.tree_util.tree_map(jnp.copy, bn_state)
    o = tx.init(p)

    # FLOPs of one step, from XLA's own cost model (for the MFU line).
    try:
        cost = raw.lower(p, st, o, b).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        step_flops = float(cost["flops"])
    except Exception:  # noqa: BLE001
        step_flops = None

    p, st, o, _ = raw(p, st, o, b)  # compile
    _materialize(p)

    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                    join_timeout_ms=100, quorum_tick_ms=10)
    trainer = FTTrainer(
        loss_fn=loss_fn, tx=tx, params=params, model_state=bn_state,
        manager_factory=lambda load, save: Manager(
            comm=HostCommunicator(timeout_sec=30),
            load_state_dict=load, state_dict=save, min_replica_size=1,
            replica_id="bench", lighthouse_addr=lh.address(),
            rank=0, world_size=1,
        ),
    )
    trainer.train_step(b)  # compile + first quorum
    _materialize(trainer.params)

    raw_sps, ft_sps = [], []
    for _ in range(segments):
        t0 = time.perf_counter()
        for _ in range(steps):
            p, st, o, _ = raw(p, st, o, b)
        _materialize(p)
        raw_sps.append(steps / (time.perf_counter() - t0))

        t0 = time.perf_counter()
        for _ in range(steps):
            _, committed = trainer.train_step(b)
            assert committed
        _materialize(trainer.params)
        ft_sps.append(steps / (time.perf_counter() - t0))

    trainer.shutdown()
    lh.shutdown()

    raw_med = statistics.median(raw_sps)
    ft_med = statistics.median(ft_sps)
    out = {
        "raw_steps_per_s": raw_med,
        "ft_steps_per_s": ft_med,
        "efficiency": ft_med / raw_med,
        "img_per_s": ft_med * batch,
        "batch": batch,
    }
    if step_flops:
        out["achieved_tflops"] = ft_med * step_flops / 1e12
    return out


# --------------------------------------------------------------- scenario 2

def bench_multigroup(n_groups: int = 2, steps: int = 20,
                     hidden: int = 512, depth: int = 2,
                     backend: str = "host",
                     bucket_bytes: int = 4 << 20,
                     wire_dtype: Optional[Any] = None,
                     overlap_steps: int = 0,
                     shard_update: bool = False,
                     tracing: Optional[bool] = None,
                     fleet_telemetry: Optional[bool] = None,
                     device_quantize: Optional[bool] = None,
                     policy: Optional[Any] = None,
                     hier_hosts: Optional[int] = None
                     ) -> Dict[str, float]:
    """N replica groups as threads, real cross-group gradient traffic.

    backend="host": device_get -> HostCommunicator ring allreduce over
    localhost TCP -> device_put (the path a single-group bench never
    touches — round-1 VERDICT weak #3).
    backend="mesh": the on-device full-membership fast path
    (backends/mesh.py) — gradients stay device-resident, the cross-group
    sum is one jitted XLA reduction, no serialization or sockets.

    ``hidden``/``depth`` size the gradient payload (hidden=512/depth=2
    ~1.2MB, the historical point; hidden=1024/depth=3 ~8.6MB, deep enough
    that main()'s 2MB buckets actually multi-bucket). The result carries
    the pipelined allreduce's per-stage busy times (fetch/ring/put, from
    Manager.metrics()) so a throughput swing is attributable to a stage —
    and, with bench_rig_probes' bandwidth lines, to the rig vs the code.

    ``overlap_steps=1`` runs the cross-step overlap engine
    (docs/design/overlap.md): step N's exchange drains under step N+1's
    compute; the result then also carries ``hidden_ms_avg`` /
    ``drain_wait_ms_avg`` (comm wall hidden behind compute vs still
    blocked on at the settle), the attribution the sync-vs-overlap A/B
    needs.

    ``tracing`` overrides the Manager's per-step span tracing (default:
    the ``TORCHFT_TRACING`` env default, i.e. on) — the knob the
    ``multigroup_8mb_trace_ab`` overhead A/B flips.

    ``fleet_telemetry`` overrides the quorum-piggybacked fleet health
    digest (docs/design/fleet_health.md; default: the
    ``TORCHFT_FLEET_TELEMETRY`` env default, i.e. on) — the knob the
    ``multigroup_8mb_fleet_ab`` overhead A/B flips. The result carries
    ``fleet_p95_ms``/``fleet_groups`` (the lighthouse's echoed hint) so
    the ON leg also proves the loop is actually closed.

    ``shard_update=True`` runs the ZeRO-style sharded weight update
    (docs/design/sharded_update.md): reduce-scatter instead of
    allreduce, stripe-local optimizer update, allgather of updated
    params. The result then carries ``update_ms_avg`` (the stripe
    update+allgather+reassembly wall from Manager.metrics()) and
    ``opt_state_mbytes`` shrinks to ~1/n_groups; ``commit_ms_avg``
    (the trainer's commit bucket, covering the optimizer apply + vote
    in BOTH modes) is the comparable update-stage wall for the A/B.

    ``device_quantize`` / ``policy`` thread straight through to the
    Manager — the ``multigroup_8mb_devquant_ab`` row flips the former
    and pins the int8 rung with the latter. ``hier_hosts=H`` simulates
    an H-host deployment on one machine: group i advertises host id
    ``bh{i % H}``, so the host backend detects co-location and builds
    the two-level ring (docs/design/hier_transport.md); the result's
    ``ring_topology`` records what was actually built and
    ``fetch_mbytes_per_step`` the ACTUAL D2H traffic (wire bytes under
    device-side quantization, not grad bytes)."""
    from torchft_tpu import (HostCommunicator, Lighthouse, Manager,
                             MeshCommunicator, MeshWorld)
    from torchft_tpu.models import MLP
    from torchft_tpu.parallel import FTTrainer

    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=n_groups,
                    join_timeout_ms=2000, quorum_tick_ms=10)
    mesh_world = MeshWorld(num_groups=n_groups, timeout_sec=60)

    def make_comm(i: int):
        if backend == "mesh":
            return MeshCommunicator(mesh_world)
        if hier_hosts:
            return HostCommunicator(timeout_sec=30,
                                    host_id=f"bh{i % hier_hosts}",
                                    hier=True)
        return HostCommunicator(timeout_sec=30, hier=False)
    model = MLP(features=(hidden,) * depth, num_classes=10)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(64,)), jnp.int32)

    def loss_fn(params, batch):
        logits = model.apply(params, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    params0 = model.init(jax.random.key(0), x[:1])
    n_params = sum(int(np.prod(np.shape(l)))
                   for l in jax.tree_util.tree_leaves(params0))
    results: Dict[str, Dict[str, float]] = {}

    policy_box: Dict[str, str] = {}
    topo_box: Dict[str, str] = {}

    def worker(gid: str) -> None:
        gidx = int(gid[1:])
        trainer = FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.05), params=params0,
            manager_factory=lambda load, save: Manager(
                comm=make_comm(gidx), load_state_dict=load,
                state_dict=save, min_replica_size=n_groups, replica_id=gid,
                lighthouse_addr=lh.address(), rank=0, world_size=1,
                quorum_timeout_ms=30_000,
                allreduce_bucket_bytes=bucket_bytes,
                allreduce_wire_dtype=wire_dtype,
                overlap_steps=overlap_steps,
                shard_update=shard_update,
                tracing=tracing,
                fleet_telemetry=fleet_telemetry,
                device_quantize=device_quantize,
                policy=policy,
            ),
        )
        # Stamp the policy in force so BENCH trajectories are
        # attributable to it (fixed-knob managers synthesize one).
        policy_box[gid] = trainer.manager.policy().name
        b = {"x": x, "y": y}
        trainer.train_step(b)  # compile + join + first reconfigure
        m0 = trainer.manager.metrics()
        lb_fn = getattr(trainer.manager._comm,
                        "hier_leader_bytes_total", None)
        lb0 = float(lb_fn()) if lb_fn is not None else 0.0
        t0 = time.perf_counter()
        done = 0
        commit_s = 0.0
        while done < steps:
            _, committed = trainer.train_step(b)
            commit_s += trainer.last_step_timings.get("commit", 0.0)
            if committed:
                done += 1
        # Overlap mode: settle the final in-flight step inside the timed
        # region — sync mode pays its last drain in-loop, so the A/B
        # must charge overlap its trailing settle too.
        trainer.flush()
        _materialize(trainer.params)
        dt = time.perf_counter() - t0
        mx = trainer.manager.metrics()
        # What the transport ACTUALLY built (resolved at configure,
        # after co-location detection) — stamped into every row.
        topo_box[gid] = trainer.manager.metrics_info().get(
            "ring_topology", "flat")
        # Leader-ring bytes come straight from the comm (leaders only;
        # members report 0) — the hier A/B sums them across groups.
        leader_bytes = ((float(lb_fn()) - lb0)
                        if lb_fn is not None else 0.0)

        def avg_ms(key: str) -> float:
            cnt = max(mx["allreduce_count"] - m0["allreduce_count"], 1)
            return (mx[key] - m0[key]) / cnt

        results[gid] = {
            "steps_per_s": steps / dt,
            "allreduce_ms_avg": avg_ms("allreduce_ms_total"),
            "fetch_ms_avg": avg_ms("allreduce_fetch_ms_total"),
            # Fetch split: dispatch (kicking off packs + async D2H) vs
            # wait (blocked on DMA) — a fetch-bound profile is only
            # actionable once you know which half it is.
            "fetch_dispatch_ms_avg":
                avg_ms("allreduce_fetch_dispatch_ms_total"),
            "fetch_wait_ms_avg": avg_ms("allreduce_fetch_wait_ms_total"),
            "ring_ms_avg": avg_ms("allreduce_ring_ms_total"),
            "put_ms_avg": avg_ms("allreduce_put_ms_total"),
            "wire_mbytes_per_step": avg_ms("allreduce_wire_bytes_total")
            / 1e6,
            # ACTUAL D2H fetch traffic per step (wire bytes under
            # device-side quantization — not grad bytes): the number
            # the fetch-wall optimization is judged by.
            "fetch_mbytes_per_step":
                avg_ms("allreduce_d2h_wire_bytes_total") / 1e6,
            # Bytes that crossed the TCP ring (vs D2H above): halved by
            # bf16 wire at 2 groups now that the narrow dtype rides
            # end-to-end.
            "ring_wire_mbytes_per_step":
                avg_ms("allreduce_ring_wire_bytes_total") / 1e6,
            # Hierarchical legs (0 on flat): loopback star traffic and
            # this group's cross-host leader-ring sends. Summed across
            # groups by the caller — per-group medians would hide that
            # only leaders carry the cross-host leg.
            "hier_intra_mbytes_per_step":
                avg_ms("hier_intra_bytes_total") / 1e6,
            "hier_leader_mbytes_per_step": leader_bytes / 1e6
            / max(mx["allreduce_count"] - m0["allreduce_count"], 1),
            # Overlap attribution (0 in sync mode): comm wall hidden
            # behind the next step's compute vs still blocked on at the
            # settle boundary.
            "hidden_ms_avg": avg_ms("allreduce_hidden_ms_total"),
            "drain_wait_ms_avg": avg_ms("allreduce_drain_wait_ms_total"),
            # Update-stage attribution for the rs A/B: the trainer's
            # commit bucket (optimizer apply + vote, comparable across
            # modes), the sharded update's own busy wall (0 in sync
            # mode), and the live optimizer-state footprint — stripe
            # state in shard mode (~1/n_groups), full tree otherwise.
            "commit_ms_avg": commit_s / max(steps, 1) * 1e3,
            "update_ms_avg": (
                (mx["update_ms_total"] - m0["update_ms_total"])
                / max(mx["update_count"] - m0["update_count"], 1)),
            "opt_state_mbytes": (
                mx["shard_state_bytes"] / 1e6 if shard_update
                else sum(
                    np.asarray(l).nbytes for l in
                    jax.tree_util.tree_leaves(trainer.opt_state)) / 1e6),
            # Control-plane attribution (docs/design/control_plane.md):
            # quorum latency distribution + the fraction of rounds served
            # from the lighthouse's membership-unchanged cache.
            "quorum_ms_p50": mx["quorum_ms_p50"],
            "quorum_ms_p95": mx["quorum_ms_p95"],
            # Fleet health hint as echoed by the lighthouse
            # (docs/design/fleet_health.md): nonzero on the ON leg of
            # the fleet A/B proves digests flowed round-trip.
            "fleet_p95_ms": mx["fleet_p95_ms"],
            "fleet_groups": mx["fleet_groups"],
            "quorum_fast_frac": (
                mx["quorum_fast_path_hits"]
                / max(mx["quorum_fast_path_hits"]
                      + mx["quorum_slow_path_rounds"], 1)),
        }
        trainer.shutdown()

    threads = [threading.Thread(target=worker, args=(f"g{i}",))
               for i in range(n_groups)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    lh.shutdown()

    med = {k: statistics.median(r[k] for r in results.values())
           for k in next(iter(results.values()))}
    return {
        "n_groups": n_groups,
        "backend": backend,
        "overlap_steps": overlap_steps,
        # The RESOLVED tracing state of this run (the per-run override
        # wins over the env default) — rows built from this result can
        # stamp what was actually measured.
        "tracing_enabled": (bool(tracing) if tracing is not None
                            else _tracing_default()),
        "policy": next(iter(policy_box.values()), "unknown"),
        "ring_topology": next(iter(topo_box.values()), "flat"),
        "steps_per_s": med["steps_per_s"],
        "allreduce_ms_avg": med["allreduce_ms_avg"],
        "grad_mbytes": n_params * 4 / 1e6,
        "stages_ms": {
            "fetch": med["fetch_ms_avg"],
            "fetch_dispatch": med["fetch_dispatch_ms_avg"],
            "fetch_wait": med["fetch_wait_ms_avg"],
            "ring": med["ring_ms_avg"],
            "put": med["put_ms_avg"],
        },
        "wire_mbytes_per_step": med["wire_mbytes_per_step"],
        "fetch_mbytes_per_step": med["fetch_mbytes_per_step"],
        "ring_wire_mbytes_per_step": med["ring_wire_mbytes_per_step"],
        # Cluster-wide sums (not medians): the hier byte-scaling A/B
        # compares TOTAL cross-host traffic, and only leaders carry
        # the leader leg — a median would average leaders with
        # members' zeros.
        "ring_wire_mbytes_per_step_total": sum(
            r["ring_wire_mbytes_per_step"] for r in results.values()),
        "hier_intra_mbytes_per_step": sum(
            r["hier_intra_mbytes_per_step"] for r in results.values()),
        "hier_leader_mbytes_per_step": sum(
            r["hier_leader_mbytes_per_step"] for r in results.values()),
        "hidden_ms_avg": med["hidden_ms_avg"],
        "drain_wait_ms_avg": med["drain_wait_ms_avg"],
        "commit_ms_avg": med["commit_ms_avg"],
        "update_ms_avg": med["update_ms_avg"],
        "opt_state_mbytes": med["opt_state_mbytes"],
        "quorum_ms_p50": med["quorum_ms_p50"],
        "quorum_ms_p95": med["quorum_ms_p95"],
        "quorum_fast_frac": med["quorum_fast_frac"],
    }


# --------------------------------------------------------------- scenario 1a

def bench_degraded_goodput(n_groups: int = 2, steps: int = 12,
                           hidden: int = 256, depth: int = 2,
                           batch_size: int = 32,
                           degrade_fraction: float = 0.5
                           ) -> Dict[str, float]:
    """Degraded-mode goodput A/B (docs/design/degraded_mode.md): N
    host-backend groups train with ElasticSampler-driven batches and
    the weighted canonical fold armed (``degraded_mode=True``); after a
    healthy phase, the LAST group "loses half its chips" — a capacity
    degrade to ``degrade_fraction``, the same transition the
    DegradedModeDriver lands on real device loss — and the run keeps
    going at nonuniform capacity.

    The metric is committed-samples/sec: the cluster's goodput should
    settle near ``1 - (1 - fraction)/n`` of the healthy baseline
    (~87.5% at 2 groups / half capacity with equal step walls, and
    never below the ~75% sample-rate floor), where whole-group
    eviction costs a full ``1/n`` (~50% at 2 groups). The nightly soak
    gates ``degraded_ratio >= 0.70``."""
    from torchft_tpu import HostCommunicator, Lighthouse, Manager
    from torchft_tpu.data import ElasticSampler
    from torchft_tpu.models import MLP
    from torchft_tpu.parallel import FTTrainer

    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=n_groups,
                    join_timeout_ms=2000, quorum_tick_ms=10)
    model = MLP(features=(hidden,) * depth, num_classes=10)
    rng = np.random.default_rng(0)
    n_rows = batch_size * 8
    x = jnp.asarray(rng.normal(size=(n_rows, 64)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(n_rows,)), jnp.int32)

    def loss_fn(params, batch):
        logits = model.apply(params, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    params0 = model.init(jax.random.key(0), x[:1])
    phase_gate = threading.Barrier(n_groups)
    lock = threading.Lock()
    samples = {"healthy": 0, "degraded": 0}
    walls: Dict[str, list] = {"healthy": [], "degraded": []}
    caps: Dict[str, float] = {}

    def worker(gid: int) -> None:
        trainer = FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.05), params=params0,
            manager_factory=lambda load, save: Manager(
                comm=HostCommunicator(timeout_sec=30),
                load_state_dict=load, state_dict=save,
                min_replica_size=n_groups, replica_id=f"dg{gid}",
                lighthouse_addr=lh.address(), rank=0, world_size=1,
                quorum_timeout_ms=30_000, degraded_mode=True))
        sampler = ElasticSampler(n_rows, trainer.manager,
                                 batch_size=batch_size, seed=0)
        drawn = {"k": 0}

        def batch():
            idx = sampler.next_indices()
            drawn["k"] = len(idx)
            return {"x": x[idx], "y": y[idx]}

        trainer.train_step(batch)  # compile + join + first reconfigure
        for phase in ("healthy", "degraded"):
            phase_gate.wait(timeout=120)
            if phase == "degraded" and gid == n_groups - 1:
                # The chip loss: landed at a commit boundary, nothing
                # in flight — exactly what DegradedModeDriver.tick does
                # after surviving_submesh on real device loss. A
                # refusal here is a harness bug (nothing can be
                # mid-heal/deferred at this barrier): fail loudly, not
                # via a -O-strippable assert that would let both
                # phases silently run healthy.
                if not trainer.manager.request_degrade(
                        degrade_fraction):
                    raise RuntimeError(
                        "degrade refused at an idle phase boundary")
            phase_gate.wait(timeout=120)
            trainer.train_step(batch)  # recompile off the clock
            t0 = time.perf_counter()
            done = 0
            got = 0
            while done < steps:
                _, committed = trainer.train_step(batch)
                if committed:
                    done += 1
                    got += drawn["k"]
            dt = time.perf_counter() - t0
            with lock:
                samples[phase] += got
                walls[phase].append(dt)
        caps[f"g{gid}"] = trainer.manager.metrics()[
            "degraded_capacity_fraction"]
        trainer.shutdown()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_groups)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    lh.shutdown()

    healthy = samples["healthy"] / max(max(walls["healthy"]), 1e-9)
    degraded = samples["degraded"] / max(max(walls["degraded"]), 1e-9)
    return {
        "n_groups": n_groups,
        "degrade_fraction": degrade_fraction,
        "healthy_samples_per_s": healthy,
        "degraded_samples_per_s": degraded,
        "degraded_ratio": degraded / max(healthy, 1e-9),
        # What whole-group eviction of the wounded group would leave.
        "eviction_ratio": (n_groups - 1) / n_groups,
        "capacity_fractions": dict(caps),
    }


# --------------------------------------------------------------- scenario 1c

def bench_rebalance_goodput(n_groups: int = 4, rounds: int = 60,
                            batch_size: int = 32,
                            slow_factor: float = 2.0,
                            tail: int = 20) -> Dict[str, float]:
    """Straggler-rebalancing goodput A/B
    (docs/design/fleet_rebalance.md), native-free: N lockstep groups
    with ONE persistently slow member, driven on a simulated clock
    through the real control loop — the ``fleet.Rebalancer`` ladder
    (adoption lagging one boundary, the decider-publish protocol's
    documented skew), real ``ElasticSampler`` draws sized by the
    assigned fraction, per-group walls proportional to samples drawn
    x per-sample cost. The uniform leg is plain lockstep data
    parallelism: every boundary waits for the slow group's full
    batch. The rebalance leg trims the straggler's slice toward the
    floor and reallocates it to the headroom groups, so the fleet
    boundary wall tracks the (boosted) fast groups instead.

    Headline: steady-tail committed-samples/sec vs the uniform leg
    (gate ``rebalance_ratio >= 0.8``; with walls this imbalanced it
    lands well ABOVE 1.0 — nonuniform parallelism strictly beats
    lockstep), the fraction floor (never below 0.5), ZERO table
    changes across the settled tail, and the weighted fold at the
    final composed weights bitwise against the single-process oracle
    over real socketpair rings."""
    import socket as _socket

    from torchft_tpu import fleet
    from torchft_tpu.backends.host import HostCommunicator, _Ring
    from torchft_tpu.data import ElasticSampler

    rids = [f"rb{i}" for i in range(n_groups)]
    slow_rid = rids[-1]
    cost_ms = {rid: (slow_factor if rid == slow_rid else 1.0)
               for rid in rids}
    overhead_ms = 5.0  # quorum + vote floor, fraction-independent

    class _Slot:
        """Duck-typed manager: the atomic slot snapshot the sampler
        draws by, recording the reported fold weight."""

        def __init__(self, rank: int) -> None:
            self.rank, self.committed, self.frac = rank, 0, 1.0
            self.samples: Optional[int] = None

        def participant_slot(self):
            return (self.rank, self.committed, self.frac)

        def set_step_samples(self, n: int) -> None:
            self.samples = int(n)

    # Uniform leg: every group draws the full batch, the boundary wall
    # is the straggler's.
    uniform_wall_ms = overhead_ms + batch_size * max(cost_ms.values())
    uniform_per_s = (n_groups * batch_size) / (uniform_wall_ms / 1e3)

    # Rebalance leg.
    rb = fleet.Rebalancer()
    slots = {rid: _Slot(i) for i, rid in enumerate(rids)}
    samplers = {rid: ElasticSampler(batch_size * 64, slots[rid],
                                    batch_size=batch_size, seed=0)
                for rid in rids}
    assigned = {rid: 1.0 for rid in rids}
    committed = 0
    min_fraction = 1.0
    tail_samples = 0
    tail_wall_ms = 0.0
    seq_at_tail = None
    for k in range(1, rounds + 1):
        draws: Dict[str, int] = {}
        walls: Dict[str, float] = {}
        for rid in rids:
            s = slots[rid]
            # The fraction adopted at the PREVIOUS boundary is the one
            # this draw runs under (one-boundary adoption lag).
            s.frac = assigned[rid]
            s.committed = committed
            idx = samplers[rid].next_indices()
            draws[rid] = len(idx)
            walls[rid] = overhead_ms + len(idx) * cost_ms[rid]
            if abs(s.frac - 1.0) > 1e-9 and s.samples != len(idx):
                raise RuntimeError(
                    "sampler did not report its draw as the fold "
                    f"weight ({s.samples} != {len(idx)})")
        if k > rounds - tail and seq_at_tail is None:
            seq_at_tail = rb.seq
        assigned = rb.observe(
            [(rid, k, walls[rid], slots[rid].frac, True)
             for rid in rids])
        min_fraction = min(min_fraction, min(assigned.values()))
        committed += n_groups
        if k > rounds - tail:
            tail_samples += sum(draws.values())
            tail_wall_ms += max(walls.values())
    tail_flaps = rb.seq - (seq_at_tail if seq_at_tail is not None
                           else rb.seq)
    rebalance_per_s = tail_samples / (tail_wall_ms / 1e3)

    # The weighted fold at the settled composed weights, bitwise on
    # every rank over real socketpair rings vs the documented oracle
    # (sum of w_r * x_r in rank order, true-divided by the total).
    weights = [int(round(batch_size * assigned[rid])) for rid in rids]
    rng = np.random.default_rng(7)
    xs = [rng.normal(size=4_099).astype(np.float32)
          for _ in range(n_groups)]
    pairs = [_socket.socketpair() for _ in range(n_groups)]
    rings = [_Ring(pairs[r][0], pairs[(r - 1) % n_groups][1],
                   _socket.socket())
             for r in range(n_groups)]
    comms = []
    for r in range(n_groups):
        c = HostCommunicator(timeout_sec=15)
        c._rank, c._world = r, n_groups
        comms.append(c)
    out: list = [None] * n_groups

    def fold(r: int) -> None:
        out[r] = comms[r]._do_allreduce_wire(
            rings[r], [xs[r].copy()], [np.dtype(np.float32)], "sum",
            "step", weights[r])

    ts = [threading.Thread(target=fold, args=(r,))
          for r in range(n_groups)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    for ring in rings:
        ring.close()
    for c in comms:
        c.shutdown()
    acc = np.zeros(4_099, np.float32)
    for w, x in zip(weights, xs):
        if w:
            acc += x * np.float32(w)
    acc /= np.float32(sum(weights))
    bitwise = all(o is not None and np.array_equal(o[0], acc)
                  for o in out)

    return {
        "n_groups": n_groups,
        "slow_factor": slow_factor,
        "rounds": rounds,
        "tail_rounds": tail,
        "uniform_samples_per_s": uniform_per_s,
        "rebalance_samples_per_s": rebalance_per_s,
        "rebalance_ratio": rebalance_per_s / max(uniform_per_s, 1e-9),
        "min_fraction": min_fraction,
        "floor": fleet.REBALANCE_FLOOR,
        "tail_flaps": tail_flaps,
        "shrinks_total": rb.shrinks_total,
        "restores_total": rb.restores_total,
        "adoption_lag_boundaries": 1,
        "bitwise_identical": bitwise,
    }


# --------------------------------------------------------------- scenario 1b

def bench_transformer(steps: int = 6, batch: int = 8, seq_len: int = 2048,
                      cfg: Optional[Any] = None) -> Dict[str, float]:
    """LLM training-step throughput + MFU on one chip: a ~440M-param
    Llama-recipe decoder (flash-attention kernel, bf16 compute, optax
    adamw) — the per-chip building block of BASELINE config 3. Shape
    chosen by an on-chip sweep: embed 1536 / 12 layers / batch 8 is the
    best MFU point that fits one v5e's HBM with full f32 adam state."""
    from torchft_tpu.models import (Transformer, TransformerConfig,
                                    chunked_causal_lm_loss)
    from torchft_tpu.ops import flash_attention

    if cfg is None:
        # head_dim 128 (12 heads), not 64 (24): the MXU contracts 128-wide,
        # so d=64 half-fills every QK^T/PV pass. 128 is also the
        # Llama-recipe head size at 7B+. A smaller ``cfg`` (the test
        # suite's smoke shape) comes only through the argument.
        cfg = TransformerConfig(vocab_size=32_000, num_layers=12,
                                embed_dim=1536, num_heads=12,
                                max_seq_len=2048,
                                attention_fn=flash_attention)

    model = Transformer(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(batch, seq_len)), jnp.int32)
    params = model.init(jax.random.key(0), tokens)
    n_params = sum(int(np.prod(np.shape(l)))
                   for l in jax.tree_util.tree_leaves(params))
    tx = optax.adamw(3e-4)

    def step_fn(p, o, toks):
        def loss_fn(p):
            # Chunked loss: the [B, S, vocab] logits tensor never
            # materializes, and the head matmul runs bf16-in/f32-accum
            # like the body's matmuls (models/transformer.py).
            hidden = model.apply(p, toks, return_hidden=True)
            return chunked_causal_lm_loss(
                hidden, p["params"]["lm_head"]["kernel"], toks,
                chunk_size=512, matmul_dtype=jnp.bfloat16)
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    step = jax.jit(step_fn, donate_argnums=(0, 1))
    opt = tx.init(params)
    # Analytic MODEL flops, the standard MFU numerator: 6*N per token for
    # the dense/embedding path (fwd 2N + bwd 4N) plus causal attention
    # (fwd QK^T+PV = 4*B*S^2*E_heads, bwd ~2.5x, halved by masking). XLA's
    # cost_analysis is wrong in both directions here: it counts a scan
    # body once (undercounting the chunked loss) and would count remat
    # recompute (which MFU by definition excludes).
    e_heads = cfg.num_heads * (cfg.embed_dim // cfg.num_heads)
    step_flops = (6.0 * n_params * batch * seq_len
                  + 3.5 * 4 * batch * seq_len ** 2 * e_heads
                  * cfg.num_layers * 0.5)

    params, opt, _ = step(params, opt, tokens)  # compile
    _materialize(params)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt, loss = step(params, opt, tokens)
    _materialize(params)
    dt = (time.perf_counter() - t0) / steps

    return {
        "n_params": n_params,
        "steps_per_s": 1.0 / dt,
        "tokens_per_s": batch * seq_len / dt,
        "achieved_tflops": step_flops / dt / 1e12,
    }


# --------------------------------------------------------------- scenario 2b

def bench_long_context(seq_len: int = 16_384, heads: int = 8,
                       head_dim: int = 128, batch: int = 1,
                       steps: int = 8) -> Dict[str, float]:
    """Flash-attention forward+backward at long sequence length on the
    chip. Dense attention at S=16384 would materialize a [S, S] f32 score
    matrix per head (8 GB for these shapes — an OOM on a v5e); the Pallas
    kernels keep O(S) residuals and O(block) VMEM, so this running at all
    is the memory claim, and tokens/s + TFLOP/s quantify the kernel."""
    from torchft_tpu.ops import flash_attention

    rng = jax.random.key(0)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (batch, seq_len, heads, head_dim)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(jnp.float32))

    # Chain the iterations INSIDE one jit (dq feeds the next q, so nothing
    # folds away): per-iteration time then measures the device, not the
    # per-dispatch host latency. One dispatch still rides on each timed
    # call, so the reported time is the DELTA between a 2x-length and a
    # 1x-length scan: dispatch + fetch cancel exactly, leaving pure
    # device time per iteration.
    def make_many(n):
        def many(q, k, v):
            def body(c, _):
                dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(c, k, v)
                # Fold all three grads into the carry: none is dead code.
                return (dq + dk + dv).astype(q.dtype), None
            return jax.lax.scan(body, q, None, length=n)[0]
        return jax.jit(many)

    # The delta must dwarf the dispatch noise (not measured on an
    # attached chip): span it over 2*steps iterations (16-iter vs 32-iter
    # scans at the default).
    short_fn, long_fn = make_many(2 * steps), make_many(4 * steps)
    _materialize(short_fn(q, k, v))  # compile
    _materialize(long_fn(q, k, v))

    def timed(fn):
        t0 = time.perf_counter()
        _materialize(fn(q, k, v))
        return time.perf_counter() - t0

    # Adjacent (short,long) pairs, per-pair deltas, median-of-3: drift is
    # slow relative to one pair, so it cancels within each delta, and the
    # median rejects a spiked pair. (Cross-pair min-matching is biased:
    # min(long) - min(short) pairs the luckiest runs of DIFFERENT drift
    # windows, and its run-to-run spread measured several-fold worse
    # than per-pair medians on this rig.) A non-positive median means
    # dispatch drift swamped the device time: fall back to the naive
    # long-run estimate, flagged, rather than emitting a clamped
    # absurdity.
    deltas = []
    tl_last = None
    for _ in range(3):
        ts_i = timed(short_fn)
        tl_last = timed(long_fn)
        deltas.append(tl_last - ts_i)
    med = statistics.median(deltas)
    delta_valid = med > 0
    dt = med / (2 * steps) if delta_valid else tl_last / (4 * steps)

    # Causal attention FLOPs: fwd 2 matmuls + bwd ~3.5x fwd, halved by
    # causal masking: ~3.5 * 4 * B*H*S^2*D * 0.5.
    flops = 3.5 * 4 * batch * heads * seq_len**2 * head_dim * 0.5
    return {
        "seq_len": seq_len,
        "ms_per_fwd_bwd": dt * 1e3,
        "tokens_per_s": batch * seq_len / dt,
        "achieved_tflops": flops / dt / 1e12,
        # False: dispatch drift defeated the delta; the numbers above are
        # the naive (dispatch-inflated) estimate, a lower bound on the
        # kernel's true device throughput.
        "delta_timing_valid": delta_valid,
    }


# --------------------------------------------------------------- scenario 2c

def bench_diloco(n_groups: int = 2, sync_every: int = 8,
                 rounds: int = 4, hidden: int = 512,
                 streaming_fragments: int = 0) -> Dict[str, float]:
    """DiLoCo local SGD (BASELINE.md config 5): inner steps touch no
    cross-group interconnect at all; only every ``sync_every``-th step
    pays an outer allreduce of the parameter delta. Reports the measured
    inner-step rate vs the per-step-DDP rate on the same model
    (bench_multigroup), i.e. the communication-reduction payoff."""
    from torchft_tpu import HostCommunicator, Lighthouse, Manager
    from torchft_tpu.local_sgd import DiLoCoTrainer, StreamingDiLoCoTrainer
    from torchft_tpu.models import MLP

    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=n_groups,
                    join_timeout_ms=2000, quorum_tick_ms=10)
    model = MLP(features=(hidden, hidden), num_classes=10)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(64,)), jnp.int32)

    def loss_fn(params, batch):
        logits = model.apply(params, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    params0 = model.init(jax.random.key(0), x[:1])
    results: Dict[str, float] = {}

    def worker(gid: str) -> None:
        cls = DiLoCoTrainer
        kwargs = {}
        if streaming_fragments:
            cls = StreamingDiLoCoTrainer
            kwargs["fragments"] = streaming_fragments
        t = cls(
            loss_fn=loss_fn, inner_tx=optax.sgd(0.05), params=params0,
            manager_factory=lambda load, save: Manager(
                comm=HostCommunicator(timeout_sec=30), load_state_dict=load,
                state_dict=save, min_replica_size=n_groups, replica_id=gid,
                lighthouse_addr=lh.address(), rank=0, world_size=1,
                quorum_timeout_ms=30_000,
            ),
            sync_every=sync_every,
            **kwargs,
        )
        b = {"x": x, "y": y}
        # warm: one full outer round (compile + first quorum)
        while t.manager.current_step() < 1:
            t.train_step(b)
        t0 = time.perf_counter()
        target = 1 + rounds
        inner = 0
        while t.manager.current_step() < target:
            t.train_step(b)
            inner += 1
        _materialize(t.anchor)
        dt = time.perf_counter() - t0
        results[gid] = inner / dt
        t.shutdown()

    threads = [threading.Thread(target=worker, args=(f"d{i}",))
               for i in range(n_groups)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    lh.shutdown()

    return {
        "n_groups": n_groups,
        "sync_every": sync_every,
        "inner_steps_per_s": statistics.median(results.values()),
        "comm_per_step_frac": 1.0 / sync_every,
    }


# --------------------------------------------------------------- scenario 3

def bench_recovery(kill_at: int = 6, total_steps: int = 16,
                   hidden: int = 64) -> Dict[str, float]:
    """Kill one of two replica groups mid-run, restart it, and measure
    BASELINE.md's stated metrics: steps of progress the survivor loses
    (must be <= 1) and wall-clock from restart to the healed group's first
    committed step.

    The result carries a **phase breakdown** of the recovery wall clock
    (round-3 verdict: an unattributed 49x outlier is useless): trainer
    re-init, quorum rounds, heal fetch, cross-group allreduce, commit
    barriers, and the unattributed remainder (jit compiles + device
    execution + loop overhead), plus ``dispatch_probe_ms`` — the measured
    latency of one no-op device round trip taken right before the restart.
    The probe measures the device path *as the victim experiences it* —
    dispatch latency plus queueing behind the still-training survivor's
    dispatches on the shared chip. A probe far above its usual value pins
    a recovery outlier on the device path rather than the FT protocol
    (whose components are itemized in the phases)."""
    from torchft_tpu import HostCommunicator, Lighthouse, Manager
    from torchft_tpu.models import MLP
    from torchft_tpu.parallel import FTTrainer

    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                    join_timeout_ms=400, quorum_tick_ms=10)
    model = MLP(features=(hidden,), num_classes=2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=(32,)), jnp.int32)
    b = {"x": x, "y": y}

    def loss_fn(params, batch):
        logits = model.apply(params, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    params0 = model.init(jax.random.key(0), x[:1])

    def make_trainer(gid: str) -> FTTrainer:
        return FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.05), params=params0,
            manager_factory=lambda load, save: Manager(
                comm=HostCommunicator(timeout_sec=15), load_state_dict=load,
                state_dict=save, min_replica_size=1, replica_id=gid,
                lighthouse_addr=lh.address(), rank=0, world_size=1,
                timeout_ms=15_000, quorum_timeout_ms=15_000,
            ),
        )

    out: Dict[str, float] = {}
    survivor_done = threading.Event()
    # Dispatch-health probe, compiled up front: only the dispatch is timed
    # (inside the victim, right before its restart).
    probe = jax.jit(lambda a: a + 1)
    _materialize(probe(jnp.zeros(())))

    def survivor() -> None:
        trainer = make_trainer("gA")
        while trainer.manager.current_step() < total_steps:
            trainer.train_step(b)
        mx = trainer.manager.metrics()
        out["survivor_aborted_steps"] = mx["aborted_steps"]
        out["survivor_committed_steps"] = mx["committed_steps"]
        out["survivor_heals"] = mx["heal_count"]
        survivor_done.set()
        trainer.shutdown()

    def victim() -> None:
        # First life: run to kill_at, then "die" (shutdown, drop state).
        trainer = make_trainer("gB")
        while trainer.manager.current_step() < kill_at:
            trainer.train_step(b)
        trainer.shutdown()
        # Dispatch-health probe: one dispatch of an already-compiled no-op.
        # Anomalously slow recovery + anomalously slow probe = device path.
        pt0 = time.perf_counter()
        _materialize(probe(jnp.zeros(())))
        out["dispatch_probe_ms"] = (time.perf_counter() - pt0) * 1e3
        # Restart: fresh trainer (fresh uuid replica member, params at
        # init) — must rejoin, heal from gA, and commit.
        t0 = time.perf_counter()
        trainer = make_trainer("gB")
        out["phase_reinit_s"] = time.perf_counter() - t0
        committed = 0
        attempts = 0
        # Main-thread wall partition (FTTrainer.last_step_timings): unlike
        # the manager's cross-thread busy counters, these sum to each
        # step's wall clock exactly, so the recovery total decomposes with
        # no ambiguous overlap (round-4 verdict weak #3: 50% of recovery
        # sat in "other"). dispatch = trace + jit compile + async dispatch
        # (the restart recompiles FTTrainer's fresh jit closures);
        # allreduce_wait = blocked on the cross-group exchange, which
        # joins the quorum — so quorum wait + heal fetch wall surface
        # here; commit = vote + commit barrier; glue = quorum kick, batch
        # placement, python loop.
        acc = {"dispatch": 0.0, "allreduce_wait": 0.0, "commit": 0.0,
               "glue": 0.0, "steps_total": 0.0}
        while committed < 1 and not survivor_done.is_set():
            _, ok = trainer.train_step(b)
            st_t = trainer.last_step_timings
            acc["dispatch"] += st_t["dispatch"]
            acc["allreduce_wait"] += st_t["allreduce_wait"]
            acc["commit"] += st_t["commit"]
            acc["glue"] += st_t["other"]
            acc["steps_total"] += st_t["total"]
            attempts += 1
            committed += bool(ok)
        total = time.perf_counter() - t0
        out["recovery_wall_clock_s"] = total
        out["victim_recovered_at_step"] = trainer.manager.current_step()
        out["recovery_attempts"] = attempts
        out["phase_dispatch_compile_s"] = acc["dispatch"]
        out["phase_allreduce_wait_s"] = acc["allreduce_wait"]
        out["phase_commit_s"] = acc["commit"]
        out["phase_glue_s"] = acc["glue"]
        # Loop overhead outside the steps themselves; ~0 by construction.
        out["phase_other_s"] = max(
            0.0, total - out["phase_reinit_s"] - acc["steps_total"])
        # Busy-time annotations from the manager (run on the quorum
        # thread, overlapping the main thread — attribution context for
        # allreduce_wait, not additional wall clock).
        mx = trainer.manager.metrics()
        out["quorum_busy_s"] = mx["quorum_ms_total"] / 1e3
        out["heal_busy_s"] = mx["heal_ms_total"] / 1e3
        out["reconfigure_busy_s"] = mx["reconfigure_ms_total"] / 1e3
        out["heal_mbytes"] = mx["heal_bytes_total"] / 1e6
        # keep participating until the survivor finishes so quorums stay 2-wide
        while not survivor_done.is_set():
            trainer.train_step(b)
        trainer.shutdown()

    errors: list = []

    def guarded(fn):
        def run():
            try:
                fn()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                survivor_done.set()  # unblock the peer
        return run

    ts = [threading.Thread(target=guarded(survivor)),
          threading.Thread(target=guarded(victim))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    lh.shutdown()
    if errors:
        raise errors[0]
    return out


# --------------------------------------------------------------- scenario 5

class _RateCapProxy:
    """TCP proxy that caps each donor->healer stream at ``mb_s`` — the
    per-donor uplink model the striped-heal A/B needs. On a loopback rig
    the raw transfer is CPU/crc-bound, so 1-vs-N donors would measure
    core count, not the protocol; capping every donor's egress the same
    way makes the A/B answer the question the design asks: with
    donor-bounded bandwidth, does striping cut heal wall to ~1/N?"""

    def __init__(self, target_addr: str, mb_s: float) -> None:
        import socket as _socket
        import urllib.parse as _up

        u = _up.urlparse(target_addr)
        self._thost, self._tport = u.hostname, u.port
        self._path = u.path
        self._per_tick = max(int(mb_s * 1e6 * 0.005), 1)  # 5ms ticks
        self._srv = _socket.create_server(("127.0.0.1", 0))
        self._alive = True
        self._threads: list = []
        t = threading.Thread(target=self._accept, daemon=True)
        t.start()
        self._threads.append(t)

    def address(self) -> str:
        host, port = self._srv.getsockname()[:2]
        return f"http://{host}:{port}{self._path}"

    def _accept(self) -> None:
        import socket as _socket

        while self._alive:
            try:
                cli, _ = self._srv.accept()
            except OSError:
                return
            up = _socket.create_connection((self._thost, self._tport))
            for src, dst, capped in ((cli, up, False), (up, cli, True)):
                t = threading.Thread(target=self._pump,
                                     args=(src, dst, capped), daemon=True)
                t.start()
                self._threads.append(t)

    def _pump(self, src, dst, capped: bool) -> None:
        try:
            while True:
                data = src.recv(self._per_tick if capped else 65536)
                if not data:
                    break
                dst.sendall(data)
                if capped:
                    time.sleep(0.005)
        except OSError:
            pass
        finally:
            for s in (src, dst):
                try:
                    s.shutdown(2)
                except OSError:
                    pass

    def shutdown(self) -> None:
        self._alive = False
        try:
            self._srv.close()
        except OSError:
            pass


def bench_heal_striped(payload_mb: float = 48.0, donors: int = 3,
                       donor_mb_s: float = 64.0) -> Dict[str, float]:
    """Torrent-striped heal A/B (docs/design/sharded_update.md): one
    healer fetches a ``payload_mb`` snapshot from 1 donor vs striped
    across ``donors`` donors, every donor's egress capped at
    ``donor_mb_s`` (see :class:`_RateCapProxy` — the donor-uplink-bound
    regime striping exists for). Pure-python transport (CheckpointServer
    + HTTP Range), no native library needed. Reports wall/MB/s for both
    legs plus the striped leg's donor accounting."""
    from torchft_tpu.checkpointing import CheckpointServer

    rng = np.random.default_rng(11)
    n_leaves = 12
    per = max(int(payload_mb * 1e6 / 4 / n_leaves), 1)
    state = {f"l{i}": rng.normal(size=per).astype(np.float32)
             for i in range(n_leaves)}
    servers = [CheckpointServer(lambda: state, bind_host="127.0.0.1")
               for _ in range(donors)]
    proxies = []
    out: Dict[str, float] = {"payload_mbytes": per * 4 * n_leaves / 1e6,
                             "donors": donors,
                             "donor_cap_mb_s": donor_mb_s}
    try:
        for s in servers:
            s.allow_checkpoint(1)
        proxies = [_RateCapProxy(s.address(), donor_mb_s)
                   for s in servers]
        addrs = [p.address() for p in proxies]
        for label, donor_addrs in (("single", None), ("striped", addrs)):
            stats: Dict[str, float] = {}
            t0 = time.perf_counter()
            CheckpointServer.load_from_address(
                addrs[0], state, device_put=False, stats=stats,
                donor_addrs=donor_addrs, stripe_seed=0)
            dt = time.perf_counter() - t0
            out[f"{label}_wall_s"] = dt
            out[f"{label}_mb_s"] = stats["bytes"] / 1e6 / max(dt, 1e-9)
            if label == "striped":
                out["donors_used"] = stats["donors_used"]
        out["striped_speedup"] = (out["single_wall_s"]
                                  / max(out["striped_wall_s"], 1e-9))
    finally:
        for p in proxies:
            p.shutdown()
        for s in servers:
            s.shutdown()
    return out


def bench_recovery_tiers(payload_mb: float = 48.0,
                         disk_mb_s: float = 32.0,
                         nic_mb_s: float = 250.0) -> Dict[str, Any]:
    """Recovery-ladder A/B (docs/design/memory_tier.md, ROADMAP item 3):
    one cold replacement restores a ``payload_mb`` snapshot from the
    RAM tier — a surviving peer's :class:`~torchft_tpu.ram_ckpt.\
RamCheckpointStore` served over the striped heal transport, NIC capped
    at ``nic_mb_s`` — vs the disk-only rung: the same bytes pulled from
    a durable store rate-capped at ``disk_mb_s`` (the cold-HDD /
    network-filesystem regime the RAM tier exists to skip; loopback
    reads are CPU-bound, so an uncapped disk leg would measure memcpy,
    not the design's question). Both legs end in the identical
    digest-verified v2 load — the image IS the on-disk stream — and the
    result is checked bitwise against the source state. Pure-python
    transport, no native library needed.

    The gate (ISSUE-16 acceptance): ``ram_speedup >= 2.0`` under the
    stated caps."""
    import shutil
    import tempfile

    from torchft_tpu import checkpoint_io, ram_ckpt
    from torchft_tpu.checkpointing import CheckpointServer
    from torchft_tpu.ram_ckpt import RamCheckpointStore

    rng = np.random.default_rng(23)
    n_leaves = 12
    per = max(int(payload_mb * 1e6 / 4 / n_leaves), 1)
    state = {f"l{i}": rng.normal(size=per).astype(np.float32)
             for i in range(n_leaves)}
    step = 7
    image = ram_ckpt.encode_image(
        state, {"step": step, "batches_committed": step})
    out: Dict[str, Any] = {"payload_mbytes": image.nbytes / 1e6,
                           "disk_cap_mb_s": disk_mb_s,
                           "nic_cap_mb_s": nic_mb_s, "step": step}
    tmp = tempfile.mkdtemp(prefix="bench_tiers_")
    srv = proxy = None
    try:
        # ---- disk-only rung: rate-capped durable fetch + verified load.
        # The image bytes ARE the v2 disk format — written verbatim they
        # are exactly what save() would have produced at this step.
        durable = os.path.join(tmp, "durable", f"ckpt_{step}")
        os.makedirs(os.path.dirname(durable), exist_ok=True)
        with open(durable, "wb") as f:
            f.write(image.data)
        spool = os.path.join(tmp, "local", f"ckpt_{step}")
        os.makedirs(os.path.dirname(spool), exist_ok=True)
        per_tick = max(int(disk_mb_s * 1e6 * 0.005), 1)  # 5ms ticks
        t0 = time.perf_counter()
        with open(durable, "rb") as src, open(spool, "wb") as dst:
            while True:
                chunk = src.read(per_tick)
                if not chunk:
                    break
                dst.write(chunk)
                time.sleep(0.005)
        disk_user, disk_mgr = checkpoint_io.load(spool, state,
                                                 device_put=False)
        disk_wall = time.perf_counter() - t0
        assert disk_mgr["step"] == step

        # ---- RAM rung: surviving peer serves its RAM image over the
        # striped heal transport (/ramckpt/{step}), NIC-capped.
        srv = CheckpointServer(lambda: state, bind_host="127.0.0.1")
        store = RamCheckpointStore(keep=2)
        store.put(image)
        srv.attach_ram_store(store)
        proxy = _RateCapProxy(
            f"{srv.ram_address()}/ramckpt/{step}", nic_mb_s)
        target = {"user": state,
                  "torchft": {"step": 0, "batches_committed": 0}}
        stats: Dict[str, float] = {}
        t0 = time.perf_counter()
        healed = CheckpointServer.load_from_address(
            proxy.address(), target, device_put=False, stats=stats)
        ram_wall = time.perf_counter() - t0
        assert healed["torchft"]["step"] == step

        identical = all(
            np.asarray(state[k]).tobytes()
            == np.asarray(healed["user"][k]).tobytes()
            == np.asarray(disk_user[k]).tobytes()
            for k in state)
        out.update({
            "disk_wall_s": disk_wall,
            "ram_wall_s": ram_wall,
            "disk_mb_s": out["payload_mbytes"] / max(disk_wall, 1e-9),
            "ram_mb_s": out["payload_mbytes"] / max(ram_wall, 1e-9),
            "ram_speedup": disk_wall / max(ram_wall, 1e-9),
            "bitwise_identical": identical,
        })
    finally:
        if proxy is not None:
            proxy.shutdown()
        if srv is not None:
            srv.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


class _UplinkCapProxy:
    """TCP proxy capping AGGREGATE egress across ALL connections at
    ``mb_s`` — the node-uplink model the publish fan-out A/B needs.
    :class:`_RateCapProxy` throttles each stream independently (the
    per-donor model); a fan-out's bottleneck is the shared NIC, so here
    every capped pump draws from one token bucket. On a loopback rig
    the raw transfer is CPU-bound and 1-vs-N topologies would measure
    core count; capping every node's egress identically makes the A/B
    answer the design's question: with uplink-bounded nodes, does a
    relay tier multiply subscriber capacity by tree width?"""

    def __init__(self, target_addr: str, mb_s: float) -> None:
        import socket as _socket
        import urllib.parse as _up

        u = _up.urlparse(target_addr)
        self._thost, self._tport = u.hostname, u.port
        self._path = u.path
        self._rate = mb_s * 1e6
        self._tokens = 0.0
        self._last = time.perf_counter()
        self._tlock = threading.Lock()
        self._srv = _socket.create_server(("127.0.0.1", 0), backlog=128)
        self._alive = True
        t = threading.Thread(target=self._accept, daemon=True)
        t.start()

    def address(self) -> str:
        host, port = self._srv.getsockname()[:2]
        return f"http://{host}:{port}{self._path}"

    def _take(self, want: int) -> int:
        with self._tlock:
            now = time.perf_counter()
            self._tokens = min(self._tokens
                               + (now - self._last) * self._rate,
                               self._rate * 0.05)  # 50ms burst bound
            self._last = now
            got = int(min(self._tokens, want))
            self._tokens -= got
            return got

    def _accept(self) -> None:
        import socket as _socket

        while self._alive:
            try:
                cli, _ = self._srv.accept()
            except OSError:
                return
            try:
                up = _socket.create_connection((self._thost, self._tport))
            except OSError:
                cli.close()
                continue
            for s in (cli, up):
                try:
                    s.setsockopt(_socket.IPPROTO_TCP,
                                 _socket.TCP_NODELAY, 1)
                except OSError:
                    pass
            for src, dst, capped in ((cli, up, False), (up, cli, True)):
                threading.Thread(target=self._pump,
                                 args=(src, dst, capped),
                                 daemon=True).start()

    def _pump(self, src, dst, capped: bool) -> None:
        try:
            while True:
                data = src.recv(65536)
                if not data:
                    break
                if not capped:
                    dst.sendall(data)
                    continue
                sent = 0
                while sent < len(data):
                    k = self._take(len(data) - sent)
                    if k == 0:
                        time.sleep(0.002)
                        continue
                    dst.sendall(data[sent:sent + k])
                    sent += k
        except OSError:
            pass
        finally:
            for s in (src, dst):
                try:
                    s.shutdown(2)
                except OSError:
                    pass

    def set_rate(self, mb_s: float) -> None:
        """Retune the cap live — legs warm their fleet uncapped (the
        initial full sync is not what's being measured), then clamp to
        the modeled uplink before the clock starts."""
        with self._tlock:
            self._rate = mb_s * 1e6
            self._tokens = min(self._tokens, self._rate * 0.05)

    def shutdown(self) -> None:
        self._alive = False
        try:
            self._srv.close()
        except OSError:
            pass


def bench_publish_fanout(payload_mb: float = 4.0, subscribers: int = 12,
                         relays: int = 6, uplink_mb_s: float = 32.0,
                         publishes: int = 4,
                         capacity_secs: float = 3.0) -> Dict[str, float]:
    """Weight-distribution tier A/B (docs/design/serving.md). Three
    measurements, one dict:

    * **publish-to-visible latency** (uncapped, long-polling
      subscribers): p50/p95 across ``subscribers x publishes`` of
      publish()-call → crc-verified atomic swap.
    * **delta minimality**: a small-touch publish (1 of 12 leaves
      changed) against a synced subscriber — wire bytes / full payload.
    * **fan-out capacity, direct vs relay, uplink-capped**: every
      node's egress capped at ``uplink_mb_s`` (:class:`_UplinkCapProxy`
      — aggregate, not per-stream). Fresh-subscriber full syncs (the
      "capacity" question: how many cold consumers/sec can the tier
      sustain) hammer (a) the publisher directly, (b) ``relays`` relay
      nodes fed by the same capped publisher. ``fanout_capacity_ratio``
      = relay/direct aggregate delivered MB/s; the design target is
      >= 4x (relay capacity grows with tree width; direct is pinned at
      one uplink).

    Pure-python transport (WeightPublisher/Subscriber/Relay over HTTP),
    no native library needed."""
    from torchft_tpu.retry import RetryPolicy
    from torchft_tpu.serving import (PublicationServer, WeightPublisher,
                                     WeightRelay, WeightSubscriber)

    rng = np.random.default_rng(23)
    n_leaves = 12
    per = max(int(payload_mb * 1e6 / 4 / n_leaves), 1)
    state = {f"l{i}": rng.normal(size=per).astype(np.float32)
             for i in range(n_leaves)}
    template = {f"l{i}": np.zeros(per, np.float32)
                for i in range(n_leaves)}
    pol = RetryPolicy(max_attempts=4, base_delay_ms=10.0, jitter=0.0)
    out: Dict[str, float] = {
        "payload_mbytes": per * 4 * n_leaves / 1e6,
        "subscribers": subscribers, "relays": relays,
        "uplink_cap_mb_s": uplink_mb_s, "publishes": publishes,
    }

    class _TimedSub(WeightSubscriber):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.seen: Dict[int, float] = {}

        def _on_generation(self, held, body_digests):
            self.seen[held.generation] = time.perf_counter()

    # --- publish-to-visible latency (uncapped, long-poll) --------------
    pub = WeightPublisher(keep_generations=2)
    srv = PublicationServer(pub, bind_host="127.0.0.1")
    subs = []
    try:
        pub.publish(state, step=0)
        subs = [_TimedSub(srv.address(), template, retry_policy=pol,
                          long_poll_s=10.0, poll_interval_s=0.02,
                          name=f"p2v{i}").start()
                for i in range(subscribers)]
        deadline = time.monotonic() + 30
        while any(s.generation() < 1 for s in subs):
            if time.monotonic() > deadline:
                raise TimeoutError("subscribers never reached gen 1")
            time.sleep(0.01)
        lat_ms = []
        for k in range(publishes):
            st = dict(state)
            st[f"l{k % n_leaves}"] = st[f"l{k % n_leaves}"] + (k + 1)
            t0 = time.perf_counter()
            gen = pub.publish(st, step=k + 1)
            deadline = time.monotonic() + 30
            while any(gen not in s.seen for s in subs):
                if time.monotonic() > deadline:
                    raise TimeoutError(f"gen {gen} never fully visible")
                time.sleep(0.005)
            lat_ms += [(s.seen[gen] - t0) * 1e3 for s in subs]
        lat_ms.sort()
        out["publish_to_visible_p50_ms"] = lat_ms[len(lat_ms) // 2]
        out["publish_to_visible_p95_ms"] = lat_ms[
            min(int(len(lat_ms) * 0.95), len(lat_ms) - 1)]

        # --- delta minimality (small-touch publish) ---------------------
        probe = WeightSubscriber(srv.address(), template,
                                 retry_policy=pol, name="delta-probe")
        probe.sync()
        st = dict(pub._head.state)
        st["l0"] = np.asarray(st["l0"]) + 1
        pub.publish(st, step=publishes + 1)
        probe.sync()
        pm = probe.metrics()
        out["delta_bytes"] = pm["serve_delta_bytes_last"]
        out["full_payload_bytes"] = pm["serve_payload_bytes_last"]
        out["delta_full_ratio"] = pm["serve_delta_ratio_last"]
        probe.stop()
    finally:
        for s in subs:
            s.stop()
        srv.shutdown()

    # --- fan-out capacity, uplink-capped: direct vs relay tier ---------
    def capacity(parent_addrs: list) -> Dict[str, float]:
        """Aggregate delivered MB/s of continuous fresh-subscriber full
        syncs across ``subscribers`` workers round-robined over
        ``parent_addrs``."""
        stop = time.perf_counter() + capacity_secs
        done = [0]
        lock = threading.Lock()

        def worker(wid: int) -> None:
            while time.perf_counter() < stop:
                s = WeightSubscriber(
                    parent_addrs[wid % len(parent_addrs)], template,
                    retry_policy=pol, stall_timeout_sec=30.0,
                    name=f"cap{wid}")
                try:
                    if s.sync():
                        with lock:
                            done[0] += 1
                except Exception:  # noqa: BLE001 — churny rig, count only
                    pass
                finally:
                    s.stop()

        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(subscribers)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=capacity_secs + 60)
        wall = time.perf_counter() - t0
        payload = per * 4 * n_leaves
        return {"syncs": float(done[0]),
                "agg_mb_s": done[0] * payload / 1e6 / max(wall, 1e-9)}

    pub2 = WeightPublisher(keep_generations=2)
    srv2 = PublicationServer(pub2, bind_host="127.0.0.1")
    pub2.publish(state, step=1)
    pub_proxy = _UplinkCapProxy(srv2.address(), uplink_mb_s)
    relay_nodes: list = []
    relay_proxies: list = []
    try:
        direct = capacity([pub_proxy.address()])
        out["direct_syncs"] = direct["syncs"]
        out["direct_agg_mb_s"] = direct["agg_mb_s"]

        relay_nodes = [
            WeightRelay(pub_proxy.address(), template,
                        bind_host="127.0.0.1", retry_policy=pol,
                        name=f"relay{i}")
            for i in range(relays)
        ]
        for r in relay_nodes:
            r.sync()  # warm: relays hold the generation before the clock
        relay_proxies = [_UplinkCapProxy(r.address(), uplink_mb_s)
                         for r in relay_nodes]
        relayed = capacity([p.address() for p in relay_proxies])
        out["relay_syncs"] = relayed["syncs"]
        out["relay_agg_mb_s"] = relayed["agg_mb_s"]
        out["fanout_capacity_ratio"] = (
            relayed["agg_mb_s"] / max(direct["agg_mb_s"], 1e-9))
        out["capacity_target_ratio"] = 4.0
    finally:
        for p in relay_proxies:
            p.shutdown()
        for r in relay_nodes:
            r.stop()
        pub_proxy.shutdown()
        srv2.shutdown()
    return out


def bench_publish_delta_ab(payload_mb: float = 4.0,
                           publishes: int = 3) -> Dict[str, float]:
    """Quantized delta publication A/B (docs/design/serving.md): one
    ``delta=True`` publisher, two synced subscribers — the delta leg
    negotiates int8+pow2-scale wires per leaf, the full leg fetches
    exact f32 — across ``publishes`` small-touch updates (1 of 12
    leaves nudged). Reported: delta wire bytes vs the changed leaves'
    f32 bytes (design target <= ~1/4 — int8 payload plus pow2 scale
    tables), total fetched bytes both legs, and the bitwise verdict
    (both legs must hold identical bits every generation — the delta
    route reconstructs the SAME published array the full route
    serves)."""
    from torchft_tpu.retry import RetryPolicy
    from torchft_tpu.serving import (PublicationServer, WeightPublisher,
                                     WeightSubscriber)

    rng = np.random.default_rng(23)
    n_leaves = 12
    per = max(int(payload_mb * 1e6 / 4 / n_leaves), 1)
    state = {f"l{i}": rng.normal(size=per).astype(np.float32)
             for i in range(n_leaves)}
    template = {f"l{i}": np.zeros(per, np.float32)
                for i in range(n_leaves)}
    pol = RetryPolicy(max_attempts=4, base_delay_ms=10.0, jitter=0.0)
    pub = WeightPublisher(keep_generations=2, delta=True)
    srv = PublicationServer(pub, bind_host="127.0.0.1")
    out: Dict[str, float] = {
        "payload_mbytes": per * 4 * n_leaves / 1e6,
        "publishes": float(publishes),
    }
    on = off = None
    try:
        pub.publish(state, step=0)
        on = WeightSubscriber(srv.address(), template, retry_policy=pol,
                              delta=True, name="delta-on")
        off = WeightSubscriber(srv.address(), template, retry_policy=pol,
                               delta=False, name="delta-off")
        on.sync()
        off.sync()
        delta_fetched = full_fetched = 0.0
        bitwise = True
        st = state
        for k in range(publishes):
            st = dict(st)
            lk = f"l{k % n_leaves}"
            st[lk] = (np.asarray(st[lk])
                      + np.float32(1e-3)
                      * rng.normal(size=per).astype(np.float32))
            pub.publish(st, step=k + 1)
            a0 = on.metrics()["serve_bytes_fetched_total"]
            b0 = off.metrics()["serve_bytes_fetched_total"]
            on.sync()
            off.sync()
            delta_fetched += on.metrics()[
                "serve_bytes_fetched_total"] - a0
            full_fetched += off.metrics()[
                "serve_bytes_fetched_total"] - b0
            wa, wb = on.weights(), off.weights()
            bitwise = bitwise and all(
                np.array_equal(np.asarray(wa[key]).view(np.uint32),
                               np.asarray(wb[key]).view(np.uint32))
                for key in wa)
        m = on.metrics()
        out["delta_wire_bytes"] = m["serve_delta_wire_bytes_total"]
        # Denominator: the full leg's MEASURED bytes for the same
        # generations — both legs fetch the same changed-leaf set (the
        # nudged leaf plus the error-feedback correction of the
        # previous one), so this is the honest f32 cost of the update.
        out["changed_f32_bytes"] = full_fetched
        out["delta_wire_ratio"] = (
            out["delta_wire_bytes"] / max(full_fetched, 1.0))
        out["delta_fetched_bytes"] = delta_fetched
        out["full_fetched_bytes"] = full_fetched
        out["fetched_ratio"] = delta_fetched / max(full_fetched, 1.0)
        out["delta_crc_fallbacks"] = m["serve_delta_crc_fallbacks"]
        out["bitwise_equal"] = float(bitwise)
        out["wire_ratio_target"] = 0.25
    finally:
        for s in (on, off):
            if s is not None:
                s.stop()
        srv.shutdown()
    return out


def bench_publish_steering_ab(payload_mb: float = 1.0,
                              base_subscribers: int = 12,
                              scale: int = 10,
                              uplink_mb_s: float = 0.5,
                              publishes: int = 2) -> Dict[str, float]:
    """Relay-steering A/B at fleet scale (docs/design/serving.md).
    Four uplink-capped legs, every node's aggregate egress pinned at
    ``uplink_mb_s`` (:class:`_UplinkCapProxy`), deltas on throughout:

    * ``base_subscribers`` steered through a depth-1 relay tree (the
      small fleet) and the same fleet direct (its control),
    * ``base_subscribers * scale`` steered through a depth-2 tree with
      the SAME bounded fan-out at every node (the ~10x fleet — the
      acceptance question: does publish-to-visible p95 stay ~flat?),
    * ``base_subscribers * scale`` direct (steering off — every
      subscriber on the root's one capped uplink; the control).

    The paired controls turn "~flat" into a measured contrast: growing
    the fleet 10x grows the steered p95 by roughly one extra tree
    level (~2-3x, log depth), while the direct control's p95 grows
    ~linearly with the fleet (~10x) because every subscriber shares
    the root's one capped uplink.

    Scaling the fleet grows the tree, never any single node's egress:
    a 10x fleet adds one tree level (log growth), so p95 tracks tree
    DEPTH x per-hop drain instead of fleet size. Subscribers find their
    leaf via cascade steering — the root steers to an L1 relay, whose
    own relay table steers onward to its least-loaded L2 child.

    The defaults keep the modeled uplink slow relative to the CPU cost
    of pumping bytes, so the capped links (not the single-core python
    harness, which serializes every node of the simulated fleet) set
    the measured latencies.

    The large steered leg then kills one relay mid-run and publishes
    again: its children must re-parent (rotate to the root, get
    steered to a live relay) and the WHOLE fleet must converge on the
    final generation bitwise — no torn observation is tolerated."""
    from torchft_tpu.retry import RetryPolicy
    from torchft_tpu.serving import (PublicationServer, WeightPublisher,
                                     WeightRelay, WeightSubscriber)

    rng = np.random.default_rng(23)
    n_leaves = 12
    per = max(int(payload_mb * 1e6 / 4 / n_leaves), 1)
    state = {f"l{i}": rng.normal(size=per).astype(np.float32)
             for i in range(n_leaves)}
    template = {f"l{i}": np.zeros(per, np.float32)
                for i in range(n_leaves)}
    pol = RetryPolicy(max_attempts=5, base_delay_ms=10.0, jitter=0.0)

    class _TimedSub(WeightSubscriber):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.seen: Dict[int, float] = {}

        def _on_generation(self, held, body_digests):
            self.seen[held.generation] = time.perf_counter()

    def leg(n_subs: int, levels: list,
            kill_relay: bool) -> Dict[str, float]:
        steer = bool(levels)
        pub = WeightPublisher(keep_generations=3, delta=True,
                              relay_ttl_s=1.5)
        srv = PublicationServer(pub, bind_host="127.0.0.1")
        pub.publish(state, step=0)
        root_proxy = _UplinkCapProxy(srv.address(), 10_000.0)
        relays: list = []
        relay_proxies: list = []
        subs: list = []
        res: Dict[str, float] = {}
        try:
            # Build the relay tree level by level (bounded fan-out at
            # every node — the CDN shape). Children beat their PARENT,
            # so each level registers in its parent's table and the
            # cascade steer (root -> L1 -> ... -> leaf) walks
            # subscribers down to a leaf relay.
            prev = [(root_proxy, pub)]
            for li, n in enumerate(levels):
                cur = []
                for i in range(n):
                    parent_proxy, _ = prev[i % len(prev)]
                    r = WeightRelay(parent_proxy.address(), template,
                                    bind_host="127.0.0.1",
                                    retry_policy=pol,
                                    beat_interval_s=0.2,
                                    relay_ttl_s=1.5,
                                    long_poll_s=5.0,
                                    poll_interval_s=0.02,
                                    name=f"steer-relay{li}.{i}")
                    rp = _UplinkCapProxy(r.address(), 10_000.0)
                    r.set_advertise(rp.address())
                    relays.append(r)
                    relay_proxies.append(rp)
                    cur.append((rp, r.publisher()))
                for r in relays[-n:]:
                    r.sync()
                    r.start()
                deadline = time.monotonic() + 20
                while (sum(len(p.relay_rows()) for _, p in prev) < n
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
                prev = cur
            subs = [_TimedSub(root_proxy.address(), template,
                              retry_policy=pol, steer=steer, delta=True,
                              long_poll_s=5.0, poll_interval_s=0.02,
                              name=f"steer-sub{i}").start()
                    for i in range(n_subs)]
            deadline = time.monotonic() + 60
            while any(s.generation() < 1 for s in subs):
                if time.monotonic() > deadline:
                    raise TimeoutError("steering fleet never warmed")
                time.sleep(0.02)
            lat_ms: list = []
            st = state
            gen = 0
            # Publish 0 runs UNCAPPED: it seeds the quantized
            # error-feedback steady state (every later small-touch
            # publish moves exactly two leaves — the nudged one plus
            # the EF correction of the previous), so the measured
            # publishes are byte-identical. Caps clamp right after it.
            for k in range(publishes + 1):
                st = dict(st)
                lk = f"l{k % n_leaves}"
                st[lk] = (np.asarray(st[lk])
                          + np.float32(1e-3)
                          * rng.normal(size=per).astype(np.float32))
                t0 = time.perf_counter()
                gen = pub.publish(st, step=k + 1)
                deadline = time.monotonic() + 60
                while any(gen not in s.seen for s in subs):
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"gen {gen} never fully visible "
                            f"(n={n_subs} steer={steer})")
                    time.sleep(0.005)
                if k == 0:
                    # Clock starts now: clamp every uplink to the cap.
                    root_proxy.set_rate(uplink_mb_s)
                    for rp in relay_proxies:
                        rp.set_rate(uplink_mb_s)
                    continue
                lat_ms += [(s.seen[gen] - t0) * 1e3 for s in subs]
            lat_ms.sort()
            res["p50_ms"] = lat_ms[len(lat_ms) // 2]
            res["p95_ms"] = lat_ms[
                min(int(len(lat_ms) * 0.95), len(lat_ms) - 1)]
            if kill_relay and relays:
                # Kill a LEAF relay: its subscribers must rotate back
                # to the root and get re-steered down a live branch.
                dead = relays[-1]
                dead_addr = relay_proxies[-1].address().rstrip("/")
                orphans = sum(
                    1 for s in subs
                    if s._parents[0].rstrip("/") == dead_addr)
                dead.stop()
                relay_proxies[-1].shutdown()
                st = dict(st)
                st["l0"] = np.asarray(st["l0"]) + np.float32(1.0)
                gen = pub.publish(st, step=publishes + 2)
                deadline = time.monotonic() + 90
                while any(gen not in s.seen for s in subs):
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            "fleet never converged after relay kill")
                    time.sleep(0.01)
                res["kill_orphans"] = float(orphans)
                res["kill_reparented"] = float(sum(
                    1 for s in subs
                    if s._parents[0].rstrip("/") != dead_addr))
            # Torn-observation audit: every subscriber's held tree must
            # be bitwise the final published generation (the publisher
            # retains the reconstruction it served).
            final = pub._head.state  # noqa: SLF001 — bench audit
            torn = 0
            for s in subs:
                w = s.weights()
                if not all(
                        np.array_equal(
                            np.asarray(w[key]).view(np.uint32),
                            np.asarray(final[key]).view(np.uint32))
                        for key in final):
                    torn += 1
            res["torn_observations"] = float(torn)
            res["steers"] = float(
                pub.metrics()["relay_steers"]
                + sum(r.publisher().metrics().get("relay_steers", 0.0)
                      for r in relays))
        finally:
            for s in subs:
                s.request_stop()
            for r in relays:
                r.request_stop()
            for s in subs:
                s.stop()
            for r in relays:
                r.stop()
            for rp in relay_proxies:
                rp.shutdown()
            root_proxy.shutdown()
            srv.shutdown()
        return res

    big = base_subscribers * scale
    small_levels = [2]
    large_levels = [4, 20]
    small = leg(base_subscribers, small_levels, kill_relay=False)
    small_direct = leg(base_subscribers, [], kill_relay=False)
    steered = leg(big, large_levels, kill_relay=True)
    direct = leg(big, [], kill_relay=False)
    return {
        "payload_mbytes": per * 4 * n_leaves / 1e6,
        "uplink_cap_mb_s": uplink_mb_s,
        "relays_small": float(sum(small_levels)),
        "relays_large": float(sum(large_levels)),
        "subscribers_small": float(base_subscribers),
        "subscribers_large": float(big),
        "small_p50_ms": small["p50_ms"],
        "small_p95_ms": small["p95_ms"],
        "small_direct_p95_ms": small_direct["p95_ms"],
        "steered_p50_ms": steered["p50_ms"],
        "steered_p95_ms": steered["p95_ms"],
        "direct_p50_ms": direct["p50_ms"],
        "direct_p95_ms": direct["p95_ms"],
        # ~flat == this ratio stays near 1 (one extra tree level) as
        # the fleet grows 10x; the direct control grows ~linearly.
        "steered_growth_p95_ratio": (
            steered["p95_ms"] / max(small["p95_ms"], 1e-9)),
        "direct_growth_p95_ratio": (
            direct["p95_ms"] / max(small_direct["p95_ms"], 1e-9)),
        "direct_over_steered_p95": (
            direct["p95_ms"] / max(steered["p95_ms"], 1e-9)),
        "steers": steered["steers"],
        "kill_orphans": steered.get("kill_orphans", 0.0),
        "kill_reparented": steered.get("kill_reparented", 0.0),
        "torn_observations": (small["torn_observations"]
                              + small_direct["torn_observations"]
                              + steered["torn_observations"]
                              + direct["torn_observations"]),
    }


def bench_qos_contention(payload_mb: float = 8.0, pub_streams: int = 6,
                         secs: float = 2.5,
                         warmup_s: float = 0.3) -> Dict[str, float]:
    """Heal-vs-publish contention on the shared transport substrate
    (docs/design/transport_substrate.md). One async server core hosts a
    ranged blob; ``pub_streams`` publication-class clients loop full
    fetches flat-out (the saturating publication leg) while ONE
    heal-class client measures its delivered MB/s through the same
    egress. Unweighted FIFO would decay the heal stream toward
    ``1/(1+pub_streams)`` of its solo rate; the DRR scheduler's 4:2
    heal:publication weights hold a backlogged heal class at
    weight-proportional drain instead. Reported:

    * ``heal_solo_mb_s`` / ``heal_contended_mb_s`` — the heal-class
      fetch rate on an idle server vs under the saturating leg.
    * ``heal_contended_share`` — contended/solo; the starvation signal.
    * ``unweighted_share_floor`` — ``1/(1+pub_streams)``, where a
      weightless server would land the heal stream.
    * ``qos_waits_delta`` — scheduler contention events observed during
      the window, proof the DRR pump (not an idle rig) produced the
      share.

    Gate (ISSUE-17 acceptance): the heal class is NOT starved —
    ``heal_contended_share`` clears the unweighted floor with margin.
    Pure-python, native-free."""
    from torchft_tpu import transport

    rng = np.random.default_rng(23)
    blob = rng.integers(0, 256, size=int(payload_mb * 1e6),
                        dtype=np.uint8).tobytes()
    view = memoryview(blob)

    def route(handler: Any) -> None:
        if handler.command != "GET":
            handler.send_error(501, "GET only")
            return
        transport.serve_ranged_bytes(handler, view, send_timeout_sec=30.0)

    srv = transport.serve_http("127.0.0.1", 0, route, name="qos-bench")
    host, port = srv.server_address[:2]
    url = f"http://{host}:{port}/blob"

    def fetch_loop(qos_name: str, stop_at: list, counter: list) -> None:
        pool = transport.ConnectionPool()
        try:
            while time.perf_counter() < stop_at[0]:
                with pool.request(
                        url, stall=60.0, auth_token=None,
                        headers={transport.QOS_HEADER: qos_name}) as resp:
                    while True:
                        chunk = resp.read(1 << 16)
                        if not chunk:
                            break
                        counter[0] += len(chunk)
        finally:
            pool.close()

    out: Dict[str, float] = {"payload_mbytes": len(blob) / 1e6,
                             "pub_streams": pub_streams,
                             "window_s": secs}
    try:
        # Solo heal leg: the reference rate everything is shared against.
        solo_c = [0]
        t0 = time.perf_counter()
        fetch_loop("heal", [t0 + secs], solo_c)
        solo = solo_c[0] / 1e6 / (time.perf_counter() - t0)

        # Saturating publication leg + the measured heal stream.
        m0 = transport.metrics()
        pub_stop = [time.perf_counter() + warmup_s + secs + 60.0]
        pub_counts = [[0] for _ in range(pub_streams)]
        pubs = [threading.Thread(target=fetch_loop,
                                 args=("publication", pub_stop, pc),
                                 daemon=True)
                for pc in pub_counts]
        for t in pubs:
            t.start()
        time.sleep(warmup_s)  # let the publication backlog form
        heal_c = [0]
        t0 = time.perf_counter()
        fetch_loop("heal", [t0 + secs], heal_c)
        wall = time.perf_counter() - t0
        pub_stop[0] = 0.0  # release the publication workers
        for t in pubs:
            t.join(timeout=120)
        contended = heal_c[0] / 1e6 / max(wall, 1e-9)
        m1 = transport.metrics()
        w = transport.QOS_WEIGHTS
        out.update({
            "heal_solo_mb_s": solo,
            "heal_contended_mb_s": contended,
            "heal_contended_share": contended / max(solo, 1e-9),
            "unweighted_share_floor": 1.0 / (1 + pub_streams),
            "qos_heal_weight_share": (
                w[transport.QoS.HEAL]
                / (w[transport.QoS.HEAL] + w[transport.QoS.PUBLICATION])),
            "pub_agg_mb_s": (sum(pc[0] for pc in pub_counts) / 1e6
                             / max(wall + warmup_s, 1e-9)),
            "qos_waits_delta": (m1["transport_qos_waits_total"]
                                - m0["transport_qos_waits_total"]),
        })
    finally:
        srv.shutdown()
        srv.server_close()
    return out


# --------------------------------------------------------------- scenario 6

def bench_sdc_overhead(hidden: int = 1024, depth: int = 4,
                       batch: int = 4096, steps: int = 5,
                       warmup: int = 2) -> Dict[str, Any]:
    """State-attestation overhead A/B (docs/design/state_attestation.md):
    the full commit boundary — a real jitted fwd/bwd/update over a
    ``depth x hidden^2`` f32 param tree, then step -> allreduce ->
    commit vote -> status publish, where the digest piggyback lives —
    with attestation on vs off. The digest is one fused jitted pass
    over the committed leaves with a 16-byte D2H; the design claims it
    is invisible next to a compute-dominated training step (its
    arithmetic is ~3 u32 ops/word vs the step's thousands of FLOPs per
    param), so the gate is ``overhead_frac < 0.02``. ``batch`` sets
    the compute:param ratio — the default keeps the step in the
    compute-dominated regime a real boundary lives in even on a CPU
    rig.

    Native-free: a mocked control plane (the same duck-typing every
    sdc unit test uses) keeps the boundary byte-identical across the
    legs while still driving the real ``_publish_status`` ->
    ``_push_digest`` -> ``_compute_state_digest`` path a live fleet
    pays."""
    from unittest.mock import MagicMock

    from torchft_tpu._native import QuorumResult
    from torchft_tpu.communicator import DummyCommunicator
    from torchft_tpu.manager import Manager

    rng = np.random.default_rng(3)
    x = jax.device_put(jnp.asarray(
        rng.normal(size=(batch, hidden)), jnp.float32))

    def loss(ps, xb):
        h = xb
        for w in ps.values():
            h = jnp.tanh(h @ w)
        return jnp.mean(h * h)

    train = jax.jit(lambda ps, xb: jax.tree_util.tree_map(
        lambda p, g: p - 0.01 * g, ps, jax.grad(loss)(ps, xb)))
    grad = {"g": jnp.ones((1024,), jnp.float32)}
    payload_mb = depth * hidden * hidden * 4 / (1 << 20)

    def leg(attest: bool) -> float:
        state = {f"w{i}": jax.device_put(jnp.asarray(
            rng.normal(size=(hidden, hidden), scale=0.02), jnp.float32))
            for i in range(depth)}
        client = MagicMock()
        client.quorum.return_value = QuorumResult(
            quorum_id=1, recover_manager_address="m:1",
            store_address="s:1", max_step=1, max_rank=0,
            max_world_size=1, replica_rank=0, replica_world_size=1,
            heal=False)
        client.should_commit.return_value = True
        m = Manager(comm=DummyCommunicator(),
                    load_state_dict=lambda s: None,
                    state_dict=lambda: state,
                    min_replica_size=1, use_async_quorum=False,
                    rank=0, world_size=1,
                    replica_id=f"sdcbench-{int(attest)}",
                    attestation=attest, fleet_telemetry=True,
                    _manager_client=client)
        # A mocked manager server whose set_digest accepts the full
        # spelling: _push_digest runs its real body, digest included.
        m._manager_server = MagicMock()

        def boundary():
            nonlocal state
            m.step()
            new = train(state, x)
            jax.block_until_ready(new)
            state.update(new)
            m.allreduce(grad).result()
            m.should_commit()

        try:
            for _ in range(warmup):
                boundary()
            walls, digests = [], []
            for _ in range(steps):
                d0 = m.metrics()["sdc_digest_ms_total"]
                t0 = time.perf_counter()
                boundary()
                walls.append(time.perf_counter() - t0)
                digests.append(m.metrics()["sdc_digest_ms_total"] - d0)
            return (1.0 / max(statistics.median(walls), 1e-9),
                    statistics.median(digests))
        finally:
            m._manager_server = None
            m.shutdown()

    off, _ = leg(False)
    on, digest_ms = leg(True)
    # The gate reads the digest's own stage share of the on-leg
    # boundary (the counter the Manager already keeps), not the
    # cross-leg steps/s ratio: adjacent single-threaded CPU matmul
    # walls jitter ~30% run to run, which would swamp a 2% read.
    # The off leg rides along so the trajectory still shows the
    # whole-boundary A/B.
    return {
        "payload_mbytes": payload_mb,
        "steps": steps,
        "on_steps_per_s": on,
        "off_steps_per_s": off,
        "digest_ms_med": digest_ms,
        "overhead_frac": digest_ms / 1e3 * on,
    }


# ------------------------------------------------------------ scenario 9
# Adaptive FT policy vs fixed policies under phase-varying chaos
# (docs/design/adaptive_policy.md; ROADMAP item 3's acceptance gate).

def bench_policy_soak(policy: str = "adaptive",
                      phases: tuple = ((5.0, 0.0), (12.0, 1.0),
                                       (5.0, 0.0)),
                      seed: int = 77, n_groups: int = 2,
                      hidden: int = 128,
                      drain_steps: int = 4) -> Dict[str, Any]:
    """One leg of the adaptive-vs-fixed A/B: ``n_groups`` replica groups
    run :class:`~torchft_tpu.policy.AdaptiveTrainer` for a FIXED wall
    budget (the phase table's total) while a seeded chaos schedule
    sweeps stable -> storm -> stable intensity over the host ring, then
    a short clean drain lets in-flight recoveries converge so the
    bitwise-lockstep oracle is exact.

    ``policy="adaptive"`` attaches a
    :class:`~torchft_tpu.policy.PolicyController` per manager (the
    quorum's rank 0 decides, the rest follow the published rung); any
    other name pins that fixed :data:`~torchft_tpu.policy.POLICIES`
    entry for the whole run.

    The gate metric is **protocol-committed batches per second** —
    ``Manager.batches_committed`` (min across groups), the repo's
    long-standing commit counter: it advances by the participating
    world per committed BOUNDARY, so a DiLoCo leg earns credit once
    per outer round, not per inner step. That deliberately prices
    DiLoCo's trade — protocol-visible commit granularity coarsens by
    ``sync_every`` (durable saves/publishes gate on commits, and a
    failure costs a whole round of agreed progress) — which also means
    a fixed ``diloco-16`` leg loses this gate by construction; the
    competitive baselines are sync-f32 and overlap-bf16. The result
    additionally reports ``trainer_batches_per_s`` (the driver's count,
    crediting a committed round with its ``sync_every`` inner batches)
    so the raw-throughput view of the same runs is visible next to the
    gate."""
    from torchft_tpu import (HostCommunicator, Lighthouse, Manager,
                             chaos)
    from torchft_tpu.chaos import ChaosCommunicator, ChaosSchedule, \
        EndpointChaos
    from torchft_tpu.policy import (POLICIES, AdaptiveTrainer,
                                    PhasedChaos, PolicyController)

    adaptive = policy == "adaptive"
    schedule = ChaosSchedule(seed=seed, endpoints={
        # Storm faults target the per-segment ring ops: narrower wire
        # rungs do fewer ops per collective, so descending the ladder
        # genuinely shrinks the per-step fault exposure (and the
        # per-op latency tax).
        "ring": EndpointChaos(latency_ms=0.5, jitter_ms=1.0,
                              reset_rate=0.03, short_rate=0.02),
        "allreduce": EndpointChaos(reset_rate=0.01),
    }, intensity=0.0)
    chaos.install(schedule)
    phaser = PhasedChaos(schedule, phases)
    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                    join_timeout_ms=1000, quorum_tick_ms=50)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(32,)), jnp.int32)
    from torchft_tpu.models import MLP

    model = MLP(features=(hidden,), num_classes=4)
    params0 = model.init(jax.random.key(7), x[:1])

    def loss_fn(params, batch):
        logits = model.apply(params, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    results: Dict[str, Dict[str, Any]] = {}

    def worker(gid: str) -> None:
        kwargs: Dict[str, Any] = {}
        if adaptive:
            kwargs["policy_controller"] = PolicyController(
                window=6, escalate_failures=2, relax_after=8,
                cooldown=3)
        else:
            kwargs["policy"] = POLICIES[policy]
        trainer = AdaptiveTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.05), params=params0,
            manager_factory=lambda load, save: Manager(
                comm=ChaosCommunicator(HostCommunicator(timeout_sec=15)),
                load_state_dict=load, state_dict=save,
                min_replica_size=1, replica_id=f"{policy}-{gid}",
                lighthouse_addr=lh.address(), rank=0, world_size=1,
                timeout_ms=15_000, quorum_timeout_ms=15_000,
                max_consecutive_failures=1000, **kwargs))
        b = {"x": x, "y": y}
        try:
            trainer.train_step(b)  # compile + join + first reconfigure
            t0 = time.perf_counter()
            base = trainer.manager.batches_committed()
            deadline = t0 + phaser.total_seconds()
            while time.perf_counter() < deadline:
                trainer.train_step(b)
            trainer.flush()
            # Clean drain TO A COMMITTED BOUNDARY: chaos is silenced
            # (intensity 0 terminal phase + uninstall below), and the
            # groups keep stepping until a boundary commits — which in
            # DiLoCo mode means driving through the remainder of the
            # inner cycle to the next outer round, where params land on
            # the shared anchor. Both groups' committed boundary is the
            # SAME collective, so both stop in the same protocol state
            # and the bitwise-lockstep oracle is exact (a fixed step
            # count would slice a DiLoCo leg mid-cycle at
            # thread-skewed local_steps).
            for _ in range(max(drain_steps, 1) * 64):
                _, committed = trainer.train_step(b)
                if committed:
                    break
            trainer.flush()
            wall = time.perf_counter() - t0
            mx = trainer.manager.metrics()
            results[gid] = {
                "params": jax.device_get(trainer.params),
                "committed_batches":
                    trainer.manager.batches_committed() - base,
                "trainer_batches": trainer.committed_batches,
                "wall_s": wall,
                "switches": mx["policy_switches_total"],
                "aborted_steps": mx["aborted_steps"],
                "policy_final":
                    trainer.manager.metrics_info()["policy_name"],
                "int8_ring_mbytes":
                    mx["allreduce_int8_ring_bytes_total"] / 1e6,
                "events": [e for e in trainer.manager.history()
                           if str(e.get("event", ""))
                           .startswith("policy")],
            }
        finally:
            trainer.shutdown()

    phaser.start()
    threads = [threading.Thread(target=worker, args=(f"g{i}",))
               for i in range(n_groups)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=phaser.total_seconds() + 240)
    finally:
        phaser.stop()
        chaos.uninstall()
        lh.shutdown()
    if len(results) != n_groups:
        raise RuntimeError(f"policy soak leg {policy!r}: only "
                           f"{len(results)}/{n_groups} groups finished")
    walls = [r["wall_s"] for r in results.values()]
    committed = min(r["committed_batches"] for r in results.values())
    trainer_batches = min(r["trainer_batches"]
                          for r in results.values())
    return {
        "policy": policy,
        "committed_batches_per_s": committed / max(max(walls), 1e-9),
        "committed_batches": committed,
        "trainer_batches_per_s":
            trainer_batches / max(max(walls), 1e-9),
        "switches": max(r["switches"] for r in results.values()),
        "aborted_steps": max(r["aborted_steps"]
                             for r in results.values()),
        "events": next(iter(results.values()))["events"],
        "groups": results,
    }


def _hard_kill_manager(m: Any) -> None:
    """SIGKILL simulation for the churn bench's control leg: tear the
    group down the way a reclaimed-without-notice VM does — sockets
    slam shut, NO farewell, NO final save, heartbeats stop — so
    survivors pay the staleness-eviction path. Reaches into Manager
    internals deliberately: a public API for dying badly would invite
    production use."""
    try:
        srv = m._manager_server
        if srv is not None:
            hs = getattr(srv, "hard_stop", None)
            (hs if hs is not None else srv.shutdown)()
    except Exception:  # noqa: BLE001
        pass
    for closer in (m._ckpt_server.shutdown, m._comm.shutdown):
        try:
            closer()
        except Exception:  # noqa: BLE001
            pass
    m._executor.shutdown(wait=False, cancel_futures=True)
    m._put_executor.shutdown(wait=False)


def bench_churn_goodput(churn_pct_per_min: float = 0.0,
                        leg: str = "graceful",
                        n_groups: int = 4,
                        duration_s: float = 30.0,
                        seed: int = 1234,
                        dim: int = 4096,
                        reclaim_s: float = 10.0,
                        replace_delay_s: float = 1.5,
                        ckpt_every: int = 4,
                        drain_steps: int = 8,
                        join_window_ms: int = 400,
                        phases: Optional[tuple] = None,
                        ram_tier: bool = False,
                        workdir: Optional[str] = None) -> Dict[str, Any]:
    """One leg of the churn-goodput curve (docs/design/churn.md, ROADMAP
    item 4): ``n_groups`` replica groups train for ``duration_s`` while
    a seeded :class:`~torchft_tpu.chaos.ChurnOrchestrator` preempts
    ``churn_pct_per_min``% of the fleet per minute — every preemption
    either a *graceful* reclaim notice (``leg="graceful"``:
    ``request_preemption(reclaim_s)`` → boundary drain → farewell →
    final sharded durable save → exit) or a SIGKILL
    (``leg="sigkill"``: sockets slam shut, no farewell — the control
    leg) — and cold replacements respawn after ``replace_delay_s``,
    cold-starting from the slot's durable checkpoints and healing in.

    The gate metric is **fleet committed-batches/sec**: any survivor's
    ``batches_committed`` delta over the window (it advances by the
    participating world per committed boundary, so it integrates the
    fleet's goodput through every membership change). The run ends with
    a churn-free drain so the bitwise-convergence oracle is exact:
    every group at the fleet's max step must hold identical bytes.

    ``phases`` optionally walks the churn intensity
    :class:`~torchft_tpu.policy.PhasedChaos`-style — a tuple of
    ``(duration_s, churn_pct_per_min)`` legs (stable -> storm ->
    stable) applied via ``ChurnOrchestrator.set_rate``; it overrides
    ``duration_s``/``churn_pct_per_min``.

    ``ram_tier=True`` arms the RAM checkpoint tier
    (docs/design/memory_tier.md) on every group: commit boundaries
    cross-replicate the just-committed image to peer hosts' RAM, and
    cold replacements probe the survivors' ``/ramckpt`` stores before
    the disk scan — the churn-goodput A/B (RAM on vs off) rides the
    nightly soak (tests/test_churn.py::TestChurnSoak).

    Needs the native control plane."""
    import shutil
    import tempfile

    from torchft_tpu import (AsyncCheckpointer, HostCommunicator,
                             Lighthouse, Manager, PreemptedExit)
    from torchft_tpu.chaos import ChurnOrchestrator

    if phases is not None:
        duration_s = sum(d for d, _ in phases)
        churn_pct_per_min = max(p for _, p in phases)
    rate_per_min = churn_pct_per_min / 100.0 * n_groups
    tmp = workdir or tempfile.mkdtemp(prefix="bench_churn_")
    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                    join_timeout_ms=1_000, quorum_tick_ms=50,
                    heartbeat_fresh_ms=300,
                    eviction_staleness_factor=3,
                    join_window_ms=join_window_ms)
    rng = np.random.default_rng(seed)
    params0 = np.asarray(rng.normal(size=(dim,)), np.float32)

    stop_all = threading.Event()
    lock = threading.Lock()
    # Per-slot mutable state shared across incarnations.
    slot_params: Dict[int, Any] = {s: {"w": params0.copy()}
                                   for s in range(n_groups)}
    registry: Dict[int, Any] = {}       # slot -> live Manager
    kill_events: Dict[int, threading.Event] = {}
    threads: Dict[int, threading.Thread] = {}
    counters = {"graceful_exits": 0, "deadline_expired": 0,
                "aborts": 0, "hard_kills": 0, "ram_heals": 0,
                "ram_replications": 0}
    finals: Dict[str, tuple] = {}  # incarnation id -> (step, batches, bytes)

    def grads(slot: int, step: int, p: Dict[str, Any]) -> Dict[str, Any]:
        # Group-varying but deterministic per (slot, step): the averaged
        # update is identical on every participant, so survivors stay
        # bitwise-lockstep through arbitrary membership drift.
        g = np.asarray(
            np.sin(np.arange(dim, dtype=np.float32) * (slot + 1)
                   + step) * 1e-2, np.float32)
        return {"w": g}

    def run_group(slot: int, incarnation: int) -> None:
        sdir = os.path.join(tmp, f"slot{slot}")
        os.makedirs(sdir, exist_ok=True)
        holder = {"p": slot_params[slot]}

        def load(state):
            holder["p"] = {k: np.asarray(v) for k, v in state.items()}

        m = Manager(
            comm=HostCommunicator(timeout_sec=10),
            load_state_dict=load, state_dict=lambda: holder["p"],
            min_replica_size=1,
            replica_id=f"g{slot}", lighthouse_addr=lh.address(),
            rank=0, world_size=1, timeout_ms=10_000,
            quorum_timeout_ms=10_000, max_consecutive_failures=10_000,
            ram_ckpt_peers=2 if ram_tier else None)
        writer = AsyncCheckpointer(keep=2, shards=2)
        m.set_durable_target(writer, sdir)
        kill_evt = threading.Event()
        with lock:
            registry[slot] = m
            kill_events[slot] = kill_evt
            slot_params[slot] = holder["p"]
        if incarnation > 0:
            peers = []
            if ram_tier:
                with lock:
                    peers = [
                        r._ckpt_server.ram_address()
                        for s2, r in registry.items() if s2 != slot]
            try:
                where = m.cold_start(
                    sdir, ram_peers=peers) if peers else m.cold_start(sdir)
                if where and "/ramckpt/" in where:
                    with lock:
                        counters["ram_heals"] += 1
            except Exception:  # noqa: BLE001 — fresh start; heal covers
                logging.getLogger(__name__).warning(
                    "cold start failed", exc_info=True)
        base = m.batches_committed()
        t0 = time.perf_counter()
        step_i = 0
        try:
            while True:
                if kill_evt.is_set():
                    with lock:
                        counters["hard_kills"] += 1
                        registry.pop(slot, None)
                    _hard_kill_manager(m)
                    return
                if stop_all.is_set() and step_i >= drain_steps:
                    break
                if stop_all.is_set():
                    step_i += 1  # churn-free drain steps before the oracle
                m.step()
                avg = m.allreduce(
                    grads(slot, m.current_step(), holder["p"])).result()
                if m.should_commit():
                    holder["p"] = {
                        k: np.asarray(holder["p"][k] - avg[k], np.float32)
                        for k in holder["p"]}
                    with lock:
                        slot_params[slot] = holder["p"]
                    if m.current_step() % ckpt_every == 0:
                        m.save_durable(writer, sdir)
                else:
                    with lock:
                        counters["aborts"] += 1
        except PreemptedExit:
            with lock:
                counters["graceful_exits"] += 1
                registry.pop(slot, None)
            return  # manager already shut down by the drain
        except Exception:  # noqa: BLE001 — a dying group is expected here
            logging.getLogger(__name__).warning(
                "churn worker g%d died", slot, exc_info=True)
            with lock:
                registry.pop(slot, None)
            # A crashed group must NOT record finals: its truncated
            # window (and possibly stale params) would pollute the
            # goodput gate and the bitwise oracle.
            return
        # Clean end-of-run exit: record the oracle inputs, then leave.
        wall = time.perf_counter() - t0
        mx = m.metrics()
        with lock:
            counters["deadline_expired"] += int(
                mx["preempt_deadline_expired_total"])
            counters["ram_replications"] += int(
                mx.get("ram_ckpt_replications_total", 0))
            finals[f"g{slot}.{incarnation}"] = (
                m.current_step(),
                (m.batches_committed() - base) / max(wall, 1e-9),
                np.asarray(holder["p"]["w"]).tobytes(),
                mx["reconfigure_count"], mx["joins_coalesced_total"],
                wall)
            registry.pop(slot, None)
        m.shutdown()

    def notify(slot: int) -> None:
        with lock:
            m = registry.get(slot)
        if m is not None:
            m.request_preemption(reclaim_s, reason="bench churn")

    def kill(slot: int) -> None:
        with lock:
            evt = kill_events.get(slot)
        if evt is not None:
            evt.set()

    def replace(slot: int) -> None:
        if stop_all.is_set():
            return
        with lock:
            inc = replace.count[slot] = replace.count.get(slot, 0) + 1
        t = threading.Thread(target=run_group, args=(slot, inc),
                             name=f"churn-g{slot}.{inc}", daemon=True)
        with lock:
            threads[f"{slot}.{inc}"] = t
        t.start()

    replace.count = {}

    orch = ChurnOrchestrator(
        seed=seed, groups=list(range(n_groups)),
        rate_per_min=rate_per_min, graceful_frac=(
            1.0 if leg == "graceful" else 0.0),
        notify=notify, kill=kill, replace=replace,
        replace_delay_s=replace_delay_s, min_live=max(1, n_groups // 2))

    for s in range(n_groups):
        t = threading.Thread(target=run_group, args=(s, 0),
                             name=f"churn-g{s}.0", daemon=True)
        threads[f"{s}.0"] = t
        t.start()
    t0 = time.monotonic()
    t_end = t0 + duration_s
    while time.monotonic() < t_end:
        if phases is not None:
            # PhasedChaos-style walk (stable -> storm -> stable).
            elapsed = time.monotonic() - t0
            pct = phases[-1][1]
            acc = 0.0
            for dur, level in phases:
                acc += dur
                if elapsed < acc:
                    pct = level
                    break
            orch.set_rate(pct / 100.0 * n_groups)
        orch.tick(time.monotonic())
        time.sleep(0.05)
    stop_all.set()
    deadline = time.monotonic() + 120.0
    for t in list(threads.values()):
        t.join(timeout=max(deadline - time.monotonic(), 1.0))
    lh.shutdown()
    if workdir is None:
        shutil.rmtree(tmp, ignore_errors=True)

    if not finals:
        raise RuntimeError("churn leg ended with no surviving group")
    max_step = max(v[0] for v in finals.values())
    at_max = {k: v for k, v in finals.items() if v[0] == max_step}
    blobs = {v[2] for v in at_max.values()}
    # Gate metric = the rate of the group with the LONGEST measurement
    # window: any survivor's batches_committed counts FLEET commits, but
    # a late replacement's short window is mostly the churn-free drain
    # phase — max() over rates would let it mask the storm's cost.
    rep = max(finals.values(), key=lambda v: v[5])
    return {
        "leg": leg,
        "churn_pct_per_min": churn_pct_per_min,
        "preempts_per_min": rate_per_min,
        "n_groups": n_groups,
        "duration_s": duration_s,
        "committed_batches_per_s": rep[1],
        "measured_window_s": rep[5],
        "graceful_exits": counters["graceful_exits"],
        "hard_kills": counters["hard_kills"],
        "deadline_expired": counters["deadline_expired"],
        "aborts": counters["aborts"],
        "notices": orch.notices, "kills": orch.kills,
        "replacements": orch.replacements,
        "reconfigures_max": max(v[3] for v in finals.values()),
        "joins_coalesced_max": max(v[4] for v in finals.values()),
        "survivors_at_max_step": len(at_max),
        "bitwise_identical": len(blobs) == 1,
        "ram_tier": bool(ram_tier),
        "ram_heals": counters["ram_heals"],
        "ram_replications": counters["ram_replications"],
    }


def bench_quorum_latency_vs_n(n: int = 64, steps: int = 30,
                              fast_path: bool = True,
                              arrival_jitter_ms: float = 2.0,
                              seed: int = 7) -> Dict[str, Any]:
    """Quorum latency at N simulated replica groups on ONE host
    (docs/design/control_plane.md): each group is a world-size-1 C++
    ManagerServer plus a thin ctypes ManagerClient thread (no JAX, no
    collectives) doing one quorum round per step behind a barrier, with a
    seeded per-step arrival jitter modeling compute imbalance — the thing
    that makes a fan-in rendezvous slow, because every group waits for the
    last arrival. The membership-unchanged fast path serves each request
    from the cached decision instead, so its latency is one RTT regardless
    of the stragglers. Reports steady-state p50/p95/max per-request quorum
    latency (first 2 warmup rounds dropped) plus the lighthouse's
    fast/slow serve counters."""
    from torchft_tpu import _native
    from torchft_tpu.retry import RetryPolicy

    lh = _native.Lighthouse(
        bind="127.0.0.1:0", min_replicas=n, join_timeout_ms=60_000,
        quorum_tick_ms=5, heartbeat_fresh_ms=500,
        eviction_staleness_factor=6, fast_path=fast_path)
    managers: list = []
    try:
        managers = [
            _native.ManagerServer(f"g{i:03d}", lh.address(),
                                  store_addr=f"store{i}",
                                  bind="127.0.0.1:0", world_size=1,
                                  heartbeat_ms=100)
            for i in range(n)
        ]
        rng = np.random.default_rng(seed)
        jitter = rng.uniform(0.0, arrival_jitter_ms * 1e-3, size=(steps, n))
        barrier = threading.Barrier(n)
        lat: list = [[] for _ in range(n)]
        errs: list = []

        def worker(i: int) -> None:
            try:
                c = _native.ManagerClient(
                    managers[i].address(), connect_timeout_ms=10_000,
                    retry_policy=RetryPolicy(max_attempts=1))
                for s in range(1, steps + 1):
                    barrier.wait()
                    time.sleep(jitter[s - 1, i])
                    t0 = time.perf_counter()
                    c.quorum(rank=0, step=s,
                             checkpoint_server_addr=f"ckpt{i}",
                             timeout_ms=120_000)
                    lat[i].append((time.perf_counter() - t0) * 1e3)
            except Exception as e:  # noqa: BLE001 — surface in the result
                errs.append(repr(e))
                try:
                    barrier.abort()
                except Exception:  # noqa: BLE001
                    pass

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        if errs:
            raise RuntimeError(f"quorum bench worker failed: {errs[0]}")
        status = lh.status()
        flat = sorted(ms for per in lat for ms in per[2:])
        return {
            "n": n, "steps": steps, "fast_path": fast_path,
            "arrival_jitter_ms": arrival_jitter_ms,
            "p50_ms": flat[len(flat) // 2],
            "p95_ms": flat[min(len(flat) - 1, int(len(flat) * 0.95))],
            "max_ms": flat[-1],
            "fast_path_hits": status.get("fast_path_hits", 0),
            "slow_path_served": status.get("slow_path_served", 0),
        }
    finally:
        for m in managers:
            m.shutdown()
        lh.shutdown()


def bench_quorum_failover(n: int = 8, steps: int = 40, kill_at: int = 20,
                          arrival_jitter_ms: float = 1.0,
                          seed: int = 13) -> Dict[str, Any]:
    """Warm-standby failover timeline: N manager groups run quorum rounds
    against a primary+standby lighthouse pair (managers configured with the
    candidate list); the primary dies at step ``kill_at``. Emits the
    per-step max quorum latency (the failover spike is the interesting
    shape), total manager re-dials, and whether the quorum_id survived the
    failover unchanged — the no-ring-rebuild contract."""
    from torchft_tpu import _native
    from torchft_tpu.retry import RetryPolicy

    primary = _native.Lighthouse(
        bind="127.0.0.1:0", min_replicas=n, join_timeout_ms=60_000,
        quorum_tick_ms=5, heartbeat_fresh_ms=500,
        eviction_staleness_factor=6)
    standby = _native.Lighthouse(
        bind="127.0.0.1:0", min_replicas=n, join_timeout_ms=60_000,
        quorum_tick_ms=5, heartbeat_fresh_ms=500,
        eviction_staleness_factor=6,
        standby_of=primary.address(), replicate_ms=25)
    managers: list = []
    primary_dead = False
    try:
        addrs = f"{primary.address()},{standby.address()}"
        managers = [
            _native.ManagerServer(f"g{i:03d}", addrs,
                                  store_addr=f"store{i}",
                                  bind="127.0.0.1:0", world_size=1,
                                  heartbeat_ms=100)
            for i in range(n)
        ]
        rng = np.random.default_rng(seed)
        jitter = rng.uniform(0.0, arrival_jitter_ms * 1e-3, size=(steps, n))
        barrier = threading.Barrier(n + 1)  # workers + the kill controller
        lat = np.zeros((steps, n))
        qids = np.zeros((steps, n), dtype=np.int64)
        errs: list = []

        def worker(i: int) -> None:
            try:
                c = _native.ManagerClient(
                    managers[i].address(), connect_timeout_ms=10_000,
                    retry_policy=RetryPolicy(max_attempts=1))
                for s in range(1, steps + 1):
                    barrier.wait()
                    time.sleep(jitter[s - 1, i])
                    t0 = time.perf_counter()
                    q = c.quorum(rank=0, step=s,
                                 checkpoint_server_addr=f"ckpt{i}",
                                 timeout_ms=120_000)
                    lat[s - 1, i] = (time.perf_counter() - t0) * 1e3
                    qids[s - 1, i] = q.quorum_id
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))
                try:
                    barrier.abort()
                except Exception:  # noqa: BLE001
                    pass

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        try:
            for s in range(1, steps + 1):
                barrier.wait()
                if s == kill_at:
                    primary.shutdown()  # in-process stand-in for SIGKILL
                    primary_dead = True
        except threading.BrokenBarrierError:
            pass  # a worker aborted; its error is in errs
        for t in threads:
            t.join(timeout=600)
        if errs:
            raise RuntimeError(f"failover bench worker failed: {errs[0]}")
        per_step_max = lat.max(axis=1)
        redials = sum(m.lighthouse_redials() for m in managers)
        return {
            "n": n, "steps": steps, "kill_at": kill_at,
            "pre_kill_p50_ms": float(np.median(per_step_max[2:kill_at - 1])),
            "failover_spike_ms": float(per_step_max[kill_at - 1:].max()),
            "post_kill_p50_ms": float(np.median(per_step_max[kill_at + 2:])),
            "per_step_max_ms": [round(float(v), 2) for v in per_step_max],
            "redials_total": int(redials),
            "quorum_id_stable_across_failover":
                bool((qids == qids[0, 0]).all()),
        }
    finally:
        for m in managers:
            m.shutdown()
        if not primary_dead:
            primary.shutdown()
        standby.shutdown()


# --------------------------------------------------------------------- main

def main() -> None:
    # A benchmark number names the device it ran on. Without a TPU, or
    # without the native control plane that every FT row needs, the run
    # fails: it never emits `error` rows and exits 0.
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py: needs a TPU backend, found {platform!r}")
    from torchft_tpu import _native
    from torchft_tpu.utils import enable_compile_cache

    _native.lib()  # raises with the build error when the core is missing
    enable_compile_cache()

    probes = bench_rig_probes()
    _emit({"metric": "rig_probes",
           "d2h_mb_s": round(probes["d2h_mb_s"], 2),
           "h2d_mb_s": round(probes["h2d_mb_s"], 2),
           "dispatch_ms": round(probes["dispatch_ms"], 1),
           "probe_mbytes": probes["probe_mbytes"]})

    single = bench_single_group()
    _emit({"metric": "img_per_s",
           "value": round(single["img_per_s"], 1),
           "unit": "images/s", "batch": single["batch"]})
    if "achieved_tflops" in single:
        _emit({"metric": "achieved_tflops",
               "value": round(single["achieved_tflops"], 2),
               "unit": "TFLOP/s",
               "mfu_vs_bf16_peak": round(
                   single["achieved_tflops"] / _peak_tflops(), 4)})

    tr = bench_transformer()
    _emit({"metric": "transformer_tokens_per_s",
           "value": round(tr["tokens_per_s"], 1), "unit": "tokens/s",
           "n_params": tr["n_params"],
           "achieved_tflops": round(tr["achieved_tflops"], 2),
           "mfu_vs_bf16_peak": round(
               tr["achieved_tflops"] / _peak_tflops(), 4)})

    def stages(r: Dict[str, Any]) -> Dict[str, float]:
        return {k: round(v, 1) for k, v in r["stages_ms"].items()}

    def mgrow(r: Dict[str, Any]) -> Dict[str, Any]:
        """Fields stamped into EVERY multigroup row: the actual D2H
        fetch bytes (wire bytes, not grad bytes) and the transport
        topology the run resolved to."""
        return {"fetch_mbytes_per_step":
                    round(r["fetch_mbytes_per_step"], 3),
                "ring_topology": r["ring_topology"]}

    mg = bench_multigroup()
    _emit({"metric": "multigroup_steps_per_s",
           "value": round(mg["steps_per_s"], 2), "unit": "steps/s",
           "n_groups": mg["n_groups"], "backend": "host",
           "policy": mg["policy"], **mgrow(mg),
           "allreduce_ms_avg": round(mg["allreduce_ms_avg"], 2),
           "grad_mbytes": round(mg["grad_mbytes"], 2),
           "quorum_ms_p50": round(mg["quorum_ms_p50"], 2),
           "quorum_ms_p95": round(mg["quorum_ms_p95"], 2),
           "quorum_fast_frac": round(mg["quorum_fast_frac"], 3),
           "stages_ms": stages(mg)})

    mw = bench_multigroup(wire_dtype=jnp.bfloat16)
    _emit({"metric": "multigroup_bf16_wire_steps_per_s",
           "value": round(mw["steps_per_s"], 2), "unit": "steps/s",
           "n_groups": mw["n_groups"], "backend": "host+bf16wire",
           "policy": mw["policy"], **mgrow(mw),
           "allreduce_ms_avg": round(mw["allreduce_ms_avg"], 2),
           "speedup_vs_exact": round(mw["steps_per_s"]
                                     / max(mg["steps_per_s"], 1e-9), 2),
           "wire_mbytes_per_step": round(mw["wire_mbytes_per_step"], 2),
           "ring_wire_mbytes_per_step":
               round(mw["ring_wire_mbytes_per_step"], 2),
           "stages_ms": stages(mw)})

    # ~8.6MB gradient point (hidden=1024, depth=3): big enough that 2MB
    # buckets multi-bucket, making the single-shot-vs-bucketed A/B
    # meaningful — and bf16 wire halves a D2H leg that dominates here.
    big = dict(hidden=1024, depth=3, steps=6)
    m1 = bench_multigroup(bucket_bytes=1 << 40, **big)  # single-shot
    mb = bench_multigroup(bucket_bytes=2 << 20, **big)  # pipelined buckets
    _emit({"metric": "multigroup_8mb_ab",
           "policy": mb["policy"], **mgrow(mb),
           "grad_mbytes": round(mb["grad_mbytes"], 2),
           "single_shot_steps_per_s": round(m1["steps_per_s"], 3),
           "bucketed_steps_per_s": round(mb["steps_per_s"], 3),
           "bucketing_speedup": round(
               mb["steps_per_s"] / max(m1["steps_per_s"], 1e-9), 2),
           "single_shot_stages_ms": stages(m1),
           "bucketed_stages_ms": stages(mb)})
    mwb = bench_multigroup(bucket_bytes=2 << 20,
                           wire_dtype=jnp.bfloat16, **big)
    _emit({"metric": "multigroup_8mb_bf16_wire",
           "value": round(mwb["steps_per_s"], 3), "unit": "steps/s",
           "policy": mwb["policy"], **mgrow(mwb),
           "speedup_vs_exact": round(
               mwb["steps_per_s"] / max(mb["steps_per_s"], 1e-9), 2),
           "wire_mbytes_per_step": round(mwb["wire_mbytes_per_step"], 2),
           "ring_wire_mbytes_per_step":
               round(mwb["ring_wire_mbytes_per_step"], 2),
           "stages_ms": stages(mwb)})

    # Sync vs cross-step-overlap A/B on the same comm-bound 8MB scenario
    # (docs/design/overlap.md): overlap drains step N's exchange under
    # step N+1's compute, so steps/s should approach max(compute, comm)
    # instead of their sum. hidden_comm_ms is the per-step comm wall the
    # engine actually hid; stage busy FRACTIONS (stage busy ms per step
    # wall ms) make a throughput swing attributable — if overlap won,
    # the ring/fetch fraction rises (same comm, less wall) while
    # steps/s climbs.
    mov = bench_multigroup(bucket_bytes=2 << 20, overlap_steps=1, **big)

    def busy_frac(r: Dict[str, Any]) -> Dict[str, float]:
        wall_ms = 1e3 / max(r["steps_per_s"], 1e-9)
        return {k: round(v / wall_ms, 3)
                for k, v in r["stages_ms"].items()}

    _emit({"metric": "multigroup_8mb_overlap_ab",
           "sync_policy": mb["policy"], "overlap_policy": mov["policy"],
           **mgrow(mov),
           "grad_mbytes": round(mov["grad_mbytes"], 2),
           "sync_steps_per_s": round(mb["steps_per_s"], 3),
           "overlap_steps_per_s": round(mov["steps_per_s"], 3),
           "overlap_speedup": round(
               mov["steps_per_s"] / max(mb["steps_per_s"], 1e-9), 2),
           "hidden_comm_ms_avg": round(mov["hidden_ms_avg"], 1),
           "drain_wait_ms_avg": round(mov["drain_wait_ms_avg"], 1),
           "sync_stage_busy_frac": busy_frac(mb),
           "overlap_stage_busy_frac": busy_frac(mov)})

    # Tracing-overhead A/B on the same comm-bound 8MB scenario
    # (docs/design/observability.md): per-step span tracing defaults ON,
    # so its cost must be a MEASURED row, not a promise — steps/s with
    # the tracer recording every stage span vs. hard-off. Gate: < 2%
    # overhead (overhead_frac = 1 - on/off); tiny negatives are rig
    # noise.
    mtr_on = bench_multigroup(bucket_bytes=2 << 20, tracing=True, **big)
    mtr_off = bench_multigroup(bucket_bytes=2 << 20, tracing=False,
                               **big)
    _emit({"metric": "multigroup_8mb_trace_ab",
           "policy": mtr_on["policy"], **mgrow(mtr_on),
           "grad_mbytes": round(mtr_on["grad_mbytes"], 2),
           "trace_on_steps_per_s": round(mtr_on["steps_per_s"], 3),
           "trace_off_steps_per_s": round(mtr_off["steps_per_s"], 3),
           "overhead_frac": round(
               1.0 - mtr_on["steps_per_s"]
               / max(mtr_off["steps_per_s"], 1e-9), 4),
           "target_max_overhead_frac": 0.02,
           "trace_on_stages_ms": stages(mtr_on),
           "trace_off_stages_ms": stages(mtr_off)})

    # Fleet-telemetry overhead A/B on the same scenario
    # (docs/design/fleet_health.md): the per-boundary digest push +
    # quorum-piggybacked aggregation defaults ON, so its cost rides the
    # same <2% gate as tracing. The ON leg's echoed fleet_p95_ms/
    # fleet_groups also prove the digest->aggregate->hint loop closed.
    mfl_on = bench_multigroup(bucket_bytes=2 << 20,
                              fleet_telemetry=True, **big)
    mfl_off = bench_multigroup(bucket_bytes=2 << 20,
                               fleet_telemetry=False, **big)
    _emit({"metric": "multigroup_8mb_fleet_ab",
           "policy": mfl_on["policy"], **mgrow(mfl_on),
           "grad_mbytes": round(mfl_on["grad_mbytes"], 2),
           "fleet_on_steps_per_s": round(mfl_on["steps_per_s"], 3),
           "fleet_off_steps_per_s": round(mfl_off["steps_per_s"], 3),
           "overhead_frac": round(
               1.0 - mfl_on["steps_per_s"]
               / max(mfl_off["steps_per_s"], 1e-9), 4),
           "target_max_overhead_frac": 0.02,
           "fleet_p95_ms": round(mfl_on["fleet_p95_ms"], 1),
           "fleet_groups": int(mfl_on["fleet_groups"]),
           "fleet_off_groups": int(mfl_off["fleet_groups"])})

    # Allreduce vs ZeRO-style reduce-scatter+allgather A/B on the same
    # 8MB scenario (docs/design/sharded_update.md): the rs leg receives
    # only its stripe of the averaged gradient, updates that stripe, and
    # allgathers updated params — per-group update wall + optimizer-state
    # memory should scale ~1/n_groups while steps/s holds or climbs
    # (less fold compute; comparable ring bytes at world 2).
    mrs = bench_multigroup(bucket_bytes=2 << 20, shard_update=True, **big)
    _emit({"metric": "multigroup_8mb_rs_ab",
           "policy": mrs["policy"], **mgrow(mrs),
           "grad_mbytes": round(mrs["grad_mbytes"], 2),
           "allreduce_steps_per_s": round(mb["steps_per_s"], 3),
           "rs_steps_per_s": round(mrs["steps_per_s"], 3),
           "rs_speedup": round(
               mrs["steps_per_s"] / max(mb["steps_per_s"], 1e-9), 2),
           "allreduce_ring_wire_mbytes_per_step":
               round(mb["ring_wire_mbytes_per_step"], 2),
           "rs_ring_wire_mbytes_per_step":
               round(mrs["ring_wire_mbytes_per_step"], 2),
           # Update stage: commit bucket (optimizer apply + vote) is the
           # cross-mode comparable; update_ms_avg is the rs leg's own
           # stripe-update busy wall; opt_state_mbytes ~1/n_groups.
           "allreduce_commit_ms_avg": round(mb["commit_ms_avg"], 1),
           "rs_commit_ms_avg": round(mrs["commit_ms_avg"], 1),
           "rs_update_ms_avg": round(mrs["update_ms_avg"], 1),
           "allreduce_opt_state_mbytes":
               round(mb["opt_state_mbytes"], 2),
           "rs_opt_state_mbytes": round(mrs["opt_state_mbytes"], 2)})

    # Device-side wire quantization A/B (ROADMAP item 2, docs/design/
    # hier_transport.md): the same comm-bound 8MB scenario with the
    # quantize/cast fused into the device pack (D2H moves WIRE bytes)
    # vs host-side (D2H moves full-precision bytes, quantize/cast on
    # the host). Two rungs: bf16 wire (2x fetch bytes host-side) and
    # the int8+EF policy rung (4x). Gate: device fetch-stage ms <=
    # 0.6x host-side at 8MB; results are bitwise identical across the
    # legs (the parity tests/test_transport.py freezes).
    from torchft_tpu import policy as _pol
    int8_policy = next(p for p in _pol.LADDER if p.name == "sync-int8")
    legs = {}
    for dq in (True, False):
        legs[("bf16", dq)] = bench_multigroup(
            bucket_bytes=2 << 20, wire_dtype=jnp.bfloat16,
            device_quantize=dq, **big)
        legs[("int8", dq)] = bench_multigroup(
            bucket_bytes=2 << 20, policy=int8_policy,
            device_quantize=dq, **big)

    def dq_fields(rung: str) -> Dict[str, Any]:
        dev, host = legs[(rung, True)], legs[(rung, False)]
        dev_f = dev["stages_ms"]["fetch"]
        host_f = host["stages_ms"]["fetch"]
        return {
            f"{rung}_dev_fetch_ms_avg": round(dev_f, 2),
            f"{rung}_host_fetch_ms_avg": round(host_f, 2),
            f"{rung}_fetch_ms_ratio": round(
                dev_f / max(host_f, 1e-9), 3),
            f"{rung}_dev_fetch_mbytes_per_step": round(
                dev["fetch_mbytes_per_step"], 3),
            f"{rung}_host_fetch_mbytes_per_step": round(
                host["fetch_mbytes_per_step"], 3),
            f"{rung}_dev_steps_per_s": round(dev["steps_per_s"], 3),
            f"{rung}_host_steps_per_s": round(host["steps_per_s"], 3),
        }

    _emit({"metric": "multigroup_8mb_devquant_ab",
           "grad_mbytes": round(
               legs[("bf16", True)]["grad_mbytes"], 2),
           "target_fetch_ms_ratio": 0.6,
           **mgrow(legs[("int8", True)]),
           **dq_fields("bf16"), **dq_fields("int8"),
           # Is the fetch stage still the majority of the host step?
           "int8_dev_fetch_frac_of_step": round(
               legs[("int8", True)]["stages_ms"]["fetch"]
               / max(1e3 / max(legs[("int8", True)]["steps_per_s"],
                               1e-9), 1e-9), 3)})

    # Flat vs hierarchical transport A/B (docs/design/
    # hier_transport.md): 4 groups as 2 simulated hosts x 2 co-located
    # ranks. The hier leg's cross-host (leader-ring) bytes must scale
    # with hosts, not groups: <= 1/per_host of the flat ring bytes at
    # 2x2 (measured: hosts*(hosts-1)*per_host vs n*(n-1) raw-buffer
    # sends), with bitwise-identical results (fold order unchanged;
    # frozen by tests/test_transport.py).
    hier_cfg = dict(n_groups=4, steps=4, hidden=1024, depth=3,
                    bucket_bytes=2 << 20, wire_dtype=jnp.bfloat16)
    mflat = bench_multigroup(**hier_cfg)
    mhier = bench_multigroup(hier_hosts=2, **hier_cfg)
    _emit({"metric": "multigroup_8mb_hier_ab",
           "policy": mhier["policy"],
           "flat_ring_topology": mflat["ring_topology"],
           "hier_ring_topology": mhier["ring_topology"],
           "fetch_mbytes_per_step": round(
               mhier["fetch_mbytes_per_step"], 3),
           "ring_topology": mhier["ring_topology"],
           "flat_steps_per_s": round(mflat["steps_per_s"], 3),
           "hier_steps_per_s": round(mhier["steps_per_s"], 3),
           "hier_speedup": round(
               mhier["steps_per_s"] / max(mflat["steps_per_s"], 1e-9),
               2),
           # Cross-host bytes, summed across groups: the flat leg's
           # ring bytes ALL cross hosts; the hier leg's leader-ring
           # slice is the cross-host traffic (intra-host star bytes
           # are loopback).
           "flat_ring_wire_mbytes_per_step": round(
               mflat["ring_wire_mbytes_per_step_total"], 2),
           "hier_leader_mbytes_per_step": round(
               mhier["hier_leader_mbytes_per_step"], 2),
           "hier_intra_mbytes_per_step": round(
               mhier["hier_intra_mbytes_per_step"], 2),
           "cross_host_bytes_ratio": round(
               mhier["hier_leader_mbytes_per_step"]
               / max(mflat["ring_wire_mbytes_per_step_total"], 1e-9),
               3),
           "target_cross_host_bytes_ratio": 0.5})

    # Degraded-mode goodput A/B (docs/design/degraded_mode.md): one
    # group loses half its capacity mid-run and keeps contributing at
    # nonuniform parallelism — committed-samples/sec should settle well
    # above the ~50% whole-group-eviction floor (nightly gate >= 70%).
    dg = bench_degraded_goodput()
    _emit({"metric": "degraded_goodput_ab",
           "n_groups": dg["n_groups"],
           "degrade_fraction": dg["degrade_fraction"],
           "healthy_samples_per_s": round(
               dg["healthy_samples_per_s"], 1),
           "degraded_samples_per_s": round(
               dg["degraded_samples_per_s"], 1),
           "degraded_ratio": round(dg["degraded_ratio"], 3),
           "eviction_ratio": dg["eviction_ratio"],
           "capacity_fractions": dg["capacity_fractions"]})

    # Striped-heal A/B: 1 vs 3 donors at a fixed per-donor egress cap
    # (the donor-uplink-bound regime); wall should drop toward 1/3.
    hs = bench_heal_striped()
    _emit({"metric": "heal_striped_ab",
           "payload_mbytes": round(hs["payload_mbytes"], 1),
           "donors": hs["donors"],
           "donor_cap_mb_s": hs["donor_cap_mb_s"],
           "single_wall_s": round(hs["single_wall_s"], 2),
           "striped_wall_s": round(hs["striped_wall_s"], 2),
           "single_mb_s": round(hs["single_mb_s"], 1),
           "striped_mb_s": round(hs["striped_mb_s"], 1),
           "striped_speedup": round(hs["striped_speedup"], 2),
           "donors_used": hs.get("donors_used")})

    # Recovery-ladder A/B (docs/design/memory_tier.md): cold replacement
    # healing from a peer's RAM tier over the NIC vs the rate-capped
    # disk-only rung. Gate: ram_speedup >= 2.0. Both server cores run
    # (threaded legacy vs async substrate); the headline fields carry
    # the async leg — the shipping configuration — and the threaded
    # leg rides along for the cut-over comparison.
    rt_thr, rt = _ab_server_cores(bench_recovery_tiers)
    _emit({"metric": "recovery_tiers_ab",
           "payload_mbytes": round(rt["payload_mbytes"], 1),
           "disk_cap_mb_s": rt["disk_cap_mb_s"],
           "nic_cap_mb_s": rt["nic_cap_mb_s"],
           "disk_wall_s": round(rt["disk_wall_s"], 2),
           "ram_wall_s": round(rt["ram_wall_s"], 2),
           "disk_mb_s": round(rt["disk_mb_s"], 1),
           "ram_mb_s": round(rt["ram_mb_s"], 1),
           "ram_speedup": round(rt["ram_speedup"], 2),
           "bitwise_identical": rt["bitwise_identical"],
           "threaded_ram_mb_s": round(rt_thr["ram_mb_s"], 1),
           "async_over_threaded_ram": round(
               rt["ram_mb_s"] / max(rt_thr["ram_mb_s"], 1e-9), 3)})

    # State-attestation overhead A/B (docs/design/state_attestation.md):
    # the commit-boundary loop with the device digest on vs off; the
    # fused fingerprint pass + 16-byte D2H must stay invisible next to
    # a real boundary. Gate: overhead_frac < 0.02. Native-free.
    so = bench_sdc_overhead()
    _emit({"metric": "sdc_overhead_ab",
           "payload_mbytes": round(so["payload_mbytes"], 1),
           "steps": so["steps"],
           "sdc_on_steps_per_s": round(so["on_steps_per_s"], 2),
           "sdc_off_steps_per_s": round(so["off_steps_per_s"], 2),
           "digest_ms_med": round(so["digest_ms_med"], 2),
           "overhead_frac": round(so["overhead_frac"], 4),
           "target_max_overhead_frac": 0.02})

    # Straggler-rebalancing goodput A/B (docs/design/fleet_rebalance.md):
    # one 2x-slow group, the real Rebalancer ladder + ElasticSampler
    # draws on a simulated clock vs lockstep uniform parallelism.
    # Gate: rebalance_ratio >= 0.8 (it lands well above 1.0), fraction
    # never below the floor, zero tail flaps, fold bitwise. Native-free.
    rg = bench_rebalance_goodput()
    _emit({"metric": "rebalance_goodput_ab",
           "n_groups": rg["n_groups"],
           "slow_factor": rg["slow_factor"],
           "uniform_samples_per_s": round(
               rg["uniform_samples_per_s"], 1),
           "rebalance_samples_per_s": round(
               rg["rebalance_samples_per_s"], 1),
           "rebalance_ratio": round(rg["rebalance_ratio"], 3),
           "target_min_ratio": 0.8,
           "min_fraction": rg["min_fraction"],
           "floor": rg["floor"],
           "tail_flaps": rg["tail_flaps"],
           "shrinks_total": rg["shrinks_total"],
           "restores_total": rg["restores_total"],
           "adoption_lag_boundaries": rg["adoption_lag_boundaries"],
           "bitwise_identical": rg["bitwise_identical"]})

    # Control-plane scale (docs/design/control_plane.md): quorum latency
    # vs N simulated manager groups with the membership-unchanged fast
    # path on/off, and the warm-standby failover timeline. Thin ctypes
    # loops against the C++ lighthouse.
    for nq in (4, 16, 64):
        legs = {}
        for fp in (True, False):
            legs[fp] = bench_quorum_latency_vs_n(n=nq, fast_path=fp)
        _emit({"metric": "quorum_latency_vs_n", "n": nq,
               "fast_p50_ms": round(legs[True]["p50_ms"], 3),
               "fast_p95_ms": round(legs[True]["p95_ms"], 3),
               "slow_p50_ms": round(legs[False]["p50_ms"], 3),
               "slow_p95_ms": round(legs[False]["p95_ms"], 3),
               "fast_path_speedup_p50": round(
                   legs[False]["p50_ms"]
                   / max(legs[True]["p50_ms"], 1e-9), 2),
               "arrival_jitter_ms": legs[True]["arrival_jitter_ms"],
               "fast_path_hits": legs[True]["fast_path_hits"]})
    # Churn goodput curve (docs/design/churn.md, ROADMAP item 4):
    # committed-batches/sec under seeded Poisson preemption at
    # accelerated churn rates (a per-commit bench can't wait out a
    # literal 5%/min hour — the nightly soak runs the gated legs),
    # graceful-drain vs SIGKILL A/B. churn_rate (%-of-fleet/min) is
    # stamped on EVERY row.
    churn_base = bench_churn_goodput(churn_pct_per_min=0.0,
                                     duration_s=20.0)
    base_rate = max(churn_base["committed_batches_per_s"], 1e-9)
    _emit({"metric": "churn_goodput", "leg": "baseline",
           "churn_rate": 0.0,
           "committed_batches_per_s": round(base_rate, 2),
           "baseline_ratio": 1.0,
           "bitwise_identical": churn_base["bitwise_identical"]})
    for leg in ("graceful", "sigkill"):
        row = bench_churn_goodput(churn_pct_per_min=150.0, leg=leg,
                                  duration_s=20.0, reclaim_s=6.0)
        _emit({"metric": "churn_goodput", "leg": leg,
               "churn_rate": row["churn_pct_per_min"],
               "committed_batches_per_s": round(
                   row["committed_batches_per_s"], 2),
               "baseline_ratio": round(
                   row["committed_batches_per_s"] / base_rate, 3),
               "notices": row["notices"], "kills": row["kills"],
               "replacements": row["replacements"],
               "graceful_exits": row["graceful_exits"],
               "deadline_expired": row["deadline_expired"],
               "aborts": row["aborts"],
               "reconfigures_max": row["reconfigures_max"],
               "joins_coalesced_max": row["joins_coalesced_max"],
               "bitwise_identical": row["bitwise_identical"]})
    # Churn-goodput RAM-tier A/B (docs/design/memory_tier.md): the
    # same sigkill leg with commit-boundary RAM cross-replication
    # and RAM-preferring cold starts on vs off.
    for armed in (False, True):
        row = bench_churn_goodput(
            churn_pct_per_min=150.0, leg="sigkill",
            duration_s=20.0, ram_tier=armed)
        _emit({"metric": "churn_goodput_ram_ab",
               "ram_tier": armed,
               "churn_rate": row["churn_pct_per_min"],
               "committed_batches_per_s": round(
                   row["committed_batches_per_s"], 2),
               "baseline_ratio": round(
                   row["committed_batches_per_s"] / base_rate, 3),
               "kills": row["kills"],
               "replacements": row["replacements"],
               "ram_heals": row["ram_heals"],
               "ram_replications": row["ram_replications"],
               "bitwise_identical": row["bitwise_identical"]})

    fo = bench_quorum_failover()
    _emit({"metric": "quorum_standby_failover", "n": fo["n"],
           "kill_at": fo["kill_at"],
           "pre_kill_p50_ms": round(fo["pre_kill_p50_ms"], 2),
           "failover_spike_ms": round(fo["failover_spike_ms"], 1),
           "post_kill_p50_ms": round(fo["post_kill_p50_ms"], 2),
           "redials_total": fo["redials_total"],
           "quorum_id_stable_across_failover":
               fo["quorum_id_stable_across_failover"],
           "per_step_max_ms": fo["per_step_max_ms"]})

    mm = bench_multigroup(backend="mesh")
    _emit({"metric": "multigroup_mesh_steps_per_s",
           "value": round(mm["steps_per_s"], 2), "unit": "steps/s",
           "n_groups": mm["n_groups"], "backend": "mesh",
           "policy": mm["policy"], **mgrow(mm),
           "allreduce_ms_avg": round(mm["allreduce_ms_avg"], 2),
           "speedup_vs_host": round(mm["steps_per_s"]
                                    / max(mg["steps_per_s"], 1e-9), 2)})

    dl = bench_diloco()
    _emit({"metric": "diloco_inner_steps_per_s",
           "value": round(dl["inner_steps_per_s"], 2), "unit": "steps/s",
           "sync_every": dl["sync_every"],
           "speedup_vs_ddp": round(dl["inner_steps_per_s"]
                                   / max(mg["steps_per_s"], 1e-9), 2)})

    # bench_diloco(streaming_fragments=K) swaps the plain trainer for the
    # streaming variant (importable for experiments; no CLI plumbing). It
    # is deliberately NOT a headline metric on this rig: streaming trades
    # K-fold more (fixed-cost) control rounds for byte smoothing + compute
    # overlap, a trade that only pays when DCN transfer bytes and inner
    # compute dominate the fixed round cost — on a single-chip localhost
    # loop the fixed costs are expected to dominate (see
    # StreamingDiLoCoTrainer's docstring; not measured on an attached
    # chip).

    lc = bench_long_context()
    _emit({"metric": "long_context_tokens_per_s",
           "value": round(lc["tokens_per_s"], 1), "unit": "tokens/s",
           "seq_len": lc["seq_len"],
           "ms_per_fwd_bwd": round(lc["ms_per_fwd_bwd"], 2),
           "achieved_tflops": round(lc["achieved_tflops"], 2),
           "delta_timing_valid": lc["delta_timing_valid"]})

    # BASELINE config 3 feasibility: per-chip HBM for the Llama-2 7B HSDP
    # step, from XLA's own buffer assignment AOT-compiled against a real
    # v5e:4x4 topology (scripts/llama7b_memory.py — minutes of TPU-target
    # compile, so the bench replays the committed result, flagged
    # aot_cached; the analysis is topology-deterministic, not a rig
    # measurement. Re-run the script after model/sharding/jaxlib changes.)
    try:
        import pathlib
        cache = pathlib.Path(__file__).parent / "docs" \
            / "llama7b_memory.json"
        mem = json.loads(cache.read_text())
        mem["aot_cached"] = True
        _emit(mem)
    except Exception as e:  # noqa: BLE001
        _emit({"metric": "llama7b_hsdp_hbm_gb_per_chip", "value": -1.0,
               "error": f"no cached AOT analysis: {e}"})

    rec = bench_recovery()
    _emit({"metric": "recovery_wall_clock_s",
           "value": round(rec.get("recovery_wall_clock_s", -1.0), 3),
           "unit": "s",
           "survivor_aborted_steps": rec.get("survivor_aborted_steps"),
           "survivor_heals": rec.get("survivor_heals"),
           "attempts": rec.get("recovery_attempts"),
           "dispatch_probe_ms": round(rec.get("dispatch_probe_ms", -1.0), 1),
           # Exact main-thread wall partition (sums to value): see
           # bench_recovery for phase meanings.
           "phases_s": {
               k[len("phase_"):-2]: round(rec[k], 3)
               for k in ("phase_reinit_s", "phase_dispatch_compile_s",
                         "phase_allreduce_wait_s", "phase_commit_s",
                         "phase_glue_s", "phase_other_s") if k in rec},
           # Quorum-thread busy annotations (overlap the phases above).
           "busy_s": {
               k[:-len("_busy_s")]: round(rec[k], 3)
               for k in ("quorum_busy_s", "heal_busy_s",
                         "reconfigure_busy_s") if k in rec},
           "heal_mbytes": round(rec.get("heal_mbytes", 0.0), 3)})

    # Weight-distribution tier (docs/design/serving.md): publish-to-
    # visible latency for a long-polling fleet, small-touch delta ratio
    # (target: ~changed-leaves/total, here 1/12), and the uplink-capped
    # fan-out capacity A/B (relay tier target: >= 4x direct). Both
    # server cores run; headline fields carry the async-substrate leg,
    # with the threaded leg's aggregate throughputs alongside for the
    # cut-over comparison (async must hold or beat threaded).
    pf_thr, pf = _ab_server_cores(bench_publish_fanout)
    _emit({"metric": "publish_fanout",
           "payload_mbytes": round(pf["payload_mbytes"], 2),
           "subscribers": pf["subscribers"], "relays": pf["relays"],
           "uplink_cap_mb_s": pf["uplink_cap_mb_s"],
           "publish_to_visible_p50_ms":
               round(pf["publish_to_visible_p50_ms"], 1),
           "publish_to_visible_p95_ms":
               round(pf["publish_to_visible_p95_ms"], 1),
           "delta_full_ratio": round(pf["delta_full_ratio"], 4),
           "direct_agg_mb_s": round(pf["direct_agg_mb_s"], 2),
           "relay_agg_mb_s": round(pf["relay_agg_mb_s"], 2),
           "fanout_capacity_ratio":
               round(pf["fanout_capacity_ratio"], 2),
           "vs_capacity_target": round(
               pf["fanout_capacity_ratio"]
               / pf["capacity_target_ratio"], 3),
           "threaded_direct_agg_mb_s": round(
               pf_thr["direct_agg_mb_s"], 2),
           "threaded_relay_agg_mb_s": round(
               pf_thr["relay_agg_mb_s"], 2),
           "async_over_threaded_direct": round(
               pf["direct_agg_mb_s"]
               / max(pf_thr["direct_agg_mb_s"], 1e-9), 3),
           "async_over_threaded_relay": round(
               pf["relay_agg_mb_s"]
               / max(pf_thr["relay_agg_mb_s"], 1e-9), 3)})

    # Quantized delta publication A/B (ISSUE 20): delta wire bytes on a
    # small-touch update must land at ~1/4 of the changed leaves' f32
    # bytes, and the delta leg must hold bitwise identity with the
    # full-fetch leg every generation.
    da = bench_publish_delta_ab()
    _emit({"metric": "publish_delta_ab",
           "payload_mbytes": round(da["payload_mbytes"], 2),
           "publishes": da["publishes"],
           "delta_wire_bytes": da["delta_wire_bytes"],
           "changed_f32_bytes": da["changed_f32_bytes"],
           "delta_wire_ratio": round(da["delta_wire_ratio"], 4),
           "fetched_ratio": round(da["fetched_ratio"], 4),
           "delta_crc_fallbacks": da["delta_crc_fallbacks"],
           "bitwise_equal": da["bitwise_equal"],
           "vs_wire_target": round(
               da["wire_ratio_target"]
               / max(da["delta_wire_ratio"], 1e-9), 3)})

    # Relay-steering A/B (ISSUE 20): with deltas + steering on, the
    # ~10x fleet's publish-to-visible p95 must stay ~flat vs the small
    # fleet under the same fixed uplink cap, and a relay killed mid-run
    # must re-parent its children with zero torn observations.
    sa = bench_publish_steering_ab()
    _emit({"metric": "publish_steering_ab",
           "payload_mbytes": round(sa["payload_mbytes"], 2),
           "uplink_cap_mb_s": sa["uplink_cap_mb_s"],
           "relays_small": sa["relays_small"],
           "relays_large": sa["relays_large"],
           "subscribers_small": sa["subscribers_small"],
           "subscribers_large": sa["subscribers_large"],
           "small_p95_ms": round(sa["small_p95_ms"], 1),
           "small_direct_p95_ms": round(sa["small_direct_p95_ms"], 1),
           "steered_p95_ms": round(sa["steered_p95_ms"], 1),
           "direct_p95_ms": round(sa["direct_p95_ms"], 1),
           "steered_growth_p95_ratio": round(
               sa["steered_growth_p95_ratio"], 3),
           "direct_growth_p95_ratio": round(
               sa["direct_growth_p95_ratio"], 3),
           "direct_over_steered_p95": round(
               sa["direct_over_steered_p95"], 3),
           "steers": sa["steers"],
           "kill_orphans": sa["kill_orphans"],
           "kill_reparented": sa["kill_reparented"],
           "torn_observations": sa["torn_observations"]})

    # Heal-vs-publish contention on the shared substrate (ISSUE 17): a
    # saturating publication leg must not starve the heal class — the
    # DRR weights (heal 4 : publication 2) hold the contended heal
    # share far above the 1/(1+pub_streams) unweighted floor.
    qc = bench_qos_contention()
    _emit({"metric": "qos_contention",
           "payload_mbytes": round(qc["payload_mbytes"], 1),
           "pub_streams": qc["pub_streams"],
           "window_s": qc["window_s"],
           "heal_solo_mb_s": round(qc["heal_solo_mb_s"], 1),
           "heal_contended_mb_s": round(qc["heal_contended_mb_s"], 1),
           "heal_contended_share": round(qc["heal_contended_share"], 3),
           "unweighted_share_floor": round(
               qc["unweighted_share_floor"], 3),
           "qos_heal_weight_share": round(
               qc["qos_heal_weight_share"], 3),
           "pub_agg_mb_s": round(qc["pub_agg_mb_s"], 1),
           "qos_waits_delta": qc["qos_waits_delta"]})

    # Headline (stdout, exactly one line): FT efficiency vs the 0.90
    # north-star bar (BASELINE.json; the reference publishes no numbers).
    print(json.dumps({
        "metric": "ft_efficiency",
        "value": round(single["ft_steps_per_s"], 3),
        "unit": "steps/s",
        "vs_baseline": round(single["efficiency"] / 0.90, 4),
        **_provenance(),
    }))
    print(f"# raw={single['raw_steps_per_s']:.3f} steps/s "
          f"ft={single['ft_steps_per_s']:.3f} steps/s "
          f"efficiency={single['efficiency']:.3f} "
          f"platform={platform}", file=sys.stderr)


if __name__ == "__main__":
    main()
