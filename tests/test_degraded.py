"""Degraded-mode groups tests (docs/design/degraded_mode.md).

Tier-1 (marker ``degrade``, ``scripts/test.sh degrade``): submesh
derivation from a live-device set, sharding re-derivation fallbacks,
the weighted canonical-order fold over real socketpair rings (bitwise
against a single-process numpy oracle at worlds 2/3, int8 rung
included), weight-mode skew detection, the chaos ``device`` channel,
the Manager's degrade -> restore lifecycle (commit-boundary discipline,
refusals, flight dumps, the atomic capacity-bearing
``participant_slot`` snapshot), ElasticSampler capacity draws, the
Manager-level weighted pipeline over a pair hub, and the
DegradedModeDriver end-to-end re-``pjit`` lifecycle on the virtual CPU
mesh.

The 2-group chip-loss goodput soak (the >= 70%-of-healthy acceptance
gate, bench row ``degraded_goodput_ab``) needs the native control
plane and rides ``nightly``+``slow``.
"""

import functools
import threading
from concurrent.futures import Future
from unittest.mock import MagicMock

import numpy as np
import pytest

import conftest
import mockplane
from mockplane import FAKE_STORE_ADDR
from torchft_tpu import chaos
from torchft_tpu.backends.host import HostCommunicator, _Ring
from torchft_tpu.communicator import (CommunicatorError,
                                      DummyCommunicator, Int8Wire,
                                      _upcast_buffers, shard_bounds)
from torchft_tpu.degraded import DegradedModeDriver, live_devices
from torchft_tpu.manager import Manager

pytestmark = pytest.mark.degrade

requires_native = conftest.requires_native()


# --------------------------------------------------------------- helpers


quorum_result = functools.partial(
    mockplane.quorum_result, max_world_size=1, replica_world_size=1)
make_manager = functools.partial(
    mockplane.make_manager, quorum=quorum_result(), min_replica_size=1,
    degraded_mode=True)


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


# ------------------------------------------------------ submesh + specs


class TestSurvivingSubmesh:
    def _mesh(self, shape, n=None):
        import jax

        from torchft_tpu.parallel.mesh import make_mesh

        devs = jax.devices()[: n or int(np.prod(list(shape.values())))]
        return make_mesh(shape, devices=devs)

    def test_full_set_returns_mesh_unchanged(self):
        mesh = self._mesh({"dp": 4})
        from torchft_tpu.parallel.mesh import surviving_submesh

        sub, frac = surviving_submesh(mesh, list(mesh.devices.flat))
        assert sub is mesh and frac == 1.0

    def test_lost_chip_drops_its_data_slice_only(self):
        from torchft_tpu.parallel.mesh import surviving_submesh

        mesh = self._mesh({"dp": 4, "tp": 2})
        devs = list(mesh.devices.flat)
        sub, frac = surviving_submesh(mesh, [d for d in devs
                                             if d != devs[3]])
        # The lost chip sits in dp slice 1; tp survives whole.
        assert frac == 0.75
        assert dict(sub.shape) == {"dp": 3, "tp": 2}
        assert devs[3] not in set(sub.devices.flat)

    def test_two_lost_chips_same_slice_cost_one_slice(self):
        from torchft_tpu.parallel.mesh import surviving_submesh

        mesh = self._mesh({"dp": 4, "tp": 2})
        devs = np.asarray(mesh.devices)
        live = [d for d in devs.flat
                if d not in set(devs[1].flat)]  # both chips of slice 1
        sub, frac = surviving_submesh(mesh, live)
        assert frac == 0.75 and dict(sub.shape) == {"dp": 3, "tp": 2}

    def test_shrink_axis_selectable(self):
        from torchft_tpu.parallel.mesh import surviving_submesh

        mesh = self._mesh({"tp": 2, "dp": 4})
        devs = list(mesh.devices.flat)
        sub, frac = surviving_submesh(mesh, devs[:-1],
                                      shrink_axis="dp")
        assert frac == 0.75 and dict(sub.shape) == {"tp": 2, "dp": 3}

    def test_no_surviving_slice_raises(self):
        from torchft_tpu.parallel.mesh import surviving_submesh

        mesh = self._mesh({"dp": 2, "tp": 4})
        devs = np.asarray(mesh.devices)
        # One chip of EACH dp slice lost -> no full slice survives.
        live = [d for d in devs.flat
                if d not in (devs[0, 0], devs[1, 1])]
        with pytest.raises(ValueError, match="no full slice"):
            surviving_submesh(mesh, live)


class TestDegradedShardings:
    def test_rule_that_no_longer_divides_falls_back(self):
        import jax
        from jax.sharding import PartitionSpec

        from torchft_tpu.parallel.mesh import make_mesh
        from torchft_tpu.parallel.sharding import degraded_shardings

        sub = make_mesh({"dp": 3}, devices=jax.devices()[:3])
        tree = {"w": np.zeros((8, 4096), np.float32),
                "b": np.zeros(4096, np.float32)}
        # dim 0 (=8) divided dp=4 on the full mesh but not dp=3: the
        # rule falls back (here to inferred replication/FSDP) instead
        # of raising — chip loss must not be fatal.
        sh = degraded_shardings(
            tree, sub, rules=((r"w", PartitionSpec("dp", None)),),
            fsdp_axis="dp")
        assert sh["w"].spec != PartitionSpec("dp", None)
        # A leaf the shrunken axis still divides keeps real sharding.
        sh2 = degraded_shardings(
            {"v": np.zeros((6, 2048), np.float32)}, sub,
            rules=((r"v", PartitionSpec("dp", None)),), fsdp_axis="dp")
        assert sh2["v"].spec == PartitionSpec("dp", None)


# ------------------------------------------------- weighted fold (ring)


def _socketpair_rings(world):
    import socket as _socket

    pairs = [_socket.socketpair() for _ in range(world)]
    return [_Ring(pairs[r][0], pairs[(r - 1) % world][1],
                  _socket.socket())
            for r in range(world)]


class TestWeightedFoldRing:
    """The weighted canonical-order fold over real sockets — the
    numeric heart of degraded mode: 2 groups with skewed contributions
    must produce the bitwise-identical weighted average on every rank,
    matching a single-process numpy oracle."""

    def _run(self, world, fn):
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

    @pytest.mark.parametrize("world,weights", [
        (2, [48, 16]),   # the 3:1 skew of the acceptance criterion
        (2, [1, 3]),
        (3, [5, 2, 1]),
    ])
    def test_bitwise_matches_numpy_oracle_on_every_rank(self, world,
                                                        weights):
        rng = np.random.default_rng(world)
        xs = [rng.normal(size=10_007).astype(np.float32)
              for _ in range(world)]
        out, errors = self._run(
            world, lambda c, ring, r: c._do_allreduce_wire(
                ring, [xs[r].copy()], [np.dtype(np.float32)], "sum",
                "step", weights[r]))
        assert not errors, errors
        expected = weighted_oracle(xs, weights)
        for o in out:
            np.testing.assert_array_equal(o[0], expected)

    def test_zero_weight_junk_never_poisons(self):
        """A healer's weight-0 contribution is EXCLUDED from the fold,
        not multiplied by zero — NaN * 0 is NaN, so inclusion would let
        one wounded rank poison the average."""
        x0 = np.ones(1_000, np.float32)
        junk = np.full(1_000, np.nan, np.float32)
        out, errors = self._run(
            2, lambda c, ring, r: c._do_allreduce_wire(
                ring, [(x0 if r == 0 else junk).copy()],
                [np.dtype(np.float32)], "sum", "step",
                7 if r == 0 else 0))
        assert not errors, errors
        for o in out:
            np.testing.assert_array_equal(o[0], x0)

    @pytest.mark.parametrize("world", [2, 3])
    def test_int8_rung_weighted_fold(self, world):
        rng = np.random.default_rng(17 + world)
        xs = [rng.normal(size=9_001).astype(np.float32)
              for _ in range(world)]
        weights = [3, 1, 2][:world]
        ws = [Int8Wire.quantize(x) for x in xs]
        out, errors = self._run(
            world, lambda c, ring, r: c._do_allreduce_wire(
                ring, [Int8Wire.quantize(xs[r])],
                [np.dtype(np.float32)], "sum", "step", weights[r]))
        assert not errors, errors
        expected = weighted_oracle(
            [w.dequantize(np.float32) for w in ws], weights)
        for o in out:
            np.testing.assert_array_equal(o[0], expected)

    @pytest.mark.parametrize("world", [2, 3])
    def test_reduce_scatter_stripes_match_allreduce(self, world):
        rng = np.random.default_rng(23)
        xs = [rng.normal(size=9_001).astype(np.float32)
              for _ in range(world)]
        weights = [4, 1, 2][:world]
        full, errors = self._run(
            world, lambda c, ring, r: c._do_allreduce_wire(
                ring, [xs[r].copy()], [np.dtype(np.float32)], "sum",
                "step", weights[r]))
        assert not errors, errors
        shards, errors = self._run(
            world, lambda c, ring, r: c._do_reduce_scatter_wire(
                ring, [xs[r].copy()], [np.dtype(np.float32)], "sum",
                "step", weights[r]))
        assert not errors, errors
        bounds = shard_bounds(9_001, world)
        for r in range(world):
            np.testing.assert_array_equal(
                shards[r][0], full[0][0][bounds[r]:bounds[r + 1]])

    def test_weight_mode_skew_aborts_cleanly(self):
        """The wire-v4 skew guarantee of the acceptance criteria: a
        rank folding weighted while its peer folds uniform must get a
        clean CommunicatorError from the preamble — never a silently
        different fold on each side."""
        x = np.ones(4_096, np.float32)
        out, errors = self._run(
            2, lambda c, ring, r: c._do_allreduce_wire(
                ring, [x.copy()], [np.dtype(np.float32)], "sum",
                "step", 8 if r == 0 else -1))
        assert len(errors) == 2, (errors, out)
        for e in errors:
            assert isinstance(e, CommunicatorError)
            assert "wire weight skew" in str(e)

    def test_geometry_skew_still_aborts_with_weights(self):
        """Weights ride the same preamble as the format hash — a
        geometry mismatch under weighted mode stays a clean abort."""
        out, errors = self._run(
            2, lambda c, ring, r: c._do_allreduce_wire(
                ring,
                [np.ones(1_024 if r == 0 else 2_048, np.float32)],
                [np.dtype(np.float32)], "sum", "step", 4))
        assert len(errors) == 2, (errors, out)
        assert all("wire format skew" in str(e) for e in errors)

    def test_bf16_wire_weighted(self):
        """Narrow wire dtypes keep the one-quantization contract under
        weights: the fold upcasts the raw bf16 contributions, weights,
        and normalizes — bitwise across ranks and vs the oracle over
        the quantized values."""
        import jax.numpy as jnp

        wdt = np.dtype(jnp.bfloat16)
        rng = np.random.default_rng(4)
        xs = [rng.normal(size=2_048).astype(np.float32)
              for _ in range(2)]
        bf = [x.astype(wdt) for x in xs]
        weights = [3, 1]
        out, errors = self._run(
            2, lambda c, ring, r: c._do_allreduce_wire(
                ring, [bf[r].copy()], [np.dtype(np.float32)], "sum",
                "step", weights[r]))
        assert not errors, errors
        expected = weighted_oracle(
            [b.astype(np.float32) for b in bf], weights)
        for o in out:
            np.testing.assert_array_equal(o[0], expected)


# ----------------------------------------------------- device chaos


class TestDeviceChaosChannel:
    def test_spec_parsable(self):
        s = chaos.parse_spec(
            "seed=9;device:chip_loss_rate=0.5,chip_return_rate=0.25")
        cfg = s.config_for("device:g0")
        assert cfg.chip_loss_rate == 0.5
        assert cfg.chip_return_rate == 0.25

    def test_seeded_event_stream_is_deterministic(self):
        def drive(seed):
            s = chaos.ChaosSchedule(seed=seed, endpoints={
                "device": chaos.EndpointChaos(chip_loss_rate=0.4,
                                              chip_return_rate=0.3)})
            return [tuple(sorted(chaos.device_fault("device:gA", 8, s)))
                    for _ in range(40)]

        assert drive(11) == drive(11)
        assert drive(11) != drive(12)

    def test_never_loses_the_last_chip(self):
        s = chaos.ChaosSchedule(seed=1, endpoints={
            "device": chaos.EndpointChaos(chip_loss_rate=1.0)})
        for _ in range(30):
            lost = chaos.device_fault("device:g", 4, s)
        assert len(lost) == 3  # one survivor, always

    def test_chip_return_revives(self):
        s = chaos.ChaosSchedule(seed=2)
        s.lose_chip("device:g", 1)
        s.lose_chip("device:g", 3)
        assert s.lost_chips("device:g") == frozenset({1, 3})
        s.return_chip("device:g", 3)
        assert s.lost_chips("device:g") == frozenset({1})

    def test_intensity_zero_freezes_events(self):
        """PhasedChaos drives the channel through stable phases: at
        intensity 0 the decision stream keeps drawing (determinism) but
        no chip events fire."""
        from torchft_tpu.policy import PhasedChaos

        s = chaos.ChaosSchedule(seed=3, endpoints={
            "device": chaos.EndpointChaos(chip_loss_rate=1.0)})
        PhasedChaos(s, ((1e9, 0.0),)).tick()
        for _ in range(10):
            assert chaos.device_fault("device:g", 8, s) == frozenset()

    def test_live_devices_applies_lost_set(self):
        s = chaos.ChaosSchedule(seed=4)
        s.lose_chip("device:r0", 0)
        devs = ["d0", "d1", "d2"]
        assert live_devices("r0", devs, s) == ["d1", "d2"]
        assert live_devices("other", devs, s) == devs


# ------------------------------------------------- manager lifecycle


class TestManagerDegradedLifecycle:
    def test_requires_degraded_mode(self):
        m = make_manager(degraded_mode=False)
        try:
            with pytest.raises(RuntimeError, match="degraded_mode"):
                m.request_degrade(0.5)
            with pytest.raises(RuntimeError, match="degraded_mode"):
                m.request_restore()
        finally:
            m.shutdown()

    def test_fraction_validation(self):
        m = make_manager()
        try:
            with pytest.raises(ValueError, match="fraction"):
                m.request_degrade(0.0)
            with pytest.raises(ValueError, match="fraction"):
                m.request_degrade(1.5)
        finally:
            m.shutdown()

    def test_degrade_restore_counters_and_events(self):
        m = make_manager()
        try:
            assert m.request_degrade(0.5, samples=16)
            assert m.capacity_fraction() == 0.5
            mx = m.metrics()
            assert mx["degraded_capacity_fraction"] == 0.5
            assert mx["degrade_events_total"] == 1
            assert m.request_restore()
            mx = m.metrics()
            assert mx["degraded_capacity_fraction"] == 1.0
            assert mx["restore_events_total"] == 1
            events = [e.get("event") for e in m.history()]
            assert "degrade" in events and "restore" in events
        finally:
            m.shutdown()

    def test_flight_dump_on_every_capacity_transition(self, tmp_path,
                                                      monkeypatch):
        import json
        import os

        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        m = make_manager(replica_id="cap0")
        try:
            m.step()
            assert m.request_degrade(0.5)
            assert m.request_restore()
            files = sorted(os.listdir(tmp_path))
            assert any("degrade" in f for f in files), files
            assert any("restore" in f for f in files), files
            body = json.loads(
                (tmp_path / next(f for f in files
                                 if "degrade" in f)).read_text())
            assert body["torchft"]["extra"]["to"] == 0.5
            assert body["traceEvents"] is not None
        finally:
            m.shutdown()

    def test_participant_slot_carries_capacity_atomically(self):
        """The satellite regression: rank and capacity are one
        lock-consistent snapshot — a reader can never observe the new
        capacity with the old rank or vice versa."""
        m = make_manager()
        stop = threading.Event()

        def writer():
            flip = False
            while not stop.is_set():
                with m._metrics_lock:
                    if flip:
                        m._participating_rank = 1
                        m._share.capacity = 0.5
                    else:
                        m._participating_rank = 0
                        m._share.capacity = 1.0
                flip = not flip

        t = threading.Thread(target=writer)
        t.start()
        try:
            for _ in range(3_000):
                rank, _bc, frac = m.participant_slot()
                assert (rank, frac) in ((0, 1.0), (1, 0.5)), (rank, frac)
        finally:
            stop.set()
            t.join(timeout=5)
            m.shutdown()

    def test_snapshot_joins_inflight_quorum(self):
        """The PR-1 residual torn window is closed: a draw between
        step() and the async quorum resolving now reflects the POST-
        quorum membership, never the previous quorum's rank."""
        import time as _time

        client = MagicMock()

        def slow_quorum(**kwargs):
            _time.sleep(0.3)
            return quorum_result(max_rank=1, replica_rank=1,
                                 max_world_size=2,
                                 replica_world_size=2)

        client.quorum.side_effect = slow_quorum
        client.should_commit.return_value = True
        m = make_manager(client=client)
        try:
            m.step()
            rank, bc, frac = m.participant_slot()  # must wait the round
            assert rank == 1
        finally:
            m.shutdown()

    def test_capacity_advertised_on_quorum_store(self):
        store = MagicMock()
        m = make_manager(store=store)
        try:
            m.request_degrade(0.25)
            q = quorum_result(store_address=FAKE_STORE_ADDR, max_world_size=2,
                              replica_world_size=2, replica_rank=1)
            m._share.publish_capacity(
                lambda: m._store_client(q.store_address), q.replica_rank)
            store.set.assert_called_with(
                "torchft/capacity/1", f"{m.current_step()}:0.25".encode())
        finally:
            m.shutdown()

    def test_wire_weight_zero_while_not_participating(self):
        m = make_manager()
        try:
            m.request_degrade(0.5, samples=24)
            assert m._share.wire_weight() == 24
            with m._metrics_lock:
                m._healing = True
            assert m._share.wire_weight() == 0
        finally:
            m.shutdown()


# ----------------------------------------------- sampler capacity


class _FakeSlotManager:
    def __init__(self, rank=0, bc=0, frac=1.0):
        self.rank, self.bc, self.frac = rank, bc, frac
        self.reported = []

    def participant_slot(self):
        return self.rank, self.bc, self.frac

    def set_step_samples(self, n):
        self.reported.append(n)


class TestElasticSamplerCapacity:
    def test_degraded_draw_shrinks_and_reports(self):
        from torchft_tpu.data import ElasticSampler

        m = _FakeSlotManager(rank=1, bc=4, frac=0.5)
        s = ElasticSampler(64, m, batch_size=8, seed=0)
        idx = s.next_indices()
        assert len(idx) == 4
        assert m.reported == [4]
        # The shrunken draw is the PREFIX of the full slot's batch.
        np.testing.assert_array_equal(idx, s.indices_for_slot(5)[:4])

    def test_full_capacity_unchanged(self):
        from torchft_tpu.data import ElasticSampler

        m = _FakeSlotManager(rank=0, bc=2, frac=1.0)
        s = ElasticSampler(64, m, batch_size=8, seed=0)
        idx = s.next_indices()
        assert len(idx) == 8
        assert m.reported == [8]

    def test_two_tuple_snapshot_back_compat(self):
        """Duck-typed managers returning the pre-capacity 2-tuple keep
        working (capacity defaults to 1.0)."""
        from torchft_tpu.data import ElasticSampler

        class Legacy:
            def participant_slot(self):
                return 1, 10

        s = ElasticSampler(64, Legacy(), batch_size=4, seed=0)
        np.testing.assert_array_equal(
            s.next_indices(), s.indices_for_slot(11))

    def test_elastic_loader_keys_cache_by_capacity(self):
        from torchft_tpu.data import ElasticLoader, ElasticSampler

        class DS:
            def __init__(self):
                self.reads = 0

            def __len__(self):
                return 64

            def __getitem__(self, idx):
                self.reads += 1
                return {"x": np.asarray(idx)}

        m = _FakeSlotManager(rank=0, bc=0, frac=1.0)
        m.num_participants = lambda: 1
        ds = DS()
        loader = ElasticLoader(ds, ElasticSampler(64, m, batch_size=8),
                               prefetch=0)
        full = loader()
        assert len(full["x"]) == 8
        m.frac = 0.5  # capacity transition: same slot, shrunken draw
        half = loader()
        assert len(half["x"]) == 4
        assert m.reported[-1] == 4


# ------------------------------------- manager-level weighted pipeline


class _WeightedHub:
    """Two-rank wire-op rendezvous that folds contributions with the
    weighted canonical-order contract (the pair-hub pattern of
    test_policy, grown a weight column): exercises the Manager's
    weight capture (set_wire_weight per op) and its skipped 1/n in
    degraded mode without the native control plane."""

    def __init__(self, world=2):
        self.lock = threading.Lock()
        self.world = world
        self.counts = {}
        self.pending = {}

    def submit(self, rank, buffers, origs, weight):
        fut = Future()
        with self.lock:
            idx = self.counts.get(rank, 0)
            self.counts[rank] = idx + 1
            entry = self.pending.setdefault(idx, {})
            entry[rank] = (list(buffers),
                           [np.dtype(d) for d in origs],
                           int(weight), fut)
            ready = len(entry) == self.world
            if ready:
                del self.pending[idx]
        if ready:
            weights = {r: w for r, (_b, _o, w, _f) in entry.items()}
            assert all(w >= 0 for w in weights.values()), weights
            vals = {r: _upcast_buffers(b, o)
                    for r, (b, o, _w, _f) in entry.items()}
            total = sum(weights.values())
            outs = []
            for i in range(len(vals[0])):
                acc = np.zeros_like(vals[0][i])
                for r in sorted(vals):
                    if weights[r]:
                        acc += vals[r][i] * acc.dtype.type(weights[r])
                if total:
                    acc /= acc.dtype.type(total)
                outs.append(acc)
            for _r, (_b, origs_r, _w, f) in entry.items():
                f.set_result([np.array(s, dtype=d)
                              for s, d in zip(outs, origs_r)])
        return fut


class _WeightedComm(DummyCommunicator):
    def __init__(self, hub, rank):
        super().__init__(rank=rank, world_size=2)
        self._hub = hub

    def allreduce_wire(self, buffers, orig_dtypes, op="sum"):
        return self._hub.submit(self.rank(), buffers, orig_dtypes,
                                getattr(self, "wire_weight", -1))


class TestManagerWeightedPipeline:
    def test_skewed_groups_average_by_samples(self):
        """Two degraded-mode Managers, 3:1 sample skew: the resolved
        average must be the samples-weighted one on BOTH groups, and
        the Manager must not re-divide by the participant count."""
        hub = _WeightedHub()
        rng = np.random.default_rng(0)
        grads = [rng.normal(size=257).astype(np.float32)
                 for _ in range(2)]
        barrier = threading.Barrier(2)
        results = {}
        errors = []

        def run_group(rank):
            client = MagicMock()
            client.quorum.return_value = quorum_result(
                max_rank=rank, replica_rank=rank, max_world_size=2,
                replica_world_size=2)
            client.should_commit.return_value = True
            m = make_manager(client=client,
                             comm=_WeightedComm(hub, rank),
                             replica_id=f"wg{rank}",
                             min_replica_size=2)
            try:
                if rank == 1:
                    assert m.request_degrade(1 / 3, samples=16)
                else:
                    m.set_step_samples(48)
                barrier.wait(timeout=30)
                m.step()
                avg = m.allreduce({"g": grads[rank].copy()}).result()
                assert m.should_commit()
                results[rank] = np.asarray(avg["g"])
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                try:
                    barrier.abort()
                except Exception:  # noqa: BLE001
                    pass
            finally:
                m.shutdown()

        ts = [threading.Thread(target=run_group, args=(r,))
              for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not errors, errors
        assert len(results) == 2
        expected = weighted_oracle(grads, [48, 16])
        np.testing.assert_array_equal(results[0], expected)
        np.testing.assert_array_equal(results[1], expected)


# ------------------------------------------------ driver end-to-end


class TestDegradedModeDriver:
    def test_degrade_rejoin_restore_lifecycle(self):
        """The full walk on the virtual CPU mesh: lose a chip -> tick
        lands the degrade (capacity, submesh placement, shrunken
        batch) -> training keeps committing -> chip returns -> tick
        restores the full mesh."""
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding

        from torchft_tpu.data import ElasticSampler
        from torchft_tpu.parallel import FTTrainer
        from torchft_tpu.parallel.mesh import make_mesh
        from torchft_tpu.parallel.sharding import (batch_spec,
                                                   combined_shardings)

        devs = jax.devices()[:4]
        mesh = make_mesh({"dp": 4}, devices=devs)
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True

        def loss_fn(params, batch):
            return ((batch["x"] @ params["w"]) ** 2).mean()

        rng = np.random.default_rng(0)
        xarr = jnp.asarray(rng.normal(size=(64, 6)), jnp.float32)
        params = {"w": np.full((6, 2), 0.1, np.float32)}
        trainer = FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.01), params=params,
            manager_factory=lambda load, save: Manager(
                comm=DummyCommunicator(), load_state_dict=load,
                state_dict=save, min_replica_size=1, rank=0,
                world_size=1, replica_id="drv0", degraded_mode=True,
                _manager_client=client),
            param_shardings=combined_shardings(params, mesh),
            batch_sharding=NamedSharding(mesh, batch_spec(mesh)))
        sampler = ElasticSampler(64, trainer.manager, batch_size=8,
                                 seed=0)
        sched = chaos.ChaosSchedule(seed=0)
        driver = DegradedModeDriver(
            trainer, mesh,
            probe=lambda: live_devices("drv0", devs, sched))
        try:
            def batch():
                return {"x": xarr[sampler.next_indices()]}

            _, committed = trainer.train_step(batch)
            assert committed
            assert not driver.tick()  # all chips live: no transition

            sched.lose_chip("device:drv0", 2)
            assert driver.tick()
            assert trainer.manager.capacity_fraction() == 0.75
            assert driver.fraction() == 0.75
            assert len(trainer.params["w"].sharding.device_set) == 3
            assert devs[2] not in trainer.params["w"].sharding.device_set
            _, committed = trainer.train_step(batch)
            assert committed
            # The shrunken draw landed as the fold weight.
            assert trainer.manager._share.wire_weight() == 6  # round(8 * .75)

            sched.return_chip("device:drv0", 2)
            assert driver.tick()
            assert trainer.manager.capacity_fraction() == 1.0
            assert len(trainer.params["w"].sharding.device_set) == 4
            _, committed = trainer.train_step(batch)
            assert committed
            mx = trainer.manager.metrics()
            assert mx["degrade_events_total"] == 1
            assert mx["restore_events_total"] == 1
        finally:
            trainer.shutdown()

    def test_tick_retries_after_refusal(self):
        """A transition refused at a bad boundary (deferred in flight)
        lands at the next tick — the save_durable-style retry."""
        import jax

        from torchft_tpu.parallel.mesh import make_mesh

        m = make_manager()
        trainer = MagicMock()
        trainer.manager = m
        mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
        devs = list(mesh.devices.flat)
        driver = DegradedModeDriver(trainer, mesh,
                                    probe=lambda: devs[:3])
        try:
            f = Future()
            f.set_result(None)
            m.stage_deferred(f)
            assert not driver.tick()  # refused: deferred in flight
            assert driver.fraction() == 1.0
            assert not trainer.set_placement.called
            m.drain_deferred()
            assert driver.tick()
            assert driver.fraction() == 0.75
            assert trainer.set_placement.called
        finally:
            m.shutdown()


# ----------------------------------------------------- nightly soak


@pytest.mark.slow
@pytest.mark.nightly
@requires_native
class TestDegradedGoodputSoak:
    def test_goodput_degrades_proportionally_not_in_group_quanta(self):
        """The acceptance gate: a 2-group host-backend run where one
        group loses half its devices mid-run must settle at >= 70% of
        the healthy committed-samples/sec baseline (whole-group
        eviction would cost ~50%)."""
        import bench

        row = bench.bench_degraded_goodput(steps=12)
        assert row["healthy_samples_per_s"] > 0
        assert row["degraded_ratio"] >= 0.70, row
        assert row["eviction_ratio"] == 0.5
