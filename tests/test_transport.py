"""Device-side wire quantization + hierarchical transport tests
(docs/design/hier_transport.md, scripts/test.sh transport).

Tier-1 (marker ``transport``), no native toolchain needed:

* the vectorized power-of-two-scale :class:`Int8Wire` quantizer's
  properties (pow2 scales, exact constant/zero reconstruction,
  non-finite masking, tail handling);
* BITWISE parity of the fused device-side quantize-pack
  (``_device_quantize_pack``) with the host-side
  ``Int8Wire.quantize``/bf16-cast path — payloads AND error-feedback
  residual trajectories over multi-step runs;
* Manager-level device-vs-host quantize A/B over a pair hub: identical
  averaged gradients, ~1/4 D2H bytes, residual gauge, and the
  schedule-fingerprint residual-migration guard (grad-signature change
  drops device-resident residuals);
* the hierarchical two-level ring over real socketpairs at 2 hosts x
  {2,3} ranks (contiguous AND interleaved rank layouts):
  exact/bf16/int8/weighted-fold allreduce + reduce-scatter all bitwise
  identical to the flat ring, leader-death latching a clean
  CommunicatorError, format/weight-mode skew aborting on the first
  hop, and cross-host (leader-leg) bytes <= 1/per_host of the flat
  ring's;
* topology accessors + wrapper forwarding.

The full-configure rendezvous E2E (host-id advertisement, leader
election, re-election across epochs) needs the native store and is
gated ``requires_native``.
"""

import socket
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import conftest
from mockplane import make_manager, quorum_result
from torchft_tpu import policy as policy_mod
from torchft_tpu.backends.host import (HostCommunicator, _HierTopo,
                                       _Ring)
from torchft_tpu.communicator import (CommunicatorError,
                                      DummyCommunicator,
                                      ErrorSwallowingCommunicator,
                                      Int8Wire)
from torchft_tpu.communicator import shard_bounds
from torchft_tpu.exchange import _device_quantize_pack

pytestmark = pytest.mark.transport

requires_native = conftest.requires_native()

F32 = np.dtype(np.float32)


# ----------------------------------------------------- quantizer units


class TestInt8QuantizePow2:
    def test_scales_are_powers_of_two(self):
        rng = np.random.default_rng(0)
        w = Int8Wire.quantize(
            (rng.normal(size=200_003) * 17.0).astype(np.float32))
        live = w.scales[w.scales > 0]
        assert live.size > 0
        mant = live.view(np.uint32) & np.uint32(0x7FFFFF)
        assert not mant.any(), "scale with non-zero mantissa bits"

    def test_scale_covers_range(self):
        """pow2 rounding is UP: |q| never exceeds 127 pre-clip for
        finite segments, so the clip is a no-op on clean data."""
        rng = np.random.default_rng(1)
        v = (rng.normal(size=70_000) * 3.0).astype(np.float32)
        w = Int8Wire.quantize(v)
        assert np.abs(w.q).max() <= 127

    def test_constant_segment_exact(self):
        v = np.full(5_000, 7.5, np.float32)
        w = Int8Wire.quantize(v)
        np.testing.assert_array_equal(w.dequantize(np.float32), v)
        assert not w.q.any() and not w.scales.any()

    def test_zeros_exact(self):
        w = Int8Wire.quantize(np.zeros(3_000, np.float32))
        assert not w.dequantize(np.float32).any()

    def test_nonfinite_segment_encodes_zero(self):
        v = np.ones(1_000, np.float32)
        v[100] = np.nan
        v[200] = np.inf
        w = Int8Wire.quantize(v)
        out = w.dequantize(np.float32)
        assert np.isfinite(out).all()
        assert not out.any()  # whole (single) segment zeroed

    def test_tail_segment(self):
        """A non-divisible tail quantizes with ITS OWN min/max (the
        pad repeats the last element, never widening the range)."""
        seg = 4_096
        v = np.concatenate([
            np.random.default_rng(2).normal(size=seg),
            np.array([1000.0, 1001.0, 1002.0]),
        ]).astype(np.float32)
        w = Int8Wire.quantize(v, seg_elems=seg)
        assert len(w.scales) == 2
        out = w.dequantize(np.float32)
        # Tail range is [1000, 1002]: reconstruction stays close.
        assert np.abs(out[-3:] - v[-3:]).max() < 1.0

    def test_roundtrip_bytes(self):
        rng = np.random.default_rng(3)
        w = Int8Wire.quantize(rng.normal(size=99_001).astype(np.float32))
        w2 = Int8Wire.from_bytes(w.to_bytes(), w.size, w.seg_elems)
        np.testing.assert_array_equal(w.q, w2.q)
        np.testing.assert_array_equal(w.scales, w2.scales)
        np.testing.assert_array_equal(w.zeros, w2.zeros)

    def test_empty_buffer(self):
        w = Int8Wire.quantize(np.zeros(0, np.float32))
        assert w.size == 0
        assert w.dequantize(np.float32).size == 0


# ------------------------------------------ device-pack bitwise parity


def _host_quant_step(v, res):
    """The Manager's host-side EF quantize spelling
    (_int8_quantize_bucket), as the parity oracle."""
    v = v.astype(np.float32, copy=False)
    if res is not None:
        v = v + res
    w = Int8Wire.quantize(v)
    r = v - w.dequantize(np.float32)
    r[~np.isfinite(r)] = 0.0
    return w, r


class TestDeviceQuantizePack:
    def _leaves(self, shapes, seed, scale=1.0):
        import jax.numpy as jnp

        rng = np.random.default_rng(seed)
        return [jnp.asarray((rng.normal(size=s) * scale)
                            .astype(np.float32)) for s in shapes]

    @pytest.mark.parametrize("shapes", [
        [(37, 11), (5_000,), (123,)],      # multi-leaf, awkward tail
        [(70_001,)],                       # > one segment + tail
        [(17,)],                           # single tiny segment
    ])
    def test_payload_bitwise_matches_host_quantize(self, shapes):
        import jax.numpy as jnp

        leaves = self._leaves(shapes, seed=5, scale=13.0)
        total = sum(int(np.prod(s)) for s in shapes)
        payload, _ = _device_quantize_pack(
            leaves, jnp.zeros(total, jnp.float32))
        host_v = np.concatenate(
            [np.ravel(np.asarray(x)) for x in leaves])
        w, _ = _host_quant_step(host_v, None)
        assert bytes(np.asarray(payload).tobytes()) == w.to_bytes()
        assert np.asarray(payload).nbytes == Int8Wire.payload_nbytes(
            total)

    def test_multi_step_ef_trajectory_bitwise(self):
        """The acceptance parity: payloads AND residuals match the
        host path bit for bit across steps, so a device-quantizing
        rank and a host-quantizing rank are interchangeable."""
        import jax.numpy as jnp

        shapes = [(9_000,), (4_099,)]
        total = 13_099
        res_d = jnp.zeros(total, jnp.float32)
        res_h = np.zeros(total, np.float32)
        for step in range(6):
            leaves = self._leaves(shapes, seed=10 + step,
                                  scale=1.0 + step)
            payload, res_d = _device_quantize_pack(leaves, res_d)
            host_v = np.concatenate(
                [np.ravel(np.asarray(x)) for x in leaves])
            w, res_h = _host_quant_step(host_v, res_h)
            assert bytes(np.asarray(payload).tobytes()) == w.to_bytes()
            np.testing.assert_array_equal(np.asarray(res_d), res_h)
            assert res_h.any()  # the trajectory is non-trivial

    def test_nonfinite_contribution_keeps_residual_finite(self):
        import jax.numpy as jnp

        v = np.ones(5_000, np.float32)
        v[7] = np.nan
        payload, res = _device_quantize_pack(
            [jnp.asarray(v)], jnp.zeros(5_000, jnp.float32))
        assert np.isfinite(np.asarray(res)).all()
        w, res_h = _host_quant_step(v, None)
        assert bytes(np.asarray(payload).tobytes()) == w.to_bytes()
        np.testing.assert_array_equal(np.asarray(res), res_h)

    def test_bf16_device_cast_matches_host_cast(self):
        """The bf16 rung's fused device cast (in _pack_leaves since
        PR 2) and a host-side astype agree — the devquant A/B's two
        legs are bitwise interchangeable for bf16 too."""
        import jax.numpy as jnp

        from torchft_tpu.exchange import _pack_leaves

        wdt = np.dtype(jnp.bfloat16)
        rng = np.random.default_rng(11)
        host = rng.normal(size=10_240).astype(np.float32)
        dev = _pack_leaves([jnp.asarray(host)], str(wdt))
        got = np.asarray(dev)
        if got.dtype != wdt:  # canonical uint carrier crossed D2H
            got = got.view(wdt)
        np.testing.assert_array_equal(got, host.astype(wdt))


# --------------------------------------- manager-level device-quant A/B


class _FoldHub:
    """Two-rank wire-op rendezvous folding RAW contributions in
    canonical rank order — the host ring's unweighted int8/wire fold
    contract, minus the sockets (the pair-hub pattern of
    test_policy/test_degraded). Counts wire payload bytes so the A/B
    can also assert the D2H/ring byte shrink."""

    def __init__(self, world=2):
        self.lock = threading.Lock()
        self.world = world
        self.counts = {}
        self.pending = {}

    @staticmethod
    def _fold(buffers_by_rank, origs):
        outs = []
        for i in range(len(origs)):
            orig = np.dtype(origs[i])
            acc = None
            for r in sorted(buffers_by_rank):
                b = buffers_by_rank[r][i]
                v = (b.dequantize(orig) if isinstance(b, Int8Wire)
                     else np.ravel(np.asarray(b)).astype(orig,
                                                         copy=False))
                acc = v.copy() if acc is None else acc + v
            outs.append(acc)
        return outs

    def submit(self, rank, buffers, origs):
        fut = Future()
        with self.lock:
            idx = self.counts.get(rank, 0)
            self.counts[rank] = idx + 1
            entry = self.pending.setdefault(idx, {})
            entry[rank] = (list(buffers),
                           [np.dtype(d) for d in origs], fut)
            ready = len(entry) == self.world
            if ready:
                del self.pending[idx]
        if ready:
            outs = self._fold({r: b for r, (b, _o, _f) in entry.items()},
                              next(iter(entry.values()))[1])
            for _r, (_b, origs_r, f) in entry.items():
                f.set_result([np.array(s, dtype=d)
                              for s, d in zip(outs, origs_r)])
        return fut


class _FoldComm(DummyCommunicator):
    def __init__(self, hub, rank):
        super().__init__(rank=rank, world_size=hub.world)
        self._hub = hub

    def allreduce_wire(self, buffers, orig_dtypes, op="sum"):
        return self._hub.submit(self.rank(), buffers, orig_dtypes)


def _int8_policy():
    return next(p for p in policy_mod.LADDER if p.name == "sync-int8")


def _devq_manager(comm, rank, device_quantize, world=2):
    return make_manager(
        comm=comm, min_replica_size=world, replica_id=f"devq{rank}",
        quorum=quorum_result(max_rank=rank, replica_rank=rank,
                             max_world_size=world,
                             replica_world_size=world),
        policy=_int8_policy(), device_quantize=device_quantize)


def _run_pair(device_quantize, steps=4, shapes=((61, 17), (3_001,))):
    """Two int8-policy managers over a fold hub, `steps` allreduces of
    device-resident grads; returns (per-step averaged results of rank
    0, final metrics of rank 0, manager internals snapshot)."""
    import jax.numpy as jnp

    hub = _FoldHub()
    barrier = threading.Barrier(2)
    results = {0: [], 1: []}
    metrics = {}
    internals = {}
    errors = []

    def run_group(rank):
        m = _devq_manager(_FoldComm(hub, rank), rank, device_quantize)
        try:
            for step in range(steps):
                rng = np.random.default_rng(100 * rank + step)
                grads = {
                    f"l{i}": jnp.asarray(
                        (rng.normal(size=s) * (1 + step))
                        .astype(np.float32))
                    for i, s in enumerate(shapes)}
                barrier.wait(timeout=30)
                m.step()
                avg = m.allreduce(grads).result()
                assert m.should_commit()
                results[rank].append(
                    {k: np.asarray(v) for k, v in avg.items()})
            metrics[rank] = m.metrics()
            internals[rank] = dict(
                dev_residuals=len(m._exchange._dev_residuals),
                ef_residuals=len(m._exchange._ef_residuals))
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
        t.join(timeout=120)
    assert not errors, errors
    return results, metrics, internals


class TestManagerDeviceQuant:
    def test_device_and_host_legs_bitwise_identical(self):
        """The acceptance bitwise gate at the Manager level: the fused
        device-quantize pipeline and the host-quantize pipeline
        produce IDENTICAL averaged gradients across a multi-step run
        (residual trajectories included), on both ranks."""
        dev, mdev, idev = _run_pair(device_quantize=True)
        host, mhost, ihost = _run_pair(device_quantize=False)
        for rank in (0, 1):
            assert len(dev[rank]) == len(host[rank]) == 4
            for sd, sh in zip(dev[rank], host[rank]):
                for k in sd:
                    np.testing.assert_array_equal(sd[k], sh[k])
        # The two legs bank their residuals on opposite sides.
        assert idev[0]["dev_residuals"] > 0
        assert idev[0]["ef_residuals"] == 0
        assert ihost[0]["dev_residuals"] == 0
        assert ihost[0]["ef_residuals"] > 0

    def test_device_leg_fetches_wire_bytes(self):
        """The fetch-wall cut itself: device-quantized D2H traffic is
        the int8 payload (~1/4 of f32 + segment headers), host-side
        quantize fetches full f32."""
        _, mdev, _ = _run_pair(device_quantize=True, steps=2)
        _, mhost, _ = _run_pair(device_quantize=False, steps=2)
        d = mdev[0]["allreduce_d2h_wire_bytes_total"]
        h = mhost[0]["allreduce_d2h_wire_bytes_total"]
        assert 0 < d < 0.3 * h, (d, h)
        # Residual gauge live on both legs.
        assert mdev[0]["wire_quant_residual_bytes"] > 0
        assert mhost[0]["wire_quant_residual_bytes"] > 0

    def test_signature_change_drops_device_residuals(self):
        """Regression (satellite): a grad-signature change re-chunks
        the pytree; device-resident residuals keyed to the OLD
        schedule fingerprint must be dropped exactly like
        _ef_residuals — never folded into the new geometry."""
        import jax.numpy as jnp

        hub = _FoldHub()
        barrier = threading.Barrier(2)
        seen = {}
        errors = []

        def run_group(rank):
            m = _devq_manager(_FoldComm(hub, rank), rank, True)
            try:
                for step, size in enumerate((5_000, 5_000, 7_777)):
                    g = {"w": jnp.asarray(
                        np.random.default_rng(step).normal(size=size)
                        .astype(np.float32))}
                    barrier.wait(timeout=30)
                    m.step()
                    m.allreduce(g).result()
                    assert m.should_commit()
                    if rank == 0:
                        fps = {k[0] for k in m._exchange._dev_residuals}
                        seen[step] = (len(m._exchange._dev_residuals),
                                      len(fps))
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
            t.join(timeout=120)
        assert not errors, errors
        # One chunk per signature; after the switch only the NEW
        # fingerprint's residual survives.
        assert seen[0] == (1, 1)
        assert seen[1] == (1, 1)
        assert seen[2] == (1, 1)

    def test_policy_switch_clears_device_residuals(self):
        m = _devq_manager(DummyCommunicator(), 0, True)
        try:
            m._exchange._dev_residuals[("fp", 0, 0)] = np.zeros(4, np.float32)
            m._switch.install(
                next(p for p in policy_mod.LADDER
                     if p.name == "sync-bf16"), "test", "policy_switch")
            assert not m._exchange._dev_residuals
        finally:
            m.shutdown()


# --------------------------------------------- hierarchical socketpairs


def _flat_rings(world):
    pairs = [socket.socketpair() for _ in range(world)]
    for a, b in pairs:
        a.settimeout(20)
        b.settimeout(20)
    return [_Ring(pairs[r][0], pairs[(r - 1) % world][1],
                  socket.socket())
            for r in range(world)]


def _hier_rig(hosts):
    """Per-rank _HierTopo over socketpairs: a leader ring among the
    hosts' min-rank leaders plus star socketpairs leader<->member."""
    leaders = [ms[0] for ms in hosts]
    nh = len(hosts)
    leader_rings = {}
    if nh >= 2:
        pairs = [socket.socketpair() for _ in range(nh)]
        for a, b in pairs:
            a.settimeout(20)
            b.settimeout(20)
        for i, lead in enumerate(leaders):
            leader_rings[lead] = _Ring(
                pairs[i][0], pairs[(i - 1) % nh][1], socket.socket())
    topos = {}
    for ms in hosts:
        lead = ms[0]
        member_socks = {}
        ups = {}
        for mr in ms[1:]:
            a, b = socket.socketpair()
            a.settimeout(20)
            b.settimeout(20)
            member_socks[mr] = a
            ups[mr] = b
        topos[lead] = _HierTopo(hosts, lead,
                                leader_ring=leader_rings.get(lead),
                                member_socks=member_socks)
        for mr in ms[1:]:
            topos[mr] = _HierTopo(hosts, mr, up_sock=ups[mr])
    return topos


def _run_ranks(world, fn, comms_factory):
    comms = comms_factory(world)
    out = [None] * world
    errors = []

    def w(r):
        try:
            out[r] = fn(comms[r], r)
        except Exception as e:  # noqa: BLE001
            errors.append((r, e))

    ts = [threading.Thread(target=w, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    alive = [t for t in ts if t.is_alive()]
    for c in comms:
        if c._hier is not None:
            c._hier.close()
        if c._flat_test_ring is not None:
            c._flat_test_ring.close()
        c.shutdown()
    assert not alive, "transport deadlocked"
    return out, errors


def _hier_comms(hosts):
    def build(world):
        topos = _hier_rig(hosts)
        comms = []
        for r in range(world):
            c = HostCommunicator(timeout_sec=15)
            c._rank, c._world = r, world
            c._hier = topos[r]
            c._flat_test_ring = None
            comms.append(c)
        return comms
    return build


def _flat_comms(world_hint=None):
    def build(world):
        rings = _flat_rings(world)
        comms = []
        for r in range(world):
            c = HostCommunicator(timeout_sec=15)
            c._rank, c._world = r, world
            c._flat_test_ring = rings[r]
            comms.append(c)
        return comms
    return build


HOST_LAYOUTS = [
    [[0, 1], [2, 3]],          # 2 hosts x 2, contiguous ranks
    [[0, 2], [1, 3]],          # 2 hosts x 2, interleaved ranks
    [[0, 1, 2], [3, 4, 5]],    # 2 hosts x 3
]


def _payloads(world, seed, size=10_007, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=size) * (r + 1)).astype(dtype)
            for r in range(world)]


class TestHierBitwiseVsFlat:
    """The tentpole invariant: the hierarchical transport changes how
    bytes travel, never what is folded in which order — every mode's
    result is BITWISE the flat ring's."""

    def _ab(self, hosts, make_bufs, orig=F32, weight=lambda r: -1,
            kind="ar"):
        world = sum(len(ms) for ms in hosts)

        def run_hier(c, r):
            fn = (c._do_allreduce_wire if kind == "ar"
                  else c._do_reduce_scatter_wire)
            return fn(None, [make_bufs(r)], [orig], "sum", "step",
                      weight(r))

        def run_flat(c, r):
            fn = (c._do_allreduce_wire if kind == "ar"
                  else c._do_reduce_scatter_wire)
            return fn(c._flat_test_ring, [make_bufs(r)], [orig],
                      "sum", "step", weight(r))

        hier, he = _run_ranks(world, run_hier, _hier_comms(hosts))
        assert not he, he
        flat, fe = _run_ranks(world, run_flat, _flat_comms())
        assert not fe, fe
        for r in range(world):
            np.testing.assert_array_equal(hier[r][0], flat[r][0])
        # Cross-rank identity (allreduce) holds on the hier leg too.
        if kind == "ar":
            for r in range(1, world):
                np.testing.assert_array_equal(hier[0][0], hier[r][0])
        return hier

    @pytest.mark.parametrize("hosts", HOST_LAYOUTS)
    def test_exact_f32(self, hosts):
        world = sum(len(ms) for ms in hosts)
        xs = _payloads(world, seed=7)
        self._ab(hosts, lambda r: xs[r].copy())

    @pytest.mark.parametrize("hosts", HOST_LAYOUTS)
    def test_exact_f32_reduce_scatter(self, hosts):
        world = sum(len(ms) for ms in hosts)
        xs = _payloads(world, seed=8)
        full = self._ab(hosts, lambda r: xs[r].copy())
        shards = self._ab(hosts, lambda r: xs[r].copy(), kind="rs")
        bounds = shard_bounds(xs[0].size, world)
        for r in range(world):
            np.testing.assert_array_equal(
                shards[r][0], full[0][0][bounds[r]:bounds[r + 1]])

    @pytest.mark.parametrize("hosts", HOST_LAYOUTS)
    def test_bf16_wire(self, hosts):
        """2x2 (world 4) sits INSIDE the raw-forwarding crossover for
        bf16; 2x3 (world 6) is past it (flat upcasts into the exact
        ring) — both branches must match flat bitwise."""
        import jax.numpy as jnp

        wdt = np.dtype(jnp.bfloat16)
        world = sum(len(ms) for ms in hosts)
        xs = [x.astype(wdt) for x in _payloads(world, seed=9,
                                               size=4_096)]
        self._ab(hosts, lambda r: xs[r].copy())
        self._ab(hosts, lambda r: xs[r].copy(), kind="rs")

    @pytest.mark.parametrize("hosts", HOST_LAYOUTS)
    def test_int8_rung(self, hosts):
        world = sum(len(ms) for ms in hosts)
        xs = _payloads(world, seed=10, size=9_001)
        self._ab(hosts, lambda r: Int8Wire.quantize(xs[r]))
        self._ab(hosts, lambda r: Int8Wire.quantize(xs[r]), kind="rs")

    @pytest.mark.parametrize("hosts", HOST_LAYOUTS)
    def test_weighted_fold_degraded(self, hosts):
        world = sum(len(ms) for ms in hosts)
        xs = _payloads(world, seed=11, size=9_001)
        weights = [5, 2, 1, 4, 3, 7][:world]
        self._ab(hosts, lambda r: xs[r].copy(),
                 weight=lambda r: weights[r])
        self._ab(hosts, lambda r: xs[r].copy(),
                 weight=lambda r: weights[r], kind="rs")

    def test_weighted_int8(self):
        hosts = [[0, 1], [2, 3]]
        xs = _payloads(4, seed=12, size=9_001)
        weights = [48, 16, 8, 0]  # a zero-weight (healer) rank too
        self._ab(hosts, lambda r: Int8Wire.quantize(xs[r]),
                 weight=lambda r: weights[r])

    def test_multi_buffer_op(self):
        """One op carrying several chunks (the bucketed pipeline's
        shape) — per-buffer folds stay independent and bitwise."""
        hosts = [[0, 1], [2, 3]]
        xs = _payloads(4, seed=13, size=5_000)
        ys = _payloads(4, seed=14, size=333)

        def run(c, r):
            return c._do_allreduce_wire(
                None, [xs[r].copy(), Int8Wire.quantize(ys[r])],
                [F32, F32], "sum", "step", -1)

        hier, he = _run_ranks(4, run, _hier_comms(hosts))
        assert not he, he

        def run_flat(c, r):
            return c._do_allreduce_wire(
                c._flat_test_ring,
                [xs[r].copy(), Int8Wire.quantize(ys[r])],
                [F32, F32], "sum", "step", -1)

        flat, fe = _run_ranks(4, run_flat, _flat_comms())
        assert not fe, fe
        for r in range(4):
            np.testing.assert_array_equal(hier[r][0], flat[r][0])
            np.testing.assert_array_equal(hier[r][1], flat[r][1])


class TestHierFailureModes:
    def test_leader_death_latches_communicator_error(self):
        """Leader dies mid-op: every survivor gets a clean
        CommunicatorError (the latch that triggers the next quorum's
        recovery rendezvous + re-election) — never a hang, never a
        garbage fold."""
        hosts = [[0, 1], [2, 3]]
        topos = _hier_rig(hosts)
        comms = []
        for r in range(4):
            c = HostCommunicator(timeout_sec=5)
            c._rank, c._world = r, 4
            c._hier = topos[r]
            comms.append(c)
        xs = _payloads(4, seed=15, size=200_000)
        errors = {}
        done = threading.Event()

        def w(r):
            try:
                comms[r]._do_allreduce_wire(
                    None, [xs[r].copy()], [F32], "sum", "step", -1)
            except Exception as e:  # noqa: BLE001
                errors[r] = e
            if len(errors) >= 3:
                done.set()

        # Ranks 1, 2, 3 participate; leader 0 "dies" instead of
        # issuing its op.
        ts = [threading.Thread(target=w, args=(r,)) for r in (1, 2, 3)]
        for t in ts:
            t.start()
        time.sleep(0.3)
        topos[0].close()  # the death: star + leader-ring sockets drop
        done.wait(timeout=30)
        for t in ts:
            t.join(timeout=30)
        try:
            assert set(errors) == {1, 2, 3}, errors
            for e in errors.values():
                assert isinstance(e, CommunicatorError), e
        finally:
            for r, c in enumerate(comms):
                if r != 0:
                    topos[r].close()
                c.shutdown()

    def test_format_skew_aborts_on_first_hop(self):
        """A member announcing a different wire-op geometry must abort
        at the leader BEFORE any payload byte is folded — and the
        member must get the relayed abort, not a hang."""
        hosts = [[0, 1]]

        def run(c, r):
            size = 1_024 if r == 0 else 2_048
            return c._do_allreduce_wire(
                None, [np.ones(size, np.float32)], [F32], "sum",
                "step", -1)

        out, errors = _run_ranks(2, run, _hier_comms(hosts))
        assert len(errors) == 2, (errors, out)
        for _r, e in errors:
            assert isinstance(e, CommunicatorError)
            assert ("wire format skew" in str(e)
                    or "abort relayed" in str(e)), e

    def test_weight_mode_skew_aborts(self):
        hosts = [[0, 1]]

        def run(c, r):
            return c._do_allreduce_wire(
                None, [np.ones(4_096, np.float32)], [F32], "sum",
                "step", 8 if r == 0 else -1)

        out, errors = _run_ranks(2, run, _hier_comms(hosts))
        assert len(errors) == 2, (errors, out)
        assert any("wire weight skew" in str(e) for _r, e in errors)

    def test_leader_skew_aborts_across_hosts(self):
        """Geometry skew BETWEEN hosts (leader vs leader) aborts on
        the leader ring's first hop."""
        hosts = [[0, 1], [2, 3]]

        def run(c, r):
            size = 1_024 if r < 2 else 2_048
            return c._do_allreduce_wire(
                None, [np.ones(size, np.float32)], [F32], "sum",
                "step", -1)

        out, errors = _run_ranks(4, run, _hier_comms(hosts))
        assert len(errors) == 4, (errors, out)
        assert any("wire format skew" in str(e) for _r, e in errors)


class TestHierByteScaling:
    def test_leader_leg_bytes_scale_with_hosts(self):
        """The acceptance byte gate at 2x2: cross-host (leader-leg)
        bytes <= 1/per_host of the flat ring's total sends for the
        same op (measured: hosts*(hosts-1)*per_host vs n*(n-1)
        raw-buffer sends for the int8 rung)."""
        hosts = [[0, 1], [2, 3]]
        xs = _payloads(4, seed=16, size=500_000)
        per_host = 2

        def run_hier(c, r):
            c._do_allreduce_wire(None, [Int8Wire.quantize(xs[r])],
                                 [F32], "sum", "step", -1)
            return (c.hier_leader_bytes_total(),
                    c.hier_intra_bytes_total())

        hier, he = _run_ranks(4, run_hier, _hier_comms(hosts))
        assert not he, he

        def run_flat(c, r):
            c._do_allreduce_wire(c._flat_test_ring,
                                 [Int8Wire.quantize(xs[r])],
                                 [F32], "sum", "step", -1)
            return (c.ring_bytes_total(), 0.0)

        flat, fe = _run_ranks(4, run_flat, _flat_comms())
        assert not fe, fe
        leader_total = sum(h[0] for h in hier)
        intra_total = sum(h[1] for h in hier)
        flat_total = sum(f[0] for f in flat)
        assert flat_total > 0
        assert leader_total > 0
        assert intra_total > 0  # the star actually carried traffic
        assert leader_total <= flat_total / per_host, (
            leader_total, flat_total)


class TestTopologyAccessors:
    def test_flat_by_default(self):
        c = HostCommunicator(timeout_sec=1)
        try:
            assert c.ring_topology() == "flat"
            assert c.hier_leader() == 0.0
            assert c.hier_intra_bytes_total() == 0.0
        finally:
            c.shutdown()

    def test_hier_topology_string(self):
        c = HostCommunicator(timeout_sec=1)
        try:
            c._hier = _HierTopo([[0, 1], [2, 3, 4]], 0)
            assert c.ring_topology() == "hier:2x3"
            assert c.hier_leader() == 1.0
            c._hier = _HierTopo([[0, 1], [2, 3, 4]], 1)
            assert c.hier_leader() == 0.0
        finally:
            c._hier = None
            c.shutdown()

    def test_wrappers_forward(self):
        inner = HostCommunicator(timeout_sec=1)
        inner._hier = _HierTopo([[0, 1], [2, 3]], 0)
        inner._count(hier_intra_bytes=42.0)
        wrapped = ErrorSwallowingCommunicator(inner)
        try:
            assert wrapped.ring_topology() == "hier:2x2"
            assert wrapped.hier_leader() == 1.0
            assert wrapped.hier_intra_bytes_total() == 42.0
        finally:
            inner._hier = None
            inner.shutdown()

    def test_abc_defaults(self):
        d = DummyCommunicator()
        assert d.ring_topology() == "flat"
        assert d.hier_leader() == 0.0
        assert d.hier_intra_bytes_total() == 0.0

    def test_tracing_stages_include_hier_legs(self):
        from torchft_tpu import tracing

        assert "hier_intra" in tracing.STAGES
        assert "hier_leader" in tracing.STAGES

    def test_manager_metrics_carry_hier_keys(self):
        m = _devq_manager(DummyCommunicator(), 0, True)
        try:
            mx = m.metrics()
            assert mx["hier_intra_bytes_total"] == 0.0
            assert mx["hier_leader"] == 0.0
            assert mx["allreduce_d2h_wire_bytes_total"] == 0.0
            assert m.metrics_info()["ring_topology"] == "flat"
        finally:
            m.shutdown()

    def test_hier_flag_rides_config_fingerprint(self):
        c = HostCommunicator(timeout_sec=1, hier=False)
        try:
            assert c._hier_flag() is False
            c2 = HostCommunicator(timeout_sec=1, hier=True)
            assert c2._hier_flag() is True
            c2.shutdown()
        finally:
            c.shutdown()


# ------------------------------- Manager E2E over the real transport


class TestManagerHierEndToEnd:
    """The capstone drive: FOUR Managers running the real pipelined
    host allreduce (pack -> device quantize -> D2H -> wire transport ->
    fold -> unpack/put) over REAL sockets, int8+EF policy — flat ring
    vs the 2x2 hierarchical topology, device-quantize vs host-quantize
    — every leg bitwise identical and every rank lockstep."""

    WORLD = 4

    def _drive(self, topo_hosts, device_quantize, steps=3):
        import jax.numpy as jnp

        world = self.WORLD

        class Wired(HostCommunicator):
            def configure(self, store_addr, rank, world_size):
                pass  # pre-wired

        comms = []
        rings = _flat_rings(world) if topo_hosts is None else None
        topos = _hier_rig(topo_hosts) if topo_hosts is not None else None
        for r in range(world):
            c = Wired(timeout_sec=15)
            c._rank, c._world = r, world
            if topos is not None:
                c._hier = topos[r]
            else:
                c._rings = [rings[r]]
            comms.append(c)

        results = {r: [] for r in range(world)}
        metrics = {}
        errors = []
        barrier = threading.Barrier(world)

        def run(rank):
            m = _devq_manager(comms[rank], rank, device_quantize,
                              world=world)
            try:
                for step in range(steps):
                    rng = np.random.default_rng(1000 * rank + step)
                    grads = {
                        "a": jnp.asarray(
                            rng.normal(size=(61, 17))
                            .astype(np.float32)),
                        "b": jnp.asarray(
                            rng.normal(size=2_001)
                            .astype(np.float32))}
                    barrier.wait(timeout=30)
                    m.step()
                    avg = m.allreduce(grads).result()
                    assert m.should_commit()
                    results[rank].append(
                        {k: np.asarray(v) for k, v in avg.items()})
                metrics[rank] = m.metrics()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                try:
                    barrier.abort()
                except Exception:  # noqa: BLE001
                    pass
            finally:
                m.shutdown()

        ts = [threading.Thread(target=run, args=(r,))
              for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        assert not errors, errors
        return results, metrics

    @staticmethod
    def _assert_equal(a, b):
        for rank in a:
            assert len(a[rank]) == len(b[rank])
            for sa, sb in zip(a[rank], b[rank]):
                for k in sa:
                    np.testing.assert_array_equal(sa[k], sb[k])

    def test_flat_vs_hier_vs_host_quant_all_bitwise(self):
        hosts = [[0, 1], [2, 3]]
        hier_dev, m_hd = self._drive(hosts, device_quantize=True)
        # Cross-rank lockstep on the hier leg.
        for step in range(3):
            for r in range(1, self.WORLD):
                for k in hier_dev[0][step]:
                    np.testing.assert_array_equal(
                        hier_dev[0][step][k], hier_dev[r][step][k])
        flat_dev, m_fd = self._drive(None, device_quantize=True)
        self._assert_equal(hier_dev, flat_dev)
        hier_host, m_hh = self._drive(hosts, device_quantize=False)
        self._assert_equal(hier_dev, hier_host)
        # Byte accounting: the device leg fetched wire bytes; the hier
        # leg's intra star carried traffic and its leaders are 2 of 4.
        assert (m_hd[0]["allreduce_d2h_wire_bytes_total"]
                < 0.3 * m_hh[0]["allreduce_d2h_wire_bytes_total"])
        assert sum(m_hd[r]["hier_leader"] for r in m_hd) == 2.0
        assert sum(m_hd[r]["hier_intra_bytes_total"]
                   for r in m_hd) > 0
        assert all(m_fd[r]["hier_intra_bytes_total"] == 0.0
                   for r in m_fd)


# ------------------------------------------- full rendezvous (native)


@requires_native
class TestHierRendezvous:
    """End-to-end configure over the real store: host ids advertised,
    co-location detected, star + leader ring built, a wire op runs,
    and a fresh configure re-elects cleanly."""

    def _configure_all(self, store_addr, world, host_ids):
        comms = [HostCommunicator(timeout_sec=15, host_id=host_ids[r],
                                  hier=True)
                 for r in range(world)]
        errs = []

        def cfg(r):
            try:
                comms[r].configure(store_addr, r, world)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=cfg, args=(r,))
              for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not errs, errs
        return comms

    def test_two_hosts_two_ranks(self):
        from torchft_tpu._native import Store

        store = Store("127.0.0.1:0")
        try:
            addr = f"{store.address()}/t/1"
            comms = self._configure_all(
                addr, 4, ["ha", "ha", "hb", "hb"])
            try:
                assert [c.ring_topology() for c in comms] == \
                    ["hier:2x2"] * 4
                assert sum(c.hier_leader() for c in comms) == 2.0
                xs = _payloads(4, seed=20, size=20_000)
                futs = [c.allreduce_wire([xs[r].copy()], [F32])
                        for r, c in enumerate(comms)]
                outs = [f.result(timeout=30) for f in futs]
                for o in outs[1:]:
                    np.testing.assert_array_equal(outs[0][0], o[0])
            finally:
                for c in comms:
                    c.shutdown()
        finally:
            store.shutdown()

    def test_unique_hosts_stay_flat(self):
        from torchft_tpu._native import Store

        store = Store("127.0.0.1:0")
        try:
            addr = f"{store.address()}/t/2"
            comms = self._configure_all(addr, 2, ["ha", "hb"])
            try:
                assert [c.ring_topology() for c in comms] == \
                    ["flat", "flat"]
            finally:
                for c in comms:
                    c.shutdown()
        finally:
            store.shutdown()
