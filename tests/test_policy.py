"""Adaptive fault-tolerance policy tests (ISSUE 10,
docs/design/adaptive_policy.md).

Tier-1 (marker ``policy``, ``scripts/test.sh policy``): the FTPolicy
knob bundle, the PolicyController's hysteresis ladder, the int8 +
error-feedback wire rung (quantizer units, socketpair-ring cross-rank
bitwise identity at worlds 2/3/5, ~1/4 ring bytes, EF drift A/B), the
Manager's commit-boundary switch machinery (refusal mid-heal /
mid-deferred, event stamping, state-dict adoption, fake-store
coordination incl. the switch-racing-a-heal deferral), the
DiLoCoTrainer cadence setter, and the AdaptiveTrainer mode transitions.

The phase-varying adaptive-vs-fixed chaos soak (the acceptance gate)
rides ``nightly``+``slow`` like the other soaks and needs the native
control plane.
"""

import functools
import threading
from unittest.mock import MagicMock

import numpy as np
import pytest

import conftest
import mockplane
from mockplane import (FAKE_STORE_ADDR, FakeStore, boundary, mock_client,
                       quorum_result)
from torchft_tpu.backends.host import HostCommunicator
from torchft_tpu.communicator import (CommunicatorError, DummyCommunicator,
                                      Int8Wire)
from torchft_tpu.policy import (LADDER, POLICIES, AdaptiveTrainer,
                                FTPolicy, PolicyController)

pytestmark = pytest.mark.policy


# --------------------------------------------------------------- helpers


make_manager = functools.partial(mockplane.make_manager, min_replica_size=1)


# --------------------------------------------------------------- FTPolicy


class TestFTPolicy:
    def test_registry_and_ladder(self):
        assert [p.name for p in LADDER] == [
            "overlap-bf16", "overlap-bf16-ckpt8", "sync-f32",
            "sync-bf16", "sync-int8", "diloco-8"]
        for name in ("sync-f32", "overlap-bf16", "diloco-16",
                     "sync-int8"):
            assert POLICIES[name].name == name

    def test_validation(self):
        with pytest.raises(ValueError, match="overlap_steps"):
            FTPolicy("x", overlap_steps=2)
        with pytest.raises(ValueError, match="wire rung"):
            FTPolicy("x", wire=9)
        with pytest.raises(ValueError, match="sync_every"):
            FTPolicy("x", sync_every=0)
        with pytest.raises(ValueError, match="mutually exclusive"):
            FTPolicy("x", diloco=True, overlap_steps=1)

    def test_state_roundtrip_matches_ladder_names(self):
        for p in LADDER:
            back = FTPolicy.from_state(p.to_state(), ladder=LADDER)
            assert back.knobs() == p.knobs()
            assert back.name == p.name
        # Off-ladder knobs synthesize a descriptive name.
        odd = FTPolicy("custom", wire=2, ckpt_every=3)
        back = FTPolicy.from_state(odd.to_state(), ladder=LADDER)
        assert back.knobs() == odd.knobs()
        assert "int8" in back.name

    def test_wire_dtype_mapping(self):
        import jax.numpy as jnp

        assert POLICIES["sync-f32"].wire_dtype() is None
        assert POLICIES["sync-bf16"].wire_dtype() == jnp.bfloat16
        # int8 transfers D2H in full precision; quantization happens
        # host-side where the EF residual lives.
        assert POLICIES["sync-int8"].wire_dtype() is None


class TestPolicyController:
    def mk(self, **kw):
        kw.setdefault("window", 4)
        kw.setdefault("escalate_failures", 2)
        kw.setdefault("relax_after", 3)
        kw.setdefault("cooldown", 1)
        return PolicyController(**kw)

    def test_escalates_on_windowed_failures(self):
        c = self.mk()
        assert c.note_boundary(False) is None  # 1 failure: under thresh
        prop = c.note_boundary(False)
        assert prop is not None and prop[0] == 1
        assert "escalate" in prop[1]
        # The controller itself does not move until the switch lands.
        assert c.rung == 0
        c.sync_rung(1)
        assert c.rung == 1

    def test_reconfigure_counts_as_failure(self):
        c = self.mk()
        c.note_boundary(True, reconfigured=True)
        prop = c.note_boundary(True, reconfigured=True)
        assert prop is not None and prop[0] == 1

    def test_relaxes_after_quiet_window(self):
        c = self.mk()
        c.sync_rung(2)
        out = [c.note_boundary(True) for _ in range(3)]
        assert out[:2] == [None, None]
        assert out[2] is not None and out[2][0] == 1
        assert "relax" in out[2][1]

    def test_cooldown_and_window_reset_bound_flapping(self):
        c = self.mk(cooldown=3)
        c.note_boundary(False)
        c.note_boundary(False)
        c.sync_rung(1)  # switch landed; window cleared
        # Immediately after a switch, neither old failures nor fresh
        # ones inside the cooldown can move the ladder again.
        assert c.note_boundary(False) is None
        assert c.note_boundary(False) is None
        prop = c.note_boundary(False)  # cooldown satisfied, 3 fresh
        assert prop is not None and prop[0] == 2

    def test_top_rung_saturates_and_bottom_stops_relaxing(self):
        c = self.mk()
        c.sync_rung(len(c.ladder) - 1)
        c.note_boundary(False)
        assert c.note_boundary(False) is None  # nowhere to escalate
        c2 = self.mk()
        for _ in range(6):
            assert c2.note_boundary(True) is None  # already at rung 0

    def test_diloco_rung_gated_on_comm_frac(self):
        c = self.mk(diloco_min_comm_frac=0.5)
        c.sync_rung(len(c.ladder) - 2)  # next rung up is diloco
        c.note_boundary(False, comm_frac=0.01)
        assert c.note_boundary(False, comm_frac=0.01) is None
        c.sync_rung(len(c.ladder) - 2)
        for _ in range(4):  # drive the comm EMA above the gate
            c.note_boundary(True, comm_frac=0.9)
        c.note_boundary(False, comm_frac=0.9)
        prop = c.note_boundary(False, comm_frac=0.9)
        assert prop is not None and c.ladder[prop[0]].diloco

    def test_signals_surface(self):
        c = self.mk()
        c.note_boundary(False, comm_frac=0.4)
        sig = c.last_signals
        assert sig.failures_in_window == 1
        assert sig.failure_rate == 1.0
        assert sig.comm_frac > 0.0
        assert set(sig.as_dict()) == {
            "failures_in_window", "window", "failure_rate",
            "comm_frac", "quiet_boundaries", "churn_rate",
            "fleet_p95_ms", "straggler_score"}
        # Fleet hints flow through note_boundary into the signals
        # (docs/design/fleet_health.md); absent they stay 0.0.
        assert sig.fleet_p95_ms == 0.0
        assert sig.straggler_score == 0.0
        c.note_boundary(True, fleet_p95_ms=1234.5, straggler_score=2.5)
        sig = c.last_signals
        assert sig.fleet_p95_ms == 1234.5
        assert sig.straggler_score == 2.5


# -------------------------------------------------------------- int8 wire


class TestInt8Quantizer:
    def test_roundtrip_error_bounded_per_segment(self):
        rng = np.random.default_rng(3)
        x = (rng.normal(size=200_003) * 10).astype(np.float32)
        w = Int8Wire.quantize(x)
        err = np.abs(w.dequantize(np.float32) - x)
        # Affine with 254 levels: |err| <= scale/2 per element.
        for s in range(len(w.scales)):
            sl = slice(s * w.seg_elems,
                       min((s + 1) * w.seg_elems, x.size))
            assert err[sl].max() <= w.scales[s] / 2 + 1e-6

    def test_non_finite_segment_encodes_zero_and_ef_recovers(self):
        """A loss-spike inf/NaN element must not poison the rung: the
        segment encodes as exact zero (finite reconstruction), and the
        Manager's residual ledger drops the junk step so the NEXT clean
        contribution quantizes normally — unlike banking a NaN residual
        that would re-fold into every later step forever."""
        x = np.linspace(-1, 1, 70_000).astype(np.float32)
        bad = x.copy()
        bad[123] = np.nan  # poisons segment 0 only
        w = Int8Wire.quantize(bad)
        d = w.dequantize(np.float32)
        assert np.isfinite(d).all()
        # The poisoned segment reconstructs to exact zero; the clean
        # segment quantizes normally.
        assert not d[:65_536].any()
        assert abs(d[65_536:] - x[65_536:]).max() <= w.scales[1] / 2 + 1e-6
        # Manager-level recovery: one poisoned step between clean ones.
        from unittest.mock import MagicMock as MM

        client = MM()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        m = make_manager(client, comm=DummyCommunicator(world_size=2),
                         policy=POLICIES["sync-int8"])
        try:
            for step_vals in (x, bad, x, x):
                m.step()
                out = m.allreduce({"g": step_vals.copy()}).result()
                assert np.isfinite(np.asarray(out["g"])).all()
                assert m.should_commit()
            for r in m._exchange._ef_residuals.values():
                assert np.isfinite(r).all()
        finally:
            m.shutdown()

    def test_constant_segments_exact(self):
        c = np.full(70_000, -7.5, np.float32)  # spans two segments
        w = Int8Wire.quantize(c)
        np.testing.assert_array_equal(w.dequantize(np.float32), c)
        z = Int8Wire.zeros_like(130_000)
        assert not z.dequantize(np.float32).any()

    def test_bytes_roundtrip_and_quarter_ratio(self):
        rng = np.random.default_rng(4)
        x = rng.normal(size=300_001).astype(np.float32)
        w = Int8Wire.quantize(x)
        raw = w.to_bytes()
        assert len(raw) == Int8Wire.payload_nbytes(x.size)
        assert len(raw) / x.nbytes < 0.26  # ~1/4 of f32 + headers
        back = Int8Wire.from_bytes(raw, x.size)
        np.testing.assert_array_equal(back.dequantize(np.float32),
                                      w.dequantize(np.float32))

    def test_error_feedback_drives_repeated_average_error_to_zero(self):
        """The rung's acceptance numeric: repeatedly quantizing the SAME
        contribution with the residual folded back drives the cumulative
        (and so the mean) reconstruction error to a bounded constant —
        mean error -> 0 as 1/t — while feedback-free quantization
        repeats the identical bias every round (unbounded cumulative
        drift, mean error constant)."""
        rng = np.random.default_rng(5)
        x = rng.normal(size=50_000).astype(np.float32)
        rounds = 40
        res = np.zeros_like(x)
        cum_ef = np.zeros_like(x)
        cum_raw = np.zeros_like(x)
        for _ in range(rounds):
            v = x + res
            w = Int8Wire.quantize(v)
            d = w.dequantize(np.float32)
            res = v - d
            cum_ef += d
            cum_raw += Int8Wire.quantize(x).dequantize(np.float32)
        drift_ef = np.abs(cum_ef - rounds * x).max()
        drift_raw = np.abs(cum_raw - rounds * x).max()
        scale = Int8Wire.quantize(x).scales.max()
        # EF: total drift stays within ~one quantization step forever.
        assert drift_ef <= scale + 1e-5
        # No feedback: the per-round bias accumulates linearly.
        assert drift_raw > 10 * drift_ef
        mean_err = np.abs(cum_ef / rounds - x).max()
        assert mean_err < np.abs(
            Int8Wire.quantize(x).dequantize(np.float32) - x).max()


def _socketpair_rings(world):
    import socket as _socket

    from torchft_tpu.backends.host import _Ring

    pairs = [_socket.socketpair() for _ in range(world)]
    return [_Ring(pairs[r][0], pairs[(r - 1) % world][1],
                  _socket.socket())
            for r in range(world)]


class TestInt8WireRing:
    """The int8+EF rung over real sockets (the same socketpair-ring
    battery as the bf16 wire, tests/test_communicator.py): raw
    contributions, canonical-rank-order folds, cross-rank bitwise
    identity, ~1/4 ring bytes, and reduce-scatter stripe identity."""

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
        assert not alive, "int8 wire ring deadlocked"
        return out, comms, errors

    @pytest.mark.parametrize("world", [2, 3, 5])
    def test_cross_rank_bitwise_identity(self, world):
        rng = np.random.default_rng(world)
        xs = [rng.normal(size=10_007).astype(np.float32)
              for _ in range(world)]
        ws = [Int8Wire.quantize(x) for x in xs]

        out, comms, errors = self._run(
            world, lambda c, ring, r: c._ring_allreduce_int8(
                ring, Int8Wire.quantize(xs[r]),
                np.dtype(np.float32)))
        assert not errors, errors
        # Canonical rank-order fold of once-quantized contributions.
        expected = np.zeros(10_007, np.float32)
        for w in ws:
            expected += w.dequantize(np.float32)
        for o in out:
            np.testing.assert_array_equal(o, expected)
        for c in comms:
            c.shutdown()

    def test_ring_bytes_quarter_of_f32(self):
        size = 300_001
        rng = np.random.default_rng(9)
        xs = [rng.normal(size=size).astype(np.float32) for _ in range(2)]
        out, comms, errors = self._run(
            2, lambda c, ring, r: c._ring_allreduce_int8(
                ring, Int8Wire.quantize(xs[r]), np.dtype(np.float32)))
        assert not errors, errors
        exact_f32_bytes = 4 * size  # 2(n-1)/n * payload at world 2
        for c in comms:
            sent = c.ring_bytes_total()
            assert sent == Int8Wire.payload_nbytes(size)
            assert sent / exact_f32_bytes < 0.26
            assert c.int8_ring_bytes_total() == sent
            c.shutdown()

    @pytest.mark.parametrize("world", [2, 3])
    def test_reduce_scatter_stripes_bitwise_match_allreduce(self, world):
        from torchft_tpu.communicator import shard_bounds

        rng = np.random.default_rng(11)
        xs = [rng.normal(size=9_001).astype(np.float32)
              for _ in range(world)]

        full, comms, errors = self._run(
            world, lambda c, ring, r: c._ring_allreduce_int8(
                ring, Int8Wire.quantize(xs[r]), np.dtype(np.float32)))
        assert not errors
        for c in comms:
            c.shutdown()
        shards, comms, errors = self._run(
            world, lambda c, ring, r: c._ring_reduce_scatter_int8(
                ring, Int8Wire.quantize(xs[r]), np.dtype(np.float32)))
        assert not errors
        bounds = shard_bounds(9_001, world)
        for r in range(world):
            np.testing.assert_array_equal(
                shards[r], full[0][bounds[r]:bounds[r + 1]])
        for c in comms:
            c.shutdown()

    def test_do_allreduce_wire_mixes_int8_and_exact_chunks(self):
        rng = np.random.default_rng(12)
        xs = [rng.normal(size=2_000).astype(np.float32)
              for _ in range(2)]
        ints = np.arange(9, dtype=np.int64)
        ws = [Int8Wire.quantize(x) for x in xs]

        def fn(c, ring, r):
            return c._do_allreduce_wire(
                ring,
                [Int8Wire.quantize(xs[r]), ints * (r + 1)],
                [np.dtype(np.float32), np.dtype(np.int64)], "sum")

        out, comms, errors = self._run(2, fn)
        assert not errors, errors
        expected = ws[0].dequantize(np.float32) \
            + ws[1].dequantize(np.float32)
        for o in out:
            np.testing.assert_array_equal(o[0], expected)
            np.testing.assert_array_equal(o[1], ints * 3)
        for c in comms:
            c.shutdown()

    def test_payload_tag_skew_detected(self):
        """DiLoCo outer-round pseudo-gradients and per-step gradients
        have identical geometry; the preamble's payload tag is what
        keeps a one-boundary DiLoCo-transition skew from folding one
        into the other."""
        x = np.ones(1_024, np.float32)

        def fn(c, ring, r):
            return c._do_allreduce_wire(
                ring, [x.copy()], [np.dtype(np.float32)], "sum",
                "step" if r == 0 else "diloco")

        out, comms, errors = self._run(2, fn)
        assert len(errors) == 2, (errors, out)
        assert all("wire format skew" in str(e) for e in errors)
        for c in comms:
            c.shutdown()

    def test_wire_format_skew_detected_not_folded(self):
        """The preamble guarantee the adaptive layer leans on: two ranks
        disagreeing on the wire format (one switched to int8, one
        missed the decision) must get a clean CommunicatorError — never
        a silent garbage fold."""
        x = np.ones(4_096, np.float32)

        def fn(c, ring, r):
            bufs = [Int8Wire.quantize(x)] if r == 0 else [x.copy()]
            return c._do_allreduce_wire(
                ring, bufs, [np.dtype(np.float32)], "sum")

        out, comms, errors = self._run(2, fn)
        assert len(errors) == 2, (errors, out)
        for e in errors:
            assert isinstance(e, CommunicatorError)
            assert "wire format skew" in str(e)
        for c in comms:
            c.shutdown()


# ------------------------------------------------------- manager policy


class TestManagerPolicy:
    def test_synthesized_policy_from_legacy_knobs(self):
        import jax.numpy as jnp

        client = MagicMock()
        m = make_manager(client, overlap_steps=1,
                         allreduce_wire_dtype=jnp.bfloat16)
        try:
            p = m.policy()
            assert p.overlap_steps == 1 and p.wire_name() == "bf16"
            assert m.metrics_info()["policy_name"] == p.name
            # Legacy managers stay legacy: no policy fields in the
            # state dict (tests pin its exact shape).
            assert set(m.state_dict()) == {"step", "batches_committed"}
        finally:
            m.shutdown()

    def test_set_policy_applies_knobs_and_stamps_event(self):
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        m = make_manager(client, policy=POLICIES["sync-f32"])
        try:
            assert m.set_policy(POLICIES["sync-int8"], reason="test")
            assert m.policy().name == "sync-int8"
            assert m._exchange.wire_dtype is None
            assert m.set_policy(POLICIES["overlap-bf16"])
            assert m.overlap_steps() == 1
            assert str(m._exchange.wire_dtype) == "bfloat16"
            mx = m.metrics()
            assert mx["policy_switches_total"] == 2
            assert m.metrics_info()["policy_name"] == "overlap-bf16"
            events = [e for e in m.history()
                      if e.get("event") == "policy_switch"]
            assert [(e["from"], e["to"]) for e in events] == [
                ("sync-f32", "sync-int8"),
                ("sync-int8", "overlap-bf16")]
            assert events[0]["reason"] == "test"
        finally:
            m.shutdown()

    def test_state_dict_adoption(self):
        client = MagicMock()
        donor = make_manager(client, policy=POLICIES["sync-int8"])
        healer = make_manager(MagicMock(), policy=POLICIES["sync-f32"],
                              replica_id="healer")
        try:
            sd = donor.state_dict()
            assert sd["policy_wire"] == POLICIES["sync-int8"].wire
            healer.load_state_dict(sd)
            assert healer.policy().name == "sync-int8"
            assert any(e.get("event") == "policy_adopt"
                       for e in healer.history())
        finally:
            donor.shutdown()
            healer.shutdown()

    def test_event_history_depth_configurable(self, monkeypatch):
        m = make_manager(MagicMock(), event_history=7)
        try:
            for i in range(30):
                m._log_event(event="x", i=i)
            assert len(m.history()) == 7
        finally:
            m.shutdown()
        monkeypatch.setenv("TORCHFT_EVENT_HISTORY", "11")
        m = make_manager(MagicMock())
        try:
            assert m._history.maxlen == 11
        finally:
            m.shutdown()

    def test_int8_pipeline_with_error_feedback(self):
        """End-to-end through the Manager's host pipeline: under the
        sync-int8 policy the averaged result is the quantized average
        (bounded error), the EF residual is banked (gauge > 0), and the
        running mean of repeated allreduces of the SAME grads converges
        onto the exact mean (the EF property, now manager-level)."""
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        comm = DummyCommunicator(world_size=2)
        m = make_manager(client, comm=comm,
                         policy=POLICIES["sync-int8"])
        rng = np.random.default_rng(21)
        x = {"g": rng.normal(size=30_000).astype(np.float32)}
        try:
            rounds = 20
            acc = np.zeros_like(x["g"])
            for _ in range(rounds):
                m.step()
                out = m.allreduce({"g": x["g"].copy()}).result()
                acc += np.asarray(out["g"])
                assert m.should_commit()
            # Dummy comm sums only this rank; n=2 halves it.
            mean_err = np.abs(acc / rounds - x["g"] / 2).max()
            single = Int8Wire.quantize(x["g"])
            single_err = np.abs(
                single.dequantize(np.float32) - x["g"]).max() / 2
            assert mean_err < single_err / 4
            assert m.metrics()["wire_quant_residual_bytes"] > 0
        finally:
            m.shutdown()

    def test_delayed_optimizer_stage_guard(self):
        import optax

        from torchft_tpu.optim import DelayedOptimizer

        client = MagicMock()
        client.quorum.return_value = quorum_result()
        m = make_manager(client, policy=POLICIES["sync-f32"])
        opt = DelayedOptimizer(m, optax.sgd(0.1))
        try:
            m.step()
            fut = m.allreduce({"g": np.ones(2, np.float32)})
            with pytest.raises(RuntimeError, match="overlap disabled"):
                opt.stage(MagicMock(), fut)
        finally:
            m.shutdown()


class TestPolicyCoordination:
    """The decider/follower protocol over a (fake) quorum store: the
    participating rank 0 publishes, everyone adopts, switches racing a
    heal are deferred and retried."""

    def _pair(self, store, ctl_kwargs=None):
        ctl_kwargs = ctl_kwargs or dict(window=4, escalate_failures=2,
                                        relax_after=3, cooldown=1)
        ms = []
        for rank in range(2):
            client = mock_client(quorum_result(
                store_address=FAKE_STORE_ADDR, max_rank=rank,
                replica_rank=rank))
            m = make_manager(client,
                             comm=DummyCommunicator(world_size=2),
                             replica_id=f"coord{rank}", store=store,
                             policy_controller=PolicyController(
                                 **ctl_kwargs))
            ms.append((m, client))
        return ms

    def test_decider_publishes_and_follower_adopts(self):
        store = FakeStore()
        ms = self._pair(store)
        try:
            for m, c in ms:
                c.should_commit.return_value = False  # storm
            for _ in range(4):
                for m, _c in ms:
                    boundary(m)
            names = [m.policy().name for m, _ in ms]
            assert names[0] == names[1] != "overlap-bf16", names
            assert store.kv["torchft/policy"]
            # The follower adopted via the coordinated read.
            follower_events = [e for e in ms[1][0].history()
                               if e.get("event") == "policy_switch"]
            assert follower_events
            assert "coordinated" in follower_events[0]["reason"]
        finally:
            for m, _ in ms:
                m.shutdown()

    def test_switch_racing_heal_deferred_then_retried(self):
        store = FakeStore()
        ms = self._pair(store)
        (decider, dc), (follower, fc) = ms
        try:
            dc.should_commit.return_value = False
            fc.should_commit.return_value = False
            # Someone in the quorum is healing: max_world < replica_world.
            dc.quorum.return_value = quorum_result(
                store_address=FAKE_STORE_ADDR, max_rank=0, replica_rank=0,
                max_world_size=1, replica_world_size=2)
            for _ in range(4):
                boundary(decider)
            mx = decider.metrics()
            assert mx["policy_switch_deferrals"] >= 1
            assert decider.policy().name == "overlap-bf16"  # unchanged
            assert any(e.get("event") == "policy_switch_deferred"
                       for e in decider.history())
            # Heal finished: the deferred switch lands at the next
            # boundary.
            dc.quorum.return_value = quorum_result(
                store_address=FAKE_STORE_ADDR, max_rank=0, replica_rank=0)
            boundary(decider)
            assert decider.policy().name != "overlap-bf16"
            boundary(follower)
            assert follower.policy().name == decider.policy().name
        finally:
            for m, _ in ms:
                m.shutdown()

    def test_follower_missing_read_catches_up_next_boundary(self):
        store = FakeStore()
        ms = self._pair(store)
        (decider, dc), (follower, fc) = ms
        try:
            dc.should_commit.return_value = False
            for _ in range(4):
                boundary(decider)
            assert decider.policy().name != "overlap-bf16"
            # The follower read nothing so far (its boundaries never
            # ran); its next boundary reads the persistent key and
            # adopts in one hop — the late-join/missed-read repair.
            boundary(follower)
            assert follower.policy().name == decider.policy().name
        finally:
            for m, _ in ms:
                m.shutdown()


class _PairHub:
    """Two-rank rendezvous 'ring': pairs each rank's n-th wire op with
    the peer's n-th, folds the (dequantized) contributions in canonical
    rank order, and resolves both futures with the identical sum —
    exercising the Manager pipelines, int8 quantization, and policy
    lockstep end-to-end without the native store the real ring's
    rendezvous needs."""

    def __init__(self):
        self.lock = threading.Lock()
        self.counts = {}
        self.pending = {}

    def submit(self, rank, buffers, origs):
        from concurrent.futures import Future

        from torchft_tpu.communicator import _upcast_buffers

        fut = Future()
        with self.lock:
            idx = self.counts.get(rank, 0)
            self.counts[rank] = idx + 1
            entry = self.pending.setdefault(idx, {})
            entry[rank] = (list(buffers), [np.dtype(d) for d in origs],
                           fut)
            ready = len(entry) == 2
            if ready:
                del self.pending[idx]
        if ready:
            vals = {r: _upcast_buffers(b, o)
                    for r, (b, o, _f) in entry.items()}
            sums = [vals[0][i] + vals[1][i]
                    for i in range(len(vals[0]))]
            for _r, (_b, origs_r, f) in entry.items():
                f.set_result([np.array(s, dtype=d)
                              for s, d in zip(sums, origs_r)])
        return fut


class _PairComm(DummyCommunicator):
    """Communicator riding a :class:`_PairHub` for its wire ops."""

    def __init__(self, hub, rank):
        super().__init__(rank=rank, world_size=2)
        self._hub = hub

    def allreduce_wire(self, buffers, orig_dtypes, op="sum"):
        return self._hub.submit(self.rank(), buffers, orig_dtypes)


class TestTwoGroupTransitionsLockstep:
    """The transition acceptance oracle, tier-1 spelling: two groups
    run the AdaptiveTrainer through scripted stable -> storm -> stable
    vote outcomes with coordinated controllers over a fake store; the
    policy must escalate through the wire ladder (including a mid-run
    switch into the int8+EF rung) and relax back, with params BITWISE
    lockstep across groups at every boundary."""

    def test_params_lockstep_through_mid_run_switches(self):
        import jax
        import jax.numpy as jnp
        import optax

        # Ladder without the DiLoCo rung: DiLoCo changes the op cadence,
        # which the hub's strict 1-op-per-boundary pairing (deliberately
        # stricter than the real ring) cannot host under one-boundary
        # adoption skew. The real ring detects that skew via the
        # payload tag (test_payload_tag_skew_detected).
        ladder = LADDER[:5]
        store = FakeStore()
        hub = _PairHub()
        script = [True] * 4 + [False] * 12 + [True] * 14
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 6)), jnp.float32)

        def loss_fn(params, batch):
            return ((batch @ params["w"]) ** 2).mean()

        barrier = threading.Barrier(2)
        results = {}
        errors = []

        def run_group(rank):
            calls = {"n": 0}

            def vote(rank=None, step=None, should_commit=None,
                     timeout_ms=None):
                i = min(calls["n"], len(script) - 1)
                calls["n"] += 1
                return script[i]

            client = MagicMock()
            client.quorum.return_value = quorum_result(
                store_address=FAKE_STORE_ADDR, max_rank=rank,
                replica_rank=rank)
            client.should_commit.side_effect = vote
            trainer = AdaptiveTrainer(
                loss_fn=loss_fn, tx=optax.sgd(0.05),
                params={"w": np.full((6, 2), 0.1, np.float32)},
                manager_factory=lambda load, save: make_manager(
                    client, comm=_PairComm(hub, rank), store=store,
                    load_state_dict=load, state_dict=save,
                    replica_id=f"pair{rank}",
                    policy_controller=PolicyController(
                        ladder=ladder, window=4, escalate_failures=2,
                        relax_after=4, cooldown=1)),
                jit=False)
            snaps = []
            names = []
            try:
                for _ in range(len(script)):
                    barrier.wait(timeout=60)
                    trainer.train_step(x)
                    snaps.append(jax.device_get(trainer.params))
                    names.append(trainer.manager.policy().name)
                trainer.flush()
                results[rank] = {
                    "snaps": snaps, "names": names,
                    "final": jax.device_get(trainer.params),
                    "metrics": trainer.manager.metrics(),
                    "events": trainer.manager.history(),
                }
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                try:
                    barrier.abort()
                except Exception:  # noqa: BLE001
                    pass
            finally:
                trainer.shutdown()

        ts = [threading.Thread(target=run_group, args=(r,))
              for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not errors, errors
        assert len(results) == 2

        # Params bitwise lockstep at EVERY boundary, switches included.
        for i, (a, b) in enumerate(zip(results[0]["snaps"],
                                       results[1]["snaps"])):
            jax.tree_util.tree_map(
                lambda u, v: np.testing.assert_array_equal(
                    u, v, err_msg=f"divergence at boundary {i}"),
                a, b)
        # The storm drove the ladder into the int8 rung mid-run...
        assert "sync-int8" in results[0]["names"], results[0]["names"]
        # ...and the quiet tail relaxed back at least one rung.
        reasons = [str(e.get("reason", ""))
                   for e in results[0]["events"]
                   if e.get("event") == "policy_switch"]
        assert any("escalate" in r for r in reasons), reasons
        assert any("relax" in r for r in reasons), reasons
        # Both groups end within the protocol's bounded adoption skew
        # (the follower reads the decider's publication no later than
        # its next boundary — exactly one rung of lag at a cut point).
        rung_of = {p.name: i for i, p in enumerate(ladder)}
        assert abs(rung_of[results[0]["names"][-1]]
                   - rung_of[results[1]["names"][-1]]) <= 1, (
            results[0]["names"][-3:], results[1]["names"][-3:])
        for r in (0, 1):
            assert results[r]["metrics"]["policy_switches_total"] <= 10
        # The int8 rung's residuals actually engaged on both groups.
        assert all(
            any(n == "sync-int8" for n in results[r]["names"])
            for r in (0, 1))


# --------------------------------------------------------- mode switching


class TestAdaptiveTrainerModes:
    def _trainer(self, policy=None, controller=None):
        import optax

        client = MagicMock()
        client.quorum.return_value = quorum_result(
            max_world_size=1, replica_world_size=1)
        client.should_commit.return_value = True

        def loss_fn(params, batch):
            return ((params["w"] - batch) ** 2).sum()

        kwargs = {}
        if policy is not None:
            kwargs["policy"] = policy
        if controller is not None:
            kwargs["policy_controller"] = controller
        trainer = AdaptiveTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.1),
            params={"w": np.zeros(4, np.float32)},
            manager_factory=lambda load, save: make_manager(
                client, load_state_dict=load, state_dict=save,
                replica_id="adaptive", **kwargs),
            jit=False)
        return trainer, client

    def test_sync_to_diloco_and_back_at_round_boundaries(self):
        import jax.numpy as jnp

        trainer, _client = self._trainer(policy=POLICIES["sync-f32"])
        batch = jnp.ones(4, jnp.float32)
        try:
            assert trainer.mode() == "sync"
            _, committed = trainer.train_step(batch)
            assert committed is True
            assert trainer.committed_batches == 1
            # Switch to DiLoCo between steps (a commit boundary).
            assert trainer.manager.set_policy(POLICIES["diloco-8"])
            trainer.train_step(batch)
            assert trainer.mode() == "diloco"
            # Inner steps: no boundary, no commit.
            for _ in range(POLICIES["diloco-8"].sync_every - 2):
                _, committed = trainer.train_step(batch)
                assert committed is None
            _, committed = trainer.train_step(batch)  # outer round
            assert committed is True
            assert trainer.committed_batches == \
                1 + POLICIES["diloco-8"].sync_every
            # Switch back mid-cycle: lands only at the NEXT outer round
            # (DiLoCo-mode boundaries ARE outer rounds).
            assert trainer.manager.set_policy(POLICIES["sync-f32"])
            _, committed = trainer.train_step(batch)
            assert trainer.mode() == "diloco" and committed is None
            for _ in range(POLICIES["diloco-8"].sync_every - 1):
                trainer.train_step(batch)
            assert trainer.mode() == "sync"
        finally:
            trainer.shutdown()

    def test_overlap_to_sync_discards_prefetched_grads(self):
        import jax.numpy as jnp

        trainer, _client = self._trainer(policy=POLICIES["overlap-bf16"])
        batch = jnp.ones(4, jnp.float32)
        try:
            assert trainer.mode() == "overlap"
            _, committed = trainer.train_step(batch)
            assert committed is None  # first step: nothing settled yet
            _, committed = trainer.train_step(batch)
            assert committed is True  # previous step's deferred vote
            # A switch while a step is staged is refused...
            assert not trainer.manager.set_policy(POLICIES["sync-f32"])
            # ...and the trainer's own boundary (inside the next
            # train_step's settle) is where a controller switch lands;
            # emulate it by flushing then switching.
            trainer.flush()
            assert trainer.manager.set_policy(POLICIES["sync-f32"])
            trainer.train_step(batch)
            assert trainer.mode() == "sync"
            assert not trainer.manager.deferred_pending()
        finally:
            trainer.shutdown()


class TestDiLoCoSetSyncEvery:
    def _trainer(self, cls, **kw):
        import optax

        from torchft_tpu import local_sgd

        client = MagicMock()
        client.quorum.return_value = quorum_result(
            max_world_size=1, replica_world_size=1)
        client.should_commit.return_value = True

        def loss_fn(params, batch):
            return ((params["w"] - batch) ** 2).sum()

        trainer = getattr(local_sgd, cls)(
            loss_fn=loss_fn, inner_tx=optax.sgd(0.1),
            params={"w": np.zeros(2, np.float32)},
            manager_factory=lambda load, save: make_manager(
                client, load_state_dict=load, state_dict=save,
                replica_id="diloco"),
            jit=False, **kw)
        return trainer

    def test_applies_at_next_outer_round(self):
        import jax.numpy as jnp

        t = self._trainer("DiLoCoTrainer", sync_every=4)
        batch = jnp.ones(2, jnp.float32)
        try:
            for _ in range(3):
                _, committed = t.train_step(batch)
                assert committed is None
            t.set_sync_every(2)
            assert t.sync_every == 4  # current cycle completes as-is
            _, committed = t.train_step(batch)  # round at step 4
            assert committed is True
            assert t.sync_every == 2  # applied at the round boundary
            _, committed = t.train_step(batch)
            assert committed is None
            _, committed = t.train_step(batch)  # step 6: new cadence
            assert committed is True
        finally:
            t.shutdown()

    def test_validation(self):
        t = self._trainer("DiLoCoTrainer", sync_every=4)
        try:
            with pytest.raises(ValueError, match="sync_every"):
                t.set_sync_every(0)
        finally:
            t.shutdown()

    def test_streaming_validates_fragment_divisibility(self):
        t = self._trainer("StreamingDiLoCoTrainer", sync_every=8,
                          fragments=4)
        try:
            with pytest.raises(ValueError, match="divisible"):
                t.set_sync_every(6)
            t.set_sync_every(12)  # valid; staged
            assert t.sync_every == 8 and t.interval == 2
        finally:
            t.shutdown()


# ------------------------------------------------------------ chaos phase


class TestChaosIntensity:
    def test_intensity_scales_fault_rates(self):
        from torchft_tpu.chaos import ChaosSchedule, EndpointChaos

        def faults_at(intensity):
            s = ChaosSchedule(seed=7, endpoints={
                "ring": EndpointChaos(reset_rate=0.2)},
                intensity=intensity)
            return sum(1 for _ in range(500)
                       if s.decide("ring", "send").fault is not None)

        assert faults_at(0.0) == 0
        lo, hi = faults_at(1.0), faults_at(3.0)
        assert 0 < lo < hi

    def test_set_intensity_live_and_draw_stream_pure(self):
        from torchft_tpu.chaos import ChaosSchedule, EndpointChaos

        cfg = {"ring": EndpointChaos(reset_rate=0.3, jitter_ms=0.0)}
        a = ChaosSchedule(seed=3, endpoints=cfg, intensity=0.0)
        b = ChaosSchedule(seed=3, endpoints=cfg, intensity=0.0)
        for i in range(100):
            if i == 50:
                a.set_intensity(1.0)
                b.set_intensity(1.0)
            a.decide("ring", "send")
            b.decide("ring", "send")
        assert a.trace() == b.trace()
        assert not any(d.fault for d in a.trace()[:50])
        assert any(d.fault for d in a.trace()[50:])

    def test_spec_parses_intensity(self):
        from torchft_tpu.chaos import parse_spec

        s = parse_spec("seed=5;intensity=0.5;ring:reset_rate=0.1")
        assert s.intensity() == 0.5

    def test_phased_chaos_walks_wall_clock(self):
        from torchft_tpu.chaos import ChaosSchedule
        from torchft_tpu.policy import PhasedChaos

        s = ChaosSchedule(seed=1)
        p = PhasedChaos(s, ((0.0, 0.0), (1000.0, 2.0)))
        assert p.total_seconds() == 1000.0
        assert p.tick() == 2.0
        assert s.intensity() == 2.0


# ------------------------------------------------------------- the soak


@pytest.mark.integration
@pytest.mark.slow
@pytest.mark.nightly
@conftest.requires_native()
class TestAdaptiveVsFixedSoak:
    """ISSUE 10's acceptance gate (ROADMAP item 3): under a seeded
    stable -> storm -> stable chaos phase schedule, the adaptive policy
    must beat EVERY fixed policy it can reach on protocol-committed
    batches/sec, with >= 1 escalation and >= 1 relaxation observed and
    a switch count bounded by the regime changes (no flapping) — and
    both groups bitwise lockstep at the end of every leg.

    Metric semantics (see bench_policy_soak): the gate counts
    ``Manager.batches_committed`` — what the commit protocol durably
    agreed on. diloco-16 loses that gate largely by construction
    (16x coarser commit granularity is exactly the trade the metric
    prices); sync-f32 and overlap-bf16 are the legs the storm-phase
    advantage must genuinely beat."""

    def test_adaptive_beats_every_fixed_policy(self):
        import jax

        import bench

        legs = {}
        for policy in ("adaptive", "sync-f32", "overlap-bf16",
                       "diloco-16"):
            legs[policy] = bench.bench_policy_soak(policy=policy)
            groups = list(legs[policy]["groups"].values())
            jax.tree_util.tree_map(
                lambda a, b: np.testing.assert_array_equal(a, b),
                groups[0]["params"], groups[1]["params"])

        ad = legs["adaptive"]
        for fixed in ("sync-f32", "overlap-bf16", "diloco-16"):
            assert ad["committed_batches_per_s"] \
                > legs[fixed]["committed_batches_per_s"], (
                    f"adaptive did not beat {fixed}: "
                    f"{ad['committed_batches_per_s']:.2f} vs "
                    f"{legs[fixed]['committed_batches_per_s']:.2f}")
            assert legs[fixed]["switches"] == 0  # fixed stayed fixed

        events = ad["events"]
        reasons = [str(e.get("reason", "")) for e in events
                   if e.get("event") == "policy_switch"]
        assert any("escalate" in r for r in reasons), events
        assert any("relax" in r for r in reasons), events
        # No flapping: bounded by regime changes x ladder walk, not by
        # fault count.
        assert ad["switches"] <= 12, events
