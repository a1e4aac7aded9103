"""Tests for the ChaosNet fault injector (:mod:`torchft_tpu.chaos`):
schedule determinism, the ``TORCHFT_CHAOS`` spec grammar, socket/stream/
communicator wrappers, the chaos-hardened heal fetch — and the seeded
multi-group chaos soak (``slow``/``nightly``) asserting zero lost or
duplicated commits while every transport is being disrupted."""

import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu import chaos
from torchft_tpu.chaos import (ChaosCommunicator, ChaosSchedule, Decision,
                               EndpointChaos, parse_spec)
from torchft_tpu.communicator import CommunicatorError, DummyCommunicator
from torchft_tpu.retry import RetryPolicy, RetryStats

import conftest
from mockplane import (FAKE_STORE_ADDR, FakeStore, make_manager,
                       quorum_result)

requires_native = conftest.requires_native()


class TestSchedule:
    def test_same_seed_same_trace(self):
        eps = {"ring": EndpointChaos(reset_rate=0.3, short_rate=0.2,
                                     latency_ms=1, jitter_ms=2)}
        a, b = ChaosSchedule(seed=9, endpoints=eps), \
            ChaosSchedule(seed=9, endpoints=eps)
        da = [a.decide("ring", "send") for _ in range(100)]
        db = [b.decide("ring", "send") for _ in range(100)]
        assert da == db
        assert any(d.fault for d in da)  # at these rates faults fired

    def test_different_seed_different_trace(self):
        eps = {"ring": EndpointChaos(reset_rate=0.3, jitter_ms=5)}
        a = [ChaosSchedule(seed=1, endpoints=eps).decide("ring", "send")
             for _ in range(50)]
        b = [ChaosSchedule(seed=2, endpoints=eps).decide("ring", "send")
             for _ in range(50)]
        assert a != b

    def test_channels_are_independent_streams(self):
        """Decision n of a channel is a pure function of (seed, channel,
        n): interleaving another channel's draws must not perturb it —
        the property that makes multi-threaded traces replayable."""
        eps = {"ring": EndpointChaos(reset_rate=0.3),
               "store": EndpointChaos(reset_rate=0.3)}
        solo = ChaosSchedule(seed=5, endpoints=eps)
        ring_solo = [solo.decide("ring", "send") for _ in range(40)]
        mixed = ChaosSchedule(seed=5, endpoints=eps)
        ring_mixed = []
        for i in range(40):
            mixed.decide("store", "get")  # interleaved foreign draws
            ring_mixed.append(mixed.decide("ring", "send"))
        assert ring_solo == ring_mixed

    def test_endpoint_fallback(self):
        s = ChaosSchedule(seed=0, endpoints={
            "ring": EndpointChaos(latency_ms=5),
            "*": EndpointChaos(latency_ms=1)})
        assert s.config_for("ring:3").latency_ms == 5
        assert s.config_for("store").latency_ms == 1
        s2 = ChaosSchedule(seed=0, endpoints={"ring": EndpointChaos()})
        assert s2.config_for("heal") is None
        assert s2.decide("heal", "fetch") is None

    def test_max_faults_cap(self):
        s = ChaosSchedule(seed=3, endpoints={
            "ring": EndpointChaos(reset_rate=1.0, max_faults=2)})
        faults = [s.decide("ring", "send").fault for _ in range(10)]
        assert faults[:2] == ["reset", "reset"]
        assert all(f is None for f in faults[2:])

    def test_trace_replay_reproduces(self):
        """The acceptance property: replaying a recorded per-channel op
        sequence through a fresh schedule with the same seed reproduces
        the identical injection trace."""
        eps = {"ring": EndpointChaos(reset_rate=0.2, short_rate=0.1,
                                     jitter_ms=3),
               "store": EndpointChaos(reset_rate=0.3)}
        s = ChaosSchedule(seed=11, endpoints=eps)
        for i in range(30):
            s.decide("ring", "send" if i % 2 else "recv")
            if i % 3 == 0:
                s.decide("store", "get")
        trace = s.trace()
        replay = ChaosSchedule(seed=11, endpoints=eps)
        for d in trace:
            replay.decide(d.endpoint, d.op)
        assert replay.trace() == trace


class TestSpecGrammar:
    def test_full_spec(self):
        s = parse_spec("seed=42;ring:reset_rate=0.02,latency_ms=5;"
                       "store:blackhole_rate=0.01,blackhole_ms=100;"
                       "*:jitter_ms=2;manager:max_faults=7")
        assert s.seed == 42
        assert s.endpoints["ring"].reset_rate == 0.02
        assert s.endpoints["ring"].latency_ms == 5
        assert s.endpoints["store"].blackhole_ms == 100
        assert s.endpoints["*"].jitter_ms == 2
        assert s.endpoints["manager"].max_faults == 7

    def test_empty_clauses_tolerated(self):
        s = parse_spec("seed=1;;ring:latency_ms=1;")
        assert s.seed == 1 and "ring" in s.endpoints

    @pytest.mark.parametrize("bad", [
        "ring",                       # no colon
        "ring:bogus_field=1",         # unknown field
        "ring:latency_ms",            # no value
    ])
    def test_bad_specs_raise(self, bad):
        with pytest.raises(ValueError):
            parse_spec(bad)

    def test_env_activation(self, monkeypatch):
        chaos.reset()  # re-arm env parsing (uninstall is sticky)
        monkeypatch.setenv("TORCHFT_CHAOS", "seed=5;ring:latency_ms=1")
        try:
            s = chaos.active()
            assert s is not None and s.seed == 5
            # parsed once, then cached
            assert chaos.active() is s
            # uninstall is STICKY against the env: the spec must NOT
            # silently re-arm on the next transport op (drain boundary).
            chaos.uninstall()
            assert chaos.active() is None
        finally:
            chaos.reset()

    def test_inactive_is_none(self, monkeypatch):
        chaos.reset()
        monkeypatch.delenv("TORCHFT_CHAOS", raising=False)
        try:
            assert chaos.active() is None
            sock = socket.socket()
            try:
                assert chaos.wrap_socket(sock, "ring") is sock
            finally:
                sock.close()
        finally:
            chaos.uninstall()


def _socketpair_with_chaos(schedule):
    a, b = socket.socketpair()
    a.settimeout(5)
    b.settimeout(5)
    return chaos.wrap_socket(a, "ring", schedule), b


class TestChaosSocket:
    def test_passthrough_when_clean(self):
        s = ChaosSchedule(seed=0, endpoints={"ring": EndpointChaos()})
        wrapped, peer = _socketpair_with_chaos(s)
        try:
            wrapped.sendall(b"hello")
            assert peer.recv(5) == b"hello"
            peer.sendall(b"world")
            buf = bytearray(5)
            assert wrapped.recv_into(memoryview(buf)) == 5
            assert bytes(buf) == b"world"
        finally:
            wrapped.close()
            peer.close()

    def test_reset_closes_both_ways(self):
        s = ChaosSchedule(seed=0, endpoints={
            "ring": EndpointChaos(reset_rate=1.0, max_faults=1)})
        wrapped, peer = _socketpair_with_chaos(s)
        try:
            with pytest.raises(ConnectionResetError, match="chaos"):
                wrapped.sendall(b"data")
            # the real socket was aborted, so the peer observes EOF/reset
            assert peer.recv(4) == b""
        finally:
            peer.close()

    def test_short_write_transfers_partial_then_resets(self):
        s = ChaosSchedule(seed=0, endpoints={
            "ring": EndpointChaos(short_rate=1.0, max_faults=1)})
        wrapped, peer = _socketpair_with_chaos(s)
        try:
            payload = b"x" * 1000
            with pytest.raises(ConnectionResetError, match="short write"):
                wrapped.sendall(payload)
            got = b""
            while True:
                part = peer.recv(4096)
                if not part:
                    break
                got += part
            assert 0 < len(got) < len(payload)  # genuinely partial
        finally:
            peer.close()

    def test_short_read_raises_after_partial_fill(self):
        s = ChaosSchedule(seed=0, endpoints={
            "ring": EndpointChaos(short_rate=1.0, max_faults=1)})
        wrapped, peer = _socketpair_with_chaos(s)
        try:
            peer.sendall(b"y" * 100)
            buf = bytearray(100)
            with pytest.raises(ConnectionResetError, match="short read"):
                wrapped.recv_into(memoryview(buf))
        finally:
            peer.close()

    def test_latency_delays_io(self):
        s = ChaosSchedule(seed=0, endpoints={
            "ring": EndpointChaos(latency_ms=30)})
        wrapped, peer = _socketpair_with_chaos(s)
        try:
            t0 = time.perf_counter()
            wrapped.sendall(b"z")
            assert (time.perf_counter() - t0) >= 0.025
            assert peer.recv(1) == b"z"
        finally:
            wrapped.close()
            peer.close()


class TestRingSegmentShortRead:
    """A short read inside the exact ring's per-segment fold (the path
    the bench-smoke chaos tier peppers): the op fails, the source it
    folded FROM is untouched, read-only or not, and the kept accumulator
    it folded INTO is neither lent nor kept."""

    @pytest.mark.parametrize("writable", [False, True],
                             ids=["readonly", "writable"])
    def test_short_read_mid_chunk_spares_the_source(self, writable):
        from torchft_tpu.backends.host import HostCommunicator, _Ring

        f32 = np.dtype(np.float32)
        sched = ChaosSchedule(seed=0, intensity=0.0, endpoints={
            "ring": EndpointChaos(short_rate=1.0, max_faults=1)})
        pairs = [socket.socketpair() for _ in range(2)]
        for a, b in pairs:
            a.settimeout(10)
            b.settimeout(10)
        rings = [
            _Ring(pairs[0][0], chaos.wrap_socket(pairs[1][1], "ring", sched),
                  socket.socket()),
            _Ring(pairs[1][0], pairs[0][1], socket.socket())]
        comms = [HostCommunicator(timeout_sec=10) for _ in range(2)]
        for r, c in enumerate(comms):
            c._rank, c._world = r, 2

        def grad(step, rank):
            return np.random.default_rng([step, rank]).normal(
                size=300_001).astype(np.float32)

        srcs = [grad(1, r) for r in range(2)]
        srcs[0].flags.writeable = writable

        def peer():
            try:
                res = comms[1]._do_allreduce_wire(
                    rings[1], [grad(0, 1)], [f32], "sum")
                comms[1].release_wire_buffers(res)
                comms[1]._do_allreduce_wire(rings[1], [srcs[1]], [f32],
                                            "sum")
            except Exception:  # noqa: BLE001 — rank 0's ring is reset
                pass

        t = threading.Thread(target=peer)
        t.start()
        c = comms[0]
        try:
            # A clean step leaves one accumulator kept.
            res = c._do_allreduce_wire(rings[0], [grad(0, 0)], [f32], "sum")
            assert res[0].tobytes() == (grad(0, 0) + grad(0, 1)).tobytes()
            c.release_wire_buffers(res)
            del res
            # The storm starts after the op's handshake: the first
            # segment of the first chunk is the read that comes short.
            preamble = c._wire_preamble

            def then_storm(*a, **kw):
                out = preamble(*a, **kw)
                sched.set_intensity(1.0)
                return out

            c._wire_preamble = then_storm
            with pytest.raises(ConnectionResetError, match="short read"):
                c._do_allreduce_wire(rings[0], [srcs[0]], [f32], "sum")
            assert srcs[0].flags.writeable == writable
            assert srcs[0].tobytes() == grad(1, 0).tobytes()
            assert len(c._accum_lent) == 0
            assert not any(c._accum_free.values())
            assert c.accum_counters() == (0.0, 1.0, 1.0)
            # rank 0 receives through a ChaosSocket: the Python loop,
            # where the short read is injected (two steps of the clean
            # op, none of the torn one)
            assert c.ring_step_counters() == (0.0, 2.0)
        finally:
            for ring in rings:
                ring.close()
            t.join(timeout=20)
            for cc in comms:
                cc.shutdown()
        assert not t.is_alive()


class TestSplitLeafShortRead:
    """A short read on the ring while a split leaf is half through the
    pipeline (``manager._SLICE_BYTES`` patched small, two Managers over
    socketpair rings): the step aborts whole — the future resolves to
    the caller's own tree, no leaf of it half-averaged — the error
    latches and the vote is no."""

    @pytest.mark.parametrize("clean_ops", [1, 2, 5],
                             ids=["slice2of4", "slice3of4", "next_leaf"])
    def test_short_read_mid_leaf_aborts_the_step_whole(self, clean_ops,
                                                       monkeypatch):
        import jax
        import jax.numpy as jnp

        import test_shard
        import torchft_tpu.exchange as exchange_mod
        from torchft_tpu.backends.host import _Ring

        monkeypatch.setattr(exchange_mod, "_SLICE_BYTES", 1024)
        sched = ChaosSchedule(seed=0, intensity=0.0, endpoints={
            "ring": EndpointChaos(short_rate=1.0, max_faults=1)})

        def rings(world):
            pairs = [socket.socketpair() for _ in range(world)]
            return [
                _Ring(pairs[0][0],
                      chaos.wrap_socket(pairs[1][1], "ring", sched),
                      socket.socket()),
                _Ring(pairs[1][0], pairs[0][1], socket.socket())]

        monkeypatch.setattr(test_shard, "_make_test_rings", rings)

        def grads(rank):
            return {"a": jnp.asarray(np.random.default_rng(rank).normal(
                        size=(257, 3)).astype(np.float32)),      # 4 slices
                    "b": np.random.default_rng(10 + rank).normal(
                        size=(1000,)).astype(np.float32)}        # 4, host

        def body(m, rank):
            m.step()
            m.wait_quorum()
            if rank == 0:
                # The storm starts after the handshake of the op that
                # follows `clean_ops` clean ones: its first segment is
                # the read that comes short.
                comm, seen = m._comm, [0]
                preamble = comm._wire_preamble

                def then_storm(*a, **kw):
                    out = preamble(*a, **kw)
                    seen[0] += 1
                    if seen[0] == clean_ops + 1:
                        sched.set_intensity(1.0)
                    return out

                comm._wire_preamble = then_storm
            tree = grads(rank)
            got = m.allreduce(tree).result(timeout=60)
            err = m.errored()
            vote = m.should_commit()
            return tree, got, err, vote, m.metrics()

        out = test_shard._run_managers(
            2, body, {"allreduce_bucket_bytes": 256}, echo_vote=True)
        for rank, (tree, got, err, vote, mx) in enumerate(out):
            assert err is not None and vote is False
            # default=tree: the caller's own leaves, each one whole
            assert jax.tree_util.tree_structure(got) == \
                jax.tree_util.tree_structure(tree)
            for k in tree:
                assert got[k] is tree[k]
            assert mx["allreduce_count"] == 0
        assert "short read" in str(out[0][2])


class TestChaosCommunicator:
    def _scripted(self, fault, phase):
        class One(ChaosSchedule):
            def config_for(self, endpoint):
                return EndpointChaos()

            def decide(self, endpoint, op):
                return Decision(endpoint=endpoint, op=op, n=0,
                                delay_ms=0.0, fault=fault, phase=phase,
                                frac=0.5, blackhole_ms=0.0)

        return One(seed=0, endpoints={})

    def test_clean_forwarding(self):
        inner = DummyCommunicator()
        c = ChaosCommunicator(inner, ChaosSchedule(seed=0, endpoints={}))
        assert c.allreduce({"g": np.ones(2)}).result()["g"].sum() == 2
        assert inner.allreduce_count == 1
        assert c.size() == 1 and c.rank() == 0
        assert not c.wants_device_arrays

    def test_pre_fault_raises_sync(self):
        c = ChaosCommunicator(DummyCommunicator(),
                              self._scripted("reset", "pre"))
        with pytest.raises(CommunicatorError, match="chaos"):
            c.allreduce({"g": np.ones(2)})

    def test_post_fault_fails_future(self):
        c = ChaosCommunicator(DummyCommunicator(),
                              self._scripted("reset", "post"))
        fut = c.allreduce({"g": np.ones(2)})
        assert isinstance(fut.exception(), CommunicatorError)

    def test_fingerprint_and_shutdown_forward(self):
        inner = DummyCommunicator()
        c = ChaosCommunicator(inner, ChaosSchedule(seed=0, endpoints={}))
        c.set_allreduce_config_fingerprint("fp")
        assert inner.allreduce_config_fingerprint == "fp"
        c.configure("store:1/x", 0, 1)
        assert inner.configure_count == 1


class TestHealUnderChaos:
    """The heal transport end to end (pure Python, no native lib): a real
    CheckpointServer streams a pytree; chaos injects a mid-stream reset
    on the first fetch; the retry layer re-fetches and the restore
    succeeds."""

    def test_fetch_retries_mid_stream_reset(self):
        from torchft_tpu.checkpointing import CheckpointServer

        state = {"w": np.arange(64, dtype=np.float32),
                 "b": np.ones(8, dtype=np.float32)}
        srv = CheckpointServer(lambda: state, bind_host="127.0.0.1")
        srv.allow_checkpoint(1)
        fails = [2]  # first two read() calls of the body get faults

        class Script(ChaosSchedule):
            def config_for(self, endpoint):
                return EndpointChaos()

            def decide(self, endpoint, op):
                fault = None
                if op == "read" and fails[0] > 0:
                    fails[0] -= 1
                    fault = "reset"
                return Decision(endpoint=endpoint, op=op, n=0,
                                delay_ms=0.0, fault=fault, phase="pre",
                                frac=0.5, blackhole_ms=0.0)

        chaos.install(Script(seed=0, endpoints={}))
        try:
            stats = RetryStats()
            target = {"w": np.zeros(64, dtype=np.float32),
                      "b": np.zeros(8, dtype=np.float32)}
            out = CheckpointServer.load_from_address(
                srv.address(), target, device_put=False,
                retry_policy=RetryPolicy(max_attempts=4, base_delay_ms=1),
                retry_stats=stats)
            np.testing.assert_array_equal(out["w"], state["w"])
            np.testing.assert_array_equal(out["b"], state["b"])
            assert stats.snapshot()["retry_count"] == 2
        finally:
            chaos.uninstall()
            srv.shutdown()

    def test_fatal_refusal_does_not_retry(self):
        from torchft_tpu.checkpointing import CheckpointServer

        srv = CheckpointServer(lambda: {"w": np.ones(2)},
                               bind_host="127.0.0.1")
        srv.allow_checkpoint(3)
        try:
            stats = RetryStats()
            # Request a WRONG step: 400 "invalid checkpoint requested"
            # must surface immediately, not retry.
            bad = srv.address().rsplit("/", 1)[0] + "/99"
            with pytest.raises(Exception, match="[Ii]nvalid|400"):
                CheckpointServer.load_from_address(
                    bad, {"w": np.ones(2)}, device_put=False,
                    retry_policy=RetryPolicy(max_attempts=5,
                                             base_delay_ms=1),
                    retry_stats=stats)
            assert stats.snapshot()["retry_count"] == 0
        finally:
            srv.shutdown()


class TestHealOverlapUnderChaos:
    """The heal's stages run beside each other (docs/design/healing.md),
    shown on a stream that chaos slows and a placement that is slow, not
    with a timing threshold: every read of the body waits 30 ms and
    every placement 40 ms, so one after the other they would take their
    sum, and the stage clocks add up to more than the transfer's wall."""

    def test_stage_clocks_sum_to_more_than_the_wall(self, monkeypatch):
        from torchft_tpu import checkpointing
        from torchft_tpu.checkpointing import CheckpointServer

        rng = np.random.RandomState(1)
        state = {f"w{i:02d}": rng.rand(1 << 18).astype(np.float32)
                 for i in range(12)}

        class Slow(ChaosSchedule):
            def config_for(self, endpoint):
                return EndpointChaos()

            def decide(self, endpoint, op):
                return Decision(endpoint=endpoint, op=op, n=0,
                                delay_ms=30.0 if op == "read" else 0.0,
                                fault=None, phase="pre", frac=0.5,
                                blackhole_ms=0.0)

        real_put = checkpointing.device_put_like

        def slow_put(arr, tleaf, **kw):
            time.sleep(0.04)
            return real_put(arr, tleaf, **kw)

        monkeypatch.setattr(checkpointing, "device_put_like", slow_put)
        srv = CheckpointServer(lambda: state, bind_host="127.0.0.1")
        srv.allow_checkpoint(1)
        chaos.install(Slow(seed=0, endpoints={}))
        try:
            import jax.numpy as jnp

            target = {k: jnp.zeros(v.shape, v.dtype)
                      for k, v in state.items()}
            stats = {}
            t0 = time.perf_counter()
            out = CheckpointServer.load_from_address(
                srv.address(), target, stats=stats)
            wall_ms = (time.perf_counter() - t0) * 1e3
            for k, v in state.items():
                np.testing.assert_array_equal(np.asarray(out[k]), v)
            assert stats["recv_ms"] >= 12 * 30
            assert stats["place_ms"] >= 12 * 40
            assert stats["verify_ms"] > 0 and stats["manifest_ms"] > 0
            stream_ms = wall_ms - stats["manifest_ms"]
            busy = (stats["recv_ms"] + stats["verify_ms"]
                    + stats["place_ms"])
            assert busy > stream_ms, (busy, stream_ms, stats)
        finally:
            chaos.uninstall()
            srv.shutdown()


class TestDonorKill:
    """The donor-kill fault family: a killed endpoint hangs up its
    in-flight stream and refuses every later dial — the way a dead donor
    process behaves — deterministically (kill_after_bytes) or drawn from
    the seeded stream (kill_rate)."""

    def test_kill_rate_latches_endpoint_dead(self):
        sched = ChaosSchedule(
            seed=1, endpoints={"heal": EndpointChaos(kill_rate=1.0)})
        with pytest.raises(ConnectionResetError, match="died"):
            chaos.begin("heal:1.2.3.4:77", "dial", sched)
        assert sched.is_dead("heal:1.2.3.4:77")
        with pytest.raises(ConnectionRefusedError, match="refused"):
            chaos.begin("heal:1.2.3.4:77", "dial", sched)
        # a different donor has its own life
        assert not sched.is_dead("heal:5.6.7.8:99")
        sched.revive_endpoint("heal:1.2.3.4:77")
        assert not sched.is_dead("heal:1.2.3.4:77")

    def test_kill_after_bytes_hangs_up_mid_stream(self):
        import io

        sched = ChaosSchedule(
            seed=0,
            endpoints={"heal": EndpointChaos(kill_after_bytes=100)})
        reader = chaos.wrap_reader(io.BytesIO(bytes(300)), "heal:a:1",
                                   sched)
        got = b""
        with pytest.raises(ConnectionResetError, match="dead"):
            while True:
                part = reader.read(40)
                if not part:
                    break
                got += part
        # the packet crossing the threshold is still delivered; the NEXT
        # read hits the dead latch
        assert 100 <= len(got) <= 140
        assert sched.is_dead("heal:a:1")
        with pytest.raises(ConnectionRefusedError):
            chaos.begin("heal:a:1", "dial", sched)
        # an independent donor (own byte counter) still streams
        reader2 = chaos.wrap_reader(io.BytesIO(b"x" * 50), "heal:b:2",
                                    sched)
        assert reader2.read(50) == b"x" * 50

    def test_spec_parses_kill_fields(self):
        sched = parse_spec(
            "seed=3;heal:kill_rate=0.5,kill_after_bytes=1000000")
        cfg = sched.config_for("heal:any:1")
        assert cfg.kill_rate == 0.5
        assert cfg.kill_after_bytes == 1000000


class TestPoisonedRingRecovery:
    """A transient collective failure with UNCHANGED membership must not
    wedge the job: a latched CommunicatorError poisons the communicator
    and the next quorum round forces a rebuild onto the deterministic
    recovery prefix keyed by (quorum_id, max_step)."""

    def _make_manager(self, comm, client):
        # The fake store keeps the rendezvous prefix a non-empty address.
        return make_manager(
            client, comm, store=FakeStore(), state_dict=lambda: {},
            min_replica_size=1, use_async_quorum=False)

    def _quorum(self, qid, max_step):
        return quorum_result(store_address=FAKE_STORE_ADDR, quorum_id=qid,
                             max_step=max_step)

    def test_comm_error_forces_recovery_rendezvous(self):
        from unittest.mock import MagicMock

        class Recording(DummyCommunicator):
            def __init__(self):
                super().__init__()
                self.prefixes = []

            def configure(self, store_addr, rank, world_size):
                super().configure(store_addr, rank, world_size)
                self.prefixes.append(store_addr)

        comm = Recording()
        client = MagicMock()
        client.quorum.return_value = self._quorum(qid=7, max_step=3)
        client.should_commit.return_value = False
        m = self._make_manager(comm, client)
        try:
            m.step()
            assert comm.prefixes == [f"{FAKE_STORE_ADDR}/torchft/7/0"]
            # Transient ring failure: membership unchanged, ring dead.
            m.report_error(CommunicatorError("connection reset by peer"))
            assert not m.should_commit()
            m.step()  # same quorum id → recovery prefix, not a no-op
            assert comm.prefixes[-1] == f"{FAKE_STORE_ADDR}/torchft/7.r3/0"
            # Poison cleared by the successful rebuild: the next same-
            # quorum round reconfigures nothing.
            client.should_commit.return_value = True
            assert m.should_commit()
            m.step()
            assert len(comm.prefixes) == 2
        finally:
            m.shutdown()

    def test_non_comm_error_does_not_rebuild_ring(self):
        from unittest.mock import MagicMock

        comm = DummyCommunicator()
        client = MagicMock()
        client.quorum.return_value = self._quorum(qid=5, max_step=2)
        client.should_commit.return_value = False
        m = self._make_manager(comm, client)
        try:
            m.step()
            assert comm.configure_count == 1
            # A quorum/heal-class error must NOT force a lone rebuild —
            # peers know nothing about it and their ring is healthy.
            m.report_error(RuntimeError("heal fetch failed"))
            assert not m.should_commit()
            m.step()
            assert comm.configure_count == 1
        finally:
            m.shutdown()

    def test_failed_recovery_keeps_poison_set(self):
        from unittest.mock import MagicMock

        class FailsOnce(DummyCommunicator):
            def __init__(self):
                super().__init__()
                self.prefixes = []
                self.fail_next = False

            def configure(self, store_addr, rank, world_size):
                self.prefixes.append(store_addr)
                if self.fail_next:
                    self.fail_next = False
                    raise CommunicatorError("rendezvous timeout")
                super().configure(store_addr, rank, world_size)

        comm = FailsOnce()
        client = MagicMock()
        client.quorum.return_value = self._quorum(qid=9, max_step=4)
        client.should_commit.return_value = False
        m = self._make_manager(comm, client)
        try:
            m.step()
            m.report_error(CommunicatorError("connection reset by peer"))
            assert not m.should_commit()
            comm.fail_next = True  # peers not at the rendezvous yet
            with pytest.raises(CommunicatorError):
                m.step()          # sync mode surfaces the failed round
            m.step()              # retried: poison still set → try again
            assert comm.prefixes[-2:] == \
                [f"{FAKE_STORE_ADDR}/torchft/9.r4/0"] * 2
        finally:
            m.shutdown()


@requires_native
@pytest.mark.integration
@pytest.mark.slow
@pytest.mark.nightly
class TestChaosSoak:
    """The capstone: two replica groups run 20+ steps while a seeded
    schedule injects connection resets, latency/jitter, and short writes
    into EVERY transport — store, manager RPC, heal, host ring, and the
    allreduce path via the ChaosCommunicator shim. Oracles:

    * both groups finish all steps with bitwise-identical params;
    * zero lost or duplicated commits: no step is committed under two
      quorum ids, and ``batches_committed`` agrees across survivors;
    * faults actually fired on every targeted channel;
    * the same ``ChaosSchedule(seed)`` reproduces the identical
      injection trace when the recorded per-channel op sequence is
      replayed.
    """

    SEED = 1234

    def _schedule(self):
        # Hard-fault caps bound wall clock: every ring/allreduce fault
        # can cost one abort + a recovery rendezvous (up to ~timeout_sec
        # when a stalled peer must notice); manager/store faults are
        # cheap (absorbed by client retries in milliseconds).
        return ChaosSchedule(seed=self.SEED, endpoints={
            "ring": EndpointChaos(latency_ms=0.2, jitter_ms=1.0,
                                  reset_rate=0.01, short_rate=0.01,
                                  max_faults=4),
            "store": EndpointChaos(latency_ms=0.2, reset_rate=0.05,
                                   max_faults=6),
            "manager": EndpointChaos(jitter_ms=1.0, reset_rate=0.04,
                                     max_faults=8),
            "heal": EndpointChaos(reset_rate=0.2, max_faults=2),
            "allreduce": EndpointChaos(reset_rate=0.02, max_faults=2),
        })

    def test_soak_two_groups_no_lost_or_duplicated_commits(self):
        self._soak(overlap_steps=0)

    def test_soak_two_groups_overlap_mode(self):
        """The same seeded soak with the cross-step overlap engine
        (``overlap_steps=1``, docs/design/overlap.md): every fault now
        has a one-step-deferred commit in flight to corrupt, so the
        oracles additionally prove the deferred vote drops stale grads
        on every failure path — both groups still finish bitwise
        identical with zero lost or duplicated commits."""
        self._soak(overlap_steps=1)

    def test_soak_hier_leader_kill(self):
        """The hierarchical round (docs/design/hier_transport.md): 4
        groups as 2 simulated hosts x 2 co-located ranks run the same
        seeded chaos soak over the two-level ring, PLUS a hard leader
        kill mid-run (its star + leader-ring sockets dropped mid-op).
        A dead leader must latch a clean CommunicatorError and recover
        through the identical poison -> recovery-rendezvous ->
        re-election path as a flat ring reset: every group finishes
        every step, params bitwise identical, zero lost or duplicated
        commits."""
        results = self._soak(overlap_steps=0, n_groups=4,
                             hier_hosts=2, leader_kill_at=8)
        topos = [r.get("ring_topology", "") for r in results]
        assert any(t.startswith("hier:") for t in topos), topos

    def _soak(self, overlap_steps: int, n_groups: int = 2,
              hier_hosts=None, leader_kill_at=None):
        import jax
        import jax.numpy as jnp
        import optax

        from torchft_tpu import HostCommunicator, Lighthouse, Manager
        from torchft_tpu.models import MLP
        from torchft_tpu.parallel import FTTrainer

        # Chaotic phase through step `chaos_until`, then a clean drain to
        # `total_steps`: a fault landing exactly on the final step would
        # let one group commit it while the other exits with it aborted —
        # a legitimate at-most-one-step divergence the heal would repair
        # on the NEXT step, which never comes. The drain gives every
        # in-flight recovery (ring rebuild, heal catch-up) steps to
        # converge, so the end-state oracles are exact.
        total_steps = 24
        chaos_until = 18
        schedule = self._schedule()
        chaos.install(schedule)
        lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                        join_timeout_ms=1000, quorum_tick_ms=50)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 8)).astype(np.float32)
        y = (x.sum(axis=1) > 0).astype(np.int32)
        model = MLP(features=(16,), num_classes=2)

        def loss_fn(params, batch):
            logits = model.apply(params, batch["x"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"]).mean()

        progress = {}  # group -> latest step (read by the main thread)
        host_comms = {}  # group -> HostCommunicator (leader-kill hook)

        def make_host_comm(group: int) -> HostCommunicator:
            if hier_hosts:
                hc = HostCommunicator(
                    timeout_sec=15, hier=True,
                    host_id=f"soakh{group % hier_hosts}")
            else:
                hc = HostCommunicator(timeout_sec=15)
            host_comms[group] = hc
            return hc

        def run_group(group: int):
            params = model.init(jax.random.key(42), jnp.zeros((1, 8)))
            trainer = FTTrainer(
                loss_fn=loss_fn, tx=optax.sgd(0.05), params=params,
                manager_factory=lambda load, save: Manager(
                    # schedule=None: the shim reads chaos.active() per
                    # op, so the main thread's uninstall() at the drain
                    # boundary silences this path too.
                    comm=ChaosCommunicator(make_host_comm(group)),
                    load_state_dict=load, state_dict=save,
                    min_replica_size=1, replica_id=f"chaos{group}",
                    lighthouse_addr=lh.address(), rank=0, world_size=1,
                    timeout_ms=15_000, quorum_timeout_ms=15_000,
                    max_consecutive_failures=100,
                    overlap_steps=overlap_steps,
                ),
            )
            commits = []
            b = {"x": x[:16], "y": y[:16]}
            try:
                first = True
                while trainer.manager.current_step() < total_steps:
                    progress[group] = trainer.manager.current_step()
                    # Overlap mode settles the PREVIOUS step inside this
                    # call, so the (step, quorum, participants) triple a
                    # commit belongs to is the one in effect BEFORE
                    # step() advances (reading any of them after
                    # train_step would describe the NEXT step's quorum).
                    prev = (trainer.manager.current_step(),
                            trainer.manager.quorum_id(),
                            trainer.manager.num_participants())
                    _, committed = trainer.train_step(b)
                    if overlap_steps:
                        if committed and not first:
                            commits.append(prev)
                        first = False
                    elif committed:
                        commits.append(
                            (trainer.manager.current_step(),
                             trainer.manager.quorum_id(),
                             trainer.manager.num_participants()))
                # Overlap mode: settle the final in-flight step BEFORE
                # snapshotting params, or the oracle would compare
                # boundary states one update apart.
                final = trainer.flush()
                if overlap_steps and final:
                    commits.append(
                        (trainer.manager.current_step(),
                         trainer.manager.quorum_id(),
                         trainer.manager.num_participants()))
                return {
                    "params": jax.device_get(trainer.params),
                    "step": trainer.manager.current_step(),
                    "batches_committed":
                        trainer.manager.batches_committed(),
                    "commits": commits,
                    "metrics": trainer.manager.metrics(),
                    "ring_topology": trainer.manager.metrics_info()
                    .get("ring_topology", "flat"),
                }
            finally:
                trainer.shutdown()

        killed = [False]
        try:
            with ThreadPoolExecutor(max_workers=n_groups) as pool:
                futs = [pool.submit(run_group, g)
                        for g in range(n_groups)]
                # Drain boundary: once every group is past `chaos_until`,
                # stop injecting and let the tail converge cleanly.
                deadline = time.monotonic() + 480
                while not (len(progress) == n_groups and all(
                        s >= chaos_until for s in progress.values())):
                    if time.monotonic() > deadline:
                        break  # let result() surface the real failure
                    if any(f.done() and f.exception() for f in futs):
                        break
                    # The leader kill: once every group is past the
                    # kill step, drop one elected leader's hier sockets
                    # mid-flight — the next wire op on any survivor
                    # latches a CommunicatorError and the recovery
                    # rendezvous must rebuild + re-elect.
                    if (leader_kill_at is not None and not killed[0]
                            and len(progress) == n_groups
                            and all(s >= leader_kill_at
                                    for s in progress.values())):
                        for hc in host_comms.values():
                            topo = hc._hier
                            if topo is not None and topo.is_leader:
                                topo.close()
                                killed[0] = True
                                break
                    time.sleep(0.25)
                chaos.uninstall()
                results = [f.result(timeout=600) for f in futs]
        finally:
            chaos.uninstall()
            lh.shutdown()
        if leader_kill_at is not None:
            assert killed[0], "leader kill never fired"

        # Everyone finished every step under sustained disruption.
        assert all(r["step"] == total_steps for r in results), results
        # Zero duplicated commits: no step committed under two quorums.
        step_qids: dict = {}
        for r in results:
            for step, qid, _ in r["commits"]:
                step_qids.setdefault(step, set()).add(qid)
        split = {s: q for s, q in step_qids.items() if len(q) > 1}
        assert not split, f"steps committed under multiple quorums: {split}"
        # Zero lost commits: batches_committed consistent across
        # survivors, and params bitwise identical (a lost commit on one
        # side would diverge both).
        for r in results[1:]:
            assert (results[0]["batches_committed"]
                    == r["batches_committed"]), results
            jax.tree_util.tree_map(
                lambda a, b_: np.testing.assert_array_equal(a, b_),
                results[0]["params"], r["params"])

        # Chaos genuinely fired into the transports...
        trace = schedule.trace()
        channels_faulted = {d.endpoint.split(":", 1)[0]
                            for d in trace if d.fault is not None}
        assert {"store", "manager"} <= channels_faulted, channels_faulted
        # ...and the retry layer absorbed transient RPC faults (visible
        # in metrics rather than as training-loop crashes).
        total_retries = sum(r["metrics"]["retry_count"] for r in results)
        assert total_retries >= 1, [r["metrics"] for r in results]

        # Determinism: replaying the recorded per-channel op sequence
        # through a fresh ChaosSchedule(seed) reproduces the identical
        # injection trace.
        replay = self._schedule()
        for d in trace:
            replay.decide(d.endpoint, d.op)
        assert replay.trace() == trace
        return results


@pytest.mark.slow
@pytest.mark.nightly
@pytest.mark.heal_soak
class TestHealSoak:
    """Seeded chaos soak of repeated heals with donor churn
    (``scripts/test.sh heal-soak``; also rides the nightly tier): every
    round the primary donor is killed mid-stream at a deterministic byte
    offset while resets/short-reads pepper the heal channel. Every heal
    must complete with bitwise-identical state by FAILING OVER and
    RESUMING — the retry traffic must stay well under
    restart-from-byte-0 cost."""

    ROUNDS = 6

    def test_repeated_heals_with_donor_churn(self):
        import urllib.parse

        from torchft_tpu.checkpointing import CheckpointServer
        from torchft_tpu.serialization import plan_pytree

        total_resent = 0.0
        total_payload = 0.0
        for seed in range(self.ROUNDS):
            rng = np.random.RandomState(seed)
            state = {f"w{i}": rng.rand(2048).astype(np.float32)
                     for i in range(6)}
            donors_srv = [
                CheckpointServer(lambda s=state: s, bind_host="127.0.0.1")
                for _ in range(2)
            ]
            for srv in donors_srv:
                srv.allow_checkpoint(1)
            payload = plan_pytree(state).total_len
            netloc_a = urllib.parse.urlparse(
                donors_srv[0].address()).netloc
            kill_at = int(payload * (0.3 + 0.4 * rng.rand()))
            sched = ChaosSchedule(seed=seed, endpoints={
                "heal": EndpointChaos(reset_rate=0.02, short_rate=0.02),
                f"heal:{netloc_a}": EndpointChaos(
                    reset_rate=0.02, short_rate=0.02,
                    kill_after_bytes=kill_at),
            })
            chaos.install(sched)
            try:
                stats = {}
                out = CheckpointServer.load_from_address(
                    donors_srv[0].address(), state, device_put=False,
                    stats=stats,
                    retry_policy=RetryPolicy(max_attempts=8,
                                             base_delay_ms=1.0,
                                             jitter=0.0),
                    stall_timeout_sec=10,
                    donors=lambda i: donors_srv[1].address())
                for key, arr in state.items():
                    assert out[key].tobytes() == arr.tobytes(), (
                        f"round {seed}: leaf {key} not bitwise identical")
                assert stats["donor_failovers"] == 1, (seed, stats)
                assert stats["bytes_resumed"] < stats["payload_bytes"], (
                    seed, stats)
                total_resent += stats["bytes_resumed"]
                total_payload += stats["payload_bytes"]
            finally:
                chaos.uninstall()
                for srv in donors_srv:
                    srv.shutdown()
        # Across the soak, resume must beat restart-from-zero by a wide
        # margin: donors die mid-transfer every round, yet the re-sent
        # traffic stays under one payload's worth per round on average.
        assert total_resent < total_payload, (total_resent, total_payload)

    def test_striped_heal_rounds_with_donor_death(self):
        """Striped rounds (docs/design/sharded_update.md): every round
        the healer stripes one heal across 3 live donors and chaos kills
        one NON-manifest donor at a deterministic mid-stripe byte
        offset. The dead donor's remaining stripe must reassign to the
        survivors — committed leaves stay committed (bytes_resumed <
        payload), final state bitwise identical."""
        import random as _random
        import urllib.parse

        from torchft_tpu.checkpointing import CheckpointServer
        from torchft_tpu.serialization import plan_pytree

        total_resent = 0.0
        total_payload = 0.0
        for seed in range(self.ROUNDS):
            rng = np.random.RandomState(100 + seed)
            state = {f"w{i}": rng.rand(4096).astype(np.float32)
                     for i in range(9)}
            donors_srv = [
                CheckpointServer(lambda s=state: s, bind_host="127.0.0.1")
                for _ in range(3)
            ]
            for srv in donors_srv:
                srv.allow_checkpoint(1)
            addrs = [srv.address() for srv in donors_srv]
            payload = plan_pytree(state).total_len
            # Replicate the healer's seed-shuffle so the chaos kill lands
            # on a donor that is NOT serving the manifest (stripe[0]) —
            # the manifest donor dying is the failover path the legacy
            # soak above already covers.
            shuffled = list(dict.fromkeys(addrs))
            _random.Random(seed).shuffle(shuffled)
            victim = urllib.parse.urlparse(shuffled[1]).netloc
            kill_at = int((payload / 3) * (0.2 + 0.5 * rng.rand()))
            sched = ChaosSchedule(seed=seed, endpoints={
                f"heal:{victim}": EndpointChaos(
                    kill_after_bytes=kill_at),
            })
            chaos.install(sched)
            try:
                stats = {}
                out = CheckpointServer.load_from_address(
                    addrs[0], state, device_put=False, stats=stats,
                    retry_policy=RetryPolicy(max_attempts=8,
                                             base_delay_ms=1.0,
                                             jitter=0.0),
                    stall_timeout_sec=10,
                    donor_addrs=addrs, stripe_seed=seed)
                for key, arr in state.items():
                    assert out[key].tobytes() == arr.tobytes(), (
                        f"round {seed}: leaf {key} not bitwise identical")
                assert stats["stripe_donor_deaths"] >= 1, (seed, stats)
                assert stats["bytes_resumed"] < stats["payload_bytes"], (
                    seed, stats)
                total_resent += stats["bytes_resumed"]
                total_payload += stats["payload_bytes"]
            finally:
                chaos.uninstall()
                for srv in donors_srv:
                    srv.shutdown()
        # Only the dead donor's remaining stripe re-fetches: across the
        # soak the re-sent traffic must stay well under one full payload
        # per round (restart-from-zero would be >= ROUNDS * payload).
        assert total_resent < total_payload / 2, (
            total_resent, total_payload)
