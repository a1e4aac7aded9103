"""RAM checkpoint tier tests (docs/design/memory_tier.md): the
in-memory v2 image codec (single-write-pass digests, disk-format
byte compatibility), the staged peer-push accept path (ranged PUTs,
crc-verified before acceptance, 422 on corruption), the bounded
RamCheckpointStore, the RamReplicator demotion pipeline (encode ->
RAM -> K peers -> local disk -> durable, AsyncCheckpointer
discipline: stall watchdog, sticky errors, fatal classification),
the chaos RAM fault band (peer-RAM loss, replication blackhole,
correlated K-peer death), and the Manager integration halves —
commit-coupled dispatch with the save_durable refusal classes,
healset-key peer discovery with tombstone filtering, the
RAM-preferring prejoin/cold-start rungs, and replication-set
collapse detection. All native-free (FakeStore control planes,
real sockets for the byte path); the RAM-on/off churn soak rides
the nightly tier in tests/test_churn.py."""

import os
import threading
import time

import numpy as np
import pytest

from mockplane import (FAKE_STORE_ADDR, FakeStore, boundary, make_manager,
                       mock_client, quorum_result)
from torchft_tpu import chaos as chaos_mod
from torchft_tpu import checkpoint_io as cio
from torchft_tpu import ram_ckpt
from torchft_tpu.chaos import ChaosSchedule, EndpointChaos
from torchft_tpu.checkpoint_io import CheckpointCorruptError
from torchft_tpu.checkpointing import CheckpointServer
from torchft_tpu.ram_ckpt import (RamCheckpointStore, RamReplicator,
                                  _Stage, encode_image, load_image,
                                  peer_steps, push_image, verify_image)

pytestmark = pytest.mark.ramckpt


def user_state(val=1.0):
    return {
        "params": {"w": np.full((16, 4), val, np.float32),
                   "b": np.zeros(8, np.float32)},
        "opt": [np.ones(3, np.float32), np.int64(4)],
    }


def mgr_state(step):
    return {"step": step, "batches_committed": step * 2}


def make_image(step=1, val=1.0):
    return encode_image(user_state(val), mgr_state(step),
                        meta={"committed": True, "replica_id": "g0"})


def tree_equal(a, b):
    import jax

    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


@pytest.fixture
def peer():
    """A peer host: real CheckpointServer + attached RAM store."""
    store = RamCheckpointStore()
    srv = CheckpointServer(lambda: {"user": {}, "torchft": {}})
    srv.attach_ram_store(store)
    yield srv, store
    srv.shutdown()


@pytest.fixture(autouse=True)
def chaos_reset():
    chaos_mod.reset()
    yield
    chaos_mod.reset()


# ------------------------------------------------------------ image codec


class TestImageCodec:
    def test_round_trip(self):
        img = make_image(step=7, val=3.5)
        assert img.step == 7
        assert img.nbytes == len(img.data) > 0
        user, mgr = load_image(img.data, target=user_state(0.0),
                               device_put=False)
        assert tree_equal(user, user_state(3.5))
        assert mgr["step"] == 7

    def test_verify_rejects_flipped_byte(self):
        img = make_image()
        data = bytearray(img.data)
        data[len(data) // 2] ^= 0xFF
        with pytest.raises(CheckpointCorruptError):
            verify_image(bytes(data))

    def test_image_is_disk_format(self, tmp_path):
        """The demotion invariant: an image written verbatim as
        {prefix}{step} IS a durable v2 checkpoint — recover() and
        load() treat it exactly like a cadence save's file."""
        img = make_image(step=5, val=2.0)
        path = str(tmp_path / "ckpt_5")
        with open(path, "wb") as f:
            f.write(img.data)
        assert cio.recover(str(tmp_path)) == path
        user, mgr = cio.load(path, target=user_state(0.0))
        assert tree_equal(user, user_state(2.0))
        assert mgr["step"] == 5

    def test_transfer_manifest_spelling(self):
        mf = make_image(step=3).transfer_manifest()
        assert mf["format"] == ram_ckpt.TRANSFER_MANIFEST_FORMAT
        assert mf["step"] == 3
        assert mf["leaves"]


# ------------------------------------------------------- staged assembly


class TestStage:
    def test_out_of_order_chunks_complete(self):
        data = bytes(range(256))
        st = _Stage(len(data), "peer")
        st.write(128, data[128:])
        assert not st.complete()
        st.write(0, data[:128])
        assert st.complete()
        assert bytes(st.buf) == data

    def test_overlap_and_repush_idempotent(self):
        data = b"x" * 100
        st = _Stage(100, "peer")
        st.write(0, data[:60])
        st.write(40, data[40:])  # overlaps [40,60)
        assert st.complete()
        st.write(0, data[:10])  # re-push of a done range
        assert st.complete()


# -------------------------------------------------------------- the store


class TestRamCheckpointStore:
    def test_put_get_latest_eviction(self):
        s = RamCheckpointStore(keep=2)
        for step in (1, 2, 3):
            s.put(make_image(step=step))
        assert s.steps() == [2, 3]
        assert s.latest().step == 3
        assert s.get(1) is None
        m = s.metrics()
        assert m["ram_ckpt_images"] == 2.0
        assert m["ram_ckpt_evictions_total"] == 1.0

    def test_put_bytes_verifies(self):
        s = RamCheckpointStore()
        img = make_image(step=4)
        data = bytearray(img.data)
        data[-20] ^= 0x01
        with pytest.raises(CheckpointCorruptError):
            s.put_bytes(bytes(data))
        assert s.steps() == []
        assert s.metrics()["ram_ckpt_rejects_total"] == 1.0
        s.put_bytes(img.data, origin="peer")
        assert s.steps() == [4]

    def test_stage_write_assembles(self):
        s = RamCheckpointStore()
        img = make_image(step=9)
        mid = len(img.data) // 2
        done = s.stage_write(9, 0, img.data[:mid], len(img.data))
        assert not done
        assert s.get(9) is None  # partial is never servable
        done = s.stage_write(9, mid, img.data[mid:], len(img.data))
        assert done
        assert s.get(9).step == 9


# ------------------------------------------------------------- HTTP path


class TestHttpPath:
    def test_push_then_heal_bitwise(self, peer):
        srv, store = peer
        img = make_image(step=6, val=4.25)
        pushed = push_image(srv.ram_address(), img, chunk_bytes=512)
        assert pushed == img.nbytes
        assert store.steps() == [6]
        # The striped digest-verified healer runs UNCHANGED against
        # the RAM tier — the bitwise convergence oracle.
        state = CheckpointServer.load_from_address(
            f"{srv.ram_address()}/ramckpt/6",
            {"user": user_state(0.0), "torchft": mgr_state(0)})
        assert tree_equal(state["user"], user_state(4.25))
        assert state["torchft"]["step"] == 6

    def test_corrupt_push_rejected_422(self, peer):
        srv, store = peer
        img = make_image(step=2)
        data = bytearray(img.data)
        data[len(data) - 30] ^= 0xFF
        img.data = bytes(data)
        with pytest.raises(CheckpointCorruptError):
            push_image(srv.ram_address(), img)
        assert store.steps() == []
        assert store.metrics()["ram_ckpt_rejects_total"] == 1.0

    def test_peer_steps_probe(self, peer):
        srv, store = peer
        assert peer_steps(srv.ram_address()) == []
        store.put(make_image(step=3))
        store.put(make_image(step=5))
        assert peer_steps(srv.ram_address()) == [3, 5]
        assert peer_steps("http://127.0.0.1:9") == []  # dead peer

    def test_auth_gate(self):
        store = RamCheckpointStore()
        srv = CheckpointServer(lambda: {}, auth_token="sekrit")
        srv.attach_ram_store(store)
        try:
            with pytest.raises(OSError):
                push_image(srv.ram_address(), make_image(step=1))
            assert store.steps() == []
            push_image(srv.ram_address(), make_image(step=1),
                       auth_token="sekrit")
            assert store.steps() == [1]
        finally:
            srv.shutdown()


# ----------------------------------------------------------- replicator


class TestRamReplicator:
    def test_pipeline_k_peers_and_demotion(self, peer, tmp_path):
        srv, pstore = peer
        local = RamCheckpointStore()
        demote = str(tmp_path / "local")
        durable = str(tmp_path / "durable")
        os.makedirs(demote)
        os.makedirs(durable)
        rep = RamReplicator(local, peers_fn=lambda: [srv.ram_address()],
                            k=1, demote_dir=demote, durable_dir=durable)
        fut = rep.replicate_image_async(make_image(step=8, val=2.0))
        assert fut.result(timeout=30) == 1
        rep.wait()
        assert local.steps() == [8]
        assert pstore.steps() == [8]
        # Both demotion rungs hold loadable v2 files.
        for d in (demote, durable):
            user, mgr = cio.load(os.path.join(d, "ckpt_8"),
                                 target=user_state(0.0))
            assert mgr["step"] == 8
        m = rep.metrics()
        assert m["ram_ckpt_peers"] == 1.0
        assert m["ram_ckpt_replications_total"] == 1.0
        assert m["ram_ckpt_bytes_replicated_total"] > 0
        assert m["demote_stage_ms_total"] > 0

    def test_dead_peer_skipped(self, peer):
        srv, pstore = peer
        rep = RamReplicator(
            RamCheckpointStore(),
            peers_fn=lambda: ["http://127.0.0.1:9", srv.ram_address()],
            k=1, push_timeout_sec=2.0)
        assert rep.replicate_image_async(
            make_image(step=1)).result(timeout=30) == 1
        assert pstore.steps() == [1]
        m = rep.metrics()
        assert m["ram_ckpt_push_failures_total"] >= 1.0
        assert m["ram_ckpt_peers"] == 1.0

    def test_zero_accepts_is_not_an_error(self):
        rep = RamReplicator(RamCheckpointStore(),
                            peers_fn=lambda: [], k=2)
        assert rep.replicate_image_async(
            make_image(step=1)).result(timeout=30) == 0
        rep.wait()  # no sticky error: local rung still landed
        assert rep.metrics()["ram_ckpt_peers"] == 0.0

    def test_snapshot_encode_path(self, peer):
        srv, pstore = peer
        rep = RamReplicator(RamCheckpointStore(),
                            peers_fn=lambda: [srv.ram_address()], k=1)
        fut = rep.replicate_async(user_state(7.0), mgr_state(11),
                                  meta={"committed": True})
        assert fut.result(timeout=30) == 1
        assert pstore.get(11) is not None
        assert rep.metrics()["demote_encode_ms"] > 0

    def test_demotion_error_is_sticky(self, tmp_path):
        # demote_dir is an existing FILE: makedirs/rename both fail.
        clash = str(tmp_path / "clash")
        with open(clash, "w") as f:
            f.write("x")
        rep = RamReplicator(RamCheckpointStore(), peers_fn=lambda: [],
                            k=0, demote_dir=clash)
        fut = rep.replicate_image_async(make_image(step=1))
        with pytest.raises(Exception):
            fut.result(timeout=30)
        with pytest.raises(RuntimeError):
            rep.wait()  # latched error surfaces exactly once
        rep.wait()
        assert rep.metrics()["ram_demote_errors"] == 1.0
        assert "Error" in (rep.last_error() or "")

    def test_stall_watchdog_abandons(self):
        release = threading.Event()

        def stuck_peers():
            release.wait(10)
            return []

        rep = RamReplicator(RamCheckpointStore(), peers_fn=stuck_peers,
                            k=1, stall_timeout_sec=0.3)
        rep.replicate_image_async(make_image(step=1))
        t0 = time.monotonic()
        with pytest.raises(RuntimeError) as ei:
            rep.wait()
        release.set()
        assert time.monotonic() - t0 < 5
        assert isinstance(ei.value.__cause__, cio.CheckpointStallError)
        assert rep.metrics()["ram_demote_stalls"] == 1.0


# ------------------------------------------------------------ chaos band


class TestRamChaos:
    def test_rate_zero_draws_no_ram_faults(self):
        sched = ChaosSchedule(seed=3, endpoints={
            "ram": EndpointChaos()})
        for _ in range(200):
            d = sched.decide("ram:h:1", "push")
            assert d is None or d.fault is None

    def test_ram_loss_drops_stored_image(self):
        chaos_mod.install(ChaosSchedule(seed=1, endpoints={
            "ram": EndpointChaos(ram_loss_rate=1.0)}))
        try:
            s = RamCheckpointStore(chaos_scope="ram:h:1")
            s.put(make_image(step=4))
            assert s.get(4) is None  # host reclaimed the RAM
            assert s.metrics()["ram_ckpt_losses_total"] >= 1.0
        finally:
            chaos_mod.uninstall()

    def test_blackhole_fails_push(self, peer):
        srv, pstore = peer
        chaos_mod.install(ChaosSchedule(seed=2, endpoints={
            "ram": EndpointChaos(ram_blackhole_rate=1.0,
                                 blackhole_ms=10)}))
        try:
            with pytest.raises(OSError):
                push_image(srv.ram_address(), make_image(step=1))
            assert pstore.steps() == []
        finally:
            chaos_mod.uninstall()

    def test_correlated_peer_death_latches(self, peer):
        """Kill latch = correlated K-peer death: every peer in the
        replication set dies, pushes fail, accepts drop to zero — and
        a reborn server at the same netloc clears the latch."""
        srv, pstore = peer
        sched = ChaosSchedule(seed=0, endpoints={
            "ram": EndpointChaos()})
        chaos_mod.install(sched)
        try:
            import urllib.parse

            netloc = urllib.parse.urlsplit(srv.ram_address()).netloc
            sched.kill_endpoint(f"ram:{netloc}")
            rep = RamReplicator(
                RamCheckpointStore(),
                peers_fn=lambda: [srv.ram_address()], k=1)
            assert rep.replicate_image_async(
                make_image(step=1)).result(timeout=30) == 0
            assert rep.metrics()["ram_ckpt_peers"] == 0.0
            sched.revive_endpoint(f"ram:{netloc}")
            assert rep.replicate_image_async(
                make_image(step=2)).result(timeout=30) == 1
        finally:
            chaos_mod.uninstall()


# ----------------------------------------------------- Manager coupling


def ram_manager(peers=1, state=None, **kw):
    client = mock_client(quorum_result(store_address=FAKE_STORE_ADDR))
    st = (state if state is not None
          else {"w": np.arange(8, dtype=np.float32)})
    m = make_manager(client, use_async_quorum=False, min_replica_size=1,
                     load_state_dict=lambda s: st.update(s),
                     state_dict=lambda: st,
                     ram_ckpt_peers=peers, store=FakeStore(), **kw)
    return m, client, st


def wire_peer(m, srv, rank=1, step=1):
    fs = m._healset_store[1]
    fs.set(f"torchft/healset/{rank}", f"{step}:{srv.address()}".encode())
    return fs


class TestManagerRamTier:
    def test_ctor_and_env_arming(self, monkeypatch):
        m, _, _ = ram_manager(peers=2)
        assert m.ram_tier_enabled()
        m.shutdown()
        monkeypatch.setenv("TORCHFT_RAM_CKPT_PEERS", "1")
        m2, _, _ = ram_manager(peers=None)
        assert m2.ram_tier_enabled()
        m2.shutdown()
        monkeypatch.delenv("TORCHFT_RAM_CKPT_PEERS")
        m3, _, _ = ram_manager(peers=None)
        assert not m3.ram_tier_enabled()
        m3.shutdown()

    def test_step_boundary_replicates_to_discovered_peer(self, peer):
        srv, pstore = peer
        m, _, _ = ram_manager(peers=1)
        wire_peer(m, srv)
        try:
            for _ in range(3):
                assert boundary(m)
            m._ram.replicator.wait()
            assert pstore.steps()  # the commit images crossed the wire
            mx = m.metrics()
            assert mx["ram_ckpt_peers"] == 1.0
            assert mx["ram_ckpt_bytes_replicated_total"] > 0
            assert mx["ram_replicate_skipped"] == 0.0
        finally:
            m.shutdown()

    def test_tombstoned_peer_never_a_push_target(self, peer):
        srv, _ = peer
        m, _, _ = ram_manager(peers=1)
        fs = wire_peer(m, srv)
        fs.set("torchft/healset/1", b"-1:")  # withdrawn (PR 14)
        try:
            assert boundary(m)
            assert m._ram_peer_bases() == []
        finally:
            m.shutdown()

    def test_replication_set_collapse_dumps_once(self, peer):
        srv, _ = peer
        m, _, _ = ram_manager(peers=1)
        wire_peer(m, srv)
        try:
            assert boundary(m)
            assert boundary(m)  # first boundary with a discovered peer
            m._ram.replicator.wait()
            assert m.metrics()["ram_ckpt_peers"] == 1.0
            srv.shutdown()  # the whole replication set dies
            for _ in range(4):
                assert boundary(m)
                m._ram.replicator.wait()
            mx = m.metrics()
            assert mx["ram_ckpt_peers"] == 0.0
            assert mx["ram_replica_collapses_total"] == 1.0  # one-shot
            assert any(e["event"] == "ram_replica_collapse"
                       for e in m.history())
        finally:
            m.shutdown()

    def test_cold_start_prefers_ram_rung(self, peer, tmp_path):
        srv, pstore = peer
        # Disk rung: a committed step-2 file; RAM rung: step 5.
        cio.save(str(tmp_path / "ckpt_2"), user_state(1.0),
                 mgr_state(2), meta={"committed": True})
        pstore.put(encode_image({"w": np.full(8, 9.0, np.float32)},
                                {"step": 5, "batches_committed": 10},
                                meta={"committed": True}))
        st = {"w": np.zeros(8, np.float32)}
        m, _, _ = ram_manager(peers=0, state=st)
        try:
            src = m.cold_start(str(tmp_path),
                               ram_peers=[srv.ram_address()])
            assert src.endswith("/ramckpt/5")
            assert np.array_equal(st["w"], np.full(8, 9.0, np.float32))
            assert m.current_step() == 5
            assert m.metrics()["ram_ckpt_heals_total"] == 1.0
        finally:
            m.shutdown()

    def test_cold_start_falls_back_to_disk(self, tmp_path):
        cio.save(str(tmp_path / "ckpt_3"),
                 {"w": np.full(8, 3.0, np.float32)}, mgr_state(3),
                 meta={"committed": True})
        st = {"w": np.zeros(8, np.float32)}
        m, _, _ = ram_manager(peers=0, state=st)
        try:
            src = m.cold_start(str(tmp_path),
                               ram_peers=["http://127.0.0.1:9"])
            assert src == str(tmp_path / "ckpt_3")  # dead peers -> disk
            assert np.array_equal(st["w"], np.full(8, 3.0, np.float32))
            assert m.metrics()["ram_ckpt_heals_total"] == 0.0
        finally:
            m.shutdown()

    def test_prejoin_heal_uses_ram_rung(self, peer):
        srv, pstore = peer
        fleet_step = 4
        pstore.put(encode_image({"w": np.full(8, 2.5, np.float32)},
                                {"step": fleet_step,
                                 "batches_committed": 8},
                                meta={"committed": True}))
        st = {"w": np.zeros(8, np.float32)}
        m, _, _ = ram_manager(peers=1, state=st)
        try:
            ok = m.prejoin_heal(
                fleet=lambda: {"members": [
                    {"step": fleet_step, "address": "m1:1"}]},
                resolve=lambda a: srv.address())
            assert ok
            assert np.array_equal(st["w"], np.full(8, 2.5, np.float32))
            mx = m.metrics()
            assert mx["prejoin_heals_total"] == 1.0
            assert mx["ram_ckpt_heals_total"] == 1.0
        finally:
            m.shutdown()

    def test_drain_withdraws_ram_tier(self, peer):
        srv, _ = peer
        m, _, _ = ram_manager(peers=1)
        wire_peer(m, srv)
        try:
            assert boundary(m)
            assert m._ckpt_server.ram_address()
            m._withdraw_advertisements()
            # Detached: the local /ramckpt stops serving.
            assert peer_steps(m._ckpt_server.ram_address()) == []
        finally:
            m.shutdown()

    def test_metrics_expose_tier_counters(self):
        m, _, _ = ram_manager(peers=1)
        try:
            mx = m.metrics()
            for key in ("ram_ckpt_heals_total", "ram_replicate_skipped",
                        "ram_replicate_errors_total",
                        "ram_replica_collapses_total", "ram_ckpt_peers",
                        "ram_ckpt_bytes_replicated_total",
                        "demote_stage_ms_total", "ram_ckpt_images",
                        "ram_ckpt_accepts_total"):
                assert key in mx, key
        finally:
            m.shutdown()


class TestRecoveryTiersBench:
    """ISSUE-16 acceptance, at tiny scale: bench_recovery_tiers must
    show the RAM rung healing >= 2x faster than the disk-only rung
    under a rate-capped disk, ending bitwise identical on both legs."""

    def test_ram_rung_beats_rate_capped_disk(self):
        import bench

        row = bench.bench_recovery_tiers(payload_mb=8.0,
                                         disk_mb_s=32.0,
                                         nic_mb_s=250.0)
        assert row["bitwise_identical"]
        assert row["ram_speedup"] >= 2.0, row
        assert row["disk_wall_s"] > row["ram_wall_s"]
