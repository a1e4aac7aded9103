"""Cold-start resilience tests (ISSUE 4): the verified commit-coupled
durable checkpoint format, the recovery scan + quarantine, disk chaos
(torn writes / bit-flips / ENOSPC / stalled IO), the AsyncCheckpointer
stall watchdog, Manager commit coupling + cold start, and the 2-group
divergent-cold-start convergence acceptance (groups recovered from
different on-disk steps end bitwise identical via the existing heal
path). The seeded kill-all→recover soak rides ``scripts/test.sh
cold-start`` (markers ``cold_start`` + ``slow`` + ``nightly``)."""

import os
import time
from unittest.mock import MagicMock, patch

import jax.numpy as jnp
import numpy as np
import pytest

from mockplane import make_manager, quorum_result
from torchft_tpu import chaos as chaos_mod
from torchft_tpu import checkpoint_io as cio
from torchft_tpu.chaos import ChaosSchedule, EndpointChaos, parse_spec
from torchft_tpu.checkpoint_io import (
    AsyncCheckpointer,
    CheckpointCorruptError,
    CheckpointUnverifiableError,
)


def user_state(val=1.0):
    return {
        "params": {"w": jnp.full((8, 8), val), "b": jnp.zeros((4,))},
        "opt": [jnp.ones((2,)), np.int64(3)],
    }


def _flip_at(path, off):
    with open(path, "r+b") as f:
        f.seek(off)
        b = f.read(1)
        f.seek(off)
        f.write(bytes([b[0] ^ 0xFF]))


def _first_leaf_offset(path):
    """Absolute offset of the first array leaf's first payload byte."""
    with open(path, "rb") as f:
        _, mf, payload_start = cio._open_verified(f)
    return payload_start + int(mf["preamble_len"])


class TestDurableFormat:
    def test_head_records_provenance(self, tmp_path):
        path = str(tmp_path / "ckpt_7")
        cio.save(path, user_state(), {"step": 7, "batches_committed": 21},
                 meta={"quorum_id": 3, "replica_id": "g0",
                       "committed": True})
        head = cio.read_meta(path)
        assert head["format"] == cio.FORMAT
        assert head["step"] == 7
        assert head["batches_committed"] == 21
        assert head["quorum_id"] == 3
        assert head["replica_id"] == "g0"
        assert head["committed"] is True

    def test_verify_ok_and_load_round_trip(self, tmp_path):
        path = str(tmp_path / "ckpt_2")
        cio.save(path, user_state(2.5), {"step": 2,
                                         "batches_committed": 4})
        assert cio.verify(path)["step"] == 2
        user, mgr = cio.load(path, target=user_state(), device_put=False)
        np.testing.assert_array_equal(user["params"]["w"],
                                      np.full((8, 8), 2.5))
        assert mgr == {"step": 2, "batches_committed": 4}

    def test_legacy_is_unverifiable_but_loads(self, tmp_path):
        from torchft_tpu.serialization import save_pytree

        path = str(tmp_path / "ckpt_3")
        with open(path, "wb") as f:
            f.write(save_pytree(
                {"user": user_state(), "torchft": {"step": 3,
                                                   "batches_committed": 3}}))
        with pytest.raises(CheckpointUnverifiableError):
            cio.verify(path)
        _, mgr = cio.load(path, target=user_state(), device_put=False)
        assert mgr["step"] == 3


class TestVerifiedLoad:
    def test_payload_flip_detected_before_device_put(self, tmp_path,
                                                     monkeypatch):
        """A corrupt leaf is caught by its digest BEFORE any device_put:
        the acceptance invariant that unverified bytes never reach the
        device."""
        path = str(tmp_path / "ckpt_1")
        cio.save(path, user_state(), {"step": 1, "batches_committed": 1})
        _flip_at(path, _first_leaf_offset(path))

        calls = []
        real = cio.device_put_like
        monkeypatch.setattr(cio, "device_put_like",
                            lambda a, t: calls.append(1) or real(a, t))
        with pytest.raises(CheckpointCorruptError, match="digest"):
            cio.load(path, target=user_state())
        assert calls == []  # the flipped first leaf was never placed

    def test_head_flip_detected(self, tmp_path):
        path = str(tmp_path / "ckpt_1")
        cio.save(path, user_state(), {"step": 1, "batches_committed": 1})
        # flip inside the json head (right after magic + length)
        _flip_at(path, len(cio._CKPT_MAGIC) + 4 + 5)
        with pytest.raises(CheckpointCorruptError):
            cio.verify(path)

    def test_preamble_flip_detected(self, tmp_path):
        """The payload preamble json carries py-leaf VALUES inline (step
        counters): a flip there must fail BOTH verify() and load(), not
        just verify — otherwise a corrupted scalar loads silently while
        every array leaf checks out."""
        path = str(tmp_path / "ckpt_1")
        cio.save(path, user_state(), {"step": 1, "batches_committed": 1})
        _flip_at(path, _first_leaf_offset(path) - 3)
        with pytest.raises(CheckpointCorruptError):
            cio.verify(path)
        with pytest.raises(CheckpointCorruptError):
            cio.load(path, target=user_state(), device_put=False)

    def test_truncation_detected(self, tmp_path):
        path = str(tmp_path / "ckpt_1")
        cio.save(path, user_state(), {"step": 1, "batches_committed": 1})
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) - 7)
        with pytest.raises(CheckpointCorruptError):
            cio.verify(path)
        with pytest.raises(CheckpointCorruptError):
            cio.load(path, target=user_state(), device_put=False)


class TestRecover:
    def test_falls_back_past_corrupt_and_quarantines(self, tmp_path):
        good = str(tmp_path / "ckpt_5")
        cio.save(good, user_state(5.0), {"step": 5,
                                         "batches_committed": 5})
        bad = str(tmp_path / "ckpt_8")
        cio.save(bad, user_state(8.0), {"step": 8, "batches_committed": 8})
        _flip_at(bad, _first_leaf_offset(bad))

        stats = {}
        assert cio.recover(str(tmp_path), stats=stats) == good
        assert stats["ckpt_corrupt_quarantined"] == 1
        assert stats["ckpt_recover_fallbacks"] == 1
        assert os.path.exists(bad + ".corrupt")
        assert not os.path.exists(bad)
        # the quarantined file is no longer a candidate for anything
        assert cio.latest(str(tmp_path)) == good

    def test_zero_byte_newest_never_a_candidate(self, tmp_path):
        good = str(tmp_path / "ckpt_2")
        cio.save(good, user_state(), {"step": 2, "batches_committed": 2})
        (tmp_path / "ckpt_9").write_bytes(b"")
        assert cio.latest(str(tmp_path)) == good
        assert cio.recover(str(tmp_path)) == good

    def test_uncommitted_snapshot_skipped(self, tmp_path):
        cio.save(str(tmp_path / "ckpt_1"), user_state(1.0),
                 {"step": 1, "batches_committed": 1})
        cio.save(str(tmp_path / "ckpt_4"), user_state(4.0),
                 {"step": 4, "batches_committed": 4},
                 meta={"committed": False})
        stats = {}
        assert cio.recover(str(tmp_path), stats=stats) == str(
            tmp_path / "ckpt_1")
        assert stats["ckpt_recover_fallbacks"] == 1
        assert stats["ckpt_corrupt_quarantined"] == 0
        assert os.path.exists(tmp_path / "ckpt_4")  # not quarantined

    def test_legacy_skipped_without_quarantine(self, tmp_path):
        from torchft_tpu.serialization import save_pytree

        cio.save(str(tmp_path / "ckpt_1"), user_state(),
                 {"step": 1, "batches_committed": 1})
        legacy = tmp_path / "ckpt_6"
        legacy.write_bytes(save_pytree(
            {"user": user_state(),
             "torchft": {"step": 6, "batches_committed": 6}}))
        assert cio.recover(str(tmp_path)) == str(tmp_path / "ckpt_1")
        assert legacy.exists()  # skipped, not quarantined

    def test_legacy_only_dir_falls_back_instead_of_fresh_start(
            self, tmp_path):
        """Upgrading a job whose directory holds ONLY legacy (pre-v2)
        checkpoints must resume from the newest one, not silently
        restart training from scratch."""
        from torchft_tpu.serialization import save_pytree

        for step in (3, 9):
            (tmp_path / f"ckpt_{step}").write_bytes(save_pytree(
                {"user": user_state(float(step)),
                 "torchft": {"step": step, "batches_committed": step}}))
        stats = {}
        got = cio.recover(str(tmp_path), stats=stats)
        assert got == str(tmp_path / "ckpt_9")
        assert stats["ckpt_recover_legacy"] == 1
        _, mgr = cio.load(got, target=user_state(), device_put=False)
        assert mgr["step"] == 9
        # opt-out restores strict behavior
        assert cio.recover(str(tmp_path), allow_legacy=False) is None

    def test_torn_legacy_never_the_last_resort(self, tmp_path):
        """A TRUNCATED legacy file still starts with the TFTPTREE magic
        (unverifiable, not corrupt) — the legacy last resort must skip
        it for an older structurally-whole one instead of handing
        load() a file that crashes."""
        from torchft_tpu.serialization import save_pytree

        good = save_pytree({"user": user_state(3.0),
                            "torchft": {"step": 3,
                                        "batches_committed": 3}})
        (tmp_path / "ckpt_3").write_bytes(good)
        (tmp_path / "ckpt_9").write_bytes(good[:len(good) // 2])  # torn
        got = cio.recover(str(tmp_path))
        assert got == str(tmp_path / "ckpt_3")
        _, mgr = cio.load(got, target=user_state(), device_put=False)
        assert mgr["step"] == 3

    def test_quarantine_false_counts_nothing_moved(self, tmp_path):
        good = str(tmp_path / "ckpt_1")
        cio.save(good, user_state(), {"step": 1, "batches_committed": 1})
        bad = str(tmp_path / "ckpt_2")
        cio.save(bad, user_state(), {"step": 2, "batches_committed": 2})
        _flip_at(bad, _first_leaf_offset(bad))
        stats = {}
        assert cio.recover(str(tmp_path), quarantine=False,
                           stats=stats) == good
        # nothing was renamed, so nothing may be counted as quarantined
        assert stats["ckpt_corrupt_quarantined"] == 0
        assert stats["ckpt_recover_fallbacks"] == 1
        assert os.path.exists(bad)

    def test_empty_dir(self, tmp_path):
        assert cio.recover(str(tmp_path)) is None
        assert cio.recover(str(tmp_path / "nope")) is None


class TestDiskChaos:
    def teardown_method(self):
        chaos_mod.uninstall()

    def test_spec_parses_disk_fields(self):
        sched = parse_spec(
            "seed=3;disk:torn_rate=0.2,flip_rate=0.1,enospc_rate=0.05")
        cfg = sched.endpoints["disk"]
        assert (cfg.torn_rate, cfg.flip_rate, cfg.enospc_rate) == (
            0.2, 0.1, 0.05)

    def test_torn_write_leaves_torn_artifact(self, tmp_path):
        good = str(tmp_path / "ckpt_1")
        cio.save(good, user_state(1.0), {"step": 1,
                                         "batches_committed": 1})
        chaos_mod.install(ChaosSchedule(seed=0, endpoints={
            "disk": EndpointChaos(torn_rate=1.0)}))
        torn = str(tmp_path / "ckpt_2")
        with pytest.raises(OSError, match="torn"):
            cio.save(torn, user_state(2.0), {"step": 2,
                                             "batches_committed": 2})
        chaos_mod.uninstall()
        # the torn file sits at the DESTINATION, fails verification, and
        # recovery quarantines it + falls back to the previous good one
        assert os.path.exists(torn)
        assert os.path.getsize(torn) > 0
        with pytest.raises(CheckpointCorruptError):
            cio.verify(torn)
        stats = {}
        assert cio.recover(str(tmp_path), stats=stats) == good
        assert stats["ckpt_corrupt_quarantined"] == 1

    def test_flip_is_silent_until_verify(self, tmp_path):
        chaos_mod.install(ChaosSchedule(seed=0, endpoints={
            "disk": EndpointChaos(flip_rate=1.0)}))
        path = str(tmp_path / "ckpt_1")
        cio.save(path, user_state(), {"step": 1,
                                      "batches_committed": 1})  # no raise
        chaos_mod.uninstall()
        with pytest.raises(CheckpointCorruptError):
            cio.verify(path)

    def test_enospc_raises_fatal_errno(self, tmp_path):
        import errno

        chaos_mod.install(ChaosSchedule(seed=0, endpoints={
            "disk": EndpointChaos(enospc_rate=1.0)}))
        with pytest.raises(OSError) as ei:
            cio.save(str(tmp_path / "ckpt_1"), user_state(),
                     {"step": 1, "batches_committed": 1})
        assert ei.value.errno == errno.ENOSPC

    def test_deterministic_fault_sequence(self):
        def run():
            sched = ChaosSchedule(seed=7, endpoints={
                "disk": EndpointChaos(torn_rate=0.3, flip_rate=0.3,
                                      enospc_rate=0.2)})
            out = []
            for i in range(30):
                try:
                    d = chaos_mod.disk_fault(f"disk:ckpt_{i}", "save",
                                             schedule=sched)
                    out.append(d.fault if d else None)
                except OSError:
                    out.append("enospc")
            return out

        a, b = run(), run()
        assert a == b
        assert "torn" in a and "flip" in a and "enospc" in a


class TestAsyncCheckpointerRobustness:
    def teardown_method(self):
        chaos_mod.uninstall()

    def test_stalled_write_shutdown_returns_within_timeout(self,
                                                           tmp_path):
        """A wedged write (chaos blackhole = stalled NFS) must not hang
        shutdown(): the no-progress watchdog abandons it within the
        stall timeout and surfaces a CheckpointStallError."""
        chaos_mod.install(ChaosSchedule(seed=0, endpoints={
            "disk": EndpointChaos(blackhole_rate=1.0,
                                  blackhole_ms=8_000.0)}))
        ck = AsyncCheckpointer(stall_timeout_sec=0.5)
        ck.save_async(str(tmp_path / "ckpt_1"), {"w": jnp.zeros(4)})
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="previous async"):
            ck.shutdown()
        elapsed = time.monotonic() - t0
        assert elapsed < 4.0, f"shutdown hung {elapsed:.1f}s"
        assert ck.metrics()["ckpt_save_stalls"] == 1
        assert "no progress" in (ck.last_error() or "")

    def test_enospc_fatal_reported_and_reraised(self, tmp_path):
        chaos_mod.install(ChaosSchedule(seed=0, endpoints={
            "disk": EndpointChaos(enospc_rate=1.0)}))
        ck = AsyncCheckpointer()
        try:
            fut = ck.save_async(str(tmp_path / "ckpt_1"),
                                {"w": jnp.zeros(4)})
            with pytest.raises(OSError):
                fut.result(timeout=30)
            mx = ck.metrics()
            assert mx["ckpt_save_errors"] == 1
            assert mx["ckpt_save_fatal"] == 1
            assert "space" in (ck.last_error() or "").lower()
            chaos_mod.uninstall()
            # the latched error still re-raises on the next call
            with pytest.raises(RuntimeError, match="previous async"):
                ck.save_async(str(tmp_path / "ckpt_2"),
                              {"w": jnp.zeros(4)})
        finally:
            ck.shutdown()

    def test_transient_eio_is_not_fatal(self, tmp_path):
        chaos_mod.install(ChaosSchedule(seed=0, endpoints={
            "disk": EndpointChaos(reset_rate=1.0, max_faults=1)}))
        ck = AsyncCheckpointer()
        try:
            fut = ck.save_async(str(tmp_path / "ckpt_1"),
                                {"w": jnp.zeros(4)})
            with pytest.raises(OSError):
                fut.result(timeout=30)
            mx = ck.metrics()
            assert mx["ckpt_save_errors"] == 1
            assert mx["ckpt_save_fatal"] == 0
        finally:
            chaos_mod.uninstall()
            try:
                ck.shutdown()
            except RuntimeError:
                pass

    def test_prune_never_deletes_newest_verified(self, tmp_path):
        """keep=2 with two newer CORRUPT files: retention must protect
        the newest checkpoint that verifies — deleting the last good
        snapshot because garbage outranks it would be data loss."""
        # two corrupt "newer" files that were never valid
        (tmp_path / "ckpt_8").write_bytes(b"TFTCKPT2garbage")
        (tmp_path / "ckpt_9").write_bytes(b"\x00" * 64)
        ck = AsyncCheckpointer(keep=2)
        try:
            for step in (1, 2, 3):
                ck.save_async(str(tmp_path / f"ckpt_{step}"),
                              {"w": jnp.full(2, float(step))},
                              {"step": step, "batches_committed": step})
            ck.wait()
        finally:
            ck.shutdown()
        # ckpt_3 is the newest VERIFIED file and must survive, even
        # though 8 and 9 occupy the keep window
        assert os.path.exists(tmp_path / "ckpt_3")
        assert cio.verify(str(tmp_path / "ckpt_3"))["step"] == 3
        assert not os.path.exists(tmp_path / "ckpt_1")
        assert not os.path.exists(tmp_path / "ckpt_2")
        # and recovery lands on it
        assert cio.recover(str(tmp_path)) == str(tmp_path / "ckpt_3")


class _StateHolder:
    """Mutable user-state cell wired into a mocked-quorum Manager."""

    def __init__(self, w):
        self.state = {"w": w}

    def load(self, s):
        self.state = s

    def dump(self):
        return self.state

    def w_bytes(self):
        return np.asarray(self.state["w"]).tobytes()


class TestManagerDurable:
    def _happy(self, holder):
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = True
        return make_manager(client, load_state_dict=holder.load,
                            state_dict=holder.dump)

    def test_save_durable_stamps_commit_meta(self, tmp_path):
        holder = _StateHolder(np.arange(16, dtype=np.float32))
        m = self._happy(holder)
        ck = AsyncCheckpointer()
        try:
            m.step()
            assert m.should_commit()
            fut = m.save_durable(ck, str(tmp_path))
            assert fut is not None
            path = fut.result(timeout=30)
            head = cio.read_meta(path)
            assert head["step"] == 1
            assert head["committed"] is True
            assert head["quorum_id"] == 1
            assert head["replica_id"] == "testgroup"
            assert head["participants"] == 2
            assert cio.verify(path)["step"] == 1
            mx = m.metrics()
            assert mx["ckpt_save_count"] == 1
            assert mx["ckpt_save_fatal"] == 0
        finally:
            ck.shutdown()
            m.shutdown()

    def test_refuses_errored_and_uncommitted_state(self, tmp_path):
        holder = _StateHolder(np.zeros(4, np.float32))
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = False  # vote aborts
        m = make_manager(client, load_state_dict=holder.load,
                         state_dict=holder.dump)
        ck = AsyncCheckpointer()
        try:
            m.step()
            m.report_error(RuntimeError("boom"))
            assert m.save_durable(ck, str(tmp_path)) is None  # errored
            assert not m.should_commit()
            assert m.save_durable(ck, str(tmp_path)) is None  # aborted
            mx = m.metrics()
            assert mx["ckpt_save_skipped"] == 2
            assert "ckpt_skip" in [e["event"] for e in m.history()]
            assert os.listdir(tmp_path) == []
        finally:
            ck.shutdown()
            m.shutdown()

    def test_refuses_mid_heal_snapshot(self, tmp_path):
        holder = _StateHolder(np.zeros(4, np.float32))
        m = self._happy(holder)
        ck = AsyncCheckpointer()
        try:
            with m._metrics_lock:  # unit shortcut: flag a staged heal
                m._healing = True
            assert m.save_durable(ck, str(tmp_path)) is None
            assert m.metrics()["ckpt_save_skipped"] == 1
            assert os.listdir(tmp_path) == []
        finally:
            ck.shutdown()
            m.shutdown()


class TestManagerColdStart:
    def test_cold_start_restores_newest_verified(self, tmp_path):
        w5 = np.arange(32, dtype=np.float32)
        cio.save(str(tmp_path / "ckpt_5"), {"w": w5},
                 {"step": 5, "batches_committed": 10},
                 meta={"quorum_id": 2, "replica_id": "old"})
        bad = str(tmp_path / "ckpt_9")
        cio.save(bad, {"w": np.zeros(32, np.float32)},
                 {"step": 9, "batches_committed": 18})
        _flip_at(bad, _first_leaf_offset(bad))

        holder = _StateHolder(np.zeros(32, np.float32))
        client = MagicMock()
        m = make_manager(client, load_state_dict=holder.load,
                         state_dict=holder.dump)
        try:
            path = m.cold_start(str(tmp_path))
            assert path == str(tmp_path / "ckpt_5")
            assert m.current_step() == 5
            assert m.batches_committed() == 10
            assert holder.w_bytes() == w5.tobytes()
            mx = m.metrics()
            assert mx["ckpt_cold_starts"] == 1
            assert mx["ckpt_corrupt_quarantined"] == 1
            assert mx["ckpt_recover_fallbacks"] == 1
            events = [e for e in m.history() if e["event"] == "cold_start"]
            assert events and events[-1]["recovered"] is True
        finally:
            m.shutdown()

    def test_cold_start_empty_dir_is_fresh_start(self, tmp_path):
        holder = _StateHolder(np.zeros(4, np.float32))
        client = MagicMock()
        m = make_manager(client, load_state_dict=holder.load,
                         state_dict=holder.dump)
        try:
            assert m.cold_start(str(tmp_path)) is None
            assert m.current_step() == 0
            assert m.metrics()["ckpt_cold_starts"] == 0
        finally:
            m.shutdown()


class TestColdStartConvergence:
    """THE acceptance: two groups cold-started from DIFFERENT on-disk
    steps (correlated failure with divergent last-good snapshots) end
    bitwise identical at the newest committed step, via the existing
    max_step heal path — no extra reconciliation protocol."""

    def test_divergent_cold_starts_converge_bitwise(self, tmp_path):
        from torchft_tpu.checkpointing import CheckpointServer

        rng = np.random.RandomState(11)
        wA = rng.rand(4096).astype(np.float32)   # newest committed (10)
        wB = rng.rand(4096).astype(np.float32)   # stale (8)
        cio.save(str(tmp_path / "a" / "ckpt_10"), {"w": wA},
                 {"step": 10, "batches_committed": 20},
                 meta={"quorum_id": 4, "replica_id": "gA"})
        cio.save(str(tmp_path / "b" / "ckpt_8"), {"w": wB},
                 {"step": 8, "batches_committed": 16},
                 meta={"quorum_id": 3, "replica_id": "gB"})
        # and a torn newest file in B's dir: recovery must skip it
        torn = tmp_path / "b" / "ckpt_9"
        torn.write_bytes(b"TFTCKPT2\x40\x00\x00\x00partial head junk")

        holderA = _StateHolder(np.zeros(4096, np.float32))
        holderB = _StateHolder(np.zeros(4096, np.float32))

        # group A: cold-starts at 10, participates, serves heals
        cellA = {}
        srvA = CheckpointServer(
            lambda: cellA["m"]._manager_state_dict(),
            bind_host="127.0.0.1")
        clientA = MagicMock()
        clientA.quorum.return_value = quorum_result(
            quorum_id=5, max_step=11, max_rank=0, max_world_size=2,
            replica_rank=0, replica_world_size=2)
        clientA.should_commit.return_value = True
        mA = make_manager(clientA, load_state_dict=holderA.load,
                          state_dict=holderA.dump, min_replica_size=1,
                          checkpoint_transport=srvA)
        cellA["m"] = mA

        # group B: cold-starts at 8, must heal from A
        clientB = MagicMock()
        clientB.quorum.return_value = quorum_result(
            quorum_id=5, max_step=11, max_rank=None, max_world_size=1,
            replica_rank=1, replica_world_size=2, heal=True,
            recover_manager_address="managerA")
        clientB.should_commit.return_value = True
        mB = make_manager(clientB, load_state_dict=holderB.load,
                          state_dict=holderB.dump, min_replica_size=1)

        def make_client(addr, **kwargs):
            mc = MagicMock()
            mc.checkpoint_address.side_effect = (
                lambda *a, **k: srvA.address())
            return mc

        try:
            assert mA.cold_start(str(tmp_path / "a")) is not None
            assert mA.current_step() == 10
            statsB = mB.cold_start(str(tmp_path / "b"))
            assert statsB == str(tmp_path / "b" / "ckpt_8")
            assert mB.current_step() == 8
            assert mB.metrics()["ckpt_corrupt_quarantined"] == 1
            # the two groups rejoin the quorum at divergent steps
            assert holderA.w_bytes() != holderB.w_bytes()

            with patch("torchft_tpu.manager.ManagerClient",
                       side_effect=make_client):
                mA.step()     # advances to 11, opens the serve window
                mB.step()     # quorum says: heal from A at max_step 11
                assert mB.should_commit()   # heal fetched + applied
                assert mA.should_commit()
        finally:
            mB.shutdown()
            mA.shutdown()

        # converged: bitwise identical at the newest committed step
        assert mA.current_step() == mB.current_step() == 11
        assert holderA.w_bytes() == holderB.w_bytes()
        assert holderB.w_bytes() == wA.tobytes()
        assert mB.metrics()["heal_count"] == 1
        assert mB.metrics()["heal_bytes_total"] > 0


@pytest.mark.cold_start
@pytest.mark.slow
@pytest.mark.nightly
class TestColdStartSoak:
    """Seeded kill-all → cold-restart soak (``scripts/test.sh
    cold-start``): every round a 2-group job checkpoints under disk
    chaos (torn writes, silent bit-flips, ENOSPC), then the whole fleet
    "dies" and cold-restarts from disk. Invariants per round: recovery
    never loads unverified bytes (every recovered file re-verifies and
    matches the state recorded at save time bitwise), and never
    regresses past the newest CLEAN save (regression is bounded by the
    checkpoint cadence around injected faults)."""

    ROUNDS = 4
    STEPS = 18
    CADENCE = 3

    def test_kill_all_cold_restart_rounds(self, tmp_path):
        for rnd in range(self.ROUNDS):
            self._one_round(rnd, tmp_path / f"r{rnd}")

    def _one_round(self, rnd, root):
        rng = np.random.RandomState(100 + rnd)
        sched = ChaosSchedule(seed=200 + rnd, endpoints={
            "disk": EndpointChaos(torn_rate=0.2, flip_rate=0.15,
                                  enospc_rate=0.08)})
        chaos_mod.install(sched)
        groups = {g: {"w": rng.rand(512).astype(np.float32)}
                  for g in (0, 1)}
        recorded = {g: {} for g in groups}   # step -> state bytes
        clean = {g: [] for g in groups}      # steps with fault-free saves
        try:
            for step in range(1, self.STEPS + 1):
                for g, state in groups.items():
                    # deterministic "training": the committed update
                    state["w"] = state["w"] * 1.01 + g
                    if step % self.CADENCE != 0:
                        continue
                    recorded[g][step] = state["w"].tobytes()
                    n_before = len(sched.trace())
                    try:
                        cio.save(str(root / str(g) / f"ckpt_{step}"),
                                 {"w": state["w"]},
                                 {"step": step,
                                  "batches_committed": 2 * step})
                    except OSError:
                        continue  # torn / ENOSPC / EIO: save failed
                    faults = [d.fault for d in
                              sched.trace()[n_before:] if d.fault]
                    if not faults:
                        clean[g].append(step)
        finally:
            chaos_mod.uninstall()

        # ---- kill-all: every group is gone; cold-restart from disk ----
        for g in groups:
            stats = {}
            path = cio.recover(str(root / str(g)), stats=stats)
            assert clean[g], "soak produced no clean save; relax rates"
            assert path is not None, (
                f"round {rnd} group {g}: no recovery despite clean "
                f"saves at {clean[g]}")
            # never an unverified load: the file re-verifies...
            head = cio.verify(path)
            user, mgr = cio.load(path, target={
                "w": np.zeros(512, np.float32)}, device_put=False)
            step = mgr["step"]
            assert head["committed"] is True
            # ...and the loaded bytes are exactly what was recorded at
            # that step (a silently-flipped file can never get here)
            assert user["w"].tobytes() == recorded[g][step], (
                f"round {rnd} group {g}: recovered state at step {step} "
                "does not match the state saved there")
            # bounded regression: at least the newest clean save
            assert step >= max(clean[g]), (
                f"round {rnd} group {g}: recovered step {step} < newest "
                f"clean save {max(clean[g])}")
