"""Control-plane scaling tests (docs/design/control_plane.md).

Three layers:
  * no-native units — the quorum-latency reservoir and the Manager's
    fast/slow round accounting, driven through a mocked ManagerClient;
  * native-gated protocol tests — piggybacked-beat freshness (the
    standalone heartbeat can be effectively off and the lighthouse still
    sees fresh beats), fast-path hit/epoch accounting through the real
    C++ stack;
  * native-gated failover acceptance — a 2-group training run whose
    PRIMARY lighthouse is SIGKILLed mid-run: managers re-dial the warm
    standby and keep committing with NO ring rebuild (reconfigure_count
    frozen) and NO vote aborts, ending bitwise identical; plus a nightly
    TORCHFT_CHAOS round with the primary black-holed (SIGSTOP — sockets
    alive, nothing answers), the worst-case death shape.

The C++-level unit matrix (cache invalidation per membership-delta class,
epoch monotonicity, fast-vs-slow decision identity) lives in
torchft_tpu/_core/core_test.cc.
"""

import functools
import os
import signal
import subprocess
import sys
import threading
import time
from unittest.mock import MagicMock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import conftest
from mockplane import make_manager, quorum_result
from torchft_tpu.manager import Manager, _LatencyReservoir

requires_native = conftest.requires_native()


# ---------------------------------------------------------------- reservoir


@pytest.mark.control_plane
class TestLatencyReservoir:
    def test_bounded_with_exact_max(self):
        r = _LatencyReservoir(size=64, seed=1)
        for i in range(10_000):
            r.add(float(i % 100))
        r.add(12345.0)  # a spike the sampler must never lose
        p = r.percentiles()
        assert len(r._samples) == 64
        assert p["max"] == 12345.0
        assert 0.0 <= p["p50"] <= p["p95"] <= p["max"]

    def test_empty(self):
        assert _LatencyReservoir().percentiles() == {
            "p50": 0.0, "p95": 0.0, "max": 0.0}

    def test_deterministic_given_seed(self):
        a, b = _LatencyReservoir(seed=9), _LatencyReservoir(seed=9)
        for i in range(5000):
            a.add(float(i))
            b.add(float(i))
        assert a.percentiles() == b.percentiles()


# --------------------------------------------------- manager-side accounting


_make_manager = functools.partial(
    make_manager, min_replica_size=1, use_async_quorum=False)


@pytest.mark.control_plane
class TestManagerControlPlaneMetrics:
    def test_fast_slow_round_split_and_epoch(self):
        client = MagicMock()
        client.quorum.side_effect = [
            quorum_result(quorum_id=7, max_step=1, fast_path=False,
                          epoch=100),
            quorum_result(quorum_id=7, max_step=2, fast_path=True,
                          epoch=101),
            quorum_result(quorum_id=7, max_step=3, fast_path=True,
                          epoch=103),
        ]
        m = _make_manager(client)
        for _ in range(3):
            m.step()
        mx = m.metrics()
        assert mx["quorum_fast_path_hits"] == 2
        assert mx["quorum_slow_path_rounds"] == 1
        assert mx["quorum_epoch_last"] == 103
        assert mx["quorum_count"] == 3
        # Reservoir percentiles ride metrics()/metrics.json.
        assert mx["quorum_ms_max"] >= mx["quorum_ms_p95"] >= mx["quorum_ms_p50"] > 0
        # No native manager server attached -> no redials, key still present.
        assert mx["lighthouse_redials"] == 0.0

    def test_mocked_client_without_new_fields_counts_slow(self):
        # Duck-typed/mocked rigs that predate fast_path/epoch must not
        # crash or miscount as fast hits.
        client = MagicMock()  # quorum() returns a bare MagicMock
        q = client.quorum.return_value
        q.replica_world_size = 2
        q.quorum_id = 3
        q.max_step = 1
        q.replica_rank = 0
        q.max_rank = 0
        q.heal = False
        q.store_address = ""
        m = _make_manager(client)
        m.step()
        mx = m.metrics()
        assert mx["quorum_fast_path_hits"] == 0
        assert mx["quorum_slow_path_rounds"] == 1


# ------------------------------------------------------- native: fast path


@requires_native
@pytest.mark.control_plane
class TestFastPathNative:
    def test_fast_path_hits_and_epochs_via_manager_stack(self):
        """Two groups through the real C++ manager+lighthouse: round 1 is
        the slow rendezvous, steady-state rounds ride the cache."""
        from torchft_tpu._native import Lighthouse, ManagerClient, ManagerServer

        lh = Lighthouse(bind="127.0.0.1:0", min_replicas=2,
                        join_timeout_ms=2000, quorum_tick_ms=10,
                        heartbeat_fresh_ms=300, fast_path=True)
        servers, clients = [], []
        try:
            for gid in ("ga", "gb"):
                s = ManagerServer(gid, lh.address(), store_addr=f"st_{gid}",
                                  bind="127.0.0.1:0", world_size=1)
                servers.append(s)
                clients.append(ManagerClient(s.address()))

            results = {}

            def run_round(step):
                def one(i):
                    results[(step, i)] = clients[i].quorum(
                        rank=0, step=step, checkpoint_server_addr=f"c{i}",
                        timeout_ms=20_000)
                ts = [threading.Thread(target=one, args=(i,))
                      for i in range(2)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()

            for step in (1, 2, 3):
                run_round(step)
            assert not results[(1, 0)].fast_path
            assert results[(2, 0)].fast_path and results[(2, 1)].fast_path
            assert results[(3, 0)].fast_path
            # quorum_id frozen (membership unchanged), epoch total order.
            ids = {r.quorum_id for r in results.values()}
            assert len(ids) == 1
            for i in (0, 1):
                epochs = [results[(s, i)].epoch for s in (1, 2, 3)]
                assert epochs == sorted(epochs)
                assert epochs[2] > epochs[0]
            st = lh.status()
            assert st["fast_path_hits"] >= 4
            assert st["slow_path_served"] >= 2
            assert servers[0].lighthouse_redials() == 0
        finally:
            for s in servers:
                s.shutdown()
            lh.shutdown()

    def test_piggybacked_beats_keep_liveness_fresh(self):
        """With the standalone heartbeat effectively disabled (60s
        cadence), quorum-RPC piggybacking alone must keep the lighthouse's
        per-member liveness fresh — the coalesced-heartbeat contract."""
        from torchft_tpu._native import Lighthouse, ManagerClient, ManagerServer

        lh = Lighthouse(bind="127.0.0.1:0", min_replicas=2,
                        join_timeout_ms=2000, quorum_tick_ms=10,
                        heartbeat_fresh_ms=400, fast_path=True)
        servers, clients = [], []
        try:
            for gid in ("ga", "gb"):
                s = ManagerServer(gid, lh.address(), store_addr=f"st_{gid}",
                                  bind="127.0.0.1:0", world_size=1,
                                  heartbeat_ms=60_000)
                servers.append(s)
                clients.append(ManagerClient(s.address()))

            for step in (1, 2, 3, 4):
                ts = [threading.Thread(
                    target=lambda i=i, s=step: clients[i].quorum(
                        rank=0, step=s, checkpoint_server_addr=f"c{i}",
                        timeout_ms=20_000)) for i in range(2)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
            # Steps 2-4 rode the fast path: only piggybacked beats could
            # have refreshed the records (the standalone thread fires once
            # a minute).
            st = lh.status()
            ages = {m["replica_id"]: m["heartbeat_age_ms"]
                    for m in st["members"]}
            assert set(ages) == {"ga", "gb"}
            for rid, age in ages.items():
                assert 0 <= age < 2_000, (rid, age)
            assert st["fast_path_hits"] >= 4
        finally:
            for s in servers:
                s.shutdown()
            lh.shutdown()


# --------------------------------------------- native: standby failover E2E


def _spawn_lighthouse_subprocess(tmp_path, *extra_args):
    """Start `python -m torchft_tpu.lighthouse` on an ephemeral port and
    return (proc, address). A real OS process so the test can SIGKILL /
    SIGSTOP it — in-process shutdown is too polite a death."""
    addr_file = os.path.join(str(tmp_path), f"lh_{os.getpid()}_"
                             f"{time.monotonic_ns()}.addr")
    proc = subprocess.Popen(
        [sys.executable, "-m", "torchft_tpu.lighthouse",
         "--bind", "127.0.0.1:0", "--min-replicas", "2",
         "--join-timeout-ms", "2000", "--quorum-tick-ms", "20",
         "--heartbeat-fresh-ms", "300", "--address-file", addr_file,
         *extra_args],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline:
        if os.path.exists(addr_file):
            with open(addr_file) as f:
                addr = f.read().strip()
            if addr:
                return proc, addr
        if proc.poll() is not None:
            raise RuntimeError("lighthouse subprocess died during startup")
        time.sleep(0.05)
    proc.kill()
    raise RuntimeError("lighthouse subprocess never wrote its address")


def _run_failover_job(lighthouse_addrs, total_steps, on_step,
                      min_replica_size=2):
    """Two replica groups (threads) training an MLP against the given
    lighthouse candidate list. ``on_step(step)`` fires from group 0's loop
    once per step (the kill hook). Returns per-group dicts with params,
    commits trace, and manager metrics."""
    from torchft_tpu import HostCommunicator
    from torchft_tpu.parallel import FTTrainer

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 8)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)
    from torchft_tpu.models import MLP

    model = MLP(features=(16,), num_classes=2)

    def loss_fn(params, batch):
        logits = model.apply(params, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    params0 = model.init(jax.random.key(7), jnp.zeros((1, 8)))
    results = {}
    errors = {}

    def worker(group: int) -> None:
        trainer = FTTrainer(
            loss_fn=loss_fn, tx=optax.sgd(0.05), params=params0,
            manager_factory=lambda load, save: Manager(
                comm=HostCommunicator(timeout_sec=30),
                load_state_dict=load, state_dict=save,
                min_replica_size=min_replica_size,
                replica_id=f"group{group}",
                lighthouse_addr=lighthouse_addrs, rank=0, world_size=1,
                timeout_ms=30_000, quorum_timeout_ms=30_000),
        )
        try:
            commits = []
            while trainer.manager.current_step() < total_steps:
                batch = {"x": x, "y": y}
                _, committed = trainer.train_step(batch)
                if committed:
                    commits.append((trainer.manager.current_step(),
                                    trainer.manager.quorum_id()))
                if group == 0:
                    on_step(trainer.manager.current_step())
            results[group] = {
                "params": jax.device_get(trainer.params),
                "commits": commits,
                "metrics": trainer.manager.metrics(),
            }
        except Exception as e:  # noqa: BLE001
            errors[group] = e
        finally:
            trainer.shutdown()

    threads = [threading.Thread(target=worker, args=(g,)) for g in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, f"group failures: {errors!r}"
    assert set(results) == {0, 1}
    return results


@requires_native
@pytest.mark.integration
@pytest.mark.control_plane
class TestStandbyFailoverMidRun:
    def test_primary_sigkill_mid_run_commits_without_ring_rebuild(
            self, tmp_path):
        """Acceptance: primary SIGKILL mid-run -> managers re-dial the warm
        standby and commit the in-flight step with no ring rebuild
        (reconfigure_count frozen at the initial one), no vote aborts, and
        bitwise-identical final params; the failover is observable as
        lighthouse_redials > 0."""
        from torchft_tpu._native import Lighthouse

        proc, primary_addr = _spawn_lighthouse_subprocess(tmp_path)
        standby = Lighthouse(bind="127.0.0.1:0", min_replicas=2,
                             join_timeout_ms=2000, quorum_tick_ms=20,
                             heartbeat_fresh_ms=300,
                             standby_of=primary_addr, replicate_ms=30)
        killed = threading.Event()
        total_steps, kill_at = 8, 4

        def on_step(step: int) -> None:
            if step >= kill_at and not killed.is_set():
                killed.set()
                proc.send_signal(signal.SIGKILL)
                proc.wait(timeout=10)

        try:
            results = _run_failover_job(
                f"{primary_addr},{standby.address()}", total_steps, on_step)
        finally:
            if proc.poll() is None:
                proc.kill()
            standby.shutdown()
        assert killed.is_set(), "kill hook never fired"

        a, b = results[0], results[1]
        # Bitwise convergence across the failover.
        for la, lb in zip(jax.tree_util.tree_leaves(a["params"]),
                          jax.tree_util.tree_leaves(b["params"])):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        for r in (a, b):
            mx = r["metrics"]
            # Same membership across the failover -> quorum_id constant on
            # every commit -> exactly the initial communicator configure.
            assert len({qid for _, qid in r["commits"]}) == 1
            assert mx["reconfigure_count"] == 1.0
            assert mx["aborted_steps"] == 0.0
            assert [s for s, _ in r["commits"]] == list(
                range(1, total_steps + 1))
        # The failover is observable: at least one group re-dialed.
        assert (a["metrics"]["lighthouse_redials"]
                + b["metrics"]["lighthouse_redials"]) >= 1


@requires_native
@pytest.mark.integration
@pytest.mark.control_plane
@pytest.mark.nightly
@pytest.mark.slow
class TestBlackholeChaosRound:
    def test_chaos_round_with_lighthouse_blackholed(self, tmp_path,
                                                    monkeypatch):
        """Nightly chaos round: transport chaos on the manager/store
        channels while the primary lighthouse is BLACK-HOLED mid-run
        (SIGSTOP: sockets stay open, nothing answers — the death shape
        that refused-connect classification cannot see). Managers must
        time out, re-dial the standby, and finish bitwise identical."""
        from torchft_tpu._native import Lighthouse

        monkeypatch.setenv(
            "TORCHFT_CHAOS",
            "seed=11;manager:latency_ms=1,reset_rate=0.02;"
            "store:reset_rate=0.02")
        proc, primary_addr = _spawn_lighthouse_subprocess(tmp_path)
        standby = Lighthouse(bind="127.0.0.1:0", min_replicas=2,
                             join_timeout_ms=2000, quorum_tick_ms=20,
                             heartbeat_fresh_ms=300,
                             standby_of=primary_addr, replicate_ms=30)
        stopped = threading.Event()
        total_steps, stop_at = 8, 3

        def on_step(step: int) -> None:
            if step >= stop_at and not stopped.is_set():
                stopped.set()
                proc.send_signal(signal.SIGSTOP)

        try:
            results = _run_failover_job(
                f"{primary_addr},{standby.address()}", total_steps, on_step)
        finally:
            try:
                proc.send_signal(signal.SIGCONT)
            except Exception:  # noqa: BLE001
                pass
            proc.kill()
            standby.shutdown()
        assert stopped.is_set()

        a, b = results[0], results[1]
        for la, lb in zip(jax.tree_util.tree_leaves(a["params"]),
                          jax.tree_util.tree_leaves(b["params"])):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        for r in (a, b):
            assert [s for s, _ in r["commits"]][-1] == total_steps
        assert (a["metrics"]["lighthouse_redials"]
                + b["metrics"]["lighthouse_redials"]) >= 1


# -------------------------------------------------- native: latency vs N


@requires_native
@pytest.mark.control_plane
@pytest.mark.slow
@pytest.mark.nightly
class TestQuorumLatencyBench:
    def test_fast_path_beats_slow_path_at_64_clients(self):
        """The acceptance gate for bench.py's quorum_latency_vs_n: at 64
        simulated manager clients with 2ms arrival jitter, steady-state
        fast-path p50 is >= 5x below the slow path's (whose floor is the
        fan-in wait for the last arrival), and fast-path p50 grows
        sublinearly with N (16 -> 64 clients: far less than 4x)."""
        import bench

        r64_fast = bench.bench_quorum_latency_vs_n(n=64, steps=20,
                                                   fast_path=True)
        r64_slow = bench.bench_quorum_latency_vs_n(n=64, steps=20,
                                                   fast_path=False)
        r16_fast = bench.bench_quorum_latency_vs_n(n=16, steps=20,
                                                   fast_path=True)
        assert r64_fast["fast_path_hits"] > 0
        assert r64_slow["fast_path_hits"] == 0
        assert r64_slow["p50_ms"] >= 5 * r64_fast["p50_ms"], (
            r64_slow["p50_ms"], r64_fast["p50_ms"])
        # Sublinear growth in N on the fast path: 4x the clients must cost
        # far less than 4x the p50.
        assert r64_fast["p50_ms"] < 4 * max(r16_fast["p50_ms"], 0.05), (
            r16_fast["p50_ms"], r64_fast["p50_ms"])

    def test_failover_bench_timeline(self):
        import bench

        fo = bench.bench_quorum_failover(n=4, steps=16, kill_at=8)
        assert fo["quorum_id_stable_across_failover"]
        assert fo["redials_total"] >= 1
        assert fo["failover_spike_ms"] > fo["pre_kill_p50_ms"]
