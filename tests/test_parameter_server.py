"""Parameter-server topology tests (BASELINE.md config 4): lighthouse-free
fault tolerance via per-session reconfigurable communicators — mirrors the
reference's parameter_server_test.py (client/server session, collectives
both ways, session isolation on failure)."""

import json
import threading
import time

import numpy as np
import pytest

from torchft_tpu.backends.host import HostCommunicator
from torchft_tpu.parameter_server import ParameterServer


class EchoPS(ParameterServer):
    """Serves its weights down (broadcast) and averages updates back
    (allreduce), once per session."""

    def __init__(self):
        super().__init__()
        self.weights = {"w": np.arange(4.0, dtype=np.float32)}
        self.sessions_served = 0
        self.session_errors = 0
        self._lock = threading.Lock()

    def new_communicator(self):
        return HostCommunicator(timeout_sec=10)

    def forward(self, session_id, comm):
        try:
            comm.broadcast(self.weights, root=0).result(timeout=30)
            # A copy of each leaf: allreduce consumes a contiguous 1-D
            # leaf (it reduces in place), and concurrent sessions would
            # otherwise fold into the one shared array.
            averaged = comm.allreduce(
                {k: v.copy() for k, v in self.weights.items()},
                op="mean").result(timeout=30)
            with self._lock:
                self.weights = averaged
                self.sessions_served += 1
        except Exception:
            with self._lock:
                self.session_errors += 1
            raise




def wait_for(predicate, timeout=20.0):
    """The server's session thread finishes (and bumps its counters) a
    beat after the client's last collective resolves — poll, don't race."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.05)
    return predicate()

@pytest.fixture
def ps():
    server = EchoPS()
    yield server
    server.shutdown()


class TestParameterServer:
    def test_session_roundtrip(self, ps):
        comm = EchoPS.new_session(ps.address())
        try:
            # weights come down from the server...
            got = comm.broadcast({"w": np.zeros(4, np.float32)},
                                 root=0).result(timeout=30)
            np.testing.assert_allclose(got["w"], [0, 1, 2, 3])
            # ...client pushes an update, both sides see the mean
            mean = comm.allreduce({"w": got["w"] + 2.0},
                                  op="mean").result(timeout=30)
            np.testing.assert_allclose(mean["w"], [1, 2, 3, 4])
        finally:
            comm.shutdown()
        assert wait_for(lambda: ps.sessions_served == 1)
        np.testing.assert_allclose(ps.weights["w"], [1, 2, 3, 4])

    def test_sequential_sessions_accumulate(self, ps):
        for k in range(3):
            comm = EchoPS.new_session(ps.address())
            try:
                got = comm.broadcast({"w": np.zeros(4, np.float32)},
                                     root=0).result(timeout=30)
                comm.allreduce({"w": got["w"]}, op="mean").result(timeout=30)
            finally:
                comm.shutdown()
            assert wait_for(lambda: ps.sessions_served == k + 1)
        assert ps.sessions_served == 3
        # each session averaged identical trees: weights unchanged
        np.testing.assert_allclose(ps.weights["w"], [0, 1, 2, 3])

    def test_client_death_kills_only_its_session(self, ps):
        """A client that dies mid-session must not poison the server:
        its session errors out alone and the next session works."""
        dead = EchoPS.new_session(ps.address())
        dead.broadcast({"w": np.zeros(4, np.float32)},
                       root=0).result(timeout=30)
        dead.shutdown()  # dies before the allreduce

        # wait for the server's session thread to observe the death
        assert wait_for(lambda: ps.session_errors == 1)

        comm = EchoPS.new_session(ps.address())
        try:
            got = comm.broadcast({"w": np.zeros(4, np.float32)},
                                 root=0).result(timeout=30)
            np.testing.assert_allclose(got["w"], [0, 1, 2, 3])
            comm.allreduce({"w": got["w"]}, op="mean").result(timeout=30)
        finally:
            comm.shutdown()
        assert wait_for(lambda: ps.sessions_served == 1)

    def test_concurrent_sessions_are_isolated(self, ps):
        """Two clients in flight at once: per-session store prefixes keep
        their collectives from crosstalking."""
        results = {}

        def client(name):
            comm = EchoPS.new_session(ps.address())
            try:
                got = comm.broadcast({"w": np.zeros(4, np.float32)},
                                     root=0).result(timeout=30)
                results[name] = comm.allreduce(
                    {"w": got["w"]}, op="mean").result(timeout=30)
            finally:
                comm.shutdown()

        ts = [threading.Thread(target=client, args=(f"c{i}",))
              for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert len(results) == 2
        for r in results.values():
            np.testing.assert_allclose(r["w"], [0, 1, 2, 3])
        assert wait_for(lambda: ps.sessions_served == 2)

    def test_bad_path_404(self, ps):
        import urllib.error
        import urllib.request

        addr = ps.address().replace("/new_session", "/nope")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(addr, timeout=10)


class _StubStore:
    """address()/shutdown() stand-in so the session machinery is
    testable without the native KV store."""

    def address(self) -> str:
        return "127.0.0.1:1/stub"

    def shutdown(self) -> None:
        pass


class _BlockingComm:
    """Communicator stub whose configure parks until shutdown — the
    shape of a session whose client vanished right after
    ``new_session`` (its rendezvous peer never arrives)."""

    def __init__(self):
        self._ev = threading.Event()
        self.shutdowns = 0

    def configure(self, store_addr, rank, world_size):
        self._ev.wait(timeout=60)

    def shutdown(self):
        self.shutdowns += 1
        self._ev.set()


class StuckPS(ParameterServer):
    """Every session blocks in configure forever (client vanished)."""

    def __init__(self, **kw):
        self.comms = []
        super().__init__(**kw)

    def _make_store(self):
        return _StubStore()

    def new_communicator(self):
        comm = _BlockingComm()
        self.comms.append(comm)
        return comm

    def forward(self, session_id, comm):
        raise AssertionError("configure never completes in this rig")


class _InstantComm(_BlockingComm):
    """Configure succeeds immediately; the session proceeds to
    forward."""

    def configure(self, store_addr, rank, world_size):
        pass


class LongForwardPS(StuckPS):
    """Sessions configure instantly, then forward runs 'forever' — the
    legitimate long-lived-collective-loop model of use."""

    def new_communicator(self):
        comm = _InstantComm()
        self.comms.append(comm)
        return comm

    def forward(self, session_id, comm):
        comm._ev.wait(timeout=60)


class TestSessionReap:
    """A client that dies after ``new_session`` must not leak its
    session (hijacked handler thread + communicator) for the process
    lifetime: the reaper force-closes it at session_timeout_sec and the
    status output makes the cycle observable."""

    def test_vanished_client_is_reaped(self):
        import urllib.request

        ps = StuckPS(session_timeout_sec=0.4, reap_interval_sec=0.05)
        try:
            with urllib.request.urlopen(ps.address(), timeout=10) as resp:
                meta = resp.read()
            assert b"session_id" in meta
            # ...and the client vanishes without ever configuring.
            assert wait_for(
                lambda: ps.status()["active_sessions"] == 1, timeout=5)
            st = ps.status()
            assert st["sessions_total"] == 1
            assert st["sessions_reaped"] == 0
            assert wait_for(
                lambda: ps.status()["sessions_reaped"] == 1, timeout=10)
            assert wait_for(
                lambda: ps.status()["active_sessions"] == 0, timeout=10)
            # The communicator was actually shut (unblocking the
            # hijacked handler thread), not just forgotten.
            assert ps.comms[0].shutdowns >= 1
        finally:
            ps.shutdown()

    def test_live_session_not_reaped_before_timeout(self):
        import urllib.request

        ps = StuckPS(session_timeout_sec=30.0, reap_interval_sec=0.05)
        try:
            with urllib.request.urlopen(ps.address(), timeout=10):
                pass
            assert wait_for(
                lambda: ps.status()["active_sessions"] == 1, timeout=5)
            time.sleep(0.3)  # several reap scans
            st = ps.status()
            assert st["sessions_reaped"] == 0
            assert st["active_sessions"] == 1
            assert st["oldest_session_age_s"] > 0.0
        finally:
            ps.shutdown()

    def test_active_session_exempt_from_reap(self):
        """A session that reached forward() is a legitimate long-lived
        collective loop: the age-based reaper must leave it alone (its
        liveness is the communicator timeout's job)."""
        import urllib.request

        ps = LongForwardPS(session_timeout_sec=0.2, reap_interval_sec=0.05)
        try:
            with urllib.request.urlopen(ps.address(), timeout=10):
                pass
            assert wait_for(
                lambda: ps.status()["active_sessions"] == 1, timeout=5)
            time.sleep(0.6)  # several timeouts past the session's age
            st = ps.status()
            assert st["sessions_reaped"] == 0
            assert st["active_sessions"] == 1
            ps.comms[0].shutdown()  # let the session thread exit
            assert wait_for(
                lambda: ps.status()["active_sessions"] == 0, timeout=5)
            assert ps.status()["sessions_reaped"] == 0
        finally:
            ps.shutdown()

    def test_status_endpoint(self):
        import urllib.request

        ps = StuckPS(session_timeout_sec=30.0)
        try:
            addr = ps.address().replace("/new_session", "/status.json")
            with urllib.request.urlopen(addr, timeout=10) as resp:
                st = json.loads(resp.read())
            assert st["active_sessions"] == 0
            assert st["sessions_total"] == 0
            assert st["sessions_reaped"] == 0
            assert st["session_timeout_sec"] == 30.0
        finally:
            ps.shutdown()
