"""Unit battery for the shared transport substrate (tier-1).

``torchft_tpu/transport.py`` is the narrow waist every HTTP byte path
rides (docs/design/transport_substrate.md): ONE pooled ranged fetch
client, ONE ranged/bearer server core on a single asyncio loop, ONE
stripe-geometry source, ONE retry classification table, and weighted
per-path QoS. These tests pin the substrate's own contracts — the tier
suites (checkpointing/serving/ram_ckpt) pin the protocols built on it.
"""

import json
import os
import socket
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from torchft_tpu import chaos, transport
from torchft_tpu.chaos import parse_spec
from torchft_tpu.communicator import shard_bounds
from torchft_tpu.transport import (
    ConnectionPool,
    PushRejectedError,
    QOS_WEIGHTS,
    QoS,
    QoSScheduler,
    chunk_spans,
    classify,
    fetch_json,
    looks_peer_dead,
    push_ranged,
    qos_for_request,
    qos_from_header,
    serve_http,
    serve_ranged_bytes,
    serve_ranged_file,
)

pytestmark = pytest.mark.substrate


def _serve(route):
    srv = serve_http("127.0.0.1", 0, route, name="substrate-test")
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


class TestGeometry:
    def test_chunk_spans_is_shard_bounds(self):
        total, max_chunk = 10_000_001, 1 << 20
        spans = chunk_spans(total, max_chunk)
        n = -(-total // max_chunk)  # same COUNT as ceil-division loops
        assert len(spans) == n
        b = shard_bounds(total, n)
        assert spans == [(int(b[i]), int(b[i + 1])) for i in range(n)]

    def test_spans_cover_and_balance(self):
        spans = chunk_spans(1000, 300)
        assert spans[0][0] == 0 and spans[-1][1] == 1000
        for (_, e0), (s1, _) in zip(spans, spans[1:]):
            assert e0 == s1
        sizes = [e - s for s, e in spans]
        assert all(sz <= 300 for sz in sizes)
        # balanced: never the runt a naive range() tail produces
        assert max(sizes) - min(sizes) <= 1

    def test_base_offset_and_empty(self):
        assert chunk_spans(0, 100) == []
        assert chunk_spans(-5, 100) == []
        spans = chunk_spans(10, 4, base=100)
        assert spans[0][0] == 100 and spans[-1][1] == 110


class TestClassification:
    def test_http_503_window_vs_shutdown(self):
        def err(code, reason):
            return urllib.error.HTTPError("http://x", code, reason, {},
                                          None)
        assert classify(err(503, "serve window closed (commit)")) is True
        assert classify(err(503, "shutting down")) is False
        assert classify(err(404, "unknown step")) is False

    def test_registered_types_take_precedence(self):
        class _Fatal(RuntimeError):
            pass

        class _Soft(RuntimeError):
            pass

        transport.register_fatal(_Fatal)
        transport.register_transient(_Soft)
        assert classify(_Fatal("x")) is False
        assert classify(_Soft("x")) is True
        # the tiers' registrations landed at import time
        from torchft_tpu.checkpoint_io import CheckpointCorruptError
        from torchft_tpu.checkpointing import (HealCorruptError,
                                               LeafDigestError)
        assert classify(HealCorruptError("bad donor")) is False
        assert classify(CheckpointCorruptError("torn")) is False
        assert classify(LeafDigestError("leaf 3 crc")) is True

    def test_looks_peer_dead_walks_wrappers(self):
        inner = ConnectionRefusedError(111, "Connection refused")
        wrapped = urllib.error.URLError(inner)
        assert looks_peer_dead(wrapped) is True
        assert looks_peer_dead(TimeoutError("slow")) is False


class TestQoS:
    def test_header_and_route_defaults(self):
        assert qos_from_header("heal", QoS.DEMOTION) is QoS.HEAL
        # unknown and RING (never carried over HTTP) fall to the default
        assert qos_from_header("bogus", QoS.HEAL) is QoS.HEAL
        assert qos_from_header("ring", QoS.HEAL) is QoS.HEAL
        assert qos_for_request("GET", "/publish/3", {}) is QoS.PUBLICATION
        assert qos_for_request("PUT", "/ramckpt/7", {}) is QoS.DEMOTION
        assert qos_for_request("GET", "/checkpoint/3", {}) is QoS.HEAL
        hdrs = transport._Headers(
            {transport.QOS_HEADER.lower(): "publication"})
        assert qos_for_request("GET", "/checkpoint/3",
                               hdrs) is QoS.PUBLICATION

    def test_weighted_fairness_under_contention(self):
        """With every class fully backlogged, per-round grants track the
        DRR weights exactly: the moment the highest class drains its
        queue, each lower class has completed ~weight-proportionally
        many chunks — the saturating-publication leg can slow a heal,
        never starve it (and vice versa)."""
        import asyncio

        done = {c: 0 for c in QoS}
        per_class = 64  # chunks queued per class up front

        async def drive():
            sched = QoSScheduler(transport._Counters())
            chunk = QoSScheduler.QUANTUM  # 1 deficit quantum per chunk

            async def one(c):
                await sched.grant(c, chunk)
                done[c] += 1

            tasks = [asyncio.get_event_loop().create_task(one(c))
                     for c in QoS for _ in range(per_class)]
            while done[QoS.RING] < per_class:
                await asyncio.sleep(0)
            snapshot = dict(done)
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            if sched._pump_task is not None:
                sched._pump_task.cancel()
                await asyncio.gather(sched._pump_task,
                                     return_exceptions=True)
            return snapshot

        snap = asyncio.new_event_loop().run_until_complete(drive())
        # RING (weight 8) drained first; every class made progress —
        # nobody starved while the highest class saturated the plane.
        assert snap[QoS.RING] == per_class
        assert all(snap[c] > 0 for c in QoS)
        # Completion ratios track weights (1-round slack for the
        # snapshot landing mid-round).
        rounds = per_class / QOS_WEIGHTS[QoS.RING]
        for c in (QoS.HEAL, QoS.PUBLICATION, QoS.DEMOTION):
            expect = rounds * QOS_WEIGHTS[c]
            assert abs(snap[c] - expect) <= QOS_WEIGHTS[c] + 1, (
                f"{c.name}: {snap[c]} vs expected ~{expect}")
        # strict ordering under full backlog
        assert snap[QoS.HEAL] > snap[QoS.PUBLICATION] > \
            snap[QoS.DEMOTION]


class TestServerCore:
    def test_pool_reuse_avoids_redial(self):
        def route(h):
            body = b"ok"
            h.send_response(200)
            h.send_header("Content-Length", "2")
            h.end_headers()
            h.wfile.write(body)

        srv, base = _serve(route)
        pool = ConnectionPool()
        try:
            for _ in range(3):
                with pool.request(f"{base}/x", 5.0, None) as r:
                    assert r.read() == b"ok"
            assert pool.redials == 1
            assert pool.redials_avoided == 2
        finally:
            pool.close()
            srv.shutdown()
            srv.server_close()

    def test_ranged_bytes_200_206_416(self):
        payload = bytes(range(256)) * 40
        view = memoryview(payload)

        def route(h):
            serve_ranged_bytes(h, view, 10.0)

        srv, base = _serve(route)
        pool = ConnectionPool()
        try:
            with pool.request(f"{base}/img", 5.0, None) as r:
                assert r.read() == payload
            with pool.request(f"{base}/img", 5.0, None,
                              headers={"Range": "bytes=100-199"}) as r:
                assert r.status == 206
                assert r.headers["Content-Range"] == \
                    f"bytes 100-199/{len(payload)}"
                assert r.read() == payload[100:200]
            with pytest.raises(urllib.error.HTTPError) as ei:
                pool.request(f"{base}/img", 5.0, None,
                             headers={"Range": f"bytes={len(payload)}-"})
            assert ei.value.code == 416
        finally:
            pool.close()
            srv.shutdown()
            srv.server_close()

    @pytest.mark.parametrize("native", [True, False])
    def test_large_body_chunks_cross_in_one_call_a_side(self, native,
                                                        monkeypatch):
        """A body chunk of NATIVE_BODY_BYTES or more is written by the
        handler's thread and read by the client in one foreign call each
        (no socket call of the interpreter's a piece the kernel moves);
        the bytes, the class's byte count, the small reads between the
        large ones and the connection's reuse are what they are without
        the native core."""
        from torchft_tpu import _native
        if not native:
            monkeypatch.setattr(_native, "sock_core", lambda: None)
        elif _native.sock_core() is None:
            pytest.skip("no native core")
        chunk = 3 * transport.NATIVE_BODY_BYTES
        rng = np.random.default_rng(59)
        payload = rng.integers(0, 256, 4 * chunk + 12_345,
                               dtype=np.uint8).tobytes()
        view = memoryview(payload)

        def route(h):
            h.send_response(200)
            h.send_header("Content-Length", str(len(payload)))
            h.end_headers()
            for a in range(0, len(payload), chunk):
                h.wfile.write(view[a:a + chunk])

        calls = {"recv": 0}
        real = socket.SocketIO.readinto

        def counted(self, b):
            calls["recv"] += 1
            return real(self, b)

        srv, base = _serve(route)
        pool = ConnectionPool()
        before = transport.metrics()["transport_qos_heal_bytes_total"]
        try:
            for _ in range(2):      # the second on the pooled connection
                got = bytearray(len(payload))
                mv = memoryview(got)
                with pool.request(f"{base}/x", 5.0, None) as r:
                    monkeypatch.setattr(socket.SocketIO, "readinto",
                                        counted)
                    calls["recv"] = 0
                    at = 0
                    # large, small, large ... : a small read fills the
                    # response's buffered reader again, which the next
                    # large one has to empty first.
                    for n in (chunk, 7, chunk, 100, 2 * chunk):
                        k = 0
                        while k < n:
                            step = r.readinto(mv[at + k:at + n])
                            assert step
                            k += step
                        at += n
                    tail = r.read()
                    monkeypatch.setattr(socket.SocketIO, "readinto", real)
                    got[at:] = tail
                    assert r.readinto(bytearray(8)) == 0
                assert bytes(got) == payload
                if native:
                    # at most the one buffer-sized read ahead of a large
                    # read, and the tail's
                    assert calls["recv"] < 40, calls
            assert pool.redials == 1 and pool.redials_avoided == 1
            # the handler's thread counts its bytes after its write
            # returns: the client can have read them all before that
            # (seen under the suite's six workers, PR 60)
            deadline = time.monotonic() + 5.0
            while True:
                after = transport.metrics()[
                    "transport_qos_heal_bytes_total"]
                if (after - before >= 2 * len(payload)
                        or time.monotonic() > deadline):
                    break
                time.sleep(0.01)
            assert after - before >= 2 * len(payload)
        finally:
            pool.close()
            srv.shutdown()
            srv.server_close()

    def test_large_body_to_a_client_that_went_away_fails_the_handler(self):
        """A direct write to a peer that hung up raises in the handler,
        as a queued one's failure did at its next write."""
        from torchft_tpu import _native
        if _native.sock_core() is None:
            pytest.skip("no native core")
        chunk = memoryview(bytes(4 * transport.NATIVE_BODY_BYTES))
        seen = []

        def route(h):
            h.connection.settimeout(2.0)
            h.send_response(200)
            h.send_header("Content-Length", str(64 * len(chunk)))
            h.end_headers()
            try:
                for _ in range(64):
                    h.wfile.write(chunk)
            except (ConnectionError, socket.timeout) as e:
                seen.append(e)
                raise

        srv, base = _serve(route)
        try:
            host, port = srv.server_address[:2]
            c = socket.create_connection((host, port), 5.0)
            c.sendall(b"GET /x HTTP/1.1\r\nHost: x\r\n\r\n")
            assert c.recv(1 << 16)
            c.close()
            deadline = time.monotonic() + 10
            while not seen and time.monotonic() < deadline:
                time.sleep(0.01)
            assert seen and isinstance(seen[0], (ConnectionError,
                                                 socket.timeout))
        finally:
            srv.shutdown()
            srv.server_close()

    def test_bearer_gate(self):
        def route(h):
            if not transport.check_bearer_auth(h, "s3cret"):
                return
            h.send_response(200)
            h.send_header("Content-Length", "2")
            h.end_headers()
            h.wfile.write(b"in")

        srv, base = _serve(route)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                fetch_json(f"{base}/x", stall=5.0)
            assert ei.value.code == 401
            req = urllib.request.Request(
                f"{base}/x",
                headers={"Authorization": "Bearer s3cret"})
            with urllib.request.urlopen(req, timeout=5) as r:
                assert r.read() == b"in"
        finally:
            srv.shutdown()
            srv.server_close()

    def test_sendfile_path_serves_and_counts(self):
        payload = os.urandom(1 << 20)
        f = tempfile.NamedTemporaryFile()
        f.write(payload)
        f.flush()
        fobj = open(f.name, "rb")

        def route(h):
            serve_ranged_file(h, fobj, len(payload), 10.0)

        before = transport.metrics()["transport_sendfile_bytes_total"]
        srv, base = _serve(route)
        pool = ConnectionPool()
        try:
            with pool.request(f"{base}/f", 5.0, None,
                              headers={"Range": "bytes=4096-8191"}) as r:
                assert r.read() == payload[4096:8192]
            if transport.async_hosting_enabled():
                # The drain task bumps the counter after the kernel
                # send — the client can observe the bytes first.
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    after = transport.metrics()[
                        "transport_sendfile_bytes_total"]
                    if after - before >= 4096:
                        break
                    time.sleep(0.01)
                assert after - before >= 4096
        finally:
            pool.close()
            srv.shutdown()
            srv.server_close()
            fobj.close()
            f.close()

    def test_push_ranged_faults_progress_and_422(self):
        got = {}
        reject = {"on": False}

        def route(h):
            if reject["on"]:
                h.send_error(422, "digest mismatch")
                return
            n = int(h.headers.get("Content-Length", "0"))
            body = h.rfile.read(n)
            rng = h.headers.get("Content-Range")
            got[rng] = body
            h.send_response(200)
            h.send_header("Content-Length", "0")
            h.end_headers()

        srv, base = _serve(route)
        payload = memoryview(os.urandom(100_000))
        faults, deltas = [], []
        try:
            pushed = push_ranged(
                base, "/ramckpt/7", payload, chunk_bytes=30_000,
                fault=lambda: faults.append(1),
                progress=deltas.append)
            assert pushed == len(payload)
            # one fault hook + one progress tick per chunk_spans chunk
            n_chunks = len(chunk_spans(len(payload), 30_000))
            assert len(faults) == n_chunks
            assert sum(deltas) == len(payload)
            assert b"".join(
                got[k] for k in sorted(
                    got, key=lambda r: int(r.split()[1].split("-")[0]))
            ) == bytes(payload)
            reject["on"] = True
            with pytest.raises(PushRejectedError):
                push_ranged(base, "/ramckpt/8", payload,
                            chunk_bytes=30_000)
        finally:
            srv.shutdown()
            srv.server_close()

    def test_metrics_keys_frozen(self):
        m = transport.metrics()
        assert set(m) == {
            "transport_qos_ring_bytes_total",
            "transport_qos_heal_bytes_total",
            "transport_qos_publication_bytes_total",
            "transport_qos_demotion_bytes_total",
            "transport_qos_waits_total",
            "transport_conns_total",
            "transport_requests_total",
            "transport_sendfile_bytes_total",
        }
        assert all(isinstance(v, float) for v in m.values())


class TestChaosSeam:
    """The chaos ``serve:``/``heal:`` channels keep working injected at
    the substrate seam: client-side begin/wrap_reader at the fetch
    sites, endpoint_reborn at the (now substrate-hosted) server bind."""

    def _state(self):
        return {"w": np.arange(64, dtype=np.float32),
                "b": np.ones((8, 8), dtype=np.float32)}

    def test_heal_kill_latch_and_rebirth_through_substrate(self):
        from torchft_tpu.checkpointing import CheckpointServer

        state = self._state()
        chaos.install(parse_spec("seed=3;heal:latency_ms=0"))
        try:
            srv = CheckpointServer(lambda: state, bind_host="127.0.0.1")
            srv.allow_checkpoint(1)
            addr = srv.address()
            netloc = addr.split("//")[1].split("/")[0]
            port = int(netloc.rsplit(":", 1)[1])
            sched = chaos.active()
            sched.kill_endpoint(f"heal:{netloc}")
            with pytest.raises(Exception) as ei:
                CheckpointServer.load_from_address(
                    addr, self._state(), device_put=False)
            assert looks_peer_dead(ei.value) or "refused" in \
                str(ei.value).lower() or "killed" in str(ei.value).lower()
            srv.shutdown()
            # A replacement binding the same port must not inherit the
            # dead latch — the rebirth call survives the hosting swap.
            srv2 = CheckpointServer(lambda: state, bind_host="127.0.0.1",
                                    bind_port=port)
            try:
                srv2.allow_checkpoint(1)
                got = CheckpointServer.load_from_address(
                    srv2.address(), self._state(), device_put=False)
                np.testing.assert_array_equal(got["w"], state["w"])
            finally:
                srv2.shutdown()
        finally:
            chaos.uninstall()

    def test_serve_short_reads_never_place_bad_bytes(self):
        """crc-verify-before-place at the seam: a publication subscriber
        fed short/reset streams retries until verified, and the placed
        weights are bitwise-identical — torn bytes never surface."""
        from torchft_tpu.retry import RetryPolicy
        from torchft_tpu.serving import (PublicationServer,
                                         WeightPublisher,
                                         WeightSubscriber)

        state = self._state()
        pub = WeightPublisher()
        srv = PublicationServer(pub, bind_host="127.0.0.1")
        netloc = srv.address().split("//")[1].split("/")[0]
        chaos.install(parse_spec(
            f"seed=11;serve:short_rate=0.4,max_faults=4"))
        sub = None
        try:
            pub.publish(state, step=1)
            sub = WeightSubscriber(
                srv.address(), self._state(),
                retry_policy=RetryPolicy(max_attempts=8,
                                         base_delay_ms=10.0,
                                         max_delay_ms=50.0))
            assert sub.sync() is True
            got = sub.weights()
            np.testing.assert_array_equal(got["w"], state["w"])
            np.testing.assert_array_equal(got["b"], state["b"])
        finally:
            chaos.uninstall()
            srv.shutdown()


class TestHealWireCompatibility:
    """The overlapped heal (docs/design/healing.md) changes neither the
    manifest, nor the stream's bytes, nor the HTTP surface: a healer of
    the serial build heals from this donor, and this healer from a donor
    of the serial build. The serial ends are spelled out here as the
    protocol has them: `/manifest`, then a Range GET from the first
    missing byte, each leaf read whole, digested, compared."""

    @staticmethod
    def _state():
        import jax.numpy as jnp

        rng = np.random.RandomState(5)
        # one leaf wider than a fetch batch (cut into runs of rows by
        # the donor), small ones around it, a scalar
        return {"a": jnp.asarray(rng.rand(300, 7).astype(np.float32)),
                "m": jnp.asarray(rng.rand(7000, 1031).astype(np.float32)),
                "z": [np.arange(1000, dtype=np.int32),
                      jnp.ones((64,), jnp.bfloat16)],
                "step": 9}

    def test_a_serial_healer_heals_from_this_donor(self):
        import zlib

        from torchft_tpu.checkpointing import CheckpointServer
        from torchft_tpu.serialization import (DEFAULT_BATCH_BYTES,
                                               plan_pytree)

        state = self._state()
        assert state["m"].nbytes > DEFAULT_BATCH_BYTES
        leaves = plan_pytree(state).array_leaves
        srv = CheckpointServer(lambda: state, bind_host="127.0.0.1")
        try:
            srv.allow_checkpoint(4)
            with urllib.request.urlopen(srv.address() + "/manifest",
                                        timeout=30) as resp:
                mf = json.loads(resp.read())
            assert sorted(mf) == ["digest", "format", "leaves",
                                  "preamble_len", "step", "total_len"]
            arrays = [e for e in mf["leaves"] if e["kind"] == "array"]
            assert [sorted(e) for e in arrays] == [
                ["crc32", "dtype", "key", "kind", "nbytes", "offset",
                 "shape"]] * len(leaves)
            req = urllib.request.Request(
                srv.address(),
                headers={"Range": f"bytes={mf['preamble_len']}-"
                                  f"{mf['total_len'] - 1}"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 206
                assert resp.headers["Content-Range"] == (
                    f"bytes {mf['preamble_len']}-{mf['total_len'] - 1}"
                    f"/{mf['total_len']}")
                for entry, leaf in zip(arrays, leaves):
                    buf = bytearray(entry["nbytes"])
                    view, got = memoryview(buf), 0
                    while got < len(buf):
                        n = resp.readinto(view[got:])
                        assert n, "truncated"
                        got += n
                    assert zlib.crc32(buf) == entry["crc32"]
                    assert bytes(buf) == np.asarray(leaf).tobytes()
                assert resp.read() == b""
        finally:
            srv.shutdown()

    def test_this_healer_heals_from_a_serial_donor(self):
        import re
        import zlib
        from http.server import BaseHTTPRequestHandler, HTTPServer

        import jax

        from torchft_tpu.checkpointing import CheckpointServer
        from torchft_tpu.serialization import plan_pytree

        state = self._state()
        plan = plan_pytree(state)
        blobs = [np.asarray(leaf).tobytes() for leaf in plan.array_leaves]
        payload = plan.preamble + b"".join(blobs)
        crcs = iter(zlib.crc32(b) for b in blobs)
        manifest = json.dumps({
            "format": "tft-manifest-1", "step": 4, "digest": "crc32",
            "preamble_len": len(plan.preamble),
            "total_len": len(payload),
            "leaves": [dict(e, crc32=next(crcs)) if e["kind"] == "array"
                       else e for e in plan.header["leaves"]],
        }).encode()

        class SerialDonor(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                if self.path == "/checkpoint/4/manifest":
                    body, status, extra = manifest, 200, {}
                else:
                    assert self.path == "/checkpoint/4"
                    m = re.match(r"bytes=(\d+)-(\d+)$",
                                 self.headers["Range"])
                    lo, hi = int(m.group(1)), int(m.group(2)) + 1
                    body, status = payload[lo:hi], 206
                    extra = {"Content-Range":
                             f"bytes {lo}-{hi - 1}/{len(payload)}"}
                self.send_response(status)
                self.send_header("Content-Length", str(len(body)))
                for k, v in extra.items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

        server = HTTPServer(("127.0.0.1", 0), SerialDonor)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        try:
            stats = {}
            got = CheckpointServer.load_from_address(
                f"http://127.0.0.1:{server.server_port}/checkpoint/4",
                state, stats=stats, stall_timeout_sec=30)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(state)):
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
            assert got["step"] == 9
            assert stats["attempts"] == 1
            assert stats["bytes"] == len(payload) - len(plan.preamble)
        finally:
            server.shutdown()
            server.server_close()
