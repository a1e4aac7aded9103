"""Checkpoint transfer tests (reference checkpointing semantics:
step gating, live lazy state, 400 on step mismatch —
/root/reference/torchft/checkpointing.py) plus the resilient-heal
protocol: manifest + digests, HTTP Range resume, donor failover, and
the stall watchdog."""

import io
import json
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import torchft_tpu.checkpointing as checkpointing
from torchft_tpu.checkpointing import CheckpointServer, HealCorruptError
from torchft_tpu.retry import RetryPolicy
from torchft_tpu.serialization import (
    iter_pytree_chunks,
    load_pytree,
    load_pytree_from,
    plan_pytree,
    save_pytree,
)


def tree_equal(a, b):
    import jax

    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestSerialization:
    def test_round_trip(self):
        tree = {
            "params": {
                "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                "b": jnp.ones((4,), dtype=jnp.bfloat16),
            },
            "opt": [jnp.zeros((2, 2)), np.int64(7)],
            "step": 42,
            "name": "model",
            "flag": True,
            "none": None,
        }
        data = save_pytree(tree)
        restored = load_pytree(data, tree)
        tree_equal(restored, tree)
        assert restored["step"] == 42
        assert restored["name"] == "model"
        assert restored["none"] is None

    def test_structure_mismatch_fails(self):
        data = save_pytree({"a": np.ones(3)})
        with pytest.raises(ValueError, match="does not match|leaves"):
            load_pytree(data, {"b": np.ones(3)})
        with pytest.raises(ValueError):
            load_pytree(data, {"a": np.ones(3), "c": np.ones(1)})

    def test_bad_magic(self):
        with pytest.raises(ValueError, match="not a torchft_tpu"):
            load_pytree(b"garbage_bytes_here", {"a": np.ones(1)})

    def test_truncated_stream_fails(self):
        data = save_pytree({"a": np.ones(100, dtype=np.float64)})
        with pytest.raises(ValueError, match="truncated"):
            load_pytree(data[:-17], {"a": np.ones(100)})

    def test_untrusted_header_rejected(self):
        # The header comes from a peer: shape, dtype, and kind claims must
        # all be validated against the target before any allocation, so a
        # malicious/corrupt server can neither OOM the healer nor swap a
        # weight tensor for a scalar.
        import json

        def forge(mutate):
            data = bytearray(save_pytree({"w": np.ones(4, np.float32)}))
            hdr_len = int.from_bytes(data[8:12], "little")
            header = json.loads(bytes(data[12:12 + hdr_len]))
            mutate(header["leaves"][0])
            new_hdr = json.dumps(header).encode()
            return (bytes(data[:8]) + len(new_hdr).to_bytes(4, "little")
                    + new_hdr + bytes(data[12 + hdr_len:]))

        target = {"w": np.ones(4, np.float32)}
        with pytest.raises(ValueError, match="shape"):
            load_pytree(forge(lambda e: e.update(shape=[10 ** 12])), target)
        with pytest.raises(ValueError, match="dtype"):
            load_pytree(forge(lambda e: e.update(dtype="complex128")), target)
        with pytest.raises(ValueError, match="py value"):
            load_pytree(
                forge(lambda e: (e.clear(),
                                 e.update(key="w", kind="py", value=0))),
                target)
        with pytest.raises(ValueError, match="implausibly large"):
            from torchft_tpu.serialization import load_pytree_from
            import io as _io
            bad = b"TFTPTREE" + (0xFFFFFFFF).to_bytes(4, "little") + b"x"
            load_pytree_from(_io.BytesIO(bad), target)


class TestStreaming:
    def test_chunks_concat_to_save_pytree(self):
        tree = {
            "w": jnp.arange(5000, dtype=jnp.float32).reshape(50, 100),
            "b": jnp.ones((7,), dtype=jnp.bfloat16),
            "step": 9,
        }
        chunks = list(iter_pytree_chunks(tree, chunk_bytes=1024))
        assert len(chunks) > 5  # the big leaf really was split
        data = b"".join(chunks)
        _, total_len, _ = plan_pytree(tree)
        assert len(data) == total_len  # Content-Length promise holds
        restored = load_pytree_from(io.BytesIO(data), tree)
        tree_equal(restored, tree)
        assert restored["step"] == 9

    def test_plan_fetches_no_data(self):
        # plan_pytree must be metadata-only: an aval-backed tracer-free
        # shape/dtype is enough. A jax array never leaves the device here.
        tree = {"x": jnp.zeros((128, 128), dtype=jnp.bfloat16), "tag": "t"}
        preamble, total_len, leaves = plan_pytree(tree)
        assert total_len == len(preamble) + 128 * 128 * 2
        assert len(leaves) == 1

    def test_transfer_rss_bounded(self):
        """Healing-path RAM ceiling: serving + fetching a checkpoint must
        not buffer the full payload on either side (verdict #5). Runs in a
        subprocess so the RSS high-water mark is clean, with the server and
        the healer sharing the process: extra peak RSS over (state +
        restored copy) must be a few leaves, not another full copy."""
        total_mb = 256
        script = f"""
import resource, sys, numpy as np
from torchft_tpu.checkpointing import CheckpointServer

RSS_UNIT = 1 if sys.platform == "darwin" else 1024  # macOS: bytes, linux: KB

LEAF = 8 * 1024 * 1024  # 8MB float32 leaves
N = {total_mb} * 1024 * 1024 // (LEAF)
state = {{f"w{{i}}": np.random.rand(LEAF // 8).astype(np.float64)
         for i in range(N)}}
total = sum(a.nbytes for a in state.values())
base = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * RSS_UNIT
server = CheckpointServer(lambda: state)
server.allow_checkpoint(1)
restored = CheckpointServer.load_from_address(
    server.address(), state, device_put=False)
server.shutdown()
peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * RSS_UNIT
delta = peak - base
# restored copy is 1.0x total; allow 0.5x slack for chunk buffers and
# allocator noise. A monolithic bytes round-trip needs >= 2.0x.
assert delta < 1.5 * total, (
    f"transfer peak RSS {{delta/1e6:.0f}}MB exceeds "
    f"{{1.5 * total / 1e6:.0f}}MB ceiling for a {{total/1e6:.0f}}MB state")
for k, v in state.items():
    np.testing.assert_array_equal(restored[k], v)
print(f"rss delta {{delta/1e6:.0f}}MB for {{total/1e6:.0f}}MB state")
"""
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=300)
        assert proc.returncode == 0, proc.stderr + proc.stdout


class TestCheckpointServer:
    def test_serve_and_load(self):
        state = {"w": np.arange(10, dtype=np.float32), "step": 3}
        server = CheckpointServer(lambda: state)
        try:
            server.allow_checkpoint(3)
            restored = CheckpointServer.load_from_address(
                server.address(), state, device_put=False)
            tree_equal(restored, state)
        finally:
            server.shutdown()

    def test_step_mismatch_is_400(self):
        server = CheckpointServer(lambda: {"x": np.ones(1)})
        try:
            server.allow_checkpoint(5)
            addr = server.address().replace("/checkpoint/5", "/checkpoint/4")
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(addr, timeout=10)
            assert exc_info.value.code == 400
        finally:
            server.shutdown()

    def test_auth_token_gates_serving(self):
        """With auth_token set, un/badly-authenticated GETs are 401 and
        leak nothing; load_from_address with the token succeeds (VERDICT
        r3 weak #6: weights must not stream to anyone who can connect)."""
        state = {"w": np.arange(4, dtype=np.float32)}
        server = CheckpointServer(lambda: state, auth_token="tok123")
        try:
            server.allow_checkpoint(1)
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(server.address(), timeout=10)
            assert exc_info.value.code == 401
            req = urllib.request.Request(
                server.address(),
                headers={"Authorization": "Bearer wrong"})
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(req, timeout=10)
            assert exc_info.value.code == 401
            restored = CheckpointServer.load_from_address(
                server.address(), state, device_put=False,
                auth_token="tok123")
            tree_equal(restored, state)
        finally:
            server.shutdown()

    def test_bind_host_localhost(self):
        server = CheckpointServer(lambda: {"x": np.ones(1)},
                                  bind_host="127.0.0.1")
        try:
            server.allow_checkpoint(1)
            host_port = server.address().split("//")[1].split("/")[0]
            addr = f"http://127.0.0.1:{host_port.rsplit(':', 1)[1]}" \
                   "/checkpoint/1"
            restored = CheckpointServer.load_from_address(
                addr, {"x": np.ones(1)}, device_put=False)
            np.testing.assert_array_equal(restored["x"], np.ones(1))
        finally:
            server.shutdown()

    def test_serves_live_state(self):
        """State is read lazily at GET time, not at allow time."""
        state = {"v": np.zeros(2)}
        server = CheckpointServer(lambda: state)
        try:
            server.allow_checkpoint(1)
            state["v"] = np.full(2, 9.0)  # mutate after allow
            restored = CheckpointServer.load_from_address(
                server.address(), state, device_put=False)
            np.testing.assert_array_equal(restored["v"], np.full(2, 9.0))
        finally:
            server.shutdown()

    def test_disallow_blocks_serving(self):
        server = CheckpointServer(lambda: {"x": np.ones(1)})
        try:
            server.allow_checkpoint(1)
            addr = server.address()
            server.disallow_checkpoint()

            result = {}

            def fetch():
                try:
                    result["data"] = CheckpointServer.load_from_address(
                        addr, {"x": np.ones(1)}, timeout_sec=10,
                        device_put=False)
                except Exception as e:  # noqa: BLE001
                    result["err"] = e

            t = threading.Thread(target=fetch)
            t.start()
            t.join(timeout=0.5)
            assert t.is_alive(), "fetch should block while disallowed"
            server.allow_checkpoint(1)  # reopen the window
            t.join(timeout=10)
            assert not t.is_alive()
            assert "data" in result
        finally:
            server.shutdown()

    def _slow_healer_socket(self, address):
        """Open a raw HTTP GET and read only the first few KB, leaving the
        server's stream blocked on socket backpressure (a throttled
        healer)."""
        import socket
        import urllib.parse

        u = urllib.parse.urlparse(address)
        s = socket.create_connection((u.hostname, u.port), timeout=60)
        s.sendall(f"GET {u.path} HTTP/1.0\r\nHost: h\r\n\r\n".encode())
        first = s.recv(4096)
        assert b"200" in first.split(b"\r\n", 1)[0], first
        return s, first

    def test_commit_never_waits_for_slow_healer(self):
        """VERDICT r2 #3: the donor's commit must not stall behind an
        in-flight heal download. The stream serves an on-device snapshot,
        so disallow_checkpoint returns immediately and the commit-time
        donated optimizer update cannot corrupt what the healer receives —
        the payload stays the bitwise pre-commit state."""
        import time

        import jax

        state = {"w": jnp.arange(1 << 22, dtype=jnp.float32)}  # 16 MB
        holder = {"state": state}
        expected_body = None
        server = CheckpointServer(lambda: holder["state"])
        try:
            server.allow_checkpoint(1)
            expected_body = save_pytree(state)
            s, buf = self._slow_healer_socket(server.address())
            # Donor commits while the healer is mid-download: must not
            # block (the reference would wait out the whole transfer here).
            t0 = time.perf_counter()
            server.disallow_checkpoint()
            commit_wait = time.perf_counter() - t0
            assert commit_wait < 0.5, f"commit stalled {commit_wait:.2f}s"
            # The commit-time update donates the old buffers (optim.py
            # donate_argnums) — the served snapshot must survive it.
            bump = jax.jit(lambda t: jax.tree_util.tree_map(
                lambda a: a + 1, t), donate_argnums=(0,))
            holder["state"] = bump(holder["state"])
            # Healer finishes its download; bytes are the pre-commit state.
            while True:
                b = s.recv(1 << 16)
                if not b:
                    break
                buf += b
            s.close()
            body = buf.split(b"\r\n\r\n", 1)[1]
            assert body == expected_body
        finally:
            server.shutdown()

    def test_lock_streaming_mode_blocks_commit(self):
        """lock_streaming=True restores the reference's discipline for
        memory-tight donors: disallow_checkpoint drains in-flight GETs."""
        import time

        state = {"w": jnp.arange(1 << 22, dtype=jnp.float32)}  # 16 MB
        server = CheckpointServer(lambda: state, lock_streaming=True)
        try:
            server.allow_checkpoint(1)
            s, buf = self._slow_healer_socket(server.address())
            done = threading.Event()

            def commit():
                server.disallow_checkpoint()
                done.set()

            t = threading.Thread(target=commit)
            t.start()
            assert not done.wait(timeout=0.3), (
                "disallow returned while a lock_streaming GET was in flight")
            while True:  # drain the stream; disallow must then complete
                b = s.recv(1 << 16)
                if not b:
                    break
            s.close()
            assert done.wait(timeout=10)
            t.join()
        finally:
            server.shutdown()

    def test_double_allow_and_double_disallow(self):
        server = CheckpointServer(lambda: {"x": np.ones(1)})
        try:
            server.allow_checkpoint(1)
            server.allow_checkpoint(2)  # idempotent-ish: moves the window
            server.disallow_checkpoint()
            server.disallow_checkpoint()  # no deadlock / double-acquire
            server.allow_checkpoint(3)
            restored = CheckpointServer.load_from_address(
                server.address(), {"x": np.ones(1)}, device_put=False)
            assert restored["x"].shape == (1,)
        finally:
            server.shutdown()


class _FlakyProxy:
    """Deterministic TCP proxy in front of a CheckpointServer, injecting
    exactly one data-stream fault (manifest requests pass through):

    * ``cut``   — forward ``fault_after`` body bytes of the first data
                  response, then close the connection (mid-stream reset);
    * ``stall`` — forward ``fault_after`` body bytes, then go silent
                  while holding the socket open (a black-holed stream);
    * ``die``   — like ``cut``, but also stop listening: every later
                  dial is refused, the way a dead donor process behaves;
    * ``flip``  — flip one byte at body offset ``flip_at`` and keep
                  streaming (in-transit corruption a digest must catch).

    After the fault fires once, later connections pass through clean
    (except ``die``)."""

    def __init__(self, upstream_url: str, mode: str = "cut",
                 fault_after: int = 1 << 60, flip_at: int = -1,
                 persistent: bool = False) -> None:
        u = urllib.parse.urlparse(upstream_url)
        self._up = (u.hostname, u.port)
        self._mode = mode
        self._fault_after = fault_after
        self._flip_at = flip_at
        self._persistent = persistent
        self._fired = False
        self._lock = threading.Lock()
        self._ls = socket.socket()
        self._ls.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._ls.bind(("127.0.0.1", 0))
        self._ls.listen(32)
        self.port = self._ls.getsockname()[1]
        threading.Thread(target=self._serve, daemon=True).start()

    def address(self, step: int) -> str:
        return f"http://127.0.0.1:{self.port}/checkpoint/{step}"

    def close(self) -> None:
        # shutdown() first: a bare close() leaves the accept() blocked in
        # another thread holding the open file description alive, so the
        # port would KEEP accepting — shutdown wakes it and refuses new
        # dials immediately (the dead-donor behavior 'die' mode needs).
        try:
            self._ls.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._ls.close()
        except OSError:
            pass

    def _serve(self) -> None:
        while True:
            try:
                conn, _ = self._ls.accept()
            except OSError:
                return
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True).start()

    def _handle(self, conn: socket.socket) -> None:
        up = None
        try:
            conn.settimeout(30)
            req = b""
            while b"\r\n\r\n" not in req:
                part = conn.recv(65536)
                if not part:
                    return
                req += part
            is_data = b"/manifest" not in req.split(b"\r\n", 1)[0]
            up = socket.create_connection(self._up, timeout=30)
            up.sendall(req)
            buf = b""
            while b"\r\n\r\n" not in buf:
                part = up.recv(65536)
                if not part:
                    return
                buf += part
            head, body0 = buf.split(b"\r\n\r\n", 1)
            conn.sendall(head + b"\r\n\r\n")
            with self._lock:
                fire = is_data and (self._persistent or not self._fired)
                if fire:
                    self._fired = True
            sent = 0
            flipped = False

            def feed():
                yield body0
                while True:
                    part = up.recv(65536)
                    if not part:
                        return
                    yield part

            for data in feed():
                if not fire:
                    conn.sendall(data)
                    continue
                if (self._mode == "flip" and not flipped
                        and sent <= self._flip_at < sent + len(data)):
                    mutable = bytearray(data)
                    mutable[self._flip_at - sent] ^= 0xFF
                    data = bytes(mutable)
                    flipped = True
                if (self._mode in ("cut", "stall", "die")
                        and sent + len(data) > self._fault_after):
                    keep = max(0, self._fault_after - sent)
                    if keep:
                        conn.sendall(data[:keep])
                    if self._mode == "stall":
                        time.sleep(60)  # hold the socket, send nothing
                    elif self._mode == "die":
                        self.close()  # later dials: connection refused
                    return
                conn.sendall(data)
                sent += len(data)
        except OSError:
            pass
        finally:
            for s in (conn, up):
                try:
                    if s is not None:
                        s.close()
                except OSError:
                    pass


def _heal_state(n_leaves: int = 8, leaf_elems: int = 4096) -> dict:
    rng = np.random.RandomState(7)
    return {f"w{i}": rng.rand(leaf_elems).astype(np.float32)
            for i in range(n_leaves)}


def _fetch_manifest(server_addr: str) -> dict:
    with urllib.request.urlopen(server_addr + "/manifest",
                                timeout=10) as resp:
        return json.loads(resp.read())


_FAST_RETRY = RetryPolicy(max_attempts=4, base_delay_ms=5.0,
                          max_delay_ms=20.0, jitter=0.0)


class TestManifestAndRange:
    def test_manifest_describes_stream(self):
        state = _heal_state(3, 100)
        state["step"] = 11
        server = CheckpointServer(lambda: state)
        try:
            server.allow_checkpoint(11)
            mf = _fetch_manifest(server.address())
            data = save_pytree(state)
            assert mf["format"] == "tft-manifest-1"
            assert mf["digest"] == "crc32"
            assert mf["step"] == 11
            assert mf["total_len"] == len(data)
            arrays = [e for e in mf["leaves"] if e["kind"] == "array"]
            assert len(arrays) == 3
            import zlib
            for e in arrays:
                lo = mf["preamble_len"] + e["offset"]
                assert e["crc32"] == zlib.crc32(
                    data[lo:lo + e["nbytes"]])
            # py leaves ride the manifest directly
            assert any(e["kind"] == "py" and e["value"] == 11
                       for e in mf["leaves"])
        finally:
            server.shutdown()

    def test_range_requests(self):
        state = _heal_state(4, 512)
        server = CheckpointServer(lambda: state)
        try:
            server.allow_checkpoint(1)
            data = save_pytree(state)
            total = len(data)
            for lo, hi in [(0, total), (100, total), (total // 2,
                                                      total // 2 + 37)]:
                req = urllib.request.Request(
                    server.address(),
                    headers={"Range": f"bytes={lo}-{hi - 1}"})
                with urllib.request.urlopen(req, timeout=10) as resp:
                    assert resp.status == 206
                    assert resp.headers["Content-Range"] == \
                        f"bytes {lo}-{hi - 1}/{total}"
                    assert resp.read() == data[lo:hi]
            # open-ended suffix
            req = urllib.request.Request(
                server.address(), headers={"Range": f"bytes={total - 5}-"})
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert resp.status == 206
                assert resp.read() == data[-5:]
            # past-the-end start: 416
            req = urllib.request.Request(
                server.address(), headers={"Range": f"bytes={total}-"})
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(req, timeout=10)
            assert exc_info.value.code == 416
        finally:
            server.shutdown()

    def test_pre_manifest_build_falls_back_to_legacy(self):
        """Rolling upgrade: a pre-manifest donor parses the step out of
        '<step>/manifest' and answers 400 "bad step" (not 404) — the
        healer must still fall back to the legacy whole-stream fetch
        instead of failing the heal."""
        from http.server import BaseHTTPRequestHandler, HTTPServer

        state = _heal_state(3, 512)
        payload = save_pytree(state)

        class OldBuildHandler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                # Faithful to the pre-manifest handler: int() the whole
                # suffix, 400 on anything non-numeric.
                try:
                    int(self.path[len("/checkpoint/"):])
                except ValueError:
                    self.send_error(400, "bad step")
                    return
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/octet-stream")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

        server = HTTPServer(("127.0.0.1", 0), OldBuildHandler)
        threading.Thread(target=server.serve_forever,
                         daemon=True).start()
        try:
            addr = f"http://127.0.0.1:{server.server_port}/checkpoint/1"
            stats = {}
            restored = CheckpointServer.load_from_address(
                addr, state, device_put=False, stats=stats)
            tree_equal(restored, state)
            assert stats["bytes"] == len(payload)
            # the Content-Length claim seeds payload_bytes on the
            # legacy path
            assert stats["payload_bytes"] == len(payload)
        finally:
            server.shutdown()
            server.server_close()

    def test_lock_streaming_has_no_manifest_and_falls_back(self):
        """lock_streaming serves live state (no immutable snapshot to
        digest): manifest is 404 and the healer's legacy whole-stream
        path still restores correctly."""
        state = _heal_state(2, 256)
        server = CheckpointServer(lambda: state, lock_streaming=True)
        try:
            server.allow_checkpoint(1)
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(server.address() + "/manifest",
                                       timeout=10)
            assert exc_info.value.code == 404
            stats = {}
            restored = CheckpointServer.load_from_address(
                server.address(), state, device_put=False, stats=stats)
            tree_equal(restored, state)
            # legacy path still counts bytes truthfully (the full stream)
            assert stats["bytes"] == len(save_pytree(state))
        finally:
            server.shutdown()


class TestResumableHeal:
    def test_byte_accounting_counts_actual_reads(self):
        """stats["bytes"] is what actually crossed the wire (the
        manifest path skips the preamble via Range), never the donor's
        Content-Length claim."""
        state = _heal_state(4, 1024)
        server = CheckpointServer(lambda: state)
        try:
            server.allow_checkpoint(1)
            mf = _fetch_manifest(server.address())
            stats = {}
            restored = CheckpointServer.load_from_address(
                server.address(), state, device_put=False, stats=stats)
            tree_equal(restored, state)
            assert stats["payload_bytes"] == mf["total_len"]
            assert stats["bytes"] == mf["total_len"] - mf["preamble_len"]
            assert stats["bytes_resumed"] == 0
            assert stats["attempts"] == 1
        finally:
            server.shutdown()

    def test_resume_after_cut_transfers_only_remaining(self):
        """A mid-stream reset resumes from the last verified leaf: the
        retry re-sends strictly less than the payload (O(remaining), not
        O(state))."""
        state = _heal_state(8, 4096)  # 8 x 16KB leaves
        server = CheckpointServer(lambda: state)
        proxy = None
        try:
            server.allow_checkpoint(1)
            mf = _fetch_manifest(server.address())
            body = mf["total_len"] - mf["preamble_len"]
            proxy = _FlakyProxy(server.address(), mode="cut",
                                fault_after=body // 2)
            stats = {}
            restored = CheckpointServer.load_from_address(
                proxy.address(1), state, device_put=False, stats=stats,
                retry_policy=_FAST_RETRY, stall_timeout_sec=10)
            tree_equal(restored, state)
            assert stats["attempts"] == 2
            # the resumed attempt re-sent only what was missing
            assert 0 < stats["bytes_resumed"] <= body // 2 + 16 * 4096
            assert stats["bytes_resumed"] < stats["payload_bytes"]
            # total wire cost: one full body's worth plus the re-read of
            # at most the one leaf the cut truncated
            assert stats["bytes"] < body + 2 * 16384
        finally:
            if proxy is not None:
                proxy.close()
            server.shutdown()

    def test_corrupted_leaf_detected_and_never_placed(self, monkeypatch):
        """A flipped byte in transit is caught by the leaf digest BEFORE
        device_put: the corrupt buffer is re-fetched, and placement only
        ever sees bytes that verified."""
        state = _heal_state(6, 2048)
        placed = []
        real_put = checkpointing.device_put_like

        def recording_put(arr, tleaf, **kw):
            placed.append(arr.copy())
            return real_put(arr, tleaf, **kw)

        monkeypatch.setattr(checkpointing, "device_put_like",
                            recording_put)
        server = CheckpointServer(lambda: state)
        proxy = None
        try:
            server.allow_checkpoint(1)
            mf = _fetch_manifest(server.address())
            # flip a byte inside the 4th array leaf's body span
            entry = [e for e in mf["leaves"] if e["kind"] == "array"][3]
            proxy = _FlakyProxy(server.address(), mode="flip",
                                flip_at=entry["offset"] + 17)
            stats = {}
            restored = CheckpointServer.load_from_address(
                proxy.address(1), state, device_put=True, stats=stats,
                retry_policy=_FAST_RETRY, stall_timeout_sec=10)
            tree_equal(restored, state)
            assert stats["digest_mismatches"] == 1
            assert stats["attempts"] == 2
            # every array the placer saw was bitwise-correct state
            good = {arr.tobytes() for arr in state.values()}
            for arr in placed:
                assert arr.tobytes() in good
        finally:
            if proxy is not None:
                proxy.close()
            server.shutdown()

    def test_donor_death_fails_over_and_completes(self):
        """ISSUE 3 acceptance: the donor dies at >=50% transfer progress
        — the healer fails over to a second donor, completes the SAME
        resumable transfer, restores bitwise-identical state, and
        bytes_resumed shows the retry re-sent strictly less than the
        payload."""
        state = _heal_state(8, 4096)
        donor_a = CheckpointServer(lambda: state)
        donor_b = CheckpointServer(lambda: state)
        proxy = None
        try:
            donor_a.allow_checkpoint(1)
            donor_b.allow_checkpoint(1)
            mf = _fetch_manifest(donor_a.address())
            body = mf["total_len"] - mf["preamble_len"]
            proxy = _FlakyProxy(donor_a.address(), mode="die",
                                fault_after=int(body * 0.6))
            resolved = []

            def donors(i):
                resolved.append(i)
                return donor_b.address()

            stats = {}
            restored = CheckpointServer.load_from_address(
                proxy.address(1), state, device_put=False, stats=stats,
                retry_policy=_FAST_RETRY, stall_timeout_sec=10,
                donors=donors)
            # bitwise-identical restored state
            for key, arr in state.items():
                assert restored[key].tobytes() == arr.tobytes()
            assert stats["donor_failovers"] == 1
            assert resolved == [0]
            assert 0 < stats["bytes_resumed"] < stats["payload_bytes"]
            # >=50% came from donor A, so the resume moved < half
            assert stats["bytes_resumed"] <= body * 0.5 + 16384
        finally:
            if proxy is not None:
                proxy.close()
            donor_a.shutdown()
            donor_b.shutdown()

    def test_cross_donor_digest_guard(self):
        """Failover onto a donor whose same-step snapshot DIFFERS (the
        bitwise-identity invariant broken): verified leaves that no
        longer match are dropped and re-fetched, so the result is a
        consistent copy of the new donor's state — never a torn mix."""
        state_a = _heal_state(6, 2048)
        rng = np.random.RandomState(99)
        state_b = {k: rng.rand(*v.shape).astype(v.dtype)
                   for k, v in state_a.items()}
        donor_a = CheckpointServer(lambda: state_a)
        donor_b = CheckpointServer(lambda: state_b)
        proxy = None
        try:
            donor_a.allow_checkpoint(1)
            donor_b.allow_checkpoint(1)
            mf = _fetch_manifest(donor_a.address())
            body = mf["total_len"] - mf["preamble_len"]
            proxy = _FlakyProxy(donor_a.address(), mode="die",
                                fault_after=int(body * 0.6))
            stats = {}
            restored = CheckpointServer.load_from_address(
                proxy.address(1), state_a, device_put=False, stats=stats,
                retry_policy=_FAST_RETRY, stall_timeout_sec=10,
                donors=lambda i: donor_b.address())
            for key, arr in state_b.items():
                assert restored[key].tobytes() == arr.tobytes()
            # the committed-but-mismatched leaves were detected
            assert stats["digest_mismatches"] >= 1
        finally:
            if proxy is not None:
                proxy.close()
            donor_a.shutdown()
            donor_b.shutdown()

    def test_stall_watchdog_aborts_fast(self):
        """A black-holed stream dies after ~stall_timeout_sec of zero
        bytes — not after the legacy 300 s wall clock."""
        state = _heal_state(8, 4096)
        server = CheckpointServer(lambda: state)
        proxy = None
        try:
            server.allow_checkpoint(1)
            mf = _fetch_manifest(server.address())
            body = mf["total_len"] - mf["preamble_len"]
            # headers flow, body bytes never do — a black-holed stream
            # on every attempt (fault_after=0, persistent)
            proxy = _FlakyProxy(server.address(), mode="stall",
                                fault_after=0, persistent=True)
            t0 = time.monotonic()
            stats = {}
            with pytest.raises(Exception) as exc_info:
                CheckpointServer.load_from_address(
                    proxy.address(1), state, device_put=False,
                    stats=stats,
                    retry_policy=RetryPolicy(max_attempts=2,
                                             base_delay_ms=5.0,
                                             jitter=0.0),
                    stall_timeout_sec=1.0)
            elapsed = time.monotonic() - t0
            assert elapsed < 20, f"watchdog took {elapsed:.1f}s"
            assert "timed out" in str(exc_info.value).lower() or \
                isinstance(exc_info.value, TimeoutError)
            # a FAILED heal still reports its attempt history truthfully
            assert stats["attempts"] == 2
            assert stats["payload_bytes"] == mf["total_len"]
        finally:
            if proxy is not None:
                proxy.close()
            server.shutdown()

    def test_persistent_corruption_is_fatal(self):
        """A leaf that mismatches on EVERY fetch (donor-side corruption)
        fails loudly with HealCorruptError instead of looping."""
        state = _heal_state(3, 512)
        server = CheckpointServer(lambda: state)
        try:
            server.allow_checkpoint(1)
            mf = _fetch_manifest(server.address())
            # lie about a digest: the real stream can never match
            bad = dict(mf)
            bad["leaves"] = [dict(e) for e in mf["leaves"]]
            for e in bad["leaves"]:
                if e["kind"] == "array":
                    e["crc32"] = (e["crc32"] + 1) & 0xFFFFFFFF
                    break

            orig = CheckpointServer._fetch_manifest

            def lying_manifest(addr, stall, auth, endpoint, **kw):
                real = orig(addr, stall, auth, endpoint, **kw)
                return bad if real is not None else None

            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(CheckpointServer, "_fetch_manifest",
                           staticmethod(lying_manifest))
                with pytest.raises(HealCorruptError):
                    CheckpointServer.load_from_address(
                        server.address(), state, device_put=False,
                        retry_policy=RetryPolicy(
                            max_attempts=8, base_delay_ms=1.0,
                            jitter=0.0),
                        stall_timeout_sec=10)
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# The heal transfer as one overlapped pass (docs/design/healing.md): the
# fetch engine cuts wide leaves into runs of rows and fetches one batch
# ahead; the healer reads, verifies and places beside each other.

MIB = 1 << 20


def _wide_state(wide_mib: int = 56, small: int = 24) -> dict:
    """One leaf several fetch batches wide (and not a whole number of
    them) among many small ones, on the device."""
    rng = np.random.RandomState(46)
    rows = wide_mib * MIB // (1031 * 4)
    state = {"a_small": [jnp.asarray(rng.rand(257, 33).astype(np.float32))
                         for _ in range(small // 2)],
             "m_wide": jnp.asarray(rng.rand(rows, 1031).astype(np.float32)),
             "z_small": [jnp.asarray(rng.rand(1000).astype(np.float32)
                                     ).astype(jnp.bfloat16)
                         for _ in range(small // 2)],
             "step": 3}
    return state


def _reference_stream(tree) -> bytes:
    """The serialized stream spelled plainly: preamble, then every array
    leaf's bytes whole."""
    preamble, _, leaves = plan_pytree(tree)
    return preamble + b"".join(np.asarray(leaf).tobytes() for leaf in leaves)


class TestFetchEngine:
    TREE = {"w": jnp.arange(7 * 300 * 5, dtype=jnp.float32
                            ).reshape(7, 300, 5),
            "e": jnp.zeros((0, 4), jnp.float32),
            "v": np.arange(5000, dtype=np.int64),
            "b": jnp.ones((33,), jnp.bfloat16),
            "s": np.float32(2.5), "tag": "t"}

    @pytest.mark.parametrize("batch_bytes", [64, 1000, 6000, 1 << 20])
    def test_sliced_stream_is_byte_for_byte_the_plain_one(
            self, batch_bytes):
        got = b"".join(iter_pytree_chunks(self.TREE, chunk_bytes=777,
                                          batch_bytes=batch_bytes))
        assert got == _reference_stream(self.TREE)
        assert got == save_pytree(self.TREE)

    @pytest.mark.parametrize("lo,hi", [
        (0, None), (5, 9), (0, 40), (300, 301), (1234, 30001),
        (40000, None), (10 ** 9, None), (77, 77)])
    def test_ranges_of_a_sliced_stream(self, lo, hi):
        ref = _reference_stream(self.TREE)
        got = b"".join(iter_pytree_chunks(
            self.TREE, chunk_bytes=512, batch_bytes=1000, start=lo,
            end=hi))
        assert got == ref[lo:hi]

    def test_digests_of_sliced_leaves_are_the_whole_leaf_crc32(self):
        import zlib

        plan = plan_pytree(self.TREE)
        want = [zlib.crc32(np.asarray(leaf).tobytes())
                for leaf in plan.array_leaves]
        assert plan.digests(batch_bytes=1000) == want
        assert plan_pytree(self.TREE).digests() == want

    def test_one_batch_ahead_and_no_more(self, monkeypatch):
        """Host bytes in flight on the donor: the batch the consumer
        holds and the one fetched ahead, 2 x batch_bytes, counted in
        fetches (not RSS); no fetch is wider than a batch."""
        from torchft_tpu import serialization

        fetched = []
        real = serialization._fetch_unit

        def counting(leaves, unit, clock):
            out = real(leaves, unit, clock)
            fetched.append(sum(len(mv) for _, _, mv in out))
            return out

        monkeypatch.setattr(serialization, "_fetch_unit", counting)
        leaves = plan_pytree(self.TREE).array_leaves
        units = serialization._fetch_units(leaves, 1000)
        assert len(units) > 20
        first_piece = {(u[0][0], u[0][1]): k for k, u in enumerate(units)}
        seen = 0
        for i, off, mv in serialization._iter_leaf_views(leaves, 1000):
            k = first_piece.get((i, off))
            if k is not None:
                # unit k in hand: nothing beyond unit k + 1 was fetched
                assert len(fetched) <= k + 2
                seen += 1
        assert seen == len(units) == len(fetched)
        assert max(fetched) <= 1000

    def test_a_tree_of_one_batch_starts_no_thread(self):
        import threading as _threading

        before = {t.ident for t in _threading.enumerate()}
        save_pytree({"w": np.ones(100, np.float32), "k": 1})
        names = [t.name for t in _threading.enumerate()
                 if t.ident not in before]
        assert not [n for n in names if n.startswith("tft-fetch")]


class TestStagingPool:
    def test_a_returned_buffer_is_lent_again_to_its_size(self):
        pool = checkpointing._StagingPool(100)
        a = pool.take(40)
        pool.give(a, True)
        assert pool.take(40) is a
        b = pool.take(40)
        assert b is not a and pool.peak == 80

    def test_take_waits_for_the_bound_and_sheds_idle_sizes(self):
        pool = checkpointing._StagingPool(100)
        a, b = pool.take(40), pool.take(40)
        got = []
        t = threading.Thread(target=lambda: got.append(pool.take(60)))
        t.start()
        time.sleep(0.1)
        assert not got                      # 80 lent: 60 more is over
        pool.give(a, True)                  # idle 40 is shed for the 60
        t.join(timeout=10)
        assert len(got) == 1 and len(got[0]) == 60
        assert pool.peak <= 100
        pool.give(b, False)                 # kept by its leaf: uncounted
        assert len(pool.take(40)) == 40 and pool.peak <= 100

    def test_a_warmed_buffer_is_the_one_taken(self):
        pool = checkpointing._StagingPool(1 << 20)
        pool.warm(300_000)
        buf = pool.take(300_000)            # waits for the warm-up
        assert len(buf) == 300_000 and pool.peak == 300_000
        assert not buf[::4096].any()

    def test_a_session_bounds_its_pool_by_its_widest_leaf(self):
        from torchft_tpu.serialization import DEFAULT_BATCH_BYTES

        small = checkpointing._HealSession({"w": np.ones(10)}, None)
        assert small.staging.bound == 2 * DEFAULT_BATCH_BYTES
        wide = checkpointing._HealSession(
            {"w": np.ones(10), "e": jnp.zeros((5000, 2048)), "k": 1}, None)
        assert wide.widest == 5000 * 2048 * 4
        assert wide.staging.bound == 2 * wide.widest


@pytest.fixture
def staging_pools(monkeypatch):
    """Every staging pool a heal of the test makes, for its counts."""
    pools = []
    real_pool = checkpointing._StagingPool

    def recording_pool(bound):
        pools.append(real_pool(bound))
        return pools[-1]

    monkeypatch.setattr(checkpointing, "_StagingPool", recording_pool)
    return pools


class TestOverlappedHeal:
    @pytest.mark.parametrize("device_put", [True, False])
    def test_wide_leaf_and_many_small_heal_bitwise(self, device_put,
                                                   staging_pools):
        """A leaf several batches wide among many small ones arrives
        bitwise, the stage clocks of both sides are filled, and the
        staging buffers stayed under their stated bound."""
        from torchft_tpu.serialization import DEFAULT_BATCH_BYTES

        state = _wide_state()
        wide = state["m_wide"].nbytes
        assert wide > 2 * DEFAULT_BATCH_BYTES
        server = CheckpointServer(lambda: state)
        try:
            server.allow_checkpoint(1)
            stats = {}
            seen = []
            restored = CheckpointServer.load_from_address(
                server.address(), state, device_put=device_put,
                stats=stats, progress_cb=lambda b, t: seen.append(b))
            import jax
            for got, want in zip(jax.tree_util.tree_leaves(restored),
                                 jax.tree_util.tree_leaves(state)):
                assert np.asarray(got).tobytes() == \
                    np.asarray(want).tobytes()
            assert restored["step"] == 3
            for stage in ("manifest", "recv", "verify", "place"):
                assert stats[f"{stage}_ms"] > 0, stage
            donor = server.metrics()
            assert donor["heal_serve_fetch_ms_total"] > 0
            assert donor["heal_serve_send_ms_total"] > 0
            assert seen == sorted(seen) and seen[-1] == \
                stats["payload_bytes"] - _fetch_manifest(
                    server.address())["preamble_len"]
            (pool,) = staging_pools
            assert pool.bound == 2 * wide
            assert 0 < pool.peak <= pool.bound
        finally:
            server.shutdown()

    def test_corrupt_chunk_mid_wide_leaf_places_nothing_of_it(
            self, monkeypatch):
        """A flipped byte in a middle chunk of a leaf many chunks long:
        the running digest fails with the leaf's last byte, the leaf
        stays missing with nothing of it placed, the other leaves of the
        round are kept, and the refetch commits it."""
        state = _wide_state(wide_mib=28, small=8)
        placed = []
        real_put = checkpointing.device_put_like

        def recording_put(arr, tleaf, **kw):
            placed.append((arr.shape, arr.tobytes()))
            return real_put(arr, tleaf, **kw)

        monkeypatch.setattr(checkpointing, "device_put_like",
                            recording_put)
        server = CheckpointServer(lambda: state)
        proxy = None
        try:
            server.allow_checkpoint(1)
            mf = _fetch_manifest(server.address())
            entry = next(e for e in mf["leaves"] if e["key"] == "m_wide")
            proxy = _FlakyProxy(
                server.address(), mode="flip",
                flip_at=entry["offset"] + entry["nbytes"] // 2)
            stats = {}
            restored = CheckpointServer.load_from_address(
                proxy.address(1), state, stats=stats,
                retry_policy=_FAST_RETRY, stall_timeout_sec=10)
            tree_equal(restored, state)
            assert stats["digest_mismatches"] == 1
            assert stats["attempts"] == 2
            # the second round moved the wide leaf alone
            assert stats["bytes_resumed"] == entry["nbytes"]
            wide = [b for shape, b in placed
                    if shape == state["m_wide"].shape]
            assert wide == [np.asarray(state["m_wide"]).tobytes()]
            assert len(placed) == len(
                [e for e in mf["leaves"] if e["kind"] == "array"])
        finally:
            if proxy is not None:
                proxy.close()
            server.shutdown()

    def test_cut_mid_wide_leaf_resumes_at_that_leaf(self):
        """A connection cut inside a wide leaf keeps every leaf verified
        before it, and the next attempt re-enters at the wide leaf's
        first byte: `bytes_resumed` is exactly the stream from there."""
        state = _wide_state(wide_mib=28, small=8)
        server = CheckpointServer(lambda: state)
        proxy = None
        try:
            server.allow_checkpoint(1)
            mf = _fetch_manifest(server.address())
            body = mf["total_len"] - mf["preamble_len"]
            entry = next(e for e in mf["leaves"] if e["key"] == "m_wide")
            proxy = _FlakyProxy(
                server.address(), mode="cut",
                fault_after=entry["offset"] + entry["nbytes"] * 2 // 3)
            stats = {}
            restored = CheckpointServer.load_from_address(
                proxy.address(1), state, stats=stats,
                retry_policy=_FAST_RETRY, stall_timeout_sec=10)
            tree_equal(restored, state)
            assert stats["attempts"] == 2
            assert stats["bytes_resumed"] == body - entry["offset"]
            assert stats["bytes"] == body + entry["nbytes"] * 2 // 3
        finally:
            if proxy is not None:
                proxy.close()
            server.shutdown()

    def test_a_failing_placement_surfaces_and_lends_nothing_out(
            self, monkeypatch, staging_pools):
        """An error on the placement thread reaches the caller as itself,
        and every staging buffer has come back."""
        def failing_put(arr, tleaf, **kw):
            raise MemoryError("device out of memory")

        monkeypatch.setattr(checkpointing, "device_put_like", failing_put)
        state = _heal_state(6, 2048)
        server = CheckpointServer(lambda: state)
        try:
            server.allow_checkpoint(1)
            with pytest.raises(MemoryError, match="out of memory"):
                CheckpointServer.load_from_address(
                    server.address(), state, stall_timeout_sec=10)
            (pool,) = staging_pools
            idle = sum(size * len(bufs)
                       for size, bufs in pool._idle.items())
            assert pool._held == idle
        finally:
            server.shutdown()
