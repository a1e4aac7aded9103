"""Rates of the heal transfer's stages alone, on the machine with the chip.

What `PERF.md`'s kill-cell paragraph needs to say which stage paces the
overlapped heal: never-touched against warm host pages under a CPU write,
under ``recv_into`` over loopback and under a D2H; ``zlib.crc32``; a host
copy; ``device_get`` by the size of the buffer it lands in (glibc maps
anything from 32 MiB up anew); ``device_put`` by the size of its source.
Run it under a mix's allocator settings to read what the benchmark's
process sees::

    env MALLOC_MMAP_THRESHOLD_=33554432 MALLOC_TRIM_THRESHOLD_=17179869184 \
        MALLOC_TOP_PAD_=268435456 python3 scripts/heal_stage_rates.py

Prints one JSON object; every rate is GB/s (1e9 bytes a second).
"""

import json
import socket
import sys
import threading
import time
import zlib

import numpy as np

MIB = 1 << 20
BIG = 723 * MIB          # InternLM2's embedding and head, float32


def rate(nbytes, seconds):
    return round(nbytes / max(seconds, 1e-9) / 1e9, 3)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def host_rates(out):
    buf = np.empty(BIG, np.uint8)
    _, dt = timed(lambda: buf.fill(1))
    out["fill_fresh_723"] = rate(BIG, dt)
    _, dt = timed(lambda: buf.fill(2))
    out["fill_warm_723"] = rate(BIG, dt)
    _, dt = timed(lambda: zlib.crc32(buf))
    out["crc32_whole_723"] = rate(BIG, dt)
    mv = memoryview(buf)

    def crc_chunks(step):
        c = 0
        for a in range(0, BIG, step):
            c = zlib.crc32(mv[a:a + step], c)
        return c
    for step in (1, 4, 16):
        _, dt = timed(lambda: crc_chunks(step * MIB))
        out[f"crc32_chunks_{step}m"] = rate(BIG, dt)
    dst = np.empty(BIG, np.uint8)
    _, dt = timed(lambda: np.copyto(dst, buf))
    out["copy_to_fresh_723"] = rate(BIG, dt)
    _, dt = timed(lambda: np.copyto(dst, buf))
    out["copy_to_warm_723"] = rate(BIG, dt)
    small = [np.empty(24 * MIB, np.uint8) for _ in range(4)]
    for s in small:
        s.fill(1)
    t0 = time.perf_counter()
    for k in range(30):
        np.copyto(small[k % 4], small[(k + 1) % 4])
    out["copy_warm_24"] = rate(30 * 24 * MIB, time.perf_counter() - t0)
    del dst
    return buf


def loopback(out, src):
    """One sender thread, 8 MiB writes of a warm source; the receiver
    reads into a ring of warm chunks, then into never-touched pages."""
    total = 2 * BIG

    def run(label, sink_of):
        srv = socket.create_server(("127.0.0.1", 0))
        port = srv.getsockname()[1]

        def send():
            c, _ = srv.accept()
            with c:
                mv = memoryview(src)
                for _ in range(2):
                    for a in range(0, BIG, 8 * MIB):
                        c.sendall(mv[a:a + 8 * MIB])
        t = threading.Thread(target=send, daemon=True)
        t.start()
        s = socket.create_connection(("127.0.0.1", port))
        got = 0
        t0 = time.perf_counter()
        while got < total:
            mv = sink_of(got)
            off = 0
            while off < len(mv):
                n = s.recv_into(mv[off:])
                if not n:
                    raise RuntimeError("short")
                off += n
            got += len(mv)
        out[label] = rate(total, time.perf_counter() - t0)
        s.close()
        t.join()
        srv.close()

    ring = [np.empty(4 * MIB, np.uint8) for _ in range(4)]
    for r in ring:
        r.fill(0)
    run("recv_warm_ring_4m", lambda got: memoryview(
        ring[(got // (4 * MIB)) % 4])[:min(4 * MIB, total - got)])
    fresh = [np.empty(BIG, np.uint8), np.empty(BIG, np.uint8)]
    run("recv_fresh_723", lambda got: memoryview(
        fresh[got // BIG])[got % BIG:][:4 * MIB])
    run("recv_warm_723", lambda got: memoryview(
        fresh[got // BIG])[got % BIG:][:4 * MIB])


def device_rates(out):
    import jax

    dev = jax.devices()[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind}
    rows, cols = 92544, 2048
    big = jax.device_put(np.ones((rows, cols), np.float32))
    big.block_until_ready()

    # device_get by landing size: whole leaf, then row slices
    _, dt = timed(lambda: jax.device_get(big))
    out["d2h_whole_723"] = rate(BIG, dt)
    for mib in (8, 16, 24, 31, 64):
        per = mib * MIB // (cols * 4)
        cut = jax.jit(lambda x, first, per=per:
                      jax.lax.dynamic_slice_in_dim(x, first, per, axis=0))
        cut(big, np.int32(0)).block_until_ready()
        n = min(rows // per, 24)
        # twice: the second pass lands in what the first freed
        for tag in ("first", "again"):
            t0 = time.perf_counter()
            for k in range(n):
                jax.device_get(cut(big, np.int32(k * per)))
            out[f"d2h_slices_{mib}m_{tag}"] = rate(
                n * per * cols * 4, time.perf_counter() - t0)
    # the same with the next slice's copy started before the wait
    per = 24 * MIB // (cols * 4)
    cut = jax.jit(lambda x, first: jax.lax.dynamic_slice_in_dim(
        x, first, per, axis=0))
    n = rows // per
    t0 = time.perf_counter()
    nxt = cut(big, np.int32(0))
    nxt.copy_to_host_async()
    for k in range(n):
        cur = nxt
        if k + 1 < n:
            nxt = cut(big, np.int32((k + 1) * per))
            nxt.copy_to_host_async()
        np.asarray(cur)
    out["d2h_slices_24m_async_ahead"] = rate(
        n * per * cols * 4, time.perf_counter() - t0)

    # device_put by source
    host = np.ones((rows, cols), np.float32)
    for tag in ("first", "again"):
        _, dt = timed(lambda: jax.device_put(host).block_until_ready())
        out[f"h2d_whole_723_{tag}"] = rate(BIG, dt)
    _, dt = timed(lambda: jax.device_put(
        host.astype(np.float32)).block_until_ready())
    out["h2d_whole_723_astype_copy"] = rate(BIG, dt)
    fresh = np.empty((rows, cols), np.float32)
    _, dt = timed(lambda: jax.device_put(fresh).block_until_ready())
    out["h2d_whole_723_untouched_source"] = rate(BIG, dt)
    per = 24 * MIB // (cols * 4)
    t0 = time.perf_counter()
    parts = [jax.device_put(host[a:a + per])
             for a in range(0, rows - per + 1, per)]
    jax.block_until_ready(parts)
    out["h2d_slices_24m"] = rate(len(parts) * per * cols * 4,
                                 time.perf_counter() - t0)
    # D2H of one leaf while another thread places one: do they share?
    res = {}

    def get():
        _, res["get"] = timed(lambda: [
            jax.device_get(cut(big, np.int32(k * per))) for k in range(n)])
    t = threading.Thread(target=get)
    t0 = time.perf_counter()
    t.start()
    _, dput = timed(lambda: jax.device_put(host).block_until_ready())
    t.join()
    out["both_ways_d2h_24m"] = rate(n * per * cols * 4, res["get"])
    out["both_ways_h2d_723"] = rate(BIG, dput)


def main():
    out = {}
    src = host_rates(out)
    loopback(out, src)
    del src
    device_rates(out)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
