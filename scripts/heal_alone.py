"""One heal transfer alone on the chip, at the kill cell's leaf shape.

A donor's `CheckpointServer` with its state on the device, a healer's
`load_from_address` into a target on the same device, both in this
process (as the kill cell's replacement is a thread of the survivor's
process): no trainer, no quorum, no ring. Prints a JSON line a transfer
with the wall, GB/s and, where the checkout has them, the stage clocks of
both sides, so a change to the transfer can be read in seconds of chip
time before the cell is run. `--root DIR` imports `torchft_tpu` from
another checkout (a parent unpacked under `.chip_archive/`)::

    python3 scripts/heal_alone.py [--root DIR] [--repeats 4] [--layers 2]

Run it under the mix's allocator settings (`benchmarks/traffic/
kill-heal-2g.json` `env`) to read what the benchmark's process sees. Not a
benchmark cell: `recover_s` is the cell's.

`--beside thread|process|burner` (PR 59) runs the
transfers beside something that never blocks: a thread spinning in Python
(what a trace is to the interpreter lock), the same spinner in another
process (cores and memory, no lock), or a thread busy in `zlib.crc32`
outside the lock. On the chip's host: 2.1 s alone and beside the other
two; beside the thread 32 s with the interpreter's own socket calls and
7.1 s with a chunk in one foreign call a side (PERF.md, Findings PR 59).
"""

import argparse
import json
import os
import subprocess
import sys
import threading
import time
import zlib

_SPIN = "x = 0\nwhile True:\n    for i in range(1000): x += i * i\n"


def _beside(kind: str):
    """Start what the transfers run beside; returns what stops it."""
    stop = threading.Event()
    if kind == "process":
        child = subprocess.Popen([sys.executable, "-c", _SPIN])
        return child.kill

    def spin():
        x = 0
        while not stop.is_set():
            for i in range(1000):
                x += i * i

    def burn():
        buf = bytes(64 << 20)
        while not stop.is_set():
            zlib.crc32(buf)

    threading.Thread(target=spin if kind == "thread" else burn,
                     daemon=True).start()
    return stop.set


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--repeats", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=92544)
    ap.add_argument("--chunk-mib", type=float, default=None,
                    help="an experiment: the donor's socket writes in "
                         "chunks of this size, not the program's")
    ap.add_argument("--beside", choices=("thread", "process", "burner"),
                    help="run the transfers beside a Python spinner thread, "
                         "the same in another process, or a thread busy "
                         "outside the interpreter lock")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import jax.numpy as jnp

    from torchft_tpu import serialization
    from torchft_tpu.checkpointing import CheckpointServer

    if args.chunk_mib is not None:
        import functools
        serialization.iter_pytree_chunks = functools.partial(
            serialization.iter_pytree_chunks,
            chunk_bytes=int(args.chunk_mib * (1 << 20)))

    hidden, ffn, kv = 2048, 8192, 1024

    def tree(fill):
        def leaf(*shape):
            return fill(shape)
        return {
            "embed": leaf(args.vocab, hidden),
            "layers": [{
                "attn_norm": leaf(hidden), "ffn_norm": leaf(hidden),
                "wq": leaf(hidden, hidden), "wk": leaf(hidden, kv),
                "wv": leaf(hidden, kv), "wo": leaf(hidden, hidden),
                "w1": leaf(hidden, ffn), "w3": leaf(hidden, ffn),
                "w2": leaf(ffn, hidden)} for _ in range(args.layers)],
            "lm_head": leaf(args.vocab, hidden),
            "norm": leaf(hidden),
            "step": 7,
        }

    key = [jax.random.PRNGKey(46)]

    def random(shape):
        key[0], sub = jax.random.split(key[0])
        return jax.random.normal(sub, shape, jnp.float32)

    state = tree(random)
    jax.block_until_ready(state)
    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "root": args.root}), flush=True)
    server = CheckpointServer(lambda: state)
    stop_beside = _beside(args.beside) if args.beside else (lambda: None)
    try:
        for k in range(args.repeats):
            server.allow_checkpoint(k + 1)
            target = tree(lambda shape: jnp.zeros(shape, jnp.float32))
            jax.block_until_ready(target)
            before = getattr(server, "metrics", dict)()
            stats = {}
            t0 = time.perf_counter()
            out = CheckpointServer.load_from_address(
                server.address(), target, stats=stats)
            jax.block_until_ready(out)
            wall = time.perf_counter() - t0
            after = getattr(server, "metrics", dict)()
            same = all(
                bool(jnp.array_equal(a, b)) for a, b in zip(
                    jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(state)))
            line = {"run": k, "wall_s": round(wall, 4),
                    "gbps": round(stats["bytes"] / wall / 1e9, 4),
                    "bitwise": same}
            line.update({k2: round(v, 1) for k2, v in stats.items()
                         if k2.endswith("_ms")})
            line.update({k2: round(after[k2] - before.get(k2, 0.0), 1)
                         for k2 in after})
            print(json.dumps(line), flush=True)
            del out, target
            server.disallow_checkpoint()
    finally:
        stop_beside()
        server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
