#!/usr/bin/env python3
"""On the chip: the flash kernels alone at the shapes the benchmark's cells
run, the forward, the fused backward and the split backward against plain
float32 softmax attention computed one head at a time, and each one's wall
per call. The default is the head of 64 this script was written for
(LFM2-8B-A1B's attention layer: 32 query heads on 8 key/value heads, 8,192
tokens); ``--shape`` names another of ``SHAPES`` and ``--shape all`` runs
them in turn.

    chiprun --chips 1 -- python3 scripts/flash_head64_check.py [--shape all]

``--root DIR`` imports ``torchft_tpu`` from another checkout (a ``git
archive`` copy of the parent commit under ``.chip_archive/``), so one call
times two trees on one chip. Prints one JSON line a shape (and appends it
to ``--out``); exits 1 where a relative error passes ``--tol``. Given more
than once (``--root .chip_archive/parent --root . --root . --root
.chip_archive/parent``), each root runs in a process of its own, one after
the other (this one stays off JAX, so each child has the chip), and the
lines end in a table, a row a shape and root: forward, fused backward and
split backward in ms.

    chiprun --chips 1 -- python3 scripts/flash_head64_check.py \
        --shape mla192 --root .chip_archive/parent --root .
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#          batch, tokens, heads, kv heads, d_qk, d_v, window
SHAPES = {
    "gqa64": (1, 8192, 32, 8, 64, 64, None),        # lfm2-8b-a1b
    "full128": (1, 8192, 32, 4, 128, 128, None),    # trinity-mini, full
    "window128": (1, 8192, 32, 4, 128, 128, 2048),  # trinity-mini, window
    "mla192": (1, 8192, 32, 32, 192, 128, None),    # joyai-llm-flash
    "gqa256": (1, 8192, 16, 2, 256, 256, None),     # qwen3-next-80b-a3b
    "mistral": (4, 4096, 32, 8, 128, 128, None),    # mistral-7b.steady-1g
}
ITERS = 10


def check(shape, tol):
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.flash_attention import flash_attention

    b, s, h, hkv, d, d_v, window = shape
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, hkv, d_v), jnp.bfloat16)
    g = jax.random.normal(ks[3], (b, s, h, d_v), jnp.bfloat16)

    def plain(q, k, v):
        rep = h // hkv
        qh = q.astype(jnp.float32).transpose(2, 0, 1, 3)
        kh = jnp.repeat(k.astype(jnp.float32).transpose(2, 0, 1, 3), rep, 0)
        vh = jnp.repeat(v.astype(jnp.float32).transpose(2, 0, 1, 3), rep, 0)
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        mask = i >= j
        if window is not None:
            mask &= i - j < window

        @jax.checkpoint
        def one(a):
            q1, k1, v1 = a
            sc = jnp.einsum("bqd,bkd->bqk", q1, k1,
                            precision="highest") * d ** -0.5
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, v1, precision="highest")

        return jax.lax.map(one, (qh, kh, vh)).transpose(1, 2, 0, 3)

    def timed(f):
        out = jax.block_until_ready(f(q, k, v))
        t0 = time.monotonic()
        for _ in range(ITERS):
            out = f(q, k, v)
        jax.block_until_ready(out)
        return out, 1e3 * (time.monotonic() - t0) / ITERS

    def grads(fn, fused):
        os.environ["TORCHFT_FLASH_FUSED_BWD"] = "1" if fused else "0"
        return timed(jax.jit(lambda q, k, v: jax.vjp(fn, q, k, v)[1](
            g.astype(fn(q, k, v).dtype))))

    def flash(q, k, v):
        return flash_attention(q, k, v, True, window=window)

    out, fwd_ms = timed(jax.jit(flash))
    want_out = jax.jit(plain)(q, k, v)
    want, _ = grads(plain, True)

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.sqrt(jnp.mean(jnp.square(a - b))
                              / jnp.mean(jnp.square(b))))

    res = {"shape": [b, s, h, hkv, d, d_v, window],
           "device": jax.devices()[0].device_kind,
           "fwd_rel": rel(out, want_out), "fwd_ms": fwd_ms}
    for name, fused in (("fused", True), ("split", False)):
        got, ms = grads(flash, fused)
        res[name] = {n: rel(a, b) for n, a, b in zip(
            ("dq", "dk", "dv"), got, want)}
        res[name]["fwd_bwd_ms"] = ms
        res[name]["bwd_ms"] = ms - fwd_ms
    worst = max([res["fwd_rel"]] + [res[n][x] for n in ("fused", "split")
                                    for x in ("dq", "dk", "dv")])
    res["worst"], res["ok"] = worst, worst <= tol
    return res


def each_root(roots, argv) -> int:
    """One child a root, in turn; their lines as a table at the end."""
    rows, rc = [], 0
    for root in roots:
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--root", root]
            + argv, stdout=subprocess.PIPE, text=True)
        sys.stdout.write(child.stdout)
        sys.stdout.flush()
        rc = rc or child.returncode
        rows += [json.loads(line) for line in child.stdout.splitlines()
                 if line.startswith("{")]
    print("| shape | root | forward ms | fused backward ms "
          "| split backward ms | worst rel |\n"
          "| --- | --- | --- | --- | --- | --- |")
    for r in sorted(rows, key=lambda r: r["name"]):
        print(f"| `{r['name']}` | `{r['root']}` | {r['fwd_ms']:.2f} "
              f"| {r['fused']['bwd_ms']:.2f} | {r['split']['bwd_ms']:.2f} "
              f"| {r['worst']:.4f} |")
    return rc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="gqa64",
                    choices=sorted(SHAPES) + ["all"])
    ap.add_argument("--seq", type=int, default=None,
                    help="tokens, where not the shape's own (a rehearsal)")
    ap.add_argument("--root", action="append", default=None,
                    help="a checkout to import torchft_tpu from; several: "
                    "each in a process of its own, then a table")
    ap.add_argument("--out", default=None)
    ap.add_argument("--tol", type=float, default=2e-2)
    args = ap.parse_args()
    roots = args.root or [HERE]
    if len(roots) > 1:
        argv = ["--shape", args.shape, "--tol", str(args.tol)]
        argv += ["--seq", str(args.seq)] if args.seq else []
        argv += ["--out", args.out] if args.out else []
        return each_root(roots, argv)
    args.root = roots[0]
    sys.path.insert(0, os.path.abspath(args.root))
    ok = True
    for name in (sorted(SHAPES) if args.shape == "all" else [args.shape]):
        shape = SHAPES[name]
        if args.seq:
            shape = shape[:1] + (args.seq,) + shape[2:]
        res = {"name": name, "root": args.root, **check(shape, args.tol)}
        line = json.dumps(res)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
        ok = ok and res["ok"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
