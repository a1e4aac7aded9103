#!/usr/bin/env python3
"""On the chip: the flash kernels at a head of 64, 32 query heads on 8
key/value heads, 8,192 tokens (LFM2-8B-A1B's attention layer; every other
configuration runs heads of 128 to 256): the forward, the fused backward and
the split backward against plain float32 softmax attention computed one
head at a time, and each one's wall per call.

    chiprun --chips 1 -- python3 scripts/flash_head64_check.py [--seq 8192]

Prints one JSON line; exits 1 where a relative error passes ``--tol``."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--tol", type=float, default=2e-2)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.flash_attention import flash_attention

    s, h, hkv, d = args.seq, args.heads, args.kv_heads, args.head_dim
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (1, s, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, s, hkv, d), jnp.bfloat16)
    g = jax.random.normal(ks[3], (1, s, h, d), jnp.bfloat16)

    def plain(q, k, v):
        rep = h // hkv
        qh = q.astype(jnp.float32).transpose(2, 0, 1, 3)
        kh = jnp.repeat(k.astype(jnp.float32).transpose(2, 0, 1, 3), rep, 0)
        vh = jnp.repeat(v.astype(jnp.float32).transpose(2, 0, 1, 3), rep, 0)
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

        @jax.checkpoint
        def one(a):
            q1, k1, v1 = a
            sc = jnp.einsum("bqd,bkd->bqk", q1, k1,
                            precision="highest") * d ** -0.5
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, v1, precision="highest")

        return jax.lax.map(one, (qh, kh, vh)).transpose(1, 2, 0, 3)

    def run(fn, fused):
        os.environ["TORCHFT_FLASH_FUSED_BWD"] = "1" if fused else "0"
        f = jax.jit(lambda q, k, v: jax.vjp(fn, q, k, v)[1](
            g.astype(fn(q, k, v).dtype)))
        out = jax.block_until_ready(f(q, k, v))
        t0 = time.monotonic()
        for _ in range(5):
            out = f(q, k, v)
        jax.block_until_ready(out)
        return out, (time.monotonic() - t0) / 5

    flash = lambda q, k, v: flash_attention(q, k, v, True)  # noqa: E731
    fwd = jax.jit(flash)
    out = jax.block_until_ready(fwd(q, k, v))
    t0 = time.monotonic()
    for _ in range(5):
        o = fwd(q, k, v)
    jax.block_until_ready(o)
    fwd_ms = 1e3 * (time.monotonic() - t0) / 5
    want_out = jax.jit(plain)(q, k, v)
    want, _ = run(plain, True)

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.sqrt(jnp.mean(jnp.square(a - b))
                              / jnp.mean(jnp.square(b))))

    res = {"shape": [1, s, h, hkv, d],
           "device": jax.devices()[0].device_kind,
           "fwd_rel": rel(out, want_out), "fwd_ms": fwd_ms}
    for name, fused in (("fused", True), ("split", False)):
        got, secs = run(flash, fused)
        res[name] = {n: rel(a, b) for n, a, b in zip(
            ("dq", "dk", "dv"), got, want)}
        res[name]["fwd_bwd_ms"] = 1e3 * secs
    worst = max([res["fwd_rel"]] + [res[n][x] for n in ("fused", "split")
                                    for x in ("dq", "dk", "dv")])
    res["worst"], res["ok"] = worst, worst <= args.tol
    print(json.dumps(res), flush=True)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
