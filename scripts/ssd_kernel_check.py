#!/usr/bin/env python3
"""On the chip: the Mamba-2 scan alone at the shapes the
``nemotron-3-nano-30b-a3b`` cell runs (64 heads of 64 in 8 groups, state
128, bfloat16 products; 1 x 8,192 tokens and the ragged 1 x 8,288 that
``tests/test_chip_compile.py`` compiles): ``ssd_scan`` forward and forward
+ backward, the wall per call against the 0.54 ms a block its bytes need
(PERF.md section 7), and the largest distance of the value and of every
gradient from the token-by-token recurrence in float32 (over the first
``ORACLE_TOKENS`` tokens: the recurrence keeps a state a token).

    chiprun --chips 1 -- python3 scripts/ssd_kernel_check.py

``--root DIR`` imports ``torchft_tpu`` from another checkout (a ``git
archive`` copy of the parent commit under ``.chip_archive/``), so one call
times two trees on one chip. The arguments are laid out as the mixer hands
them over (``x`` a slice of ``[B, T, H P]``, ``B`` and ``C`` of
``[B, T, G N]``, reshaped inside the jitted call). Prints one JSON line a
length (and appends it to ``--out``); exits 1 where a relative error
passes ``--tol``."""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ITERS = 10
ORACLE_TOKENS = 512
ROOFLINE_MS = 0.54    # a block at 8,192 tokens, every byte moved once


def check(tokens, heads, head_dim, groups, state, tol):
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import ssd

    f32, bf16 = jnp.float32, jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 8)
    # what the mixer hands the scan: activations of order one, steps of
    # about 0.007-0.1 (softplus of a bias drawn as the model's is), rates
    # of -1 to -16
    x = jax.random.normal(ks[0], (1, tokens, heads * head_dim)).astype(bf16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, tokens, heads))
                         + jnp.log(jnp.expm1(jax.random.uniform(
                             ks[2], (heads,), f32, 1e-3, 0.1))))
    a = -jax.random.uniform(ks[3], (heads,), f32, 1.0, 16.0)
    b_in = jax.random.normal(ks[4], (1, tokens, groups * state)).astype(bf16)
    c_in = jax.random.normal(ks[5], (1, tokens, groups * state)).astype(bf16)
    d = jnp.ones((heads,), f32)
    ct = jax.random.normal(ks[6], (1, tokens, heads * head_dim))
    args = (x, dt, a, b_in, c_in, d)

    def flat(fn):
        def call(x, dt, a, b_in, c_in, d):
            t = x.shape[1]
            return fn(x.reshape(1, t, heads, head_dim), dt, a,
                      b_in.reshape(1, t, groups, state),
                      c_in.reshape(1, t, groups, state),
                      d).reshape(1, t, heads * head_dim)
        return call

    def timed(f, *a):
        out = jax.block_until_ready(f(*a))
        t0 = time.monotonic()
        for _ in range(ITERS):
            out = f(*a)
        jax.block_until_ready(out)
        return out, 1e3 * (time.monotonic() - t0) / ITERS

    def grads(fn, ct=ct):
        return jax.jit(lambda *a: jax.vjp(flat(fn), *a)[1](ct))

    def rel(got, want):
        got, want = got.astype(f32), want.astype(f32)
        return float(jnp.sqrt(jnp.mean(jnp.square(got - want))
                              / jnp.mean(jnp.square(want))))

    res = {"shape": [tokens, heads, head_dim, groups, state],
           "device": jax.devices()[0].device_kind,
           "roofline_ms": ROOFLINE_MS * tokens / 8192}
    if hasattr(ssd, "_heads_a_step"):
        res["hb"] = ssd._heads_a_step(heads // groups, head_dim, state, 2)
    out, res["fwd_ms"] = timed(jax.jit(flat(ssd.ssd_scan)), *args)
    _, res["fwd_bwd_ms"] = timed(grads(ssd.ssd_scan), *args)
    # held to the recurrence over the first ``ORACLE_TOKENS``, gradients of
    # those alone (the scan is causal: their values are the same)
    short = (x[:, :ORACLE_TOKENS], dt[:, :ORACLE_TOKENS], a,
             b_in[:, :ORACLE_TOKENS], c_in[:, :ORACLE_TOKENS], d)
    ct_short = ct[:, :ORACLE_TOKENS]
    got = grads(ssd.ssd_scan, ct_short)(*short)
    want = grads(ssd.ssd_recurrent, ct_short)(*short)
    res["rel"] = {"out": rel(out[:, :ORACLE_TOKENS],
                             jax.jit(flat(ssd.ssd_recurrent))(*short)),
                  **{"d" + name: rel(g, w) for name, g, w in zip(
                      ("x", "dt", "a", "b", "c", "d"), got, want)}}
    res["worst"] = max(res["rel"].values())
    res["ok"] = res["worst"] <= tol
    return res


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", default="8192,8288",
                    help="lengths, comma-separated (fewer tokens for a "
                         "rehearsal)")
    ap.add_argument("--heads", default="64,64,8,128",
                    help="heads, head size, groups, state")
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--out", default=None)
    ap.add_argument("--tol", type=float, default=3e-2)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    ok = True
    for tokens in (int(t) for t in args.seq.split(",")):
        res = {"root": args.root,
               **check(tokens, *map(int, args.heads.split(",")), args.tol)}
        ok = ok and res["ok"]
        line = json.dumps(res)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
