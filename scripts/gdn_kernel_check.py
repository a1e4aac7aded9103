#!/usr/bin/env python3
"""On the chip: the gated delta rule alone at the shapes the
``qwen3-next-80b-a3b`` cell runs (1 x 8,192 tokens, 32 value heads of
128 x 128 on 16 key heads, bfloat16 products): the rule forward and
forward + backward against the token-by-token recurrence in float32 (over
the first 512 tokens: the recurrence keeps a state a token), and the wall
per call of the rule, of its chunk recurrence alone (the two
Pallas kernels, at each ``--hb``) and of its chunk inverse alone beside
``triangular_solve`` on the same matrices.

    chiprun --chips 1 -- python3 scripts/gdn_kernel_check.py

``--root DIR`` imports ``torchft_tpu`` from another checkout (a ``git
archive`` copy of the parent commit under ``.chip_archive/``), so one call
times two trees on one chip; a tree without the kernels reports the rule
and the substitution only. Prints one JSON line (and appends it to
``--out``); exits 1 where a relative error passes ``--tol``."""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ITERS = 10
ORACLE_TOKENS = 512


def check(tokens, heads, key_heads, d_k, d_v, hbs, tol):
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import gated_delta as gd

    f32, bf16 = jnp.float32, jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 7)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    # what the layer hands the rule: unit keys, unit queries x d_k^-1/2,
    # decays of 0.1-10 % a token, beta in (0, 1)
    q = (unit(jax.random.normal(ks[0], (1, tokens, key_heads, d_k)))
         * d_k ** -0.5).astype(bf16)
    k = unit(jax.random.normal(ks[1], (1, tokens, key_heads, d_k))).astype(
        bf16)
    v = jax.random.normal(ks[2], (1, tokens, heads, d_v)).astype(bf16)
    g = -jnp.exp(jax.random.uniform(ks[3], (1, tokens, heads), f32,
                                    jnp.log(1e-3), jnp.log(0.1)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, tokens, heads)))
    ct = jax.random.normal(ks[5], (1, tokens, heads, d_v))
    args = (q, k, v, g, beta)

    def timed(f, *a):
        out = jax.block_until_ready(f(*a))
        t0 = time.monotonic()
        for _ in range(ITERS):
            out = f(*a)
        jax.block_until_ready(out)
        return out, 1e3 * (time.monotonic() - t0) / ITERS

    def grads(fn, ct=ct):
        return jax.jit(lambda *a: jax.vjp(fn, *a)[1](ct))

    def rel(a, b):
        a, b = a.astype(f32), b.astype(f32)
        return float(jnp.sqrt(jnp.mean(jnp.square(a - b))
                              / jnp.mean(jnp.square(b))))

    res = {"shape": [tokens, heads, key_heads, d_k, d_v],
           "device": jax.devices()[0].device_kind}
    out, res["rule_fwd_ms"] = timed(jax.jit(gd.gated_delta_rule), *args)
    _, res["rule_fwd_bwd_ms"] = timed(grads(gd.gated_delta_rule), *args)
    # the recurrence keeps a state a token for its backward (64 KiB a head):
    # held to it over the first ``ORACLE_TOKENS``, gradients of those alone
    short = tuple(x[:, :ORACLE_TOKENS] for x in args)
    ct_short = ct[:, :ORACLE_TOKENS]
    got = grads(gd.gated_delta_rule, ct_short)(*short)
    want_out = jax.jit(gd.gated_delta_recurrent)(*short)
    want = grads(gd.gated_delta_recurrent, ct_short)(*short)
    out = out[:, :ORACLE_TOKENS]
    res["rel"] = {"out": rel(out, want_out), **{
        "d" + name: rel(a, b)
        for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want)}}
    worst = max(res["rel"].values())

    # the chunk inverse alone, on the matrices the rule inverts
    n = tokens // gd.CHUNK
    kc = jnp.repeat(k, heads // key_heads, 2).reshape(
        n, gd.CHUNK, heads, d_k).transpose(2, 0, 1, 3)
    gamma = jnp.cumsum(g[0].reshape(n, gd.CHUNK, heads), 1).transpose(
        2, 0, 1)
    a = jnp.tril(beta[0].reshape(n, gd.CHUNK, heads).transpose(
        2, 0, 1)[..., None]
        * jnp.einsum("hnid,hnjd->hnij", kc, kc, preferred_element_type=f32)
        * jnp.exp(jnp.minimum(gamma[..., :, None] - gamma[..., None, :], 0)),
        -1)
    ct_a = jax.random.normal(ks[6], a.shape)
    eye = jnp.eye(gd.CHUNK, dtype=f32)

    def solve(a):
        return jax.lax.linalg.triangular_solve(
            eye + a, jnp.broadcast_to(eye, a.shape), left_side=True,
            lower=True, unit_diagonal=True)

    def pullback(fn):
        return jax.jit(lambda a: jax.vjp(fn, a)[1](ct_a)[0])

    t_solve, res["solve_fwd_ms"] = timed(jax.jit(solve), a)
    d_solve, res["solve_fwd_bwd_ms"] = timed(pullback(solve), a)
    if hasattr(gd, "unit_lower_inverse"):
        t, res["inverse_fwd_ms"] = timed(jax.jit(gd.unit_lower_inverse), a)
        d, res["inverse_fwd_bwd_ms"] = timed(
            pullback(gd.unit_lower_inverse), a)
        res["inverse_rel"] = {"t": rel(t, t_solve),
                              "da": rel(jnp.tril(d, -1),
                                        jnp.tril(d_solve, -1))}
        worst = max(worst, *res["inverse_rel"].values())

    # the chunk recurrence alone: the two kernels at each hb
    if hasattr(gd, "chunk_recurrence"):
        bh = heads
        kk = jax.random.split(ks[6], 6)

        def rnd(i, cols, dtype):
            return (0.1 * jax.random.normal(
                kk[i], (bh, n, gd.CHUNK, cols))).astype(dtype)

        xs = (rnd(0, d_k, bf16), rnd(1, d_v, f32), rnd(2, d_k, bf16),
              rnd(3, gd.CHUNK, bf16), rnd(4, d_k, bf16),
              jnp.full((bh, n, 1, d_v), 0.9, f32))
        do = rnd(5, d_v, f32)
        res["recurrence"] = {}
        for hb in hbs or [gd._heads_a_step(bh, d_k, d_v, 2)]:
            def fn(*xs, hb=hb):
                return gd.chunk_recurrence(
                    *xs, hb, jax.default_backend() != "tpu")

            _, fwd = timed(jax.jit(fn), *xs)
            _, both = timed(jax.jit(
                lambda *xs, fn=fn: jax.vjp(fn, *xs)[1](do)), *xs)
            res["recurrence"][str(hb)] = {"fwd_ms": fwd,
                                          "fwd_bwd_ms": both}
    res["worst"], res["ok"] = worst, worst <= tol
    return res


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192,
                    help="tokens (fewer for a rehearsal)")
    ap.add_argument("--heads", default="32,16,128,128",
                    help="value heads, key heads, d_k, d_v")
    ap.add_argument("--hb", default="",
                    help="heads a grid step to time, comma-separated "
                         "(default: what the rule picks)")
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--out", default=None)
    ap.add_argument("--tol", type=float, default=3e-2)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    hbs = [int(x) for x in args.hb.split(",") if x]
    res = {"root": args.root,
           **check(args.seq, *map(int, args.heads.split(",")), hbs,
                   args.tol)}
    line = json.dumps(res)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
