#!/usr/bin/env python3
"""On the chip: the learned sparse attention's kernels alone at the
``keye-vl-2.0-30b-a3b`` cell's widths (32 query heads on 4 key/value heads
of 128, an indexer of 16 heads of 64, bfloat16 operands).

    chiprun --chips 1 -- python3 scripts/sparse_kernel_check.py

Two parts, one JSON line each (appended to ``--out``):

- ``check`` at ``--check-tokens`` (2,048, ``topk`` a quarter of it), where
  the written-out forms fit: the selection against ``jax.lax.top_k`` on the
  same float32 scores (the share of keys that differ, which is 0 where the
  scores are computed alike), the selected flash kernels' output, logsumexp
  and gradients against a masked softmax, the loss kernel's value and
  gradients against autodiff of the divergence written out; with every
  causal key selected, the output against ``flash_attention``'s.
- ``time`` at ``--tokens`` (8,192, ``topk`` 2,048): the wall a call of
  ``sparse_select``, of the selected flash forward and forward + backward,
  of the dense flash kernels beside them, and of ``indexer_loss``.

Exits 1 where a distance passes ``--tol``."""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ITERS = 5
H, G, D, J, C = 32, 4, 128, 16, 64


def _inputs(tokens, seed=0):
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    ks = jax.random.split(jax.random.key(seed), 8)
    q = jax.random.normal(ks[0], (1, tokens, H, D)).astype(bf16)
    k = jax.random.normal(ks[1], (1, tokens, G, D)).astype(bf16)
    v = jax.random.normal(ks[2], (1, tokens, G, D)).astype(bf16)
    a = jax.random.normal(ks[3], (1, tokens, J, C)).astype(bf16)
    b = jax.random.normal(ks[4], (1, tokens, C)).astype(bf16)
    u = jax.random.normal(ks[5], (1, tokens, J))
    g = jax.random.normal(ks[6], (1, tokens, H, D)).astype(bf16)
    return q, k, v, a, b, u, g


def _rel(x, y):
    import jax.numpy as jnp

    x, y = x.astype(jnp.float32), y.astype(jnp.float32)
    return float(jnp.sqrt(jnp.mean(jnp.square(x - y))
                          / jnp.mean(jnp.square(y))))


def check(tokens):
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import flash_attention as _  # noqa: F401
    from torchft_tpu.ops import sparse_index as si

    fa = sys.modules["torchft_tpu.ops.flash_attention"]
    topk = tokens // 4
    q, k, v, a, b, u, g = _inputs(tokens)
    causal = jnp.tril(jnp.ones((tokens, tokens), bool))

    def scores(a, b, u):
        z = jnp.maximum(jnp.einsum("btjc,bsc->btjs", a, b,
                                   preferred_element_type=jnp.float32), 0.0)
        return jnp.einsum("btjs,btj->bts", z, u) * (J * C) ** -0.5

    def top(sc):
        _, idx = jax.lax.top_k(jnp.where(causal, sc, -jnp.inf), topk)
        m = jnp.zeros(sc.shape, bool).at[
            0, jnp.arange(tokens)[:, None], idx[0]].set(True)
        return jnp.logical_and(m, causal)

    def masked(q, k, v, sel):
        kk, vv = jnp.repeat(k, H // G, 2), jnp.repeat(v, H // G, 2)
        lg = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) * D ** -0.5
        lg = jnp.where(sel[:, None], lg, -jnp.inf)
        p = jax.nn.softmax(lg, axis=-1)
        return (jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), vv),
                jax.nn.logsumexp(lg, axis=-1), p)

    def written_kl(a, b, u, q, k, sel):
        p = jnp.mean(masked(q, k, k, sel)[2], axis=1)
        log_r = jax.nn.log_softmax(jnp.where(sel, scores(a, b, u), -jnp.inf),
                                   axis=-1)
        kl = jnp.where(sel, p * (jnp.log(jnp.where(sel, p, 1.0))
                                 - jnp.where(sel, log_r, 0.0)), 0.0)
        return jnp.sum(kl) / tokens

    sel, index_lse = jax.jit(lambda a, b, u: si.select_keys(a, b, u, topk))(
        a, b, u)
    want_sel = jax.jit(lambda a, b, u: top(scores(a, b, u)))(a, b, u)
    row = {"part": "check", "tokens": tokens, "topk": topk,
           "keys_flipped_share": float(
               jnp.sum((sel != 0) != want_sel) / (2 * jnp.sum(want_sel))),
           "rows_short": int(jnp.sum(jnp.sum(sel, -1)[0] != jnp.minimum(
               jnp.arange(tokens) + 1, topk)))}
    selb = sel != 0
    f = jax.jit(lambda q, k, v: fa.sparse_flash_attention(
        q, k, v, sel, return_lse=True))
    (out, lse), (w_out, w_lse, _) = f(q, k, v), jax.jit(
        lambda q, k, v: masked(q, k, v, selb))(q, k, v)
    row["fwd_out"], row["fwd_lse"] = _rel(out, w_out), _rel(lse, w_lse)
    grads = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        f(q, k, v)[0].astype(jnp.float32) * g), (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        masked(q, k, v, selb)[0].astype(jnp.float32) * g), (0, 1, 2)))(
            q, k, v)
    for n, x, y in zip(("dq", "dk", "dv"), grads, want):
        row[n] = _rel(x, y)
    every = jnp.broadcast_to(causal, (1, tokens, tokens))
    row["every_key_vs_flash"] = _rel(
        jax.jit(lambda q, k, v: fa.sparse_flash_attention(q, k, v, every))(
            q, k, v),
        jax.jit(lambda q, k, v: fa.flash_attention(q, k, v))(q, k, v))
    kl, kg = jax.jit(jax.value_and_grad(
        lambda a, b, u: si.indexer_kl(a, b, u, q, k, lse, sel, index_lse),
        (0, 1, 2)))(a, b, u)
    w_kl, w_kg = jax.jit(jax.value_and_grad(
        lambda a, b, u: written_kl(a, b, u, q, k, selb), (0, 1, 2)))(a, b, u)
    row["kl"], row["kl_want"] = float(kl), float(w_kl)
    for n, x, y in zip(("da", "db", "du"), kg, w_kg):
        row["kl_" + n] = _rel(x, y)
    return row


def _wall(fn, *args):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / ITERS


def timings(tokens, topk):
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import sparse_index as si

    fa = sys.modules["torchft_tpu.ops.flash_attention"]
    q, k, v, a, b, u, g = _inputs(tokens, seed=1)
    select = jax.jit(lambda a, b, u: si.select_keys(a, b, u, topk))
    sel, index_lse = select(a, b, u)
    sparse = jax.jit(lambda q, k, v: fa.sparse_flash_attention(
        q, k, v, sel, return_lse=True))
    _, lse = sparse(q, k, v)

    def both(attn):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * g), (0, 1, 2)))

    dense = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v))
    loss = jax.jit(jax.value_and_grad(
        lambda a, b, u: si.indexer_kl(a, b, u, q, k, lse, sel, index_lse),
        (0, 1, 2)))
    return {
        "part": "time", "tokens": tokens, "topk": topk,
        "keys_a_query": float(jnp.mean(jnp.sum(sel.astype(jnp.float32),
                                               -1))),
        "select_ms": _wall(select, a, b, u),
        "sparse_fwd_ms": _wall(sparse, q, k, v),
        "sparse_fwd_bwd_ms": _wall(both(
            lambda q, k, v: fa.sparse_flash_attention(q, k, v, sel)),
            q, k, v),
        "dense_fwd_ms": _wall(dense, q, k, v),
        "dense_fwd_bwd_ms": _wall(both(fa.flash_attention), q, k, v),
        "indexer_loss_ms": _wall(loss, a, b, u)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--check-tokens", type=int, default=2048)
    ap.add_argument("--tol", type=float, default=0.02)
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "sparse_kernel_check.jsonl"))
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    import torchft_tpu.ops.flash_attention  # noqa: F401

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rows = [check(args.check_tokens), timings(args.tokens, 2048)]
    with open(args.out, "a") as f:
        for row in rows:
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
    c = rows[0]
    bad = [n for n in ("fwd_out", "fwd_lse", "dq", "dk", "dv",
                       "every_key_vs_flash", "kl_da", "kl_db", "kl_du")
           if not c[n] <= args.tol]
    bad += ["keys"] * (c["keys_flipped_share"] > 1e-3 or c["rows_short"] > 0)
    print("failed:", bad)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
