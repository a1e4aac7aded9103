#!/usr/bin/env python3
"""On the chip: one routed expert layer alone (``models/moe.py``
``RoutedMoEMLP``, no shared expert) at the shapes the six sparse cells run
(8,192 tokens; model width, expert width, experts, experts held, experts a
token and the expert's form from ``benchmarks/configs/<cell's
configuration>.json``), bfloat16 rows, under two routings: ``uniform``
(seeded router and inputs: the held experts take about their share of the
pairs) and ``collapsed`` (every token picks the held experts first: every
pair it can lands here and every pass runs). The wall per call of the
layer's forward and of forward + backward (gradients of every weight and of
the input), the layer's own stats (pairs routed, pairs local, largest load
and, since PR 53, passes) and a digest of the output
and of every gradient's bits, which two trees that compute the same bits
share, and each one's sum of magnitudes (float64 on the host: how far two
trees whose bits differ are apart).

    chiprun --chips 1 -- python3 scripts/moe_pass_check.py

``--root DIR`` imports ``torchft_tpu`` from another checkout (a ``git
archive`` copy of the parent commit under ``.chip_archive/``), so one call
times two trees on one chip. ``--tile N`` sets the tree's ``TOKEN_TILE``
where it has one. ``--trace DIR`` also writes a profile of three forward +
backward calls of the first configuration under the uniform routing
(``scripts/chip_trace_order.py`` reads it). Prints one JSON line a
configuration and routing (and appends them to ``--out``)."""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ITERS = 10
CONFIGS = ("smallthinker-21b-a3b", "lfm2-8b-a1b", "trinity-mini",
           "qwen3-next-80b-a3b", "joyai-llm-flash",
           "nemotron-3-nano-30b-a3b")


def shape_of(name: str) -> dict:
    """The expert layer's sizes as the configuration's file states them."""
    with open(os.path.join(HERE, "benchmarks", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)

    def first(*keys):
        return next(cfg[k] for k in keys if k in cfg)

    form = "swiglu"
    if cfg.get("mlp_hidden_act") == "relu2":
        form = "relu2"
    elif "moe_num_primary_experts" in cfg:
        form = "reglu"
    return {"d": cfg["hidden_size"], "h": cfg["moe_intermediate_size"],
            "experts": first("n_routed_experts", "num_experts",
                             "moe_num_primary_experts"),
            "held": cfg["num_experts_held"],
            "k": first("num_experts_per_tok",
                       "moe_num_active_primary_experts"),
            "form": form}


def digest(leaf) -> list:
    """A wrapping sum of the leaf's bits weighted by place, and the sum of
    its magnitudes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    leaf = leaf.reshape(-1).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(leaf, jnp.uint32)
    return [int(jnp.sum(bits * (2 * jnp.arange(bits.size, dtype=jnp.uint32)
                                + 1))),
            float(np.abs(np.asarray(leaf, dtype=np.float64)).sum())]


def check(name, tokens, tile, trace):
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import moe

    if tile and hasattr(moe, "TOKEN_TILE"):
        moe.TOKEN_TILE = tile
    s = shape_of(name)
    layer = moe.RoutedMoEMLP(
        num_experts=s["experts"], mlp_dim=s["h"], top_k=s["k"],
        held=(0, s["held"]), form=s["form"],
        score="softmax" if s["form"] == "reglu" else "sigmoid",
        dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(1), (1, tokens, s["d"]),
                          jnp.bfloat16)
    params = jax.jit(layer.init)(jax.random.key(0), x)

    def loss(p, x):
        out, stats = layer.apply(p, x, return_stats=True)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), (out, stats)

    fwd = jax.jit(lambda p, x: layer.apply(p, x, return_stats=True))
    both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))

    def timed(f, *a):
        out = jax.block_until_ready(f(*a))
        t0 = time.monotonic()
        for _ in range(ITERS):
            out = f(*a)
        jax.block_until_ready(out)
        return out, 1e3 * (time.monotonic() - t0) / ITERS

    # collapsed: positive inputs and a router whose every row favours the
    # held experts, so each token's first picks are held ones
    col = jnp.where(jnp.arange(s["experts"]) < s["held"], 1.0, -1.0)
    routed = {"uniform": (params, x), "collapsed": (
        {"params": {**params["params"], "router": {"kernel":
         jnp.broadcast_to(col, (s["d"], s["experts"])) * 0.05}}},
        jnp.abs(x) + jnp.bfloat16(0.1))}
    for routing, (p, xx) in routed.items():
        res = {"config": name, "routing": routing, "tokens": tokens, **s,
               "device": jax.devices()[0].device_kind,
               "token_tile": getattr(moe, "TOKEN_TILE", None)}
        (_, stats), res["fwd_ms"] = timed(fwd, p, xx)
        ((_, (out, _)), grads), res["fwd_bwd_ms"] = timed(both, p, xx)
        whole = stats[0] if isinstance(stats, tuple) else stats
        res["stats"] = [int(v) for v in whole]
        res["digest"] = {"out": digest(out), **{
            jax.tree_util.keystr(path): digest(leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(grads)[0]}}
        if trace and routing == "uniform":
            jax.profiler.start_trace(trace)
            for _ in range(3):
                jax.block_until_ready(both(p, xx))
            jax.profiler.stop_trace()
        yield res


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8192,
                    help="tokens (fewer for a rehearsal)")
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--tile", type=int, default=0)
    ap.add_argument("--trace", default=None)
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    for i, name in enumerate(args.configs.split(",")):
        for res in check(name, args.tokens, args.tile,
                         args.trace if i == 0 else None):
            line = json.dumps({"root": args.root, **res})
            print(line, flush=True)
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                            exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
