#!/usr/bin/env python3
"""How many of the program's token-expert selections differ from the plain
float32 reference's, at a cell's own size, on the chip (outside any window):
the router compares scores, activations above it are bfloat16 in the program
and float32 in the reference, and where a token's k-th and (k+1)-th scores
lie close the two select differently. A flipped pair moves a whole row into
or out of an expert's gradient, which is what ``grad_vs_reference`` of the
expert leaves is made of.

    python3 benchmarks/route_flips.py --workload <cell> [--seeds 3] [--first-seed N]

For every seed and expert layer: the share of the K selections a token that
are not in the reference's set (``flipped``), the share of tokens with any
such, and the same counting only selections of held experts. Also the
reference with bfloat16 matmul inputs and stream (the stated precision), to
tell what the program's own kernels add. One JSON line a seed, a summary
last. Needs a builder with ``program_selections`` / ``reference_selections``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
for p in (REPO_ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def differ(got, want, first, held):
    """``got`` / ``want`` [T, K] expert ids. Shares of selections in ``got``
    that ``want`` lacks, over all selections and over held experts'."""
    import jax.numpy as jnp

    missing = ~jnp.any(got[:, :, None] == want[:, None, :], axis=-1)  # [T,K]
    on_held = (got >= first) & (got < first + held)
    gone = ~jnp.any(want[:, :, None] == got[:, None, :], axis=-1)
    gone_held = gone & (want >= first) & (want < first + held)
    return {"flipped": float(jnp.mean(missing)),
            "tokens_with_a_flip": float(jnp.mean(jnp.any(missing, axis=-1))),
            "held_rows_added": float(jnp.sum(missing & on_held)),
            "held_rows_lost": float(jnp.sum(gone_held)),
            "held_rows": float(jnp.sum((want >= first)
                                       & (want < first + held)))}


def leaf_distances(model, cfg, seq, rehearse, params, toks):
    """``[[leaf, rms(program - reference) / rms(reference)]]``, largest
    first."""
    import jax
    import jax.numpy as jnp

    from harness import reference as R

    _, want = R.loss_and_grads(model, cfg)(params, toks)
    _, got = jax.jit(jax.value_and_grad(
        model.make_loss_fn(cfg, seq, interpret=rehearse)))(
            params, {"tokens": toks})
    out = []
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        d = jnp.sqrt(jnp.mean(jnp.square(g.astype(jnp.float32) - w))
                     / jnp.mean(jnp.square(w)))
        out.append([jax.tree_util.keystr(path), float(d)])
    return sorted(out, key=lambda kv: -kv[1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_600_000_000)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--leaves", type=int, default=0,
                    help="for the first seed also print this many gradient "
                         "leaves with the largest distance from the "
                         "reference: what grad_vs_reference is made of")
    args = ap.parse_args()

    from harness import spec

    cell = spec.Cell(args.workload)
    if args.rehearse:
        from torchft_tpu.utils import force_cpu_devices

        force_cpu_devices(1)
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    from harness import reference as R

    driver = spec.module("drivers", cell.mix["driver"])
    cfg, seq = driver.run_config(cell, args.rehearse)
    model = spec.model_of(cfg)
    first = int(cfg.get("first_expert_held", 0))
    held = int(cfg["num_experts_held"])
    program = jax.jit(model.program_selections(cfg, seq, args.rehearse))
    sides = {"reference": {},
             "stated_bf16": {k: R._rounder(v) for k, v in
                             model.PROBES["stated_bf16"].items()}}
    refs = {name: jax.jit(lambda p, t, r=r: model.reference_selections(
        p, t, cfg, r)) for name, r in sides.items()}
    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = R.init_params(model, cfg, seed)
        toks = R.make_tokens(cfg, seed, 0, 0, 1, seq)
        got = program(params, toks)
        want = {name: fn(params, toks) for name, fn in refs.items()}
        row = {"seed": seed,
               "program_vs_reference": [
                   differ(g, w, first, held)
                   for g, w in zip(got, want["reference"])],
               "stated_bf16_vs_reference": [
                   differ(g, w, first, held)
                   for g, w in zip(want["stated_bf16"], want["reference"])]}
        if args.leaves and seed == args.first_seed:
            row["largest_leaf_distances"] = leaf_distances(
                model, cfg, seq, args.rehearse, params, toks)[:args.leaves]
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for side in ("program_vs_reference", "stated_bf16_vs_reference"):
        for key in ("flipped", "tokens_with_a_flip"):
            vals = [layer[key] for r in rows for layer in r[side]]
            summary[f"{side}.{key}.min"] = min(vals)
            summary[f"{side}.{key}.max"] = max(vals)
        moved = [(l["held_rows_added"] + l["held_rows_lost"]) / l["held_rows"]
                 for r in rows for l in r[side] if l["held_rows"]]
        if moved:
            summary[f"{side}.held_rows_moved.min"] = min(moved)
            summary[f"{side}.held_rows_moved.max"] = max(moved)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
