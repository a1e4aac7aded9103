#!/usr/bin/env python3
"""On the chip: the share of a learned sparse attention's selected keys that
differ between the program (bfloat16 compute, the ``sparse_select`` kernel)
and the builder's float32 reference (``jax.lax.top_k`` a row), layer by
layer, on seeded weights at the cell's own size; and the same for the
reference at the stated precision. What ``limits_readings.made_of`` of a
configuration with such an attention quotes beside the experts' flips
(``benchmarks/route_flips.py``).

    chiprun --chips 1 -- python3 scripts/sparse_key_flips.py --workload keye-vl-2.0-30b-a3b.steady-1g-8k
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.join(HERE, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_600_000_000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from harness import spec

    cell = spec.Cell(args.workload)
    if args.rehearse:
        from torchft_tpu.utils import force_cpu_devices

        force_cpu_devices(1)
    import jax
    import jax.numpy as jnp

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    from harness import reference as R

    driver = spec.module("drivers", cell.mix["driver"])
    cfg, seq = driver.run_config(cell, args.rehearse)
    model = spec.model_of(cfg)
    program = jax.jit(model.program_key_selections(cfg, seq, args.rehearse))
    sides = {"reference": {},
             "stated_bf16": {k: R._rounder(v) for k, v in
                             model.PROBES["stated_bf16"].items()}}
    refs = {name: jax.jit(lambda p, t, r=r: model.reference_key_selections(
        p, t, cfg, r)) for name, r in sides.items()}

    def flipped(got, want):
        # a flipped key is one lost and one gained: half the differing pairs
        return float(jnp.sum((got != 0) != (want != 0)) / (2 * jnp.sum(want)))

    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = R.init_params(model, cfg, seed)
        toks = R.make_tokens(cfg, seed, 0, 0, 1, seq)
        got = program(params, toks)
        want = {name: fn(params, toks) for name, fn in refs.items()}
        row = {"seed": seed,
               "program_vs_reference": [
                   flipped(g, w) for g, w in zip(got, want["reference"])],
               "stated_bf16_vs_reference": [
                   flipped(g, w) for g, w in zip(want["stated_bf16"],
                                                 want["reference"])]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for side in ("program_vs_reference", "stated_bf16_vs_reference"):
        vals = [v for r in rows for v in r[side]]
        summary[f"{side}.keys_flipped.min"] = min(vals)
        summary[f"{side}.keys_flipped.max"] = max(vals)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
