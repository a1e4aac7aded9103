#!/usr/bin/env python3
"""Reads, on the chip and at a cell's own size, the numbers that the limits of
``limits.json`` are set from: what sound computations give over many seeds,
and what the controls give, which must come out as not correct.

    python3 benchmarks/control.py --workload <cell> --seeds 12 [--control-seeds 3] [--first-seed N]

For every seed, in one process:

- (d) ``grad_vs_reference``: the program's gradients against the builder's
  plain float32 reference (sound). For the first ``--control-seeds`` seeds
  also every entry of the builder's ``CONTROLS`` (the reference computed in
  the precision below the stated one, put in the program's place: must come
  out above the limit) and of its ``PROBES`` (lower-precision computations
  the comparison cannot tell from the stated precision: read and recorded,
  not required to fail).
- (c) ``state_vs_oracle`` controls (unless ``--skip-oracle``): the averaging
  oracle with a bfloat16 wire and with bfloat16 parameters, put in the
  program's place and compared with the float32 oracle. The sound numbers of
  (c) come from the cell's own runs (``run.py`` prints them), since only those
  run the Manager.

Prints one JSON line per seed and a summary last.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
for p in (REPO_ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--skip-oracle", action="store_true")
    args = ap.parse_args()

    from harness import spec

    cell = spec.Cell(args.workload)
    if args.rehearse:
        from torchft_tpu.utils import force_cpu_devices

        force_cpu_devices(1)
    import jax

    from torchft_tpu.utils import enable_compile_cache

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    enable_compile_cache()

    from harness import reference as R

    driver = spec.module("drivers", cell.mix["driver"])
    cfg, seq = driver.run_config(cell, args.rehearse)
    model = spec.model_of(cfg)
    loss_fn = model.make_loss_fn(cfg, seq, interpret=args.rehearse)
    tx = driver.make_tx(cell.mix)
    n = int(cell.mix["groups"])
    batch = int(cell.mix["batch_per_group"])
    program = jax.jit(jax.value_and_grad(loss_fn))
    ref = R.loss_and_grads(model, cfg)
    lowered = {**{f"control.{k}": v for k, v in model.CONTROLS.items()},
               **{f"probe.{k}": v for k, v in model.PROBES.items()}}
    lowered = {k: R.loss_and_grads(model, cfg, v) for k, v in lowered.items()}
    rows = []
    for i, seed in enumerate(range(args.first_seed,
                                   args.first_seed + args.seeds)):
        row = {"seed": seed}
        params = R.init_params(model, cfg, seed)
        toks = R.make_tokens(cfg, seed, 0, 0, 1, seq)
        want_loss, want = ref(params, toks)
        got_loss, got = program(params, {"tokens": toks})
        row["sound"] = R.grad_distance(got, want)
        row["loss_sound"] = abs(float(got_loss) - float(want_loss)) \
            / abs(float(want_loss))
        del got
        if i < args.control_seeds:
            for name, fn in lowered.items():
                _, ctl = fn(params, toks)
                row[name] = R.grad_distance(ctl, want)
                del ctl
        del want
        gc.collect()
        if not args.skip_oracle and i < args.control_seeds:
            everyone = list(range(n))
            batches = [[R.make_tokens(cfg, seed, g, k, batch, seq)
                        for g in everyone]
                       for k in range(driver.ORACLE_STEPS)]
            who = [[0], everyone]

            def oracle(**kw):
                return R.oracle_steps(loss_fn, tx,
                                      R.init_params(model, cfg, seed),
                                      batches, who, **kw)

            sound = oracle()
            row["state_bf16_wire_control"] = R.state_distance(
                oracle(wire="bfloat16")["sample"], sound)
            row["state_bf16_params_control"] = R.state_distance(
                oracle(store="bfloat16")["sample"], sound)
            row["state_oracle_twice"] = R.state_distance(
                oracle()["sample"], sound)
            del sound
        del params
        gc.collect()
        rows.append(row)
        print(json.dumps(row), flush=True)
    keys = sorted({k for r in rows for k in r if k != "seed"})
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in keys:
        vals = [r[k] for r in rows if k in r]
        summary[f"{k}.n"] = len(vals)
        summary[f"{k}.min"], summary[f"{k}.max"] = min(vals), max(vals)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
