#!/usr/bin/env python3
"""``benchmarks/control.py``'s readings of the builder's ``CONTROLS`` and
``PROBES``, a few entries a process: the one-chip machine's host has 40 GiB,
and nine plain references of a sparse configuration compiled in one process
ran it out (PR 60: each executable is 240-270 MB and its compile takes many
times that). Same seeds, same numbers, same JSON lines as ``control.py``
prints for its control seeds; the sound readings stay ``control.py``'s
(``--control-seeds 0``). Each reading stands beside the configuration's own
limit (``not_correct``: over it, what the cell's run would report), and
``--leaves N`` names the ``N`` leaves that read farthest (``sound`` in
``--only`` is the program itself: which leaves decide a sound reading).

    python3 scripts/control_some.py --workload <cell> --only fp8_matmul,dense_attention [--seeds 3] [--control-seeds N] [--first-seed N] [--leaves 4]
"""

import argparse
import gc
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
    ap.add_argument("--only", required=True,
                    help="names of CONTROLS / PROBES entries, comma-separated")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="entries other than 'sound' only on the first "
                         "this many seeds (default: all)")
    ap.add_argument("--leaves", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from harness import spec

    cell = spec.Cell(args.workload)
    if args.rehearse:
        from torchft_tpu.utils import force_cpu_devices

        force_cpu_devices(1)
    import jax
    import jax.numpy as jnp

    from torchft_tpu.utils import enable_compile_cache

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    enable_compile_cache()
    from harness import reference as R

    driver = spec.module("drivers", cell.mix["driver"])
    cfg, seq = driver.run_config(cell, args.rehearse)
    model = spec.model_of(cfg)
    table = {**{k: ("control", v) for k, v in model.CONTROLS.items()},
             **{k: ("probe", v) for k, v in model.PROBES.items()}}
    limit = float(cfg["limits"]["grad_vs_reference"])
    ref = R.loss_and_grads(model, cfg)
    program = jax.jit(jax.value_and_grad(
        model.make_loss_fn(cfg, seq, interpret=args.rehearse)))
    table["sound"] = ("program", None)
    lowered = {name: (lambda p, t: program(p, {"tokens": t}))
               if name == "sound" else
               R.loss_and_grads(model, cfg, table[name][1])
               for name in args.only.split(",")}

    @jax.jit
    def by_leaf(got, want):
        return [jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32) - y))
                         / jnp.mean(jnp.square(y)))
                for x, y in zip(jax.tree_util.tree_leaves(got),
                                jax.tree_util.tree_leaves(want))]

    for i, seed in enumerate(range(args.first_seed,
                                   args.first_seed + args.seeds)):
        row = {"seed": seed, "limit": limit}
        params = R.init_params(model, cfg, seed)
        toks = R.make_tokens(cfg, seed, 0, 0, 1, seq)
        _, want = ref(params, toks)
        for name in args.only.split(","):
            if (name != "sound" and args.control_seeds is not None
                    and i >= args.control_seeds):
                continue
            _, ctl = lowered[name](params, toks)
            key = f"{table[name][0]}.{name}"
            row[key] = R.grad_distance(ctl, want)
            row[f"{key}.not_correct"] = row[key] > limit
            if args.leaves:
                names = [jax.tree_util.keystr(p) for p, _ in
                         jax.tree_util.tree_leaves_with_path(want)]
                far = sorted(zip(map(float, by_leaf(ctl, want)), names),
                             reverse=True)[:args.leaves]
                row[f"{key}.leaves"] = [[n, round(d, 4)] for d, n in far]
            del ctl
        del want, params
        gc.collect()
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
