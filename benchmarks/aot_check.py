#!/usr/bin/env python3
"""Compiles every cell's step programs at the real widths for a *described*
TPU v5e (no chip attached), before any chip call: what the chip's compiler
would refuse (a kernel's tiling, a program that does not fit) is refused here
at no chip time. Nothing runs, so this says nothing about results or times.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check.py [--workload <cell>] [--batches 1,2,4,8]

For each cell: the single-group fused step (forward, backward and optimizer
in one program, not donated: old and new state live at once, as
``FTTrainer`` runs it) where the mix has one group, else the split step
(forward/backward, then the update). ``--batches`` compiles the first cell
named at those batch sizes and prints which fit: how ``steady-1g``'s
``batch_per_group`` was chosen.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
for p in (REPO_ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

HBM_BYTES = 15.75 * 2**30   # what a v5e chip gives a program (PERF.md, PR 25)


def compile_cell(cell, batch, one_chip):
    import jax
    import jax.numpy as jnp
    from harness import reference, spec

    driver = spec.module("drivers", cell.mix["driver"])
    cfg, seq = driver.run_config(cell, rehearse=False)
    model = spec.model_of(cfg)
    loss_fn = model.make_loss_fn(cfg, seq, interpret=False)
    tx = driver.make_tx(cell.mix)

    def spec_of(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        spec_of, model.param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))
    opt = jax.tree_util.tree_map(
        lambda x: spec_of(x.shape, x.dtype), jax.eval_shape(tx.init, params))
    tokens = {"tokens": spec_of((batch, seq), jnp.int32)}

    # The trainer's own two programs (``reference.step_programs``): the
    # oracle runs them, so what fits here is what both hold.
    fwd_bwd, fused = reference.step_programs(loss_fn, tx)
    if int(cell.mix["groups"]) == 1:
        programs = {"fused_step": (fused, (params, None, opt, tokens))}
    else:
        programs = {"fwd_bwd": (fwd_bwd, (params, None, tokens))}
    out = {}
    for name, (fn, args) in programs.items():
        compiled = fn.lower(*args).compile()
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        out[name] = {"arguments": m.argument_size_in_bytes,
                     "outputs": m.output_size_in_bytes,
                     "temporaries": m.temp_size_in_bytes,
                     "total_gib": total / 2**30,
                     "fits": total <= HBM_BYTES,
                     "flash_kernels": compiled.as_text().count(
                         "tpu_custom_call")}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", action="append")
    ap.add_argument("--batches", default=None)
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness.spec import Cell

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        names = args.workload or [w["name"]
                                  for w in json.load(f)["workloads"]]
    ok = True
    for name in names:
        cell = Cell(name)
        batches = ([int(b) for b in args.batches.split(",")]
                   if args.batches else [int(cell.mix["batch_per_group"])])
        for batch in batches:
            try:
                res = compile_cell(cell, batch, one_chip)
            except Exception as e:  # noqa: BLE001 — the compiler's refusal
                res = {"refused": repr(e)[:400]}
                ok = ok and bool(args.batches)
            print(json.dumps({"workload": name, "batch": batch, **res}),
                  flush=True)
            if not args.batches:
                ok = ok and all(v.get("fits", False) for v in res.values()
                                if isinstance(v, dict))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
