"""Does a cell's fused step with its counts output match the same step
differentiated by hand, bit for bit, on the attached chip? (PERF.md, PR 43)

The benchmark's ``state_vs_oracle`` compares ``FTTrainer``'s step (the loss
under ``tracing.collect_counts``, the counts an output) with a copy of the
step that differentiates the loss by hand (no counts). The sparse models'
routers turn a last-bit difference into another selection, so the two have to
be the same arithmetic; the chip's compiler decides that, and what the counts
look like can change its mind (one joined vector did, in
``joyai-llm-flash``). Both steps run twice from one seeded state here: after
the first step every norm scale has left 1.0, and only then does a
difference in rounding show.

    chiprun --chips 1 -- python3 scripts/step_bits.py trinity-mini.steady-1g-8k [seed]
    JAX_PLATFORMS=cpu python3 scripts/step_bits.py <cell> --rehearse

Exit code 1 where a leaf differs.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "benchmarks")):
    if path not in sys.path:
        sys.path.insert(0, path)


def main() -> int:
    import jax
    import numpy as np
    import optax

    from harness import reference, spec
    from harness.spec import Cell
    from torchft_tpu import tracing
    from torchft_tpu.utils import enable_compile_cache

    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    rehearse = "--rehearse" in sys.argv
    name, seed = args[0], int(args[1]) if len(args) > 1 else 7
    enable_compile_cache()
    cell = Cell(name)
    driver = spec.module("drivers", cell.mix["driver"])
    cfg, seq = driver.run_config(cell, rehearse=rehearse)
    model = spec.model_of(cfg)
    loss_fn = model.make_loss_fn(cfg, seq, interpret=rehearse)
    tx = driver.make_tx(cell.mix)
    batch = int(cell.mix["batch_per_group"])

    def step(collect: bool):
        def fused(p, o, b):
            if collect:
                (loss, counts), grads = jax.value_and_grad(
                    tracing.collect_counts(loss_fn), has_aux=True)(p, b)
            else:
                loss, grads = jax.value_and_grad(loss_fn)(p, b)
                counts = None
            updates, o = tx.update(grads, o, p)
            return loss, optax.apply_updates(p, updates), o, counts
        return jax.jit(fused)

    # "by hand" last: its output is the next step's state, and a third
    # state beside it would not fit the chip.
    programs = {"with counts": step(True), "by hand": step(False)}
    state = {"params": reference.init_params(model, cfg, seed)}
    state["opt_state"] = jax.jit(tx.init)(state["params"])
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(state)[0]]
    worst = 0
    for k in range(2):
        tokens = {"tokens": reference.make_tokens(cfg, seed, 0, k, batch,
                                                  seq)}
        seen = {}
        for which, program in programs.items():
            loss, new_p, new_o, counts = program(
                state["params"], state["opt_state"], tokens)
            new = {"params": new_p, "opt_state": new_o}
            seen[which] = reference.leaf_digests(new)
            print(f"step {k + 1} {which}: loss {float(loss)!r}, counts "
                  f"{counts.totals() if counts is not None else None}",
                  flush=True)
            del new_p, new_o
            if which == "by hand":
                state = new
            del new
        differ = [path for path, a, b in zip(paths, seen["by hand"],
                                             seen["with counts"]) if a != b]
        print(f"step {k + 1}: {len(differ)} of {len(paths)} leaves differ"
              + "".join(f"\n   {path}" for path in differ[:8]), flush=True)
        worst = max(worst, len(differ))
    device = jax.devices()[0]
    print(f"device {device.platform} {device.device_kind}; "
          f"{np.sum([x.size for x in jax.tree_util.tree_leaves(state)])} "
          "elements of state")
    return 1 if worst else 0


if __name__ == "__main__":
    sys.exit(main())
