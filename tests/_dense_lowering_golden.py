"""What ``tests/golden_dense_lowering_pr53.json`` holds and how it is made:
the four dense cells of ``BENCHMARK.json`` (no expert layer) at their
rehearsal size, their two step programs (``fwd_bwd`` and ``fused`` of
``benchmarks/harness/reference.py``, the trainer's own) lowered on the CPU,
and of each the sha256 of the lowered text. The file was written by running
this module on PR 53's parent (``python tests/_dense_lowering_golden.py
<file>`` in a ``git archive`` of ``255ea02``); ``tests/test_moe_routed.py``
computes the same on the tree: a change to the expert layers that reaches a
model without one fails there. A change that means to alter the dense
models' program takes the file again and says so."""

import hashlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DENSE_CELLS = ("mistral-7b.steady-1g", "mistral-7b.steady-2g",
               "internlm2-1.8b.kill-heal-2g", "mistral-7b.steady-4g")
PROGRAMS = ("fwd_bwd", "fused")


def lowered_hashes(cell_name: str) -> dict:
    """``{program: sha256 of its lowered text}`` for one dense cell."""
    for p in (REPO, os.path.join(REPO, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import jax.numpy as jnp
    from harness import reference, spec
    from harness.spec import Cell

    cell = Cell(cell_name)
    driver = spec.module("drivers", cell.mix["driver"])
    cfg, seq = driver.run_config(cell, rehearse=True)
    model = spec.model_of(cfg)
    tx = driver.make_tx(cell.mix)
    params = jax.tree_util.tree_map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32),
        model.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    batch = {"tokens": jax.ShapeDtypeStruct((1, seq), jnp.int32)}
    fwd_bwd, fused = reference.step_programs(
        model.make_loss_fn(cfg, seq, interpret=True), tx)
    texts = {
        "fwd_bwd": fwd_bwd.lower(params, None, batch).as_text(),
        "fused": fused.lower(params, None, jax.eval_shape(tx.init, params),
                             batch).as_text()}
    return {name: hashlib.sha256(text.encode()).hexdigest()
            for name, text in texts.items()}


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        json.dump({name: lowered_hashes(name) for name in DENSE_CELLS}, f,
                  indent=1)
        f.write("\n")
