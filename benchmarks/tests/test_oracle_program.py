"""The averaging oracle is the trainer's step, in program and in memory
(PR 44).

In program: ``reference.step_programs`` lowers to the text of an
``FTTrainer``'s own ``_fwd_bwd`` and ``_fused``, for a dense builder and for
a sparse one whose loss counts. Where a test here fails after a change to
``torchft_tpu/parallel/step.py``, carry the changed lines of ``FTTrainer``'s
``fwd_bwd`` (the branch without model state) and ``fused`` over into
``benchmarks/harness/reference.py`` ``step_programs``: on the chip two
programs of the same mathematics differ in rounding, and ``state_vs_oracle``
then reads a difference between two copies of a step as
``outputs_incorrect``.

In memory: once the oracle's second step runs, no buffer of the seeded tree
is alive and no sample of an earlier step, so a one-group cell's peak is the
program's six trees and not the check's seven.
"""

import os
import sys
import weakref

import jax
import pytest

from harness import reference as R
from harness import spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
DENSE, COUNTING = "mistral-7b.steady-1g", "trinity-mini.steady-1g-8k"


def job(cell_name):
    """A cell's loss, optimizer and seeded state at its rehearsal widths."""
    cell = spec.Cell(cell_name, REPO)
    driver = spec.module("drivers", cell.mix["driver"])
    cfg, seq = driver.run_config(cell, rehearse=True)
    model = spec.model_of(cfg)
    batch = int(cell.mix["batch_per_group"])
    return {"model": model, "cfg": cfg,
            "loss_fn": model.make_loss_fn(cfg, seq, interpret=True),
            "tx": driver.make_tx(cell.mix),
            "tokens": lambda k: R.make_tokens(cfg, 44, 0, k, batch, seq)}


def trainer_of(loss_fn, tx, params):
    """An ``FTTrainer`` on the mocked control plane, as
    ``tests/test_program_counts.py`` builds its own."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    try:
        from mockplane import make_manager
    finally:
        sys.path.remove(os.path.join(REPO, "tests"))
    from torchft_tpu.parallel import FTTrainer

    return FTTrainer(
        loss_fn=loss_fn, tx=tx, params=params,
        manager_factory=lambda load, save: make_manager(
            load_state_dict=load, state_dict=save, min_replica_size=1))


@pytest.mark.parametrize("cell", [DENSE, COUNTING])
def test_the_oracles_programs_are_the_trainers_by_their_lowered_text(cell):
    j = job(cell)
    params = R.init_params(j["model"], j["cfg"], 44)
    opt = j["tx"].init(params)
    batch = {"tokens": j["tokens"](0)}
    trainer = trainer_of(j["loss_fn"], j["tx"], params)
    try:
        fwd_bwd, fused = R.step_programs(j["loss_fn"], j["tx"])
        ours = {"fwd_bwd": fwd_bwd.lower(params, None, batch),
                "fused": fused.lower(params, None, opt, batch)}
        theirs = {"fwd_bwd": trainer._fwd_bwd.lower(params, None, batch),
                  "fused": trainer._fused.lower(params, None, opt, batch)}
    finally:
        trainer.shutdown()
    for name in ours:
        assert ours[name].as_text() == theirs[name].as_text(), name
        assert ours[name].out_tree == theirs[name].out_tree, name
    # The counting loss's counts are the programs' last output, the dense
    # loss's programs have none.
    counts = jax.tree_util.tree_leaves(
        jax.eval_shape(fused, params, None, opt, batch)[-1])
    assert bool(counts) is (cell == COUNTING)


@pytest.mark.parametrize("cell", [DENSE, COUNTING])
def test_the_oracle_defers_no_counts(cell):
    """The run's counters stay the trainer's."""
    from torchft_tpu import tracing

    j = job(cell)
    before = tracing.program_counters()
    R.oracle_steps(j["loss_fn"], j["tx"],
                   R.init_params(j["model"], j["cfg"], 44),
                   [[j["tokens"](0)], [j["tokens"](1)]], [[0], [0]])
    tracing.settle_program_counts(wait=True)
    after = tracing.program_counters()
    assert {k: v for k, v in after.items() if k.startswith(("moe_", "gdn_"))} \
        == {k: v for k, v in before.items()
            if k.startswith(("moe_", "gdn_"))}


class Watched(list):
    """The oracle's ``batches``: each time a step's batches are handed out it
    records what is alive on the device, in bytes, and which of the seeded
    tree's buffers still are."""

    def __init__(self, batches, seeded):
        super().__init__(batches)
        self.seeded, self.seen = seeded, []

    def __iter__(self):
        for step_batches in list.__iter__(self):
            self.seen.append({
                "bytes": sum(x.nbytes for x in jax.live_arrays()),
                "seeded": sum(ref() is not None for ref in self.seeded)})
            yield step_batches


@pytest.mark.parametrize("groups", [1, 2])
def test_nothing_of_the_seeded_tree_or_an_earlier_sample_is_alive_in_step_two(
        groups):
    j = job(DENSE)
    seeded = []

    def the_seeded_tree():           # no name for it survives this call
        params = R.init_params(j["model"], j["cfg"], 44)
        seeded.extend(weakref.ref(x)
                      for x in jax.tree_util.tree_leaves(params))
        return params

    shapes = jax.eval_shape(lambda: R.init_params(j["model"], j["cfg"], 44))
    state = jax.eval_shape(
        lambda p: {"params": p, "opt_state": j["tx"].init(p)}, shapes)
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(state))
    everyone = list(range(groups))
    batches = Watched([[R.make_tokens(j["cfg"], 44, g, k, 1, 64)
                        for g in everyone] for k in range(3)], seeded)
    already = sum(x.nbytes for x in jax.live_arrays())
    oracle = R.oracle_steps(j["loss_fn"], j["tx"], the_seeded_tree(),
                            batches, [[0], everyone, everyone])
    first, second, third = batches.seen
    assert first["seeded"] == len(seeded) > 0
    # From the second step on: the state, and nothing else the oracle made.
    for moment in (second, third):
        assert moment["seeded"] == 0
        assert moment["bytes"] - already == state_bytes
    # Afterwards: the last step's sample, and no tree.
    assert sum(x.nbytes for x in jax.live_arrays()) - already \
        == sum(x.nbytes for x in oracle["sample"])
    assert len(oracle["moved"]) == len(oracle["sample"])
