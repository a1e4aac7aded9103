"""The comparison that decides ``correct``, at a size a test run can hold:
the program is inside the limits of ``limits.json`` and every control is
outside them."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from harness import reference as R
from harness import spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
M = spec.module("models", "dense_gqa_decoder")
make_loss_fn = M.make_loss_fn
make_tx = spec.module("drivers", "train_groups").make_tx
CFG = {**M.REHEARSE, "num_hidden_layers": 2, "rms_norm_eps": 1e-5,
       "rope_theta": 1e4}
SEQ = 64


@pytest.fixture(scope="module")
def limits():
    with open(os.path.join(BENCH, "limits.json")) as f:
        return {k: v["limit"] for k, v in json.load(f).items()}


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_001])
def test_program_matches_the_reference_and_every_control_does_not(
        seed, limits):
    loss_fn = make_loss_fn(CFG, SEQ, interpret=True)
    params = R.init_params(M, CFG, seed)
    toks = R.make_tokens(CFG, seed, 0, 0, 1, SEQ)
    _, want = R.loss_and_grads(M, CFG)(params, toks)
    _, got = jax.jit(jax.value_and_grad(loss_fn))(params, {"tokens": toks})
    assert R.grad_distance(got, want) <= limits["grad_vs_reference"]
    assert M.CONTROLS
    for lowered in M.CONTROLS.values():
        _, ctl = R.loss_and_grads(M, CFG, lowered)(params, toks)
        assert R.grad_distance(ctl, want) > 3 * limits["grad_vs_reference"]
    # The probes are computations the comparison cannot tell from the stated
    # precision: they run, and come out inside the limit (PERF.md section 2).
    for lowered in M.PROBES.values():
        _, probe = R.loss_and_grads(M, CFG, lowered)(params, toks)
        assert 0 < R.grad_distance(probe, want) <= limits["grad_vs_reference"]


@pytest.mark.parametrize("opt", [{"name": "sgd", "lr": 1e-3},
                                 {"name": "adamw", "lr": 3e-4}])
def test_a_narrower_wire_or_parameter_type_fails_the_oracle(opt, limits):
    loss_fn = make_loss_fn(CFG, SEQ, interpret=True)
    tx = make_tx({"optimizer": opt})
    seed, groups = 11, [0, 1]
    batches = [[R.make_tokens(CFG, seed, g, k, 1, SEQ) for g in groups]
               for k in range(2)]

    def oracle(**kw):
        return R.oracle_steps(loss_fn, tx, R.init_params(M, CFG, seed), batches,
                              [[0], groups], **kw)

    sound = oracle()
    assert R.state_distance(oracle()["sample"], sound) == 0.0
    limit = limits["state_vs_oracle"]
    # (at these tiny widths adam's wire control reads 0.005-0.007, on the
    # chip at real widths 0.025-0.065)
    assert R.state_distance(oracle(wire="bfloat16")["sample"],
                            sound) > limit
    assert R.state_distance(oracle(store="bfloat16")["sample"],
                            sound) > 3 * limit


def test_digests_see_one_changed_bit_and_seeds_fold():
    params = R.init_params(M, CFG, 5)
    a = R.leaf_digests(params)
    leaf = params["params"]["lm_head"]["kernel"]
    bits = jax.lax.bitcast_convert_type(leaf, jnp.uint32)
    params["params"]["lm_head"]["kernel"] = jax.lax.bitcast_convert_type(
        bits.at[3, 5].set(bits[3, 5] ^ 1), jnp.float32)
    b = R.leaf_digests(params)
    assert sum(x != y for x, y in zip(a, b)) == 1
    assert R.fold_seed(2**31 + 5) != R.fold_seed(5)
    assert 0 <= R.fold_seed(2**31 + 2**20) < 2**31
    assert (R.make_tokens(CFG, 2**31 + 5, 0, 0, 1, 8)
            == R.make_tokens(CFG, 2**31 + 5, 0, 0, 1, 8)).all()
    assert (R.make_tokens(CFG, 2**31 + 5, 0, 0, 1, 8)
            != R.make_tokens(CFG, 2**31 + 5, 1, 0, 1, 8)).any()
