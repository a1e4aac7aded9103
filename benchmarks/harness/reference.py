"""What every builder's reference shares: weights and data made from the
seed, the roundings a control puts into the reference, the numbers two
computations are compared by, the data-parallel averaging oracle and the
bitwise digests.

The model itself (the program's loss function and the plain reference beside
it) is the builder's: ``models/<builder>.py``, named by the configuration.
"""

from __future__ import annotations

import functools
from typing import (Any, Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple)


def init_params(model: Any, cfg: Mapping[str, Any], seed: int) -> Any:
    """Seeded float32 weights at the builder's ``param_shapes``, made on the
    default device in one jitted program: normal(0, ``initializer_range``)
    matrices, one-dimensional scales at one. ``seed`` is any whole number; it
    is folded to the 32 bits a key takes."""
    shapes = model.param_shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = [jnp.ones(s, jnp.float32) if len(s) == 1 else
               std * jax.random.normal(k, s, jnp.float32)
               for k, s in zip(keys, leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(jax.random.key(fold_seed(seed)))


def fold_seed(seed: int) -> int:
    """Any whole number -> 31 bits, so that it fits a signed 32-bit key
    and numpy's seed alike. Injective on 0 .. 2**31-1."""
    seed = int(seed)
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


def make_tokens(cfg: Mapping[str, Any], seed: int, stream: int, step: int,
                batch: int, seq: int) -> Any:
    """The ``step``-th batch of data stream ``stream`` (one stream per
    replica group): uniform token ids, as numpy int32."""
    import numpy as np

    rng = np.random.default_rng([fold_seed(seed), int(stream), int(step)])
    return rng.integers(0, int(cfg["vocab_size"]), size=(batch, seq),
                        dtype=np.int32)


# ------------------------------------------------------------- roundings

# exponent and mantissa bits of the types a control rounds to
PRECISIONS = {"bfloat16": (8, 7), "float8_e4m3": (4, 3)}


def round_to(precision: str) -> Callable:
    """A function that rounds float32 values to ``precision`` and returns
    float32. ``reduce_precision`` and not a pair of converts: on a TPU, XLA
    is allowed to keep excess precision and drops a float32 -> bfloat16 ->
    float32 round trip inside a program (the bfloat16-parameters control
    read exactly 0 that way, PR 26). Gradients pass straight through, as in
    training with narrow matmul inputs and wide gradients."""
    exp, man = PRECISIONS[precision]

    def rounded(x):
        return x + jax.lax.stop_gradient(
            jax.lax.reduce_precision(x, exp, man) - x)

    return rounded


def round_both(precision: str) -> Callable:
    """As ``round_to``, and the cotangent is rounded too: what a computation
    carried out in ``precision`` does to a value on the way forward and to
    its gradient on the way back."""
    exp, man = PRECISIONS[precision]

    @jax.custom_vjp
    def rounded(x):
        return jax.lax.reduce_precision(x, exp, man)

    rounded.defvjp(lambda x: (rounded(x), None),
                   lambda _, g: (jax.lax.reduce_precision(g, exp, man),))
    return rounded


def _rounder(how: str) -> Callable:
    precision, _, mode = how.partition("/")
    if mode not in ("", "forward"):
        raise ValueError(f"unknown rounding mode in {how!r}")
    return round_to(precision) if mode else round_both(precision)


def loss_and_grads(model: Any, cfg: Mapping[str, Any],
                   lowered: Optional[Mapping[str, str]] = None) -> Callable:
    """The builder's plain reference as ``(params, tokens) -> (loss,
    gradients)``. ``lowered`` maps the reference's rounding sites to a type
    (a control or a probe of the builder's); ``grads`` rounds the gradient
    leaves."""
    lowered = dict(lowered or {})
    grads_to = lowered.pop("grads", None)
    rounding = {site: _rounder(how) for site, how in lowered.items()}
    fn = jax.value_and_grad(functools.partial(
        model.reference_loss, cfg=cfg, rounding=rounding))
    if grads_to is None:
        return jax.jit(fn)
    leaf = _rounder(grads_to)

    @jax.jit
    def with_rounded_grads(params, tokens):
        loss, grads = fn(params, tokens)
        return loss, jax.tree_util.tree_map(leaf, grads)

    return with_rounded_grads


# ----------------------------------------------------- comparison numbers

def grad_distance(got: Any, want: Any) -> float:
    """Largest over the leaves of rms(got - want) / rms(want): a gradient
    leaf's error against that leaf's own scale. A root mean square over a
    leaf's millions of elements is steady from seed to seed (within a few
    percent); the largest element's error, compared until the review of
    PR 26, swung by 60 %."""
    @jax.jit
    def dist(a, b):
        return jnp.stack([
            jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32) - y))
                     / jnp.mean(jnp.square(y)))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))])

    return float(jnp.max(dist(got, want)))


# ------------------------------------------------- the averaging oracle

SAMPLE = 1 << 20  # elements kept of each leaf


def sample_state(state: Any) -> List[Any]:
    """Every leaf of ``state`` thinned to at most about SAMPLE elements at a
    fixed stride: small enough to keep beside two replica groups' state, and
    a lower wire or parameter precision shows in every element."""
    return jax.jit(_thin)(state)


def _thin(tree: Any) -> List[Any]:
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        flat = x.reshape(-1)
        out.append(flat[:: max(1, flat.size // SAMPLE)])
    return out


def step_programs(loss_fn: Callable, tx: Any) -> Tuple[Callable, Callable]:
    """``(fwd_bwd, fused)``, jitted: the two programs of an ``FTTrainer``
    without model state, letter for letter
    (``torchft_tpu/parallel/step.py``, ``FTTrainer.__init__``: the
    ``fwd_bwd`` of its ``else`` branch and ``fused``), so that the chip's
    compiler gives the oracle and the trainer the same code: a different
    program of the same mathematics differs at bfloat16 level there (XLA may
    keep excess precision wherever a fusion ends), which adam then amplifies
    and a router turns into another selection. That includes the loss under
    ``tracing.collect_counts`` and its counts as each program's last output:
    differentiated by hand the step has one output fewer, and the compiler
    is then free to schedule it otherwise (PERF.md, PR 43).
    ``benchmarks/tests/test_oracle_program.py`` holds the two to the
    trainer's own by their lowered text: a change to either side alone
    fails there, and these lines are then carried over from ``step.py``."""
    import optax

    from torchft_tpu import tracing

    def fwd_bwd(p, st, batch):
        (loss, counts), grads = jax.value_and_grad(
            tracing.collect_counts(loss_fn), has_aux=True)(p, batch)
        return loss, None, grads, counts

    def fused(p, st, o, batch):
        loss, new_st, grads, counts = fwd_bwd(p, st, batch)
        updates, new_o = tx.update(grads, o, p)
        return (loss, new_st, optax.apply_updates(p, updates), new_o,
                counts)

    return jax.jit(fwd_bwd), jax.jit(fused)


def oracle_steps(loss_fn: Callable, tx: Any, params0: Any,
                 batches: Sequence[Sequence[Any]],
                 contributors: Sequence[Sequence[int]], wire: Any = None,
                 store: Any = None) -> Dict[str, Any]:
    """What the job's first ``len(batches)`` data-parallel steps from
    ``params0`` must give. In step ``k`` the groups named in
    ``contributors[k]`` each take the gradient of ``batches[k][group]`` by
    the program's own ``loss_fn`` with no Manager; the sum is divided by the
    number of groups in float32 and ``tx`` is applied. (The protocol's first
    step is its init sync: every group but the primary adopts the primary's
    weights and contributes zeros, so only group 0 is named there.)

    Returns the thinned state ``{"params", "opt_state"}`` after the last
    step and, leaf by leaf, how far that step moved it: the scale an error
    is read against.

    ``params0`` becomes the oracle's: pass the seeded tree without keeping
    a name for it, and its buffers go when the first step has run. With one
    group the oracle then holds what the trainer's non-donated step holds,
    state in and state out, and one thinned sample beside them in the last
    step; it must not hold more, or a cell is sized by its check.

    The controls: ``wire`` rounds each group's gradients to that type before
    the sum, as a narrower wire would; ``store`` rounds the updated
    parameters to that type, as parameters kept in it would be.

    What the loss counts (``tracing.count_in_program``) leaves the programs
    as in the trainer and is dropped here: the run's counters stay the
    trainer's."""
    import optax

    n_groups = len(batches[0])
    wire_fn = round_to(wire) if wire is not None else None
    store_fn = round_to(store) if store is not None else None
    fwd_bwd_jit, fused_jit = step_programs(loss_fn, tx)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(total, params, opt, n):
        avg = jax.tree_util.tree_map(lambda x: x / n, total)
        updates, new_opt = tx.update(avg, opt, params)
        return optax.apply_updates(params, updates), new_opt

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    thin = jax.jit(_thin)
    params, opt = params0, jax.jit(tx.init)(params0)
    del params0
    before = None
    for k, (step_batches, who) in enumerate(zip(batches, contributors)):
        if k == len(contributors) - 1:
            before = thin({"params": params, "opt_state": opt})
        if n_groups == 1 and wire_fn is None:
            _, _, new, new_opt, _ = fused_jit(
                params, None, opt, {"tokens": step_batches[0]})
        else:
            total = None
            for g in who:
                _, _, one, _ = fwd_bwd_jit(params, None,
                                           {"tokens": step_batches[g]})
                if wire_fn is not None:
                    one = jax.jit(lambda t: jax.tree_util.tree_map(
                        wire_fn, t))(one)
                # The sum in place, and waited for: a gradient let go
                # while the sum that reads it is queued would still be
                # there when the next group's is allocated (with four
                # groups the oracle held eight trees so, 15.09 GiB of
                # the chip's 15.75, where a trainer holds six; PR 44).
                total = one if total is None else add(total, one)
                del one
                jax.block_until_ready(total)
            new, new_opt = update(total, params, opt, float(n_groups))
        if store_fn is not None:
            new = jax.jit(lambda t: jax.tree_util.tree_map(store_fn, t),
                          donate_argnums=(0,))(new)
        # Wait for the step, then let the old state go: a buffer dropped
        # while its program runs is freed some time after, and what is
        # enqueued next (the sample below) is allocated beside it: a second
        # sample at the peak (read on the chip, PR 44).
        jax.block_until_ready((new, new_opt))
        params, opt = new, new_opt
        del new, new_opt
    sample = thin({"params": params, "opt_state": opt})
    del params, opt
    moved = [float(_rms(a, b)) for a, b in zip(sample, before)]
    return {"sample": sample, "moved": moved}


def _rms(a: Any, b: Any) -> Any:
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sqrt(jnp.mean(d * d))


def state_distance(got: List[Any], oracle: Dict[str, Any]) -> float:
    """Largest over the leaves of rms(got - want) / rms(how far the oracle's
    last step moved that leaf). A leaf the step does not move must be equal.

    Root mean square and not the largest element: adam divides by |g|, so
    the few elements whose gradient is near zero amplify a last-bit
    difference between two compiled programs to the size of the step
    (1.6 of it was read on the chip, PR 26), while a narrower wire or
    parameter type shows in every element."""
    worst = 0.0
    for g, w, moved in zip(got, oracle["sample"], oracle["moved"]):
        diff = float(_rms(g, w))
        if moved == 0.0:
            worst = max(worst, 0.0 if diff == 0.0 else float("inf"))
        else:
            worst = max(worst, diff / moved)
    return worst


def leaf_digests(tree: Any) -> List[List[int]]:
    """Two wrapping 32-bit sums of every leaf's bit patterns, computed where
    the leaf lives: equal lists are bitwise equal trees as far as any
    single-element change goes, and nothing crosses to the host but the
    sums."""
    @jax.jit
    def digest(tree):
        out = []
        for x in jax.tree_util.tree_leaves(tree):
            bits = jax.lax.bitcast_convert_type(
                x.reshape(-1), jnp.uint32 if x.dtype.itemsize == 4
                else jnp.uint16).astype(jnp.uint32)
            idx = jnp.arange(bits.size, dtype=jnp.uint32)
            out.append(jnp.stack([jnp.sum(bits),
                                  jnp.sum(bits * (2 * idx + 1))]))
        return out

    return [[int(v) for v in d] for d in digest(tree)]
