"""The routed expert layer over a share of the experts
(``models/moe.py: RoutedMoEMLP``) against a plain loop over experts: held all,
a share, an empty share; the shares add up to the whole layer; nothing is
dropped under the worst imbalance, each for every form of an expert
(``swiglu``: three matrices; ``reglu``: the same three under a ReLU gate,
with its share of active units; ``relu2``: two and a squared ReLU; the pass
loops' hand-written backward against plain autodiff of the loop, a pass
that ends inside a group among them); what the router reads apart from what
the experts compute on (``route_on``); the published order "top k of the
logits, then a softmax over them" against ``route``; the dense-dispatch
layer and the routed one share one router; counters go up once a step under
remat; the rolled sweep over token tiles (PR 53) against the plain loop and,
to the bit, against the loop over a token's pairs it replaces; ``tgmm``
adding into the stacks it is given; and a layer's program is of one size
whatever its tokens, with no ``cond`` and no float32 add of two stacks in
its pass loops."""

import collections
import itertools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _moe_forms_golden import FORMS_THEN, digests

from torchft_tpu import tracing
from torchft_tpu.models import Transformer, tiny_config
from torchft_tpu.models import moe
from torchft_tpu.models.moe import (FORMS, GATES, MOE_COUNTERS,
                                    REGLU_COUNTER, MoEMLP, RoutedMoEMLP,
                                    _tile, padded_rows, route)

E, K, D, H = 16, 4, 64, 32
SCALE = 2.826
EVERY_FORM = pytest.mark.parametrize("form", list(FORMS))


def layer(held, **kw):
    kw = {"shared_dim": H, "pass_rows": 512, "top_k": K, **kw}
    return RoutedMoEMLP(num_experts=E, mlp_dim=H, held=held,
                        route_scale=SCALE, dtype=jnp.float32,
                        interpret=True, **kw)


def whole(stats):
    """A layer's four whole-number stats (a ``reglu`` layer's come with
    its share of active units)."""
    return stats[0] if isinstance(stats, tuple) else stats


def expert(u, p, e=None, form="swiglu"):
    """One expert as its equations read, by the matrices it has: ``gate``,
    ``up``, ``down`` (SwiGLU, or ReGLU by ``form``) or ``up``, ``down``
    (squared ReLU); ``e`` picks it out of the routed stacks, ``None`` is
    the shared one."""
    if e is None:
        up, down = p["up"]["kernel"], p["down"]["kernel"]
        gate = p["gate"]["kernel"] if "gate" in p else None
    else:
        up, down = p["wi_up"][e], p["wo"][e]
        gate = p["wi_gate"][e] if "wi_gate" in p else None
    if gate is None:
        return jnp.square(jax.nn.relu(u @ up)) @ down
    act = jax.nn.relu if form == "reglu" else jax.nn.silu
    return (act(u @ gate) * (u @ up)) @ down


def plain(p, x, first, count, shared=True, form="swiglu", k=K):
    """The layer as its equations read: every held expert computes every
    token under a mask of the pairs routed to it."""
    u = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(u @ p["router"]["kernel"])
    top, idx = jax.lax.top_k(s, k)
    w = SCALE * top / (top.sum(-1, keepdims=True) + 1e-20)
    out = jnp.zeros_like(u)
    if shared:
        out = expert(u, p["shared"], form=form)
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        out = out + w_e[:, None] * expert(u, p, e, form)
    return out.reshape(x.shape)


def sin_grads(fn, p, x):
    """Gradients of ``sum(sin(fn(p, x)))`` in ``p`` and ``x``."""
    return jax.grad(lambda q, x: jnp.sum(jnp.sin(fn(q, x))),
                    argnums=(0, 1))(p, x)


def assert_leaves_close(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=1e-4)


def full_params(seed=0, tokens=256, form="swiglu"):
    x = jax.random.normal(jax.random.key(seed + 100), (2, tokens // 2, D))
    return layer(None, form=form).init(jax.random.key(seed), x)["params"], x


def share_of(p, first, count):
    q = {"router": p["router"], "shared": p["shared"]}
    if count:
        q.update({k: p[k][first:first + count]
                  for k in ("wi_gate", "wi_up", "wo") if k in p})
    return q


_TESTS_RUN = itertools.count(1)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield
    # An interpreted kernel's program is hundreds of memory maps on the CPU
    # and a process may hold 65,530 (``vm.max_map_count``): this file's
    # programs alone pass that, and the compiler then dies in ``mmap``
    # (a segmentation fault in ``backend_compile_and_load``, the worker
    # lost with every test it still had). Dropping them after every test
    # triples the file's time (what the tests share is compiled again).
    if next(_TESTS_RUN) % 16 == 0:
        jax.clear_caches()


@pytest.fixture(autouse=True, scope="module")
def leave_no_programs_behind():
    yield
    jax.clear_caches()


@EVERY_FORM
@pytest.mark.parametrize("held", [(0, E), (4, 3), (15, 1), (5, 0)],
                         ids=["all", "share", "last", "empty"])
def test_routed_layer_against_the_plain_loop(held, form):
    """Forward, and the backward of the pass loops (a ``custom_vjp`` that
    recomputes each pass) against plain autodiff of the loop over
    experts."""
    p, x = full_params(form=form)
    assert sorted(k for k in p if k.startswith("w")) == sorted(FORMS[form])
    assert ("gate" in p["shared"]) == (form in GATES)
    first, count = held
    mine = share_of(p, first, count)
    m = layer(held, form=form)
    out, stats = m.apply({"params": mine}, x, return_stats=True)
    want = plain(mine, x, first, count, form=form)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert isinstance(stats, tuple) == (form == "reglu")
    stats = whole(stats)
    assert int(stats[0]) == x.shape[0] * x.shape[1] * K
    assert int(stats[1]) <= int(stats[0]) and (count or int(stats[1]) == 0)

    assert_leaves_close(
        sin_grads(lambda q, x: m.apply({"params": q}, x,
                                       return_stats=True)[0], mine, x),
        sin_grads(lambda q, x: plain(q, x, first, count, form=form),
                  mine, x))


@EVERY_FORM
@pytest.mark.parametrize("shares", [16, 4], ids=["16x1", "4x4"])
def test_the_shares_add_up_to_the_whole_layer(shares, form):
    """What all the shares give, with the shared expert counted once, is
    the uncut layer."""
    p, x = full_params(seed=3, form=form)
    per = E // shares
    shared_only = plain(share_of(p, 0, 0), x, 0, 0, form=form)
    total = shared_only
    for i in range(shares):
        part = layer((i * per, per), form=form).apply(
            {"params": share_of(p, i * per, per)}, x)
        total = total + (part - shared_only)
    np.testing.assert_allclose(total, plain(p, x, 0, E, form=form),
                               atol=5e-5)


def test_softmax_shares_with_no_shared_expert_add_up_to_the_whole_layer():
    """Two shares of 4 of 8 experts under softmax scores over all 8, the 2
    largest renormalised to sum to one, no scale and NO shared expert (the
    routing ``keye-vl-2.0-30b-a3b`` holds a share under): their sum is the
    uncut layer written out."""
    def make(held):
        return RoutedMoEMLP(num_experts=8, mlp_dim=H, top_k=2, held=held,
                            score="softmax", route_norm=True,
                            dtype=jnp.float32, interpret=True)

    x = jax.random.normal(jax.random.key(7), (2, 64, D))
    p = make(None).init(jax.random.key(8), x)["params"]
    assert "shared" not in p
    u = x.reshape(-1, D)
    top, idx = jax.lax.top_k(jax.nn.softmax(u @ p["router"]["kernel"]), 2)
    w = top / (top.sum(-1, keepdims=True) + 1e-20)
    want = sum(jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
               * expert(u, p, e) for e in range(8)).reshape(x.shape)
    got = sum(make((first, 4)).apply({"params": {
        "router": p["router"],
        **{k: p[k][first:first + 4] for k in ("wi_gate", "wi_up", "wo")}}},
        x) for first in (0, 4))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(make(None).apply({"params": p}, x), want,
                               atol=2e-5)


@EVERY_FORM
@pytest.mark.parametrize("pass_rows", [512, 32768], ids=["passes", "one"])
def test_no_pair_is_dropped_when_every_token_picks_the_same_experts(
        pass_rows, form):
    """Every token's K picks are the K held experts: all T*K pairs land
    here, every pass runs, and the result is still the plain loop's."""
    p, x = full_params(seed=5, tokens=512, form=form)
    x = jnp.abs(x) + 0.1
    col = jnp.where(jnp.arange(E) < K, 1.0, -1.0)
    p = {**p, "router": {"kernel": jnp.broadcast_to(col, (D, E)) * 0.05}}
    mine = share_of(p, 0, K)
    out, stats = layer((0, K), pass_rows=pass_rows, form=form).apply(
        {"params": mine}, x, return_stats=True)
    t = x.shape[0] * x.shape[1]
    assert [int(v) for v in whole(stats)] == [
        t * K, t * K, t, -(-t * K // min(pass_rows, t * K))]
    np.testing.assert_allclose(out, plain(mine, x, 0, K, form=form),
                               atol=5e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("route_on", [None, "x"], ids=["unnamed", "input"])
@pytest.mark.parametrize("form", FORMS_THEN)
def test_the_forms_of_pr50_are_bitwise_what_they_were(form, route_on, dtype):
    """Output, stats and every gradient of the layer in the two forms it
    had, as PR 50's tree computed them here on the CPU
    (``tests/golden_moe_forms_pr50.json``): with the router's input not
    named the program is the parent's, and naming the layer's own input
    gives the same bits but in the input's own gradient, whose two parts
    (the router's, the experts') are then summed in another order."""
    with open(os.path.join(os.path.dirname(__file__),
                           "golden_moe_forms_pr50.json")) as f:
        golden = json.load(f)[form][dtype]
    call = {"route_on": lambda x: x} if route_on else {}
    got = digests(form, dtype, **call)
    if route_on:
        assert got.pop("grad[1]") and golden.pop("grad[1]")
    assert got == golden


def _picks_within(p, x, lo, hi):
    """Inputs and a router under which every token's K picks lie among the
    experts ``lo .. hi``: the inputs' first feature is 1 and the router's
    first row lifts those experts over the others; the rest is the seeded
    routing."""
    x = x.at[..., 0].set(1.0)
    lift = jnp.where(jnp.logical_and(jnp.arange(E) >= lo, jnp.arange(E) < hi),
                     6.0, -6.0)
    kernel = p["router"]["kernel"].at[0].set(lift)
    return {**p, "router": {"kernel": kernel}}, x


@pytest.mark.parametrize("pass_rows,tile", [(512, 96), (32768, 1024)],
                         ids=["passes-tiles", "one"])
@pytest.mark.parametrize("fanout,held", [
    (0, (9, 4)), (1, (7, 3)), (K - 1, (5, 4)), (K, (3, 5)), (3, (5, 3))],
    ids=["0", "1", "J-1", "J", "held<K"])
@EVERY_FORM
def test_the_rolled_sweep_in_the_layer_against_the_plain_loop(
        form, fanout, held, pass_rows, tile, monkeypatch):
    """A token's pairs are summed over the first ``J = min(K, held)``
    columns whatever the most any token has on held experts (``jmax``):
    none (no pass runs), 1, ``J - 1`` (a column of zeros), ``J``, and
    ``J`` = 3 held under 4 a token. Every token picks among the experts
    2..7 and the layer's share holds ``fanout`` of those, so the fan-out
    is that number: forward and every gradient against the plain loop,
    over several passes (each holds part of a token's pairs) and tiles of
    tokens that do not divide them, and at one pass and one tile; and the
    tiled layer gives the bits of the layer in one tile."""
    monkeypatch.setattr(moe, "TOKEN_TILE", tile)
    p, x = full_params(seed=20 + fanout, form=form)
    p, x = _picks_within(p, x, 2, 8)
    first, count = held
    mine = share_of(p, first, count)
    m = layer(held, form=form, pass_rows=pass_rows)

    def mine_of(q, x):
        return m.apply({"params": q}, x, return_stats=True)[0]

    out, stats = m.apply({"params": mine}, x, return_stats=True)
    np.testing.assert_allclose(
        out, plain(mine, x, first, count, form=form), atol=2e-5)
    routed, local, _, passes = (int(v) for v in whole(stats))
    assert passes == -(-local // min(pass_rows, routed))
    assert (local == 0) == (fanout == 0)
    assert (passes > 1) == (pass_rows == 512 and fanout > 2)
    grads = sin_grads(mine_of, mine, x)
    assert_leaves_close(
        grads, sin_grads(lambda q, x: plain(q, x, first, count, form=form),
                         mine, x))
    if tile >= x.shape[0] * x.shape[1]:
        return
    monkeypatch.setattr(moe, "TOKEN_TILE", 4096)
    for a, b in zip(jax.tree_util.tree_leaves((out, grads)),
                    jax.tree_util.tree_leaves(
                        (mine_of(mine, x), sin_grads(mine_of, mine, x)))):
        np.testing.assert_array_equal(a, b)


def _loop_it_replaces(carry, y, pos, start, jmax, scale, round_to):
    """The sums as the pass loops made them before PR 53: a loop over a
    token's pairs of one gather and one add over the whole ``[T, D]`` from
    zeros, the pass's part rounded where the backward rounded it, and the
    part then added to the carry."""
    def add(j, part):
        col = jax.lax.dynamic_index_in_dim(pos, j, 1, keepdims=False)
        rows, inside = moe._slot_rows(y, col, start)
        rows = rows.astype(jnp.float32)
        if scale is None:
            return part + jnp.where(inside[:, None], rows, 0.0)
        by = jax.lax.dynamic_index_in_dim(scale, j, 1, keepdims=False)
        return part + jnp.where(inside, by, 0.0)[:, None] * rows

    part = jax.lax.fori_loop(0, jmax, add, jnp.zeros(carry.shape,
                                                     jnp.float32))
    if round_to is not None:
        part = part.astype(round_to).astype(jnp.float32)
    return carry + part


@pytest.mark.parametrize("tokens,tile", [(256, 96), (256, 64), (100, 1024),
                                         (97, 96)],
                         ids=["ragged", "whole", "one", "one-over"])
@pytest.mark.parametrize("jmax", [0, 1, 5, 6], ids=["0", "1", "J-1", "J"])
@pytest.mark.parametrize("side", ["out", "dx"])
def test_the_rolled_sweep_is_the_loop_it_replaces_bit_for_bit(
        side, jmax, tokens, tile, monkeypatch):
    """:func:`_add_pairs` against the loop over a token's pairs it
    replaces, to the bit: the forward's side (weights, float32) and the
    backward's (no weights, rounded to bfloat16 a pass), for ``jmax`` of 0,
    1, ``J - 1`` and ``J`` = 6 (the columns past ``jmax`` point outside the
    pass, as a pair of an expert that is not held does), a pass that holds
    a third of the slots (so part of a token's pairs), and token tiles
    that divide the tokens, do not, and exceed them."""
    monkeypatch.setattr(moe, "TOKEN_TILE", tile)
    fanout, width = 6, 40
    rows = 8 * -(-tokens * fanout // 24)        # a third of the slots
    keys = jax.random.split(jax.random.key(7 * jmax + tokens), 5)
    slots = jax.random.permutation(keys[0], 3 * rows)[: tokens * jmax]
    pos = jnp.concatenate(
        [slots.reshape(tokens, jmax).astype(jnp.int32),
         jnp.full((tokens, fanout - jmax), 3 * rows + 5, jnp.int32)], axis=1)
    start = jnp.int32(rows)
    y = jax.random.normal(keys[1], (rows, width), jnp.bfloat16)
    carry = jax.random.normal(keys[2], (tokens, width), jnp.float32)
    scale = jax.random.uniform(keys[3], (tokens, fanout)) \
        if side == "out" else None
    round_to = jnp.bfloat16 if side == "dx" else None
    inside = jnp.logical_and(pos >= rows, pos < 2 * rows)
    if jmax:
        assert 0 < int(inside.sum()) < tokens * jmax
    got = jax.jit(lambda *a: moe._add_pairs(
        *a, scale=scale, round_to=round_to))(carry, y, pos, start)
    # the loop's length an argument, as the routing's is: not a constant
    # the compiler could unroll the loop by
    want = jax.jit(lambda *a: _loop_it_replaces(*a, scale, round_to))(
        carry, y, pos, start, jnp.int32(jmax))
    np.testing.assert_array_equal(got, want)
    if not jmax:
        np.testing.assert_array_equal(got, carry)


@pytest.mark.parametrize("sizes", [(40, 0, 24, 0, 64), (0, 0, 0, 0, 128),
                                   (128, 0, 0, 0, 0), (30, 30, 30, 30, 8)],
                         ids=["two-empty", "all-outside", "one", "all"])
def test_tgmm_adds_into_the_stacks_it_is_given(sizes):
    """:func:`_tgmm_into` against ``acc + tgmm(...)``: a group that has rows
    gets the product added to its block, one that has none keeps its block
    as it was, and the rows after the held groups (the last size) add to
    none."""
    rows, groups = sum(sizes), len(sizes) - 1
    keys = jax.random.split(jax.random.key(sum(sizes[:2])), 3)
    lhs = jax.random.normal(keys[0], (rows, D), jnp.float32)
    g = jax.random.normal(keys[1], (rows, H), jnp.float32)
    acc = jax.random.normal(keys[2], (groups, D, H), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = moe._tgmm_into(acc, lhs, g, group_sizes, True)
    fresh = moe._megablox().tgmm(
        lhs.swapaxes(0, 1), g, group_sizes, jnp.float32,
        (moe._row_tile(rows), D, H), num_actual_groups=groups,
        interpret=True)
    ends = np.cumsum(sizes)
    for e in range(groups):
        if sizes[e]:
            np.testing.assert_array_equal(got[e], acc[e] + fresh[e])
            lo = ends[e] - sizes[e]
            np.testing.assert_allclose(
                got[e] - acc[e], lhs[lo:ends[e]].T @ g[lo:ends[e]],
                atol=1e-4)
        else:
            np.testing.assert_array_equal(got[e], acc[e])


def _eqns(jaxpr, inside_pass=False):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold,
    each with whether it lies in a pass loop's body (a ``while`` that
    carries the held stacks); a kernel's own jaxpr is not entered."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_pass
        if eqn.primitive.name == "pallas_call":
            continue
        is_pass = eqn.primitive.name == "while" and any(
            v.aval.shape == (8, D, H) for v in eqn.invars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside_pass or is_pass)


def _layer_and_gradient(form, tokens):
    """A layer that holds 8 of the experts under 8 a token, and its
    forward + gradient as a function of ``(params, x)``."""
    held = (4, 8)
    p, x = full_params(form=form, tokens=tokens)
    m = layer(held, form=form, top_k=8)
    return jax.grad(
        lambda q, x: jnp.sum(jnp.sin(m.apply({"params": q}, x))),
        argnums=(0, 1)), share_of(p, *held), x


@EVERY_FORM
def test_the_program_of_a_layer_does_not_grow_with_its_tokens(
        form, monkeypatch):
    """What PR 52 was refused for, held here: the layer's forward +
    gradient is a program of one size whatever ``T / TOKEN_TILE`` is. The
    lowered text holds the same number of ``gather`` operations (and of
    every other operation) at two tiles of tokens and at eight; the two
    pass loops (the ``while`` equations that carry the held stacks) hold
    no ``cond`` outside a kernel (no ladder of fan-outs, no ``lax.switch``;
    an interpreted kernel has its own); their only
    loops over rows are the sweeps over token tiles, one a loop, whose
    bodies hold ``J`` gathers of rows (the other loops left are
    megablox's binary searches over the groups' sizes, vectors of a few
    numbers); and no float32 ``add`` of two ``[held, D, H]`` or
    ``[held, H, D]`` stacks is left in them (the stacks' gradients are
    added inside ``tgmm``, which takes them as an aliased input)."""
    monkeypatch.setattr(moe, "TOKEN_TILE", 64)

    def ops(tokens):
        f, mine, x = _layer_and_gradient(form, tokens)
        text = jax.jit(f).lower(mine, x).as_text()
        return collections.Counter(re.findall(r"stablehlo\.[a-z_]+", text))

    two, eight = ops(2 * 64), ops(8 * 64)
    assert two["stablehlo.gather"] == eight["stablehlo.gather"] > 0
    assert two == eight

    f, mine, x = _layer_and_gradient(form, 8 * 64)
    eqns = list(_eqns(jax.make_jaxpr(f)(mine, x).jaxpr))
    loops = [e for e, _ in eqns if e.primitive.name == "while" and any(
        v.aval.shape == (8, D, H) for v in e.invars)]
    assert len(loops) == 2                      # forward and backward
    in_pass = [e for e, inside in eqns if inside]
    names = {e.primitive.name for e in in_pass}
    assert "pallas_call" in names
    assert not names & {"cond", "switch"}
    sweeps = [e for e in in_pass if e.primitive.name in ("while", "scan")
              and any(v.aval.ndim > 1 for v in e.invars)]
    assert len(sweeps) == 2                     # the output's, the input's
    for e in sweeps:
        body, = [j for j in jax.core.jaxprs_in_params(e.params)
                 if len(j.eqns) > 4]
        wide = [q for q in body.eqns if q.primitive.name == "gather"
                and q.outvars[0].aval.shape == (64, D)]
        assert len(wide) == 8                   # J = min(8 a token, 8 held)
    stacks = {(8, D, H), (8, H, D)}
    assert not [e for e in in_pass if e.primitive.name in ("add", "add_any")
                and e.outvars[0].aval.shape in stacks]
    # the kernels that add the stacks' gradients take them as an input
    into = [e for e in in_pass if e.primitive.name == "pallas_call"
            and e.outvars[0].aval.shape in stacks]
    assert len(into) == len(FORMS[form])
    assert all(e.params["input_output_aliases"] for e in into)


def _routed_on(p, x, r, first, count, form):
    """``plain`` with the router reading ``r`` and the experts ``x``; no
    shared expert."""
    u, ur = x.reshape(-1, x.shape[-1]), r.reshape(-1, r.shape[-1])
    top, idx = jax.lax.top_k(jax.nn.sigmoid(ur @ p["router"]["kernel"]), K)
    w = SCALE * top / (top.sum(-1, keepdims=True) + 1e-20)
    out = jnp.zeros_like(u)
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        out = out + w_e[:, None] * expert(u, p, e, form)
    return out.reshape(x.shape)


@EVERY_FORM
def test_the_router_reads_route_on_and_the_experts_the_input(form):
    """``route_on``: selection and weights are that stream's, the experts
    compute on the input. Against the plain loop, forward and every
    gradient (the router's kernel and ``route_on`` take the weights'
    gradient, the input only the experts'); the selection follows
    ``route_on`` alone."""
    first, count = 4, 8
    m = layer((first, count), form=form, shared_dim=0)
    x = jax.random.normal(jax.random.key(11), (2, 128, D))
    r = jax.random.normal(jax.random.key(12), (2, 128, D))
    p = m.init(jax.random.key(0), x)["params"]

    def mine(q, x, r):
        return m.apply({"params": q}, x, route_on=r)

    np.testing.assert_allclose(mine(p, x, r),
                               _routed_on(p, x, r, first, count, form),
                               atol=2e-5)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2))(p, x, r)

    got = grads(mine)
    want = grads(lambda q, x, r: _routed_on(q, x, r, first, count, form))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=1e-4)
    assert float(jnp.max(jnp.abs(got[2]))) > 1e-4      # reaches route_on
    # the input's gradient holds nothing of the router's: the router's
    # kernel scaled changes it only through the weights' values
    def picks(x, r):
        _, state = m.apply({"params": p}, x, route_on=r,
                           mutable=["intermediates"])
        return np.asarray(state["intermediates"]["experts"][0])

    np.testing.assert_array_equal(picks(x, r), picks(2.0 * x + 1.0, r))
    assert (picks(x, r) != picks(x, r[:, ::-1])).any()
    with pytest.raises(ValueError, match="route_on"):
        m.apply({"params": p}, x, route_on=r[:, :64])


def test_reglu_counts_the_share_of_active_units():
    """The fourth number of a ``reglu`` layer: over the rows that hold a
    pair of a held expert, the share of hidden units with ``gate > 0``,
    added up over the passes (two here, the first ending inside a group),
    against the plain count; zeroed gates read 0."""
    first, count = 4, 8
    p, x = full_params(seed=9, form="reglu")
    mine = share_of(p, first, count)
    _, (stats, active) = layer((first, count), form="reglu").apply(
        {"params": mine}, x, return_stats=True)
    u = x.reshape(-1, D)
    _, idx = jax.lax.top_k(jax.nn.sigmoid(u @ p["router"]["kernel"]), K)
    hot = rows = 0
    for e in range(count):
        on_e = np.asarray(jnp.any(idx == first + e, axis=-1))
        rows += int(on_e.sum())
        hot += int((np.asarray(u @ mine["wi_gate"][e]) > 0)[on_e].sum())
    assert rows == int(stats[1]) and rows > 512     # more than one pass
    np.testing.assert_allclose(float(active), hot / (rows * H), rtol=1e-6)
    assert 0.4 < float(active) < 0.6
    dead = {**mine, "wi_gate": -jnp.abs(mine["wi_gate"])}
    _, (_, none) = layer((first, count), form="reglu").apply(
        {"params": dead}, jnp.abs(x), return_stats=True)
    assert float(none) == 0.0


def test_top_k_then_softmax_is_the_softmax_router_normalised():
    """The published order of a softmax router that normalises (the k
    largest logits, then a softmax over those k) is ``route("softmax",
    route_norm=True)``: a softmax over all, the k largest, divided by their
    sum. Selections equal, weights to float32's rounding."""
    logits = 3.0 * jax.random.normal(jax.random.key(3), (512, 64))
    top, idx = jax.lax.top_k(logits, 6)
    want = jax.nn.softmax(top, axis=-1)
    w, got_idx, _ = route(logits, 6, "softmax", True, 1.0)
    np.testing.assert_array_equal(got_idx, idx)
    np.testing.assert_allclose(w, want, rtol=2e-6, atol=1e-9)
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, rtol=1e-6)


def test_dense_and_routed_dispatch_share_one_router():
    """The same weights through MoEMLP (every expert computes every token)
    and through RoutedMoEMLP with the same router settings give the same
    layer: there is one router, not two that could disagree."""
    x = jax.random.normal(jax.random.key(2), (2, 64, D))
    dense = MoEMLP(num_experts=8, mlp_dim=H, top_k=2, dtype=jnp.float32)
    p = dense.init(jax.random.key(0), x)["params"]
    routed = RoutedMoEMLP(num_experts=8, mlp_dim=H, top_k=2, score="softmax",
                          dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(routed.apply({"params": p}, x),
                               dense.apply({"params": p}, x), atol=2e-5)
    w, idx, scores = route(jnp.log(jnp.array([[0.1, 0.2, 0.3, 0.4]])), 2)
    np.testing.assert_allclose(w, [[4 / 7, 3 / 7]], rtol=1e-6)
    assert idx.tolist() == [[3, 2]]


@pytest.mark.parametrize("pairs,slots", [(8, 8), (100, 104), (512, 512),
                                         (1000, 1024), (131072, 131072)])
def test_slots_are_whole_row_tiles(pairs, slots):
    assert padded_rows(pairs) == slots


@pytest.mark.parametrize("dim,cap,tile", [
    # the widths the three SwiGLU cells run: what they always were
    (2048, 1024, 1024), (2048, 512, 512), (1024, 1024, 1024),
    (1024, 512, 512), (768, 1024, 768), (768, 512, 256), (512, 512, 512),
    # 2688 = 21 x 128 and 1856 = 14.5 x 128 have no power-of-two divisor
    # over 128: the multiple of 128 that pads least, the largest such
    (2688, 1024, 896), (2688, 512, 384), (1856, 1024, 640),
    (1856, 512, 384),
    # 1792 = 14 x 128 = 7 x 256: a quarter of the cap divides it, in seven
    # steps; 896 does in two (PR 47). Under the backward's cap half divides
    (1792, 1024, 896), (1792, 512, 256)])
def test_product_tiles_by_width(dim, cap, tile):
    assert _tile(dim, cap) == tile
    assert tile <= cap and (tile % 128 == 0 or tile == dim)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_counters_go_up_once_a_step(remat):
    """Three expert layers, one step of value_and_grad: each counter goes up
    by one step's worth, also when the layers are rematerialised."""
    cfg = tiny_config(num_layers=4, moe_experts=8, moe_top_k=2,
                      moe_dispatch="routed", moe_held=(2, 4), moe_dim=32,
                      moe_dense_layers=1, moe_interpret=True, remat=remat,
                      dtype=jnp.float32)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    params = model.init(jax.random.key(0), toks)
    before = tracing.program_counters()
    step = jax.jit(jax.value_and_grad(tracing.collect_counts(
        lambda p: jnp.mean(model.apply(p, toks) ** 2)), has_aux=True))

    def run():
        (_, counts), _ = step(params)
        assert counts.keys == (tuple(sorted(MOE_COUNTERS)),)
        assert REGLU_COUNTER not in counts.keys[0]
        tracing.defer_program_counts(counts)
        tracing.settle_program_counts(wait=True)

    run()
    after = tracing.program_counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in MOE_COUNTERS}
    assert delta["moe_pairs_routed_total"] == 3 * 64 * 2
    assert 0 < delta["moe_pairs_local_total"] < 3 * 64 * 2
    assert 0 < delta["moe_expert_load_max_total"] \
        <= delta["moe_pairs_local_total"]
    # a pass a layer (64 tokens' pairs fit one), three layers
    assert delta["moe_passes_total"] == 3
    run()
    assert tracing.program_counters()["moe_pairs_routed_total"] \
        == after["moe_pairs_routed_total"] + 3 * 64 * 2


@pytest.mark.parametrize("counter", MOE_COUNTERS[1:])
def test_manager_metrics_report_the_program_counters(counter):
    from mockplane import make_manager

    tracing.add_program_counters(**{counter: 5})
    m = make_manager()
    try:
        got = m.metrics()
        assert got[counter] >= 5.0
        assert all(isinstance(got[k], float) for k in got
                   if k.startswith("moe_"))
    finally:
        m.shutdown()
