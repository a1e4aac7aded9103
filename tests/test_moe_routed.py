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
remat."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _moe_forms_golden import FORMS_THEN, digests

from torchft_tpu import tracing
from torchft_tpu.models import Transformer, tiny_config
from torchft_tpu.models.moe import (FORMS, GATES, MOE_COUNTERS,
                                    REGLU_COUNTER, MoEMLP, RoutedMoEMLP,
                                    _tile, padded_rows, route)

E, K, D, H = 16, 4, 64, 32
SCALE = 2.826
EVERY_FORM = pytest.mark.parametrize("form", list(FORMS))


def layer(held, **kw):
    kw = {"shared_dim": H, "pass_rows": 512, **kw}
    return RoutedMoEMLP(num_experts=E, mlp_dim=H, top_k=K, held=held,
                        route_scale=SCALE, dtype=jnp.float32,
                        interpret=True, **kw)


def whole(stats):
    """A layer's three whole-number stats (a ``reglu`` layer's come with
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


def plain(p, x, first, count, shared=True, form="swiglu"):
    """The layer as its equations read: every held expert computes every
    token under a mask of the pairs routed to it."""
    u = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(u @ p["router"]["kernel"])
    top, idx = jax.lax.top_k(s, K)
    w = SCALE * top / (top.sum(-1, keepdims=True) + 1e-20)
    out = jnp.zeros_like(u)
    if shared:
        out = expert(u, p["shared"], form=form)
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        out = out + w_e[:, None] * expert(u, p, e, form)
    return out.reshape(x.shape)


def full_params(seed=0, tokens=256, form="swiglu"):
    x = jax.random.normal(jax.random.key(seed + 100), (2, tokens // 2, D))
    return layer(None, form=form).init(jax.random.key(seed), x)["params"], x


def share_of(p, first, count):
    q = {"router": p["router"], "shared": p["shared"]}
    if count:
        q.update({k: p[k][first:first + count]
                  for k in ("wi_gate", "wi_up", "wo") if k in p})
    return q


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


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

    def f(fn):
        return jax.grad(lambda q, x: jnp.sum(jnp.sin(fn(q, x))),
                        argnums=(0, 1))(mine, x)

    got = f(lambda q, x: m.apply({"params": q}, x, return_stats=True)[0])
    ref = f(lambda q, x: plain(q, x, first, count, form=form))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=1e-4)


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
    assert [int(v) for v in whole(stats)] == [t * K, t * K, t]
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
    run()
    assert tracing.program_counters()["moe_pairs_routed_total"] \
        == after["moe_pairs_routed_total"] + 3 * 64 * 2


def test_manager_metrics_report_the_program_counters():
    from mockplane import make_manager

    tracing.add_program_counters(moe_pairs_local_total=5)
    m = make_manager()
    try:
        got = m.metrics()
        assert got["moe_pairs_local_total"] >= 5.0
        assert all(isinstance(got[k], float) for k in got
                   if k.startswith("moe_"))
    finally:
        m.shutdown()
