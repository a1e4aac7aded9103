"""The routed expert layer over a share of the experts
(``models/moe.py: RoutedMoEMLP``) against a plain loop over experts: held all,
a share, an empty share; the shares add up to the whole layer; nothing is
dropped under the worst imbalance, each for both forms of an expert
(``swiglu``: three matrices; ``relu2``: two and a squared ReLU, the pass
loops' hand-written backward against plain autodiff of the loop); the
dense-dispatch layer and the routed one share one router; counters go up
once a step under remat."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu import tracing
from torchft_tpu.models import Transformer, tiny_config
from torchft_tpu.models.moe import (FORMS, MOE_COUNTERS, MoEMLP,
                                    RoutedMoEMLP, _tile, padded_rows, route)

E, K, D, H = 16, 4, 64, 32
SCALE = 2.826
BOTH_FORMS = pytest.mark.parametrize("form", list(FORMS))


def layer(held, **kw):
    kw = {"shared_dim": H, "pass_rows": 512, **kw}
    return RoutedMoEMLP(num_experts=E, mlp_dim=H, top_k=K, held=held,
                        route_scale=SCALE, dtype=jnp.float32,
                        interpret=True, **kw)


def expert(u, p, e=None):
    """One expert as its equations read, by the matrices it has: ``gate``,
    ``up``, ``down`` (SwiGLU) or ``up``, ``down`` (squared ReLU); ``e``
    picks it out of the routed stacks, ``None`` is the shared one."""
    if e is None:
        up, down = p["up"]["kernel"], p["down"]["kernel"]
        gate = p["gate"]["kernel"] if "gate" in p else None
    else:
        up, down = p["wi_up"][e], p["wo"][e]
        gate = p["wi_gate"][e] if "wi_gate" in p else None
    if gate is None:
        return jnp.square(jax.nn.relu(u @ up)) @ down
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def plain(p, x, first, count, shared=True):
    """The layer as its equations read: every held expert computes every
    token under a mask of the pairs routed to it."""
    u = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(u @ p["router"]["kernel"])
    top, idx = jax.lax.top_k(s, K)
    w = SCALE * top / (top.sum(-1, keepdims=True) + 1e-20)
    out = jnp.zeros_like(u)
    if shared:
        out = expert(u, p["shared"])
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        out = out + w_e[:, None] * expert(u, p, e)
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


@BOTH_FORMS
@pytest.mark.parametrize("held", [(0, E), (4, 3), (15, 1), (5, 0)],
                         ids=["all", "share", "last", "empty"])
def test_routed_layer_against_the_plain_loop(held, form):
    """Forward, and the backward of the pass loops (a ``custom_vjp`` that
    recomputes each pass) against plain autodiff of the loop over
    experts."""
    p, x = full_params(form=form)
    assert sorted(k for k in p if k.startswith("w")) == sorted(FORMS[form])
    assert ("gate" in p["shared"]) == (form == "swiglu")
    first, count = held
    mine = share_of(p, first, count)
    m = layer(held, form=form)
    out, stats = m.apply({"params": mine}, x, return_stats=True)
    want = plain(mine, x, first, count)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert int(stats[0]) == x.shape[0] * x.shape[1] * K
    assert int(stats[1]) <= int(stats[0]) and (count or int(stats[1]) == 0)

    def f(fn):
        return jax.grad(lambda q, x: jnp.sum(jnp.sin(fn(q, x))),
                        argnums=(0, 1))(mine, x)

    got = f(lambda q, x: m.apply({"params": q}, x, return_stats=True)[0])
    ref = f(lambda q, x: plain(q, x, first, count))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=1e-4)


@BOTH_FORMS
@pytest.mark.parametrize("shares", [16, 4], ids=["16x1", "4x4"])
def test_the_shares_add_up_to_the_whole_layer(shares, form):
    """What all the shares give, with the shared expert counted once, is
    the uncut layer."""
    p, x = full_params(seed=3, form=form)
    per = E // shares
    shared_only = plain(share_of(p, 0, 0), x, 0, 0)
    total = shared_only
    for i in range(shares):
        part = layer((i * per, per), form=form).apply(
            {"params": share_of(p, i * per, per)}, x)
        total = total + (part - shared_only)
    np.testing.assert_allclose(total, plain(p, x, 0, E), atol=5e-5)


@BOTH_FORMS
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
    assert [int(v) for v in stats] == [t * K, t * K, t]
    np.testing.assert_allclose(out, plain(mine, x, 0, K), atol=5e-5)


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
