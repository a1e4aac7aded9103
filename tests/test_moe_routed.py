"""The routed expert layer over a share of the experts
(``models/moe.py: RoutedMoEMLP``) against a plain loop over experts: held all,
a share, an empty share; the shares add up to the whole layer; nothing is
dropped under the worst imbalance; the dense-dispatch layer and the routed
one share one router; counters go up once a step under remat."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu import tracing
from torchft_tpu.models import Transformer, tiny_config
from torchft_tpu.models.moe import (MOE_COUNTERS, MoEMLP, RoutedMoEMLP,
                                    padded_rows, route)

E, K, D, H = 16, 4, 64, 32
SCALE = 2.826


def layer(held, **kw):
    kw = {"shared_dim": H, "pass_rows": 512, **kw}
    return RoutedMoEMLP(num_experts=E, mlp_dim=H, top_k=K, held=held,
                        route_scale=SCALE, dtype=jnp.float32,
                        interpret=True, **kw)


def plain(p, x, first, count, shared=True):
    """The layer as its equations read: every held expert computes every
    token under a mask of the pairs routed to it."""
    u = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(u @ p["router"]["kernel"])
    top, idx = jax.lax.top_k(s, K)
    w = SCALE * top / (top.sum(-1, keepdims=True) + 1e-20)
    out = jnp.zeros_like(u)
    if shared:
        sh = p["shared"]
        out = (jax.nn.silu(u @ sh["gate"]["kernel"])
               * (u @ sh["up"]["kernel"])) @ sh["down"]["kernel"]
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        y = (jax.nn.silu(u @ p["wi_gate"][e]) * (u @ p["wi_up"][e])) \
            @ p["wo"][e]
        out = out + w_e[:, None] * y
    return out.reshape(x.shape)


def full_params(seed=0, tokens=256):
    x = jax.random.normal(jax.random.key(seed + 100), (2, tokens // 2, D))
    return layer(None).init(jax.random.key(seed), x)["params"], x


def share_of(p, first, count):
    q = {"router": p["router"], "shared": p["shared"]}
    if count:
        q.update({k: p[k][first:first + count]
                  for k in ("wi_gate", "wi_up", "wo")})
    return q


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("held", [(0, E), (4, 3), (15, 1), (5, 0)],
                         ids=["all", "share", "last", "empty"])
def test_routed_layer_against_the_plain_loop(held):
    p, x = full_params()
    first, count = held
    mine = share_of(p, first, count)
    m = layer(held)
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


@pytest.mark.parametrize("shares", [16, 4], ids=["16x1", "4x4"])
def test_the_shares_add_up_to_the_whole_layer(shares):
    """What all the shares give, with the shared expert counted once, is
    the uncut layer."""
    p, x = full_params(seed=3)
    per = E // shares
    shared_only = plain(share_of(p, 0, 0), x, 0, 0)
    total = shared_only
    for i in range(shares):
        part = layer((i * per, per)).apply(
            {"params": share_of(p, i * per, per)}, x)
        total = total + (part - shared_only)
    np.testing.assert_allclose(total, plain(p, x, 0, E), atol=5e-5)


@pytest.mark.parametrize("pass_rows", [512, 32768], ids=["passes", "one"])
def test_no_pair_is_dropped_when_every_token_picks_the_same_experts(
        pass_rows):
    """Every token's K picks are the K held experts: all T*K pairs land
    here, every pass runs, and the result is still the plain loop's."""
    p, x = full_params(seed=5, tokens=512)
    x = jnp.abs(x) + 0.1
    col = jnp.where(jnp.arange(E) < K, 1.0, -1.0)
    p = {**p, "router": {"kernel": jnp.broadcast_to(col, (D, E)) * 0.05}}
    mine = share_of(p, 0, K)
    out, stats = layer((0, K), pass_rows=pass_rows).apply(
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
