"""Mixture-of-experts layers.

Two expert layers share ONE router (:func:`route`): scores over all experts,
the ``top_k`` largest, their weights normalised over the selection and
scaled. They differ in how tokens reach the experts and in what a device
holds:

* :class:`MoEMLP` — **dense dispatch**, for the ``ep`` mesh axis. Every
  expert computes every token and a dense combine weight zeroes what was
  not routed; the expert dimension of the weight stacks shards over ``ep``
  (:func:`ep_rules`) and XLA inserts the combine reduction. ``E / top_k``
  times the needed expert work: right for a handful of experts sharded by
  XLA, not an option at 128. Softmax scores and the Switch/GShard
  load-balancing loss (sown into the ``aux_loss`` collection).

* :class:`RoutedMoEMLP` — **routed dispatch** over **a share** of the
  experts. The layer is told which experts it holds
  (``held = (first, count)``), routes every token over all ``num_experts``
  and computes its own experts' part of the layer's output: token-expert
  pairs whose expert is held are grouped by expert (one sort), gathered,
  multiplied through a Pallas grouped matmul and combined with their
  weights. No capacity and no dropped pair: there is a slot for every pair,
  the slots go through the experts a pass at a time and passes beyond the
  held experts' last pair are skipped, so time follows the pairs that are
  there (the grouped matmul's grid is sized by the loads at run time; the
  gathers move a whole pass) and memory is one pass's. A shared
  expert, where there is one, is an ordinary MLP of the experts' form on
  every token. An expert's form (``form``) is ``"swiglu"``
  (``down(silu(gate(u)) * up(u))``, three matrices), ``"reglu"``
  (``down(relu(gate(u)) * up(u))``, the same three and another gate) or
  ``"relu2"`` (``down(relu(up(u))^2)``, two, no gate): one static field that
  the layer, the pass loops, their hand-written backward and the shared
  expert read. What the router reads need not be what the experts compute
  on (``route_on``: a router placed before the layer's mixer). The
  parts that the shares of one layer give add up to the whole layer with
  the shared expert counted once (``tests/test_moe_routed.py``). This is the
  layer expert parallelism needs on each device; the exchange that would
  carry the other shares' parts is not here (ROADMAP R1b).

Both are capacity-free with static shapes.
"""

from __future__ import annotations

import functools
import importlib
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchft_tpu import tracing


def route(logits: jnp.ndarray, top_k: int, score: str = "softmax",
          route_norm: bool = True, route_scale: float = 1.0
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The router both expert layers use. ``logits`` [..., E] float32 ->
    ``(weights [..., K], experts [..., K], scores [..., E])``: scores are
    the softmax or the sigmoid of the logits, the selection is their
    ``top_k`` largest, and a selected expert's weight is its score over the
    selection's sum (``route_norm``) times ``route_scale``."""
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router score {score!r}")
    top_w, top_idx = jax.lax.top_k(scores, top_k)
    if route_norm:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    if route_scale != 1.0:
        top_w = top_w * route_scale
    return top_w, top_idx, scores


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU expert MLP with dense dispatch, for ``ep``
    sharding (see the module docstring). Input [B, S, D] → [B, S, D].

    Attributes:
        num_experts: E, ideally a multiple of the ``ep`` axis size.
        top_k: experts per token (1 = Switch, 2 = GShard-ish).
        mlp_dim: per-expert hidden width (MXU-friendly multiples of 128).
        aux_loss_weight: weight for the load-balance loss (sown into the
            ``aux_loss`` collection as ``moe_aux``).
    """

    num_experts: int
    mlp_dim: int
    top_k: int = 2
    dtype: Any = jnp.bfloat16
    aux_loss_weight: float = 0.01

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        d = x.shape[-1]
        e, h = self.num_experts, self.mlp_dim

        router = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                          name="router")
        # Expert weight stacks: leading expert dim shards over "ep".
        wi_gate = self.param("wi_gate", nn.initializers.lecun_normal(),
                             (e, d, h))
        wi_up = self.param("wi_up", nn.initializers.lecun_normal(),
                           (e, d, h))
        wo = self.param("wo", nn.initializers.lecun_normal(), (e, h, d))

        logits = router(x.astype(jnp.float32))          # [B,S,E]
        top_w, top_idx, probs = route(logits, self.top_k)   # [B,S,K]
        # Dense combine weights: sum of renormalized top-k one-hots [B,S,E].
        combine = jnp.sum(
            jax.nn.one_hot(top_idx, e, dtype=jnp.float32)
            * top_w[..., None],
            axis=2,
        )

        # Load-balance aux loss (Switch: E * sum_e fraction_e * prob_e).
        token_frac = jnp.mean(
            jnp.sum(jax.nn.one_hot(top_idx, e, dtype=jnp.float32), axis=2),
            axis=(0, 1)) / self.top_k
        prob_frac = jnp.mean(probs, axis=(0, 1))
        aux = self.aux_loss_weight * e * jnp.sum(token_frac * prob_frac)
        self.sow("aux_loss", "moe_aux", aux)

        # Dense expert compute: every expert sees every token; the combine
        # weight zeroes non-routed contributions. O(E/topk) extra FLOPs
        # traded for static shapes + clean ep sharding — the standard
        # small-E TPU tradeoff.
        xc = x.astype(self.dtype)
        gate = jnp.einsum("bsd,edh->ebsh", xc, wi_gate.astype(self.dtype))
        up = jnp.einsum("bsd,edh->ebsh", xc, wi_up.astype(self.dtype))
        act = nn.silu(gate) * up
        out = jnp.einsum("ebsh,ehd->ebsd", act, wo.astype(self.dtype))
        mixed = jnp.einsum("ebsd,bse->bsd",
                           out.astype(jnp.float32),
                           combine)
        return mixed.astype(x.dtype)


# --------------------------------------------------------- routed dispatch

def _megablox() -> Any:
    # The package's __init__ shadows the module ``gmm`` with the function.
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _tile(dim: int, cap: int) -> int:
    """A tile for a dimension the kernels may tile unevenly: the whole of a
    short one, else ``cap`` or half of it where that divides the dimension;
    else, for a width neither divides (2688 = 21 x 128, 1856 = 14.5 x 128,
    1792 = 14 x 128), the multiple of 128 up to ``cap`` that pads the
    dimension least, the largest of those (896, 640 and 896 under a cap of
    1024: the kernels mask the remainder). A tile of 128 there made a grid
    of thousands of steps of microseconds each (PERF.md, PR 45), and a
    quarter of the cap, which divides 1792, seven steps where 896 makes two
    (PERF.md, PR 47)."""
    if dim <= cap:
        return dim
    for t in (cap, cap // 2):
        if dim % t == 0:
            return t
    return min(range(128, cap + 1, 128),
               key=lambda t: (-(-dim // t) * t - dim, -t))


ROW_TILE = 512   # rows of sorted pairs a grid step of the products takes


def padded_rows(pairs: int) -> int:
    """Slots for ``pairs`` token-expert pairs: a whole number of row
    tiles."""
    tm = ROW_TILE if pairs >= ROW_TILE else -(-pairs // 8) * 8
    return -(-pairs // tm) * tm


def _row_tile(rows: int) -> int:
    return ROW_TILE if rows >= ROW_TILE else rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """``lhs`` [M, K] holds rows sorted by group; ``rhs`` [G, K, N] one
    matrix a group; ``group_sizes`` [G + 1] int32, whose last entry counts
    the rows after the G groups (pairs of experts not held, padding): those
    rows are not computed and come out zero. Returns [M, N] in ``lhs``'s
    type. The Pallas kernels are jax's ``megablox`` (``gmm`` / ``tgmm``);
    their grid is sized by the groups' rows at run time, so the products
    cost what the rows that are there cost, to a row tile a group.
    ``rhs`` is given in float32 and multiplied in ``lhs``'s type; its
    gradient comes back in float32 from the kernel's float32 accumulator."""
    return _gmm(lhs, rhs.astype(lhs.dtype), group_sizes, False, interpret)


def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret):
    m = lhs.shape[0]
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _megablox().gmm(
        lhs, rhs, group_sizes, lhs.dtype,
        (_row_tile(m), _tile(k, 1024), _tile(n, 1024)),
        transpose_rhs=transpose_rhs, interpret=interpret)


def _grouped_fwd(lhs, rhs, group_sizes, interpret):
    rhs_c = rhs.astype(lhs.dtype)
    return (_gmm(lhs, rhs_c, group_sizes, False, interpret),
            (lhs, rhs_c, group_sizes))


def _grouped_bwd(interpret, res, g):
    lhs, rhs_c, group_sizes = res
    d_lhs = _gmm(g, rhs_c, group_sizes, True, interpret)
    m, k = lhs.shape
    n = g.shape[1]
    d_rhs = _megablox().tgmm(
        lhs.swapaxes(0, 1), g, group_sizes, jnp.float32,
        (_row_tile(m), _tile(k, 512), _tile(n, 1024)),
        num_actual_groups=rhs_c.shape[0], interpret=interpret)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def _rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return x.at[idx].get(mode="promise_in_bounds")


def _slot_rows(y: jnp.ndarray, slot: jnp.ndarray, start: jnp.ndarray):
    """Rows of ``y`` (the slots ``start .. start + len(y)``) for the global
    slots ``slot`` [T], and which of them lie in that range; a row asked
    for from outside it is some row of ``y`` and is to be masked."""
    idx = slot - start
    inside = jnp.logical_and(idx >= 0, idx < y.shape[0])
    return _rows(y, jnp.clip(idx, 0, y.shape[0] - 1)), inside


def _column(a: jnp.ndarray, j: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_index_in_dim(a, j, axis=1, keepdims=False)


# The two data movements of a pass over the slots ``start .. start + R`` of
# the sorted pairs. ``src`` [R] (a slot's token) and ``pos`` [T, K] (the
# slot of token t's j-th pair) are one permutation read both ways, so both
# directions of both are gathers and no backward needs a scatter. A token's
# pairs on held experts come first among its K, and ``jmax`` is the largest
# number of them any token has: the gathers over tokens stop there (a
# handful where K is 8 and a sixteenth of the experts is held).

@jax.custom_vjp
def dispatch_rows(x: jnp.ndarray, src: jnp.ndarray, pos: jnp.ndarray,
                  start: jnp.ndarray, jmax: jnp.ndarray) -> jnp.ndarray:
    """``x`` [T, D] -> [R, D], row ``i`` the row of token ``src[i]``."""
    return _rows(x, src)


def _dispatch_fwd(x, src, pos, start, jmax):
    return _rows(x, src), (pos, start, jmax)


def _dispatch_bwd(res, g):
    pos, start, jmax = res

    def add(j, dx):
        rows, inside = _slot_rows(g, _column(pos, j), start)
        return dx + jnp.where(inside[:, None], rows.astype(jnp.float32), 0.0)

    dx = jax.lax.fori_loop(
        0, jmax, add, jnp.zeros((pos.shape[0], g.shape[1]), jnp.float32))
    return dx.astype(g.dtype), None, None, None, None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine_rows(y: jnp.ndarray, w: jnp.ndarray, pos: jnp.ndarray,
                 src: jnp.ndarray, w_slot: jnp.ndarray,
                 start: jnp.ndarray, jmax: jnp.ndarray) -> jnp.ndarray:
    """The pass's part of every token's output, float32 [T, D]:
    ``sum_j w[t, j] * y[pos[t, j] - start]`` over the pairs whose slot the
    pass holds. ``y`` [R, D] by slot, ``w`` [T, K] float32 (zero for a pair
    whose expert is not held), ``w_slot`` [R] the same weights by slot."""
    return _combine(y, w, pos, start, jmax)


def _combine(y, w, pos, start, jmax):
    def add(j, out):
        rows, inside = _slot_rows(y, _column(pos, j), start)
        return out + jnp.where(inside, _column(w, j), 0.0)[:, None] \
            * rows.astype(jnp.float32)

    return jax.lax.fori_loop(
        0, jmax, add, jnp.zeros((pos.shape[0], y.shape[1]), jnp.float32))


def _combine_fwd(y, w, pos, src, w_slot, start, jmax):
    return _combine(y, w, pos, start, jmax), (y, pos, src, w_slot, start)


def _combine_bwd(res, g):
    y, pos, src, w_slot, start = res
    g_rows = _rows(g, src)                                   # [R, D]
    dy = (w_slot[:, None] * g_rows).astype(y.dtype)
    # a weight's gradient where its pair's slot is, then back by token
    dw_slot = jnp.sum(g_rows * y.astype(jnp.float32), axis=-1)
    dw, inside = _slot_rows(dw_slot, pos, start)
    return dy, jnp.where(inside, dw, 0.0), None, None, None, None, None


combine_rows.defvjp(_combine_fwd, _combine_bwd)


FORMS = {"swiglu": ("wi_gate", "wi_up", "wo"), "relu2": ("wi_up", "wo"),
         "reglu": ("wi_gate", "wi_up", "wo")}
#         an expert's matrices by its form, in the order they are held
# the gate's function in the forms that have one
GATES = {"swiglu": nn.silu, "reglu": nn.relu}


def _one_pass(uc, w, weights, idx, start, rows_a_pass: int,
              interpret: bool, form: str) -> Tuple[jnp.ndarray, Any]:
    """The slots ``start .. start + rows_a_pass`` through the held experts:
    float32 [T, D], their part of every token's output, and with it what
    the pass has for the layer's counts: under ``"reglu"`` the hidden units
    with ``gate > 0`` over the pass's rows on held experts (a float32
    count), else ``None``. ``weights`` are the experts' stacks in
    ``FORMS[form]``'s order; ``idx`` is the routing's integer side:
    ``(src [M], pos [T, K], w_slot [M], ends [count + 1], jmax)``."""
    src, pos, w_slot, ends, jmax = idx
    count = weights[0].shape[0]
    with jax.named_scope("moe_dispatch"):
        src_p = jax.lax.dynamic_slice(src, (start,), (rows_a_pass,))
        w_p = jax.lax.dynamic_slice(w_slot, (start,), (rows_a_pass,))
        # each group's rows inside the pass; the last group takes what is
        # left, so that the sizes add up to the pass
        upto = jnp.clip(ends - start, 0, rows_a_pass)
        sizes_p = jnp.diff(upto, prepend=0).astype(jnp.int32)
        sizes_p = sizes_p.at[count].set(rows_a_pass - upto[count - 1])
        rows = dispatch_rows(uc, src_p, pos, start, jmax)
    active = None
    with jax.named_scope("moe_experts"):
        if form in GATES:
            wi_gate, wi_up, wo = weights
            gate = grouped_matmul(rows, wi_gate, sizes_p, interpret)
            up = grouped_matmul(rows, wi_up, sizes_p, interpret)
            y = grouped_matmul(GATES[form](gate) * up, wo, sizes_p,
                               interpret)
            if form == "reglu":
                held_row = jnp.arange(rows_a_pass) < upto[count - 1]
                active = jnp.sum(
                    jnp.logical_and(gate > 0, held_row[:, None]),
                    dtype=jnp.float32)
        else:
            wi_up, wo = weights
            up = grouped_matmul(rows, wi_up, sizes_p, interpret)
            y = grouped_matmul(jnp.square(nn.relu(up)), wo, sizes_p,
                               interpret)
    with jax.named_scope("moe_combine"):
        return combine_rows(y, w, pos, src_p, w_p, start, jmax), active


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def experts_over_passes(uc, w, weights, idx, n_local, rows_a_pass: int,
                        interpret: bool, form: str
                        ) -> Tuple[jnp.ndarray, Any]:
    """Every pass that holds a pair of a held expert, one after the other:
    float32 [T, D], and the passes' counts (:func:`_one_pass`) added up in
    the loop's carry (``None`` where the form has none; no gradient flows
    into them). The passes are a ``while_loop`` whose length is the
    routing's (``n_local`` pairs on held experts fill the first slots), which
    reverse-mode differentiation cannot unroll: the backward is written here,
    a second loop over the same passes that recomputes each and adds its
    gradients up, so memory is one pass's in both directions."""
    return _passes_fwd(uc, w, weights, idx, n_local, rows_a_pass, interpret,
                       form)[0]


def _passes_fwd(uc, w, weights, idx, n_local, rows_a_pass, interpret, form):
    def body(c):
        start, out, active = c
        nxt = start + rows_a_pass    # first, where the loop always had it
        part, hot = _one_pass(uc, w, weights, idx, start, rows_a_pass,
                              interpret, form)
        return nxt, out + part, None if hot is None else active + hot

    _, out, active = jax.lax.while_loop(
        lambda c: c[0] < n_local, body,
        (jnp.int32(0), jnp.zeros(uc.shape, jnp.float32),
         jnp.float32(0.0) if form == "reglu" else None))
    return (out, active), (uc, w, weights, idx, n_local)


def _passes_bwd(rows_a_pass, interpret, form, res, g):
    uc, w, weights, idx, n_local = res
    diff = (uc, w, tuple(weights))

    def body(c):
        start, acc = c
        _, vjp, _ = jax.vjp(
            lambda *a: _one_pass(*a, idx, start, rows_a_pass, interpret,
                                 form), *diff, has_aux=True)
        return start + rows_a_pass, jax.tree_util.tree_map(
            lambda a, d: a + d.astype(a.dtype), acc, vjp(g[0]))

    zeros = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), diff)
    _, grads = jax.lax.while_loop(lambda c: c[0] < n_local, body,
                                  (jnp.int32(0), zeros))
    return jax.tree_util.tree_map(lambda d, x: d.astype(x.dtype), grads,
                                  diff) + (None, None)


experts_over_passes.defvjp(_passes_fwd, _passes_bwd)


class RoutedMoEMLP(nn.Module):
    """Routed experts over a share of them (see the module docstring).
    Input [B, S, D] -> [B, S, D].

    Attributes:
        num_experts: the router's width: every expert of the layer, held
            here or not.
        mlp_dim: a routed expert's hidden width.
        top_k: experts a token.
        held: ``(first, count)``: the experts whose weights this layer has
            and whose part it computes; ``None`` holds all.
        shared_dim: hidden width of the shared expert (0: none).
        shared_gate: the shared expert's output times ``sigmoid(u . w_s)``,
            ``w_s`` one column of its own (``shared_gate/kernel`` [D, 1]).
        score / route_norm / route_scale: the router (:func:`route`).
        form: ``"swiglu"`` or ``"reglu"`` (stacks ``wi_gate``, ``wi_up``,
            ``wo``) or ``"relu2"`` (``wi_up``, ``wo``), the shared
            expert's too.
        pass_rows: sorted pairs taken through the experts at a time.
        interpret: run the Pallas grouped matmul interpreted (``None``: off
            a TPU).

    The router's inputs, logits and scores are float32 and its product is
    taken at the highest precision: the selection is a comparison of
    scores, and a lower precision flips the close ones. The router reads
    ``route_on`` [B, S, D] where the caller gives one (a router placed
    before the layer's mixer: the selection, its weights and the router's
    gradient are the mixer's input's, and nothing of the routing waits for
    the mixer), else ``x``; experts and shared expert compute on ``x``.

    **Passes.** All ``T * top_k`` pairs are sorted by group (a held
    expert's place, then everything else) into as many slots, which is room
    for every pair landing on held experts: nothing is dropped however
    unbalanced the routing. The slots go through gather, products and
    combine ``pass_rows`` at a time, in a loop that ends with the last pair
    of a held expert (:func:`experts_over_passes`): time follows the pairs
    that are there in units of a pass, memory is one pass's, and the worst
    case takes every pass.

    Three process-wide program counters (``tracing.count_in_program``;
    ``Manager.metrics()`` reports them) go up once a call made under a
    collector (``tracing.collect_counts``, which every trainer of this
    package wraps its loss in; under a hand-written
    ``jax.jit(jax.value_and_grad(...))`` nothing is counted):
    ``moe_pairs_routed_total`` (token-expert pairs routed),
    ``moe_pairs_local_total`` (those whose expert is held) and
    ``moe_expert_load_max_total`` (the largest load of a held expert). The
    collector returns them from the program, so they have to be values of
    the function it wraps: a caller that rematerialises the layer
    (``jax.checkpoint``) or runs it in a ``lax.scan`` body asks for the
    numbers (``return_stats=True``: ``(out, int32[3])`` in that order),
    returns them out of that region and counts them outside, as
    ``Transformer`` does, once a step for all its layers. A ``"reglu"``
    layer has a fourth number, ``moe_reglu_active_micro_total``: over the
    rows that hold a pair of a held expert, the share of the hidden units
    with ``gate > 0``, in millionths (about half at seeded weights; 0 says
    the experts carry nothing, 1,000,000 that the gate is a plain product),
    added up in the pass loop's carry; its stats are the pair
    ``(int32[3], float32 share)``.
    """

    num_experts: int
    mlp_dim: int
    top_k: int = 2
    held: Optional[Tuple[int, int]] = None
    shared_dim: int = 0
    shared_gate: bool = False
    score: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 1.0
    form: str = "swiglu"
    dtype: Any = jnp.bfloat16
    pass_rows: int = 8192
    interpret: Optional[bool] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 route_on: Optional[jnp.ndarray] = None,
                 return_stats: bool = False) -> Any:
        out, stats = self._routed(x, route_on)
        if return_stats:
            return out, stats
        tracing.count_in_program(**moe_counts(stats))
        return out

    def _routed(self, x: jnp.ndarray, route_on: Optional[jnp.ndarray]
                ) -> Tuple[jnp.ndarray, Any]:
        b, s, d = x.shape
        if route_on is not None and route_on.shape != x.shape:
            raise ValueError(f"route_on {route_on.shape} is not the "
                             f"input's shape {x.shape}")
        e, h, k = self.num_experts, self.mlp_dim, self.top_k
        first, count = self.held if self.held is not None else (0, e)
        if not (0 <= first and count >= 0 and first + count <= e):
            raise ValueError(f"held {self.held} is not a range of the "
                             f"{e} experts")
        if self.form not in FORMS:
            raise ValueError(f"unknown expert form {self.form!r}; "
                             f"one of {sorted(FORMS)}")
        interpret = (jax.default_backend() != "tpu"
                     if self.interpret is None else bool(self.interpret))
        t = b * s
        u = x.reshape(t, d)

        shared = None
        if self.shared_dim:
            shared = _SharedExpert(self.shared_dim, self.dtype, self.form,
                                   name="shared")(x)
            if self.shared_gate:
                gate = nn.Dense(1, use_bias=False, dtype=self.dtype,
                                name="shared_gate")(x)
                shared = shared * jax.nn.sigmoid(
                    gate.astype(jnp.float32)).astype(shared.dtype)
            shared = shared.reshape(t, d)

        router = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST, name="router")
        init = nn.initializers.lecun_normal()
        if count:
            weights = tuple(
                self.param(name, init,
                           (count, h, d) if name == "wo" else (count, d, h))
                for name in FORMS[self.form])

        with jax.named_scope("moe_route"):
            read = u if route_on is None else route_on.reshape(t, d)
            logits = router(read.astype(jnp.float32))         # [T, E]
            top_w, top_idx, _ = route(logits, k, self.score,
                                      self.route_norm, self.route_scale)
            # read only by a caller that asks for "intermediates"
            self.sow("intermediates", "experts", top_idx)

        with jax.named_scope("moe_dispatch"):
            # A token's pairs on held experts first among its K (the
            # gathers over tokens stop at the most any token has).
            local = jnp.logical_and(top_idx >= first, top_idx < first + count)
            by_local = jnp.argsort(jnp.logical_not(local), axis=-1,
                                   stable=True)
            top_idx = jnp.take_along_axis(top_idx, by_local, axis=-1)
            top_w = jnp.take_along_axis(top_w, by_local, axis=-1)
            local = jnp.take_along_axis(local, by_local, axis=-1)
            jmax = jnp.max(jnp.sum(local, axis=-1, dtype=jnp.int32))
            # A pair's group: its expert's place among the held ones, or
            # ``count`` for an expert that is not held. One stable sort puts
            # the held experts' pairs first, expert by expert.
            group = jnp.where(local, top_idx - first, count).reshape(t * k)
            m, rows_a_pass = _slots(t * k, self.pass_rows)
            group = jnp.concatenate(
                [group, jnp.full((m - t * k,), count, group.dtype)])
            pair = jnp.argsort(group, stable=True).astype(jnp.int32)  # [M]
            src = jnp.minimum(pair // k, t - 1)
            # the same permutation read the other way: a second sort, not a
            # scatter of scalars
            pos = jnp.argsort(pair).astype(jnp.int32)[: t * k].reshape(t, k)
            sizes = jnp.sum(group[:, None] == jnp.arange(count + 1)[None, :],
                            axis=0, dtype=jnp.int32)
            n_local = jnp.sum(sizes[:count])
            stats = jnp.stack([jnp.int32(t * k), n_local,
                               jnp.max(sizes[:count], initial=0)])
            w = jnp.where(local, top_w, 0.0)
            # a slot's weight; a padding slot reads the zero at the end
            w_slot = jax.lax.stop_gradient(_rows(
                jnp.concatenate([w.reshape(-1), jnp.zeros((1,), w.dtype)]),
                jnp.minimum(pair, t * k)))
            ends = jnp.cumsum(sizes)

        def with_share(active):
            # a "reglu" layer's fourth number beside the three
            if self.form != "reglu":
                return stats
            return stats, active / (jnp.maximum(n_local, 1) * h)

        if not count:
            out = jnp.zeros((t, d), x.dtype) if shared is None else shared
            return out.reshape(b, s, d), with_share(jnp.float32(0.0))

        out, active = experts_over_passes(
            u.astype(self.dtype), w, weights,
            (src, pos, w_slot, ends, jmax), n_local, rows_a_pass, interpret,
            self.form)
        stats = with_share(active)

        with jax.named_scope("moe_combine"):
            out = out.astype(x.dtype)
            if shared is not None:
                out = out + shared
        return out.reshape(b, s, d), stats


def _slots(pairs: int, pass_rows: int) -> Tuple[int, int]:
    """``(slots, rows a pass)`` for ``pairs`` token-expert pairs: whole
    passes of whole row tiles."""
    if pairs <= pass_rows:
        m = padded_rows(pairs)
        return m, m
    if pass_rows % ROW_TILE:
        raise ValueError(f"pass_rows must be a multiple of {ROW_TILE}")
    return -(-pairs // pass_rows) * pass_rows, pass_rows


class _SharedExpert(nn.Module):
    """The expert every token passes through: an MLP of the experts'
    ``form`` and a width of its own."""

    mlp_dim: int
    dtype: Any = jnp.bfloat16
    form: str = "swiglu"

    @nn.compact
    def __call__(self, x):
        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            name=name)

        if self.form in GATES:
            gate = dense(self.mlp_dim, "gate")(x)
            up = dense(self.mlp_dim, "up")(x)
            act = GATES[self.form](gate) * up
        else:
            act = jnp.square(nn.relu(dense(self.mlp_dim, "up")(x)))
        return dense(x.shape[-1], "down")(act)


MOE_COUNTERS = ("moe_pairs_routed_total", "moe_pairs_local_total",
                "moe_expert_load_max_total")
REGLU_COUNTER = "moe_reglu_active_micro_total"
ROUTE_AHEAD_COUNTER = "moe_route_ahead_layers_total"


def moe_counts(stats: Any, layers: int = 1) -> dict:
    """A routed layer's ``stats`` as ``{counter: value}`` for
    ``tracing.count_in_program`` (at the collecting function's own level,
    outside any rematerialised region or scan body); a ``"reglu"`` layer's
    share of active units (the pair's second) in millionths over
    ``layers``, so that the layers' sum is the step's mean."""
    if isinstance(stats, tuple):
        stats, active = stats
        return {**dict(zip(MOE_COUNTERS, stats)),
                REGLU_COUNTER: active * (1e6 / layers)}
    return dict(zip(MOE_COUNTERS, stats))


def ep_rules() -> list:
    """Expert-parallel PartitionSpecs for ``apply_rules``: shard the expert
    stacks' leading dim over ``ep``; router stays replicated."""
    return [
        (r"wi_gate$|wi_up$", P("ep", None, None)),
        (r"/wo$", P("ep", None, None)),
    ]
