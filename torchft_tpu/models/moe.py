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
  gathers move a whole pass) and memory is one pass's. A pass adds where
  it produces: the loop's float32 carries go into the pass and come out
  added to, a token's pairs and the passes before it in one sweep over
  ``[T, D]`` (a loop over tiles of tokens), the stacks' gradients inside
  the ``tgmm`` kernel (:func:`experts_over_passes`). A shared
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
from typing import Any, NamedTuple, Optional, Tuple

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


def _gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray,
         interpret: bool, transpose_rhs: bool = False) -> jnp.ndarray:
    """``lhs`` [M, K] holds rows sorted by group; ``rhs`` [G, K, N] one
    matrix a group (``transpose_rhs``: [G, N, K]), in ``lhs``'s type;
    ``group_sizes`` [G + 1] int32, whose last entry counts the rows after
    the G groups (pairs of experts not held, padding): those rows are not
    computed and come out zero. Returns [M, N] in ``lhs``'s type. The Pallas
    kernel is jax's ``megablox`` ``gmm``; its grid is sized by the groups'
    rows at run time, so the product costs what the rows that are there
    cost, to a row tile a group."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _megablox().gmm(
        lhs, rhs, group_sizes, lhs.dtype,
        (_row_tile(m), _tile(k, 1024), _tile(n, 1024)),
        transpose_rhs=transpose_rhs, interpret=interpret)


def _tgmm_into(acc: jnp.ndarray, lhs: jnp.ndarray, g: jnp.ndarray,
               group_sizes: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """``acc`` [G, K, N] float32 plus the groups' ``lhs^T @ g`` (``lhs``
    [M, K], ``g`` [M, N], rows sorted by group as in :func:`_gmm`): the
    gradient of a product's matrices added to what the earlier passes gave.
    megablox's ``tgmm`` takes ``acc`` as its ``existing_out``, aliased in
    place: a group's block is read once and stored once, the kernel's
    float32 accumulator added to it, and a group without rows keeps its
    block."""
    m, k = lhs.shape
    n = g.shape[1]
    return _megablox().tgmm(
        lhs.swapaxes(0, 1), g, group_sizes, jnp.float32,
        (_row_tile(m), _tile(k, 512), _tile(n, 1024)),
        num_actual_groups=acc.shape[0], existing_out=acc,
        interpret=interpret)


def _rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return x.at[idx].get(mode="promise_in_bounds")


def _slot_rows(y: jnp.ndarray, slot: jnp.ndarray, start: jnp.ndarray):
    """Rows of ``y`` (the slots ``start .. start + len(y)``) for the global
    slots ``slot`` [T], and which of them lie in that range; a row asked
    for from outside it is some row of ``y`` and is to be masked."""
    idx = slot - start
    inside = jnp.logical_and(idx >= 0, idx < y.shape[0])
    return _rows(y, jnp.clip(idx, 0, y.shape[0] - 1)), inside


# The data movements of a pass over the slots ``start .. start + R`` of the
# sorted pairs. ``src`` [R] (a slot's token) and ``pos`` [T, K] (the slot of
# token t's j-th pair) are one permutation read both ways, so both
# directions of both movements are gathers and no backward needs a scatter.
# A token's pairs on held experts come first among its K, so the sums over a
# token's pairs read the first ``min(K, held)`` columns and no more (six
# where K is 6 of 64 experts and sixteen are held).

TOKEN_TILE = 1024   # tokens whose pairs' rows one step of a sweep gathers


def _add_pairs(carry: jnp.ndarray, y: jnp.ndarray, pos: jnp.ndarray,
               start: jnp.ndarray, scale: Optional[jnp.ndarray] = None,
               round_to: Any = None) -> jnp.ndarray:
    """``carry`` [T, D] float32 plus, for every token, the rows of ``y``
    [R, D] (the slots ``start .. start + R``) at its pairs' slots ``pos``
    [T, J] that the pass holds, each times its ``scale`` [T, J] where there
    is one: ``carry[t] + sum_j scale[t, j] * y[pos[t, j] - start]``, the
    addends summed in the order ``j = 0, 1, ...`` in float32 (and rounded
    to ``round_to`` and back where that is given) and the sum then added to
    the carry. One sweep over the carry, rolled: a ``fori_loop`` over tiles
    of ``TOKEN_TILE`` tokens whose body gathers the tile's ``J`` columns (a
    gather a column: the rows in flight are ``J`` tiles, which the compiler
    keeps in its fast memory; PERF.md, PR 52 and PR 53) and adds them to
    the tile of the carry in place. The program holds ``J`` gathers a
    sweep however many tiles ``T`` makes. Where ``TOKEN_TILE`` does not
    divide ``T`` the last tile starts at ``T - TOKEN_TILE`` and the rows
    the tile before it has done keep their sums. A column that holds no
    pair of a held expert adds zeros: a loop over the columns up to the
    most any token has was timed and lost (PERF.md, PR 53)."""
    return _sweep(carry, y, pos, start, scale, round_to,
                  min(TOKEN_TILE, pos.shape[0]))


@functools.partial(jax.jit, static_argnums=(5, 6))
def _sweep(carry, y, pos, start, scale, round_to, tile: int):
    """:func:`_add_pairs` at a tile of ``tile`` tokens. A function of its
    own under ``jax.jit`` so that every layer's sweep of one direction is
    traced once and not a layer at a time: inlined by the compiler it is
    the program the loop written in place would be, but Python traces its
    ``J`` gathers once a model instead of once a site, which every run's
    set-up pays whatever its compile cache holds (PERF.md, PR 53)."""
    t, fanout = pos.shape

    def body(i, carry):
        t0 = jnp.minimum(i * tile, t - tile)
        slots = jax.lax.dynamic_slice(pos, (t0, 0), (tile, fanout))
        if scale is not None:
            by = jax.lax.dynamic_slice(scale, (t0, 0), (tile, fanout))
        total = None
        for j in range(fanout):
            rows, inside = _slot_rows(y, slots[:, j], start)
            rows = rows.astype(jnp.float32)
            if scale is None:
                term = jnp.where(inside[:, None], rows, 0.0)
            else:
                term = jnp.where(inside, by[:, j], 0.0)[:, None] * rows
            # the product on the left: a backend that contracts
            # ``x * y + s`` then rounds as a loop over j would
            total = term if total is None else term + total
        if round_to is not None:
            total = total.astype(round_to).astype(jnp.float32)
        old = jax.lax.dynamic_slice(carry, (t0, 0), (tile, carry.shape[1]))
        new = old + total
        if t % tile:
            done = t0 + jnp.arange(tile) < i * tile
            new = jnp.where(done[:, None], old, new)
        return jax.lax.dynamic_update_slice(carry, new, (t0, 0))

    return jax.lax.fori_loop(0, -(-t // tile), body, carry)


def _held_first(a: jnp.ndarray, stacks: Tuple[jnp.ndarray, ...]
                ) -> jnp.ndarray:
    """The columns of ``a`` [T, K] that can hold a pair of a held expert: a
    token's pairs on held experts are the first of its K and at most as
    many as the experts ``stacks`` hold."""
    return a[:, :min(a.shape[1], stacks[0].shape[0])]


FORMS = {"swiglu": ("wi_gate", "wi_up", "wo"), "relu2": ("wi_up", "wo"),
         "reglu": ("wi_gate", "wi_up", "wo")}
#         an expert's matrices by its form, in the order they are held: the
#         matrices into the hidden width, then ``wo`` out of it
# the gate's function in the forms that have one
GATES = {"swiglu": nn.silu, "reglu": nn.relu}


def _hidden(form: str, pre: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """What ``wo`` multiplies, from the rows' products with the matrices
    before it (``FORMS[form]``'s order)."""
    if form in GATES:
        gate, up = pre
        return GATES[form](gate) * up
    up, = pre
    return jnp.square(nn.relu(up))


class _Pass(NamedTuple):
    """A pass's values up to the rows that leave the experts."""
    src: jnp.ndarray        # [R] a slot's token
    w: jnp.ndarray          # [R] a slot's weight
    sizes: jnp.ndarray      # [count + 1] each group's rows inside the pass
    held_rows: jnp.ndarray  # how many of the pass's rows are a held expert's
    rows: jnp.ndarray       # [R, D] the tokens' rows by slot
    pre: Tuple[jnp.ndarray, ...]   # their products into the hidden width
    act: jnp.ndarray        # [R, H] what ``wo`` multiplies
    y: jnp.ndarray          # [R, D] the experts' output by slot


def _pass_products(uc, stacks, idx, start, rows_a_pass: int,
                   interpret: bool, form: str) -> _Pass:
    """The slots ``start .. start + rows_a_pass`` through the held experts,
    up to the rows that leave them. ``stacks`` are the experts' matrices in
    ``FORMS[form]``'s order and the rows' type; ``idx`` is the routing's
    integer side: ``(src [M], pos [T, K], w_slot [M], ends [count + 1])``."""
    src, _, w_slot, ends = idx
    count = stacks[0].shape[0]
    with jax.named_scope("moe_dispatch"):
        src_p = jax.lax.dynamic_slice(src, (start,), (rows_a_pass,))
        w_p = jax.lax.dynamic_slice(w_slot, (start,), (rows_a_pass,))
        # each group's rows inside the pass; the last group takes what is
        # left, so that the sizes add up to the pass
        upto = jnp.clip(ends - start, 0, rows_a_pass)
        sizes_p = jnp.diff(upto, prepend=0).astype(jnp.int32)
        sizes_p = sizes_p.at[count].set(rows_a_pass - upto[count - 1])
        rows = _rows(uc, src_p)
    with jax.named_scope("moe_experts"):
        pre = tuple(_gmm(rows, wi, sizes_p, interpret) for wi in stacks[:-1])
        act = _hidden(form, pre)
        y = _gmm(act, stacks[-1], sizes_p, interpret)
    return _Pass(src_p, w_p, sizes_p, upto[count - 1], rows, pre, act, y)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def experts_over_passes(uc, w, weights, idx, n_local, rows_a_pass: int,
                        interpret: bool, form: str
                        ) -> Tuple[jnp.ndarray, Any]:
    """Every pass that holds a pair of a held expert, one after the other:
    float32 [T, D], the held experts' part of every token's output, and the
    passes' counts added up in the loop's carry: under ``"reglu"`` the
    hidden units with ``gate > 0`` over the rows on held experts (a float32
    count), else ``None``; no gradient flows into them. ``uc`` [T, D] the
    tokens in the experts' type, ``w`` [T, K] float32 (zero for a pair whose
    expert is not held), ``weights`` the experts' float32 stacks in
    ``FORMS[form]``'s order, multiplied in ``uc``'s type; ``idx`` as
    :func:`_pass_products` takes it.

    The passes are a ``while_loop`` whose length is the routing's
    (``n_local`` pairs on held experts fill the first slots), which
    reverse-mode differentiation cannot unroll: the backward is written
    here, a second loop over the same passes that recomputes each pass's
    products and adds its gradients up, so memory is one pass's in both
    directions. **A pass adds where it produces.** The loop's carry goes
    into the pass and comes out added to, every sum in float32 and made by
    the operation that has the addends: a token's pairs and the passes
    before in one sweep over ``[T, D]`` (:func:`_add_pairs`: the output
    forward, the tokens' gradient backward, that one rounded to the rows'
    type a pass as the rows' gradient is), the stacks' gradients inside
    ``tgmm`` (:func:`_tgmm_into`). No pass starts a sum from zeros of its
    own and none is added to the carry afterwards."""
    return _passes_fwd(uc, w, weights, idx, n_local, rows_a_pass, interpret,
                       form)[0]


def _passes_fwd(uc, w, weights, idx, n_local, rows_a_pass, interpret, form):
    stacks = tuple(x.astype(uc.dtype) for x in weights)
    pos_j, w_j = _held_first(idx[1], stacks), _held_first(w, stacks)

    def body(c):
        start, out, active = c
        p = _pass_products(uc, stacks, idx, start, rows_a_pass, interpret,
                           form)
        with jax.named_scope("moe_combine"):
            out = _add_pairs(out, p.y, pos_j, start, scale=w_j)
        if form == "reglu":
            on_held = jnp.arange(rows_a_pass) < p.held_rows
            active = active + jnp.sum(
                jnp.logical_and(p.pre[0] > 0, on_held[:, None]),
                dtype=jnp.float32)
        return start + rows_a_pass, out, active

    _, out, active = jax.lax.while_loop(
        lambda c: c[0] < n_local, body,
        (jnp.int32(0), jnp.zeros(uc.shape, jnp.float32),
         jnp.float32(0.0) if form == "reglu" else None))
    return (out, active), (uc, w, weights, idx, n_local)


def _passes_bwd(rows_a_pass, interpret, form, res, g):
    uc, w, weights, idx, n_local = res
    pos = idx[1]
    stacks = tuple(x.astype(uc.dtype) for x in weights)
    pos_j = _held_first(pos, stacks)
    g = g[0]                                                 # [T, D] f32

    def body(c):
        start, dx, dw, dstacks = c
        p = _pass_products(uc, stacks, idx, start, rows_a_pass, interpret,
                           form)
        with jax.named_scope("moe_combine"):
            g_rows = _rows(g, p.src)                         # [R, D]
            dy = (p.w[:, None] * g_rows).astype(p.y.dtype)
            # a weight's gradient where its pair's slot is, then back by
            # token
            dw_slot = jnp.sum(g_rows * p.y.astype(jnp.float32), axis=-1)
            dw_p, inside = _slot_rows(dw_slot, pos, start)
            dw = dw + jnp.where(inside, dw_p, 0.0)
        with jax.named_scope("moe_experts"):
            d_act = _gmm(dy, stacks[-1], p.sizes, interpret,
                         transpose_rhs=True)
            d_wo = _tgmm_into(dstacks[-1], p.act, dy, p.sizes, interpret)
            d_pre, = jax.vjp(functools.partial(_hidden, form),
                             p.pre)[1](d_act)
            d_rows, d_in = None, []
            for wi, d, acc in zip(stacks[:-1], d_pre, dstacks[:-1]):
                part = _gmm(d, wi, p.sizes, interpret, transpose_rhs=True)
                d_rows = part if d_rows is None else d_rows + part
                d_in.append(_tgmm_into(acc, p.rows, d, p.sizes, interpret))
        with jax.named_scope("moe_dispatch"):
            dx = _add_pairs(dx, d_rows, pos_j, start,
                            round_to=d_rows.dtype)
        return start + rows_a_pass, dx, dw, (*d_in, d_wo)

    _, dx, dw, dstacks = jax.lax.while_loop(
        lambda c: c[0] < n_local, body,
        (jnp.int32(0), jnp.zeros(uc.shape, jnp.float32),
         jnp.zeros(w.shape, jnp.float32),
         tuple(jnp.zeros(x.shape, jnp.float32) for x in weights)))
    return (dx.astype(uc.dtype), dw.astype(w.dtype),
            tuple(d.astype(x.dtype) for d, x in zip(dstacks, weights)),
            None, None)


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
    case takes every pass. A pass's sums are made where their addends are
    (since PR 53): the output's carry takes a token's pairs, weighted, in
    one sweep (:func:`_add_pairs`: the columns ``j < J`` of the token's
    pairs, ``J`` the most a token can have on held experts (``top_k``, or
    the held experts where those are fewer); a loop over tiles of
    ``TOKEN_TILE`` tokens, so the program's size does not follow the
    tokens), the backward's carry of the tokens' gradient the same way, and
    the three (or two) float32 stacks of the experts' gradients go through
    ``tgmm`` as its ``existing_out``.

    Four process-wide program counters (``tracing.count_in_program``;
    ``Manager.metrics()`` reports them) go up once a call made under a
    collector (``tracing.collect_counts``, which every trainer of this
    package wraps its loss in; under a hand-written
    ``jax.jit(jax.value_and_grad(...))`` nothing is counted):
    ``moe_pairs_routed_total`` (token-expert pairs routed),
    ``moe_pairs_local_total`` (those whose expert is held),
    ``moe_expert_load_max_total`` (the largest load of a held expert) and
    ``moe_passes_total`` (passes the loops ran: the pairs on held experts
    over the slots a pass takes, rounded up; a pass sweeps ``[T, D]`` once
    a direction). The
    collector returns them from the program, so they have to be values of
    the function it wraps: a caller that rematerialises the layer
    (``jax.checkpoint``) or runs it in a ``lax.scan`` body asks for the
    numbers (``return_stats=True``: ``(out, int32[4])`` in that order),
    returns them out of that region and counts them outside, as
    ``Transformer`` does, once a step for all its layers. A ``"reglu"``
    layer has one number more, ``moe_reglu_active_micro_total``: over the
    rows that hold a pair of a held expert, the share of the hidden units
    with ``gate > 0``, in millionths (about half at seeded weights; 0 says
    the experts carry nothing, 1,000,000 that the gate is a plain product),
    added up in the pass loop's carry; its stats are the pair
    ``(int32[4], float32 share)``.
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
            # A token's pairs on held experts first among its K (the sums
            # over a token's pairs read the first ``min(K, count)``).
            local = jnp.logical_and(top_idx >= first, top_idx < first + count)
            by_local = jnp.argsort(jnp.logical_not(local), axis=-1,
                                   stable=True)
            top_idx = jnp.take_along_axis(top_idx, by_local, axis=-1)
            top_w = jnp.take_along_axis(top_w, by_local, axis=-1)
            local = jnp.take_along_axis(local, by_local, axis=-1)
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
                               jnp.max(sizes[:count], initial=0),
                               -(-n_local // rows_a_pass)])
            w = jnp.where(local, top_w, 0.0)
            # a slot's weight; a padding slot reads the zero at the end
            w_slot = jax.lax.stop_gradient(_rows(
                jnp.concatenate([w.reshape(-1), jnp.zeros((1,), w.dtype)]),
                jnp.minimum(pair, t * k)))
            ends = jnp.cumsum(sizes)

        def with_share(active):
            # a "reglu" layer's share of active units beside the four
            if self.form != "reglu":
                return stats
            return stats, active / (jnp.maximum(n_local, 1) * h)

        if not count:
            out = jnp.zeros((t, d), x.dtype) if shared is None else shared
            return out.reshape(b, s, d), with_share(jnp.float32(0.0))

        out, active = experts_over_passes(
            u.astype(self.dtype), w, weights,
            (src, pos, w_slot, ends), n_local, rows_a_pass, interpret,
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
                "moe_expert_load_max_total", "moe_passes_total")
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
