"""DiLoCo-style fault-tolerant local SGD (BASELINE.md config 5).

Each replica group trains *locally* for ``sync_every`` inner steps (no
cross-group traffic at all — the DCN is idle), then runs one **outer
round**: the groups quorum, average their parameter deltas since the last
synchronized anchor, and apply an outer optimizer (SGD with Nesterov
momentum, the DiLoCo recipe) to the anchor. Communication drops by a
factor of ``sync_every`` versus per-step DDP, which is exactly what makes
cross-region / cheap-interconnect training viable.

Fault tolerance composes cleanly at outer-round granularity: the quorum,
1/n averaging, commit vote, and live-weight healing all operate on rounds
instead of steps — a killed group costs at most one *outer round* of its
own progress, and a healed group restores ``(anchor, params, optimizer
states)`` from a peer then applies the same averaged outer update,
landing bit-identical (the same convergence mechanism as
:class:`~torchft_tpu.parallel.step.FTTrainer`).
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Optional, Tuple

import jax
import optax

from torchft_tpu import tracing
from torchft_tpu.manager import Manager

logger = logging.getLogger(__name__)


def diloco_outer_optimizer(lr: float = 0.7, momentum: float = 0.9,
                           ) -> optax.GradientTransformation:
    """The DiLoCo outer optimizer: Nesterov momentum SGD."""
    return optax.sgd(lr, momentum=momentum, nesterov=True)


class DiLoCoTrainer:
    """Owns ``(params, anchor, inner/outer optimizer state)`` and runs the
    two-level schedule.

    Args:
        loss_fn: ``loss_fn(params, batch) -> loss`` (traced once).
        inner_tx: the per-step local optimizer (e.g. AdamW).
        outer_tx: the cross-group outer optimizer; default
            :func:`diloco_outer_optimizer`.
        sync_every: inner steps per outer round.
        manager_factory: as in FTTrainer — wires healing to live pytrees.
    """

    def __init__(
        self,
        loss_fn: Callable[[Any, Any], Any],
        inner_tx: optax.GradientTransformation,
        params: Any,
        manager_factory: Callable[..., Manager],
        outer_tx: Optional[optax.GradientTransformation] = None,
        sync_every: int = 16,
        jit: bool = True,
    ) -> None:
        self.sync_every = sync_every
        self._inner_tx = inner_tx
        self._outer_tx = outer_tx or diloco_outer_optimizer()

        self.params = params
        self.anchor = params  # last globally-synchronized params
        self.inner_state = inner_tx.init(params)
        self.outer_state = self._outer_tx.init(params)
        self.local_steps = 0
        # Boundary-staged sync_every change (set_sync_every): applied at
        # the END of the next outer round, so the current inner cycle
        # completes under the cadence its peers are counting with.
        self._pending_sync_every: Optional[int] = None

        def inner_step(p, st, batch):
            (loss, counts), grads = jax.value_and_grad(
                tracing.collect_counts(loss_fn), has_aux=True)(p, batch)
            updates, st = inner_tx.update(grads, st, p)
            return optax.apply_updates(p, updates), st, loss, counts

        def outer_update(anchor, ostate, avg_delta):
            updates, ostate = self._outer_tx.update(avg_delta, ostate,
                                                    anchor)
            return optax.apply_updates(anchor, updates), ostate

        def delta(anchor, p):
            return jax.tree_util.tree_map(lambda a, b: a - b, anchor, p)

        # The loss's counts (tracing.count_in_program) are the program's
        # last output; the host queues them and adds what has finished.
        self._inner_step = tracing.deferring_counts(
            jax.jit(inner_step) if jit else inner_step)
        self._outer_update = jax.jit(outer_update) if jit else outer_update
        self._delta = jax.jit(delta) if jit else delta

        self.manager: Manager = manager_factory(
            self.load_state_dict, self.state_dict)

    # ------------------------------------------------------------------ api

    def train_step(self, batch: Any) -> Tuple[Any, Optional[bool]]:
        """One inner step; every ``sync_every``-th call also runs the outer
        round. Returns ``(loss, outer_committed)`` — ``None`` when no outer
        round ran this call."""
        self.params, self.inner_state, loss = self._inner_step(
            self.params, self.inner_state, batch)
        self.local_steps += 1
        committed: Optional[bool] = None
        if self.local_steps % self.sync_every == 0:
            committed = self.outer_round()
        return loss, committed

    def outer_round(self) -> bool:
        """Quorum + averaged-delta outer update (the FT protocol at round
        granularity)."""
        m = self.manager
        m.step()
        # Pseudo-gradient: how far this group moved from the shared anchor.
        pseudo_grad = self._delta(self.anchor, self.params)
        avg = m.allreduce(pseudo_grad).result()
        committed = m.should_commit()  # may heal this holder in-place
        if committed:
            # Healers included: restored anchor/outer_state + same averaged
            # delta → identical post-round params everywhere.
            self.anchor, self.outer_state = self._outer_update(
                self.anchor, self.outer_state, avg)
            self.params = self.anchor
        else:
            logger.warning("outer round %d aborted; continuing locally",
                           m.current_step())
        self._apply_pending_sync_every()
        return committed

    # ---------------------------------------------- adaptive cadence

    def set_sync_every(self, sync_every: int) -> None:
        """Boundary-safe cadence change (needed by the adaptive policy
        controller — the DiLoCo rung tunes ``sync_every`` to the
        observed failure rate — and useful standalone): validated
        eagerly (same rules as the constructor, including the
        ``fragments`` divisibility in
        :class:`StreamingDiLoCoTrainer`), staged, and applied at the
        END of the next outer round — the current inner cycle completes
        under the old cadence, so every group's round boundaries keep
        agreeing (rounds are the only point the FT protocol
        synchronizes, and cadence must only change there)."""
        self._validate_sync_every(int(sync_every))
        self._pending_sync_every = int(sync_every)

    def _validate_sync_every(self, sync_every: int) -> None:
        if sync_every < 1:
            raise ValueError(
                f"sync_every must be >= 1, got {sync_every!r}")

    def _apply_pending_sync_every(self) -> None:
        if self._pending_sync_every is None:
            return
        old, self.sync_every = self.sync_every, self._pending_sync_every
        self._pending_sync_every = None
        if old != self.sync_every:
            logger.info("sync_every %d -> %d at round boundary "
                        "(step %d)", old, self.sync_every,
                        self.manager.current_step())

    # ------------------------------------------------- state (for healing)

    def state_dict(self) -> Any:
        return {
            "params": self.params,
            "anchor": self.anchor,
            "inner_state": self.inner_state,
            "outer_state": self.outer_state,
        }

    def load_state_dict(self, state: Any) -> None:
        self.params = state["params"]
        self.anchor = state["anchor"]
        self.inner_state = state["inner_state"]
        self.outer_state = state["outer_state"]

    def shutdown(self) -> None:
        self.manager.shutdown()


def _fragment_leaves(leaves: list, fragments: int) -> list:
    """Split leaf indices into ``fragments`` contiguous groups balanced by
    byte size. Deterministic (every process computes the identical split)
    and non-empty whenever there are at least ``fragments`` leaves: a
    group closes when it reaches its fair share of the REMAINING bytes,
    or when the remaining leaves are exactly one-per-remaining-group."""
    import numpy as np

    sizes = [int(np.prod(np.shape(leaf) or (1,)))
             * np.dtype(getattr(leaf, "dtype", None)
                        or np.asarray(leaf).dtype).itemsize
             for leaf in leaves]
    groups: list = []
    cur: list = []
    cur_bytes = 0
    remaining = sum(sizes)
    for i, nbytes in enumerate(sizes):
        cur.append(i)
        cur_bytes += nbytes
        groups_after = fragments - len(groups) - 1
        leaves_left = len(sizes) - i - 1
        groups_left = fragments - len(groups)
        if groups_after > 0 and (
            cur_bytes >= remaining / groups_left
            or leaves_left <= groups_after
        ):
            groups.append(cur)
            remaining -= cur_bytes
            cur, cur_bytes = [], 0
    if cur:
        groups.append(cur)
    while len(groups) < fragments:  # more fragments than leaves
        groups.append([])
    return groups


class StreamingDiLoCoTrainer(DiLoCoTrainer):
    """DiLoCo with the outer communication OVERLAPPED and SMOOTHED:
    parameters are split into ``fragments`` leaf groups, and each outer
    exchange syncs ONE fragment while the next ``sync_every/fragments``
    inner steps keep training — the DCN transfer of a fragment rides under
    compute instead of stalling the loop, and bandwidth is a steady trickle
    of 1/K-model-size transfers rather than a full-model burst every H
    steps (the streaming-DiLoCo recipe; upstream torchft grew the same
    capability after the reference snapshot this project matches).

    Per-fragment schedule and consistency: the fragment synced by an outer
    round is ``round_number % fragments`` — the manager's commit-gated step
    counter, which quorum/healing already keep identical across groups, so
    every group always averages the SAME leaf set. When a fragment's
    averaged delta arrives (collected at the next sync point), the outer
    optimizer advances that fragment's anchor and the live params keep the
    local progress made while the transfer was in flight:
    ``params_f = anchor_f' + (params_f - params_f_at_send)``. A healed
    group discards in-flight local progress for the restored fragment
    (``params_f = anchor_f'``), exactly like the synchronous trainer.

    Fault tolerance is unchanged: each fragment round is a full
    quorum/allreduce/commit round, aborted rounds retry the same fragment,
    and healing restores the complete state at round granularity.

    **When it pays (measured + modeled):** streaming runs
    ``fragments``-times more control rounds per window, each with the full
    fixed cost (quorum RPC, device→host dispatch, ring rendezvous), to
    move 1/K of the bytes per round under 1/K of the compute. Per sync
    window of H inner steps each taking t_step, with model bytes M, DCN
    bandwidth B, and fixed per-round cost c:

        plain window     = H*t_step + c + M/B        (one stalling burst)
        streaming window = K * max(H/K * t_step,     (transfer hidden
                                   c + (M/K)/B)       under compute)

    Streaming wins iff the per-fragment exchange fits under its compute
    slice: ``c + M/(K*B) < (H/K) * t_step`` — then the window costs
    H*t_step flat and the speedup approaches ``1 + (c + M/B)/(H*t_step)``.
    Worked example (the design center): 7B f32 deltas M=27 GB over
    B=25 GB/s inter-slice DCN, c=50 ms, H=64, t_step=0.5 s, K=4: plain
    window 32 + 1.13 s; streaming max(8, 0.05+0.27)=8 s per fragment x 4
    = 32 s flat -> ~3.5% end-to-end win, growing with sync frequency
    (H=16: 8+1.13 vs 8 -> +14%) and with slower DCN (B=5 GB/s, H=16:
    8+5.45 vs 8 -> +68%). The break-even reads off the same two
    expressions: streaming pays exactly when the plain window's stall
    ``c + M/B`` exceeds the streaming window's excess
    ``K*max(0, c + M/(K*B) - (H/K)*t_step)`` — in particular whenever
    each fragment exchange hides fully under its compute slice, which is
    the regime real DCN and real model sizes sit in.

    On a fixed-cost-dominated link the model predicts a strict loss
    (c >> (M/K)/B and c comparable to H/K*t_step) — not measured on an
    attached chip. Use :class:`DiLoCoTrainer` there; a single-chip
    localhost loop is that regime, which is why the tests pin the
    schedule/consistency contract (tests/test_local_sgd.py) rather than
    throughput.
    """

    def __init__(
        self,
        loss_fn: Callable[[Any, Any], Any],
        inner_tx: optax.GradientTransformation,
        params: Any,
        manager_factory: Callable[..., Manager],
        outer_tx: Optional[optax.GradientTransformation] = None,
        sync_every: int = 16,
        fragments: int = 4,
        jit: bool = True,
    ) -> None:
        if sync_every % fragments:
            raise ValueError("sync_every must be divisible by fragments")
        self.fragments = fragments
        self.interval = sync_every // fragments
        leaves, self._treedef = jax.tree_util.tree_flatten(params)
        self._frag_idx = _fragment_leaves(leaves, fragments)
        # In-flight fragment round: (fragment_id, allreduce future,
        # params-at-send leaf list). Must exist before super().__init__
        # wires the manager to state_dict/load_state_dict.
        self._pending: Optional[Tuple[int, Any, list]] = None
        # Per-fragment outer state over the fragment's leaf list (a leaf
        # list is a pytree): fragment updates must not touch the momentum
        # of leaves that did not sync this round.
        outer = outer_tx or diloco_outer_optimizer()
        self.outer_states = [
            outer.init([leaves[i] for i in idx]) for idx in self._frag_idx
        ]

        def frag_delta(anchor_f: list, params_f: list) -> list:
            return [a - b for a, b in zip(anchor_f, params_f)]

        def frag_outer(anchor_f: list, ostate, avg_f: list):
            updates, ostate = outer.update(avg_f, ostate, anchor_f)
            return optax.apply_updates(anchor_f, updates), ostate

        def frag_merge(anchor_new: list, params_f: list,
                       at_send: list) -> list:
            # Global correction + local progress made during the flight.
            return [a + (p - s)
                    for a, p, s in zip(anchor_new, params_f, at_send)]

        self._frag_delta = jax.jit(frag_delta) if jit else frag_delta
        self._frag_outer = jax.jit(frag_outer) if jit else frag_outer
        self._frag_merge = jax.jit(frag_merge) if jit else frag_merge

        # Shared plumbing (inner step, params/anchor/inner_state, manager
        # wiring, shutdown) comes from DiLoCoTrainer.
        super().__init__(loss_fn, inner_tx, params, manager_factory,
                         outer_tx=outer_tx, sync_every=sync_every, jit=jit)
        # The base class's full-tree outer momentum is replaced by the
        # per-fragment states; holding it would pin a model-size buffer.
        self.outer_state = None

    # ------------------------------------------------------------------ api

    def _leaves(self, tree: Any) -> list:
        return jax.tree_util.tree_flatten(tree)[0]

    def _rebuild(self, leaves: list) -> Any:
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def train_step(self, batch: Any) -> Tuple[Any, Optional[bool]]:
        """One inner step; every ``sync_every/fragments``-th call collects
        the in-flight fragment (if any) and launches the next one. Returns
        ``(loss, committed)`` — ``None`` when no fragment round completed
        this call."""
        self.params, self.inner_state, loss = self._inner_step(
            self.params, self.inner_state, batch)
        self.local_steps += 1
        committed: Optional[bool] = None
        if self.local_steps % self.interval == 0:
            committed = self.collect_pending()
            self.launch_fragment()
            self._apply_pending_sync_every()
        return loss, committed

    def outer_round(self) -> bool:
        """Streaming equivalent of one outer exchange: collect the
        in-flight fragment round (if any), then launch the next one."""
        committed = self.collect_pending()
        self.launch_fragment()
        self._apply_pending_sync_every()
        return bool(committed)

    def _validate_sync_every(self, sync_every: int) -> None:
        super()._validate_sync_every(sync_every)
        if sync_every % self.fragments:
            raise ValueError(
                f"sync_every ({sync_every}) must be divisible by "
                f"fragments ({self.fragments})")

    def _apply_pending_sync_every(self) -> None:
        changed = self._pending_sync_every is not None
        super()._apply_pending_sync_every()
        if changed:
            self.interval = self.sync_every // self.fragments

    def launch_fragment(self) -> int:
        """Start the next fragment's outer round: the fragment's
        pseudo-gradient is handed to the cross-group allreduce and inner
        steps continue while the transfer flies."""
        m = self.manager
        m.step()
        # The fragment id must be the QUORUM-AGREED round, not the
        # pre-quorum local step: an async-healing rejoiner's step counter
        # is rewritten to the survivors' max_step on the quorum thread,
        # and choosing the fragment before that lands would feed a
        # different leaf set into the same ring than everyone else.
        # (Manager.allreduce joins the quorum future anyway, so this
        # costs no overlap.)
        m.wait_quorum()
        frag = m.current_step() % self.fragments
        idx = self._frag_idx[frag]
        a = self._leaves(self.anchor)
        p = self._leaves(self.params)
        anchor_f = [a[i] for i in idx]
        params_f = [p[i] for i in idx]
        pseudo = self._frag_delta(anchor_f, params_f)
        fut = m.allreduce(pseudo)
        self._pending = (frag, fut, params_f)
        return frag

    def collect_pending(self) -> Optional[bool]:
        """Resolve the in-flight fragment round: commit vote, advance the
        fragment's anchor, merge the correction into live params."""
        if self._pending is None:
            return None
        m = self.manager
        frag, fut, at_send = self._pending
        self._pending = None
        avg_f = fut.result()
        committed = m.should_commit()  # may heal this holder in-place
        if not committed:
            logger.warning("fragment round %d (frag %d) aborted; "
                           "continuing locally", m.current_step(), frag)
            return False
        healed = m.is_healing()
        idx = self._frag_idx[frag]
        a = self._leaves(self.anchor)
        p = self._leaves(self.params)
        anchor_f = [a[i] for i in idx]
        new_anchor_f, self.outer_states[frag] = self._frag_outer(
            anchor_f, self.outer_states[frag], avg_f)
        if healed:
            # Restored state: take the synchronized values outright.
            new_params_f = list(new_anchor_f)
        else:
            params_f = [p[i] for i in idx]
            new_params_f = self._frag_merge(new_anchor_f, params_f, at_send)
        for j, i in enumerate(idx):
            a[i] = new_anchor_f[j]
            p[i] = new_params_f[j]
        self.anchor = self._rebuild(a)
        self.params = self._rebuild(p)
        return True

    def flush(self) -> Optional[bool]:
        """Drain the in-flight round (end of training / before a durable
        checkpoint)."""
        return self.collect_pending()

    # ------------------------------------------------- state (for healing)

    def state_dict(self) -> Any:
        return {
            "params": self.params,
            "anchor": self.anchor,
            "inner_state": self.inner_state,
            "outer_states": self.outer_states,
            "local_steps": self.local_steps,
        }

    def load_state_dict(self, state: Any) -> None:
        self.params = state["params"]
        self.anchor = state["anchor"]
        self.inner_state = state["inner_state"]
        self.outer_states = state["outer_states"]
        self.local_steps = int(state["local_steps"])

