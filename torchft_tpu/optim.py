"""Commit-gated optimizer wrappers.

The reference hides the whole fault-tolerance protocol inside an unchanged
4-line torch loop via ``OptimizerWrapper``
(/root/reference/torchft/optim.py:23-54): ``zero_grad()`` starts the step
(quorum), ``step()`` applies the update only if the distributed commit vote
passed.

JAX is functional, which makes the commit gate *structurally* safe: "don't
commit" simply means the caller keeps the old ``(params, opt_state)`` pytree
— there is no zero_grad / half-applied-optimizer subtlety to undo. Two
idioms are offered:

:class:`FTOptimizer`
    The JAX-native shape. The canonical loop::

        opt = FTOptimizer(manager, optax.adamw(3e-4))
        opt_state = opt.init(params)
        for batch in data:
            opt.begin_step()                       # quorum, async
            grads = grad_fn(params, batch)         # jitted, overlaps quorum
            grads = manager.allreduce(grads).result()
            params, opt_state, ok = opt.apply(params, opt_state, grads)

    ``apply`` runs the commit vote; on False it returns the inputs
    unchanged (one step of progress lost at most, exactly the reference's
    guarantee).

:class:`OptimizerWrapper`
    Imperative adapter with the reference's exact method names
    (``zero_grad``/``step``/``state_dict``/``load_state_dict``) for porting
    torch-shaped training loops; holds ``(params, opt_state)`` internally.

:class:`DelayedOptimizer`
    The cross-step overlap engine's commit side (``Manager(
    overlap_steps=1)``, docs/design/overlap.md): step N's in-flight
    averaged-grad future is *staged* instead of drained, runs
    concurrently with step N+1's forward/backward, and is *settled* —
    drained, voted, applied-or-dropped — at the N+1 boundary. Gradients
    are one step stale; every failure path (vote abort, latched comm
    error) drops the stale grads, and a heal restore composes exactly
    like the sync path (the received average applies to the restored
    state, landing bitwise on the donor).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Optional, Tuple

import jax
import numpy as np
import optax

from torchft_tpu.exchange import ShardedGrads
from torchft_tpu.manager import Manager


class FTOptimizer:
    """Fault-tolerant optax wrapper: updates apply only on a committed step.

    Args:
        manager: the per-step FT manager.
        tx: any :mod:`optax` gradient transformation.
        jit: jit-compile the update function (donating the old pytrees so
            XLA can update buffers in place on TPU).
    """

    def __init__(self, manager: Manager, tx: optax.GradientTransformation,
                 jit: bool = True) -> None:
        self.manager = manager
        self.tx = tx
        self._jit = jit
        # The update's own dispatch is an ``update`` span on the step
        # thread, beside the boundary's hooks (Manager.should_commit).
        self._tracer = manager.tracer()

        def update(params: Any, opt_state: Any, grads: Any):
            updates, new_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_state

        # Donation: on commit the old params/opt_state are dead — letting
        # XLA alias them halves peak HBM for the update.
        self._update: Callable = (
            jax.jit(update, donate_argnums=(0, 1)) if jit else update
        )
        # ZeRO-style sharded update (docs/design/sharded_update.md):
        # when the manager opts in, apply() receives a ShardedGrads and
        # updates only this rank's stripe; the stripe optimizer state
        # lives HERE, keyed on the stripe geometry — deliberately
        # outside the holder's (healed/checkpointed) state_dict, whose
        # structure must match across ranks while stripe shapes differ
        # per rank. _update_shard is the NON-donating spelling: the
        # stripe update runs speculatively BEFORE the vote, so an abort
        # must keep the old state alive.
        # `is True`, not truthiness: duck-typed manager stand-ins
        # (MagicMock rigs) answer every call with a truthy mock, and
        # they must land in sync mode — same discipline as the
        # trainer's `overlap_steps() == 1` probe.
        sh = getattr(manager, "shard_update", None)
        self._shard_mode = callable(sh) and sh() is True
        self._shard_state: Optional[Tuple[tuple, Any]] = None
        self._update_shard: Optional[Callable] = None
        # Wall split of the most recent stripe update (ms): read by the
        # bench's rs A/B row.
        self.last_update_timings: dict = {}

    def init(self, params: Any) -> Any:
        return self.tx.init(params)

    def begin_step(self) -> None:
        """Start the FT step (kicks the async quorum). Call before the
        forward pass — the reference's ``zero_grad`` hook (optim.py:47-49)."""
        self.manager.step()

    def apply(self, holder: Any, grads: Any) -> bool:
        """Commit vote + conditional in-place update of ``holder``.

        ``holder`` is any object with ``.params`` / ``.opt_state``
        attributes (:class:`~torchft_tpu.parallel.step.FTTrainer`,
        :class:`OptimizerWrapper`, or your own state object). The holder is
        read *after* the vote — ordering that matters: when this replica is
        healing, ``should_commit()`` restores the peer's state into the
        holder on this thread (reference ``manager.py:441-442``), and the
        update must apply to the *restored* params, not a stale snapshot.

        Healers included: a healing replica's ``grads`` (from
        ``manager.allreduce``) are the *received* average of the
        participants' gradients, and its params were just restored to the
        primary's pre-step state — applying the same update lands it
        bitwise-identical to the primary's post-step state. That is the heal
        convergence mechanism; do not gate this on ``is_participating()``.

        Returns ``committed``; on False the holder is left untouched
        (reference optim.py:51-54).

        Sharded mode (``Manager(shard_update=True)``): ``grads`` is
        usually a :class:`~torchft_tpu.exchange.ShardedGrads` from
        :meth:`Manager.reduce_scatter` and the update runs on this
        rank's stripe only — see :meth:`_apply_sharded`. A plain tree in
        sharded mode (single-group fast path, on-device backend
        fallback) takes the same stripe machinery at world 1 (the stripe
        is everything), so the stripe state stays the one source of
        optimizer state either way.
        """
        if isinstance(grads, ShardedGrads):
            return self._apply_sharded(holder, grads)
        if self._shard_mode:
            return self._apply_sharded(holder,
                                       self.manager.full_shards(grads))
        committed = self.manager.should_commit()
        if committed:
            with self._tracer.span("update"):
                holder.params, holder.opt_state = self._update(
                    holder.params, holder.opt_state, grads)
        return committed

    def _apply_sharded(self, holder: Any, sg: ShardedGrads) -> bool:
        """ZeRO-style commit: heal-restore first, stripe update
        speculatively, allgather updated stripes, THEN vote — so the
        vote covers the allgather and a healer's published stripe comes
        from its RESTORED params. On abort the holder and stripe state
        are untouched (the gathered values are discarded), exactly the
        sync path's drop semantics.

        Stripe optimizer state is keyed on the stripe geometry
        (world, rank, sizes): a membership change moves every rank's
        stripe, so every rank re-inits together — params stay bitwise
        lockstep (the allgather republishes whatever each owner
        computed); only momentum restarts, counted in
        ``shard_state_resets``. Requires an ELEMENTWISE optimizer (sgd,
        adam & friends): a transform coupling elements across leaves
        (global-norm clipping) would need the full gradient this rank no
        longer holds."""
        m = self.manager
        # Heal restore must land in the holder BEFORE the stripe update
        # reads params — same ordering as the sync path's vote, split so
        # the allgather below stays covered by the vote.
        m.prepare_commit()
        if not sg.chunks:
            return m.should_commit()
        t0 = time.perf_counter()
        pshards = sg.param_shards(holder.params)
        key = sg.geometry_key()
        resets = 0
        if self._shard_state is not None and self._shard_state[0] == key:
            state = self._shard_state[1]
        else:
            if self._shard_state is not None:
                resets = 1
            state = self.tx.init(pshards)
        if self._update_shard is None:
            tx = self.tx

            def upd(p: Any, s: Any, g: Any):
                updates, ns = tx.update(g, s, p)
                return optax.apply_updates(p, updates), ns

            self._update_shard = jax.jit(upd) if self._jit else upd
        new_shards, new_state = self._update_shard(pshards, state,
                                                   sg.shards)
        new_np = [np.asarray(s) for s in new_shards]
        t1 = time.perf_counter()
        if sg.world > 1:
            gathered = m.allgather_shards(new_np).result()
        else:
            gathered = [new_np]
        t2 = time.perf_counter()
        committed = m.should_commit()
        # The vote wall is commit synchronization, not update work — it
        # already rides the trainer's commit bucket and must not leak
        # into update_ms_total (it would double-count and swamp the
        # allreduce-vs-reduce-scatter A/B the metric exists for).
        tv = time.perf_counter()
        if committed:
            holder.params = sg.assemble_params(gathered, holder.params)
            self._shard_state = (key, new_state)
            state_bytes = float(sum(
                np.asarray(leaf).nbytes
                for leaf in jax.tree_util.tree_leaves(new_state)))
            t3 = time.perf_counter()
            self.last_update_timings = {
                "update": t1 - t0, "allgather": t2 - t1,
                "assemble": t3 - tv, "vote": tv - t2,
            }
            m.record_update(((t2 - t0) + (t3 - tv)) * 1e3, state_bytes,
                            resets)
        return committed

    def shard_state_bytes(self) -> float:
        """Host-byte footprint of this rank's stripe optimizer state
        (~1/world of the full state) — 0.0 before the first committed
        sharded step."""
        if self._shard_state is None:
            return 0.0
        return float(sum(
            np.asarray(leaf).nbytes
            for leaf in jax.tree_util.tree_leaves(self._shard_state[1])))

    def update(self, params: Any, opt_state: Any, grads: Any,
               ) -> Tuple[Any, Any]:
        """The bare (jitted) optimizer update, no vote."""
        return self._update(params, opt_state, grads)


class DelayedOptimizer:
    """Deferred-commit optax wrapper: the commit half of the cross-step
    overlap engine (``Manager(overlap_steps=1)``,
    docs/design/overlap.md).

    The canonical overlap loop (what
    :class:`~torchft_tpu.parallel.step.FTTrainer` runs when its
    manager has ``overlap_steps() == 1``)::

        opt = DelayedOptimizer(manager, optax.adamw(3e-4))
        for batch in data:
            grads = grad_fn(holder.params, batch)   # async dispatch —
                                                    # overlaps the
                                                    # in-flight ring
            committed_prev = opt.settle() if opt.pending() else None
            opt.begin_step()                        # gated on the vote
            fut = manager.allreduce(grads)          # in flight across
                                                    # the boundary
            opt.stage(holder, fut)
        opt.flush()                                 # final step applies

    Semantics vs :class:`FTOptimizer` (the sync engine):

    * **One-step staleness.** Step k's gradients are computed at the
      params *before* step k-1's update applied (the speculative
      dispatch precedes the settle). Params remain in lockstep across
      groups — the applied update is always the agreed average — only
      the point each gradient is evaluated at shifts by one step.
    * **Deferred vote.** Step N's ``should_commit`` is cast at the N+1
      boundary, BEFORE ``step()`` advances the counter, so
      abort-doesn't-advance semantics are preserved unchanged.
    * **Drop on failure.** A vote abort (latched comm error, quorum
      change killing the transfer, too-few participants) leaves the
      holder untouched — the stale in-flight grads are dropped, never
      applied (``overlap_grads_dropped`` counts them).
    * **Heals converge bitwise.** When this replica healed during the
      staged step, ``settle`` restores the donor's state (inside the
      vote, exactly like sync mode) and then applies the *received*
      average to it — landing bitwise on the donor's post-step state.

    ``pending()``/``flush()`` exist for clean shutdown and checkpoint
    coupling: ``Manager.save_durable`` refuses to snapshot while a
    deferred step is in flight (its metadata and params would describe
    different steps) — flush first, then save.
    """

    def __init__(self, manager: Manager, tx: optax.GradientTransformation,
                 jit: bool = True) -> None:
        self._ft = FTOptimizer(manager, tx, jit=jit)
        self.manager = manager
        self._staged: Optional[Tuple[Any, Optional[Callable[[], None]]]] \
            = None
        # Main-thread wall split of the most recent settle (seconds):
        # "drain" = blocked on the in-flight allreduce, "vote_apply" =
        # commit vote + optimizer update. Read by FTTrainer's step
        # timings.
        self.last_settle_timings: dict = {}

    def init(self, params: Any) -> Any:
        return self._ft.init(params)

    def begin_step(self) -> None:
        """Start the next FT step. Raises if a deferred step is still
        staged (``Manager.step`` enforces settle-before-advance)."""
        self.manager.step()

    def stage(self, holder: Any, fut: Any,
              on_commit: Optional[Callable[[], None]] = None) -> None:
        """Stage the current step's in-flight averaged-grad future for
        application at the next boundary.

        ``holder`` follows :meth:`FTOptimizer.apply`'s contract
        (``.params`` / ``.opt_state`` attributes, read *after* the
        vote). ``on_commit`` runs only when the settled step commits —
        the hook non-param per-step state (e.g. BN stats adoption)
        rides on."""
        if self._staged is not None:
            # RuntimeError, not assert (must survive python -O):
            # overwriting the staged step would silently lose it.
            raise RuntimeError("settle the pending step first")
        # Adaptive-policy transition guard (docs/design/
        # adaptive_policy.md): when the manager's policy switched
        # overlap OFF at the boundary this step's settle just crossed,
        # staging another deferred step would violate the transition
        # contract (stale in-flight grads are exactly what the
        # escalation disabled). Drivers switch loops at the boundary
        # (AdaptiveTrainer does); this catches the ones that missed it.
        pol = getattr(self.manager, "policy", None)
        if callable(pol) and getattr(pol(), "overlap_steps", 1) == 0:
            raise RuntimeError(
                "manager policy has cross-step overlap disabled; "
                "staging a deferred step would violate the policy "
                "transition contract — switch to the sync loop at the "
                "commit boundary")
        self.manager.stage_deferred(fut)
        self._staged = (holder, on_commit)

    def pending(self) -> bool:
        """True while a staged step awaits its settle."""
        return self._staged is not None

    def settle(self) -> bool:
        """Drain the staged step's allreduce, cast its commit vote, and
        apply its update to the holder (or drop the stale grads on
        abort). Returns ``committed``. Must be called before the next
        :meth:`begin_step`."""
        if self._staged is None:
            raise RuntimeError("no staged step to settle")
        holder, on_commit = self._staged
        self._staged = None
        t0 = time.perf_counter()
        avg = self.manager.drain_deferred()
        t1 = time.perf_counter()
        # The vote drains remaining pending work, applies a staged heal
        # restore into the holder, then (on True) applies the update to
        # the — possibly just-restored — holder state. Identical
        # ordering to the sync path; only the boundary moved.
        committed = self._ft.apply(holder, avg)
        self.last_settle_timings = {
            "drain": t1 - t0,
            "vote_apply": time.perf_counter() - t1,
        }
        if committed:
            if on_commit is not None:
                on_commit()
        else:
            self.manager.note_deferred_dropped()
        return committed

    def flush(self) -> Optional[bool]:
        """Settle the staged step if any (clean shutdown / pre-checkpoint
        coupling). Returns the vote, or ``None`` when nothing was
        pending."""
        return self.settle() if self.pending() else None


class OptimizerWrapper:
    """Imperative adapter with the reference's method surface
    (/root/reference/torchft/optim.py:23-54) for torch-shaped loops.

    Owns the ``(params, opt_state)`` pair; ``.grads`` must be set (usually
    to the result of ``manager.allreduce``) before ``step()``.
    """

    def __init__(self, manager: Manager, tx: optax.GradientTransformation,
                 params: Any) -> None:
        self._ft = FTOptimizer(manager, tx)
        self.manager = manager
        self.params = params
        self.opt_state = self._ft.init(params)
        self.grads: Optional[Any] = None

    def zero_grad(self) -> None:
        self.grads = None
        self._ft.begin_step()

    def step(self) -> bool:
        assert self.grads is not None, "set .grads before step()"
        committed = self._ft.apply(self, self.grads)
        self.grads = None
        return committed

    def state_dict(self) -> Any:
        return {"params": self.params, "opt_state": self.opt_state}

    def load_state_dict(self, state: Any) -> None:
        self.params = state["params"]
        self.opt_state = state["opt_state"]
