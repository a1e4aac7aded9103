"""Graceful preemption drain (docs/design/churn.md): spot-instance
churn survival as a commit-boundary feature.

A cloud reclaim notice (SIGTERM with ``TORCHFT_RECLAIM_SEC`` of warning,
or an explicit :meth:`PreemptionDrain.request`) arms a drain that lands
at the next CLEAN commit boundary
(:meth:`~torchft_tpu.boundary.Boundary.blocked`, with the vote): at the
``step()`` call that follows it, once the caller has APPLIED the
committed update. The drain itself: (1) farewell FIRST — the leaving
intent must reach the lighthouse before the survivors' next quorum round
is served, or their already-dispatched step would run a collective
against a peer that is about to vanish; everything after the farewell is
local, so ordering it first costs nothing. (2) the final durable save to
the registered target. (3) advertisement withdrawal, so no healer or
subscriber is steered at a corpse. (4) shutdown; the next ``step()``
raises :class:`PreemptedExit` and the loop exits 0. Deadline expiry at
any point degrades to the hard-kill behavior with a flight-recorder dump
attributing where the drain was stuck.
"""

from __future__ import annotations

import logging
import os
import signal
import time
from typing import Any, Callable, Dict, Optional

from torchft_tpu.boundary import Boundary, BoundaryFeature

logger = logging.getLogger(__name__)


class PreemptedExit(RuntimeError):
    """Raised by :meth:`Manager.step` once a graceful preemption drain
    has completed (docs/design/churn.md): the manager has taken its
    final durable save, withdrawn its heal/publish advertisements, said
    farewell to the quorum, and shut down — the training loop must exit
    (with status 0: this is the *noticed-reclaim success path*, not a
    failure)."""


class PreemptionDrain(BoundaryFeature):
    """The drain's state and its step-edge entry point. Built from the
    boundary and the four things the drain does in order: ``farewell``,
    ``save`` (``Manager.save_durable``), ``withdraw`` (advertisements),
    ``shutdown``."""

    # Preemption notices received (SIGTERM / request), drains deferred
    # past a boundary, reclaim deadlines that expired before the drain
    # landed (degraded to hard-kill behavior + a flight dump), graceful
    # exits completed (farewell sent, ads withdrawn).
    METRICS = {
        "preempt_notices_total": 0.0,
        "preempt_drain_deferrals_total": 0.0,
        "preempt_deadline_expired_total": 0.0,
        "graceful_exits_total": 0.0,
    }

    def __init__(self, boundary: Boundary, farewell: Callable[[], None],
                 save: Callable[..., Any],
                 withdraw: Callable[[], None],
                 shutdown: Callable[[], None]) -> None:
        self._b = boundary
        self._farewell = farewell
        self._save = save
        self._withdraw = withdraw
        self._shutdown = shutdown
        # None or {"deadline": monotonic, "reason": str,
        # "pending_notices": int}; drained flips once the drain
        # completed; _expired latches the degraded-to-hard-kill outcome.
        self._preempt: Optional[Dict[str, Any]] = None
        self._drained = False
        self._expired = False
        # (writer, directory, prefix, user_state_fn) of the final save.
        self.target: Optional[tuple] = None
        self._target_explicit = False

    def set_target(self, writer: Any, directory: str, prefix: str,
                   user_state_fn: Optional[Callable[[], Any]]) -> None:
        """Register where the drain's FINAL durable save goes. Callers
        already saving through ``Manager.save_durable`` get this for
        free (:meth:`remember_target`), but a trainer that wants drain
        coverage from step 0 should register explicitly.

        ``user_state_fn``: optional snapshot source for the final save,
        for callers whose durable tree is richer than the
        manager-registered state (the ``user_state`` analogue of
        ``save_durable``). The drain's file must load against the same
        target structure as the cadence saves, or cold-start resume
        breaks on a tree mismatch."""
        self.target = (writer, directory, prefix, user_state_fn)
        self._target_explicit = True

    def remember_target(self, writer: Any, directory: str,
                        prefix: str) -> None:
        """``save_durable``'s last target; never clobbers an explicit
        registration."""
        if not self._target_explicit:
            self.target = (writer, directory, prefix, None)

    def request(self, deadline_s: Optional[float] = None,
                reason: str = "reclaim",
                _signal_safe: bool = False) -> float:
        """Arm the drain: this group will exit cleanly at the next
        clean commit boundary (see the module docstring). Idempotent
        under repeated notices: every notice counts, the EARLIEST
        deadline wins.

        ``_signal_safe`` (the installed SIGTERM handler passes True):
        skip everything that acquires a lock — the metrics lock and the
        logging module's handler locks: a signal handler runs ON the
        main thread between bytecodes, and a non-reentrant lock the
        interrupted frame already holds would deadlock the training
        loop (docs/design/churn.md). The skipped accounting is staged
        in the ``_preempt`` dict and flushed at the next boundary.

        ``deadline_s`` is the reclaim warning the cloud gave (env
        ``TORCHFT_RECLAIM_SEC``, default 120 — the common spot/
        preemptible notice); past it the drain degrades to hard-kill
        behavior with a flight dump. Returns the deadline in force (s
        from now)."""
        if deadline_s is None:
            deadline_s = float(os.environ.get("TORCHFT_RECLAIM_SEC", 120.0))
        deadline_s = max(float(deadline_s), 0.0)
        now = time.monotonic()
        # Work on a LOCAL snapshot: notices can arrive from a signal
        # handler or a watcher/orchestrator thread while the training
        # thread's _execute nulls self._preempt — re-reading the
        # attribute after the None check would TypeError. (Two racing
        # FIRST notices can still drop one from the count — benign: the
        # deadline is near-identical and the drain arms either way.)
        p = self._preempt
        if p is None:
            p = {"deadline": now + deadline_s, "reason": str(reason),
                 "pending_notices": 1}
            self._preempt = p
        elif self._expired:
            # A FRESH notice after an expired one (spot reprieve, then
            # re-reclaim): re-arm with the new deadline — min() against
            # the long-expired stamp would keep the drain inert forever
            # while logging a negative deadline.
            p["deadline"] = now + deadline_s
            p["reason"] = str(reason)
            p["pending_notices"] += 1
            self._expired = False
        else:
            p["deadline"] = min(p["deadline"], now + deadline_s)
            p["pending_notices"] += 1
        remaining = p["deadline"] - now
        if not _signal_safe:
            self._flush_notices()
            logger.warning(
                "%s: preemption notice (%s) — draining at the next clean "
                "commit boundary, deadline %.1fs",
                self._b.view().replica_id, reason, remaining)
        return remaining

    def _flush_notices(self) -> None:
        """Move signal-staged notice accounting into the locked
        counters/events — always on the training thread, never inside
        a signal handler."""
        p = self._preempt
        if p is None:
            return
        pending = p.get("pending_notices", 0)
        if pending:
            p["pending_notices"] = 0
            self._b.record(preempt_notices_total=pending)
            self._b.log_event(
                event="preempt_notice", step=self._b.view().step,
                deadline_s=round(p["deadline"] - time.monotonic(), 3),
                reason=p["reason"], notices=pending)

    def install_handler(self, deadline_s: Optional[float] = None,
                        signum: int = signal.SIGTERM) -> Any:
        """Install a ``SIGTERM`` handler that turns the cloud's reclaim
        signal into :meth:`request` (deadline from ``deadline_s`` /
        ``TORCHFT_RECLAIM_SEC``), chaining any previously-installed
        handler. Returns the previous handler. Must run on the main
        thread (a Python signal constraint)."""
        prev = signal.getsignal(signum)

        def handler(sig: int, frame: Any) -> None:
            # _signal_safe: no locks here — see request.
            self.request(deadline_s, reason=f"signal {sig}",
                         _signal_safe=True)
            if callable(prev) and prev not in (signal.SIG_IGN,
                                               signal.SIG_DFL):
                prev(sig, frame)

        signal.signal(signum, handler)
        return prev

    def pending(self) -> bool:
        return self._preempt is not None and not self._drained \
            and not self._expired

    def drained(self) -> bool:
        return self._drained

    def at_step_edge(self, committed: bool) -> None:
        """Land the drain, defer it, or expire it (the final save
        snapshots exactly what a cadence save at this step would), and
        end the run once it has landed."""
        p = self._preempt
        if p is not None and not self._drained and not self._expired:
            self._land(p, committed)
        if self._drained:
            v = self._b.view()
            raise PreemptedExit(
                f"{v.replica_id}: graceful preemption drain completed "
                f"at step {v.step}; the training loop must exit "
                "(this is the noticed-reclaim success path)")

    def _land(self, p: Dict[str, Any], committed: bool) -> None:
        self._flush_notices()  # signal-staged accounting
        blocked = self._b.blocked(decision=committed)
        if time.monotonic() > p["deadline"]:
            self._expire(",".join(blocked) or "notice deadline "
                         "passed before a boundary")
            return
        if blocked:
            # This boundary's state is not a settled committed step's
            # — a final save now would persist (and a farewell would
            # strand) exactly the inconsistent state the drain exists
            # to escape. Retry at the next boundary; the deadline
            # bounds how long.
            v = self._b.view()
            self._b.record(preempt_drain_deferrals_total=1)
            self._b.log_event(event="preempt_deferred", step=v.step,
                              why=",".join(blocked))
            logger.warning(
                "%s: preemption drain deferred at step %d (%s); retrying "
                "at the next boundary", v.replica_id, v.step,
                ",".join(blocked))
            return
        self._execute(p)

    def _expire(self, why: str) -> None:
        """The reclaim deadline passed before the drain landed: degrade
        to the pre-protocol hard-kill behavior — the imminent SIGKILL
        will look like a crash to survivors (staleness eviction, not
        farewell) — leaving a flight-recorder dump attributing where
        the drain was stuck."""
        v = self._b.view()
        self._expired = True
        self._b.record(preempt_deadline_expired_total=1)
        self._b.log_event(event="preempt_deadline_expired",
                          step=v.step, why=why)
        self._b.flight_dump("preempt_deadline_expired", why=why)
        logger.error(
            "%s: preemption deadline expired before the drain landed "
            "(%s); degrading to hard-kill behavior", v.replica_id, why)

    def _execute(self, p: Dict[str, Any]) -> None:
        v = self._b.view()
        self._b.log_event(event="preempt_drain", step=v.step,
                          reason=p["reason"])
        # (1) Farewell: membership intent out FIRST (module docstring).
        self._farewell()
        # (2) Final durable save, bounded by the remaining deadline.
        if self.target is not None:
            writer, directory, prefix, user_fn = self.target
            remaining = p["deadline"] - time.monotonic()
            try:
                fut = self._save(
                    writer, directory, prefix=prefix,
                    user_state=(user_fn() if user_fn is not None
                                else None))
                if fut is None:
                    # save_durable REFUSED: state turned unclean between
                    # _land's check and here (an async callback latched
                    # an error, the quorum thread flagged a heal).
                    # Completing the drain would log "final save taken"
                    # while the newest checkpoint is a cadence stale —
                    # degrade like a failed save instead.
                    self._expire(
                        "final durable save refused (state no longer a "
                        "settled committed step's)")
                    return
                fut.result(timeout=max(remaining, 0.001))
            except Exception as e:  # noqa: BLE001
                self._expire(f"final durable save failed: {e!r}")
                return
        # (3) Withdraw heal/publish advertisements.
        self._withdraw()
        # (4) Done: mark, count, shut down. step() raises PreemptedExit.
        self._drained = True
        self._preempt = None
        self._b.record(graceful_exits_total=1)
        self._b.log_event(event="graceful_exit", step=v.step,
                          reason=p["reason"])
        logger.warning(
            "%s: graceful preemption drain complete at step %d "
            "(farewell sent, final save %s, advertisements withdrawn)",
            v.replica_id, v.step,
            "taken" if self.target is not None else "skipped "
            "(no durable target registered)")
        self._shutdown()
