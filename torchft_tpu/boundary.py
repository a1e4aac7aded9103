"""The commit boundary's one home (docs/design/commit_boundary.md).

A fault-tolerance feature changes a group's state only at a commit
boundary, and only when the boundary is clean. This module holds what
every such feature shares: the refusal rule, written once as the two
predicates of :class:`Boundary`, and the shape of a feature
(:class:`BoundaryFeature`: at most three entry points, walked by
``Manager.step`` and ``Manager.should_commit`` in the order of the
Manager's one tuple). The features live beside the code they drive
(``preemption.py``, ``ram_ckpt.py``, ``chaos.py``, ``policy.py``,
``degraded.py``).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Optional

logger = logging.getLogger(__name__)


class View(NamedTuple):
    """One read of the step thread's protocol state (``healing`` and
    ``quarantined`` under the Manager's metrics lock): it serves a
    whole boundary call."""

    replica_id: str
    step: int
    healing: bool
    quarantined: bool
    deferred: bool
    errored: bool
    committed: bool  # the last vote's decision


class BoundaryFeature:
    """A unit that owns its state and offers at most three entry
    points to the boundary. ``METRICS`` declares the counters and
    gauges it owns (merged into ``Manager.metrics()``)."""

    METRICS: Dict[str, float] = {}

    def at_step_edge(self, committed: bool) -> None:
        """Top of ``step()``: the post-apply half of the last boundary
        (the caller has applied the update ``committed`` voted on)."""

    def pre_vote(self) -> None:
        """In ``should_commit``, drained and healed, before the vote."""

    def post_vote(self, decision: bool) -> None:
        """In ``should_commit``, after the vote and its own record."""


@dataclass(frozen=True)
class Boundary:
    """The refusal rule, and what a feature is built from in place of
    the Manager: the tracer, ``record`` (counter deltas) and ``gauge``
    (values) into the metrics dict, the event log and the flight
    recorder. ``lock`` is the Manager's metrics lock, for state a
    feature shares with ``participant_slot()`` snapshots.
    ``replica_id``, ``participating`` and ``decider`` (participating
    rank 0) are the step's facts that need no lock; ``coordination()``
    -> ``(store_addr, replica_world, max_world, coordinated)`` and
    ``store_client(addr)`` are the current quorum round's."""

    tracer: Any
    lock: Any
    record: Callable[..., None]
    gauge: Callable[..., None]
    log_event: Callable[..., None]
    flight_dump: Callable[..., None]
    view: Callable[[], View]
    replica_id: Callable[[], str]
    participating: Callable[[], bool]
    decider: Callable[[], bool]
    coordination: Callable[[], tuple]
    store_client: Callable[[str], Any]
    timeout_ms: int

    def blocked(self, decision: Optional[bool] = None,
                ignore_errored: bool = False,
                v: Optional[View] = None) -> list:
        """May a change land here? The reasons it may not (empty: it
        may): a heal in flight (the restored state and the change must
        not interleave), a deferred allreduce in flight (metadata and
        params describe different steps), a latched error, an aborted
        vote. Callers that run before a vote, or for which an aborted
        step is no obstacle, pass no ``decision``; the coordinated
        policy adoption ignores ``errored``."""
        v = v if v is not None else self.view()
        reasons = []
        if v.healing:
            reasons.append("healing")
        if v.deferred:
            reasons.append("deferred in flight")
        if v.errored and not ignore_errored:
            reasons.append("errored")
        if decision is False:
            reasons.append("vote aborted")
        return reasons

    def settled(self, what: str, counter: str, event: str) -> bool:
        """Is this state a settled committed step's? Not for any reason
        of :meth:`blocked`, nor when the last vote did not commit or
        the state is quarantined (its bytes lost the fleet's
        attestation vote) — with the consequence every snapshot path
        shares: an unsettled state is not snapshotted, and the skip is
        warned of, counted under ``counter`` (and ``sdc_refusals_total``
        when quarantined) and logged as ``event``."""
        v = self.view()
        if not (self.blocked(decision=v.committed, v=v)
                or v.quarantined):
            return True
        logger.warning(
            "%s: skipping %s at step %d (healing=%s errored=%s "
            "committed=%s deferred=%s quarantined=%s) — state is not a "
            "settled committed step's%s", v.replica_id, what, v.step,
            v.healing, v.errored, v.committed, v.deferred, v.quarantined,
            " (flush() the deferred step first)" if v.deferred else "")
        self.record(**{counter: 1},
                    **({"sdc_refusals_total": 1} if v.quarantined else {}))
        self.log_event(event=event, step=v.step, healing=v.healing,
                       errored=v.errored, committed=v.committed,
                       deferred=v.deferred, quarantined=v.quarantined)
        return False

    # A coordinated decision rides one FIXED key of the quorum store
    # (no delete/TTL there: a per-step key would leak an entry a
    # boundary). The decider refreshes it before every vote, so a read
    # after the vote never blocks on an absent key; the ring collective
    # between boundaries orders each publication before the NEXT read.

    def publish(self, key: str, value: str) -> None:
        """Decider half (``pre_vote``); best-effort, retried at the
        next boundary. No-op on an uncoordinated round."""
        addr, _rw, _mw, coordinated = self.coordination()
        if not coordinated:
            return
        try:
            store = self.store_client(addr)
            if store is not None:
                store.set(key, value.encode())
        except Exception:  # noqa: BLE001 — retried next boundary
            logger.debug("publication of %s failed", key, exc_info=True)

    def read(self, key: str) -> Optional[str]:
        """All-groups half (``post_vote``): the published value, or
        None when the read failed (the next boundary re-reads)."""
        try:
            store = self.store_client(self.coordination()[0])
            if store is not None:
                return store.get(
                    key, timeout_ms=min(self.timeout_ms, 2000)).decode()
        except Exception:  # noqa: BLE001 — next boundary re-reads
            logger.debug("read of %s failed", key, exc_info=True)
        return None
