"""Degraded-mode groups: survive partial chip loss with nonuniform
parallelism instead of whole-group eviction
(docs/design/degraded_mode.md).

Today's baseline behavior — a replica group that loses one chip dies
wholesale and its work redistributes in whole-group quanta — wastes the
group's surviving capacity. Per *Nonuniform-Tensor-Parallelism* (arxiv
2504.06095) a wounded group should rejoin the quorum at reduced
capacity and keep contributing; per the 100k-GPU HSDP paper (arxiv
2602.00277) partial-capacity operation is the dominant production
regime, not the exception.

The pieces, each living where its layer lives:

* :func:`torchft_tpu.parallel.mesh.surviving_submesh` — largest usable
  submesh over the live-device set (the data axis shrinks, TP/SP axes
  survive intact) plus the capacity fraction;
* :func:`torchft_tpu.parallel.sharding.degraded_shardings` — param
  layout re-derivation that falls back to replication where the
  shrunken axis no longer divides;
* :meth:`torchft_tpu.manager.Manager.request_degrade` /
  ``request_restore`` — the capacity transition itself, landing only at
  commit boundaries and refused mid-heal/mid-deferred like
  ``save_durable``;
* the **weighted canonical-order fold** in the host ring
  (``backends/host.py``) — every group's gradient weighted by samples
  actually contributed, the weight riding the per-op wire preamble so
  weight/geometry skew aborts cleanly;
* :class:`~torchft_tpu.data.ElasticSampler` — the per-group batch
  shrinks with the capacity fraction riding the same atomic
  ``participant_slot`` snapshot as the slot itself.

This module is the per-group GLUE: :class:`DegradedModeDriver` polls
the live-device set once per commit boundary (the chaos ``device``
channel is the test/soak injection point — :func:`live_devices`), and
on a change walks the full degrade -> rejoin -> restore lifecycle.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable, Optional, Sequence, Tuple

from torchft_tpu import chaos, fleet
from torchft_tpu.boundary import Boundary, BoundaryFeature

logger = logging.getLogger(__name__)

__all__ = ["BatchShare", "DegradedModeDriver", "live_devices"]

# The quorum-store key the fleet-rebalance decision rides on
# (:meth:`~torchft_tpu.boundary.Boundary.publish`).
_REBALANCE_KEY: str = "torchft/rebalance"
# Fold-weight encoding of a fraction when the caller never reports
# exact per-step sample counts: weight = round(fraction * SCALE). Only
# RATIOS between groups matter, so any shared scale works; 10_000 keeps
# three decimal places of fraction resolution in integer weights.
_CAPACITY_WEIGHT_SCALE = 10_000


def _flag(value: Optional[bool], env: str) -> bool:
    if value is None:
        value = os.environ.get(env, "0").strip().lower() in ("1", "true")
    return bool(value)


class BatchShare(BoundaryFeature):
    """This group's share of the global batch — degraded-mode capacity
    times the lighthouse's rebalance fraction — as a commit-boundary
    feature (docs/design/degraded_mode.md, fleet_rebalance.md).

    Arming either (``degraded_mode`` / env ``TORCHFT_DEGRADED``,
    ``rebalance`` / env ``TORCHFT_REBALANCE``) switches the ring fold
    into weighted mode — a CLUSTER-WIDE wire-format property (every
    group weighted or none; mode mixing is a per-op preamble abort) —
    so both are launch flags, not live knobs. The fractions ARE live,
    and land only at commit boundaries under the boundary's refusal
    rule (:meth:`~torchft_tpu.boundary.Boundary.blocked`), minus its
    aborted-vote reason, DELIBERATELY: an aborted step applied nothing,
    and the dominant degrade trigger IS a chip loss that keeps aborting
    the vote.

    *Capacity* moves by :meth:`request_degrade` /
    :meth:`request_restore` (:class:`DegradedModeDriver` re-pjits the
    trainer onto the surviving submesh). *Rebalance*: the lighthouse
    Rebalancer turns persistent straggler scores into per-group batch
    fractions and echoes the table in every FleetHint (``table``,
    refreshed by the quorum thread); the fractions land through the
    SAME decider-publishes/all-adopt protocol as policy switches
    (:meth:`pre_vote` / :meth:`post_vote`).

    ``capacity``, ``rebalance_fraction``, ``step_samples`` and
    ``table`` are read and written under ``boundary.lock`` (the
    Manager's metrics lock), so ``participant_slot()`` snapshots never
    observe a torn combination."""

    # The capacity fraction in force (gauge, 1.0 = full capacity) and
    # the degrade / restore transitions that landed (refusals ride the
    # event log); the lighthouse-assigned batch fraction in force
    # (gauge, 1.0 = uniform share), adoptions that landed, and
    # adoptions deferred a boundary by the refusal rule.
    METRICS = {
        "degraded_capacity_fraction": 1.0,
        "degrade_events_total": 0.0,
        "restore_events_total": 0.0,
        "rebalance_fraction": 1.0,
        "rebalance_adoptions_total": 0.0,
        "rebalance_deferred_total": 0.0,
    }

    def __init__(self, boundary: Boundary, degraded_mode: Optional[bool],
                 rebalance: Optional[bool], device_backend: bool) -> None:
        self._b = boundary
        self.degraded = _flag(degraded_mode, "TORCHFT_DEGRADED")
        self.rebalance = _flag(rebalance, "TORCHFT_REBALANCE")
        self.weighted = self.degraded or self.rebalance
        for on, name in ((self.degraded, "degraded_mode"),
                         (self.rebalance, "rebalance")):
            if on and device_backend:
                raise ValueError(
                    f"{name} requires a host-path communicator: the "
                    "weighted fold lives in the host ring's wire ops, "
                    "which on-device backends never issue")
        self.capacity = 1.0
        self.rebalance_fraction = 1.0
        self.step_samples: Optional[int] = None
        self.table = ""
        # The rebalance fraction that was IN FORCE for the step the
        # next digest measures (roll_digest_fraction).
        self._frac_prev = 1.0

    def wire_weight(self) -> int:
        """This step's fold weight: 0 while healing or benched (the
        zero contribution must carry zero weight), else the samples the
        caller reported via ``Manager.set_step_samples`` (an
        :class:`~torchft_tpu.data.ElasticSampler` draw reports
        automatically), else a fixed-scale encoding of the EFFECTIVE
        fraction (capacity x rebalance — the same product
        ``participant_slot`` snapshots, so the sampler's draw and the
        fallback weight always agree) — so groups that share a batch
        config stay PROPORTIONAL whether or not they report exact
        counts, as long as every group uses the same convention."""
        if not self._b.participating():
            return 0
        with self._b.lock:
            samples = self.step_samples
            frac = self.capacity * self.rebalance_fraction
        if samples is not None:
            return max(int(samples), 0)
        return max(1, int(round(frac * _CAPACITY_WEIGHT_SCALE)))

    def roll_digest_fraction(self) -> float:
        """The rebalance fraction to stamp into this boundary's fleet
        digest: the one IN FORCE for the step the digest MEASURES — the
        digest is pushed after this boundary's adoption landed, so the
        live value would mis-normalize the just-measured wall by one
        boundary. Rolled on EVERY boundary so it always holds the
        previous boundary's adoption."""
        with self._b.lock:
            prev, self._frac_prev = self._frac_prev, self.rebalance_fraction
        return prev

    def _land(self, attr: str, fraction: float, event: str, counter: str,
              refused: str, reason: str, samples: Any = ...) -> bool:
        """Move ``attr`` to ``fraction`` at this commit boundary, or
        refuse (logged as ``refused``; the caller retries at the next
        boundary). Every landed transition leaves a Perfetto-loadable
        dump: the span ring around it is exactly what the "why did this
        group shrink" postmortem wants."""
        v = self._b.view()
        blocked = self._b.blocked()
        if blocked:
            self._b.log_event(event=refused, step=v.step,
                              fraction=fraction, why=",".join(blocked))
            logger.warning(
                "%s: %s to fraction %.4f refused (%s); retry at the "
                "next boundary", v.replica_id, event, fraction,
                ",".join(blocked))
            return False
        with self._b.lock:
            prev = getattr(self, attr)
            setattr(self, attr, float(fraction))
            if samples is not ...:
                self.step_samples = (None if samples is None
                                     else int(samples))
        self._b.gauge(**{"degraded_capacity_fraction" if attr == "capacity"
                         else attr: float(fraction)})
        self._b.record(**{counter: 1})
        self._b.log_event(event=event, step=v.step, reason=reason,
                          **{"from": prev, "to": fraction})
        self._b.flight_dump(event, **{"from": prev, "to": fraction,
                                      "why": reason})
        logger.info("%s %s %.4f -> %.4f at step %d (%s)", v.replica_id,
                    attr, prev, fraction, v.step, reason)
        return True

    def request_degrade(self, fraction: float,
                        samples: Optional[int] = None,
                        reason: str = "device_loss") -> bool:
        """Land a capacity degrade at the current commit boundary: this
        group keeps training on its surviving submesh, contributing
        ``fraction`` of its nominal batch, its gradient weighted by
        samples actually contributed. Refused — returning False and
        stamping a ``degrade_refused`` event — mid-heal, mid-deferred,
        or errored; callers retry at the next boundary
        (:class:`DegradedModeDriver` does). ``samples`` optionally pins
        the exact per-step sample count the fold weight uses. Under a
        DiLoCo policy call this only at outer-round boundaries (where
        the driver's tick naturally lands): the round's pseudo-gradient
        is weighted by the per-step rate, which represents the round
        only while capacity is constant across it."""
        self._require_degraded("request_degrade")
        if not 0.0 < fraction <= 1.0:
            raise ValueError(
                f"capacity fraction must be in (0, 1], got {fraction!r}"
                " — a group at fraction 0 is dead, which is the "
                "whole-group eviction path's job")
        return self._land("capacity", fraction, "degrade",
                          "degrade_events_total", "degrade_refused",
                          reason, samples)

    def request_restore(self, reason: str = "devices_returned") -> bool:
        """Land the restore back to full capacity (devices returned /
        replaced): the inverse of :meth:`request_degrade`, with the
        same boundary discipline and refusal rules."""
        self._require_degraded("request_restore")
        return self._land("capacity", 1.0, "restore",
                          "restore_events_total", "restore_refused",
                          reason, None)

    def _require_degraded(self, what: str) -> None:
        if not self.degraded:
            raise RuntimeError(
                f"{self._b.replica_id()}: {what} needs "
                "Manager(degraded_mode=True) — the weighted fold must "
                "be armed cluster-wide at launch")

    def publish_capacity(self, store_fn: Callable[[], Any],
                         replica_rank: int) -> None:
        """Quorum-thread half: advertise this group's capacity fraction
        under the fixed per-rank key ``torchft/capacity/{replica_rank}``
        on the quorum store, value ``"{step}:{fraction}"`` — the
        fleet-visibility half of "rejoins the quorum advertising a
        capacity fraction" (the fold itself learns weights from the
        wire preamble, which is authoritative). Best-effort, like the
        healset keys, and the key is fixed per rank for the same
        no-TTL-store reason."""
        if not self.degraded:
            return
        try:
            store = store_fn()
            if store is None:
                return
            with self._b.lock:
                frac = self.capacity
            store.set(f"torchft/capacity/{replica_rank}",
                      f"{self._b.view().step}:{frac}".encode())
        except Exception:  # noqa: BLE001 — advertisement is best-effort
            logger.debug("capacity publication failed", exc_info=True)

    def pre_vote(self) -> None:
        """Decider half of the rebalance hook: participating rank 0
        publishes ``{step}:{table}`` (the latest FleetHint fraction
        table) every boundary — unconditionally, like the policy
        decider."""
        if not self.rebalance or not self._b.decider():
            return
        with self._b.lock:
            table = self.table
        self._b.publish(_REBALANCE_KEY, f"{self._b.view().step}:{table}")

    def post_vote(self, decision: bool) -> None:
        """All-groups half: read the published table (coordinated) or
        fall back to this group's own hint copy (single-group /
        storeless runs), pick out our entry — absent means 1.0, the
        restore-to-uniform spelling and the farewell path's implicit
        clear (a departed group's entry is dropped from the table the
        same round the lighthouse forgets its digests) — clamp to the
        ladder bounds, and adopt it; a refused adoption counts
        ``rebalance_deferred_total`` and retries at the next boundary
        (the table re-reads every round, so nothing is lost). A failed
        read adopts nothing: stale-but-consistent beats a torn
        default."""
        if not self.rebalance:
            return
        if self._b.coordination()[3]:
            raw = self._b.read(_REBALANCE_KEY)
            if raw is None:
                return
            table = raw.partition(":")[2]
        else:
            with self._b.lock:
                table = self.table
        fractions = fleet.parse_rebalance_table(table)
        target = float(fractions.get(self._b.replica_id(), 1.0))
        target = min(fleet.REBALANCE_CEIL,
                     max(fleet.REBALANCE_FLOOR, target))
        with self._b.lock:
            cur = self.rebalance_fraction
        if abs(target - cur) < 1e-9:
            return
        if not self._land("rebalance_fraction", target, "rebalance_adopt",
                          "rebalance_adoptions_total",
                          "rebalance_deferred", "lighthouse table"):
            self._b.record(rebalance_deferred_total=1)


def live_devices(replica_id: str,
                 devices: Optional[Sequence[Any]] = None,
                 schedule: Optional["chaos.ChaosSchedule"] = None) -> list:
    """The group's current live-device list: ``devices`` (default
    ``jax.devices()``) minus the chaos ``device`` channel's lost-chip
    set for endpoint ``device:<replica_id>`` — one ``device_fault``
    decision is drawn per call, so polling this once per commit
    boundary IS the seeded chip-loss/chip-return event stream the
    degraded-mode soak drives (optionally through
    :class:`~torchft_tpu.policy.PhasedChaos` intensity phases). With no
    chaos installed it returns the real device list unchanged — the
    production spelling, where a lost TPU chip simply vanishes from the
    runtime's view."""
    if devices is None:
        import jax

        devices = jax.devices()
    devices = list(devices)
    lost = chaos.device_fault(f"device:{replica_id}", len(devices),
                              schedule)
    if not lost:
        return devices
    return [d for i, d in enumerate(devices) if i not in lost]


class DegradedModeDriver:
    """Per-group degrade -> rejoin -> restore driver.

    Owns one group's full mesh and layout inputs; :meth:`tick` — called
    once per commit boundary, after the step's vote settled — probes
    the live-device set and, when the surviving capacity changed,
    lands the transition end to end:

    1. derive the surviving submesh + capacity fraction
       (:func:`~torchft_tpu.parallel.mesh.surviving_submesh`);
    2. land it on the manager (:meth:`Manager.request_degrade` /
       ``request_restore`` — refused mid-heal/mid-deferred and simply
       retried at the next tick);
    3. re-derive shardings for the target mesh
       (:func:`~torchft_tpu.parallel.sharding.degraded_shardings`) and
       re-place the trainer's pytrees
       (:meth:`FTTrainer.set_placement` — the re-``pjit``: jit
       re-specializes on the new placement at the next step).

    The per-group batch shrink needs no driver action: the capacity
    fraction rides the manager's atomic ``participant_slot`` snapshot,
    so the group's :class:`~torchft_tpu.data.ElasticSampler` draws the
    shrunken batch (and reports its exact size as the fold weight) on
    the very next step. Restore is the same walk back onto the full
    mesh — the params re-heal onto it by re-placement (their values
    never left lockstep; only their layout was wounded).

    Args:
        trainer: the group's :class:`~torchft_tpu.parallel.FTTrainer`
            (anything with ``manager`` + ``set_placement`` works).
        mesh: the FULL mesh the group was launched on.
        rules: TP partition rules, as given to ``combined_shardings``.
        fsdp_axis / min_size: FSDP inference knobs, ditto.
        batch_axes: data axes of the batch spec.
        shrink_axis: mesh axis chip loss shrinks (default: first).
        probe: zero-arg callable returning the current live-device
            list; defaults to :func:`live_devices` over the manager's
            replica id and the full mesh's devices (the chaos-drivable
            spelling).
    """

    def __init__(self, trainer: Any, mesh: Any, rules: Sequence = (),
                 fsdp_axis: str = "fsdp", min_size: int = 1024,
                 batch_axes: Tuple[str, ...] = ("dp", "fsdp"),
                 shrink_axis: Optional[str] = None,
                 probe: Optional[Callable[[], Sequence[Any]]] = None
                 ) -> None:
        self.trainer = trainer
        self.mesh = mesh
        self.rules = tuple(rules)
        self.fsdp_axis = fsdp_axis
        self.min_size = min_size
        self.batch_axes = tuple(batch_axes)
        self.shrink_axis = shrink_axis
        self._probe = probe
        self._fraction = 1.0  # capacity the trainer's layout reflects

    @property
    def manager(self) -> Any:
        return self.trainer.manager

    def fraction(self) -> float:
        """Capacity the trainer's CURRENT layout reflects (the
        manager's own fraction can briefly differ only between a landed
        transition and this driver's re-placement, which happen in one
        tick)."""
        return self._fraction

    def _live(self) -> list:
        if self._probe is not None:
            return list(self._probe())
        return live_devices(self.manager.replica_id(),
                            list(self.mesh.devices.flat))

    def _place(self, target_mesh: Any) -> None:
        from jax.sharding import NamedSharding

        from torchft_tpu.parallel.sharding import (batch_spec,
                                                   degraded_shardings)

        shardings = degraded_shardings(
            self.trainer.params, target_mesh, rules=self.rules,
            fsdp_axis=self.fsdp_axis, min_size=self.min_size)
        self.trainer.set_placement(
            param_shardings=shardings,
            batch_sharding=NamedSharding(
                target_mesh, batch_spec(target_mesh, self.batch_axes)))

    def tick(self) -> bool:
        """One boundary's poll; returns True when a capacity transition
        landed (manager + placement). Call between steps, after the
        vote — never with a collective in flight.

        The manager transition and the re-placement are independently
        idempotent: the manager half keys on ``capacity_fraction()``,
        the placement half on this driver's own ``fraction()``. A
        ``_place`` failure (e.g. transient OOM replicating a fallback
        leaf) therefore propagates WITHOUT desyncing — the next tick
        sees the manager already at the target fraction (no duplicate
        degrade event/flight dump) and retries only the placement."""
        from torchft_tpu.parallel.mesh import surviving_submesh

        try:
            submesh, frac = surviving_submesh(
                self.mesh, self._live(), self.shrink_axis)
        except ValueError:
            # No slice survives: the group is effectively dead. Leave
            # the layout alone — the quorum's liveness machinery (lapsed
            # heartbeats, eviction) owns this case.
            logger.warning("%s: no usable submesh survives the device "
                           "loss; leaving degraded-mode state unchanged "
                           "(whole-group eviction path takes over)",
                           self.manager.replica_id())
            return False
        if frac == self._fraction \
                and frac == self.manager.capacity_fraction():
            return False
        if frac != self.manager.capacity_fraction():
            if frac < 1.0:
                landed = self.manager.request_degrade(frac)
            else:
                landed = self.manager.request_restore()
            if not landed:
                return False  # refused (mid-heal/deferred); retry next tick
        if frac != self._fraction:
            self._place(submesh if frac < 1.0 else self.mesh)
            self._fraction = frac
        return True
