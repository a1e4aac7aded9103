"""Adaptive fault-tolerance policy: hot-swappable FT knobs driven by
live failure signals (ROADMAP item 3, docs/design/adaptive_policy.md).

PRs 1-8 grew a large fault-tolerance knob space — cross-step overlap
(``overlap_steps``), the wire-dtype ladder (exact f32 / bf16 / the int8 +
error-feedback rung), DiLoCo mode with its ``sync_every``, and the
durable-checkpoint cadence — but froze every knob at ``Manager``
construction. Per *Chameleon: Adaptive Fault Tolerance via Real-time
Policy Selection* (arxiv 2508.21613), the right configuration depends on
the *observed* failure rate and comm/compute ratio, which this framework
already measures live; and per *Training LLMs with Fault Tolerant HSDP
on 100,000 GPUs* (arxiv 2602.00277), jobs at scale move through distinct
regimes — stable, churning, degraded — that no single static policy
serves well.

This module bundles the knobs into a hot-swappable :class:`FTPolicy`,
ranks them on an escalation :data:`LADDER` (performance-first when
stable, robustness-first under churn), and drives switches from a
:class:`PolicyController` — a windowed failure-rate estimator with
hysteresis and a cooldown so the controller cannot flap. The Manager
applies switches only **between steps, at the commit boundary**, where
every existing invariant already synchronizes (:class:`PolicySwitch`,
a commit-boundary feature; ``Manager.set_policy`` delegates), and
refuses them mid-heal under the boundary's one refusal rule.

Cross-group lockstep (the part a naive per-group controller gets wrong):
wire-format and mode knobs must change on every replica group at the
SAME boundary or the ring collectives skew. Only the quorum's
participating rank 0 *decides*; it publishes ``{step}:{rung}:{reason}``
under a fixed key on the quorum store every boundary, and every group
adopts on read — the ring collective between consecutive boundaries
orders each publication before every group's next read, bounding
adoption skew to one boundary. Healers adopt the donor's policy with
the rest of the manager metadata (it rides ``Manager.state_dict``), and
any residual skew (a publish racing a same-boundary read, a store read
lost to chaos) is *detected*, not silently folded: the wire ring's
per-op preamble (``backends/host.py``) turns mismatched formats into a
``CommunicatorError``, which aborts the step cleanly and re-syncs at
the next boundary.
"""

from __future__ import annotations

import logging
import time
from collections import deque
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Optional, Tuple

from torchft_tpu.boundary import Boundary, BoundaryFeature

logger = logging.getLogger(__name__)

# Wire-rung codes, numeric so a policy serializes into the manager
# metadata state dict (which heals and durable checkpoints carry) as
# plain ints — no string leaves for the pytree wire format to trip on.
WIRE_F32 = 0    # exact: no wire compression
WIRE_BF16 = 1   # bf16 wire dtype end-to-end (PR 2's ladder rung)
WIRE_INT8 = 2   # int8 + error-feedback (this PR's new rung)

_WIRE_NAMES = {WIRE_F32: "f32", WIRE_BF16: "bf16", WIRE_INT8: "int8"}


@dataclass(frozen=True)
class FTPolicy:
    """One hot-swappable bundle of fault-tolerance knobs.

    Every field maps onto a Manager/trainer knob that PRs 1-8 introduced
    statically:

    - ``overlap_steps``: the cross-step deferred-commit engine
      (docs/design/overlap.md). Escalation disables it first — stale
      in-flight grads are pure loss when aborts are frequent.
    - ``wire``: the wire-compression rung (:data:`WIRE_F32` /
      :data:`WIRE_BF16` / :data:`WIRE_INT8`). Narrower wire = fewer ring
      bytes = fewer transport ops a fault can land on per collective.
    - ``diloco`` + ``sync_every``: DiLoCo mode — cross-group traffic
      only every ``sync_every`` inner steps (local_sgd.py), the deepest
      rung: 1/sync_every the failure exposure per batch.
    - ``ckpt_every``: durable-checkpoint cadence in committed steps,
      consulted by trainers/drivers via ``Manager.policy().ckpt_every``
      (the Manager never initiates saves itself). Shortening it is the
      cheapest escalation: bounded loss on the next correlated failure.
    """

    name: str
    overlap_steps: int = 0
    wire: int = WIRE_F32
    diloco: bool = False
    sync_every: int = 16
    ckpt_every: int = 64

    def __post_init__(self) -> None:
        if self.overlap_steps not in (0, 1):
            raise ValueError(
                f"overlap_steps must be 0 or 1, got {self.overlap_steps!r}")
        if self.wire not in _WIRE_NAMES:
            raise ValueError(f"unknown wire rung {self.wire!r} "
                             f"(valid: {sorted(_WIRE_NAMES)})")
        if self.sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got "
                             f"{self.sync_every!r}")
        if self.ckpt_every < 1:
            raise ValueError(f"ckpt_every must be >= 1, got "
                             f"{self.ckpt_every!r}")
        if self.diloco and self.overlap_steps:
            raise ValueError("diloco and overlap_steps are mutually "
                             "exclusive (DiLoCo already defers commits "
                             "to outer rounds)")

    def wire_name(self) -> str:
        return _WIRE_NAMES[self.wire]

    def wire_dtype(self) -> Optional[Any]:
        """The ``allreduce_wire_dtype`` this rung maps to for the
        schedule/pack layer: bf16 for the bf16 rung, ``None`` otherwise
        (the int8 rung transfers D2H in full precision and quantizes
        host-side, where the error-feedback residual lives — see
        ``Manager._quantize_chunks``)."""
        if self.wire == WIRE_BF16:
            import jax.numpy as jnp

            return jnp.bfloat16
        return None

    def to_state(self) -> Dict[str, int]:
        """Numeric encoding for the manager metadata state dict (rides
        heals and durable checkpoints, so a healer/cold-start adopts the
        job's current policy — name resolved back via the ladder or
        synthesized)."""
        return {
            "policy_overlap": int(self.overlap_steps),
            "policy_wire": int(self.wire),
            "policy_diloco": int(self.diloco),
            "policy_sync_every": int(self.sync_every),
            "policy_ckpt_every": int(self.ckpt_every),
        }

    @staticmethod
    def from_state(state: Dict[str, Any],
                   ladder: Tuple["FTPolicy", ...] = ()) -> "FTPolicy":
        """Inverse of :meth:`to_state`; matches a ladder entry by knobs
        when possible so the adopted policy keeps its canonical name."""
        p = FTPolicy(
            name="adopted",
            overlap_steps=int(state.get("policy_overlap", 0)),
            wire=int(state.get("policy_wire", WIRE_F32)),
            diloco=bool(int(state.get("policy_diloco", 0))),
            sync_every=int(state.get("policy_sync_every", 16)),
            ckpt_every=int(state.get("policy_ckpt_every", 64)),
        )
        for cand in ladder:
            if cand.knobs() == p.knobs():
                return cand
        return replace(p, name=f"adopted-{p.describe()}")

    def knobs(self) -> tuple:
        """The identity that matters for lockstep: everything but the
        display name."""
        return (self.overlap_steps, self.wire, self.diloco,
                self.sync_every, self.ckpt_every)

    def describe(self) -> str:
        mode = ("diloco" if self.diloco
                else "overlap" if self.overlap_steps else "sync")
        return f"{mode}-{self.wire_name()}"


def from_knobs(overlap_steps: int = 0, wire_dtype: Optional[Any] = None,
               name: Optional[str] = None) -> FTPolicy:
    """Synthesize a policy from the legacy Manager constructor knobs, so
    every Manager — policy-aware or not — reports a coherent
    ``policy_name`` and serves one to healers."""
    import numpy as np

    wire = WIRE_F32
    if wire_dtype is not None:
        wire = (WIRE_BF16 if np.dtype(wire_dtype).itemsize == 2
                else WIRE_F32)
    p = FTPolicy(name="fixed", overlap_steps=overlap_steps, wire=wire)
    return replace(p, name=name or f"fixed-{p.describe()}")


# The default escalation ladder, performance-first at rung 0 and one
# robustness trade per rung (ISSUE 10's escalation order): shorten the
# durable-checkpoint cadence -> disable cross-step overlap (stale
# in-flight grads are pure loss when aborts are frequent) -> descend the
# wire ladder f32 -> bf16 -> int8+EF (fewer bytes = fewer transport ops
# per collective for faults to land on) -> drop to DiLoCo (cross-group
# traffic only every sync_every steps). Relaxation walks back one rung
# per quiet hysteresis window.
LADDER: Tuple[FTPolicy, ...] = (
    FTPolicy("overlap-bf16", overlap_steps=1, wire=WIRE_BF16,
             ckpt_every=64),
    FTPolicy("overlap-bf16-ckpt8", overlap_steps=1, wire=WIRE_BF16,
             ckpt_every=8),
    FTPolicy("sync-f32", wire=WIRE_F32, ckpt_every=8),
    FTPolicy("sync-bf16", wire=WIRE_BF16, ckpt_every=8),
    FTPolicy("sync-int8", wire=WIRE_INT8, ckpt_every=8),
    FTPolicy("diloco-8", diloco=True, sync_every=8, ckpt_every=8),
)

# Named fixed policies (the A/B baselines the adaptive soak must beat,
# plus the ladder rungs by name).
POLICIES: Dict[str, FTPolicy] = {p.name: p for p in LADDER}
POLICIES["overlap-f32"] = FTPolicy("overlap-f32", overlap_steps=1)
POLICIES["diloco-16"] = FTPolicy("diloco-16", diloco=True, sync_every=16,
                                 ckpt_every=8)


@dataclass
class PolicySignals:
    """The live inputs one controller decision was made from (stamped
    into ``policy_switch`` events and the metrics gauges)."""

    failures_in_window: int = 0
    window: int = 0
    failure_rate: float = 0.0   # failures per commit boundary, windowed
    comm_frac: float = 0.0      # allreduce wall / step wall, windowed
    quiet_boundaries: int = 0   # consecutive clean boundaries
    # Live churn regime (docs/design/churn.md): ring reconfigures in the
    # trailing minute, fed by the Manager's reconfigure-timestamp window
    # — under spot churn this is the failure REGIME signal (groups are
    # coming and going) even when every individual boundary commits.
    churn_rate: float = 0.0
    # Fleet health hints (docs/design/fleet_health.md), echoed by the
    # lighthouse on every quorum round: the FLEET's p95 step wall and
    # THIS group's robust-z straggler score. A controller previously saw
    # only its own group's failure rate/churn; these give it the fleet's
    # regime (Chameleon, arxiv 2508.21613: real-time policy selection is
    # only as good as its signals). Both 0.0 without fleet telemetry.
    fleet_p95_ms: float = 0.0
    straggler_score: float = 0.0

    def as_dict(self) -> Dict[str, float]:
        return {
            "failures_in_window": float(self.failures_in_window),
            "window": float(self.window),
            "failure_rate": round(self.failure_rate, 4),
            "comm_frac": round(self.comm_frac, 4),
            "quiet_boundaries": float(self.quiet_boundaries),
            "churn_rate": round(self.churn_rate, 4),
            "fleet_p95_ms": round(self.fleet_p95_ms, 3),
            "straggler_score": round(self.straggler_score, 4),
        }


class PolicyController:
    """Windowed failure-rate estimator + hysteresis ladder walker.

    Pure decision logic — no Manager import, no IO — so it unit-tests
    with scripted boundary sequences. One instance is attached per
    Manager (``Manager(policy_controller=...)``); only the quorum's
    participating rank 0 acts on its proposals (the others mirror the
    agreed rung via :meth:`sync_rung` when the Manager adopts a
    published switch).

    Signals per commit boundary (all already measured by PRs 1-8):

    - ``committed``: the commit vote's outcome. Aborts are the universal
      failure symptom — vote aborts cover latched comm errors, quorum
      failures, and chaos-injected resets alike.
    - ``reconfigured``: the communicator was rebuilt this step
      (membership change, donor death, latched-error recovery
      rendezvous, lighthouse redial fallout) — churn even when the step
      still committed.
    - ``comm_frac``: windowed allreduce-wall / step-wall ratio. Gates
      the DiLoCo rung: dropping to local SGD only pays when the job is
      comm-bound (``diloco_min_comm_frac``).

    Hysteresis: escalate one rung when >= ``escalate_failures`` of the
    last ``window`` boundaries failed; relax one rung after
    ``relax_after`` consecutive clean boundaries; never switch twice
    within ``cooldown`` boundaries, and the failure window resets on
    every switch — so the switch count is bounded by the number of
    regime changes, not the number of faults (the no-flap guarantee the
    soak asserts).
    """

    def __init__(self, ladder: Tuple[FTPolicy, ...] = LADDER,
                 window: int = 8, escalate_failures: int = 2,
                 relax_after: int = 12, cooldown: int = 4,
                 diloco_min_comm_frac: float = 0.0) -> None:
        if len(ladder) < 2:
            raise ValueError("a policy ladder needs >= 2 rungs")
        self.ladder = tuple(ladder)
        self.window = int(window)
        self.escalate_failures = int(escalate_failures)
        self.relax_after = int(relax_after)
        self.cooldown = int(cooldown)
        self.diloco_min_comm_frac = float(diloco_min_comm_frac)

        self.rung = 0
        self._recent: deque = deque(maxlen=self.window)
        self._quiet = 0
        self._since_switch = self.cooldown  # allow an immediate first move
        self._comm_ema = 0.0
        self.last_signals = PolicySignals()

    # ------------------------------------------------------------- state

    def policy(self) -> FTPolicy:
        return self.ladder[self.rung]

    def rung_of(self, policy: FTPolicy) -> Optional[int]:
        for i, p in enumerate(self.ladder):
            if p.knobs() == policy.knobs():
                return i
        return None

    def sync_rung(self, rung: int) -> None:
        """Adopt an externally-agreed rung (a published switch, a healed
        policy): counters reset exactly as if this controller had
        switched itself, so follower groups keep the same hysteresis
        clock as the decider."""
        rung = max(0, min(int(rung), len(self.ladder) - 1))
        if rung != self.rung:
            self.rung = rung
            self._recent.clear()
            self._quiet = 0
            self._since_switch = 0

    # ---------------------------------------------------------- decision

    def note_boundary(self, committed: bool, reconfigured: bool = False,
                      comm_frac: float = 0.0, churn_rate: float = 0.0,
                      fleet_p95_ms: float = 0.0,
                      straggler_score: float = 0.0
                      ) -> Optional[Tuple[int, str, PolicySignals]]:
        """Record one commit boundary; return ``(target_rung, reason,
        signals)`` when the ladder should move, else ``None``. The
        caller (the deciding Manager) applies/publishes the move; this
        method never mutates ``rung`` itself — :meth:`sync_rung` does,
        when the move actually lands."""
        failure = (not committed) or reconfigured
        self._recent.append(1 if failure else 0)
        self._quiet = 0 if failure else self._quiet + 1
        self._since_switch += 1
        # EMA smooths the per-boundary comm ratio (a single slow quorum
        # would otherwise gate/ungate the DiLoCo rung at random).
        self._comm_ema = (0.7 * self._comm_ema + 0.3 * max(comm_frac, 0.0)
                          if self._comm_ema else max(comm_frac, 0.0))
        fails = int(sum(self._recent))
        sig = PolicySignals(
            failures_in_window=fails, window=len(self._recent),
            failure_rate=fails / max(len(self._recent), 1),
            comm_frac=self._comm_ema, quiet_boundaries=self._quiet,
            churn_rate=max(churn_rate, 0.0),
            fleet_p95_ms=max(fleet_p95_ms, 0.0),
            straggler_score=float(straggler_score))
        self.last_signals = sig
        if self._since_switch < self.cooldown:
            return None
        if fails >= self.escalate_failures \
                and self.rung < len(self.ladder) - 1:
            target = self.rung + 1
            if self.ladder[target].diloco \
                    and self._comm_ema < self.diloco_min_comm_frac:
                return None  # DiLoCo only pays when comm-bound
            return (target,
                    f"escalate: {fails}/{len(self._recent)} boundaries "
                    "failed in window", sig)
        if self._quiet >= self.relax_after and self.rung > 0:
            return (self.rung - 1,
                    f"relax: {self._quiet} quiet boundaries", sig)
        return None


# The quorum-store key the policy decision rides on
# (:meth:`~torchft_tpu.boundary.Boundary.publish`).
_POLICY_KEY: str = "torchft/policy"


class PolicySwitch(BoundaryFeature):
    """The policy in force and its switches, as a commit-boundary
    feature (docs/design/adaptive_policy.md). Switches land ONLY at the
    commit boundary — after ``prepare_commit`` drained every in-flight
    collective and applied any staged heal, before the next step's
    quorum — and in cross-group lockstep, as the module docstring
    describes: :meth:`pre_vote` is the decider's half, :meth:`post_vote`
    every group's; healers adopt the donor's policy with the manager
    metadata (:meth:`adopt_state`).

    An explicit ``policy`` wins over the legacy knob args; with only a
    controller, its ladder's rung 0 is the starting policy; with
    neither, a fixed policy is synthesized from ``legacy_knobs``
    (``overlap_steps``, ``wire_dtype``) so every Manager reports a
    coherent policy_name (and stays switchable). ``aware`` gates the
    parts with cross-version surface (state-dict policy fields, the
    "dynamic" rendezvous fingerprint): only managers explicitly opted
    into hot-swapping carry them.

    Built from the boundary, ``install_knobs(policy)`` (the exchange's
    wire) and ``metrics()`` (``Manager.metrics``: the counters the
    controller's signals derive from)."""

    # The ladder rung in force (gauge; -1 = not on the attached
    # controller's ladder / no controller), applied switches, refusals
    # at an unclean boundary, switches deferred because a heal was in
    # flight somewhere in the quorum, and the controller's windowed
    # failure-rate estimate (gauge). policy_name / policy_last_reason
    # are strings and live in metrics_info().
    METRICS = {
        "policy_current": -1.0,
        "policy_switches_total": 0.0,
        "policy_switch_refusals": 0.0,
        "policy_switch_deferrals": 0.0,
        "failure_rate": 0.0,
    }

    def __init__(self, boundary: Boundary, policy: Optional[FTPolicy],
                 controller: Optional[PolicyController],
                 legacy_knobs: tuple,
                 install_knobs: Callable[[FTPolicy], None],
                 metrics: Callable[[], Dict[str, float]]) -> None:
        self._b = boundary
        self.controller = controller
        self.aware = policy is not None or controller is not None
        if policy is None:
            policy = (controller.policy() if controller is not None
                      else from_knobs(*legacy_knobs))
        self.policy = policy
        self._install_knobs = install_knobs
        self._metrics = metrics
        if controller is not None:
            rung = controller.rung_of(policy)
            if rung is not None:
                controller.sync_rung(rung)
            self.METRICS = dict(self.METRICS,
                                policy_current=float(controller.rung))
        # Decider-side staged proposal + latest published decision
        # (step, rung, reason, signals), and the per-boundary counter
        # snapshot the comm/compute signal derives from.
        self._pending: Optional[tuple] = None
        self._published: Optional[tuple] = None
        self.last_reason = "init"
        self._prev_counters: Optional[Dict[str, float]] = None

    def _ladder(self) -> Tuple[FTPolicy, ...]:
        return self.controller.ladder if self.controller else LADDER

    def state(self) -> Dict[str, int]:
        """The policy's numeric knob encoding for the manager metadata
        (policy-aware managers only), so a healer or cold start adopts
        the JOB's current policy — a restarted group defaulting to rung
        0 while the fleet runs int8 would otherwise skew the wire
        format for its first participating step."""
        return self.policy.to_state() if self.aware else {}

    def adopt_state(self, state_dict: Dict[str, Any]) -> None:
        """Adopt the donor's / snapshot's policy (policy-aware managers
        only; legacy state dicts simply lack the keys). Runs on the
        quorum thread BEFORE this step's collectives join the quorum
        future, so a healer's zero contribution is already in the
        fleet's wire format."""
        if self.aware and "policy_wire" in state_dict:
            p = FTPolicy.from_state(state_dict, ladder=self._ladder())
            if p.knobs() != self.policy.knobs():
                self.install(p, reason="adopted with restored state",
                             event="policy_adopt")

    def install(self, p: FTPolicy, reason: str, event: str,
                signals: Optional[Any] = None) -> None:
        """Unconditional install (callers hold the safety checks):
        knobs (the exchange flushes its residuals on a wire-rung
        change), controller rung sync, counters, and the
        ``policy_switch``/``policy_adopt`` event with
        from/to/reason/signals."""
        old = self.policy
        old_rung = (self.controller.rung_of(old)
                    if self.controller is not None else None)
        self.policy = p
        self._install_knobs(p)
        self._b.tracer.set_context(policy_name=p.name)
        rung = -1.0
        if self.controller is not None:
            r = self.controller.rung_of(p)
            if r is not None:
                self.controller.sync_rung(r)
                rung = float(r)
        self.last_reason = str(reason)
        self._b.record(policy_switches_total=1)
        self._b.gauge(policy_current=rung)
        sig = {}
        if signals is not None:
            sig = {"signals": signals.as_dict()
                   if hasattr(signals, "as_dict") else signals}
        v = self._b.view()
        self._b.log_event(event=event, step=v.step, reason=reason,
                          **{"from": old.name, "to": p.name}, **sig)
        if old_rung is not None and rung > old_rung:
            # An escalation means the failure regime just got worse —
            # exactly the moment a postmortem wants the span ring and
            # event window that DROVE the controller's decision.
            self._b.flight_dump("policy_escalation",
                                **{"from": old.name, "to": p.name,
                                   "why": reason})
        logger.info("%s policy %s -> %s at step %d (%s)",
                    v.replica_id, old.name, p.name, v.step, reason)

    def set(self, p: FTPolicy, reason: str = "manual",
            signals: Optional[Any] = None, _force: bool = False) -> bool:
        """Switch the FT policy at the current commit boundary.

        Refused — returning False, counting ``policy_switch_refusals``
        and stamping a ``policy_switch_refused`` event — at a boundary
        that is not clean (:meth:`~torchft_tpu.boundary.Boundary.
        blocked`; wire/overlap transitions drain deferred state first —
        flush via ``DelayedOptimizer.flush()``; the coordinated
        adoption forces past a latched error). Callers retry at the
        next boundary; the controller hook does so automatically."""
        if p.knobs() == self.policy.knobs():
            return True
        blocked = self._b.blocked(ignore_errored=_force)
        if blocked:
            v = self._b.view()
            self._b.record(policy_switch_refusals=1)
            self._b.log_event(event="policy_switch_refused",
                              step=v.step, to=p.name, reason=reason,
                              why=",".join(blocked))
            logger.warning("%s: policy switch to %s refused (%s); retry "
                           "at the next boundary", v.replica_id,
                           p.name, ",".join(blocked))
            return False
        self.install(p, reason, "policy_switch", signals)
        return True

    def pre_vote(self) -> None:
        """Decider half: promote the staged proposal to the published
        decision (unless a heal is in flight anywhere in the quorum —
        deferred, retried next boundary) and refresh the decision key.
        The key always carries the CURRENT agreed rung, so a group that
        missed a boundary (failed read, late join) catches up at its
        next one. Adoption is immediate-on-read rather than gated on a
        future step: commit-step clocks freeze under exactly the churn
        that makes escalation urgent."""
        if self.controller is None or not self._b.decider():
            return
        _addr, replica_world, max_world, _c = self._b.coordination()
        step = self._b.view().step
        if self._pending is not None:
            if max_world < replica_world:
                # A quorum member is healing: a switch would race its
                # restore — refused, retried next boundary.
                self._b.record(policy_switch_deferrals=1)
                self._b.log_event(event="policy_switch_deferred",
                                  step=step, to=self._pending[0],
                                  why="heal in flight")
            else:
                rung, reason, sig = self._pending
                self._pending = None
                self._published = (step, rung, reason, sig)
        pub = self._published
        if pub is None:
            value = f"{step}:{self.controller.rung}:init"
        else:
            value = (f"{pub[0]}:{pub[1]}:"
                     f"{str(pub[2]).replace(':', ';')}")
        self._b.publish(_POLICY_KEY, value)

    def post_vote(self, decision: bool) -> None:
        """All-groups half: adopt the published rung when it differs
        from the one in force (a missed switch is DETECTED by the
        wire-op preamble — abort, not garbage — and repaired at the
        next read), then feed this boundary's outcome to the controller
        (failure window, comm/compute ratio) and stage any new proposal
        for the decider's next pre-vote."""
        if self.controller is None:
            return
        ladder = self._ladder()
        if self._b.coordination()[3]:
            raw = self._b.read(_POLICY_KEY)
            if raw:
                _seq, _, rest = raw.partition(":")
                rung_s, _, reason = rest.partition(":")
                try:
                    rung = int(rung_s)
                except ValueError:
                    rung = -1
                if 0 <= rung < len(ladder):
                    self.set(ladder[rung],
                             reason=f"coordinated: {reason}", _force=True)
        else:
            pub = self._published
            if pub is not None and 0 <= pub[1] < len(ladder):
                if self.set(ladder[pub[1]], reason=pub[2],
                            signals=pub[3], _force=True):
                    self._published = None

        now = time.monotonic()
        mx = self._metrics()
        rc, ar = mx["reconfigure_count"], mx["allreduce_ms_total"]
        prev = self._prev_counters
        reconfigured = prev is not None and rc > prev["rc"]
        comm_frac = 0.0
        if prev is not None:
            wall_ms = (now - prev["t"]) * 1e3
            if wall_ms > 0:
                comm_frac = min(1.0, max(0.0, ar - prev["ar"]) / wall_ms)
        self._prev_counters = {"rc": rc, "ar": ar, "t": now}
        proposal = self.controller.note_boundary(
            decision, reconfigured=reconfigured, comm_frac=comm_frac,
            churn_rate=mx["reconfigures_per_min"],
            fleet_p95_ms=mx["fleet_p95_ms"],
            straggler_score=mx["straggler_score"])
        self._b.gauge(
            failure_rate=self.controller.last_signals.failure_rate)
        if self._b.decider() and proposal is not None \
                and self._pending is None:
            self._pending = proposal


class AdaptiveTrainer:
    """Mode-switching training driver: obeys ``manager.policy()`` at
    every commit boundary, running the sync, cross-step-overlap, or
    DiLoCo loop that the policy in force calls for — the glue that makes
    a controller-driven policy switch an actual behavior change instead
    of a flag flip.

    Transition safety (docs/design/adaptive_policy.md has the full
    table): switches only land at commit boundaries, where no collective
    is in flight — overlap's deferred step was settled by the boundary
    itself, and DiLoCo-mode boundaries only occur at outer rounds, so
    DiLoCo transitions land on outer-round boundaries by construction.
    Entering DiLoCo re-anchors at the current (lockstep) params;
    entering overlap simply starts staging at the next step; leaving
    overlap stops staging after the settle that observed the switch.

    The state dict keeps a constant structure across modes (params,
    inner opt state, DiLoCo anchor + outer state) so heals between
    groups in any mode pair restore cleanly.
    """

    def __init__(self, loss_fn: Callable[[Any, Any], Any], tx: Any,
                 params: Any,
                 manager_factory: Callable[..., Any],
                 outer_tx: Optional[Any] = None,
                 jit: bool = True) -> None:
        import jax
        import optax

        from torchft_tpu import tracing
        from torchft_tpu.local_sgd import diloco_outer_optimizer
        from torchft_tpu.optim import DelayedOptimizer, FTOptimizer

        self.params = params
        self.opt_state = tx.init(params)
        self.anchor = params  # DiLoCo anchor; re-anchored on mode entry
        self._outer_tx = outer_tx or diloco_outer_optimizer()
        self.outer_state = self._outer_tx.init(params)
        self.local_steps = 0  # inner steps since the last outer round
        self.committed_batches = 0

        def fwd_bwd(p, batch):
            (loss, counts), grads = jax.value_and_grad(
                tracing.collect_counts(loss_fn), has_aux=True)(p, batch)
            return loss, grads, counts

        def delta(anchor, p):
            return jax.tree_util.tree_map(lambda a, b: a - b, anchor, p)

        def outer_update(anchor, ostate, avg_delta):
            updates, ostate = self._outer_tx.update(avg_delta, ostate,
                                                    anchor)
            return optax.apply_updates(anchor, updates), ostate

        # The loss's counts (tracing.count_in_program) are the program's
        # last output; the host queues them and adds what has finished.
        self._fwd_bwd = tracing.deferring_counts(
            jax.jit(fwd_bwd) if jit else fwd_bwd)
        self._delta = jax.jit(delta) if jit else delta
        self._outer_update = (jax.jit(outer_update) if jit
                              else outer_update)

        self.manager = manager_factory(self.load_state_dict,
                                       self.state_dict)
        self._ft = FTOptimizer(self.manager, tx, jit=jit)
        self._dopt = DelayedOptimizer(self.manager, tx, jit=jit)
        self._mode = self._mode_of(self._current_policy())
        self._diloco_sync_every = self._current_policy().sync_every

    # ------------------------------------------------------------- modes

    def _current_policy(self) -> FTPolicy:
        pol = getattr(self.manager, "policy", None)
        p = pol() if callable(pol) else None
        return p if p is not None else FTPolicy("sync-f32")

    @staticmethod
    def _mode_of(p: FTPolicy) -> str:
        if p.diloco:
            return "diloco"
        return "overlap" if p.overlap_steps else "sync"

    def mode(self) -> str:
        return self._mode

    def _refresh_mode(self) -> None:
        """Commit-boundary hook: pick up a policy switch (the Manager
        applied it inside ``should_commit``). Runs with nothing in
        flight, which is exactly what makes each transition safe."""
        new = self._mode_of(self._current_policy())
        if new == self._mode:
            return
        logger.info("AdaptiveTrainer mode %s -> %s (policy %s)",
                    self._mode, new, self._current_policy().name)
        if new == "diloco":
            # Re-anchor at the current committed params: lockstep across
            # groups because params are. The cadence is captured at
            # entry: a later switch request must not shift the CURRENT
            # cycle's round boundary out from under the fleet.
            self.anchor = self.params
            self.local_steps = 0
            self._diloco_sync_every = self._current_policy().sync_every
        self._mode = new

    # -------------------------------------------------------------- step

    def train_step(self, batch: Any) -> Tuple[Any, Optional[bool]]:
        """One training step under the policy in force. Returns
        ``(loss, committed)`` — ``committed`` is ``None`` on DiLoCo
        inner steps (no boundary ran) and, in overlap mode, reports the
        PREVIOUS step's deferred vote."""
        # Between steps with nothing in flight is itself a safe
        # boundary: pick up a policy applied via set_policy() outside
        # the controller hook (manual operator switches). DiLoCo mode
        # stays sticky mid-cycle — its transitions land only on outer
        # rounds.
        if self._mode == "sync" or (self._mode == "overlap"
                                    and not self._dopt.pending()):
            self._refresh_mode()
        if self._mode == "diloco":
            return self._step_diloco(batch)
        if self._mode == "overlap":
            return self._step_overlap(batch)
        return self._step_sync(batch)

    def _step_sync(self, batch: Any) -> Tuple[Any, bool]:
        m = self.manager
        m.step()
        loss, grads = self._fwd_bwd(self.params, batch)
        avg = m.allreduce(grads).result()
        committed = self._ft.apply(self, avg)
        if committed:
            self.committed_batches += 1
        self._refresh_mode()
        return loss, committed

    def _step_overlap(self, batch: Any) -> Tuple[Any, Optional[bool]]:
        m = self.manager
        # Dispatch this step's grads FIRST (async under jit) so the
        # staged allreduce drains under them — the overlap win.
        loss, grads = self._fwd_bwd(self.params, batch)
        committed_prev: Optional[bool] = None
        if self._dopt.pending():
            committed_prev = self._dopt.settle()
            if committed_prev:
                self.committed_batches += 1
            self._refresh_mode()
            if self._mode != "overlap":
                # The settle's boundary switched us out of overlap: the
                # just-computed grads were evaluated at pre-settle
                # params; every group discards them identically (policy
                # switches are lockstep), keeping params lockstep.
                return loss, committed_prev
        m.step()
        fut = m.allreduce(grads)
        self._dopt.stage(self, fut)
        return loss, committed_prev

    def _step_diloco(self, batch: Any) -> Tuple[Any, Optional[bool]]:
        import optax

        loss, grads = self._fwd_bwd(self.params, batch)
        updates, self.opt_state = self._ft.tx.update(
            grads, self.opt_state, self.params)
        self.params = optax.apply_updates(self.params, updates)
        self.local_steps += 1
        committed: Optional[bool] = None
        if self.local_steps >= self._diloco_sync_every:
            committed = self._outer_round()
        return loss, committed

    def _outer_round(self) -> bool:
        """DiLoCo outer round: the FT protocol at round granularity —
        and, because this is the only place DiLoCo mode votes, the only
        boundary where a policy switch can land (outer-round-boundary
        transitions by construction)."""
        m = self.manager
        sync_every = self._diloco_sync_every
        m.step()
        pseudo = self._delta(self.anchor, self.params)
        avg = m.allreduce(pseudo).result()
        committed = m.should_commit()  # may heal this holder in-place
        if committed:
            self.anchor, self.outer_state = self._outer_update(
                self.anchor, self.outer_state, avg)
            self.params = self.anchor
            # A committed outer round lands sync_every inner batches of
            # globally-agreed progress.
            self.committed_batches += sync_every
            self.local_steps = 0
        self._refresh_mode()
        if self._mode == "diloco":
            # Round boundaries are the one safe point to re-tune the
            # cadence (the controller's adaptive sync_every) — the same
            # rule as DiLoCoTrainer.set_sync_every.
            self._diloco_sync_every = self._current_policy().sync_every
        return committed

    def flush(self) -> Optional[bool]:
        """Settle any in-flight deferred step (end of run / before a
        durable save)."""
        out = self._dopt.flush()
        if out:
            self.committed_batches += 1
        return out

    # ------------------------------------------------- state (for heals)

    def state_dict(self) -> Any:
        return {
            "params": self.params,
            "opt_state": self.opt_state,
            "anchor": self.anchor,
            "outer_state": self.outer_state,
        }

    def load_state_dict(self, state: Any) -> None:
        self.params = state["params"]
        self.opt_state = state["opt_state"]
        self.anchor = state["anchor"]
        self.outer_state = state["outer_state"]

    def shutdown(self) -> None:
        if self._dopt.pending():
            self.flush()
        self.manager.shutdown()


class PhasedChaos:
    """Wall-clock phase driver for a soak's chaos intensity
    (stable -> storm -> stable): ``phases`` is ``[(duration_sec,
    intensity), ...]``; :meth:`run` walks them against an installed
    :class:`~torchft_tpu.chaos.ChaosSchedule` via ``set_intensity``,
    either inline (call :meth:`tick` from the driving loop) or from a
    daemon thread (:meth:`start`)."""

    def __init__(self, schedule: Any,
                 phases: Tuple[Tuple[float, float], ...]) -> None:
        self.schedule = schedule
        self.phases = tuple(phases)
        self._t0 = time.monotonic()
        self._stop = False

    def total_seconds(self) -> float:
        return sum(d for d, _ in self.phases)

    def tick(self) -> float:
        """Apply the intensity of the phase the wall clock is in;
        returns it (the terminal phase's intensity persists after the
        schedule runs out)."""
        t = time.monotonic() - self._t0
        intensity = self.phases[-1][1]
        acc = 0.0
        for dur, level in self.phases:
            acc += dur
            if t < acc:
                intensity = level
                break
        self.schedule.set_intensity(intensity)
        return intensity

    def start(self) -> None:
        import threading

        def loop() -> None:
            while not self._stop:
                self.tick()
                time.sleep(0.05)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="chaos-phases")
        self._thread.start()

    def stop(self) -> None:
        self._stop = True
