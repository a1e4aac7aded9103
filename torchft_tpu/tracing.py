"""Per-step distributed tracing + flight recorder (the observability
tier, docs/design/observability.md).

Three layers, native-free like the serving tier (jax is imported only
for the profiler annotation below, and its absence is tolerated):

* :class:`Tracer` — a low-overhead span tracer. Every hot-path stage of
  the step protocol (the step thread's own partition: step_begin,
  dispatch, the waits, every commit-boundary hook, the update; quorum,
  per-bucket fetch dispatch/wait, ring ops, unpack/put, heal stripes per
  donor, durable saves, publishes) records a span: a
  ``time.monotonic_ns()`` start + duration tagged with the step-protocol
  coordinates (``replica_id/quorum_id/epoch/step/policy_name``) that
  make spans from different groups alignable, the recording ``thread``,
  an ``id`` and the ``parent`` open on that thread when it began (self
  time = duration less what the children cover). A span used as a
  context manager also enters a ``jax.profiler.TraceAnnotation`` of its
  stage, so a profiler capture of a live job holds the stages beside
  the device's operations. Spans live in a bounded per-step
  ring (last ``TORCHFT_TRACE_STEPS`` steps, default 64), so memory is
  O(steps x spans/step) forever. The run-total counters in
  ``Manager.metrics()`` answer "how much"; the spans answer "when, and
  overlapped with what" — the attribution layer the fetch-wall work
  and the churn soak need (the 100k-GPU HSDP paper's per-step
  telemetry, arxiv 2602.00277).

* :class:`FlightRecorder` — crash-time dumps. On vote abort, latched
  CommunicatorError, heal failover, policy escalation, and
  atexit-after-an-unhandled-exception, the span ring + event history +
  a metrics snapshot are written to ``TORCHFT_FLIGHT_DIR`` as one JSON
  file that Perfetto loads directly (``traceEvents`` + a ``torchft``
  sidecar object), so any incident is postmortem-able without a
  re-run.

* Exports — :func:`chrome_trace` renders the ring in Chrome
  trace-event format (one track per pipeline stage; served at
  ``GET /trace.json`` on the CheckpointServer),
  :func:`prometheus_text` renders a metrics snapshot in Prometheus
  text exposition (served at ``GET /metrics``), and
  :func:`merge_traces` aligns many groups' traces on
  ``(quorum_id, epoch, step)`` into one fleet timeline
  (``scripts/tracefleet.py``).

Tracing defaults ON. What it costs on the chip (PERF.md, PR 42: the
benchmark's ``mistral-7b.steady-1g`` cell on a TPU v5e, ten spans a
step, seven same-seed pairs against ``TORCHFT_TRACING=0``): a step of
337.07 ms with it against 337.02 ms without, the pairs' differences
-0.92 to +0.80 ms with no direction, so under what two runs of one
seed differ by. With two groups, about 500 spans a step a group
(PERF.md, PR 58: ``mistral-7b.steady-2g``, four same-seed pairs):
tokens/s -1.5, -0.6, -1.1, +1.1 % against ``TORCHFT_TRACING=0``: a
median of -0.9 % with three pairs of four one way, which four pairs do
not resolve from zero. ``TORCHFT_TRACING=0`` disables it process-wide,
turning every ``span()`` into a shared no-op.
"""

from __future__ import annotations

import atexit
import itertools
import json
import logging
import os
import re
import sys
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

logger: logging.Logger = logging.getLogger(__name__)

FLIGHT_FORMAT = "tft-flight-1"
TRACE_FORMAT = "tft-trace-1"

# Context tag keys every exported span carries (missing ones render as
# their neutral defaults): the cross-group alignment coordinates plus
# the policy attribution. Frozen by tests/test_metrics_schema.py.
CONTEXT_TAGS = ("replica_id", "quorum_id", "epoch", "step", "policy_name")

# Stable track (tid) order for the known pipeline stages — one Perfetto
# track per stage, in protocol order. Unknown stages append after.
STAGES = (
    "step_begin", "dispatch", "wait_quorum",
    "quorum", "reconfigure", "heal", "heal_stripe", "heal_adopt",
    "fetch_dispatch", "fetch_wait",
    "ring", "ring_preamble", "hier_intra", "hier_leader", "put",
    "exchange_wait",
    "overlap_drain", "drain", "pre_vote", "vote", "post_vote",
    "publish_status", "state_digest", "update", "ckpt_save", "publish",
    "heal_manifest", "heal_recv", "heal_verify", "heal_place",
)


def default_enabled() -> bool:
    """Process-wide tracing default: on unless ``TORCHFT_TRACING`` is
    ``0``/``false`` (the bench A/B and overhead-sensitive jobs opt
    out)."""
    return os.environ.get("TORCHFT_TRACING", "1").strip().lower() \
        not in ("0", "false")


def default_trace_steps() -> int:
    """Ring depth in steps (``TORCHFT_TRACE_STEPS``, default 64)."""
    try:
        return max(int(os.environ.get("TORCHFT_TRACE_STEPS", 64)), 1)
    except ValueError:
        return 64


# ------------------------------------------------------ program counters
#
# Totals counted by the jitted programs themselves (how many token-expert
# pairs a step routed, how many landed on held experts). Process-wide:
# every Manager of the process reports the same totals
# (``Manager.metrics()`` merges them). A count leaves its program as an
# ordinary output: while a program is traced under :func:`collect_counts`,
# :func:`count_in_program` appends its values to the collector open on the
# tracing thread, and the collecting function returns them beside its own
# result as one :class:`ProgramCounts` (a stacked vector for each kind of
# number; the keys are the host's from the trace on). The host queues that
# output at dispatch (:func:`defer_program_counts`) and adds it to the
# totals once the program has finished (:func:`settle_program_counts`), so
# no program this package builds holds a host callback: each takes jit's
# C++ dispatch path and jax's persistent compile cache. What is known when a program is traced
# needs no output at all: the head's loss adds
# ``head_loss_fused_traces_total`` (one each time its fused gradient rule is
# traced) and ``head_loss_chunks_traced_total`` (the chunks of each such
# loss; their ratio is the chunks a loss) on the host, then and there, and
# ``ops/flash_attention.py`` adds each traced kernel's grid steps and those
# a static mask skips (``flash_grid_steps_traced_total``,
# ``flash_skipped_steps_traced_total``) and which backward a call took
# (``flash_dq_resident_traces_total`` / ``flash_dq_split_traces_total``),
# and ``ops/gated_delta.py`` each traced sweep of its chunk recurrence and
# the sweep's grid steps (``gdn_kernel_traces_total``,
# ``gdn_kernel_grid_steps_traced_total``).

_program_counters: Dict[str, float] = {}
_program_counters_lock = threading.Lock()
# Per thread: the collectors open while it traces, innermost last.
_collectors = threading.local()
# Outputs of dispatched programs, not yet added to the totals.
_pending_counts: List["ProgramCounts"] = []
_counts_registered = False


class ProgramCounts:
    """What a collecting program returns for its counts: at most two small
    vectors, the whole numbers stacked as ``int32`` and the others as
    ``f32``; ``keys[i]`` names ``values[i]``'s elements in (sorted) order.
    A pytree whose leaves are the vectors and whose keys ride in the
    tree's structure: two output leaves and two transfers a step at most,
    whatever the number of counters, and the host knows the names from
    the trace on. The totals are summed on the host in f64.

    Why not one vector: joined, values from the routers (early in the
    forward) and from the loss (its end) make one operation depend on
    both, and the chip's compiler then scheduled a step differently
    enough that it no longer matched the same step written without counts
    bit for bit (``joyai-llm-flash``, PERF.md PR 43); side by side they
    leave the rest of the program as it was. Whole numbers also stay
    exact beyond 2**24."""

    __slots__ = ("keys", "values")

    def __init__(self, keys: Tuple[Tuple[str, ...], ...],
                 values: Tuple[Any, ...]) -> None:
        self.keys = keys
        self.values = values

    def __repr__(self) -> str:
        return f"ProgramCounts({self.keys!r}, {self.values!r})"

    def ready(self) -> bool:
        """Whether the program that made them has finished."""
        return all(getattr(v, "is_ready", lambda: True)()
                   for v in self.values)

    def totals(self) -> Dict[str, float]:
        """Host side: ``{key: value}``; waits for the program."""
        import numpy as np

        return {key: float(x) for names, vector in zip(self.keys, self.values)
                for key, x in zip(names, np.asarray(vector))}


def _register_counts() -> None:
    """Make :class:`ProgramCounts` a pytree, at the first use that needs
    jax (this module imports without it)."""
    global _counts_registered
    if _counts_registered:
        return
    from jax import tree_util

    with _program_counters_lock:
        if not _counts_registered:
            tree_util.register_pytree_node(
                ProgramCounts,
                lambda c: (c.values, c.keys),
                lambda keys, values: ProgramCounts(keys, tuple(values)))
            _counts_registered = True


def add_program_counters(**values: Any) -> None:
    """Host side: add ``values`` to the totals."""
    with _program_counters_lock:
        for key, value in values.items():
            _program_counters[key] = (_program_counters.get(key, 0.0)
                                      + float(value))


def count_in_program(**values: Any) -> None:
    """Inside a function traced under :func:`collect_counts`: hand
    ``values`` (traced scalars or Python numbers) to the open collector,
    which returns them from the program; the host adds them to the totals
    each time the program has run. The same key counted twice in one trace
    is summed. Outside a collector (a ``jax.jit(jax.value_and_grad(...))``
    written by hand) nothing is counted and nothing is raised.

    The values must be the collecting function's own: a count taken inside
    ``jax.checkpoint``, a ``lax.scan`` body or an inner ``jax.jit`` is a
    tracer of that region and has to be returned out of it first (as
    ``RoutedMoEMLP(return_stats=True)`` does for a rematerialised layer),
    or jax reports a leaked tracer when the collector stacks it."""
    stack = getattr(_collectors, "open", None)
    if not stack:
        return
    taken = stack[-1]
    for key, value in values.items():
        taken[key] = taken[key] + value if key in taken else value


def collect_counts(fn: Callable[..., Any],
                   has_aux: bool = False) -> Callable[..., Any]:
    """``fn'`` that runs ``fn`` under a collector and returns
    ``(fn(...), counts)``: ``counts`` is what :func:`count_in_program` was
    handed during the call as one :class:`ProgramCounts`, or ``None`` where
    nothing was counted (the program then has no output more than ``fn``'s).
    With ``has_aux`` (``fn`` returns ``(out, aux)``) it returns
    ``(out, (aux, counts))``. Either form is what
    ``jax.value_and_grad(..., has_aux=True)`` takes: wrap the function that
    is differentiated, so the collector opens inside the differentiation
    and the values are tracers of its level. The caller returns ``counts``
    from its jitted program and hands that output to
    :func:`defer_program_counts`."""
    _register_counts()

    def collecting(*args: Any, **kwargs: Any) -> Any:
        import jax
        import jax.numpy as jnp

        stack = getattr(_collectors, "open", None)
        if stack is None:
            stack = _collectors.open = []
        taken: Dict[str, Any] = {}
        stack.append(taken)
        try:
            out = fn(*args, **kwargs)
        finally:
            stack.pop()
        counts = None
        if taken:
            whole: Dict[str, Any] = {}
            other: Dict[str, Any] = {}
            for key in sorted(taken):
                value = jnp.asarray(taken[key])
                if (jnp.issubdtype(value.dtype, jnp.integer)
                        or value.dtype == jnp.bool_):
                    whole[key] = value.astype(jnp.int32)
                else:
                    other[key] = value.astype(jnp.float32)
            kinds = [kind for kind in (whole, other) if kind]
            counts = ProgramCounts(
                tuple(tuple(kind) for kind in kinds),
                tuple(jax.lax.stop_gradient(jnp.stack(list(kind.values())))
                      for kind in kinds))
        if has_aux:
            out, aux = out
            return out, (aux, counts)
        return out, counts

    return collecting


def defer_program_counts(counts: Optional[ProgramCounts]) -> None:
    """Host side, after dispatching a collecting program: queue its
    ``counts`` output (``None``, a program that counted nothing, is
    ignored). Nothing waits here: :func:`settle_program_counts` adds them
    once the program has finished. A result that is thrown away (a
    discarded speculative step) is simply not deferred."""
    if counts is None:
        return
    with _program_counters_lock:
        _pending_counts.append(counts)


def settle_program_counts(wait: bool = False) -> None:
    """Host side: add the queued counts of every program that has finished
    to the totals, and leave the others queued; with ``wait`` block for
    those too. Without ``wait`` it costs the caller no wait for the
    device; the trainers call it right after they enqueue the next
    program, so the read of what finished before runs under that
    program. The counts of a program that failed are
    dropped (the step's own error path reports the failure)."""
    if not _pending_counts:
        return
    with _program_counters_lock:
        queued = list(_pending_counts)
        del _pending_counts[:]
    later = []
    for counts in queued:
        if not wait and not counts.ready():
            later.append(counts)
            continue
        try:
            totals = counts.totals()
        except Exception:  # noqa: BLE001 - observability never fails a step
            logger.warning("a program's counts were dropped", exc_info=True)
            continue
        add_program_counters(**totals)
    if later:
        with _program_counters_lock:
            _pending_counts[0:0] = later


def deferring_counts(program: Callable[..., Any]) -> Callable[..., Any]:
    """Host side, for a loop that dispatches one collecting program a
    step: ``program`` (jitted or not) returns ``(..., counts)``; the
    wrapper queues the counts, adds what has finished meanwhile, and
    returns the rest."""

    def run(*args: Any) -> Any:
        *out, counts = program(*args)
        defer_program_counts(counts)
        settle_program_counts()
        return tuple(out)

    return run


# What building programs costs, process-wide: jax reports every lowering
# and backend compile (the last includes a read of the persistent compile
# cache, which it also reports alone) as a duration event, on whichever
# thread built the program. A steady step builds nothing and fires none.
# Neither event nests in itself or in the other, so the total is
# thread-seconds of building. jax's trace durations do nest (a jitted
# function traced inside another is inside its duration too) and are left
# out: the ``dispatch`` span's ``traced=True`` says which step traced, and
# its own stamps what that cost (``dispatch_traced_ms_total``). These are
# the host's clocks, not what a program counted, so they keep a dict of
# their own beside ``_program_counters``.
_BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "program_build_ms_total",
    "/jax/core/compile/backend_compile_duration": "program_build_ms_total",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "program_cache_read_ms_total",
}
_build_ms: Dict[str, float] = {}    # empty until the listener is there
_build_ms_lock = threading.Lock()


def _on_build_event(event: str, duration_secs: float, **_: Any) -> None:
    key = _BUILD_EVENTS.get(event)
    if key is not None:
        with _build_ms_lock:
            _build_ms[key] += duration_secs * 1e3


def watch_program_builds() -> None:
    """Register the one listener a process needs for
    ``program_build_ms_total`` / ``program_cache_read_ms_total`` (the first
    ``Manager`` calls it; later calls do nothing, and so does a process
    without jax)."""
    try:
        from jax import monitoring
    except ImportError:
        return
    with _build_ms_lock:
        if _build_ms:
            return
        _build_ms.update(dict.fromkeys(_BUILD_EVENTS.values(), 0.0))
    monitoring.register_event_duration_secs_listener(_on_build_event)


def program_build_ms() -> Dict[str, float]:
    """The process's ``program_build_ms_total`` and
    ``program_cache_read_ms_total`` (``Manager.metrics()`` merges them in);
    empty before :func:`watch_program_builds`."""
    with _build_ms_lock:
        return dict(_build_ms)


def program_counters() -> Dict[str, float]:
    """A snapshot of the totals, with every finished program's counts in
    (the queued outputs of programs still running are not waited for)."""
    settle_program_counts()
    with _program_counters_lock:
        return dict(_program_counters)


class _NoopSpan:
    """Shared do-nothing span for disabled tracers: ``span()`` on the
    hot path must cost one attribute read + one method call, nothing
    else."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        pass

    def set(self, **tags: Any) -> "_NoopSpan":
        return self


_NOOP_SPAN = _NoopSpan()


class _Span:
    """One in-flight span: started on construction, recorded into the
    tracer's ring on ``__exit__``. ``ctx`` is the tracer's copy-on-write
    context dict at start time (shared, never mutated), so capturing it
    is one reference, not a copy. ``thread`` is the recording thread's
    name and ``thread_id`` its ``threading.get_ident()`` (names repeat:
    pool threads share them), ``id`` the span's number in its tracer.
    Used as a context manager it also learns its ``parent`` (the id of
    the span open on the same thread when it was entered; a span closed
    by a bare ``__exit__`` has none) and sits in a profiler capture
    under its stage's name. After the exit ``t0_ns``, ``dur_ns`` and
    ``end_ns`` are the caller's to read (:meth:`Tracer.timed`)."""

    __slots__ = ("tracer", "stage", "tags", "ctx", "t0_ns", "dur_ns",
                 "thread", "thread_id", "id", "parent", "_stack",
                 "_annotation")

    def __init__(self, tracer: "Tracer", stage: str,
                 tags: Optional[Dict[str, Any]],
                 t0_ns: Optional[int] = None) -> None:
        self.tracer = tracer
        self.stage = stage
        self.tags = tags
        self.ctx = tracer._ctx
        self.thread = threading.current_thread().name
        self.thread_id = threading.get_ident()
        self.id = next(tracer._ids)
        self.parent: Optional[int] = None
        # The entering thread's stack of open spans, while this one is
        # on it.
        self._stack: Optional[List["_Span"]] = None
        self._annotation: Any = None
        self.t0_ns = time.monotonic_ns() if t0_ns is None else t0_ns
        self.dur_ns = -1  # open until __exit__

    @property
    def end_ns(self) -> int:
        return self.t0_ns + self.dur_ns

    def set(self, **tags: Any) -> "_Span":
        """Attach/overwrite tags mid-span (e.g. the vote's decision,
        the quorum's fast/slow classification — facts only known at the
        end)."""
        if self.tags is None:
            self.tags = {}
        self.tags.update(tags)
        return self

    def __enter__(self) -> "_Span":
        tracer = self.tracer
        stack = tracer._open_here()
        if stack:
            self.parent = stack[-1].id
        stack.append(self)
        self._stack = stack
        if tracer._annotate is not None:
            self._annotation = tracer._annotate(self.stage)
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        self.dur_ns = time.monotonic_ns() - self.t0_ns
        stack, self._stack = self._stack, None
        if stack is not None:
            if self._annotation is not None:
                self._annotation.__exit__(exc_type, exc, tb)
                self._annotation = None
            # Off the stack it was pushed on, wherever it sits there: a
            # span left out of order (a generator holding its ``with``,
            # an exit on another thread) must not stay behind as every
            # later span's parent.
            if stack and stack[-1] is self:
                stack.pop()
            else:
                try:
                    stack.remove(self)
                except ValueError:
                    pass
        if exc is not None:
            self.set(error=repr(exc))
        self.tracer._finish(self)

    def as_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "stage": self.stage,
            "t0_ns": self.t0_ns,
            "dur_ns": self.dur_ns,
            "thread": self.thread,
            "thread_id": self.thread_id,
            "id": self.id,
            "parent": self.parent,
        }
        d.update(self.ctx)
        if self.tags:
            d.update(self.tags)
        return d


class _Stopwatch:
    """What :meth:`Tracer.timed` hands out on a disabled tracer: the two
    stamps for the caller, and nothing recorded."""

    __slots__ = ("t0_ns", "dur_ns")

    def __init__(self, t0_ns: Optional[int]) -> None:
        self.t0_ns = time.monotonic_ns() if t0_ns is None else t0_ns
        self.dur_ns = -1

    @property
    def end_ns(self) -> int:
        return self.t0_ns + self.dur_ns

    def set(self, **tags: Any) -> "_Stopwatch":
        return self

    def __enter__(self) -> "_Stopwatch":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.dur_ns = time.monotonic_ns() - self.t0_ns


def _trace_annotation() -> Any:
    """``jax.profiler.TraceAnnotation``, or None where jax is absent."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class Tracer:
    """Bounded per-step span ring.

    Thread-safe: spans are recorded from the caller thread, the quorum
    thread, the comm worker, the put executor, and striped-heal fetch
    threads; the ring append is one short lock hold. Span START costs a
    ``monotonic_ns`` + one object allocation, entering it one push on
    its thread's stack of open spans and one (inert unless a profiler
    capture is running) ``TraceAnnotation``; a disabled tracer's
    ``span()`` returns a shared no-op and does none of this.

    Args:
        steps: ring depth in steps (default ``TORCHFT_TRACE_STEPS`` /
            64): spans whose context ``step`` falls more than this many
            distinct steps behind are evicted oldest-first.
        enabled: overrides the ``TORCHFT_TRACING`` default.
        max_spans_per_step: hard per-step bound (default 4096) so a
            pathological caller (per-leaf spans) degrades to counted
            drops, never unbounded memory.
    """

    def __init__(self, steps: Optional[int] = None,
                 enabled: Optional[bool] = None,
                 max_spans_per_step: int = 4096) -> None:
        self.enabled = (bool(enabled) if enabled is not None
                        else default_enabled())
        self._steps = (int(steps) if steps is not None
                       else default_trace_steps())
        self._steps = max(self._steps, 1)
        self._max_per_step = max(int(max_spans_per_step), 1)
        self._lock = threading.Lock()
        # step -> [span dict, ...], oldest step first. Keys are the
        # context step at span START (spans opened before the first
        # step() land under step 0/-1 and age out like any other).
        self._ring: "OrderedDict[Any, List[Dict[str, Any]]]" = \
            OrderedDict()
        # Copy-on-write context: set_context REPLACES the dict, so an
        # in-flight span's captured reference stays a consistent
        # snapshot without per-span copies.
        self._ctx: Dict[str, Any] = {
            "replica_id": "", "quorum_id": -1, "epoch": 0, "step": 0,
            "policy_name": "",
        }
        # Open spans (begin recorded, no end yet): exported as B events
        # with a synthesized E at dump time, so a dump taken mid-step
        # still shows what was in flight.
        self._open: Dict[int, _Span] = {}
        self._ids = itertools.count(1)
        # Per thread: the spans entered and not yet left, outermost
        # first (a span's parent is the innermost at its entry).
        self._stacks = threading.local()
        self._annotate = _trace_annotation() if self.enabled else None
        self.spans_total = 0
        self.spans_dropped = 0

    # ------------------------------------------------------------ record

    def set_context(self, **tags: Any) -> None:
        """Update the alignment context stamped on subsequent spans
        (copy-on-write; cheap, called at step/quorum boundaries).
        Maintained even when span recording is disabled: the flight
        recorder keys its per-(reason, step) dedup — and its filenames
        — on this context, and a disabled tracer must not collapse
        every later incident onto step 0."""
        with self._lock:
            ctx = dict(self._ctx)
            ctx.update(tags)
            self._ctx = ctx

    def context(self) -> Dict[str, Any]:
        return dict(self._ctx)

    def span(self, stage: str, **tags: Any) -> Any:
        """Context manager recording one span of ``stage``. Extra kwargs
        become span tags (bucket index, donor address, byte counts...).
        """
        if not self.enabled:
            return _NOOP_SPAN
        return self._start(stage, tags, None)

    def timed(self, stage: str, after: Any = None, **tags: Any) -> Any:
        """As :meth:`span`, for a caller that reads the stamps itself
        (``t0_ns``, ``dur_ns``, ``end_ns`` after the exit): the span is
        the caller's stopwatch too, so one set of clock reads serves the
        trace and the caller's own bookkeeping. A disabled tracer hands
        out a bare stopwatch: the two stamps, nothing recorded.
        ``after``: the span this one follows on its thread; it begins at
        that one's end, one clock read for the boundary between them."""
        t0_ns = None if after is None else after.end_ns
        if not self.enabled:
            return _Stopwatch(t0_ns)
        return self._start(stage, tags, t0_ns)

    def _start(self, stage: str, tags: Dict[str, Any],
               t0_ns: Optional[int]) -> _Span:
        s = _Span(self, stage, tags or None, t0_ns)
        with self._lock:
            self._open[id(s)] = s
        return s

    def _open_here(self) -> List[_Span]:
        try:
            return self._stacks.spans
        except AttributeError:
            self._stacks.spans = []
            return self._stacks.spans

    def _finish(self, s: _Span) -> None:
        rec = s.as_dict()
        step = rec.get("step", 0)
        with self._lock:
            self._open.pop(id(s), None)
            lst = self._ring.get(step)
            if lst is None:
                lst = self._ring[step] = []
                while len(self._ring) > self._steps:
                    self._ring.popitem(last=False)
            if len(lst) >= self._max_per_step:
                self.spans_dropped += 1
                return
            lst.append(rec)
            self.spans_total += 1

    # ------------------------------------------------------------ export

    def spans(self, steps: Optional[int] = None) -> List[Dict[str, Any]]:
        """Recorded spans of the last ``steps`` steps (default: the
        whole ring), oldest step first."""
        with self._lock:
            keys = list(self._ring.keys())
            if steps is not None:
                n = max(int(steps), 0)
                # explicit, not keys[-n:]: a -0 slice is the WHOLE
                # list, inverting a zero-step request.
                keys = keys[len(keys) - n:] if n else []
            return [dict(rec) for k in keys for rec in self._ring[k]]

    def open_spans(self) -> List[Dict[str, Any]]:
        """Snapshot of spans currently in flight (no duration yet)."""
        with self._lock:
            return [s.as_dict() for s in list(self._open.values())]

    def stage_totals(self, step: Optional[Any] = None
                     ) -> Dict[str, float]:
        """Summed span wall (ms) per stage for one step of the ring
        (default: the newest step) — the fleet telemetry digest's
        stage-split source (docs/design/fleet_health.md). Empty when
        the step has no spans (tracing off, or nothing recorded)."""
        with self._lock:
            if step is None:
                if not self._ring:
                    return {}
                step = next(reversed(self._ring))
            out: Dict[str, float] = {}
            for rec in self._ring.get(step, ()):
                out[rec["stage"]] = (out.get(rec["stage"], 0.0)
                                     + max(rec["dur_ns"], 0) / 1e6)
            return out

    def chrome_trace(self, steps: Optional[int] = None) -> Dict[str, Any]:
        """The ring as a Chrome trace-event JSON object
        (Perfetto-loadable): completed spans are ``ph: "X"`` complete
        events, still-open spans a ``B``/``E`` pair whose ``E`` is
        synthesized at export (``args.open = true``), one track (tid)
        per pipeline stage, and ``M`` metadata events naming the
        process (replica id) and each track."""
        return chrome_trace(self.spans(steps), self.open_spans(),
                            now_ns=time.monotonic_ns())

    def metrics(self) -> Dict[str, float]:
        """Tracer health counters (merged into ``Manager.metrics()``)."""
        with self._lock:
            return {
                "trace_spans_total": float(self.spans_total),
                "trace_spans_dropped": float(self.spans_dropped),
            }


def maybe_span(tracer: Optional["Tracer"], stage: str,
               **tags: Any) -> Any:
    """``tracer.span(stage, **tags)``, or the shared no-op context
    manager when ``tracer`` is None — the ONE null-tracer guard for
    modules that receive an optional tracer (heal sessions, backends),
    so null semantics can never drift between them."""
    if tracer is None:
        return _NOOP_SPAN
    return tracer.span(stage, **tags)


# ---------------------------------------------------------------- chrome


def _stage_tids(stages: List[str]) -> Dict[str, int]:
    tids: Dict[str, int] = {}
    for s in STAGES:
        tids[s] = len(tids) + 1
    for s in stages:
        if s not in tids:
            tids[s] = len(tids) + 1
    return tids


def _span_args(rec: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in rec.items()
            if k not in ("stage", "t0_ns", "dur_ns")}


def chrome_trace(spans: List[Dict[str, Any]],
                 open_spans: Optional[List[Dict[str, Any]]] = None,
                 now_ns: Optional[int] = None,
                 pid: Optional[int] = None) -> Dict[str, Any]:
    """Render span dicts as a Chrome trace-event object. Timestamps are
    the spans' monotonic clock in microseconds — meaningful relative to
    each other within one process; :func:`merge_traces` aligns clocks
    ACROSS processes on the shared protocol coordinates."""
    open_spans = open_spans or []
    pid = os.getpid() if pid is None else int(pid)
    tids = _stage_tids([r["stage"] for r in spans]
                       + [r["stage"] for r in open_spans])
    replica = ""
    for r in spans + open_spans:
        if r.get("replica_id"):
            replica = str(r["replica_id"])
            break
    events: List[Dict[str, Any]] = [{
        "ph": "M", "name": "process_name", "pid": pid,
        "args": {"name": replica or f"pid {pid}"},
    }]
    used = {r["stage"] for r in spans} | {r["stage"] for r in open_spans}
    for stage, tid in tids.items():
        if stage in used:
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": stage}})
    for r in spans:
        events.append({
            "name": r["stage"], "cat": "torchft", "ph": "X",
            "ts": r["t0_ns"] / 1e3, "dur": max(r["dur_ns"], 0) / 1e3,
            "pid": pid, "tid": tids[r["stage"]],
            "args": _span_args(r),
        })
    end_ts = (now_ns if now_ns is not None
              else time.monotonic_ns()) / 1e3
    for r in open_spans:
        tid = tids[r["stage"]]
        args = _span_args(r)
        args["open"] = True
        events.append({"name": r["stage"], "cat": "torchft", "ph": "B",
                       "ts": r["t0_ns"] / 1e3, "pid": pid, "tid": tid,
                       "args": args})
        events.append({"name": r["stage"], "cat": "torchft", "ph": "E",
                       "ts": max(end_ts, r["t0_ns"] / 1e3), "pid": pid,
                       "tid": tid})
    return {"traceEvents": events, "torchft": {"format": TRACE_FORMAT}}


# ------------------------------------------------------------ prometheus

_LABEL_ESCAPE = {"\\": "\\\\", '"': '\\"', "\n": "\\n"}
_NAME_OK = re.compile(r"[^a-zA-Z0-9_]")


def _escape_label(v: Any) -> str:
    s = str(v)
    for a, b in _LABEL_ESCAPE.items():
        s = s.replace(a, b)
    return s


def _metric_name(key: str) -> str:
    return "torchft_" + _NAME_OK.sub("_", key)


# Metric families rendered as proper Prometheus SUMMARIES instead of
# bare per-quantile gauges: {summary name: (quantile -> source key,
# _sum source key, _count source key)}. The quantile source keys are
# consumed (they do not ALSO render as torchft_<key> gauges); the
# sum/count sources still render under their own documented names —
# they are read by bench/dashboards directly. The exact max stays its
# own gauge (summaries have no max slot). Frozen by
# tests/test_metrics_schema.py.
SUMMARY_SPECS: Dict[str, tuple] = {
    "quorum_ms": ({"0.5": "quorum_ms_p50", "0.95": "quorum_ms_p95"},
                  "quorum_ms_total", "quorum_count"),
}


def prometheus_text(numeric: Dict[str, Any],
                    info: Optional[Dict[str, str]] = None,
                    labels: Optional[Dict[str, str]] = None) -> str:
    """Render a numeric metrics snapshot (``Manager.metrics()``) as
    Prometheus text exposition: every key becomes
    ``torchft_<key>{<labels>}``, typed ``counter`` when the name ends
    in ``_total``/``_count`` (the repo's counter spelling) and
    ``gauge`` otherwise, with ``# HELP``/``# TYPE`` lines on every
    family. Latency-reservoir quantile triples listed in
    ``SUMMARY_SPECS`` render as ONE Prometheus ``summary`` family
    (``torchft_quorum_ms{quantile="0.5"} ... torchft_quorum_ms_sum /
    _count``) instead of bare gauges, so PromQL's
    ``histogram/summary`` tooling works on them. String diagnostics
    (``Manager.metrics_info()``) render as ONE ``torchft_info``
    info-style metric whose value is 1 and whose labels carry the
    strings — the Prometheus idiom for non-numeric facts, and the
    reason the numeric dict must stay numeric at the source."""
    base = "".join(f'{k}="{_escape_label(v)}",'
                   for k, v in sorted((labels or {}).items()))
    lines: List[str] = []
    consumed: set = set()
    for sname, (quantiles, sum_key, count_key) in \
            sorted(SUMMARY_SPECS.items()):
        if not all(k in numeric for k in quantiles.values()):
            continue
        name = _metric_name(sname)
        lines.append(f"# HELP {name} torchft_tpu {sname} summary")
        lines.append(f"# TYPE {name} summary")
        for q in sorted(quantiles, key=float):
            key = quantiles[q]
            consumed.add(key)
            pairs = base + f'quantile="{q}",'
            lines.append(
                f"{name}{{{pairs[:-1]}}} {float(numeric[key])!r}")
        label_s = f"{{{base[:-1]}}}" if base else ""
        if sum_key in numeric:
            lines.append(
                f"{name}_sum{label_s} {float(numeric[sum_key])!r}")
        if count_key in numeric:
            lines.append(
                f"{name}_count{label_s} {float(numeric[count_key])!r}")
    for key in sorted(numeric):
        if key in consumed:
            continue  # rendered as a summary quantile above
        val = numeric[key]
        if isinstance(val, bool) or not isinstance(val, (int, float)):
            continue  # defensively skip anything non-numeric
        name = _metric_name(key)
        kind = ("counter" if key.endswith(("_total", "_count"))
                else "gauge")
        lines.append(f"# HELP {name} torchft_tpu {key}")
        lines.append(f"# TYPE {name} {kind}")
        label_s = f"{{{base[:-1]}}}" if base else ""
        # repr, not %g: 6 significant digits would freeze counters past
        # 1e6 (1000000 and 1000001 both render "1e+06"), zeroing
        # Prometheus rate() exactly where byte counters live.
        lines.append(f"{name}{label_s} {float(val)!r}")
    if info:
        pairs = base + "".join(
            f'{_NAME_OK.sub("_", k)}="{_escape_label(v)}",'
            for k, v in sorted(info.items()))
        lines.append("# HELP torchft_info torchft_tpu string diagnostics")
        lines.append("# TYPE torchft_info gauge")
        lines.append(f"torchft_info{{{pairs[:-1]}}} 1")
    return "\n".join(lines) + "\n"


# ------------------------------------------------------------- fleet merge


def _align_key(args: Dict[str, Any]) -> Optional[tuple]:
    try:
        return (int(args["quorum_id"]), int(args["epoch"]),
                int(args["step"]))
    except (KeyError, TypeError, ValueError):
        return None


def merge_traces(traces: List[Dict[str, Any]],
                 names: Optional[List[str]] = None) -> Dict[str, Any]:
    """Merge many groups' Chrome traces into ONE fleet timeline.

    Each group's spans carry monotonic timestamps from its OWN clock;
    wall clocks step and monotonic zeros differ per process, so raw
    merging would scatter the fleet. Alignment instead uses the step
    protocol itself: spans tagged with the same
    ``(quorum_id, epoch, step)`` describe the SAME global round, so for
    every shared key the earliest span start should coincide across
    groups (the quorum round is a barrier). The reference group is the
    one sharing keys with the MOST other groups (a cold-restarted or
    tracing-off first group must not blank the fleet's alignment);
    every other group's offset is the median over keys shared with the
    reference of (reference's earliest start - its own), robust to a
    few skewed stages. A group sharing NO keys with the reference keeps
    its raw clock, is listed in ``torchft.unaligned_groups``, and logs
    a warning - never a silent scatter. Groups are reassigned distinct
    pids (1..N) with their replica id as the process name."""
    # Pass 1: per-group events, alignment keys, process names.
    infos: List[Dict[str, Any]] = []
    for i, trace in enumerate(traces):
        events = list(trace.get("traceEvents", []))
        keys: Dict[tuple, float] = {}
        pname = ""
        for ev in events:
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                pname = str(ev.get("args", {}).get("name", "")) or pname
            if ev.get("ph") not in ("X", "B"):
                continue
            key = _align_key(ev.get("args", {}))
            if key is None:
                continue
            ts = float(ev["ts"])
            if key not in keys or ts < keys[key]:
                keys[key] = ts
        if not pname:
            # Caller-supplied fallback (the scrape address) only when
            # the trace itself names no replica.
            pname = (names[i] if names is not None and i < len(names)
                     and names[i] else f"group{i}")
        infos.append({"events": events, "keys": keys, "pname": pname})

    def overlap_score(i: int) -> tuple:
        shared = sum(
            1 for j, o in enumerate(infos)
            if j != i and infos[i]["keys"].keys() & o["keys"].keys())
        return (shared, len(infos[i]["keys"]), -i)

    ref = max(range(len(infos)), key=overlap_score) if infos else 0
    ref_keys = infos[ref]["keys"] if infos else {}

    merged: List[Dict[str, Any]] = []
    offsets: List[float] = []
    unaligned: List[str] = []
    for i, info in enumerate(infos):
        if i == ref:
            offset = 0.0
        else:
            deltas = sorted(
                ref_keys[k] - info["keys"][k]
                for k in info["keys"].keys() & ref_keys.keys())
            if deltas:
                offset = deltas[len(deltas) // 2]
            else:
                offset = 0.0
                unaligned.append(info["pname"])
                logger.warning(
                    "merge_traces: group %r shares no (quorum_id, "
                    "epoch, step) keys with reference %r - its spans "
                    "keep their raw clock and will NOT align",
                    info["pname"], infos[ref]["pname"])
        offsets.append(offset)
        pid = i + 1
        for ev in info["events"]:
            ev = dict(ev)
            ev["pid"] = pid
            if "ts" in ev:
                ev["ts"] = float(ev["ts"]) + offset
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                ev["args"] = {"name": info["pname"]}
            merged.append(ev)
    return {
        "traceEvents": merged,
        "torchft": {
            "format": TRACE_FORMAT,
            "merged_groups": [o["pname"] for o in infos],
            "aligned_on": ["quorum_id", "epoch", "step"],
            "reference_group": infos[ref]["pname"] if infos else "",
            "offsets_us": offsets,
            "unaligned_groups": unaligned,
        },
    }


# --------------------------------------------------------- flight recorder

# Crash-hook state: the sys/threading excepthooks latch "an unhandled
# exception happened" and atexit then asks every live FlightRecorder to
# dump — the "the job died, what was it doing" file that makes an
# incident postmortem-able without a re-run.
_CRASH_LOCK = threading.Lock()
_CRASH_SEEN: Dict[str, Any] = {"seen": False, "what": ""}
_CRASH_HOOKS_INSTALLED = False
_RECORDERS: List["FlightRecorder"] = []


def _note_crash(what: str) -> None:
    with _CRASH_LOCK:
        _CRASH_SEEN["seen"] = True
        if not _CRASH_SEEN["what"]:
            _CRASH_SEEN["what"] = what


def _install_crash_hooks() -> None:
    global _CRASH_HOOKS_INSTALLED
    with _CRASH_LOCK:
        if _CRASH_HOOKS_INSTALLED:
            return
        _CRASH_HOOKS_INSTALLED = True

    prev_sys = sys.excepthook

    def sys_hook(exc_type, exc, tb):  # noqa: ANN001
        _note_crash(repr(exc))
        prev_sys(exc_type, exc, tb)

    sys.excepthook = sys_hook

    prev_thread = threading.excepthook

    def thread_hook(args):  # noqa: ANN001
        # SystemExit from daemon teardown is routine, not a crash.
        if args.exc_type is not SystemExit:
            _note_crash(repr(args.exc_value))
        prev_thread(args)

    threading.excepthook = thread_hook
    atexit.register(_atexit_dump)


def _atexit_dump() -> None:
    with _CRASH_LOCK:
        seen, what = _CRASH_SEEN["seen"], _CRASH_SEEN["what"]
        recorders = list(_RECORDERS)
    if not seen:
        return
    for rec in recorders:
        rec.dump("atexit_after_exception", extra={"exception": what})


class FlightRecorder:
    """Crash-time dump writer: the span ring + event history + a
    metrics snapshot as one Perfetto-loadable JSON file under
    ``TORCHFT_FLIGHT_DIR``.

    Disabled (every ``dump`` a no-op) when no directory is configured —
    flight recording is an operational opt-in, the tracer itself stays
    on. Dumps never raise (observability must never fail a step), are
    deduped per (reason, step) so a flapping trigger cannot spam one
    file per retry, and are capped per process
    (``TORCHFT_FLIGHT_MAX``, default 64).

    Args:
        tracer: the span ring to dump.
        directory: dump directory (default ``TORCHFT_FLIGHT_DIR``).
        replica_id: stamped into filenames + the dump body.
        metrics_fn / info_fn / history_fn: zero-arg snapshot callables
            (the Manager wires its own) captured at dump time.
    """

    def __init__(self, tracer: Tracer,
                 directory: Optional[str] = None,
                 replica_id: str = "",
                 metrics_fn: Optional[Callable[[], Dict]] = None,
                 info_fn: Optional[Callable[[], Dict]] = None,
                 history_fn: Optional[Callable[[], List]] = None) -> None:
        self.tracer = tracer
        self.directory = (directory if directory is not None
                          else os.environ.get("TORCHFT_FLIGHT_DIR", ""))
        self.replica_id = replica_id
        self._metrics_fn = metrics_fn
        self._info_fn = info_fn
        self._history_fn = history_fn
        self._lock = threading.Lock()
        self._seen: set = set()
        self.dumps_total = 0
        self.last_path = ""
        try:
            self._max_dumps = max(
                int(os.environ.get("TORCHFT_FLIGHT_MAX", 64)), 1)
        except ValueError:
            self._max_dumps = 64
        if self.enabled:
            _install_crash_hooks()
            with _CRASH_LOCK:
                _RECORDERS.append(self)

    @property
    def enabled(self) -> bool:
        return bool(self.directory)

    def close(self) -> None:
        """Unregister from the atexit crash dump (Manager.shutdown)."""
        with _CRASH_LOCK:
            if self in _RECORDERS:
                _RECORDERS.remove(self)

    def dump(self, reason: str,
             extra: Optional[Dict[str, Any]] = None) -> Optional[str]:
        """Write one dump; returns its path, or None when disabled /
        deduped / failed. Safe from any thread."""
        if not self.enabled:
            return None
        try:
            return self._dump(reason, extra)
        except Exception:  # noqa: BLE001 — never fail the step
            logger.exception("flight-recorder dump failed (reason=%s)",
                             reason)
            return None

    def _dump(self, reason: str,
              extra: Optional[Dict[str, Any]]) -> Optional[str]:
        step = self.tracer.context().get("step", 0)
        with self._lock:
            key = (reason, step)
            if key in self._seen or self.dumps_total >= self._max_dumps:
                return None
            # Reserve the dedup slot + cap so concurrent triggers of
            # the same incident write once; ROLLED BACK on a failed
            # write (transient ENOSPC must not permanently suppress
            # this incident's dump or count phantom dumps).
            self._seen.add(key)
            self.dumps_total += 1
        try:
            return self._write_dump(reason, step, extra)
        except BaseException:
            with self._lock:
                self._seen.discard(key)
                self.dumps_total -= 1
            raise

    def _write_dump(self, reason: str, step: Any,
                    extra: Optional[Dict[str, Any]]) -> str:
        trace = self.tracer.chrome_trace()
        body: Dict[str, Any] = dict(trace)
        side: Dict[str, Any] = {
            "format": FLIGHT_FORMAT,
            "reason": reason,
            "replica_id": self.replica_id,
            "step": step,
            "wall_time": time.time(),
            "mono_ns": time.monotonic_ns(),
            "context": self.tracer.context(),
        }
        for name, fn in (("metrics", self._metrics_fn),
                         ("info", self._info_fn),
                         ("history", self._history_fn)):
            if fn is not None:
                try:
                    side[name] = fn()
                except Exception:  # noqa: BLE001
                    side[name] = {"error": "snapshot failed"}
        if extra:
            side["extra"] = extra
        body["torchft"] = side
        os.makedirs(self.directory, exist_ok=True)
        rid = _NAME_OK.sub("_", self.replica_id or f"pid{os.getpid()}")
        fname = f"flight_{rid}_s{step}_{_NAME_OK.sub('_', reason)}.json"
        path = os.path.join(self.directory, fname)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            # default=str: span tags are open-ended (callers attach
            # whatever attributes a stage has); an unserializable tag
            # must degrade to its repr, never kill the dump.
            json.dump(body, f, default=str)
        os.replace(tmp, path)
        with self._lock:
            self.last_path = path
        logger.warning("flight recorder: dumped %s (reason=%s, step=%s)",
                       path, reason, step)
        return path

    def metrics(self) -> Dict[str, float]:
        with self._lock:
            return {"flight_dumps_total": float(self.dumps_total)}
