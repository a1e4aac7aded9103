"""The step thread under spans (docs/design/observability.md, tier-1).

(a) one ``FTTrainer.train_step`` on a mocked control plane: its top-level
spans follow one another on the step thread, cover the step, and
``last_step_timings`` is made of their stamps; (b) a span's ``thread``,
``thread_id``, ``id`` and ``parent``; (c) spans in a ``jax.profiler``
capture; (d) the counters leave a program without a callback; (e) the
benchmark's two readers of these spans on a hand-built record.
"""

import glob
import os
import sys
import tempfile
import threading
import time
from unittest.mock import MagicMock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mockplane import make_manager, quorum_result
from torchft_tpu import tracing
from torchft_tpu.communicator import DummyCommunicator
from torchft_tpu.manager import Manager
from torchft_tpu.parallel import FTTrainer

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

# A mocked RPC's latency: long against the microseconds of glue between two
# spans, so that a stage that lost its span costs the cover a whole RPC.
RPC_S = 0.04


def _client(worlds):
    """A ManagerClient mock whose rounds and votes take ``RPC_S``; round k
    answers a quorum of ``worlds[k]`` groups (the last one from then on)."""
    client = MagicMock()
    left = list(worlds)

    def quorum(**_):
        time.sleep(RPC_S)
        world = left.pop(0) if len(left) > 1 else left[0]
        return quorum_result(max_rank=0, max_world_size=world,
                             replica_rank=0, replica_world_size=world)

    def vote(**_):
        time.sleep(RPC_S)
        return True

    client.quorum.side_effect = quorum
    client.should_commit.side_effect = vote
    return client


def _trainer(worlds, overlap):
    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    return FTTrainer(
        loss_fn=loss_fn, tx=optax.sgd(0.1),
        params={"w": jnp.zeros((4,), jnp.float32)},
        manager_factory=lambda load, save: Manager(
            comm=DummyCommunicator(), load_state_dict=load, state_dict=save,
            min_replica_size=1, rank=0, world_size=1, replica_id="spans",
            overlap_steps=overlap, _manager_client=_client(worlds)))


def _batch(k):
    rng = np.random.default_rng(k)
    return {"x": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
            "y": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}


# Which top-level spans make which key of last_step_timings. The overlap
# loop's keys are what they were before the spans: ``dispatch`` runs from
# the step's first stamp to the end of the speculative dispatch and from
# the step's kick to the end of the recompute (the step_begin spans
# included), ``allreduce_wait`` is the drain of the step before alone, and
# this step's quorum join and stage loop are ``other``.
KEY_STAGES = {
    "dispatch": {"dispatch"},
    "allreduce_wait": {"wait_quorum", "exchange_wait", "fetch_dispatch",
                       "fetch_wait"},
    "commit": {"drain", "pre_vote", "vote", "post_vote", "publish_status",
               "update"},
    "other": {"step_begin"},
}
OVERLAP_KEY_STAGES = dict(
    KEY_STAGES, dispatch={"step_begin", "dispatch"},
    allreduce_wait={"overlap_drain"},
    other={"wait_quorum", "fetch_dispatch", "fetch_wait"})


def _one_step(trainer, k):
    """Run step ``k``; its top-level spans on this thread, in order, and
    its timings in ns."""
    t0 = time.monotonic_ns()
    trainer.train_step(_batch(k))
    t1 = time.monotonic_ns()
    me = threading.get_ident()
    top = sorted((s for s in trainer.manager.tracer().spans()
                  if s["thread_id"] == me and s["parent"] is None
                  and t0 <= s["t0_ns"] <= t1), key=lambda s: s["t0_ns"])
    return top, {k: v * 1e9 for k, v in trainer.last_step_timings.items()}


class TestStepPartition:
    """(a): fused, split, a misprediction, the overlap loop."""

    @pytest.mark.parametrize("worlds,overlap,programs", [
        ([1], 0, ["fused"]),
        ([2], 0, ["fwd_bwd"]),
        # steps 3, 5 and 7 mispredict (fused dispatched, two groups found)
        ([1, 1, 2, 1, 2, 1, 2], 0, ["fused", "fwd_bwd"]),
        ([2], 1, ["fwd_bwd"]),
    ], ids=["fused", "split", "mispredicted", "overlap"])
    def test_spans_partition_the_step(self, worlds, overlap, programs):
        trainer = _trainer(worlds, overlap)
        try:
            steps = [_one_step(trainer, k)
                     for k in range(max(4, len(worlds)))]
        finally:
            trainer.shutdown()
        # Any settled step of the kind will do: the one with the least
        # glue, so that a descheduled thread fails nothing. Of a
        # misprediction there are three (until PR 45 step 3 alone was
        # judged, and under six loaded workers one preemption of a few ms
        # between two spans failed the cover; it failed in the driver's run
        # of 7c58aa3): every step whose quorum differs from the prediction,
        # the third among them.
        if len(worlds) > 1:
            chosen = [st for st in steps
                      if [s.get("program") for s in st[0]
                          if s["stage"] == "dispatch"] == programs]
            assert steps[2] in chosen and len(chosen) == 3
        else:
            chosen = steps[1:]
        for top, _ in steps:
            for a, b in zip(top, top[1:]):
                assert a["t0_ns"] + a["dur_ns"] <= b["t0_ns"], (a, b)
        top, timings = max(
            chosen, key=lambda st: sum(s["dur_ns"] for s in st[0])
            / st[1]["total"])
        covered = sum(s["dur_ns"] for s in top)
        assert covered >= 0.98 * timings["total"], (
            covered, timings, [(s["stage"], s["dur_ns"]) for s in top])
        assert [s.get("program") for s in top
                if s["stage"] == "dispatch"] == programs
        key_stages = OVERLAP_KEY_STAGES if overlap else KEY_STAGES
        assert {s["stage"] for s in top} <= set().union(
            *key_stages.values())
        slack = 0.02 * timings["total"]
        for key, stages in key_stages.items():
            spans_ns = sum(s["dur_ns"] for s in top if s["stage"] in stages)
            # A key is its spans and the glue beside them, never less.
            assert spans_ns - 1e3 <= timings[key] <= spans_ns + slack, (
                key, timings[key], spans_ns)
        # The dispatch key is the spans' own stamps, to the nanosecond's
        # rounding; and the four keys are the step.
        dispatch_ns = sum(s["dur_ns"] for s in top
                          if s["stage"] in key_stages["dispatch"])
        assert abs(timings["dispatch"] - dispatch_ns) < 1.0
        assert abs(sum(timings[k] for k in KEY_STAGES)
                   - timings["total"]) < 1e3

    def test_dispatch_says_which_call_traced(self):
        trainer = _trainer([1], 0)
        try:
            tops = [_one_step(trainer, k)[0] for k in range(3)]
        finally:
            trainer.shutdown()
        traced = [[s.get("traced", False) for s in top
                   if s["stage"] == "dispatch"] for top in tops]
        assert traced == [[True], [False], [False]]

    @pytest.mark.parametrize("op", ["allreduce", "reduce_scatter"])
    def test_the_exchange_joins_the_quorum_under_its_span(self, op):
        """The join is the one the exchange always made, now named; with
        an error latched the call returns at once and joins nothing."""
        release = threading.Event()
        client = _client([2])
        quorum = client.quorum.side_effect

        def held(**kw):
            release.wait(timeout=30)
            return quorum(**kw)

        client.quorum.side_effect = held
        m = make_manager(client, min_replica_size=1)
        tree = {"g": np.ones(4, np.float32)}
        try:
            m.step()
            m.report_error(RuntimeError("latched"))
            t0 = time.monotonic()
            assert getattr(m, op)(tree).result() is tree
            assert time.monotonic() - t0 < RPC_S    # the round is held
            assert [s for s in m.tracer().spans()
                    if s["stage"] == "wait_quorum"] == []
            release.set()
            m.should_commit()
            m.step()
            getattr(m, op)(tree).result()
            joins = [s for s in m.tracer().spans()
                     if s["stage"] == "wait_quorum"]
        finally:
            release.set()
            m.shutdown()
        assert len(joins) == 1
        assert joins[0]["thread_id"] == threading.get_ident()
        assert joins[0]["dur_ns"] >= 0.5 * RPC_S * 1e9

    def test_a_disabled_tracer_still_times_the_step(self):
        trainer = _trainer([1], 0)
        trainer.manager.tracer().enabled = False
        try:
            trainer.train_step(_batch(0))
            timings = dict(trainer.last_step_timings)
            assert trainer.manager.tracer().spans() == []
        finally:
            trainer.shutdown()
        assert timings["total"] >= 2 * RPC_S
        assert timings["commit"] >= RPC_S
        assert abs(sum(timings[k] for k in KEY_STAGES)
                   - timings["total"]) < 1e-6


class TestWhoAndUnderWhat:
    """(b): thread, id, parent."""

    def test_a_span_names_the_one_open_on_its_thread(self):
        tr = tracing.Tracer(steps=4, enabled=True)
        with tr.span("publish_status") as outer:
            with tr.span("state_digest") as inner:
                with tr.span("ring") as innermost:
                    pass
            with tr.span("vote") as sibling:
                pass
        by_stage = {s["stage"]: s for s in tr.spans()}
        assert by_stage["publish_status"]["parent"] is None
        assert by_stage["state_digest"]["parent"] == outer.id
        assert by_stage["ring"]["parent"] == inner.id
        assert by_stage["vote"]["parent"] == outer.id
        ids = [outer.id, inner.id, innermost.id, sibling.id]
        assert len(set(ids)) == 4
        me = threading.current_thread()
        assert {(s["thread"], s["thread_id"]) for s in tr.spans()} \
            == {(me.name, me.ident)}
        assert {s["id"] for s in tr.spans()} == set(ids)

    def test_another_threads_span_has_no_parent_here(self):
        tr = tracing.Tracer(steps=4, enabled=True)

        def other():
            with tr.span("quorum"):
                pass

        with tr.span("dispatch"):
            t = threading.Thread(target=other, name="quorum-thread")
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
        by_stage = {s["stage"]: s for s in tr.spans()}
        assert by_stage["quorum"]["parent"] is None
        assert by_stage["quorum"]["thread"] == "quorum-thread"
        assert by_stage["quorum"]["thread_id"] == t.ident
        assert by_stage["dispatch"]["thread"] \
            == threading.current_thread().name
        assert by_stage["dispatch"]["thread_id"] == threading.get_ident()

    def test_two_threads_of_one_name_are_told_apart(self):
        tr = tracing.Tracer(steps=4, enabled=True)
        gate = threading.Barrier(2)

        def work():
            with tr.span("put"):
                gate.wait(timeout=30)   # both alive at once: two idents

        pool = [threading.Thread(target=work, name="pool") for _ in "ab"]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=30)
        spans = tr.spans()
        assert [s["thread"] for s in spans] == ["pool", "pool"]
        assert {s["thread_id"] for s in spans} == {t.ident for t in pool}

    def test_a_span_closed_by_hand_records_no_parent(self):
        tr = tracing.Tracer(steps=4, enabled=True)
        with tr.span("quorum") as outer:
            bare = tr.span("heal")          # as Manager's heal span
            with tr.span("heal_stripe"):
                pass
            bare.__exit__(None, None, None)
        by_stage = {s["stage"]: s for s in tr.spans()}
        assert by_stage["heal"]["parent"] is None
        assert by_stage["heal_stripe"]["parent"] == outer.id

    @pytest.mark.parametrize("how", ["generator", "other_thread"])
    def test_a_span_left_out_of_order_leaves_the_stack(self, how):
        """A span that is not on top when it exits (a generator holding
        its ``with`` is closed late; an exit on another thread) comes off
        the stack it was pushed on and is nobody's parent afterwards."""
        tr = tracing.Tracer(steps=4, enabled=True)

        def holding():
            with tr.span("heal"):
                yield

        if how == "generator":
            gen = holding()
            next(gen)                       # heal entered, left open
            with tr.span("dispatch") as inner:
                gen.close()                 # heal exits under dispatch
                with tr.span("put"):
                    pass
        else:
            late = tr.span("heal").__enter__()
            with tr.span("dispatch") as inner:
                t = threading.Thread(
                    target=late.__exit__, args=(None, None, None))
                t.start()
                t.join(timeout=30)
                with tr.span("put"):
                    pass
        with tr.span("vote"):
            pass
        by_stage = {s["stage"]: s for s in tr.spans()}
        assert by_stage["heal"]["parent"] is None
        assert by_stage["dispatch"]["parent"] == by_stage["heal"]["id"]
        assert by_stage["put"]["parent"] == inner.id
        assert by_stage["vote"]["parent"] is None
        assert tr._open_here() == [] and tr.open_spans() == []

    def test_a_disabled_tracer_does_none_of_it(self):
        tr = tracing.Tracer(steps=4, enabled=False)
        assert tr.span("vote") is tracing._NOOP_SPAN
        assert tracing.maybe_span(tr, "vote") is tracing._NOOP_SPAN
        with tr.span("vote"):
            with tr.timed("dispatch") as watch:
                time.sleep(0.001)
        assert watch.dur_ns >= 1_000_000
        assert watch.end_ns == watch.t0_ns + watch.dur_ns
        assert tr.spans() == [] and tr.open_spans() == []
        assert tr.metrics()["trace_spans_total"] == 0.0
        assert tr._annotate is None

    def test_timed_begins_where_the_one_before_ended(self):
        tr = tracing.Tracer(steps=4, enabled=True)
        with tr.timed("step_begin") as a:
            pass
        with tr.timed("dispatch", after=a) as b:
            pass
        assert b.t0_ns == a.end_ns == a.t0_ns + a.dur_ns
        assert [s["stage"] for s in tr.spans()] == ["step_begin", "dispatch"]


class TestProfilerAnnotation:
    """(c): a capture holds the stages; without one nothing is written."""

    def test_capture_holds_the_stages(self):
        from jax.profiler import ProfileData

        tr = tracing.Tracer(steps=4, enabled=True)
        with tempfile.TemporaryDirectory() as d:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(d, profiler_options=opts)
            try:
                with tr.span("publish_status"):
                    with tr.span("state_digest"):
                        time.sleep(0.001)
            finally:
                jax.profiler.stop_trace()
            files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                              recursive=True)
            assert len(files) == 1
            before = os.stat(files[0])
            with tr.span("vote"):       # no capture running: inert
                pass
            after = os.stat(files[0])
            assert (before.st_size, before.st_mtime_ns) \
                == (after.st_size, after.st_mtime_ns)
            assert glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True) == files
            names = {e.name
                     for plane in ProfileData.from_file(files[0]).planes
                     if not plane.name.startswith("/device:")
                     for line in plane.lines for e in line.events}
        assert {"publish_status", "state_digest"} <= names
        assert "vote" not in names


class TestProgramCallback:
    """(d): no program holds a host callback any more; the key that counted
    its runs stays in the schema, at rest."""

    def test_a_collecting_program_counts_without_a_callback(self):
        m = make_manager()
        try:
            def rows(x):
                tracing.count_in_program(test_rows_total=x.shape[0])
                return x * 2

            program = jax.jit(tracing.collect_counts(rows))
            assert "callback" not in program.lower(jnp.ones(4)).as_text()
            before = tracing.program_counters()
            for _ in range(3):
                _, counts = program(jnp.ones(4))
                tracing.defer_program_counts(counts)
            tracing.settle_program_counts(wait=True)
            after = tracing.program_counters()
            counters = m.metrics()
            spans = m.tracer().spans()
        finally:
            m.shutdown()
        assert after["test_rows_total"] \
            - before.get("test_rows_total", 0.0) == 12
        assert counters["test_rows_total"] == after["test_rows_total"]
        # Adding the counts records nothing: the count is all.
        assert spans == []


# ------------------------------------------------- (e) the two readers

MS = 1_000_000


def _span(ident, stage, t0_ms, t1_ms, thread="step", parent=None,
          thread_id=None):
    if thread_id is None:
        thread_id = {"step": 11, "quorum": 12}[thread]
    return {"stage": stage, "t0_ns": int(t0_ms * MS),
            "dur_ns": int((t1_ms - t0_ms) * MS), "thread": thread,
            "thread_id": thread_id, "id": ident, "parent": parent}


def _record():
    """Three steps of 100 ms. The device runs the step's program from 10
    to 90 of each step's 100 ms, so it idles 20 ms a step: 60 ms."""
    steps, spans, ops, modules = [], [], [], []
    ident = iter(range(1, 1000))
    for k in range(3):
        o = 100 * k
        steps.append({"phase": "window", "committed": True, "world": 1,
                      "t0": o * MS, "t1": (o + 98) * MS})
        modules.append(("jit_fused(1)", (o + 10) * MS, (o + 90) * MS))
        ops.append(("%fusion.1 = f32[4]", (o + 10) * MS, (o + 50) * MS))
        ops.append(("%fusion.2 = f32[4]", (o + 50) * MS, (o + 90) * MS))
        spans.append(_span(next(ident), "step_begin", o, o + 2))
        # idle until 10: 8 ms of it under dispatch
        spans.append(_span(next(ident), "dispatch", o + 2, o + 12))
        spans.append(_span(next(ident), "wait_quorum", o + 12, o + 14))
        pub = next(ident)
        # the boundary: publish_status 14-94, its child the digest's read
        # 16-93 and a grandchild inside that; idle from 90: 4 ms under it
        spans.append(_span(pub, "publish_status", o + 14, o + 94))
        dig = next(ident)
        spans.append(_span(dig, "state_digest", o + 16, o + 93, parent=pub))
        spans.append(_span(next(ident), "ring", o + 20, o + 30, parent=dig))
        spans.append(_span(next(ident), "update", o + 94, o + 95))
        # another thread's spans lie over everything and count for nothing
        spans.append(_span(next(ident), "quorum", o + 1, o + 99,
                           thread="quorum"))
        # ... nor does a thread that only shares the step thread's name
        spans.append(_span(next(ident), "put", o + 96, o + 99,
                           thread_id=13))
    return {"steps": {0: steps}, "groups": 1, "spans": spans,
            "device_trace": {"planes": {"/device:TPU:0": ops},
                             "modules": {"/device:TPU:0": modules},
                             "lo": -5 * MS, "hi": 400 * MS}}


BOUNDARY = ["step_begin", "pre_vote", "drain", "vote", "post_vote",
            "publish_status", "update"]
WAITS = ["wait_quorum", "exchange_wait", "fetch_dispatch", "fetch_wait"]
MODULE = "^jit_(fused|fwd_bwd)"


@pytest.fixture(scope="module")
def readers():
    from harness import spec

    spec.configure(REPO)
    return {kind: spec.module("readers", kind)
            for kind in ("idle_under_span", "span_self_sum")}


class TestReaders:
    def test_the_four_idle_metrics_sum_to_the_idle_time(self, readers):
        run = _record()
        read = readers["idle_under_span"].read
        got = {name: read(run, {"stages": stages, "module": MODULE})
               for name, stages in (("dispatch", ["dispatch"]),
                                    ("boundary", BOUNDARY),
                                    ("wait", WAITS), ("unspanned", []))}
        # The window: first start to last end, 0 to 298 ms. Idle: 0-10,
        # 90-110, 190-210, 290-298 = 58 ms in three steps.
        # dispatch: 2-10, 102-110, 202-210 = 24; boundary: step_begin 0-2,
        # 100-102, 200-202 and publish_status/update 90-95, 190-195,
        # 290-295 = 21; waits: none idle; unspanned: 95-100, 195-200,
        # 295-298 = 13.
        assert got["dispatch"] == pytest.approx(24 / 3)
        assert got["boundary"] == pytest.approx(21 / 3)
        assert got["wait"] == pytest.approx(0.0)
        assert got["unspanned"] == pytest.approx(13 / 3)
        assert sum(got.values()) == pytest.approx(58 / 3)

    def test_only_steps_the_device_trace_shows_whole(self, readers):
        run = _record()
        # The device trace ends inside the third step: its program is cut.
        dev = run["device_trace"]
        dev["modules"]["/device:TPU:0"].pop()
        dev["planes"]["/device:TPU:0"] = dev["planes"]["/device:TPU:0"][:4]
        read = readers["idle_under_span"].read
        got = [read(run, {"stages": stages, "module": MODULE})
               for stages in (["dispatch"], BOUNDARY, WAITS, [])]
        # Two steps, 0 to 198 ms: idle 0-10, 90-110, 190-198 = 38 ms.
        assert sum(got) == pytest.approx(38 / 2)

    def test_a_childs_time_is_not_counted_twice(self, readers):
        run = _record()
        read = readers["span_self_sum"].read
        # publish_status 80 ms less its child's 77: 3 ms of its own.
        assert read(run, {"stages": ["publish_status"]}) \
            == pytest.approx(3.0)
        # the digest's read less the grandchild's 10
        assert read(run, {"stages": ["state_digest"]}) \
            == pytest.approx(67.0)
        # step_begin 2 + publish_status 3 + update 1; the other thread's
        # spans and the waits are not in it
        assert read(run, {"stages": BOUNDARY}) == pytest.approx(6.0)
        assert read(run, {"stages": ["publish_status", "state_digest"]}) \
            == pytest.approx(70.0)

    def test_a_program_without_these_spans_reads_nothing(self, readers):
        run = _record()
        for s in run["spans"]:           # the parent's spans: no thread
            for k in ("thread", "thread_id", "id", "parent"):
                del s[k]
        assert readers["idle_under_span"].read(
            run, {"stages": [], "module": MODULE}) is None
        assert readers["span_self_sum"].read(
            run, {"stages": BOUNDARY}) is None
        run["spans"] = [s for s in run["spans"]
                        if s["stage"] in ("quorum", "vote")]
        assert readers["idle_under_span"].read(
            run, {"stages": ["dispatch"], "module": MODULE}) is None
