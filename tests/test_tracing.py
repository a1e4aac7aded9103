"""Tests for the observability tier (:mod:`torchft_tpu.tracing`,
docs/design/observability.md): the span ring's bounds and context
propagation, the flight recorder's triggers (vote abort, latched
CommunicatorError, heal failover, policy escalation, crash exit), the
``/trace.json`` + ``/metrics`` endpoints over real HTTP, the fleet
merger's ``(quorum_id, epoch, step)`` alignment, event-log monotonic
ordering — and the nightly 2-group chaos round: an injected ring reset
must leave a Perfetto-loadable flight-recorder dump on BOTH groups
whose spans attribute the abort to the fault, with
``scripts/tracefleet.py`` merging both groups' live ``/trace.json``
into one timeline."""

import functools
import json
import os
import sys
import threading
import urllib.request
from unittest.mock import MagicMock

import numpy as np
import pytest

import mockplane
from mockplane import quorum_result
from torchft_tpu import tracing
from torchft_tpu.communicator import (CommunicatorError,
                                      DummyCommunicator)

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


make_manager = functools.partial(
    mockplane.make_manager, min_replica_size=1, use_async_quorum=False,
    replica_id="obs0",
    state_dict=lambda: {"w": np.arange(8, dtype=np.float32)})


# ------------------------------------------------------------- span ring


class TestSpanRing:
    def test_ring_bounded_to_last_k_steps(self):
        tr = tracing.Tracer(steps=3, enabled=True)
        for step in range(10):
            tr.set_context(step=step)
            with tr.span("vote"):
                pass
        steps_seen = {s["step"] for s in tr.spans()}
        assert steps_seen == {7, 8, 9}
        assert tr.spans_total == 10  # recorded, then evicted

    def test_per_step_span_cap_counts_drops(self):
        tr = tracing.Tracer(steps=2, enabled=True, max_spans_per_step=5)
        tr.set_context(step=1)
        for _ in range(9):
            with tr.span("ring"):
                pass
        assert len(tr.spans()) == 5
        assert tr.spans_dropped == 4
        assert tr.metrics()["trace_spans_dropped"] == 4.0

    def test_context_snapshot_is_consistent(self):
        """A span captures the context in force at its START even if
        the context moves before it finishes (copy-on-write)."""
        tr = tracing.Tracer(steps=4, enabled=True)
        tr.set_context(step=5, quorum_id=2)
        sp = tr.span("heal")
        tr.set_context(step=6, quorum_id=3)
        sp.__exit__(None, None, None)
        rec = tr.spans()[0]
        assert rec["step"] == 5 and rec["quorum_id"] == 2

    def test_tags_and_steps_window_param(self):
        tr = tracing.Tracer(steps=8, enabled=True)
        for step in (1, 2, 3):
            tr.set_context(step=step)
            with tr.span("fetch_wait", bucket=step * 10):
                pass
        last2 = tr.spans(steps=2)
        assert [s["step"] for s in last2] == [2, 3]
        assert [s["bucket"] for s in last2] == [20, 30]
        # steps=0 means ZERO steps — a -0 slice must not invert it
        # into the whole ring.
        assert tr.spans(steps=0) == []

    def test_disabled_tracer_is_noop(self):
        tr = tracing.Tracer(steps=4, enabled=False)
        with tr.span("vote", x=1):
            pass
        assert tr.spans() == []
        assert tr.spans_total == 0
        # and the context manager is the shared singleton (no per-call
        # allocation on the hot path)
        assert tr.span("a") is tr.span("b")

    def test_exception_tags_error_and_closes(self):
        tr = tracing.Tracer(steps=4, enabled=True)
        with pytest.raises(ValueError):
            with tr.span("ring"):
                raise ValueError("connection reset (injected)")
        rec = tr.spans()[0]
        assert "connection reset" in rec["error"]
        assert not tr.open_spans()

    def test_thread_safety_smoke(self):
        tr = tracing.Tracer(steps=4, enabled=True)
        tr.set_context(step=1)

        def worker():
            for _ in range(200):
                with tr.span("ring"):
                    pass

        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert tr.spans_total == 800


# ---------------------------------------------------------- manager spans


class TestManagerSpans:
    def test_step_protocol_records_stage_spans(self):
        m = make_manager()
        try:
            m.step()
            fut = m.allreduce({"g": np.ones(4, np.float32)})
            fut.result()
            assert m.should_commit()
            stages = {s["stage"] for s in m.tracer().spans()}
            assert {"quorum", "fetch_dispatch", "fetch_wait", "put",
                    "drain", "vote"} <= stages
            # every span carries the alignment coordinates
            for s in m.tracer().spans():
                assert s["replica_id"] == "obs0"
                assert s["quorum_id"] == 1
                assert s["policy_name"]
        finally:
            m.shutdown()

    def test_vote_span_tags_decision(self):
        m = make_manager()
        try:
            m.step()
            m.should_commit()
            votes = [s for s in m.tracer().spans()
                     if s["stage"] == "vote"]
            assert votes and votes[-1]["decision"] is True
        finally:
            m.shutdown()

    def test_tracing_opt_out_kwarg(self):
        m = make_manager(tracing=False)
        try:
            m.step()
            m.should_commit()
            assert m.tracer().spans() == []
            # counters still present and numeric
            assert m.metrics()["trace_spans_total"] == 0.0
        finally:
            m.shutdown()


class TestRecoveryPhases:
    """The quorum thread's two phases of a membership change, on a mocked
    control plane (tests/test_recovery_timeline.py has the real one)."""

    def test_a_changed_round_is_tagged_and_counted_apart(self):
        m = make_manager()
        try:
            for _ in range(3):
                m.step()
                assert m.should_commit()
            rounds = [s for s in m.tracer().spans()
                      if s["stage"] == "quorum"]
            # The first round hands out a quorum id the Manager did not
            # hold; the mocked plane then repeats it.
            assert [s["changed"] for s in rounds] == [True, False, False]
            assert all(s["world"] == 2 and s["heal"] is False
                       for s in rounds)
            mx = m.metrics()
            assert mx["quorum_changed_count"] == 1
            assert mx["quorum_changed_ms_total"] == pytest.approx(
                rounds[0]["dur_ns"] / 1e6, rel=1e-12)
            assert mx["quorum_ms_total"] == pytest.approx(
                sum(s["dur_ns"] for s in rounds) / 1e6, rel=1e-12)
        finally:
            m.shutdown()

    def test_reconfigure_is_a_span_after_its_round_on_its_thread(self):
        m = make_manager()
        try:
            m.step()
            assert m.should_commit()
            spans = m.tracer().spans()
            (reconf,) = [s for s in spans if s["stage"] == "reconfigure"]
            (round_,) = [s for s in spans if s["stage"] == "quorum"]
            assert reconf["thread_id"] == round_["thread_id"]
            assert reconf["t0_ns"] >= round_["t0_ns"] + round_["dur_ns"]
            assert (reconf["world"], reconf["rank"], reconf["recovery"],
                    reconf["quorum_id"]) == (2, 0, False, 1)
            assert m.metrics()["reconfigure_ms_total"] == pytest.approx(
                reconf["dur_ns"] / 1e6, rel=1e-12)
        finally:
            m.shutdown()

    def test_first_commit_is_noted_once(self):
        client = mockplane.mock_client()
        client.should_commit.side_effect = [False, True, True]
        m = make_manager(client)
        try:
            seen = []
            for _ in range(3):
                m.step()
                m.should_commit()
                seen.append(m.metrics()["join_first_commit_ms"])
            assert seen[0] == 0.0 and seen[1] > 0.0 and seen[2] == seen[1]
            firsts = [e for e in m.history()
                      if e["event"] == "first_commit"]
            assert len(firsts) == 1 and firsts[0]["step"] == 1
            assert firsts[0]["ms"] == round(seen[1], 1)
        finally:
            m.shutdown()


class TestProgramBuilds:
    """``program_build_ms_total`` / ``program_cache_read_ms_total``: what
    the process spent building programs, from jax's own duration events."""

    def test_a_first_call_builds_and_a_second_does_not(self):
        import jax
        import jax.numpy as jnp

        m = make_manager()
        try:
            x = jnp.arange(7.0)
            before = m.metrics()["program_build_ms_total"]
            fn = jax.jit(lambda v: v * 3.0 + 1.0)
            fn(x).block_until_ready()
            first = m.metrics()["program_build_ms_total"]
            assert first > before
            fn(x).block_until_ready()
            assert m.metrics()["program_build_ms_total"] == first
        finally:
            m.shutdown()

    def test_one_listener_however_many_managers(self):
        from jax._src import monitoring

        managers = [make_manager() for _ in range(3)]
        try:
            mine = [fn for fn in monitoring.get_event_duration_listeners()
                    if fn is tracing._on_build_event]
            assert len(mine) == 1
        finally:
            for m in managers:
                m.shutdown()

    def test_a_cache_read_counts_apart_and_other_events_nowhere(self):
        import jax.monitoring

        m = make_manager()
        try:
            before = m.metrics()
            jax.monitoring.record_event_duration_secs(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
            # A trace nests in its parent's duration: not summed (the
            # dispatch span's stamps time the step's).
            for other in ("/jax/core/compile/jaxpr_trace_duration",
                          "/jax/some/other_duration"):
                jax.monitoring.record_event_duration_secs(other, 9.0)
            after = m.metrics()
            assert after["program_cache_read_ms_total"] == pytest.approx(
                before["program_cache_read_ms_total"] + 250.0)
            assert after["program_build_ms_total"] == \
                before["program_build_ms_total"]
        finally:
            m.shutdown()


# ------------------------------------------------------- flight recorder


class TestFlightRecorder:
    def test_disabled_without_dir(self, monkeypatch):
        monkeypatch.delenv("TORCHFT_FLIGHT_DIR", raising=False)
        m = make_manager()
        try:
            assert m.flight_recorder() is not None
            assert not m.flight_recorder().enabled
            assert m.flight_recorder().dump("manual") is None
        finally:
            m.shutdown()

    def test_vote_abort_dumps(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        client = MagicMock()
        client.quorum.return_value = quorum_result()
        client.should_commit.return_value = False
        m = make_manager(client=client, replica_id="abort0")
        try:
            m.step()
            assert not m.should_commit()
            files = [f for f in os.listdir(tmp_path)
                     if "vote_abort" in f]
            assert len(files) == 1
            body = json.loads((tmp_path / files[0]).read_text())
            assert body["torchft"]["reason"] == "vote_abort"
            assert body["torchft"]["replica_id"].startswith("abort0")
            assert body["traceEvents"], "dump must carry the span ring"
            assert body["torchft"]["metrics"]["aborted_steps"] == 1
            assert isinstance(body["torchft"]["history"], list)
            assert m.metrics()["flight_dumps_total"] == 1.0
            assert m.metrics_info()["flight_last_path"].endswith(
                files[0])
        finally:
            m.shutdown()

    def test_latched_comm_error_dumps_once(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        m = make_manager(replica_id="comm0")
        try:
            m.step()
            m.report_error(CommunicatorError("connection reset by peer"))
            m.report_error(CommunicatorError("second reset"))  # latched
            files = [f for f in os.listdir(tmp_path)
                     if "comm_error" in f]
            assert len(files) == 1
            body = json.loads((tmp_path / files[0]).read_text())
            assert "reset" in body["torchft"]["extra"]["error"]
        finally:
            m.shutdown()

    def test_dedupe_per_reason_and_step(self, tmp_path):
        tr = tracing.Tracer(steps=4, enabled=True)
        rec = tracing.FlightRecorder(tr, directory=str(tmp_path),
                                     replica_id="d0")
        try:
            tr.set_context(step=1)
            assert rec.dump("vote_abort") is not None
            assert rec.dump("vote_abort") is None  # same (reason, step)
            tr.set_context(step=2)
            assert rec.dump("vote_abort") is not None  # new step
            assert rec.dumps_total == 2
        finally:
            rec.close()

    def test_failed_write_rolls_back_dedupe_and_count(self, tmp_path):
        """A transient write failure (ENOSPC-class) must not consume
        the incident's dedup slot, the dump cap, or the counter — the
        SAME incident must dump once space clears, and
        flight_dumps_total must never claim a file that was never
        written."""
        tr = tracing.Tracer(steps=4, enabled=True)
        blocked = tmp_path / "flight"
        blocked.write_text("not a directory")  # makedirs -> raises
        rec = tracing.FlightRecorder(tr, directory=str(blocked),
                                     replica_id="e0")
        try:
            tr.set_context(step=7)
            assert rec.dump("vote_abort") is None  # write failed
            assert rec.dumps_total == 0
            blocked.unlink()  # "space clears"
            path = rec.dump("vote_abort")  # same (reason, step) again
            assert path is not None and os.path.exists(path)
            assert rec.dumps_total == 1
        finally:
            rec.close()

    def test_dedupe_tracks_steps_even_with_tracing_disabled(
            self, tmp_path):
        """TORCHFT_TRACING=0 + TORCHFT_FLIGHT_DIR is a supported combo
        (zero-overhead spans, incidents still recorded): the context —
        and with it the per-(reason, step) dedup and the filename stamp
        — must keep tracking steps with span recording off, or every
        later incident collapses onto step 0's dedup slot."""
        tr = tracing.Tracer(steps=4, enabled=False)
        rec = tracing.FlightRecorder(tr, directory=str(tmp_path),
                                     replica_id="off0")
        try:
            tr.set_context(step=100)
            p1 = rec.dump("vote_abort")
            tr.set_context(step=200)
            p2 = rec.dump("vote_abort")
            assert p1 is not None and p2 is not None
            assert "s100" in p1 and "s200" in p2
        finally:
            rec.close()

    def test_atexit_after_exception_hook(self, tmp_path):
        tr = tracing.Tracer(steps=4, enabled=True)
        rec = tracing.FlightRecorder(tr, directory=str(tmp_path),
                                     replica_id="crash0")
        try:
            with tr.span("ring"):
                pass
            # Simulate the unhandled-exception latch + process exit.
            tracing._note_crash("RuntimeError('boom')")
            tracing._atexit_dump()
            files = [f for f in os.listdir(tmp_path)
                     if "atexit_after_exception" in f]
            assert len(files) == 1
            body = json.loads((tmp_path / files[0]).read_text())
            assert body["torchft"]["extra"]["exception"] == \
                "RuntimeError('boom')"
        finally:
            rec.close()
            with tracing._CRASH_LOCK:
                tracing._CRASH_SEEN["seen"] = False
                tracing._CRASH_SEEN["what"] = ""

    def test_dump_is_perfetto_loadable_shape(self, tmp_path):
        """The dump IS a Chrome trace JSON object: traceEvents at the
        top level (phases within the frozen B/E/X/M set), sidecar data
        under a separate key — what Perfetto's JSON importer accepts."""
        tr = tracing.Tracer(steps=4, enabled=True)
        rec = tracing.FlightRecorder(tr, directory=str(tmp_path),
                                     replica_id="p0")
        try:
            tr.set_context(step=3, quorum_id=1, epoch=1,
                           replica_id="p0", policy_name="sync-f32")
            with tr.span("quorum"):
                pass
            path = rec.dump("manual")
            body = json.loads(open(path).read())
            assert set(ev["ph"] for ev in body["traceEvents"]) <= \
                {"X", "B", "E", "M"}
            assert body["torchft"]["format"] == tracing.FLIGHT_FORMAT
        finally:
            rec.close()


# --------------------------------------------------------- event ordering


class TestEventOrdering:
    def test_events_carry_monotonic_stamp_and_seq(self):
        """Satellite: events interleaved across threads/groups order by
        (t_mono_ns, seq) even under wall-clock steps — `t` alone can go
        BACKWARD when ntp slews."""
        m = make_manager()
        try:
            m.step()
            m.report_error(RuntimeError("x"))
            m.should_commit()
            events = m.history()
            assert events, "expected events"
            for e in events:
                assert "t" in e and "t_mono_ns" in e and "seq" in e
            seqs = [e["seq"] for e in events]
            assert seqs == sorted(seqs)
            assert len(set(seqs)) == len(seqs)
            monos = [e["t_mono_ns"] for e in events]
            assert monos == sorted(monos)
        finally:
            m.shutdown()


# ----------------------------------------------------------- HTTP exports


def _http_get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.getcode(), resp.read()


class TestHTTPEndpoints:
    def test_trace_json_over_real_http(self):
        m = make_manager(replica_id="http0")
        try:
            m.step()
            m.allreduce({"g": np.ones(4, np.float32)}).result()
            m.should_commit()
            base = m._ckpt_server.address()
            base = base[:base.rindex("/checkpoint/")]
            code, body = _http_get(base + "/trace.json?steps=8")
            assert code == 200
            trace = json.loads(body)
            names = {ev["name"] for ev in trace["traceEvents"]
                     if ev["ph"] == "X"}
            assert {"quorum", "vote"} <= names
        finally:
            m.shutdown()

    def test_metrics_prometheus_over_real_http(self):
        m = make_manager(replica_id="http1")
        try:
            m.step()
            m.should_commit()
            base = m._ckpt_server.address()
            base = base[:base.rindex("/checkpoint/")]
            code, body = _http_get(base + "/metrics")
            assert code == 200
            text = body.decode()
            assert "torchft_committed_steps" in text
            assert 'torchft_info{' in text
            assert 'policy_name="' in text
            assert 'replica_id="http1"' in text
        finally:
            m.shutdown()

    def test_bad_steps_param_is_400(self):
        m = make_manager()
        try:
            base = m._ckpt_server.address()
            base = base[:base.rindex("/checkpoint/")]
            with pytest.raises(urllib.error.HTTPError) as ei:
                _http_get(base + "/trace.json?steps=banana")
            assert ei.value.code == 400
        finally:
            m.shutdown()

    def test_unattached_server_404s(self):
        from torchft_tpu.checkpointing import CheckpointServer

        srv = CheckpointServer(lambda: {"x": np.zeros(1)})
        try:
            base = srv.address()
            base = base[:base.rindex("/checkpoint/")]
            with pytest.raises(urllib.error.HTTPError) as ei:
                _http_get(base + "/trace.json")
            assert ei.value.code == 404
        finally:
            srv.shutdown()


# ------------------------------------------------------------ fleet merge


def _synthetic_trace(replica, offset_us, steps=(1, 2)):
    """A hand-built per-group trace whose quorum spans start exactly
    ``offset_us`` later than group time 0 — known ground truth for the
    aligner."""
    events = [{"ph": "M", "name": "process_name", "pid": 99,
               "args": {"name": replica}}]
    for step in steps:
        base = offset_us + step * 1000.0
        for i, stage in enumerate(("quorum", "vote")):
            events.append({
                "name": stage, "cat": "torchft", "ph": "X",
                "ts": base + i * 100.0, "dur": 50.0, "pid": 99,
                "tid": i + 1,
                "args": {"replica_id": replica, "quorum_id": 1,
                         "epoch": 1, "step": step,
                         "policy_name": "sync-f32"},
            })
    return {"traceEvents": events}


class TestMergeTraces:
    def test_aligns_on_quorum_epoch_step(self):
        a = _synthetic_trace("g0", offset_us=0.0)
        b = _synthetic_trace("g1", offset_us=123456.0)  # skewed clock
        merged = tracing.merge_traces([a, b])
        assert merged["torchft"]["aligned_on"] == [
            "quorum_id", "epoch", "step"]
        # g1's offset recovered exactly: after alignment, same-key
        # quorum spans coincide.
        assert merged["torchft"]["offsets_us"] == [0.0, -123456.0]
        assert merged["torchft"]["reference_group"] == "g0"
        assert merged["torchft"]["unaligned_groups"] == []
        by_group = {}
        for ev in merged["traceEvents"]:
            if ev.get("ph") == "X" and ev["name"] == "quorum" \
                    and ev["args"]["step"] == 1:
                by_group[ev["pid"]] = ev["ts"]
        assert len(by_group) == 2
        ts = list(by_group.values())
        assert abs(ts[0] - ts[1]) < 1e-6
        # distinct pids + process names survive
        names = {ev["args"]["name"] for ev in merged["traceEvents"]
                 if ev.get("ph") == "M"
                 and ev.get("name") == "process_name"}
        assert names == {"g0", "g1"}

    def test_no_shared_keys_flagged_unaligned(self):
        a = _synthetic_trace("g0", 0.0, steps=(1,))
        b = _synthetic_trace("g1", 500.0, steps=(9,))
        merged = tracing.merge_traces([a, b])
        assert merged["torchft"]["offsets_us"] == [0.0, 0.0]
        # no silent scatter: the unalignable group is NAMED
        assert merged["torchft"]["unaligned_groups"] == ["g1"]

    def test_reference_is_best_connected_group(self):
        """A first group with an empty/disjoint ring (cold restart,
        tracing off) must not blank the fleet's alignment: the
        reference is the group sharing keys with the most others."""
        empty = {"traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 9,
             "args": {"name": "cold0"}}]}
        b = _synthetic_trace("g1", 0.0)
        c = _synthetic_trace("g2", 777.0)
        merged = tracing.merge_traces([empty, b, c])
        assert merged["torchft"]["reference_group"] in ("g1", "g2")
        assert merged["torchft"]["unaligned_groups"] == ["cold0"]
        # g1/g2 still align with each other
        offs = merged["torchft"]["offsets_us"]
        assert 0.0 in (offs[1], offs[2])
        assert abs(abs(offs[1] - offs[2]) - 777.0) < 1e-6


class TestTracefleetCLI:
    def test_merges_two_live_groups_over_http(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import tracefleet
        finally:
            sys.path.pop(0)

        managers = []
        addrs = []
        try:
            for i in range(2):
                m = make_manager(replica_id=f"fleet{i}")
                m.step()
                m.allreduce({"g": np.ones(4, np.float32)}).result()
                m.should_commit()
                managers.append(m)
                addrs.append(m._ckpt_server.address())
            out = tmp_path / "fleet.json"
            rc = tracefleet.main(addrs + ["--out", str(out),
                                          "--steps", "16"])
            assert rc == 0
            merged = json.loads(out.read_text())
            pids = {ev["pid"] for ev in merged["traceEvents"]}
            assert pids == {1, 2}
            names = {ev["args"]["name"] for ev in merged["traceEvents"]
                     if ev.get("ph") == "M"
                     and ev.get("name") == "process_name"}
            assert names == {"fleet0", "fleet1"}
            stages = {ev["name"] for ev in merged["traceEvents"]
                      if ev.get("ph") == "X"}
            assert {"quorum", "vote"} <= stages
        finally:
            for m in managers:
                m.shutdown()

    def test_dead_group_skipped_not_fatal(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import tracefleet
        finally:
            sys.path.pop(0)

        m = make_manager(replica_id="alive0")
        try:
            m.step()
            m.should_commit()
            out = tmp_path / "fleet.json"
            rc = tracefleet.main(
                [m._ckpt_server.address(), "127.0.0.1:1",  # dead
                 "--out", str(out), "--timeout", "2"])
            assert rc == 0
            assert json.loads(out.read_text())["traceEvents"]
        finally:
            m.shutdown()


# ------------------------------------------- nightly chaos acceptance


class _PairHub:
    """Two-rank rendezvous hub pairing each rank's n-th wire op with
    the peer's n-th and resolving both with the canonical-order fold —
    the native-free 2-group ring used across the policy tests."""

    def __init__(self):
        self.lock = threading.Lock()
        self.counts = {}
        self.pending = {}

    def submit(self, rank, buffers, origs):
        from concurrent.futures import Future

        from torchft_tpu.communicator import _upcast_buffers

        fut = Future()
        with self.lock:
            idx = self.counts.get(rank, 0)
            self.counts[rank] = idx + 1
            entry = self.pending.setdefault(idx, {})
            entry[rank] = (list(buffers),
                           [np.dtype(d) for d in origs], fut)
            ready = len(entry) == 2
            if ready:
                del self.pending[idx]
        if ready:
            vals = {r: _upcast_buffers(b, o)
                    for r, (b, o, _f) in entry.items()}
            sums = [vals[0][i] + vals[1][i]
                    for i in range(len(vals[0]))]
            for _r, (_b, origs_r, f) in entry.items():
                f.set_result([np.array(s, dtype=d)
                              for s, d in zip(sums, origs_r)])
        return fut


class _PairComm(DummyCommunicator):
    def __init__(self, hub, rank):
        super().__init__(rank=rank, world_size=2)
        self._hub = hub

    def configure(self, store_addr, rank, world_size):
        self.configure_count += 1  # keep the pair's fixed rank/world

    def allreduce_wire(self, buffers, orig_dtypes, op="sum"):
        return self._hub.submit(self.rank(), buffers, orig_dtypes)


@pytest.mark.slow
@pytest.mark.nightly
class TestFlightRecorderChaosNightly:
    """Acceptance: a 2-group run with an injected ring reset (the
    ChaosCommunicator shim — the same CommunicatorError class a real
    TCP reset surfaces as) leaves a parseable, Perfetto-shaped
    flight-recorder dump on BOTH groups whose spans/extra attribute the
    abort to the fault, and tracefleet merges both groups' /trace.json
    into one timeline aligned on (quorum_id, epoch, step)."""

    def test_injected_ring_reset_leaves_attributable_dumps(
            self, tmp_path, monkeypatch):
        from torchft_tpu.chaos import ChaosCommunicator, ChaosSchedule

        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import tracefleet
        finally:
            sys.path.pop(0)

        RESET_STEP = 3  # 1-based step whose ring op resets

        class ResetOnce(ChaosSchedule):
            """Scripted: the RESET_STEP-th allreduce_wire op on each
            group fails post-submit with a connection reset."""

            def __init__(self):
                super().__init__(seed=0)
                self.n = 0
                self.lock = threading.Lock()

            def config_for(self, endpoint):
                return object()

            def decide(self, endpoint, op):
                from torchft_tpu.chaos import Decision

                with self.lock:
                    self.n += 1
                    n = self.n
                if n == RESET_STEP:
                    return Decision(endpoint=endpoint, op=op, n=n,
                                    delay_ms=0, fault="reset",
                                    phase="post", frac=1.0,
                                    blackhole_ms=0.0)
                return None

        hub = _PairHub()
        barrier = threading.Barrier(2)
        managers = {}
        errors = []
        done = threading.Barrier(2 + 1)

        def run_group(rank):
            try:
                client = MagicMock()
                client.quorum.return_value = quorum_result(
                    max_rank=rank, replica_rank=rank)
                client.should_commit.side_effect = (
                    lambda **kw: kw["should_commit"])
                comm = ChaosCommunicator(_PairComm(hub, rank),
                                         schedule=ResetOnce(),
                                         endpoint="ring")
                m = make_manager(client=client, comm=comm,
                                 replica_id=f"chaos{rank}")
                managers[rank] = m
                for _ in range(5):
                    barrier.wait(timeout=60)
                    m.step()
                    m.allreduce(
                        {"g": np.ones(64, np.float32)}).result()
                    m.should_commit()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                try:
                    barrier.abort()
                except Exception:  # noqa: BLE001
                    pass
            finally:
                done.wait(timeout=60)

        ts = [threading.Thread(target=run_group, args=(r,))
              for r in range(2)]
        for t in ts:
            t.start()
        done.wait(timeout=120)
        for t in ts:
            t.join(timeout=60)
        try:
            assert not errors, errors

            # Both groups dumped on the latched reset, and the dumps
            # are parseable Chrome-trace JSON attributing the abort.
            for rank in range(2):
                dumps = [f for f in os.listdir(tmp_path)
                         if f.startswith(f"flight_chaos{rank}_")
                         and "comm_error" in f]
                assert len(dumps) == 1, sorted(os.listdir(tmp_path))
                body = json.loads((tmp_path / dumps[0]).read_text())
                side = body["torchft"]
                assert side["reason"] == "comm_error"
                assert "reset" in side["extra"]["error"]
                assert side["step"] == RESET_STEP
                assert side["metrics"]["trace_spans_total"] > 0
                phases = {ev["ph"] for ev in body["traceEvents"]}
                assert phases <= {"X", "B", "E", "M"}
                # the span ring covers the aborted step's pipeline
                span_steps = {ev["args"]["step"]
                              for ev in body["traceEvents"]
                              if ev["ph"] == "X"}
                assert RESET_STEP in span_steps
                # vote_abort fired at the same step too
                aborts = [f for f in os.listdir(tmp_path)
                          if f.startswith(f"flight_chaos{rank}_")
                          and "vote_abort" in f]
                assert aborts, sorted(os.listdir(tmp_path))

            # Fleet merge of both groups' live /trace.json.
            out = tmp_path / "fleet.json"
            addrs = [managers[r]._ckpt_server.address()
                     for r in range(2)]
            assert tracefleet.main(addrs + ["--out", str(out)]) == 0
            merged = json.loads(out.read_text())
            pids = {ev["pid"] for ev in merged["traceEvents"]}
            assert pids == {1, 2}
            keyed = {(ev["args"]["quorum_id"], ev["args"]["epoch"],
                      ev["args"]["step"])
                     for ev in merged["traceEvents"]
                     if ev.get("ph") == "X"}
            assert any(k[2] == RESET_STEP for k in keyed)
        finally:
            for m in managers.values():
                m.shutdown()
