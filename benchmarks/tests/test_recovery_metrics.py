"""What PR 58 added to the yardstick: the reader ``span_wall_between`` on a
hand-made record, and every metric file of ``BENCHMARK.json`` loading
through ``harness/spec.py`` and naming a reader that exists. (A file of its
own: a PR that adds metrics edits no test file that is here.)"""

import json
import os

import pytest

from harness import readers, spec

MS = 1_000_000
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
BARE_WAITS = ("wait_quorum", "exchange_wait", "drain")


def test_span_wall_between_two_moments():
    """Spans of two threads between a kill and a commit: side by side they
    count once, one that straddles a moment counts for its part inside."""
    spans = [
        # the quorum thread: a round that began before the kill
        {"stage": "quorum", "thread_id": 1, "id": 1, "parent": None,
         "t0_ns": 90 * MS, "dur_ns": 40 * MS},
        {"stage": "reconfigure", "thread_id": 1, "id": 2, "parent": None,
         "t0_ns": 130 * MS, "dur_ns": 5 * MS},
        # two lanes' ops, overlapping; the second ends after the commit
        # and spends its first 25 ms at its handshake
        {"stage": "ring", "thread_id": 2, "id": 3, "parent": None,
         "t0_ns": 140 * MS, "dur_ns": 30 * MS},
        {"stage": "ring_preamble", "thread_id": 2, "id": 4, "parent": 3,
         "t0_ns": 140 * MS, "dur_ns": 2 * MS},
        {"stage": "ring", "thread_id": 3, "id": 5, "parent": None,
         "t0_ns": 150 * MS, "dur_ns": 70 * MS},
        {"stage": "ring_preamble", "thread_id": 3, "id": 6, "parent": 5,
         "t0_ns": 150 * MS, "dur_ns": 25 * MS},
        {"stage": "vote", "thread_id": 4, "id": 7, "parent": None,
         "t0_ns": 400 * MS, "dur_ns": 10 * MS}]
    run = {"spans": spans,
           "events": {"window.kill": 100 * MS,
                      "window.survivor_commit": 200 * MS}}
    between = {"kind": "span_wall_between", "from": "window.kill",
               "to": "window.survivor_commit"}

    def read(**args):
        return readers.read(run, {**between, **args})

    assert read(stages=["quorum"]) == pytest.approx(0.030)
    assert read(stages=["reconfigure"]) == pytest.approx(0.005)
    assert read(stages=["ring"]) == pytest.approx(0.060)
    assert read(stages=["ring_preamble"]) == pytest.approx(0.027)
    # Each op less its own handshake: lane 0 on the wire 142-170, lane 1
    # 175-200. While lane 1 waits (150-175) lane 0 moves bytes until 170,
    # so every open op waits only in 140-142 and 170-175.
    wire = dict(stages=["ring"], minus_children=["ring_preamble"])
    assert read(**wire) == pytest.approx(0.053)
    assert read(**wire, complement="stages") == pytest.approx(0.007)
    assert read(**wire, complement=True) == pytest.approx(0.047)
    assert read(**wire, scale=1000.0) == pytest.approx(53.0)
    assert read(stages=["quorum", "reconfigure", "ring"]) == \
        pytest.approx(0.095)
    assert read(stages=["quorum", "reconfigure", "ring"],
                complement=True) == pytest.approx(0.005)
    # a stage with spans, none of them in the interval: zero, not nothing
    assert read(stages=["vote"]) == 0.0
    assert read(stages=["vote"], complement=True) == pytest.approx(0.100)
    # a record from before the stage, or a run that never recovered
    assert read(stages=["heal_adopt"]) is None
    assert read(stages=["ring"], minus_children=["heal_adopt"]) is None
    assert readers.read(run, {**between, "to": "window.recovered",
                              "stages": ["ring"]}) is None
    assert readers.read({**run, "events": {}},
                        {**between, "stages": ["ring"]}) is None


def test_span_wall_between_walks_the_counted_steps_without_moments():
    """No ``from`` / ``to``: the median over the counted steps, which is
    how ``xchg_preamble_ms`` reads a steady step's skew."""
    def step(i, committed=True):
        return {"phase": "window", "committed": committed, "world": 2,
                "t0": i * 100 * MS, "t1": (i + 1) * 100 * MS}

    spans = []
    for i, waited in enumerate((4, 6, 50, 8)):
        spans += [
            {"stage": "ring", "id": 2 * i, "parent": None,
             "t0_ns": (i * 100 + 10) * MS, "dur_ns": 60 * MS},
            {"stage": "ring_preamble", "id": 2 * i + 1, "parent": 2 * i,
             "t0_ns": (i * 100 + 10) * MS, "dur_ns": waited * MS}]
    run = {"spans": spans, "groups": 2, "events": {},
           "steps": {0: [step(0), step(1), step(2, committed=False),
                         step(3)]}}
    reader = {"kind": "span_wall_between", "stages": ["ring"],
              "minus_children": ["ring_preamble"], "complement": "stages",
              "scale": 1000.0}
    assert readers.read(run, reader) == pytest.approx(6.0)
    assert readers.read({**run, "steps": {0: []}}, reader) is None


def _metric_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]]


@pytest.mark.parametrize("metric", _metric_files())
def test_every_metric_file_loads_and_names_a_reader(metric):
    spec.configure(REPO)
    entry = spec.data("metrics", metric)
    assert entry["what"]
    assert callable(spec.module("readers", entry["reader"]["kind"]).read)


def test_a_recovery_metric_reads_a_moment_the_kill_event_records():
    """``span_wall_between`` and ``event_interval`` name their moments in
    data; a name the event never records would read nothing for ever."""
    spec.configure(REPO)
    with open(spec.find("events", "kill", ".py")) as f:
        recorded = f.read()
    files = [spec.data("metrics", m) for m in _metric_files()]
    moments = {r[k] for r in (e["reader"] for e in files)
               if r["kind"] in ("span_wall_between", "event_interval")
               for k in ("from", "to") if k in r}
    assert moments
    for moment in moments:
        phase, name = moment.split(".", 1)
        assert phase == "window" and "{phase}." + name in recorded, moment


def test_the_unnamed_rest_is_taken_against_every_stage_but_the_bare_waits():
    from torchft_tpu import tracing

    spec.configure(REPO)
    reader = spec.data("metrics", "recover_unnamed_s")["reader"]
    assert reader["complement"] is True
    assert reader["stages"] == [s for s in tracing.STAGES
                                if s not in BARE_WAITS]
