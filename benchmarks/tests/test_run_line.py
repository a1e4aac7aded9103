"""What PR 36 added to a run: the count of stalled steps as a plain function
of the window's walls, the restart with a traffic mix's environment, and
every traffic mix of ``BENCHMARK.json`` still loading through
``harness/spec.py`` with an environment a process can be started with."""

import json
import os

import pytest

import run as bench
from harness import readers, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


@pytest.mark.parametrize("walls, want", [
    ([], 0),
    ([1.0] * 40, 0),
    ([1.0] * 20 + [16.9] + [1.0] * 20, 1),             # one stall
    ([1.0, 1.1, 2.9, 1.0, 1.05], 0),                   # slow, not stalled
    ([1.0] * 30 + [13.8, 3.5], 2),
    ([0.371] * 14 + [1.296] + [0.371] * 100, 1),       # the profiler's stop
])
def test_stalled_steps(walls, want):
    assert bench.stalled_steps(walls) == want


@pytest.mark.parametrize("have, want, restarts", [
    ({}, {}, False),                                   # a one-group mix
    ({"A": "1"}, {"A": "1"}, False),                   # started with it
    ({}, {"A": "1"}, True),
    ({"A": "0"}, {"A": "1"}, True),
    ({"A": "1"}, {"A": "1", "MALLOC_TOP_PAD_": "268435456"}, True),
])
def test_restart_with_the_mix_environment(monkeypatch, have, want, restarts):
    """One ``execv`` of the process's own command line with the mix's
    environment and the first start's clock; none where the process has
    it."""
    calls = []
    environ = dict(have)
    line = ["python3", "-X", "dev", "benchmarks/run.py", "--workload", "w"]
    monkeypatch.setattr(bench.os, "environ", environ)
    monkeypatch.setattr(bench.sys, "orig_argv", line)
    monkeypatch.setattr(bench.os, "execv",
                        lambda exe, argv: calls.append((exe, argv)))
    bench.start_with(want)
    assert bool(calls) is restarts
    if restarts:
        assert calls == [(bench.sys.executable,
                          line + ["--t-process", str(bench.T_PROCESS_NS)])]
        assert all(environ[k] == v for k, v in want.items())


def _cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_every_traffic_mix_loads(cell):
    mix = spec.Cell(cell, REPO).mix
    assert int(mix["warmup"]["joint_steps"]) >= 2     # the oracle's steps
    assert all(isinstance(k, str) and isinstance(v, str)
               for k, v in mix["env"].items())
    if any(k.startswith("MALLOC_") for k in mix["env"]):
        assert "why_malloc_env" in mix                # a setting has a reason
    if int(mix["groups"]) == 1:
        assert mix["env"] == {}                       # and so no restart


@pytest.mark.parametrize("reader, want", [
    ({"key": "a_total", "per": "committed_steps"}, 3.0),
    ({"key": "b_total", "per": "committed_steps"}, None),   # no such counter
    ({"key": "a_total", "per": "b_total"}, None),
    ({"key": "b_total"}, None),
])
def test_a_counter_the_program_lacks_reads_as_nothing(reader, want):
    """A counter metric added with its counter is read on the parent commit
    too, whose snapshots lack the key: nothing to read, no ``KeyError``."""
    run = {"counters": {"begin.0": {"a_total": 2.0, "committed_steps": 4.0},
                        "end.0.0": {"a_total": 14.0, "committed_steps": 8.0}}}
    assert readers.read(run, {"kind": "counter_delta", **reader}) == want
