"""A recovery under spans and counters, on the survivor and on the
replacement (docs/design/observability.md, "A recovery, second by second").

One rig: two ``FTTrainer`` groups as threads over the host ring and an
in-process lighthouse. After two joint steps group 1 stops the way a
reclaimed machine does (no farewell, beats stop, sockets close); the same
thread starts a replacement with other weights, which heals from the
survivor and commits. What the two tracers and ``metrics()`` then hold is
what the benchmark's ``recover_*`` / ``replacement_*`` metrics read.
"""

import threading

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import conftest
from torchft_tpu import HostCommunicator, Lighthouse, Manager
from torchft_tpu.parallel import FTTrainer

pytestmark = [pytest.mark.obs, conftest.requires_native()]

RIG_LIMIT_S = 60.0      # the rig's own limit: a few seconds when healthy
JOINT_BEFORE = 2        # joint steps before the stop
JOINT_AFTER = 2         # joint steps the replacement takes before it ends


def _hard_stop(manager):
    """As ``benchmarks/events/kill.py``: nothing says goodbye."""
    manager._manager_server.hard_stop()
    manager._ckpt_server.shutdown()
    manager._comm.shutdown()
    manager._executor.shutdown(wait=False, cancel_futures=True)
    manager._put_executor.shutdown(wait=False)


def _snapshot(trainer):
    m = trainer.manager
    return {"metrics": m.metrics(), "history": m.history(),
            "spans": m.tracer().spans()}


def run_recovery():
    """``{"survivor", "replacement", "first"}``: both sides' spans,
    metrics and history at the end, and the replacement's right after its
    first commit."""
    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                    join_timeout_ms=2000, quorum_tick_ms=10)
    out, errors = {}, []
    killed, done = threading.Event(), threading.Event()

    def trainer_of(name, fill):
        return FTTrainer(
            loss_fn=lambda p, b: jnp.sum((b @ p["w"]) ** 2),
            tx=optax.sgd(1e-2),
            params={"w": jnp.full((3, 4), fill, jnp.float32)},
            manager_factory=lambda load, save: Manager(
                comm=HostCommunicator(timeout_sec=10),
                load_state_dict=load, state_dict=save,
                min_replica_size=1, replica_id=name,
                lighthouse_addr=lh.address(), rank=0, world_size=1,
                timeout_ms=10_000, quorum_timeout_ms=10_000))

    def joint(trainer, committed):
        return committed and trainer.manager.num_participants() == 2

    def survivor():
        trainer = trainer_of("rt_survivor", 0.5)
        try:
            batch = jnp.ones((2, 3))
            while not done.is_set():
                # After the replacement has left, a step may abort.
                trainer.train_step(batch)
            out["survivor"] = _snapshot(trainer)
            out["survivor"]["w"] = np.asarray(trainer.params["w"])
        finally:
            trainer.shutdown()

    def victim_then_replacement():
        batch = jnp.full((2, 3), 2.0)
        trainer = trainer_of("rt_victim", 0.5)
        try:
            taken = 0
            while taken < JOINT_BEFORE:
                taken += joint(trainer, trainer.train_step(batch)[1])
            _hard_stop(trainer.manager)
            killed.set()
            # Other weights: what it commits with must be the survivor's.
            trainer = trainer_of("rt_replacement", 9.0)
            taken = 0
            while taken < JOINT_AFTER:
                _, committed = trainer.train_step(batch)
                if committed and "first" not in out:
                    out["first"] = _snapshot(trainer)
                taken += joint(trainer, committed)
            out["replacement"] = _snapshot(trainer)
            out["replacement"]["w"] = np.asarray(trainer.params["w"])
        finally:
            done.set()
            trainer.shutdown()

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)
                done.set()
        return run

    threads = [threading.Thread(target=guarded(fn), name=fn.__name__)
               for fn in (survivor, victim_then_replacement)]
    try:
        with conftest.time_limit(RIG_LIMIT_S, "the recovery rig"):
            for t in threads:
                t.start()
            for t in threads:
                t.join(RIG_LIMIT_S)
    finally:
        done.set()
        lh.shutdown()
    if errors:
        raise errors[0]
    assert not any(t.is_alive() for t in threads), "a group hung"
    assert killed.is_set()
    return out


@pytest.fixture(scope="module")
def recovery():
    return run_recovery()


def _of(spans, stage):
    return [s for s in spans if s["stage"] == stage]


def test_the_replacement_commits_with_the_survivors_weights(recovery):
    assert recovery["replacement"]["metrics"]["heal_count"] == 1
    assert not np.any(recovery["replacement"]["w"] == 9.0)


def test_the_survivor_waits_for_the_cut_then_reconfigures(recovery):
    spans = recovery["survivor"]["spans"]
    # The rounds after the stop that changed the quorum: the shrunken
    # one, with the healer in it already unless it came late.
    cuts = [s for s in _of(spans, "quorum")
            if s["changed"] and s["step"] > JOINT_BEFORE]
    assert cuts and any(s["world"] == 2 for s in cuts), _of(spans, "quorum")
    for cut in cuts:
        assert cut["heal"] is False
        after = [s for s in _of(spans, "reconfigure")
                 if s["t0_ns"] >= cut["t0_ns"] + cut["dur_ns"]]
        assert after and after[0]["thread_id"] == cut["thread_id"]
        assert after[0]["quorum_id"] == cut["quorum_id"]
        assert after[0]["world"] == cut["world"]
        assert {"rank", "recovery"} <= set(after[0])
    # Steady rounds change nothing and say so.
    assert any(not s["changed"] for s in _of(spans, "quorum"))


def test_a_preamble_is_a_child_of_its_lanes_ring_span(recovery):
    spans = recovery["survivor"]["spans"]
    rings = {s["id"]: s for s in _of(spans, "ring")}
    preambles = _of(spans, "ring_preamble")
    assert preambles
    for p in preambles:
        ring = rings[p["parent"]]
        assert ring["lane"] == p["lane"]
        assert ring["thread_id"] == p["thread_id"]
        assert ring["t0_ns"] <= p["t0_ns"]
        assert p["t0_ns"] + p["dur_ns"] <= ring["t0_ns"] + ring["dur_ns"]


def test_the_replacement_records_its_rejoin(recovery):
    stages = {s["stage"] for s in recovery["replacement"]["spans"]}
    assert {"quorum", "reconfigure", "heal", "heal_adopt"} <= stages
    adopt = _of(recovery["replacement"]["spans"], "heal_adopt")[0]
    heal = _of(recovery["replacement"]["spans"], "heal")[0]
    assert adopt["t0_ns"] >= heal["t0_ns"] + heal["dur_ns"]
    assert adopt["thread_id"] != heal["thread_id"]   # step thread, not quorum


def test_the_replacements_counters_account_for_its_first_commit(recovery):
    first = recovery["first"]["metrics"]
    for key in ("quorum_changed_ms_total", "reconfigure_ms_total",
                "heal_adopt_ms_total", "join_first_commit_ms"):
        assert first[key] > 0, key
    assert first["quorum_changed_count"] >= 1
    assert first["join_first_commit_ms"] >= (
        first["quorum_changed_ms_total"] + first["reconfigure_ms_total"]
        + first["heal_adopt_ms_total"])
    # Set once: the commits after the first do not move it.
    last = recovery["replacement"]["metrics"]
    assert last["committed_steps"] > first["committed_steps"]
    assert last["join_first_commit_ms"] == first["join_first_commit_ms"]


def test_first_commit_is_one_line_of_the_history(recovery):
    events = [e for e in recovery["replacement"]["history"]
              if e["event"] == "first_commit"]
    assert len(events) == 1
    e, first = events[0], recovery["first"]["metrics"]
    assert e["ms"] == round(first["join_first_commit_ms"], 1)
    assert e["heal_ms"] == round(first["heal_ms_total"], 1)
    assert {"quorum_changed_ms", "reconfigure_ms", "heal_adopt_ms"} <= set(e)
    assert sum(1 for e in recovery["survivor"]["history"]
               if e["event"] == "first_commit") == 1


def test_each_counter_is_its_spans_own_stamps(recovery):
    """One set of clock reads: the span's and the counter's."""
    first = recovery["first"]
    for stage, key in (("reconfigure", "reconfigure_ms_total"),
                       ("heal_adopt", "heal_adopt_ms_total")):
        spans = _of(first["spans"], stage)
        assert first["metrics"][key] == pytest.approx(
            sum(s["dur_ns"] for s in spans) / 1e6, rel=1e-12), stage
    # The trainer's first dispatch traces its program, after the heal.
    traced = [s for s in _of(first["spans"], "dispatch") if s.get("traced")]
    assert traced
    assert first["metrics"]["dispatch_traced_ms_total"] == pytest.approx(
        sum(s["dur_ns"] for s in traced) / 1e6, rel=1e-12)
    changed = [s for s in _of(first["spans"], "quorum") if s["changed"]]
    assert first["metrics"]["quorum_changed_ms_total"] == pytest.approx(
        sum(s["dur_ns"] for s in changed) / 1e6, rel=1e-12)
    assert first["metrics"]["quorum_ms_total"] == pytest.approx(
        sum(s["dur_ns"] for s in _of(first["spans"], "quorum")) / 1e6,
        rel=1e-12)


def test_with_tracing_off_the_counters_fill_and_no_span_is_kept(monkeypatch):
    monkeypatch.setenv("TORCHFT_TRACING", "0")
    got = run_recovery()
    for side in ("survivor", "replacement"):
        assert got[side]["spans"] == []
        assert got[side]["metrics"]["trace_spans_total"] == 0
    m = got["replacement"]["metrics"]
    for key in ("quorum_changed_ms_total", "reconfigure_ms_total",
                "heal_adopt_ms_total", "join_first_commit_ms",
                "quorum_ms_total", "dispatch_traced_ms_total"):
        assert m[key] > 0, key
    assert got["survivor"]["metrics"]["quorum_changed_count"] >= 2
