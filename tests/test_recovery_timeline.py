"""A recovery under spans and counters, on the survivor and on the
replacement (docs/design/observability.md, "A recovery, second by second").

One rig: two ``FTTrainer`` groups as threads over the host ring and an
in-process lighthouse. After two joint steps group 1 stops the way a
reclaimed machine does (no farewell, beats stop, sockets close); the same
thread starts a replacement with other weights, which heals from the
survivor and commits. What the two tracers and ``metrics()`` then hold is
what the benchmark's ``recover_*`` / ``replacement_*`` metrics read.

A healer builds its step while it heals (``FTTrainer._build_ahead``): the
same recovery holds the order of the replacement's two threads; fresh
jobs (:func:`run_fresh`) hold who builds nothing ahead; and a healer on a
mocked control plane (:func:`run_failed_heal`) holds a round or a donor
that fails beside the build.
"""

import concurrent.futures
import functools
import threading
import time
import urllib.parse
from unittest.mock import MagicMock, patch

import jax.monitoring
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import conftest
from mockplane import make_manager, quorum_result
from torchft_tpu import HostCommunicator, Lighthouse, Manager
from torchft_tpu import chaos as chaos_mod
from torchft_tpu.checkpointing import CheckpointServer
from torchft_tpu.parallel import FTTrainer
from torchft_tpu.serialization import plan_pytree

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


# What jax reports when a program is lowered and when it is compiled (or
# read from the compile cache); a call that finds its program built fires
# neither.
BUILD_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")


def _trainer(lh, name, fill=0.5, loss_fn=None, groups=1, **manager_kw):
    """An ``FTTrainer`` over the host ring, joined to ``lh``."""
    return FTTrainer(
        loss_fn=loss_fn or (lambda p, b: jnp.sum((b @ p["w"]) ** 2)),
        tx=optax.sgd(1e-2),
        params={"w": jnp.full((3, 4), fill, jnp.float32)},
        manager_factory=lambda load, save: Manager(
            comm=HostCommunicator(timeout_sec=10),
            load_state_dict=load, state_dict=save,
            min_replica_size=groups, replica_id=name,
            lighthouse_addr=lh.address(), rank=0, world_size=1,
            timeout_ms=10_000, quorum_timeout_ms=10_000, **manager_kw))


def _run_groups(lh, what, fns, done):
    """Each of ``fns`` on a thread of its own under the rig's limit;
    ``done`` is set when one fails and when all have ended. Raises the
    first failure, and fails where a thread hung."""
    errors = []

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)
                done.set()
        return run

    threads = [threading.Thread(target=guarded(fn),
                                name=getattr(fn, "__name__", None))
               for fn in fns]
    try:
        with conftest.time_limit(RIG_LIMIT_S, what):
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


def run_recovery():
    """``{"survivor", "replacement", "first"}``: both sides' spans,
    metrics and history at the end, and the replacement's right after its
    first commit; ``"traces"``: how often each trainer's loss was traced;
    ``"builds"``: every lowering and backend compile of the process while
    the rig ran, as ``(thread_id, end_ns)``."""
    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                    join_timeout_ms=2000, quorum_tick_ms=10)
    out = {"traces": {}, "builds": []}
    killed, done = threading.Event(), threading.Event()

    def on_build(event, _secs, **_):
        if event in BUILD_EVENTS:
            out["builds"].append((threading.get_ident(),
                                  time.monotonic_ns()))

    def trainer_of(name, fill):
        out["traces"][name] = 0

        def loss_fn(p, b):
            out["traces"][name] += 1    # the Python body runs per trace
            return jnp.sum((b @ p["w"]) ** 2)

        return _trainer(lh, name, fill, loss_fn)

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

    jax.monitoring.register_event_duration_secs_listener(on_build)
    try:
        _run_groups(lh, "the recovery rig",
                    (survivor, victim_then_replacement), done)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_build)
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
    # The trainer's first dispatch after the heal is the call in which
    # the jitted function's cache grew; what was built ahead of it is
    # another counter's.
    traced = [s for s in _of(first["spans"], "dispatch") if s.get("traced")]
    assert traced and not any(s.get("ahead") for s in traced)
    ahead = [s for s in _of(first["spans"], "dispatch") if s.get("ahead")]
    assert first["metrics"]["dispatch_ahead_count"] == len(ahead) == 1
    assert first["metrics"]["dispatch_ahead_ms_total"] == pytest.approx(
        ahead[0]["dur_ns"] / 1e6, rel=1e-12)
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
                "quorum_ms_total", "dispatch_traced_ms_total",
                "dispatch_ahead_ms_total"):
        assert m[key] > 0, key
    assert got["survivor"]["metrics"]["quorum_changed_count"] >= 2


# ------------------------------------------- a healer builds while it heals

def _end(span):
    return span["t0_ns"] + span["dur_ns"]


def test_the_replacement_builds_its_step_while_it_heals(recovery):
    spans = recovery["replacement"]["spans"]
    (ahead,) = [s for s in _of(spans, "dispatch") if s.get("ahead")]
    (heal,) = _of(spans, "heal")
    traced = [s for s in _of(spans, "dispatch") if s.get("traced")]
    assert ahead["program"] == traced[0]["program"] == "fwd_bwd"
    # Two threads: the build beside the heal, the call after both.
    assert ahead["thread_id"] == traced[0]["thread_id"] != heal["thread_id"]
    assert ahead["t0_ns"] < _end(heal)
    assert _end(ahead) <= traced[0]["t0_ns"] >= _end(heal)
    # Either side of the build the step thread waits for the round: for
    # its answer, then for its end.
    waits = [s for s in _of(spans, "wait_quorum")
             if s["step"] == ahead["step"] and s["parent"] is None]
    assert _end(waits[0]) == ahead["t0_ns"]
    assert _end(ahead) == waits[1]["t0_ns"]
    # One trace, one lowering, one compile: all three in the build, none
    # in the call.
    assert recovery["traces"]["rt_replacement"] == 1
    me = ahead["thread_id"]

    def builds_in(span):
        return [ns for tid, ns in recovery["builds"]
                if tid == me and span["t0_ns"] <= ns <= _end(span)]

    assert len(builds_in(ahead)) == len(BUILD_EVENTS)
    assert builds_in(traced[0]) == []


def test_the_lock_is_handed_over_sooner_for_a_builds_length_only():
    """Bodies in flight share one shortened switch interval; the last one
    out puts back what the first found, and an interval already shorter
    is left alone."""
    import sys
    from torchft_tpu.parallel import step as step_mod
    found = sys.getswitchinterval()
    short = step_mod._BUILD_SWITCH_INTERVAL
    try:
        sys.setswitchinterval(0.005)
        with step_mod._yielding_lock():
            assert sys.getswitchinterval() == pytest.approx(short)
            with step_mod._yielding_lock():
                assert sys.getswitchinterval() == pytest.approx(short)
            assert sys.getswitchinterval() == pytest.approx(short)
        assert sys.getswitchinterval() == pytest.approx(0.005)
        with pytest.raises(RuntimeError):
            with step_mod._yielding_lock():
                raise RuntimeError("a build that fails")
        assert sys.getswitchinterval() == pytest.approx(0.005)
        sys.setswitchinterval(short / 10)
        shorter = sys.getswitchinterval()       # whole microseconds
        with step_mod._yielding_lock():
            assert sys.getswitchinterval() == shorter
        assert sys.getswitchinterval() == shorter
    finally:
        sys.setswitchinterval(found)


def run_fresh(groups, **manager_kw):
    """A fresh job of ``groups`` trainers that start together and take two
    joint steps; each one's snapshot, by its name."""
    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=groups,
                    join_timeout_ms=2000, quorum_tick_ms=10)
    out = {}

    def group(name):
        trainer = _trainer(lh, name, groups=groups, **manager_kw)
        try:
            for _ in range(2):
                _, committed = trainer.train_step(jnp.ones((2, 3)))
                assert committed
            out[name] = _snapshot(trainer)
        finally:
            trainer.shutdown()

    _run_groups(lh, "the fresh job",
                [functools.partial(group, f"fresh_{i}")
                 for i in range(groups)], threading.Event())
    return out


@pytest.mark.parametrize("groups,manager_kw,who", [
    (1, {}, "everyone"),
    (2, {}, "the primary"),
    (2, {"use_async_quorum": False}, "everyone"),
], ids=["one_group", "the_primary_of_two", "sync_quorum"])
def test_who_does_not_heal_beside_its_step_builds_nothing_ahead(
        groups, manager_kw, who):
    got = run_fresh(groups, **manager_kw)
    healers = [n for n, g in got.items() if g["metrics"]["heal_count"]]
    # A fresh job's non-primary groups heal from the primary at step 1.
    assert len(healers) == groups - 1
    for name, g in got.items():
        built = [s for s in _of(g["spans"], "dispatch") if s.get("ahead")]
        if who == "everyone" or name not in healers:
            assert built == [], name
            assert g["metrics"]["dispatch_ahead_count"] == 0, name
            assert g["metrics"]["dispatch_ahead_ms_total"] == 0, name
        else:
            # ... and under an async quorum they heal beside the step
            # thread, which builds meanwhile.
            assert len(built) == 1, name
            assert g["metrics"]["dispatch_ahead_count"] == 1, name


# ----------------------------------- the round fails while the step builds

HEAL_STEP = 20


def run_failed_heal(donor_dies):
    """A fresh trainer whose first round says it heals and then fails:
    the donor's manager cannot be resolved (the round raises at once), or
    (``donor_dies``) the donor's stream hangs up half way and the
    re-quorum offers no other donor. The trainer's loss, once it is being
    traced for the build ahead, holds the build open until the round has
    ended: the failure lands beside the build. From the second round on
    the trainer is alone. Returns its snapshot after four steps, what each
    step returned, and how often the loss was traced."""
    shape = (8, 1024)
    state = {"params": {"w": np.full(shape, 0.25, np.float32)},
             "opt_state": optax.sgd(1e-2).init({"w": np.zeros(shape)})}
    donor_state = {"user": state, "torchft": {"step": HEAL_STEP,
                                              "batches_committed": 40}}
    donor = CheckpointServer(lambda: donor_state, bind_host="127.0.0.1")
    donor.allow_checkpoint(HEAL_STEP)
    if donor_dies:
        netloc = urllib.parse.urlparse(donor.address()).netloc
        chaos_mod.install(chaos_mod.ChaosSchedule(seed=0, endpoints={
            f"heal:{netloc}": chaos_mod.EndpointChaos(
                kill_after_bytes=plan_pytree(donor_state).total_len // 2)}))

    heals = quorum_result(quorum_id=1, max_step=HEAL_STEP, max_rank=None,
                          max_world_size=1, replica_rank=1,
                          replica_world_size=2, heal=True,
                          recover_manager_address="donor")
    moved_on = quorum_result(quorum_id=1, max_step=HEAL_STEP + 5,
                             max_rank=1, max_world_size=2, replica_rank=1,
                             replica_world_size=2, heal=False)
    alone = quorum_result(quorum_id=2, max_step=1, max_rank=0,
                          max_world_size=1, replica_rank=0,
                          replica_world_size=1, heal=False)
    steps, rounds = [], []

    def quorum(**_):
        # The first step's own round; then, while that step lasts, the
        # heal's search for another donor; then the later steps' rounds.
        rounds.append(len(steps))
        if len(rounds) == 1:
            return heals
        return alone if steps else moved_on

    client = MagicMock()
    client.quorum.side_effect = quorum
    client.should_commit.side_effect = lambda **kw: kw["should_commit"]

    def resolve(addr, **_):
        if not donor_dies:
            raise ConnectionError(f"{addr} is gone")
        peer = MagicMock()
        peer.checkpoint_address.return_value = donor.address()
        return peer

    traces, held = [], []

    def loss_fn(p, b):
        traces.append(trainer.manager.current_step())
        if len(traces) == 1:
            round_ = trainer.manager._quorum_future
            held.append(concurrent.futures.wait([round_], RIG_LIMIT_S / 2))
        return jnp.sum((b * p["w"]) ** 2)

    trainer = FTTrainer(
        loss_fn=loss_fn, tx=optax.sgd(1e-2),
        params={"w": jnp.full(shape, 9.0, jnp.float32)},
        manager_factory=lambda load, save: make_manager(
            client, load_state_dict=load, state_dict=save,
            min_replica_size=1, replica_id="rt_failed"))
    try:
        with conftest.time_limit(RIG_LIMIT_S, "the failed heal"), \
                patch("torchft_tpu.manager.ManagerClient",
                      side_effect=resolve):
            for _ in range(4):
                _, committed = trainer.train_step(jnp.ones(shape))
                steps.append((committed, trainer.manager.errored()))
        got = _snapshot(trainer)
        got["w"] = np.asarray(trainer.params["w"])
    finally:
        trainer.shutdown()
        chaos_mod.uninstall()
        donor.shutdown()
    (done, _pending), = held
    assert done, "the round outlasted the build it was to fail beside"
    return got, steps, traces


@pytest.mark.parametrize("donor_dies", [False, True],
                         ids=["the_round_raises", "the_donor_goes_away"])
def test_a_heal_that_fails_beside_the_build_aborts_the_step_and_no_more(
        donor_dies):
    got, steps, traces = run_failed_heal(donor_dies)
    # The step aborts and latches; nothing of the donor's was adopted.
    (committed, latched), after = steps[0], steps[1:]
    assert not committed and latched is not None
    assert got["metrics"]["heal_count"] == 1
    assert got["metrics"]["heal_adopt_ms_total"] == 0
    assert (got["metrics"]["heal_bytes_total"] > 0) == donor_dies
    assert not np.any(got["w"] == 0.25)
    # The next step commits, and every one after.
    assert [c for c, _ in after] == [True] * 3
    assert [e for _, e in after] == [None] * 3
    # What was built ahead was built once; alone, the trainer builds the
    # fused step (its loss traced a second time) and runs that.
    dispatches = _of(got["spans"], "dispatch")
    assert [s.get("ahead", False) for s in dispatches] \
        == [True] + [False] * (len(dispatches) - 1)
    assert got["metrics"]["dispatch_ahead_count"] == 1
    assert len(traces) == 2
    fused = [s for s in dispatches if s["program"] == "fused"]
    assert fused and fused[0].get("traced")
    assert dispatches[-1]["program"] == "fused"
    assert not any(s["program"] == "fwd_bwd" and s["t0_ns"] > fused[0]["t0_ns"]
                   for s in dispatches)

