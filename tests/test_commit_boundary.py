"""The commit boundary's one rule and one order (tier-1;
docs/design/commit_boundary.md).

(a) Every feature that changes state at a boundary is refused by the
same rule (:class:`torchft_tpu.boundary.Boundary`), with the reason
string, the counter and the event it has always had, and lands at the
next clean boundary. (b) ``Manager.step`` and ``Manager.should_commit``
walk one ordered tuple of features and name none of them.
"""

import inspect
import os
import re
from concurrent.futures import Future
from unittest.mock import MagicMock

import pytest

import torchft_tpu
from mockplane import boundary, make_manager, mock_client, quorum_result
from torchft_tpu.boundary import BoundaryFeature
from torchft_tpu.manager import Manager, PreemptedExit
from torchft_tpu.policy import POLICIES

# ------------------------------------------------------- unclean states
# Each: (set it on a manager one clean committed boundary old, clear it).


def _set_healing(m, on):
    with m._metrics_lock:
        m._healing = on


def _set_deferred(m, on):
    if on:
        fut = Future()
        fut.set_result({"g": 1})
        m.stage_deferred(fut)
    else:
        m.drain_deferred()


def _set_errored(m, on):
    m._errored = RuntimeError("injected") if on else None


def _set_aborted(m, on):
    m._should_step = not on


def _set_quarantined(m, on):
    with m._metrics_lock:
        m._sdc_quarantined = on


STATES = {
    "healing": _set_healing,
    "deferred in flight": _set_deferred,
    "errored": _set_errored,
    "vote aborted": _set_aborted,
    "quarantined": _set_quarantined,
}

# ------------------------------------------------------------- features
# Each: manager kwargs, arm(m) -> ctx, attempt(m, ctx) -> landed?, the
# event a refusal logs, the counter it counts (None: the event log
# alone), and the states that do NOT refuse it (deliberately).


def _drain_attempt(m, ctx):
    try:
        m._drain.at_step_edge(m._should_step)
    except PreemptedExit:
        return True
    return False


def _rebalance_arm(m):
    with m._metrics_lock:
        m._share.table = "testgroup=0.7500"


def _rebalance_attempt(m, ctx):
    m._share.post_vote(True)
    return m.rebalance_fraction() == 0.75


def _ram_arm(m):
    m.enable_ram_tier(peers=1)
    m._ram.replicator.shutdown()
    m._ram.replicator = MagicMock()
    m._ram.replicator.metrics.return_value = {}


def _writer():
    w = MagicMock()
    w.metrics.return_value = {}
    w.last_error.return_value = ""
    return w


def _publisher():
    p = MagicMock()
    p.metrics.return_value = {}
    p.publish.return_value = 3
    return p


LANDING = {"quarantined", "vote aborted"}  # no obstacle to a landing
FEATURES = {
    "preemption drain": dict(
        arm=lambda m: m.request_preemption(60.0),
        attempt=_drain_attempt, event="preempt_deferred",
        counter="preempt_drain_deferrals_total",
        passes={"quarantined"}),
    "capacity landing": dict(
        kwargs=dict(degraded_mode=True),
        attempt=lambda m, ctx: m.request_degrade(0.5),
        event="degrade_refused", counter=None, passes=LANDING),
    "rebalance landing": dict(
        kwargs=dict(rebalance=True), arm=_rebalance_arm,
        attempt=_rebalance_attempt, event="rebalance_deferred",
        counter="rebalance_deferred_total", passes=LANDING),
    "policy switch": dict(
        attempt=lambda m, ctx: m.set_policy(POLICIES["sync-bf16"]),
        event="policy_switch_refused", counter="policy_switch_refusals",
        passes=LANDING),
    "RAM replication": dict(
        arm=_ram_arm,
        attempt=lambda m, ctx: m.replicate_ram() is not None,
        event="ram_replicate_skip", counter="ram_replicate_skipped",
        settled=True),
    "save_durable": dict(
        arm=lambda m: _writer(),
        attempt=lambda m, w: m.save_durable(w, "/nowhere") is not None,
        event="ckpt_skip", counter="ckpt_save_skipped", settled=True),
    "publish": dict(
        arm=lambda m: _publisher(),
        attempt=lambda m, p: m.publish(p) is not None,
        event="publish_skip", counter="publish_skipped", settled=True),
}


@pytest.mark.parametrize("state", sorted(STATES))
@pytest.mark.parametrize("feature", sorted(FEATURES))
def test_one_refusal_rule(feature, state):
    spec = FEATURES[feature]
    m = make_manager(**spec.get("kwargs", {}))
    try:
        assert boundary(m)  # one clean committed boundary behind us
        ctx = spec["arm"](m) if "arm" in spec else None
        STATES[state](m, True)
        if state in spec.get("passes", ()):
            landed = spec["attempt"](m, ctx)
            assert landed, "no obstacle to this feature"
            assert not [e for e in m.history()
                        if e["event"] == spec["event"]]
            return
        assert not spec["attempt"](m, ctx)
        refusals = [e for e in m.history() if e["event"] == spec["event"]]
        assert len(refusals) == 1
        mx = m.metrics()
        if spec["counter"] is not None:
            assert mx[spec["counter"]] == 1
        if spec.get("settled"):
            # "is this a settled committed step's": the five facts.
            field = {"healing": "healing", "deferred in flight": "deferred",
                     "errored": "errored", "quarantined": "quarantined"}
            facts = {k: refusals[0][k] for k in
                     ("healing", "errored", "committed", "deferred",
                      "quarantined")}
            expect = dict(healing=False, errored=False, committed=True,
                          deferred=False, quarantined=False)
            if state == "vote aborted":
                expect["committed"] = False
            else:
                expect[field[state]] = True
            assert facts == expect
            assert mx["sdc_refusals_total"] == (state == "quarantined")
        else:
            # "may a change land here": the reason, by its string.
            assert refusals[0]["why"] == state
        # ... and it lands at the next clean boundary.
        STATES[state](m, False)
        landed = spec["attempt"](m, ctx)
        assert landed
        assert len([e for e in m.history()
                    if e["event"] == spec["event"]]) == 1
    finally:
        m.shutdown()  # idempotent: a landed drain has shut it down


def test_forced_policy_adoption_ignores_a_latched_error():
    """The rule's one argument beside the vote: the coordinated
    adoption lands over ``errored`` (the step that failed is the reason
    the fleet escalated), never over a heal or a deferred step."""
    m = make_manager()
    try:
        assert boundary(m)
        _set_errored(m, True)
        _set_healing(m, True)
        assert not m.set_policy(POLICIES["sync-bf16"], _force=True)
        assert m.history()[-1]["why"] == "healing"
        _set_healing(m, False)
        assert m.set_policy(POLICIES["sync-bf16"], _force=True)
    finally:
        m.shutdown()


def test_the_reasons_are_written_once():
    """The four reason strings appear in one function of the package."""
    root = os.path.dirname(torchft_tpu.__file__)
    hits = {}
    for name in sorted(os.listdir(root)):
        if not name.endswith(".py"):
            continue
        src = open(os.path.join(root, name)).read()
        for reason in ('"healing"', '"deferred in flight"', '"errored"',
                       '"vote aborted"'):
            if re.search(r"append\(\s*" + re.escape(reason), src):
                hits.setdefault(name, []).append(reason)
    assert list(hits) == ["boundary.py"] and len(hits["boundary.py"]) == 4
    src = open(os.path.join(root, "manager.py")).read()
    assert src.count("healing = self._healing") <= 2


# ---------------------------------------------------------- (b) the order


class _Recorder(BoundaryFeature):
    def __init__(self, name, log):
        self.name, self.log = name, log

    def at_step_edge(self, committed):
        self.log.append((self.name, "step_edge", committed))

    def pre_vote(self):
        self.log.append((self.name, "pre_vote"))

    def post_vote(self, decision):
        self.log.append((self.name, "post_vote", decision))


def test_the_boundary_walks_one_ordered_tuple():
    client = mock_client(quorum_result(max_world_size=2,
                                       replica_world_size=2))
    client.should_commit.side_effect = [True, False, True]
    m = make_manager(client)
    log = []
    try:
        n = len(m._features)
        m._features = tuple(_Recorder(i, log) for i in range(n))
        votes = [boundary(m) for _ in range(3)]
    finally:
        m.shutdown()
    assert votes == [True, False, True]
    expect = []
    last = True  # a fresh manager starts as if the last step committed
    for vote in votes:
        expect += [(i, "step_edge", last) for i in range(n)]
        expect += [(i, "pre_vote") for i in range(n)]
        expect += [(i, "post_vote", vote) for i in range(n)]
        last = vote
    assert log == expect  # every slot once a step, in the tuple's order


def test_the_stated_order_and_no_feature_named():
    m = make_manager()
    try:
        order = [type(f).__name__ for f in m._features]
        names = [k for k, v in vars(m).items()
                 if any(v is f for f in m._features)]
    finally:
        m.shutdown()
    assert order == ["PreemptionDrain", "RamTier", "SdcBand", "SlowBand",
                     "PolicySwitch", "BatchShare"]
    assert len(names) == len(order)
    for fn in (Manager.step, Manager.should_commit):
        src = inspect.getsource(fn)
        assert "self._features" in src
        for name in names:
            assert f"self.{name}." not in src, (fn.__name__, name)
