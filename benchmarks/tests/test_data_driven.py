"""A model family, an event kind, a reader, a configuration, a traffic mix, a
cell and per-layer metrics added as new files (in a directory of their own)
are found and run in rehearsal with no edit to a file that is there; and the
hard stop fails loudly when the Manager's internals have moved."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from harness import readers, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

# A model family that is not a transformer block: token embedding, one
# hidden matrix with a tanh, untied head. The program's side in bfloat16,
# the plain reference in float32.
NEW_BUILDER = """
import jax, jax.numpy as jnp
REHEARSE = dict(hidden_size=64, vocab_size=256)
REHEARSE_SEQ = 32
CONTROLS = {"fp8_matmul": {"matmul": "float8_e4m3/forward"}}
PROBES = {}

def param_shapes(cfg):
    e, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    return {"params": {"embed": {"embedding": (v, e)},
                       "mix": {"kernel": (e, e)},
                       "lm_head": {"kernel": (e, v)}}}

def _loss(p, tokens, mm):
    x = p["embed"]["embedding"][tokens[:, :-1]]
    x = jnp.tanh(mm(x) @ mm(p["mix"]["kernel"]))
    logp = jax.nn.log_softmax(mm(x) @ mm(p["lm_head"]["kernel"]), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

def make_loss_fn(cfg, seq, interpret):
    to16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    return lambda params, batch: _loss(params["params"], batch["tokens"], to16)

def reference_loss(params, tokens, cfg, rounding=None):
    with jax.default_matmul_precision("highest"):
        return _loss(params["params"], tokens,
                     (rounding or {}).get("matmul", lambda x: x))

def train_flops_per_token(cfg, seq):
    e, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    return 3.0 * (2 * e * e + 2 * e * v)
"""

# An event kind that is not a kill: one group is late for one step.
NEW_EVENT = """
import time

def own_threads(spec):
    return set()

def designed_abort_window(spec, events, phase="window"):
    a, b = events.get(f"{phase}.late_from"), events.get(f"{phase}.late_to")
    return (a, b) if a is not None and b is not None else None

class Event:
    def __init__(self, spec, host, gi, phase):
        self.spec, self.host, self.gi, self.phase = spec, host, gi, phase
        self.joint, self.fired, self.settled = 0, False, False

    def before_step(self, st):
        if (not self.fired and self.joint >= int(self.spec["after_joint_step"])):
            self.fired = True
            if self.gi == int(self.spec["group"]):
                self.host.event(f"{self.phase}.late_from", time.monotonic_ns())
                time.sleep(float(self.spec["seconds"]))
                self.host.event(f"{self.phase}.late_to", time.monotonic_ns())

    def after_step(self, st, r, is_joint, joint):
        self.joint = joint
        self.settled = self.fired and is_joint
        return False
"""

NEW_READER = """
from harness.readers import counted_steps

def read(run, args):
    return float(len(counted_steps(run))) or None
"""


def test_new_files_make_a_new_cell(tmp_path):
    root = tmp_path / "tree"
    for sub in ("configs", "traffic", "metrics", "models", "events",
                "readers", "kernels", "drivers"):
        shutil.copytree(os.path.join(BENCH, sub), root / "benchmarks" / sub)
    shutil.copy(os.path.join(BENCH, "limits.json"), root / "benchmarks")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # What a later PR brings: a directory of its own with files, and entries.
    new = root / "later_pr"
    for sub, name, text in (("models", "tiny_lm.py", NEW_BUILDER),
                            ("events", "late.py", NEW_EVENT),
                            ("readers", "step_count.py", NEW_READER)):
        os.makedirs(new / sub, exist_ok=True)
        (new / sub / name).write_text(textwrap.dedent(text))
    for sub in ("configs", "traffic", "metrics"):
        os.makedirs(new / sub)
    json.dump({"builder": "tiny_lm", "hidden_size": 256, "vocab_size": 4096,
               "initializer_range": 0.02},
              open(new / "configs/newmodel.json", "w"))
    mix = json.load(open(root / "benchmarks/traffic/steady-2g.json"))
    mix["optimizer"] = {"name": "adamw", "lr": 1e-4}
    mix["events"] = [{"kind": "late", "group": 1, "after_joint_step": 1,
                      "seconds": 0.3}]
    json.dump(mix, open(new / "traffic/newmix.json", "w"))
    json.dump({"what": "put wall", "reader": {
        "kind": "span_union", "stage": "put", "stat": "median"}},
        open(new / "metrics/xchg_put_ms.json", "w"))
    json.dump({"what": "how long the late group was late", "reader": {
        "kind": "event_interval", "from": "window.late_from",
        "to": "window.late_to"}}, open(new / "metrics/late_s.json", "w"))
    json.dump({"what": "counted steps", "reader": {"kind": "step_count"}},
              open(new / "metrics/counted_steps.json", "w"))
    bench["paths"].append("later_pr")
    bench["configs"].append({
        "name": "newmodel", "source": "test",
        "file": "later_pr/configs/newmodel.json", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "newmodel.newmix", "config": "newmodel",
                               "traffic": "newmix", "chips": 1, "why": "t"})
    for name, unit in (("xchg_put_ms", "ms"), ("late_s", "s"),
                       ("counted_steps", "steps"), ):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": "cross-group exchange",
            "moves": "tokens_per_s", "workloads": ["newmodel.newmix"]})
    bench["per_layer"].append({     # a metric that is there, in the new cell
        "name": "mfu_pct.new", "unit": "%", "better": "higher",
        "source": "host_clock", "layer": "model step",
        "moves": "tokens_per_s", "workloads": ["newmodel.newmix"]})
    shutil.copy(root / "benchmarks/metrics/mfu_pct.json",
                new / "metrics/mfu_pct.new.json")
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "newmodel.newmix", "--seed", str(2**31 + 17), "--seconds", "1",
         "--trace", "1", "--rehearse", "--root", str(root)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, out.stdout[-3000:]
    assert result["device"]["platform"] == "cpu"     # never a TPU number
    got = result["metrics"]
    assert got["xchg_put_ms"]["value"] > 0
    assert 0.3 <= got["late_s"]["value"] < 0.6            # the new event ran
    assert got["counted_steps"]["value"] >= 3             # the new reader
    assert "mfu_pct.new" not in got        # no chip, no peak: left out
    assert "quorum_ms" not in got          # not listed for the cell


def test_without_a_tpu_and_without_rehearse_there_is_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mistral-7b.steady-1g", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_hard_stop_fails_loudly_when_the_internals_moved():
    class Moved:
        _manager_server = None

    with pytest.raises(AttributeError, match="kill.py"):
        spec.module("events", "kill").hard_stop(Moved())


def test_counter_and_timing_readers():
    step = {"phase": "window", "committed": True, "world": 2, "t0": 0,
            "t1": 1, "timings": {"other": 0.002, "allreduce_wait": 4.0}}
    run = {"groups": 2, "steps": {0: [step, {**step, "world": 1},
                                      {**step, "committed": False}]},
           "events_spec": [{"kind": "kill", "victim": 1}],
           "events": {"window.kill": 5_000_000_000,
                      "window.survivor_commit": 7_500_000_000},
           "counters": {"begin.0": {"a_ms": 10.0, "n": 2.0},
                        "end.0.0": {"a_ms": 40.0, "n": 5.0},
                        "end.1.1": {"heal_ms_total": 1.0},
                        "end.1.2": {"heal_ms_total": 8000.0,
                                    "heal_bytes_total": 2.0e9}}}
    r = readers.read
    assert r(run, {"kind": "step_timing", "key": "other", "scale": 1000}) == 2.0
    assert r(run, {"kind": "counter_delta", "key": "a_ms", "per": "n"}) == 10.0
    assert r(run, {"kind": "counter_delta", "key": "heal_ms_total",
                   "on": "replacement", "scale": 0.001}) == 8.0
    assert r(run, {"kind": "counter_delta", "key": "heal_bytes_total",
                   "per": "heal_ms_total", "on": "replacement",
                   "scale": 1e-6}) == pytest.approx(0.25)
    assert r(run, {"kind": "event_interval", "from": "window.kill",
                   "to": "window.survivor_commit"}) == 2.5
    assert r(run, {"kind": "event_interval", "from": "window.kill",
                   "to": "nothing"}) is None
    with pytest.raises(FileNotFoundError, match="readers/nonesuch.py"):
        r(run, {"kind": "nonesuch"})
