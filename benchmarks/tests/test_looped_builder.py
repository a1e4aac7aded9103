"""Builder ``looped_dense_decoder`` and what PR 56 added beside it: the
configuration file against the catalog's row, the parameter count against
the tree and a hand count, operation counts and the new kernel file against
a hand count, the new metrics' patterns against names pinned from a traced
run on the chip, and the cell itself found and run in rehearsal."""

import json
import math
import os
import re
import subprocess
import sys

import jax
import pytest

from harness import readers, spec
from harness.peaks import peaks_for

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "ouro-2.6b.steady-1g-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
V5E = "TPU v5 lite"
NEW = ["attn_loop_roofline", "loop_body_device_ms",
       "loop_exit_loss_device_ms", "loop_passes", "loop_expected_exit_milli"]


@pytest.fixture(scope="module")
def bench():
    return spec.configure(REPO)


@pytest.fixture(scope="module")
def cfg(bench):
    return spec.Cell(CELL, REPO).config


@pytest.fixture(scope="module")
def M(bench):
    return spec.module("models", "looped_dense_decoder")


@pytest.fixture(scope="module")
def names():
    with open(os.path.join(BENCH, "tests/looped_op_names.json")) as f:
        return json.load(f)


def test_param_count_is_the_trees_size_from_shapes_only(cfg, M):
    shapes = jax.tree_util.tree_leaves(
        M.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in shapes) == M.param_count(cfg)
    # one set of layers whatever the passes: a six-layer four-norm dense
    # model's 69 leaves and the gate's one (the bias its last row)
    assert len(shapes) == 6 * 11 + 3 + 1 == 70
    # by hand (ISSUE 56's arithmetic)
    attention, mlp, norms = 4 * 2048 * 2048, 3 * 2048 * 5632, 4 * 2048
    assert (attention, mlp, norms) == (16_777_216, 34_603_008, 8_192)
    layer = attention + mlp + norms
    assert layer == 51_388_416
    tables = 2 * 49_152 * 2048
    assert tables == 201_326_592
    assert 6 * layer + tables + 2048 + 2049 == 509_661_185
    assert M.param_count(cfg) == 509_661_185
    # the fallback the ISSUE names (layers 0-4), not needed
    assert M.param_count({**cfg, "num_hidden_layers": 5}) == 458_272_769
    # the whole model
    assert M.param_count({**cfg, "num_hidden_layers": 48}) == 2_667_974_657


def test_operation_counts_by_hand(cfg, M):
    proj = 2 * 2048 * 3 * 2048 + 2 * 2048 * 2048
    mlp = 3 * 2 * 2048 * 5632
    scores = 2 * (2 * 8192 * 128 * 16) / 2
    head = 2 * 2048 * 49_152
    total = M.forward_flops_per_token(cfg, 8192)
    # four passes of the body and of attention, four heads, three gated
    # exits' dot products
    assert total == 4 * (6 * (proj + mlp + scores) + head) + 3 * 2 * 2048
    assert 4.07e9 < total < 4.08e9
    assert M.train_flops_per_token(cfg, 8192) == 3 * total
    step = 8192 * M.train_flops_per_token(cfg, 8192)
    assert 100.1e12 < step < 100.3e12           # 0.509 s at the chip's peak
    assert 0.19 < 4 * head / total < 0.20       # the heads' share here
    whole = {**cfg, "num_hidden_layers": 48}
    assert 4 * head / M.forward_flops_per_token(whole, 8192) < 0.031
    # a quarter of it at one pass, to the gate's dots
    once = M.forward_flops_per_token({**cfg, "total_ut_steps": 1}, 8192)
    assert total == 4 * once + 3 * 2 * 2048


def test_the_configuration_file_against_the_catalogs_row(bench, cfg):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["source"] == row["source_url"] == cfg["source"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == ["num_hidden_layers"] == entry["reduced"] \
        == cfg["reduced"]
    assert cfg["num_hidden_layers"] == 6 and cfg["total_ut_steps"] == 4
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["published_layers"] == [0, 1, 2, 3, 4, 5]
    assert cfg["exit_entropy_weight"] == 0.1
    assert cfg["exit_gate_bias_shift"] == -1.1
    assert {"limits", "limits_readings", "assumed", "stands_for",
            "cut"} <= set(cfg)
    assert {"loop_norm", "sandwich_norm", "exit_gate", "exit_entropy_weight",
            "exit_gate_bias_shift", "objective"} <= set(cfg["assumed"])
    assert "8 pipeline stages of 6 layers" in cfg["stands_for"]
    assert "509,661,185" in cfg["cut"]


def test_the_limit_lies_between_its_readings(cfg):
    """At most a third of the weakest control and over the largest sound
    reading with room (``benchmarks/control.py`` on the chip, PR 56; the
    file's ``why`` says how much room both sides have)."""
    limit = cfg["limits"]["grad_vs_reference"]
    r = cfg["limits_readings"]["grad_vs_reference"]
    assert r["limit"] == limit
    sound = max(hi for _, hi in r["sound"].values())
    controls = {k: lo for k, (lo, _) in r["controls"].items()}
    for control in ("fp8_matmul", "one_pass", "uniform_exits"):
        assert any(k.startswith(control) for k in controls), control
    assert 1.2 * sound <= limit <= min(controls.values()) / 3


def test_the_cells_entries_in_the_benchmark_file(bench):
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady-1g-8k"
    assert cell["config"] == "ouro-2.6b"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    # at least these (later PRs add metrics to the cell's list)
    assert listed >= {
        "entry_other_ms", "quorum_ms", "commit_ms", "raw_step_ms", "mfu_pct",
        "device_idle_pct", "peak_hbm_gib", "attest_device_ms", "dispatch_ms",
        "publish_status_ms", "state_digest_wait_ms", "boundary_host_ms",
        "idle_dispatch_ms", "idle_boundary_ms", "idle_wait_ms",
        "idle_unspanned_ms", *NEW}
    # one call a layer would read four times the truth; another shape
    assert not listed & {"flash_roofline", "head_loss_device_ms"}
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["attn_loop_roofline"] == layers["flash_roofline"]
    assert layers["loop_body_device_ms"] == layers["raw_step_ms"]
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_looped_kernel_counts_a_call_a_layer_a_pass_by_hand(cfg):
    k = spec.module("kernels", "looped_flash_attention")
    assert k.calls_per_step(cfg) == 6 * 4 == 24
    square = 2.0 * 16 * 8192 * 8192 * 128 / 2
    least = k.least_seconds(cfg, 1, 8192, peaks_for(V5E))
    assert least["flops"] == 7 * square
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(7 * square / 197e12)
    assert least["seconds"] == pytest.approx(4.88e-3, rel=2e-3)
    q, stat = 8192 * 16 * 128 * 2, 8192 * 16 * 4
    assert least["bytes"] == (4 * q + stat) + (8 * q + 2 * stat)
    # what kernels/flash_attention.py would read of this configuration: the
    # same call (hidden / heads is the stated head_dim here) in six layers
    # and one pass: a share four times the truth
    old = spec.module("kernels", "flash_attention")
    assert old.calls_per_step(cfg) == 6
    assert old.least_seconds(cfg, 1, 8192, peaks_for(V5E)) == least


def _traced_run(events, counters, step_ns=2000):
    steps = [{"phase": "window", "committed": True, "world": 1,
              "t0": 100 + step_ns * i, "t1": 100 + step_ns * (i + 1),
              "timings": {}} for i in range(2)]
    return {"groups": 1, "groups_on_device": 1, "batch": 1, "seq": 8192,
            "steps": {0: steps}, "counters": counters,
            "cfg": spec.Cell(CELL, REPO).config, "device_kind": V5E,
            "device_trace": {"planes": {"/device:TPU:0": events},
                             "modules": {}, "lo": 0,
                             "hi": 1100 + 2 * step_ns}}


PINNED = {"loop_body_device_ms": "loop", "loop_exit_loss_device_ms": "loss",
          "attn_loop_roofline": "attention"}


@pytest.mark.parametrize("metric", list(PINNED), ids=list(PINNED))
def test_patterns_match_the_names_a_traced_run_gave(bench, names, metric):
    """``tests/looped_op_names.json`` holds event names as the chip's
    profile spelt them (my traced run, PR 56, seed 5600000311: the three
    ``while`` ops whole, since the backward scan names its carried
    gradients before the stacked ``[4,1,8192,2048]``, the others' first
    1,500 characters): each metric's pattern finds its own and none of the
    others'."""
    pattern = spec.data("metrics", metric)["reader"]["pattern"]
    mine = names[PINNED[metric]]
    assert mine and all(re.search(pattern, n) for n in mine)
    rest = [n for key, group in names.items() if key != PINNED[metric]
            for n in group]
    assert rest and not any(re.search(pattern, n) for n in rest)


def test_the_trace_holds_one_loss_scan_and_two_pass_scans(names):
    """One ``while`` carries the float32 ``[2048,49152]`` head gradient
    (none an exit), two carry a pass's stacked ``[4,1,8192,2048]`` (the
    scan over passes, forward and backward)."""
    assert len(names["loss"]) == 1 and len(names["loop"]) == 2
    assert all("f32[2048,49152]" in n for n in names["loss"])


OTHERS = ("afmoe_op_names.json", "mla_op_names.json", "gdn_op_names.json",
          "mamba2_op_names.json", "lfm2_op_names.json",
          "smallthinker_op_names.json")


@pytest.mark.parametrize("file", OTHERS)
def test_new_shape_patterns_match_nothing_of_the_other_cells(bench, file):
    with open(os.path.join(BENCH, "tests", file)) as f:
        theirs = [n for group in json.load(f).values() for n in group]
    for metric in ("loop_body_device_ms", "loop_exit_loss_device_ms"):
        pattern = spec.data("metrics", metric)["reader"]["pattern"]
        assert not any(re.search(pattern, n) for n in theirs), metric


def test_device_metrics_read_their_ops_inside_the_steps(bench, names):
    for metric, key in (("loop_body_device_ms", "loop"),
                        ("loop_exit_loss_device_ms", "loss")):
        reader = spec.data("metrics", metric)["reader"]
        events = [(n, 200 + 10 * i, 205 + 10 * i)
                  for i, n in enumerate(names[key] + names["other"])]
        events.append((names[key][0], 4500, 4600))     # outside every step
        run = _traced_run(events, {})
        assert readers.read(run, reader) == pytest.approx(
            5e-9 * len(names[key]) * 1e3 / 2)
        assert readers.read(_traced_run(
            [e for e in events if e[0] in names["other"]], {}),
            reader) is None
        assert readers.read({**run, "device_trace": None}, reader) is None


def test_the_roofline_reads_a_share_under_a_hundred(bench, names):
    cfg = spec.Cell(CELL, REPO).config
    reader = spec.data("metrics", "attn_loop_roofline")["reader"]
    k = spec.module("kernels", "looped_flash_attention")
    least = k.least_seconds(cfg, 1, 8192, peaks_for(V5E))["seconds"]
    took = int(4 * 24 * least * 1e9)       # 24 calls at a quarter of the roof
    step = took + 2000
    events = [(names["attention"][0], 200 + i * step, 200 + i * step + took)
              for i in range(2)]
    assert readers.read(_traced_run(events, {}, step), reader) \
        == pytest.approx(25.0, rel=1e-3)


@pytest.mark.parametrize("metric,key,a_step", [
    ("loop_passes", "loop_passes_total", 4.0),
    ("loop_expected_exit_milli", "loop_expected_exit_milli_total", 2731.5)])
def test_counter_metrics_read_a_committed_step(bench, metric, key, a_step):
    reader = spec.data("metrics", metric)["reader"]
    counters = {"begin.0": {key: 2 * a_step, "committed_steps": 2},
                "end.0.0": {key: 12 * a_step, "committed_steps": 12}}
    assert readers.read(_traced_run([], counters), reader) \
        == pytest.approx(a_step)
    assert readers.read(_traced_run([], {}), reader) is None
    # the parent's program has no such counter: nothing to read
    assert readers.read(_traced_run([], {
        "begin.0": {"committed_steps": 2},
        "end.0.0": {"committed_steps": 12}}), reader) is None


def test_the_builder_stops_a_program_without_the_looped_loss(tmp_path):
    """The builder imports ``looped_causal_lm_loss`` at its top: a checkout
    whose program lacks it (the parent of PR 56) fails when the driver
    loads the builder, at once and with rc 1."""
    src = os.path.join(BENCH, "models", "looped_dense_decoder.py")
    with open(src) as f:
        head = f.read().split("REHEARSE =")[0]
    assert "looped_causal_lm_loss" in head
    pkg = tmp_path / "torchft_tpu" / "models"
    pkg.mkdir(parents=True)
    (tmp_path / "torchft_tpu" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "transformer.py").write_text("")
    out = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys; sys.path.insert(0, sys.argv[1]); "
         "runpy.run_path(sys.argv[2])", str(tmp_path), src],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 1
    assert "ImportError" in out.stderr
    assert "looped_causal_lm_loss" in out.stderr


def test_the_cell_is_found_and_runs_in_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 56), "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, out.stdout[-3000:]
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    assert got["loop_passes"]["value"] == 4.0
    assert 1000 < got["loop_expected_exit_milli"]["value"] < 4000
    for device_metric in ("loop_body_device_ms", "loop_exit_loss_device_ms",
                          "attn_loop_roofline", "mfu_pct"):
        assert device_metric not in got
