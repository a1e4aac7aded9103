"""Builder ``mamba2_moe_decoder`` and what PR 45 added beside it: the
configuration file against the catalog's row, the parameter count against
the tree and a hand count, operation counts and the two new kernel files
against a hand count, the new metrics' patterns against names pinned from a
traced run on the chip, and the cell itself found and run in rehearsal."""

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
CELL = "nemotron-3-nano-30b-a3b.steady-1g-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def bench():
    return spec.configure(REPO)


@pytest.fixture(scope="module")
def cfg(bench):
    return spec.Cell(CELL, REPO).config


@pytest.fixture(scope="module")
def M(bench):
    return spec.module("models", "mamba2_moe_decoder")


@pytest.fixture(scope="module")
def names():
    with open(os.path.join(BENCH, "tests/mamba2_op_names.json")) as f:
        return json.load(f)


def test_param_count_is_the_trees_size_from_shapes_only(cfg, M):
    shapes = jax.tree_util.tree_leaves(
        M.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in shapes) == M.param_count(cfg)
    assert len(shapes) == 53
    # by hand (ISSUE 45's table)
    mamba = (2688 * 10304 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * 2688
             + 2688)
    assert mamba == 38_744_896
    experts = 2688 * 128 + 2 * 2688 * 3712 + 8 * 2 * 2688 * 1856 + 2688
    assert experts == 100_125_312
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    assert attention == 23_399_040
    assert 3 * mamba + 3 * experts + attention == 440_009_664
    assert M.param_count(cfg) == 440_009_664 + 2 * 16384 * 2688 + 2688 \
        == 528_092_736


@pytest.mark.parametrize("block,part,want", [
    (0, "proj", 2 * 2688 * 10304 + 2 * 4096 * 2688),
    (0, "conv", 2 * 4 * 6144),
    (0, "scan", 8 * 128 * 128 + 64 * 128 * 64 + 2 * 64 * 2 * 64 * 128
     + 64 * 64 * 128 * 64 / 128),
    (1, "router", 2 * 2688 * 128), (1, "shared", 4 * 2688 * 3712),
    (1, "routed", 6 * 8 / 128 * 4 * 2688 * 1856),
    (5, "proj", 2 * 2688 * (4096 + 512) + 2 * 4096 * 2688),
    (5, "attn", 2 * 2 * 128 * 32 * 8193 / 2)])
def test_forward_flops_of_a_block_by_hand(cfg, M, block, part, want):
    assert M.layer_forward_flops(cfg, 8192)[block][part] == pytest.approx(
        want)


def test_train_flops_are_needed_work_only(cfg, M):
    parts = M.layer_forward_flops(cfg, 8192)
    assert ["scan" in p for p in parts] == [True, False, True, False, True,
                                            False, False]
    fwd = M.forward_flops_per_token(cfg, 8192)
    assert fwd == pytest.approx(sum(sum(p.values()) for p in parts)
                                + 2 * 2688 * 16384)
    assert M.train_flops_per_token(cfg, 8192) == 3 * fwd
    assert 580e6 < fwd < 595e6            # ISSUE 45: about 588 MFLOP a token
    mamba = sum(sum(p.values()) for p in parts if "scan" in p)
    assert 0.38 < mamba / fwd < 0.44      # "the largest part, about 40 %"
    assert sum(p.get("routed", 0.0) for p in parts) / fwd < 0.05


def test_the_file_holds_every_number_of_the_catalog_row(bench, cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["source"] == row["source_url"] == cfg["source"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == ["num_hidden_layers", "vocab_size"]
    assert set(differ) | {"num_experts_held"} == set(entry["reduced"])
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert cfg["published"] == {**cfg["published"], "num_hidden_layers": 52,
                                "vocab_size": 131072,
                                "num_experts_held": 128}
    assert cfg["published_layers"] == list(range(7))
    assert cfg["hybrid_override_pattern"][:7] == "MEMEM*E"
    assert cfg["vocab_size"] * 8 == 131072
    assert {"limits", "limits_readings", "assumed", "stands_for",
            "cut"} <= set(cfg)
    assert {"no_position_signal", "selection_bias", "routing", "experts",
            "mamba2", "dt_bias_shift", "seeded_conv_bias", "not_built",
            "training_precision", "values"} <= set(cfg["assumed"])
    assert "16 chips a layer" in cfg["stands_for"]
    assert "528,092,736" in cfg["cut"]


def test_the_cells_entries_in_the_benchmark_file(bench):
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady-1g-8k"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    new = ["ssd_device_ms", "ssd_chunks", "moe_device_ms_1856",
           "moe_relu2_roofline", "attn_gqa32x2_roofline"]
    # at least these (later PRs add metrics to the cell's list)
    assert listed >= {
        "entry_other_ms", "quorum_ms", "commit_ms", "raw_step_ms", "mfu_pct",
        "device_idle_pct", "peak_hbm_gib", "attest_device_ms", "dispatch_ms",
        "publish_status_ms", "state_digest_wait_ms", "boundary_host_ms",
        "idle_dispatch_ms", "idle_boundary_ms", "idle_wait_ms",
        "idle_unspanned_ms", "moe_pairs_local", *new}
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_relu2_kernel_counts_two_products_by_hand(cfg):
    k = spec.module("kernels", "grouped_matmul_relu2")
    assert k.expert_layers(cfg) == 3
    rows = 9216.0                     # 3 blocks x 8192 x 6 x 8 / 128
    f = k.grouped_flops(rows, 2688, 1856)
    assert f["fwd"] == 2 * 2 * rows * 2688 * 1856 and f["bwd"] == 2 * f["fwd"]
    b = k.grouped_bytes(rows, 3, 8, 2688, 1856)
    weights = 3 * 8 * 2 * 2688 * 1856
    acts = rows * (2688 + 1856 + 1856 + 2688)
    assert b["fwd"] == 2 * (weights + acts)
    assert b["bwd"] == 2 * (weights + 2 * acts) + 4 * weights
    least = k.least_seconds(cfg, rows, peaks_for(V5E))
    assert least["bound"] == "memory"
    # the SwiGLU file would count three products and, having no
    # num_dense_layers here, cannot read this configuration at all
    three = spec.module("kernels", "grouped_matmul").grouped_flops(
        rows, 2688, 1856)
    assert three["fwd"] == 1.5 * f["fwd"]


def test_pattern_kernel_counts_the_one_attention_block_by_hand(cfg):
    k = spec.module("kernels", "pattern_flash_attention")
    assert k.calls_per_step(cfg) == 1
    assert k.attention_layers(
        {**cfg, "published_layers": list(range(13))}) == 2
    pairs = 32 * 8192 * 8193 / 2
    f = spec.module("kernels", "hybrid_flash_attention").triangle_flops(
        1, 8192, 32, 128)
    assert f["fwd"] == 2 * 2 * 128 * pairs and f["bwd"] == 5 * 2 * 128 * pairs
    least = k.least_seconds(cfg, 1, 8192, peaks_for(V5E))
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx((f["fwd"] + f["bwd"]) / 197e12)
    q, kv, stat = 8192 * 32 * 128 * 2, 8192 * 2 * 128 * 2, 8192 * 32 * 4
    assert least["bytes"] == (2 * q + 2 * kv + stat) \
        + (4 * q + 4 * kv + 2 * stat)
    # what kernels/flash_attention.py would read of this configuration: a
    # head of 2688 / 32 = 84 and a call in each of seven blocks
    old = spec.module("kernels", "flash_attention")
    assert old.calls_per_step(cfg) == 7


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


PINNED = {"ssd_device_ms": "scan", "moe_device_ms_1856": "moe",
          "attn_gqa32x2_roofline": "attention", "moe_relu2_roofline": "gmm"}


@pytest.mark.parametrize("metric", list(PINNED), ids=list(PINNED))
def test_patterns_match_the_names_a_traced_run_gave(bench, names, metric):
    """``tests/mamba2_op_names.json`` holds event names as the chip's
    profile spelt them (my traced run, PR 45): each metric's pattern finds
    its own and none of the others'."""
    pattern = spec.data("metrics", metric)["reader"]["pattern"]
    mine = names[PINNED[metric]]
    assert mine and all(re.search(pattern, n) for n in mine)
    rest = [n for key, group in names.items() if key != PINNED[metric]
            for n in group]
    assert rest and not any(re.search(pattern, n) for n in rest)


OTHERS = ("afmoe_op_names.json", "mla_op_names.json", "gdn_op_names.json")


@pytest.mark.parametrize("file", OTHERS)
def test_new_shape_patterns_match_nothing_of_the_other_cells(bench, file):
    with open(os.path.join(BENCH, "tests", file)) as f:
        theirs = [n for group in json.load(f).values() for n in group]
    for metric in ("ssd_device_ms", "moe_device_ms_1856"):
        pattern = spec.data("metrics", metric)["reader"]["pattern"]
        assert not any(re.search(pattern, n) for n in theirs), metric


def test_device_metrics_read_their_ops_inside_the_steps(bench, names):
    for metric, key in (("ssd_device_ms", "scan"),
                        ("moe_device_ms_1856", "moe")):
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


def test_rooflines_read_shares_under_a_hundred(bench, names):
    cfg = spec.Cell(CELL, REPO).config
    reader = spec.data("metrics", "attn_gqa32x2_roofline")["reader"]
    k = spec.module("kernels", "pattern_flash_attention")
    least = k.least_seconds(cfg, 1, 8192, peaks_for(V5E))["seconds"]
    took = int(2 * least * 1e9)                        # half of the roof
    step = took + 2000
    events = [(names["attention"][0], 200 + i * step, 200 + i * step + took)
              for i in range(2)]
    assert readers.read(_traced_run(events, {}, step), reader) \
        == pytest.approx(50.0, rel=1e-3)
    reader = spec.data("metrics", "moe_relu2_roofline")["reader"]
    g = spec.module("kernels", "grouped_matmul_relu2")
    counters = {"begin.0": {"moe_pairs_local_total": 0.0,
                            "committed_steps": 2},
                "end.0.0": {"moe_pairs_local_total": 10 * 9216.0,
                            "committed_steps": 12}}
    least = g.least_seconds(cfg, 9216.0, peaks_for(V5E))["seconds"]
    took = int(4 * least * 1e9)
    step = took + 2000
    events = [(names["gmm"][0], 200 + i * step, 200 + i * step + took)
              for i in range(2)]
    assert readers.read(_traced_run(events, counters, step), reader) \
        == pytest.approx(25.0, rel=1e-3)
    # a program without the counter (the parent's): nothing, and no error
    assert readers.read(_traced_run(events, {}, step), reader) is None


def test_chunks_metric_reads_the_counter_a_committed_step(bench):
    reader = spec.data("metrics", "ssd_chunks")["reader"]
    counters = {"begin.0": {"ssd_chunks_total": 384.0, "committed_steps": 2},
                "end.0.0": {"ssd_chunks_total": 384.0 + 10 * 192,
                            "committed_steps": 12}}
    assert readers.read(_traced_run([], counters), reader) == 192.0
    assert readers.read(_traced_run([], {}), reader) is None
    # the parent's program has no such counter: nothing to read
    assert readers.read(_traced_run([], {
        "begin.0": {"committed_steps": 2},
        "end.0.0": {"committed_steps": 12}}), reader) is None


def test_the_cell_is_found_and_runs_in_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 45), "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, out.stdout[-3000:]
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    # 1 x 256 tokens: three mamba blocks x 2 chunks; every one of 4 experts
    # selected, 2 held, 3 expert blocks
    assert got["ssd_chunks"]["value"] == 3 * 2
    assert got["moe_pairs_local"]["value"] == 3 * 256 * 2
    for device_metric in ("ssd_device_ms", "moe_device_ms_1856",
                          "moe_relu2_roofline", "attn_gqa32x2_roofline",
                          "mfu_pct"):
        assert device_metric not in got
