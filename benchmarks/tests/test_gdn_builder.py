"""Builder ``gdn_moe_decoder`` and what PR 40 added beside it: the
configuration file against the catalog's row, the parameter count against
the tree and a hand count, operation counts and the hybrid kernel file
against a hand count, the four new metrics' patterns against names pinned
from a traced run on the chip, and the cell itself found and run in
rehearsal."""

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
CELL = "qwen3-next-80b-a3b.steady-1g-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def bench():
    return spec.configure(REPO)


@pytest.fixture(scope="module")
def cfg(bench):
    return spec.Cell(CELL, REPO).config


@pytest.fixture(scope="module")
def M(bench):
    return spec.module("models", "gdn_moe_decoder")


@pytest.fixture(scope="module")
def names():
    with open(os.path.join(BENCH, "tests/gdn_op_names.json")) as f:
        return json.load(f)


def test_param_count_is_the_trees_size_from_shapes_only(cfg, M):
    shapes = jax.tree_util.tree_leaves(
        M.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in shapes) == M.param_count(cfg)
    # by hand (ISSUE 40's arithmetic)
    linear = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128
              + 4096 * 2048)
    assert linear == 33_718_464
    full = 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 512
    assert full == 27_263_488

    def experts(held):
        return 2048 * 512 + 3 * 2048 * 512 + 2048 + held * 3 * 2048 * 512

    assert experts(16) == 54_528_000 and experts(8) == 29_362_176
    assert cfg["num_experts_held"] == 8
    assert M.param_count(cfg) == (3 * linear + full + 4 * (experts(8) + 4096)
                                  + 2 * 18992 * 2048 + 2048) == 323_677_248
    # ISSUE 40's first cut, which aot_check.py's bytes turned down
    assert M.param_count({**cfg, "num_experts_held": 16}) == 424_340_544


@pytest.mark.parametrize("layer,part,want", [
    (0, "proj", 2 * 2048 * (12288 + 64) + 2 * 4096 * 2048),
    (0, "conv", 2 * 4 * 8192),
    (0, "scan", 32 * (64 * 640 + 64 * 64 / 3 + 6 * 128 * 128)),
    (3, "proj", 2 * 2048 * (4096 + 4096 + 512 + 512) + 2 * 4096 * 2048),
    (3, "attn", 2 * 2 * 256 * 16 * 8193 / 2),
    (3, "router", 2 * 2048 * 512), (3, "shared", 6 * 2048 * 512 + 2 * 2048),
    (3, "routed", 10 * 8 / 512 * 6 * 2048 * 512)])
def test_forward_flops_of_a_layer_by_hand(cfg, M, layer, part, want):
    assert M.layer_forward_flops(cfg, 8192)[layer][part] == pytest.approx(
        want)


def test_train_flops_are_needed_work_only(cfg, M):
    parts = M.layer_forward_flops(cfg, 8192)
    assert [("scan" in p, "attn" in p) for p in parts] == \
        [(True, False)] * 3 + [(False, True)]
    fwd = M.forward_flops_per_token(cfg, 8192)
    assert fwd == pytest.approx(sum(sum(p.values()) for p in parts)
                                + 2 * 2048 * 18992)
    assert M.train_flops_per_token(cfg, 8192) == 3 * fwd
    # ISSUE 40: about 1.4 GFLOP a token to train (it counts the attention's
    # backward by its five matmuls; here the backward is twice the forward)
    assert 1.30e9 < 3 * fwd < 1.40e9
    scan = sum(p.get("scan", 0.0) for p in parts)
    routed = sum(p["routed"] for p in parts)
    assert 0.025 < scan / fwd < 0.035       # the rule's own products: 3 %
    assert routed / fwd < 0.01              # "under 1 %"
    linear = sum(sum(p.values()) for p in parts[:3])
    assert 0.50 < linear / fwd < 0.58       # the DeltaNet layers: over half


def test_the_file_holds_every_number_of_the_catalog_row(bench, cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["source"] == row["source_url"] == cfg["source"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == ["num_hidden_layers", "vocab_size"]
    assert set(differ) | {"num_experts_held"} == set(entry["reduced"])
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert cfg["published"] == {**cfg["published"], "num_hidden_layers": 48,
                                "vocab_size": 151936, "num_experts_held": 512}
    assert cfg["published_layers"] == [0, 1, 2, 3]
    assert cfg["vocab_size"] * 8 == 151936
    assert {"limits", "limits_readings", "assumed", "stands_for",
            "cut"} <= set(cfg)
    assert {"zero_centred_norms", "fused_projection_layout", "dt_bias_shift",
            "routing", "shared_expert", "attention", "delta_rule",
            "not_built", "training_precision", "values"} \
        <= set(cfg["assumed"])


def test_every_line_of_the_benchmark_file_is_within_its_limits(bench):
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "qwen3-next-80b-a3b"
    cell = bench["workloads"][-1]
    assert cell["chips"] == 1 and cell["traffic"] == "steady-1g-8k"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    new = ["gdn_scan_device_ms", "gdn_chunks", "moe_device_ms_512",
           "attn_gqa256_roofline"]
    # At least these (later PRs add metrics to the cell's list, and after
    # these four in the file).
    assert listed >= {
        "entry_other_ms", "quorum_ms", "commit_ms", "raw_step_ms", "mfu_pct",
        "device_idle_pct", "peak_hbm_gib", "attest_device_ms",
        "moe_pairs_local", *new}
    # not moe_experts_roofline: its reader divides by every step between the
    # profiler's start and stop, and this cell's device trace can end early
    # (readers/op_ms_seen.py): a share over 100 % there would be the trace's
    assert CELL not in next(m for m in bench["per_layer"]
                            if m["name"] == "moe_experts_roofline")[
                                "workloads"]
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL        # appended, last
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_hybrid_kernel_counts_the_full_layers_only_by_hand(cfg):
    k = spec.module("kernels", "hybrid_flash_attention")
    assert k.calls_per_step(cfg) == 1
    assert k.full_layers({**cfg, "published_layers": list(range(8))}) == 2
    pairs = 16 * 8192 * 8193 / 2
    f = k.triangle_flops(1, 8192, 16, 256)
    assert f["fwd"] == 2 * 2 * 256 * pairs and f["bwd"] == 5 * 2 * 256 * pairs
    least = k.least_seconds(cfg, 1, 8192, peaks_for("TPU v5 lite"))
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx((f["fwd"] + f["bwd"]) / 197e12)
    q, kv, stat = 8192 * 16 * 256 * 2, 8192 * 2 * 256 * 2, 8192 * 16 * 4
    assert least["bytes"] == (2 * q + 2 * kv + stat) \
        + (4 * q + 4 * kv + 2 * stat)
    # what kernels/flash_attention.py would read of this configuration: a
    # head of 2048 / 16 and four layers, so flash_roofline is not extended
    old = spec.module("kernels", "flash_attention")
    assert old.calls_per_step(cfg) == 4
    assert old.least_seconds(cfg, 1, 8192, peaks_for("TPU v5 lite"))[
        "seconds"] == pytest.approx(least["seconds"] / 2, rel=1e-3)
    # what the grouped-matmul file reads of this configuration
    g = spec.module("kernels", "grouped_matmul")
    assert g.expert_layers(cfg) == 4
    assert g.least_seconds(cfg, 5120.0, peaks_for("TPU v5 lite"))[
        "bound"] == "memory"


STEP_MODULE = "jit_fused(7192192422693754499)"


def _traced_run(events, counters, steps_seen=1, step_ns=2000):
    """Two counted steps of ``step_ns`` between the profiler's start and
    stop; the device trace holds the step's program for the first
    ``steps_seen`` of them."""
    steps = [{"phase": "window", "committed": True, "world": 1,
              "t0": 100 + step_ns * i, "t1": 100 + step_ns * (i + 1),
              "timings": {}} for i in range(2)]
    modules = [(STEP_MODULE, s["t0"] + 10, s["t1"] - 10)
               for s in steps[:steps_seen]]
    return {"groups": 1, "groups_on_device": 1, "batch": 1, "seq": 8192,
            "steps": {0: steps}, "counters": counters,
            "cfg": spec.Cell(CELL, REPO).config, "device_kind": "TPU v5 lite",
            "device_trace": {"planes": {"/device:TPU:0": events},
                             "modules": {"/device:TPU:0": modules},
                             "lo": 0, "hi": 1100 + 2 * step_ns}}


PINNED = {"gdn_scan_device_ms": "scan", "moe_device_ms_512": "moe",
          "attn_gqa256_roofline": "attention"}


@pytest.mark.parametrize("metric", list(PINNED), ids=list(PINNED))
def test_patterns_match_the_names_a_traced_run_gave(bench, names, metric):
    """``tests/gdn_op_names.json`` holds event names as the chip's profile
    spelt them (my traced run, PR 40): each metric's pattern finds its own
    and none of the others'."""
    pattern = spec.data("metrics", metric)["reader"]["pattern"]
    mine = names[PINNED[metric]]
    assert mine and all(re.search(pattern, n) for n in mine)
    rest = [n for key, group in names.items() if key != PINNED[metric]
            for n in group]
    assert rest and not any(re.search(pattern, n) for n in rest)


OTHERS = {"afmoe_op_names.json": ("moe_device_ms",),
          "mla_op_names.json": ("moe_device_ms_768", "head_loss_device_ms",
                                "attn_mla_roofline")}


@pytest.mark.parametrize("file", list(OTHERS), ids=list(OTHERS))
def test_new_patterns_match_nothing_of_the_other_cells(bench, names, file):
    """The two loop patterns PR 40 adds find none of the names pinned from
    the other sparse cells' traces, and those cells' loop patterns none of
    this cell's."""
    with open(os.path.join(BENCH, "tests", file)) as f:
        theirs = [n for group in json.load(f).values() for n in group]
    for metric in ("gdn_scan_device_ms", "moe_device_ms_512"):
        pattern = spec.data("metrics", metric)["reader"]["pattern"]
        assert not any(re.search(pattern, n) for n in theirs), metric
    mine = [n for group in names.values() for n in group]
    for metric in OTHERS[file]:
        pattern = spec.data("metrics", metric)["reader"]["pattern"]
        assert not any(re.search(pattern, n) for n in mine), metric


def test_loop_metrics_read_their_ops_inside_the_steps_seen(bench, names):
    for metric, key in (("gdn_scan_device_ms", "scan"),
                        ("moe_device_ms_512", "moe")):
        reader = spec.data("metrics", metric)["reader"]
        assert reader["kind"] == "op_ms_seen"
        events = [(n, 200 + 10 * i, 205 + 10 * i)
                  for i, n in enumerate(names[key] + names["other"])]
        events.append((names[key][0], 4500, 4600))     # outside every step
        run = _traced_run(events, {})
        assert readers.read(run, reader) == pytest.approx(
            5e-9 * len(names[key]) * 1e3)
        assert readers.read(_traced_run(
            [e for e in events if e[0] in names["other"]], {}),
            reader) is None
        assert readers.read({**run, "device_trace": None}, reader) is None
        assert readers.read(_traced_run(events, {}, steps_seen=0),
                            reader) is None


def test_a_trace_that_ends_early_reads_the_same_over_fewer_steps(bench,
                                                                 names):
    """The device trace of this cell stopped after 6 of 14 steps in one
    traced run of two (PERF.md, PR 40). ``op_ms`` / ``kernel_roofline``
    divide by every step between the profiler's start and stop and would
    read 10.5 ms for 25.3 and 149 % for 53; the readers this cell's device
    metrics name count the steps the trace shows whole."""
    scan = spec.data("metrics", "gdn_scan_device_ms")["reader"]
    roof = spec.data("metrics", "attn_gqa256_roofline")["reader"]
    k = spec.module("kernels", "hybrid_flash_attention")
    least = k.least_seconds(spec.Cell(CELL, REPO).config, 1, 8192,
                            peaks_for("TPU v5 lite"))["seconds"]
    took = int(2 * least * 1e9)                       # half of the roof
    step = took + 2000
    one_step = [(names["scan"][0], 200, 700),
                (names["attention"][0], 800, 800 + took)]
    again = [(n, a + step, b + step) for n, a, b in one_step]
    whole = _traced_run(one_step + again, {}, 2, step)
    early = _traced_run(one_step, {}, 1, step)
    for run in (whole, early):
        assert readers.read(run, scan) == pytest.approx(500e-9 * 1e3)
        assert readers.read(run, roof) == pytest.approx(50.0, rel=1e-3)
    old = {"kind": "kernel_roofline", "pattern": roof["pattern"],
           "kernel": roof["kernel"]}
    assert readers.read(early, old) == pytest.approx(100.0, rel=1e-3)


def test_gqa256_roofline_reads_the_attn_kernels(bench, names):
    reader = spec.data("metrics", "attn_gqa256_roofline")["reader"]
    assert reader["kind"] == "kernel_roofline_seen"
    k = spec.module("kernels", "hybrid_flash_attention")
    cfg = spec.Cell(CELL, REPO).config
    least = k.least_seconds(cfg, 1, 8192, peaks_for("TPU v5 lite"))
    took = int(4 * least["seconds"] * 1e9)           # a quarter of the roof
    run = _traced_run([(names["attention"][0], 200, 200 + took)], {},
                      step_ns=took + 400)
    assert readers.read(run, reader) == pytest.approx(25.0, rel=1e-3)
    # a program without such kernels: nothing, and no error
    assert readers.read(_traced_run(
        [(names["other"][0], 200, 300)], {}), reader) is None


def test_chunks_metric_reads_the_counter_a_committed_step(bench):
    reader = spec.data("metrics", "gdn_chunks")["reader"]
    counters = {"begin.0": {"gdn_chunks_total": 768.0, "committed_steps": 2},
                "end.0.0": {"gdn_chunks_total": 768.0 + 10 * 384,
                            "committed_steps": 12}}
    assert readers.read(_traced_run([], counters), reader) == 384.0
    assert readers.read(_traced_run([], {}), reader) is None


def test_the_cell_is_found_and_runs_in_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 40), "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, out.stdout[-3000:]
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    # 1 x 128 tokens: three linear layers x 2 chunks; every one of 4
    # experts selected, 2 held, 4 layers
    assert got["gdn_chunks"]["value"] == 3 * 2
    assert got["moe_pairs_local"]["value"] == 4 * 128 * 2
    for device_metric in ("gdn_scan_device_ms", "moe_device_ms_512",
                          "attn_gqa256_roofline", "mfu_pct"):
        assert device_metric not in got
