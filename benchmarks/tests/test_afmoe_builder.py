"""Builder ``afmoe_decoder`` and what PR 29 added beside it: the
configuration file against the catalog's row, the parameter count against
the tree, operation counts against a hand count, the two kernel files, the
two readers, and the cell itself found and run in rehearsal."""

import json
import math
import os
import subprocess
import sys

import jax
import pytest

from harness import readers, spec
from harness.peaks import peaks_for

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "trinity-mini.steady-1g-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def bench():
    return spec.configure(REPO)


@pytest.fixture(scope="module")
def cfg(bench):
    return spec.Cell(CELL, REPO).config


@pytest.fixture(scope="module")
def M(bench):
    return spec.module("models", "afmoe_decoder")


def test_param_count_is_the_trees_size_from_shapes_only(cfg, M):
    shapes = jax.tree_util.tree_leaves(
        M.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in shapes) == M.param_count(cfg)
    # by hand: attention 3 x 2048 x 4096 + 2 x 2048 x 512 + 2 x 128, norms
    # 4 x 2048; dense MLP 3 x 2048 x 6144; experts 2048 x 128 + 3 x 2048 x
    # 1024 + 8 x 3 x 2048 x 1024; embedding and head 2 x 25024 x 2048
    attn, norms = 27_263_232, 8_192
    assert M.param_count(cfg) == (
        5 * (attn + norms) + 37_748_736
        + 4 * (262_144 + 6_291_456 + 50_331_648) + 102_498_304 + 2_048)
    assert M.param_count(cfg) == 504_147_200


@pytest.mark.parametrize("part,want", [
    ("proj", 2 * 2048 * (2 * 4096 + 2 * 512) + 2 * 4096 * 2048),
    ("router", 2 * 2048 * 128), ("shared", 6 * 2048 * 1024),
    ("routed", 8 * 8 / 128 * 6 * 2048 * 1024)])
def test_forward_flops_of_an_expert_layer_by_hand(cfg, M, part, want):
    assert M.layer_forward_flops(cfg, 8192)[1][part] == want


@pytest.mark.parametrize("layer,keys", [
    (0, (2048 * 2049 / 2 + 6144 * 2048) / 8192),      # sliding
    (2, 8193 / 2)])                                     # full
def test_attention_flops_count_the_visible_part(cfg, M, layer, keys):
    got = M.layer_forward_flops(cfg, 8192)[layer]["attn"]
    assert got == pytest.approx(2 * 2 * 128 * 32 * keys)
    assert M.visible_keys_per_query(1024, 2048) == 1025 / 2  # window > seq


def test_train_flops_are_three_forwards_and_the_routed_share_is_small(cfg, M):
    fwd = M.forward_flops_per_token(cfg, 8192)
    assert M.train_flops_per_token(cfg, 8192) == 3 * fwd
    parts = M.layer_forward_flops(cfg, 8192)
    assert fwd == pytest.approx(sum(sum(p.values()) for p in parts)
                                + 2 * 2048 * 25024)
    routed = sum(p.get("routed", 0.0) for p in parts)
    assert 0.03 < routed / fwd < 0.04          # "about 3.5 %"
    assert "mlp" in parts[0] and "routed" not in parts[0]


def test_the_file_holds_every_number_of_the_catalog_row(bench, cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    entry = next(c for c in bench["configs"] if c["name"] == "trinity-mini")
    assert entry["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == ["num_hidden_layers", "vocab_size"]
    assert set(differ) | {"num_experts_held"} == set(entry["reduced"])
    assert cfg["published"]["num_hidden_layers"] == 32
    assert [cfg["layer_types"][i] for i in cfg["published_layers"]] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert {"limits", "assumed", "stands_for", "cut"} <= set(cfg)


def test_every_line_of_the_benchmark_file_is_within_its_limits(bench):
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    # At least these: a tracing PR adds metrics of the step thread to every
    # cell's list, and this test is not the one to know them.
    assert listed >= {
        "entry_other_ms", "quorum_ms", "commit_ms", "raw_step_ms", "mfu_pct",
        "device_idle_pct", "peak_hbm_gib", "attest_device_ms",
        "moe_device_ms", "moe_experts_roofline", "attn_window_roofline",
        "moe_pairs_local"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_window_kernel_counts_the_band(cfg):
    k = spec.module("kernels", "window_flash_attention")
    assert k.layer_windows(cfg) == [2048, 2048, None, 2048, 2048]
    band, triangle = k.visible_pairs(8192, 2048), k.visible_pairs(8192, None)
    assert triangle == 8192 * 8193 / 2
    assert 0.43 < band / triangle < 0.44       # "44 % of the triangle"
    assert k.visible_pairs(1024, 2048) == 1024 * 1025 / 2
    f = k.window_flops(2, 8192, 32, 128, 2048)
    assert f["fwd"] == 2 * 2 * 2 * 32 * 128 * band and f["bwd"] == 2.5 * f["fwd"]
    least = k.least_seconds(cfg, 2, 8192, peaks_for("TPU v5 lite"))
    full = spec.module("kernels", "flash_attention").least_seconds(
        {**cfg, "hidden_size": 4096}, 2, 8192, peaks_for("TPU v5 lite"))
    assert least["seconds"] == pytest.approx(
        full["seconds"] * (1 + 4 * band / triangle), rel=1e-3)
    assert k.calls_per_step(cfg) == 1


def test_grouped_matmul_kernel_counts_rows(cfg):
    k = spec.module("kernels", "grouped_matmul")
    assert k.expert_layers(cfg) == 4
    assert k.grouped_flops(100, 2048, 1024) == {
        "fwd": 6 * 100 * 2048 * 1024, "bwd": 12 * 100 * 2048 * 1024}
    peaks = peaks_for("TPU v5 lite")
    many = k.least_seconds(cfg, 65536, peaks)
    assert many["bound"] == "compute"
    assert many["seconds"] == pytest.approx(
        18 * 65536 * 2048 * 1024 / 197e12)
    assert k.least_seconds(cfg, 64, peaks)["bound"] == "memory"


def _traced_run(events, counters):
    step = {"phase": "window", "committed": True, "world": 1, "t0": 100,
            "t1": 2100, "timings": {}}
    return {"groups": 1, "steps": {0: [step]}, "counters": counters,
            "cfg": spec.Cell(CELL, REPO).config, "device_kind": "TPU v5 lite",
            "device_trace": {"planes": {"/device:TPU:0": events},
                             "modules": {}, "lo": 0, "hi": 3000}}


MOE_RX = spec.data("metrics", "moe_device_ms")["reader"]["pattern"] \
    if os.path.exists(os.path.join(BENCH, "metrics/moe_device_ms.json")) \
    else None


def test_op_ms_reads_named_ops_inside_counted_steps(bench):
    reader = spec.data("metrics", "moe_device_ms")["reader"]
    names = json.load(open(os.path.join(
        BENCH, "tests/afmoe_op_names.json")))
    events = [(n, 200 + 10 * i, 205 + 10 * i)
              for i, n in enumerate(names["moe"] + names["other"])]
    events.append((names["moe"][0], 2500, 2600))       # outside the step
    run = _traced_run(events, {})
    assert readers.read(run, reader) == pytest.approx(
        5e-9 * len(names["moe"]) * 1e3)
    assert readers.read(_traced_run([e for e in events if e[0]
                                     in names["other"]], {}), reader) is None
    assert readers.read({**run, "device_trace": None}, reader) is None


def test_moe_roofline_reckons_from_the_windows_own_pairs(bench):
    reader = spec.data("metrics", "moe_experts_roofline")["reader"]
    names = json.load(open(os.path.join(
        BENCH, "tests/afmoe_op_names.json")))
    least = spec.module("kernels", "grouped_matmul").least_seconds(
        spec.Cell(CELL, REPO).config, 60000.0, peaks_for("TPU v5 lite"))
    took = 4 * least["seconds"]
    events = [(names["experts"][0], 200, 200 + int(took * 1e9))]
    counters = {"begin.0": {"moe_pairs_local_total": 1000.0,
                            "committed_steps": 10.0},
                "end.0.0": {"moe_pairs_local_total": 1000.0 + 60000 * 5,
                            "committed_steps": 15.0}}
    run = _traced_run(events, counters)
    run["steps"][0][0]["t1"] = 300 + int(took * 1e9)
    run["device_trace"]["hi"] = 10 ** 12
    assert readers.read(run, reader) == pytest.approx(25.0, rel=1e-3)
    # a program without the counter (the parent): nothing, and no error
    bare = {"begin.0": {"committed_steps": 10.0},
            "end.0.0": {"committed_steps": 15.0}}
    assert readers.read({**run, "counters": bare}, reader) is None
    assert readers.read({**run, "counters": {}}, reader) is None


def test_the_cell_is_found_and_runs_in_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 29), "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, out.stdout[-3000:]
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    # 1 x 64 tokens a step (the mix's batch of 1 at REHEARSE_SEQ), every one
    # of 4 experts selected, 2 held, 4 expert layers
    assert got["moe_pairs_local"]["value"] == 4 * 64 * 2
    assert "moe_device_ms" not in got and "mfu_pct" not in got
