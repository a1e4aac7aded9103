"""Builder ``mla_moe_decoder`` and what PR 33 added beside it: the
configuration file against the catalog's row, the parameter count against
the tree, operation counts and the latent kernel's file against a hand
count, the three new metrics' patterns against names pinned from a traced
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
CELL = "joyai-llm-flash.steady-1g-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def bench():
    return spec.configure(REPO)


@pytest.fixture(scope="module")
def cfg(bench):
    return spec.Cell(CELL, REPO).config


@pytest.fixture(scope="module")
def M(bench):
    return spec.module("models", "mla_moe_decoder")


@pytest.fixture(scope="module")
def names():
    with open(os.path.join(BENCH, "tests/mla_op_names.json")) as f:
        return json.load(f)


def test_param_count_is_the_trees_size_from_shapes_only(cfg, M):
    shapes = jax.tree_util.tree_leaves(
        M.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in shapes) == M.param_count(cfg)
    # by hand (ISSUE 33's table): attention W_qa + W_qb + W_kva + W_kvb + W_o
    attn = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
            + 4096 * 2048)
    assert attn == 26_345_472
    expert_layer = attn + 2_048 + 4_096 + 2048 * 256 + 3 * 2048 * 768 * 9
    assert expert_layer == 69_343_232
    dense_layer = attn + 2_048 + 4_096 + 3 * 2048 * 7168
    assert dense_layer == 70_391_808
    module = 4096 * 2048 + expert_layer + 3 * 2048
    assert module == 77_737_984
    assert M.param_count(cfg) == (dense_layer + 3 * expert_layer + module
                                  + 2 * 16160 * 2048 + 2048)
    assert M.param_count(cfg) == 422_352_896
    # ISSUE 33's cut, one trunk layer more, which the oracle has no room for
    five = {**cfg, "num_hidden_layers": 5,
            "published_layers": [0, 1, 2, 3, 4, 40]}
    assert M.param_count(five) == 491_696_128


@pytest.mark.parametrize("part,want", [
    ("proj", 2 * 26_345_472),
    ("attn", 2 * (192 + 128) * 32 * 8193 / 2),
    ("router", 2 * 2048 * 256), ("shared", 6 * 2048 * 768),
    ("routed", 8 * 8 / 256 * 6 * 2048 * 768)])
def test_forward_flops_of_an_expert_layer_by_hand(cfg, M, part, want):
    assert M.layer_forward_flops(cfg, 8192)[1][part] == want


def test_train_flops_count_the_module_and_the_head_twice(cfg, M):
    parts = M.layer_forward_flops(cfg, 8192)
    assert len(parts) == 5                      # four layers and the module
    assert "mlp" in parts[0] and "routed" not in parts[0]
    assert parts[4]["eh_proj"] == 2 * 4096 * 2048
    fwd = M.forward_flops_per_token(cfg, 8192)
    assert fwd == pytest.approx(sum(sum(p.values()) for p in parts)
                                + 2 * 2 * 2048 * 16160)
    assert M.train_flops_per_token(cfg, 8192) == 3 * fwd
    assert fwd == pytest.approx(971.6e6, rel=1e-3)
    five = {**cfg, "num_hidden_layers": 5,
            "published_layers": [0, 1, 2, 3, 4, 40]}
    assert M.forward_flops_per_token(five, 8192) == pytest.approx(
        1121.0e6, rel=1e-3)                              # ISSUE 33: 1,121
    attn = sum(p["attn"] for p in parts)
    routed = sum(p.get("routed", 0.0) for p in parts)
    assert 0.42 < attn / fwd < 0.45                      # "about 45 %"
    assert 0.009 < routed / fwd < 0.012                  # "about 1 %"


def test_the_file_holds_every_number_of_the_catalog_row(bench, cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "JoyAI-LLM-Flash")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "joyai-llm-flash")
    assert entry["source"] == row["source_url"] == cfg["source"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == ["num_hidden_layers", "vocab_size"]
    assert set(differ) | {"num_experts_held"} == set(entry["reduced"])
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert cfg["published"] == {**cfg["published"], "num_hidden_layers": 40,
                                "vocab_size": 129280, "num_experts_held": 256}
    assert cfg["published_layers"] == [0, 1, 2, 3, 40]
    assert cfg["vocab_size"] * 8 == 129280 and cfg["num_experts_held"] == 8
    assert {"limits", "limits_readings", "assumed", "stands_for",
            "cut"} <= set(cfg)
    assert {"mtp_loss_weight", "mtp_module", "expert_bias", "routing",
            "training_precision", "values"} <= set(cfg["assumed"])


def test_every_line_of_the_benchmark_file_is_within_its_limits(bench):
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady-1g-8k"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    # At least these (later PRs add metrics to the cell's list, and after
    # these three in the file).
    assert listed >= {
        "entry_other_ms", "quorum_ms", "commit_ms", "raw_step_ms", "mfu_pct",
        "device_idle_pct", "peak_hbm_gib", "attest_device_ms",
        "moe_experts_roofline", "moe_pairs_local", "attn_mla_roofline",
        "moe_device_ms_768", "head_loss_device_ms"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_latent_kernel_counts_two_head_sizes_by_hand(cfg):
    k = spec.module("kernels", "mla_flash_attention")
    assert k.calls_per_step(cfg) == 5
    pairs = 32 * 8192 * 8193 / 2
    f = k.mla_flops(1, 8192, 32, 192, 128)
    assert f["fwd"] == 2 * (192 + 128) * pairs
    assert f["bwd"] == 2 * (3 * 192 + 2 * 128) * pairs
    # a fifth less than one head size of 192 for all five backward matmuls
    one = spec.module("kernels", "flash_attention").flash_flops(
        1, 8192, 32, 192)
    assert (f["fwd"] + f["bwd"]) / (one["fwd"] + one["bwd"]) \
        == pytest.approx((320 + 832) / (7 * 192) * 8193 / 8192)
    b = k.mla_bytes(1, 8192, 32, 128, 64, 128)
    q, kk, v, stat = (8192 * 32 * 192 * 2, 8192 * (32 * 128 + 64) * 2,
                      8192 * 32 * 128 * 2, 8192 * 32 * 4)
    assert b["fwd"] == q + kk + 2 * v + stat
    assert b["bwd"] == 2 * q + 2 * kk + 4 * v + 2 * stat
    least = k.least_seconds(cfg, 1, 8192, peaks_for("TPU v5 lite"))
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(
        (f["fwd"] + f["bwd"]) / 197e12)
    # what the grouped-matmul file reads of this configuration
    g = spec.module("kernels", "grouped_matmul")
    assert g.expert_layers(cfg) == 4
    assert g.least_seconds(cfg, 8192.0, peaks_for("TPU v5 lite"))[
        "bound"] == "memory"


def _traced_run(events, counters):
    step = {"phase": "window", "committed": True, "world": 1, "t0": 100,
            "t1": 2100, "timings": {}}
    return {"groups": 1, "groups_on_device": 1, "batch": 1, "seq": 8192,
            "steps": {0: [step]}, "counters": counters,
            "cfg": spec.Cell(CELL, REPO).config, "device_kind": "TPU v5 lite",
            "device_trace": {"planes": {"/device:TPU:0": events},
                             "modules": {}, "lo": 0, "hi": 3000}}


PINNED = {"attn_mla_roofline": "attention", "moe_device_ms_768": "moe",
          "head_loss_device_ms": "loss", "moe_experts_roofline": "experts"}


@pytest.mark.parametrize("metric", list(PINNED), ids=list(PINNED))
def test_patterns_match_the_names_a_traced_run_gave(bench, names, metric):
    """``tests/mla_op_names.json`` holds event names as the chip's profile
    spelt them (my traced run, PR 33): each metric's pattern finds its own
    and none of the others'."""
    pattern = spec.data("metrics", metric)["reader"]["pattern"]
    mine = names[PINNED[metric]]
    assert mine and all(re.search(pattern, n) for n in mine)
    rest = [n for key, group in names.items() if key != PINNED[metric]
            for n in group]
    assert rest and not any(re.search(pattern, n) for n in rest)


def test_op_ms_metrics_read_their_ops_inside_counted_steps(bench, names):
    for metric, key in (("moe_device_ms_768", "moe"),
                        ("head_loss_device_ms", "loss")):
        reader = spec.data("metrics", metric)["reader"]
        events = [(n, 200 + 10 * i, 205 + 10 * i)
                  for i, n in enumerate(names[key] + names["other"])]
        events.append((names[key][0], 2500, 2600))     # outside the step
        run = _traced_run(events, {})
        assert readers.read(run, reader) == pytest.approx(
            5e-9 * len(names[key]) * 1e3)
        assert readers.read(_traced_run(
            [e for e in events if e[0] in names["other"]], {}),
            reader) is None
        assert readers.read({**run, "device_trace": None}, reader) is None


def test_latent_roofline_reads_the_mla_kernels(bench, names):
    reader = spec.data("metrics", "attn_mla_roofline")["reader"]
    k = spec.module("kernels", "mla_flash_attention")
    cfg = spec.Cell(CELL, REPO).config
    least = k.least_seconds(cfg, 1, 8192, peaks_for("TPU v5 lite"))
    took = int(4 * 5 * least["seconds"] * 1e9)       # a quarter of the roof
    events = [(names["attention"][0], 200, 200 + took)]
    run = _traced_run(events, {})
    run["steps"][0][0]["t1"] = 300 + took
    run["device_trace"]["hi"] = 10 ** 12
    assert readers.read(run, reader) == pytest.approx(25.0, rel=1e-3)
    # a program without such kernels (the parent): nothing, and no error
    assert readers.read(_traced_run(
        [(names["other"][0], 200, 300)], {}), reader) is None


def test_the_cell_is_found_and_runs_in_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 33), "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, out.stdout[-3000:]
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    # 1 x 64 tokens, every one of 4 experts selected, 2 held, 4 layers
    assert got["moe_pairs_local"]["value"] == 4 * 64 * 2
    for device_metric in ("attn_mla_roofline", "moe_device_ms_768",
                          "head_loss_device_ms", "mfu_pct"):
        assert device_metric not in got
