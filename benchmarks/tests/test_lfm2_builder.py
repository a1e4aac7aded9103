"""Builder ``lfm2_moe_decoder`` and what PR 47 added beside it: the
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
CELL = "lfm2-8b-a1b.steady-1g-8k"
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
    return spec.module("models", "lfm2_moe_decoder")


@pytest.fixture(scope="module")
def names():
    with open(os.path.join(BENCH, "tests/lfm2_op_names.json")) as f:
        return json.load(f)


def test_param_count_is_the_trees_size_from_shapes_only(cfg, M):
    shapes = jax.tree_util.tree_leaves(
        M.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in shapes) == M.param_count(cfg)
    assert len(shapes) == 49
    # by hand (ISSUE 47's arithmetic)
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert conv == 16_783_360
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert attention == 10_485_888
    dense, router, norms = 3 * 2048 * 7168, 2048 * 32, 2 * 2048
    held = 8 * 3 * 2048 * 1792
    assert (dense, router, held) == (44_040_192, 65_536, 88_080_384)
    layer0 = conv + dense + norms
    layer2 = attention + router + held + norms
    layer3 = conv + router + held + norms
    assert (layer0, layer2, layer3) == (60_827_648, 98_635_904, 104_933_376)
    table = 16_384 * 2048
    assert layer0 + layer2 + 3 * layer3 + table + 2048 == 507_820_160
    assert M.param_count(cfg) == 507_820_160
    # the fallback the ISSUE names (an eighth of the vocabulary), not run
    assert M.param_count({**cfg, "vocab_size": 8192}) == 491_042_944


def test_the_table_is_the_head_and_counts_once(cfg, M):
    tree = M.param_shapes(cfg)["params"]
    assert "lm_head" not in tree
    assert tree["embed"]["embedding"] == (16_384, 2048)
    untied = M.param_count(cfg) + 16_384 * 2048
    assert untied == 541_374_592


def test_operation_counts_by_hand(cfg, M):
    layers = M.layer_forward_flops(cfg, 8192)
    assert [sorted(p) for p in layers] == [
        ["conv", "mlp", "proj"], ["attn", "proj", "routed", "router"],
        *[["conv", "proj", "routed", "router"]] * 3]
    assert layers[0]["proj"] == 2 * 2048 * 6144 + 2 * 2048 * 2048
    assert layers[0]["mlp"] == 3 * 2 * 2048 * 7168
    assert layers[1]["proj"] == 2 * 2048 * (2048 + 2 * 512) + 2 * 2048 * 2048
    assert layers[1]["attn"] == 2 * (2 * 64 * 32 * 8193 / 2)
    # one pair a token a layer lands on this chip's quarter at uniform
    # routing
    assert layers[2]["routed"] == 1.0 * 3 * 2 * 2048 * 1792
    total = M.forward_flops_per_token(cfg, 8192)
    assert total == sum(sum(p.values()) for p in layers) + 2 * 2048 * 16_384
    assert 432e6 < total < 434e6
    assert M.train_flops_per_token(cfg, 8192) == 3 * total
    share = 4 * layers[2]["routed"] / total
    assert 0.19 < share < 0.21


def test_the_configuration_file_against_the_catalogs_row(bench, cfg):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b")
    assert entry["source"] == row["source_url"] == cfg["source"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == ["num_hidden_layers", "vocab_size"]
    assert set(differ) | {"num_experts_held"} == set(entry["reduced"])
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert cfg["published"] == {**cfg["published"], "num_hidden_layers": 24,
                                "vocab_size": 65536, "num_experts_held": 32}
    assert cfg["published_layers"] == [0, 2, 3, 4, 5]
    assert [cfg["layer_types"][i] for i in cfg["published_layers"]] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["vocab_size"] * 4 == 65536
    assert cfg["num_experts_held"] * 4 == cfg["num_experts"]
    assert {"limits", "limits_readings", "assumed", "stands_for",
            "cut"} <= set(cfg)
    assert {"tied_head", "conv_operator", "attention", "routing",
            "expert_bias", "dense_width", "not_built", "training_precision",
            "values"} <= set(cfg["assumed"])
    assert "4 chips a layer" in cfg["stands_for"]
    assert "507,820,160" in cfg["cut"]


def test_the_limit_lies_between_its_readings(cfg):
    """At least 1.5 times the largest sound reading, at most a third of the
    weakest control (``benchmarks/control.py`` on the chip, PR 47)."""
    limit = cfg["limits"]["grad_vs_reference"]
    r = cfg["limits_readings"]["grad_vs_reference"]
    assert r["limit"] == limit
    sound = max(hi for _, hi in r["sound"].values())
    controls = {k: lo for k, (lo, _) in r["controls"].items()}
    assert any(k.startswith("fp8_matmul") for k in controls)
    assert any(k.startswith("drop_taps") for k in controls)
    assert 1.5 * sound <= limit <= min(controls.values()) / 3


def test_the_cells_entries_in_the_benchmark_file(bench):
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady-1g-8k"
    assert cell["config"] == "lfm2-8b-a1b"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    new = ["attn_gqa64_roofline", "shortconv_device_ms", "shortconv_tokens",
           "moe_device_ms_1792"]
    # at least these (later PRs add metrics to the cell's list)
    assert listed >= {
        "entry_other_ms", "quorum_ms", "commit_ms", "raw_step_ms", "mfu_pct",
        "device_idle_pct", "peak_hbm_gib", "attest_device_ms", "dispatch_ms",
        "publish_status_ms", "state_digest_wait_ms", "boundary_host_ms",
        "idle_dispatch_ms", "idle_boundary_ms", "idle_wait_ms",
        "idle_unspanned_ms", "moe_pairs_local", "moe_experts_roofline", *new}
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["shortconv_device_ms"] == layers["shortconv_tokens"] \
        == "short convolution"
    assert layers["attn_gqa64_roofline"] == layers["flash_roofline"]
    assert layers["moe_device_ms_1792"] == layers["moe_device_ms"]
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_layer_types_kernel_counts_the_one_attention_layer_by_hand(cfg):
    k = spec.module("kernels", "layer_types_flash_attention")
    assert k.calls_per_step(cfg) == 1
    assert k.attention_layers(
        {**cfg, "published_layers": list(range(11))}) == 3
    pairs = 32 * 8192 * 8193 / 2
    f = spec.module("kernels", "hybrid_flash_attention").triangle_flops(
        1, 8192, 32, 64)
    assert f["fwd"] == 2 * 2 * 64 * pairs and f["bwd"] == 5 * 2 * 64 * pairs
    least = k.least_seconds(cfg, 1, 8192, peaks_for(V5E))
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx((f["fwd"] + f["bwd"]) / 197e12)
    q, kv, stat = 8192 * 32 * 64 * 2, 8192 * 8 * 64 * 2, 8192 * 32 * 4
    assert least["bytes"] == (2 * q + 2 * kv + stat) \
        + (4 * q + 4 * kv + 2 * stat)
    # what kernels/flash_attention.py would read of this configuration: the
    # same head (it divides hidden by heads) but a call in each of five
    # layers
    old = spec.module("kernels", "flash_attention")
    assert old.calls_per_step(cfg) == 5
    # (and the square's half where this file counts the triangle)
    assert old.least_seconds(cfg, 1, 8192, peaks_for(V5E))["seconds"] \
        == pytest.approx(least["seconds"], rel=1e-3)


def test_the_grouped_products_kernel_reads_this_configuration(cfg):
    """``kernels/grouped_matmul.py`` as it stands: four expert layers among
    the five that run, 8 held experts of 2048 x 1792, three products."""
    k = spec.module("kernels", "grouped_matmul")
    assert k.expert_layers(cfg) == 4
    rows = 32768.0                     # 4 layers x 8192 x 4 x 8 / 32
    least = k.least_seconds(cfg, rows, peaks_for(V5E))
    f = k.grouped_flops(rows, 2048, 1792)
    assert f["fwd"] == 3 * 2 * rows * 2048 * 1792
    assert least["flops"] == 3 * f["fwd"]
    b = k.grouped_bytes(rows, 4, 8, 2048, 1792)
    assert least["bytes"] == b["fwd"] + b["bwd"]
    assert least["bound"] == "compute"
    assert k.least_seconds(cfg, 8192.0, peaks_for(V5E))["bound"] == "memory"


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


PINNED = {"shortconv_device_ms": "shortconv", "moe_device_ms_1792": "moe",
          "attn_gqa64_roofline": "attention", "moe_experts_roofline": "gmm"}


@pytest.mark.parametrize("metric", list(PINNED), ids=list(PINNED))
def test_patterns_match_the_names_a_traced_run_gave(bench, names, metric):
    """``tests/lfm2_op_names.json`` holds event names as the chip's profile
    spelt them (my traced run, PR 47, the first 1,500 characters of each):
    each metric's pattern finds its own and none of the others'."""
    pattern = spec.data("metrics", metric)["reader"]["pattern"]
    mine = names[PINNED[metric]]
    assert mine and all(re.search(pattern, n) for n in mine)
    rest = [n for key, group in names.items() if key != PINNED[metric]
            for n in group]
    assert rest and not any(re.search(pattern, n) for n in rest)


OTHERS = ("afmoe_op_names.json", "mla_op_names.json", "gdn_op_names.json",
          "mamba2_op_names.json")


@pytest.mark.parametrize("file", OTHERS)
def test_new_shape_patterns_match_nothing_of_the_other_cells(bench, file):
    with open(os.path.join(BENCH, "tests", file)) as f:
        theirs = [n for group in json.load(f).values() for n in group]
    metrics = ["moe_device_ms_1792"]
    if file != "mamba2_op_names.json":
        # that cell's convolution runs over 6,144 channels too, and its
        # compiler keeps the batch's 1 there as well: the streams' shape is
        # this cell's alone only among this cell's operations, and the
        # metric lists this cell alone
        metrics.append("shortconv_device_ms")
    for metric in metrics:
        pattern = spec.data("metrics", metric)["reader"]["pattern"]
        assert not any(re.search(pattern, n) for n in theirs), metric


def test_device_metrics_read_their_ops_inside_the_steps(bench, names):
    for metric, key in (("shortconv_device_ms", "shortconv"),
                        ("moe_device_ms_1792", "moe")):
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
    reader = spec.data("metrics", "attn_gqa64_roofline")["reader"]
    k = spec.module("kernels", "layer_types_flash_attention")
    least = k.least_seconds(cfg, 1, 8192, peaks_for(V5E))["seconds"]
    took = int(4 * least * 1e9)                        # a quarter of the roof
    step = took + 2000
    events = [(names["attention"][0], 200 + i * step, 200 + i * step + took)
              for i in range(2)]
    assert readers.read(_traced_run(events, {}, step), reader) \
        == pytest.approx(25.0, rel=1e-3)
    reader = spec.data("metrics", "moe_experts_roofline")["reader"]
    g = spec.module("kernels", "grouped_matmul")
    counters = {"begin.0": {"moe_pairs_local_total": 0.0,
                            "committed_steps": 2},
                "end.0.0": {"moe_pairs_local_total": 10 * 10_000.0,
                            "committed_steps": 12}}
    least = g.least_seconds(cfg, 10_000.0, peaks_for(V5E))["seconds"]
    took = int(4 * least * 1e9)
    step = took + 2000
    events = [(names["gmm"][0], 200 + i * step, 200 + i * step + took)
              for i in range(2)]
    assert readers.read(_traced_run(events, counters, step), reader) \
        == pytest.approx(25.0, rel=1e-3)
    # a program without the counter (the parent's): nothing, and no error
    assert readers.read(_traced_run(events, {}, step), reader) is None


def test_tokens_metric_reads_the_counter_a_committed_step(bench):
    reader = spec.data("metrics", "shortconv_tokens")["reader"]
    counters = {"begin.0": {"shortconv_tokens_total": 65536.0,
                            "committed_steps": 2},
                "end.0.0": {"shortconv_tokens_total": 65536.0 + 10 * 32768,
                            "committed_steps": 12}}
    assert readers.read(_traced_run([], counters), reader) == 32768.0
    assert readers.read(_traced_run([], {}), reader) is None
    # the parent's program has no such counter: nothing to read
    assert readers.read(_traced_run([], {
        "begin.0": {"committed_steps": 2},
        "end.0.0": {"committed_steps": 12}}), reader) is None


def test_the_builder_stops_a_program_without_the_mixer(tmp_path):
    """The builder imports ``torchft_tpu.models.short_conv`` at its top: a
    checkout whose program lacks the module (the parent of PR 47) fails
    when the driver loads the builder, at once and with rc 1."""
    src = os.path.join(BENCH, "models", "lfm2_moe_decoder.py")
    with open(src) as f:
        head = f.read().split("CONV, FULL =")[0]
    assert "import torchft_tpu.models.short_conv" in head
    (tmp_path / "torchft_tpu").mkdir()
    (tmp_path / "torchft_tpu" / "__init__.py").write_text("")
    (tmp_path / "torchft_tpu" / "models").mkdir()
    (tmp_path / "torchft_tpu" / "models" / "__init__.py").write_text("")
    out = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys; sys.path.insert(0, sys.argv[1]); "
         "runpy.run_path(sys.argv[2])", str(tmp_path), src],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 1
    assert "ModuleNotFoundError" in out.stderr
    assert "torchft_tpu.models.short_conv" in out.stderr


def test_the_cell_is_found_and_runs_in_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 47), "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, out.stdout[-3000:]
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    # 1 x 64 tokens: four conv layers; every one of 4 experts selected, 2
    # held, 4 expert layers
    assert got["shortconv_tokens"]["value"] == 4 * 64
    assert got["moe_pairs_local"]["value"] == 4 * 64 * 2
    for device_metric in ("shortconv_device_ms", "moe_device_ms_1792",
                          "moe_experts_roofline", "attn_gqa64_roofline",
                          "mfu_pct"):
        assert device_metric not in got
