"""Builder ``dsa_moe_decoder`` and what PR 60 added beside it: the
configuration file against the catalog's row, the parameter count against
the tree and a hand count, operation counts and the three new kernel files
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
CELL = "keye-vl-2.0-30b-a3b.steady-1g-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
V5E = "TPU v5 lite"
PAIRS = 14_681_088          # sum_t min(t + 1, 2048) at 8,192 tokens
NEW = ["attn_sparse_roofline", "sparse_select_roofline",
       "sparse_select_device_ms", "indexer_loss_roofline",
       "indexer_loss_device_ms", "sparse_keys_per_query_milli",
       "indexer_kl_micro", "moe_device_ms_16x768"]


@pytest.fixture(scope="module")
def bench():
    return spec.configure(REPO)


@pytest.fixture(scope="module")
def cfg(bench):
    return spec.Cell(CELL, REPO).config


@pytest.fixture(scope="module")
def M(bench):
    return spec.module("models", "dsa_moe_decoder")


@pytest.fixture(scope="module")
def names():
    with open(os.path.join(BENCH, "tests/dsa_op_names.json")) as f:
        return json.load(f)


def test_param_count_is_the_trees_size_from_shapes_only(cfg, M):
    shapes = jax.tree_util.tree_leaves(
        M.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in shapes) == M.param_count(cfg)
    # a layer: six attention leaves, the indexer's five, two norms, the
    # router and three stacks
    assert len(shapes) == 4 * (6 + 5 + 2 + 4) + 3 == 71
    # by hand (ISSUE 60's arithmetic)
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16
    assert (attention, indexer) == (18_874_368, 2_260_992)
    layer = attention + indexer + 128 + 262_144 + 4_096 + 256
    assert layer == 21_401_984
    expert = 3 * 2048 * 768
    total = 4 * (layer + 16 * expert) + 2 * 18_992 * 2048 + 2048
    assert total == M.param_count(cfg) == 465_391_104


def test_operation_counts_by_hand(cfg, M):
    assert M.selected_pairs(8192, 2048) == PAIRS
    assert M.selected_pairs(48, 16) == 136 + 32 * 16
    part = {k: v * 8192 for k, v in M.layer_forward_flops(cfg, 8192)[0].items()}
    assert part["proj"] == 2.0 * 8192 * 2048 * (2 * 4096 + 2 * 512)
    assert part["attn"] == 2 * 2.0 * 128 * 32 * PAIRS
    assert part["indexer_proj"] == 2.0 * 8192 * 2048 * (1024 + 64 + 16)
    assert part["index_scores"] == 2.0 * 16 * 64 * 8192 * 8193 / 2
    assert part["router"] == 2.0 * 8192 * 2048 * 128
    assert part["routed"] == pytest.approx(8192 * 3 * 2.0 * 2048 * 768)
    forward = 4 * sum(part.values()) + 2.0 * 8192 * 2048 * 18_992
    assert M.forward_flops_per_token(cfg, 8192) * 8192 == pytest.approx(
        forward)
    assert M.train_flops_per_token(cfg, 8192) * 8192 == pytest.approx(
        3 * forward)
    assert 3 * forward == pytest.approx(10.76e12, rel=1e-3)


def test_the_configuration_file_against_the_catalogs_row(bench, cfg):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["source"] == row["source_url"] == cfg["source"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == ["num_hidden_layers", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts_held", "vocab_size"]
    assert cfg["sa_config"] == row["config"]["sa_config"]
    assert cfg["rope_scaling"] == row["config"]["rope_scaling"]
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (4, 16, 18_992)
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["published"]["num_experts_held"] == 128
    assert cfg["published"]["vocab_size"] == 151_936 == 8 * 18_992
    assert cfg["published_layers"] == [0, 1, 2, 3]
    assert {"limits", "limits_readings", "assumed", "stands_for",
            "cut"} <= set(cfg)
    assert {"qk_norm", "positions", "indexer_key_norm", "indexer_positions",
            "indexer_loss_weight", "objective", "chunk_sizes", "routing",
            "training_precision", "tower"} <= set(cfg["assumed"])
    assert "8 chips a layer" in cfg["stands_for"]
    assert "465,391,104" in cfg["cut"]


def test_the_limit_lies_between_the_readings_it_can_tell(cfg):
    """``scripts/control_some.py`` (``benchmarks/control.py``'s functions)
    on the chip, PR 60: the limit is at most a third of every control's
    least reading, ``no_indexer_loss`` (a zero gradient on the indexer's
    leaves, exactly 1) among them, and at least 1.5 times the largest sound
    reading, the cell's own runs included."""
    limit = cfg["limits"]["grad_vs_reference"]
    r = cfg["limits_readings"]["grad_vs_reference"]
    assert r["limit"] == limit
    sound = max(hi for _, hi in r["sound"].values())
    controls = {k: lo for k, (lo, _) in r["controls"].items()}
    for control in ("fp8_matmul", "dense_attention", "topk_half",
                    "no_indexer_loss"):
        assert any(k.startswith(control) for k in controls), control
    assert 1.5 * sound <= limit <= min(controls.values()) / 3
    assert cfg["embedding_rows_times_sqrt_hidden"] is True
    assert "head_norm_gain_shift" not in cfg


def test_the_cells_entries_in_the_benchmark_file(bench):
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady-1g-8k"
    assert cell["config"] == "keye-vl-2.0-30b-a3b"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    # at least these (later PRs add metrics to the cell's list)
    assert listed >= {
        "entry_other_ms", "quorum_ms", "commit_ms", "raw_step_ms", "mfu_pct",
        "device_idle_pct", "peak_hbm_gib", "attest_device_ms", "dispatch_ms",
        "publish_status_ms", "state_digest_wait_ms", "boundary_host_ms",
        "idle_dispatch_ms", "idle_boundary_ms", "idle_wait_ms",
        "idle_unspanned_ms", "moe_pairs_local", "moe_passes",
        "moe_experts_roofline", *NEW}
    # flash_roofline counts the whole triangle: a kernel that does only the
    # selected work would read over 100 % by it
    assert "flash_roofline" not in listed
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["attn_sparse_roofline"] == layers["flash_roofline"]
    assert layers["moe_device_ms_16x768"] == layers["moe_device_ms_768"]
    assert layers["indexer_kl_micro"] == layers["raw_step_ms"]
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_the_kernel_files_count_by_hand(cfg):
    peaks = peaks_for(V5E)
    k = spec.module("kernels", "sparse_flash_attention")
    assert k.calls_per_step(cfg) == 4
    assert k.selected_pairs(8192, 2048) == PAIRS
    least = k.least_seconds(cfg, 1, 8192, peaks)
    pair = 2.0 * 32 * PAIRS * 128
    assert least["flops"] == 7 * pair == pytest.approx(0.842e12, rel=1e-3)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(4.27e-3, rel=2e-3)
    dense = spec.module("kernels", "flash_attention").flash_bytes(
        1, 8192, 32, 4, 128)
    assert least["bytes"] == dense["fwd"] + dense["bwd"] \
        + 2 * 8192 * 2048 * 2
    # the whole triangle, which flash_roofline's file counts: 2.29 times
    whole = spec.module("kernels", "flash_attention").flash_flops(
        1, 8192, 32, 128)
    assert (whole["fwd"] + whole["bwd"]) / least["flops"] == pytest.approx(
        8192 * 8192 / 2 / PAIRS)
    s = spec.module("kernels", "sparse_select")
    one = s.least_seconds(cfg, 1, 8192, peaks)
    assert s.calls_per_step(cfg) == 4
    assert one["flops"] == 2.0 * 16 * 64 * 8192 * 8193 / 2
    assert one["bytes"] == 8192 * ((1024 + 64) * 2 + 16 * 4 + 2048 * 2)
    assert one["bound"] == "compute"
    lo = spec.module("kernels", "indexer_loss")
    two = lo.least_seconds(cfg, 1, 8192, peaks)
    assert lo.calls_per_step(cfg) == 4
    assert two["flops"] == 2 * 2.0 * 16 * 64 * PAIRS
    assert two["seconds"] == pytest.approx(
        max(two["flops"] / 197e12, two["bytes"] / 819e9))


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


PINNED = {"attn_sparse_roofline": "attention",
          "sparse_select_device_ms": "select",
          "sparse_select_roofline": "select",
          "indexer_loss_device_ms": "loss",
          "indexer_loss_roofline": "loss",
          "moe_device_ms_16x768": "experts"}


@pytest.mark.parametrize("metric", list(PINNED), ids=list(PINNED))
def test_patterns_match_the_names_a_traced_run_gave(bench, names, metric):
    """``tests/dsa_op_names.json`` holds event names as the chip's profile
    spelt them (my traced run, PR 60; the first 1,500 characters of each):
    each metric's pattern finds its own and none of the others'."""
    pattern = spec.data("metrics", metric)["reader"]["pattern"]
    mine = names[PINNED[metric]]
    assert mine and all(re.search(pattern, n) for n in mine)
    rest = [n for key, group in names.items() if key != PINNED[metric]
            for n in group]
    assert rest and not any(re.search(pattern, n) for n in rest)


def test_the_trace_holds_a_layers_four_kernels(names):
    """A forward and a fused backward a layer, one selection, one loss
    pass; two pass loops (forward, backward) an expert layer at most."""
    kinds = [re.match(r"%(\w+?)[.\d]* =", n).group(1)
             for n in names["attention"]]
    assert set(kinds) == {"flash_fwd_sparse", "flash_bwd_sparse"}
    assert all(n.startswith("%sparse_select") for n in names["select"])
    assert all(n.startswith("%indexer_loss") for n in names["loss"])
    assert all("bf16[16,2048,768]" in n for n in names["experts"])


OTHERS = ("afmoe_op_names.json", "mla_op_names.json", "gdn_op_names.json",
          "mamba2_op_names.json", "lfm2_op_names.json",
          "smallthinker_op_names.json", "looped_op_names.json")


@pytest.mark.parametrize("file", OTHERS)
def test_new_patterns_match_nothing_of_the_other_cells(bench, file):
    with open(os.path.join(BENCH, "tests", file)) as f:
        theirs = [n for group in json.load(f).values() for n in group]
    for metric in PINNED:
        pattern = spec.data("metrics", metric)["reader"]["pattern"]
        assert not any(re.search(pattern, n) for n in theirs), metric


def test_accepted_flash_patterns_do_not_read_the_selected_kernels(bench,
                                                                  names):
    """``flash_roofline`` and its kin find the dense kernels by their
    caller's scope or their own names: none matches a selected kernel."""
    for m in bench["per_layer"]:
        reader = spec.data("metrics", m["name"])["reader"]
        if m["name"] in PINNED or "pattern" not in reader \
                or CELL in m.get("workloads", []):
            continue
        for group in ("attention", "select", "loss"):
            assert not any(re.search(reader["pattern"], n)
                           for n in names[group]), m["name"]


def test_device_metrics_read_their_ops_inside_the_steps(bench, names):
    for metric, key in (("sparse_select_device_ms", "select"),
                        ("indexer_loss_device_ms", "loss"),
                        ("moe_device_ms_16x768", "experts")):
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


@pytest.mark.parametrize("metric,kernel,key", [
    ("attn_sparse_roofline", "sparse_flash_attention", "attention"),
    ("sparse_select_roofline", "sparse_select", "select"),
    ("indexer_loss_roofline", "indexer_loss", "loss")])
def test_the_rooflines_read_a_share_under_a_hundred(bench, names, metric,
                                                    kernel, key):
    cfg = spec.Cell(CELL, REPO).config
    reader = spec.data("metrics", metric)["reader"]
    k = spec.module("kernels", kernel)
    least = k.least_seconds(cfg, 1, 8192, peaks_for(V5E))["seconds"]
    took = int(4 * 4 * least * 1e9)        # 4 calls at a quarter of the roof
    step = took + 2000
    events = [(names[key][0], 200 + i * step, 200 + i * step + took)
              for i in range(2)]
    assert readers.read(_traced_run(events, {}, step), reader) \
        == pytest.approx(25.0, rel=1e-3)


@pytest.mark.parametrize("metric,key,a_step", [
    ("sparse_keys_per_query_milli", "sparse_selected_keys_milli_total",
     1_792_125.0),
    ("indexer_kl_micro", "indexer_kl_micro_total", 81_234.5)])
def test_counter_metrics_read_a_committed_step(bench, metric, key, a_step):
    assert 1000 * PAIRS / 8192 == 1_792_125.0
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


def test_the_builder_stops_a_program_without_the_sparse_loss(tmp_path):
    """The builder imports ``sparse_lm_loss`` at its top: a checkout whose
    program lacks it (the parent of PR 60) fails when the driver loads the
    builder, at once and with rc 1."""
    src = os.path.join(BENCH, "models", "dsa_moe_decoder.py")
    with open(src) as f:
        head = f.read().split("REHEARSE =")[0]
    assert "sparse_lm_loss" in head
    pkg = tmp_path / "torchft_tpu" / "models"
    pkg.mkdir(parents=True)
    (tmp_path / "torchft_tpu" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    out = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys; sys.path.insert(0, sys.argv[1]); "
         "runpy.run_path(sys.argv[2])", str(tmp_path), src],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 1
    assert "ImportError" in out.stderr
    assert "sparse_lm_loss" in out.stderr


def test_the_cell_is_found_and_runs_in_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 60), "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    # the rehearsal's 64 tokens under topk 32
    assert got["sparse_keys_per_query_milli"]["value"] == pytest.approx(
        1000 * (32 * 33 / 2 + 32 * 32) / 64)
    assert got["indexer_kl_micro"]["value"] > 0
    assert got["moe_pairs_local"]["value"] > 0
    for device_metric in ("attn_sparse_roofline", "sparse_select_roofline",
                          "indexer_loss_roofline", "mfu_pct"):
        assert device_metric not in got
