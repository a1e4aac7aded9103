"""Builder ``smallthinker_decoder`` and what PR 51 added beside it: the
configuration file against the catalog's row, its derived names against the
published keys they come from, the parameter count against the tree and a
hand count, operation counts and the two accepted kernel files reading this
configuration as they stand, the new metrics' patterns against names pinned
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
CELL = "smallthinker-21b-a3b.steady-1g-8k"
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
    return spec.module("models", "smallthinker_decoder")


@pytest.fixture(scope="module")
def names():
    with open(os.path.join(BENCH, "tests/smallthinker_op_names.json")) as f:
        return json.load(f)


def test_param_count_is_the_trees_size_from_shapes_only(cfg, M):
    shapes = jax.tree_util.tree_leaves(
        M.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in shapes) == M.param_count(cfg)
    assert len(shapes) == 43
    # by hand (ISSUE 51's arithmetic)
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    router, norms, expert = 2560 * 64, 2 * 2560, 3 * 2560 * 768
    assert (attention, router, expert) == (20_971_520, 163_840, 5_898_240)
    layer = attention + router + norms + 16 * expert
    assert layer == 115_512_320
    assert 4 * layer + 2 * 18_992 * 2560 + 2560 == 559_290_880
    assert M.param_count(cfg) == 559_290_880
    # the fallback the ISSUE names (8 of 64 held), not run
    assert M.param_count({**cfg, "num_experts_held": 8}) == 370_547_200


def test_operation_counts_by_hand(cfg, M):
    layers = M.layer_forward_flops(cfg, 8192)
    assert [sorted(p) for p in layers] == [
        ["attn", "proj", "routed", "router"]] * 4
    assert layers[0]["proj"] == 2 * 2560 * (3584 + 1024) + 2 * 3584 * 2560
    # the full layer's triangle, a windowed layer's band
    assert layers[0]["attn"] == 2 * (2 * 128 * 28 * 8193 / 2)
    keys = (4096 * 4097 / 2 + 4096 * 4096) / 8192
    assert keys == 3072.25
    assert layers[1]["attn"] == layers[3]["attn"] == 2 * (2 * 128 * 28 * keys)
    # 1.5 held experts a token a layer at uniform routing
    assert layers[2]["routed"] == 1.5 * 3 * 2 * 2560 * 768
    total = M.forward_flops_per_token(cfg, 8192)
    assert total == sum(sum(p.values()) for p in layers) + 2 * 2560 * 18_992
    assert total == 527_959_552
    assert M.train_flops_per_token(cfg, 8192) == 3 * total
    share = {k: sum(p[k] for p in layers) / total for k in layers[0]}
    assert 0.36 < share["attn"] < 0.365 and 0.13 < share["routed"] < 0.135


def test_the_configuration_file_against_the_catalogs_row(bench, cfg, M):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "smallthinker-21b-a3b")
    assert entry["source"] == row["source_url"] == cfg["source"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == ["num_hidden_layers", "vocab_size"]
    assert set(differ) | {"num_experts_held"} == set(entry["reduced"])
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert cfg["published"] == {**cfg["published"], "num_hidden_layers": 52,
                                "vocab_size": 151936, "num_experts_held": 64}
    assert cfg["published_layers"] == [0, 1, 2, 3]
    assert cfg["vocab_size"] * 8 == 151936
    assert cfg["num_experts_held"] * 4 == cfg["moe_num_primary_experts"]
    assert {"limits", "limits_readings", "assumed", "stands_for", "cut",
            "derived"} <= set(cfg)
    assert {"router_input", "routing", "expert", "secondary_experts",
            "attention", "norms", "not_built", "training_precision",
            "values"} <= set(cfg["assumed"])
    assert "4 chips a layer" in cfg["stands_for"]
    assert "559,290,880" in cfg["cut"]
    # every derived name equals what its published key gives
    for key, value in M.derived(cfg).items():
        assert cfg[key] == value and key in cfg["derived"], key
    assert [cfg["layer_types"][i] for i in cfg["published_layers"]] == [
        "full_attention"] + ["sliding_attention"] * 3
    assert cfg["sliding_window"] == cfg["sliding_window_size"] == 4096
    assert cfg["moe_intermediate_size"] == cfg["moe_ffn_hidden_size"] == 768
    assert cfg["num_dense_layers"] == 0


def test_the_limit_lies_between_its_readings(cfg):
    """Above every sound reading and at most a third of the weakest control
    (``benchmarks/control.py`` on the chip, PR 51). The five other sparse
    configurations also keep 1.5 times the largest sound reading; here a
    third of ``fp8_matmul`` (0.479) is 1.15 times it, so that cannot be
    had: the readings spread under 2 %, and the limit stands five of their
    whole ranges above the largest (``PERF.md`` section 7)."""
    limit = cfg["limits"]["grad_vs_reference"]
    r = cfg["limits_readings"]["grad_vs_reference"]
    assert r["limit"] == limit
    sound = max(hi for _, hi in r["sound"].values())
    controls = {k: lo for k, (lo, _) in r["controls"].items()}
    assert any(k.startswith("fp8_matmul") for k in controls)
    assert any(k.startswith("route_late") for k in controls)
    lo = min(lo for lo, _ in r["sound"].values())
    assert sound + 1.5 * (sound - lo) <= limit <= min(controls.values()) / 3


def test_the_cells_entries_in_the_benchmark_file(bench):
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "steady-1g-8k"
    assert cell["config"] == "smallthinker-21b-a3b"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    new = ["moe_device_ms_reglu", "reglu_active_ppm"]
    # at least these (later PRs add metrics to the cell's list)
    assert listed >= {
        "entry_other_ms", "quorum_ms", "commit_ms", "raw_step_ms", "mfu_pct",
        "device_idle_pct", "peak_hbm_gib", "attest_device_ms", "dispatch_ms",
        "publish_status_ms", "state_digest_wait_ms", "boundary_host_ms",
        "idle_dispatch_ms", "idle_boundary_ms", "idle_wait_ms",
        "idle_unspanned_ms", "moe_pairs_local", "moe_experts_roofline",
        "attn_window_roofline", *new}
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["moe_device_ms_reglu"] == layers["reglu_active_ppm"] \
        == layers["moe_device_ms"]
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_the_window_kernel_file_reads_this_configuration(cfg):
    """``kernels/window_flash_attention.py`` as it stands, through the
    derived names: one full layer's triangle and three bands of 4,096 at 28
    heads of 128 on 4 key/value heads."""
    k = spec.module("kernels", "window_flash_attention")
    assert k.layer_windows(cfg) == [None, 4096, 4096, 4096]
    assert k.calls_per_step(cfg) == 1
    assert k.visible_pairs(8192, None) == 8192 * 8193 / 2 == 33_558_528
    assert k.visible_pairs(8192, 4096) == 25_167_872     # 25.2 M of 33.6 M
    least = k.least_seconds(cfg, 1, 8192, peaks_for(V5E))
    pairs = 33_558_528 + 3 * 25_167_872
    assert least["flops"] == 7 * 2.0 * 28 * 128 * pairs
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(least["flops"] / 197e12)
    q, kv, stat = 8192 * 28 * 128 * 2, 8192 * 4 * 128 * 2, 8192 * 28 * 4
    assert least["bytes"] == 4 * ((2 * q + 2 * kv + stat)
                                  + (4 * q + 4 * kv + 2 * stat))


def test_the_grouped_products_kernel_reads_this_configuration(cfg):
    """``kernels/grouped_matmul.py`` as it stands: four expert layers, 16
    held experts of 2560 x 768, three products (a ReGLU's are a SwiGLU's
    sizes)."""
    k = spec.module("kernels", "grouped_matmul")
    assert k.expert_layers(cfg) == 4
    rows = 49152.0                     # 4 layers x 8192 x 6 x 16 / 64
    least = k.least_seconds(cfg, rows, peaks_for(V5E))
    f = k.grouped_flops(rows, 2560, 768)
    assert f["fwd"] == 3 * 2 * rows * 2560 * 768
    assert least["flops"] == 3 * f["fwd"]
    b = k.grouped_bytes(rows, 4, 16, 2560, 768)
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


PINNED = {"moe_device_ms_reglu": "moe", "attn_window_roofline": "attention",
          "moe_experts_roofline": "gmm"}


@pytest.mark.parametrize("metric", list(PINNED), ids=list(PINNED))
def test_patterns_match_the_names_a_traced_run_gave(bench, names, metric):
    """``tests/smallthinker_op_names.json`` holds event names as the chip's
    profile spelt them (my traced run, PR 51, the first 1,500 characters of
    each): each metric's pattern finds its own and none of the others'."""
    pattern = spec.data("metrics", metric)["reader"]["pattern"]
    mine = names[PINNED[metric]]
    assert mine and all(re.search(pattern, n) for n in mine)
    rest = [n for key, group in names.items() if key != PINNED[metric]
            for n in group]
    assert rest and not any(re.search(pattern, n) for n in rest)


OTHERS = ("afmoe_op_names.json", "mla_op_names.json", "gdn_op_names.json",
          "mamba2_op_names.json", "lfm2_op_names.json")


@pytest.mark.parametrize("file", OTHERS)
def test_the_new_shape_pattern_matches_nothing_of_the_other_cells(bench,
                                                                  file):
    with open(os.path.join(BENCH, "tests", file)) as f:
        theirs = [n for group in json.load(f).values() for n in group]
    pattern = spec.data("metrics", "moe_device_ms_reglu")["reader"]["pattern"]
    assert not any(re.search(pattern, n) for n in theirs)


def test_the_loops_metric_reads_its_ops_inside_the_steps(bench, names):
    reader = spec.data("metrics", "moe_device_ms_reglu")["reader"]
    events = [(n, 200 + 10 * i, 205 + 10 * i)
              for i, n in enumerate(names["moe"] + names["other"])]
    events.append((names["moe"][0], 4500, 4600))       # outside every step
    run = _traced_run(events, {})
    assert readers.read(run, reader) == pytest.approx(
        5e-9 * len(names["moe"]) * 1e3 / 2)
    # a program without the form (the parent's): nothing, and no error
    assert readers.read(_traced_run(
        [e for e in events if e[0] in names["other"]], {}), reader) is None
    assert readers.read({**run, "device_trace": None}, reader) is None


def test_rooflines_read_shares_under_a_hundred(bench, names):
    cfg = spec.Cell(CELL, REPO).config
    reader = spec.data("metrics", "attn_window_roofline")["reader"]
    k = spec.module("kernels", "window_flash_attention")
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
                "end.0.0": {"moe_pairs_local_total": 10 * 49_152.0,
                            "committed_steps": 12}}
    least = g.least_seconds(cfg, 49_152.0, peaks_for(V5E))["seconds"]
    took = int(4 * least * 1e9)
    step = took + 2000
    events = [(names["gmm"][0], 200 + i * step, 200 + i * step + took)
              for i in range(2)]
    assert readers.read(_traced_run(events, counters, step), reader) \
        == pytest.approx(25.0, rel=1e-3)
    assert readers.read(_traced_run(events, {}, step), reader) is None


def test_the_active_share_reads_the_counter_a_committed_step(bench):
    reader = spec.data("metrics", "reglu_active_ppm")["reader"]
    counters = {"begin.0": {"moe_reglu_active_micro_total": 1_000_000.0,
                            "committed_steps": 2},
                "end.0.0": {"moe_reglu_active_micro_total":
                            1_000_000.0 + 10 * 499_000.0,
                            "committed_steps": 12}}
    assert readers.read(_traced_run([], counters), reader) == 499_000.0
    assert readers.read(_traced_run([], {}), reader) is None
    # the parent's program has no such counter: nothing to read
    assert readers.read(_traced_run([], {
        "begin.0": {"committed_steps": 2},
        "end.0.0": {"committed_steps": 12}}), reader) is None


def test_the_builder_stops_a_program_without_the_field(M, cfg):
    """A tree whose ``TransformerConfig`` lacks ``moe_route_input`` (the
    parent of PR 51) stops when the driver asks the builder for its loss:
    an unknown field, at once, before anything is compiled."""
    import dataclasses

    from torchft_tpu.models import transformer

    fields = {f.name for f in dataclasses.fields(transformer.TransformerConfig)}
    assert "moe_route_input" in fields

    class Parent:                     # the parent's dataclass, by behaviour
        def __init__(self, **kw):
            unknown = sorted(set(kw) - (fields - {"moe_route_input"}))
            if unknown:
                raise TypeError("TransformerConfig.__init__() got an "
                                f"unexpected keyword argument {unknown[0]!r}")

    real = transformer.TransformerConfig
    transformer.TransformerConfig = Parent
    try:
        with pytest.raises(TypeError, match="moe_route_input"):
            M.make_loss_fn({**cfg, **M.REHEARSE}, M.REHEARSE_SEQ,
                           interpret=True)
    finally:
        transformer.TransformerConfig = real


def test_the_cell_is_found_and_runs_in_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 51), "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, out.stdout[-3000:]
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    # 1 x 64 tokens: every one of 4 experts selected, 2 held, 4 layers
    assert got["moe_pairs_local"]["value"] == 4 * 64 * 2
    assert 300_000 < got["reglu_active_ppm"]["value"] < 700_000
    for device_metric in ("moe_device_ms_reglu", "moe_experts_roofline",
                          "attn_window_roofline", "mfu_pct"):
        assert device_metric not in got
