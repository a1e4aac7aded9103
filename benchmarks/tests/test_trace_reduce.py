"""trace_reduce.py on synthetic spans and device events, and on one small
trace recorded here (the CPU has no device plane, but the clock mark is read
the same way)."""

import glob
import time

import pytest

from harness import readers, trace_reduce as tr

MS = 1_000_000


def test_union_and_gaps():
    ivs = [(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)]
    assert tr.merge(ivs) == [(0, 20), (30, 45)]
    assert tr.union_ns(ivs) == 35
    assert tr.gaps(ivs, 0, 60) == [(20, 30), (45, 60)]
    assert tr.gaps(ivs, 10, 35) == [(20, 30)]
    assert tr.clip(ivs, 8, 32) == [(8, 10), (8, 20), (30, 32)]


def test_stage_union_counts_overlapping_spans_once():
    # eight buckets wait side by side for 10 ms in step 0; one for 3 ms in
    # step 1; the summed meter would say 83 ms.
    spans = [{"stage": "ring", "t0_ns": 1 * MS, "dur_ns": 10 * MS}] * 8 + [
        {"stage": "ring", "t0_ns": 22 * MS, "dur_ns": 3 * MS},
        {"stage": "vote", "t0_ns": 12 * MS, "dur_ns": 1 * MS}]
    steps = [(0, 20 * MS), (20 * MS, 40 * MS)]
    assert tr.stage_union_per_step(spans, "ring", steps) == [10.0, 3.0]
    assert tr.stage_union_per_step(spans, "vote", steps) == [1.0, 0.0]
    assert tr.stage_union_per_step(spans, "put", steps) == []
    assert tr.label_gap((0, 11 * MS), spans) == "ring"
    assert tr.label_gap((50 * MS, 60 * MS), spans) == "host"


DEVICES = {"/device:TPU:0": [
    ("%attn.3 = (bf16[32,4096,128]{2,1,0}, f32[32,4096,128]{2,1,0}) "
     "custom-call(bf16[32,4096,128]{2,1,0} %x), custom_call_target=\"tpu\"",
     2 * MS, 4 * MS),
    ("%fusion.40 = f32[4096,32000]{1,0:T(8,128)} fusion(f32[4096,32000] %p)",
     3 * MS, 9 * MS),
    ("%attn.4 = (bf16[32,4096,128]{2,1,0}) custom-call(bf16[32,4096,128] %y)",
     12 * MS, 15 * MS)]}


def test_device_busy_kernel_time_and_gaps():
    assert tr.busy_seconds(DEVICES, 0, 20 * MS) == pytest.approx(0.010)
    assert tr.busy_seconds(DEVICES, 8 * MS, 13 * MS) == pytest.approx(0.002)
    secs, n = tr.kernel_seconds(DEVICES, r"^%attn[\w.]* = .*custom-call\(",
                                0, 20 * MS)
    assert (secs, n) == (pytest.approx(0.005), 2)
    # an event that straddles the bound is left out whole
    assert tr.kernel_seconds(DEVICES, "attn", 3 * MS, 20 * MS)[1] == 1
    top = tr.top_ops(DEVICES, 0, 20 * MS, n=2)
    assert top[0] == ["fusion.40 fusion f32[4096,32000]", pytest.approx(0.006)]
    assert top[1][0] == "attn.4 custom-call bf16[32,4096,128]"
    spans = [{"stage": "quorum", "t0_ns": 9 * MS, "dur_ns": 3 * MS}]
    assert tr.idle_gaps(DEVICES, spans, 0, 20 * MS, n=2) == [
        ["host", pytest.approx(0.005)], ["quorum", pytest.approx(0.003)]]


def test_idle_and_roofline_readers_on_a_synthetic_run():
    step = {"phase": "window", "committed": True, "world": 1,
            "t0": 1 * MS, "t1": 16 * MS, "timings": {}}
    run = {"groups": 1, "batch": 1, "seq": 4096, "device_kind": "TPU v5 lite",
           "groups_on_device": 1,
           "cfg": {"builder": "dense_gqa_decoder",
                   "num_attention_heads": 32, "num_key_value_heads": 8,
                   "hidden_size": 4096, "num_hidden_layers": 1},
           "steps": {0: [step]},
           "device_trace": {"planes": DEVICES, "modules": {}, "lo": 0,
                            "hi": 20 * MS}}
    assert readers.read(run, {"kind": "device_idle"}) == pytest.approx(50.0)
    roofline = {"kind": "kernel_roofline", "kernel": "flash_attention",
                "pattern": r"^%attn[\w.]* = .*custom-call\("}
    # least 7 * 68,719,476,736 / 197e12 = 2.4418 ms against 5 ms measured
    least = 100 * 7 * 68_719_476_736 / 197e12
    assert readers.read(run, roofline) == pytest.approx(least / 0.005)
    assert readers.read({**run, "device_trace": None},
                        {"kind": "device_idle"}) is None
    assert readers.read({**run, "device_trace": None}, roofline) is None


def test_what_runs_in_a_step_that_does_not_count_is_left_out():
    # three steps; the middle one (a solo step of a recovery) does not count,
    # and the kernel's 3 ms inside it belong to no counted step.
    def step(t0, t1, world):
        return {"phase": "window", "committed": True, "world": world,
                "t0": t0 * MS, "t1": t1 * MS, "timings": {}}

    kernel = "%attn.1 = (bf16[8]) custom-call(bf16[8] %x)"
    planes = {"/device:TPU:0": [(kernel, 1 * MS, 3 * MS),
                                (kernel, 11 * MS, 14 * MS),
                                (kernel, 21 * MS, 23 * MS)]}
    modules = {"/device:TPU:0": [("jit_attest(123)", 4 * MS, 5 * MS),
                                 ("jit_attest(123)", 15 * MS, 17 * MS),
                                 ("jit_attest(123)", 24 * MS, 25 * MS)]}
    run = {"groups": 2, "batch": 1, "seq": 4096, "device_kind": "TPU v5 lite",
           "groups_on_device": 1,
           "cfg": {"num_attention_heads": 32, "num_key_value_heads": 8,
                   "hidden_size": 4096, "num_hidden_layers": 1},
           "steps": {0: [step(0, 10, 2), step(10, 20, 1), step(20, 30, 2)]},
           "device_trace": {"planes": planes, "modules": modules, "lo": 0,
                            "hi": 30 * MS}}
    assert readers.read(run, {"kind": "module_ms", "pattern": "^jit_attest"}) \
        == pytest.approx(1.0)
    least = 100 * 7 * 68_719_476_736 / 197e12
    assert readers.read(run, {
        "kind": "kernel_roofline", "kernel": "flash_attention",
        "pattern": "^%attn"}) == pytest.approx(2 * least / 0.004)


def test_the_clock_mark_is_found_in_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.MARK):
        mark = time.monotonic_ns()
    jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    trace = tr.read_xplane(path)
    assert trace["mark_ns"] is not None and trace["mark_ns"] >= 0
    assert trace["devices"] == {}           # no TPU here
    assert tr.to_monotonic(trace, mark) == {}
    with pytest.raises(ValueError, match="cannot be aligned"):
        tr.to_monotonic({**trace, "mark_ns": None}, mark)
