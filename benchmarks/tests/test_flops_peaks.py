"""The builder's and the kernel's operation counts and ``peaks.py`` against
counts made by hand (in the comments)."""

import json
import os

import pytest

from harness import peaks, spec

model = spec.module("models", "dense_gqa_decoder")
flash = spec.module("kernels", "flash_attention")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name, **over):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return {**json.load(f), **over}


def test_param_counts_match_the_published_models():
    # Mistral-7B: layer = 2*4096*4096 (q, o) + 2*4096*1024 (k, v)
    # + 3*4096*14336 (mlp) + 2*4096 (norms) = 218,112,000; x32
    # + 2*32000*4096 (embedding, head) + 4096 = 7,241,732,096.
    m = cfg("mistral-7b")
    assert model.param_count({**m, "num_hidden_layers": 32}) == 7_241_732_096
    assert m["published"]["parameters"] == 7_241_732_096
    assert model.param_count(m) == 480_260_096          # as run, depth 1
    # InternLM2-1.8B: layer = 2*2048*2048 + 2*2048*1024 + 3*2048*8192
    # + 2*2048 = 62,918,656; x24 + 2*92544*2048 + 2048 = 1,889,110,016.
    i = cfg("internlm2-1.8b")
    assert model.param_count({**i, "num_hidden_layers": 24}) == 1_889_110_016
    assert i["published"]["parameters"] == 1_889_110_016
    assert model.param_count(i) == 504_899_584          # as run, depth 2


def test_flops_per_token_by_hand():
    # Mistral widths, 4096-token causal sequence, forward, per token:
    # qkv 2*4096*(4096+2*1024) = 50,331,648; o 2*4096*4096 = 33,554,432;
    # mlp 3*2*4096*14336 = 352,321,536; attention 2 matmuls * 2*4096*128*32
    # / 2 (causal) = 33,554,432; head 2*4096*32000 = 262,144,000.
    m = cfg("mistral-7b")
    assert model.forward_flops_per_token(m, 4096) == 469_762_048 + 262_144_000
    assert model.train_flops_per_token(m, 4096) == 3 * 731_906_048


def test_flash_flops_and_bytes_by_hand():
    # [1, 4096, 32 (8 kv), 128]: one matmul over the square is
    # 2*32*4096^2*128 = 137,438,953,472, causal half 68,719,476,736.
    f = flash.flash_flops(1, 4096, 32, 128)
    assert f == {"fwd": 2 * 68_719_476_736, "bwd": 5 * 68_719_476_736}
    # q = o = 4096*32*128*2 = 33,554,432 B; k = v = 4096*8*128*2 =
    # 8,388,608 B; row statistics 32*4096*4 = 524,288 B.
    b = flash.flash_bytes(1, 4096, 32, 8, 128)
    assert b["fwd"] == 2 * 33_554_432 + 2 * 8_388_608 + 524_288
    assert b["bwd"] == 4 * 33_554_432 + 4 * 8_388_608 + 2 * 524_288
    r = flash.flash_roofline_seconds(1, 4096, 32, 8, 128,
                                     peaks.peaks_for("TPU v5 lite"))
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(7 * 68_719_476_736 / 197e12)


def test_an_unknown_chip_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5e")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9")
