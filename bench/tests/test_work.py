"""The operation and byte counts against hand counts."""
import pytest

from bench import work
from bench.harness import load_json, ROOT

QWEN = load_json(ROOT / "bench/configs/qwen3-0.6b.json")


def _gemm(name):
    return next(g for g in work.resnet50_gemms() if g.name == name)


def test_resnet50_layer_list():
    gemms = work.resnet50_gemms()
    assert len(gemms) == 53 + 1           # 53 convs and the fc
    assert _gemm("stem") == work.Gemm("stem", 112 * 112, 64, 147,
                                      224 * 224 * 3)
    # The unpadded 3x3/2 max-pool leaves 55 x 55 maps; the last stage 7x7.
    assert _gemm("s0b0.c1").m == 55 * 55
    assert _gemm("s3b2.c3").m == 7 * 7
    assert 7.5e9 < work.model_ops(gemms) < 8.3e9


def test_one_conv_by_hand():
    # s1b0.c2: 3x3 stride 2, 128 -> 128 channels, 55 x 55 -> 28 x 28.
    g = _gemm("s1b0.c2")
    assert (g.m, g.n, g.k) == (28 * 28, 128, 9 * 128)
    assert work.gemm_ops(g, 16) == 2 * 16 * 784 * 128 * 1152
    # input map at 8 bits, weights at 8 bits, int32 result
    assert work.gemm_bytes(g, 16, 8, 8) == (16 * 55 * 55 * 128
                                            + 1152 * 128 + 16 * 784 * 128 * 4)


def test_fc_by_hand():
    g = _gemm("head")
    assert work.gemm_ops(g, 1) == 2 * 2048 * 1000
    assert work.gemm_bytes(g, 2, 4, 8) == 2 * 2048 + 2048 * 1000 / 2 \
        + 2 * 1000 * 4


def test_qwen3_layer_by_hand():
    one = dict(QWEN, num_hidden_layers=1)
    # q, k, v: 1024 x (16 + 8 + 8) x 128; o: 2048 x 1024; mlp: 3 x 1024 x 3072
    proj = 1024 * 32 * 128 + 2048 * 1024 + 3 * 1024 * 3072
    assert work.qwen3_proj_params(one) == proj == 15_728_640
    head = 2 * 1024 * 151_936
    # A token at position 99 attends to 100 keys: 4 * 16 heads * 128 each.
    assert work.qwen3_token_ops(one, 99) == 2 * proj + 4 * 16 * 128 * 100 \
        + head
    assert work.qwen3_prefill_ops(one, 3) == 3 * 2 * proj \
        + 4 * 16 * 128 * (1 + 2 + 3) + head
    kv = 2 * 8 * 128 * 2                  # keys and values, bfloat16
    assert work.kv_bytes_per_token(one) == kv
    assert work.qwen3_decode_bytes(one, 8, 1000) == proj + 151_936 * 1024 * 2 \
        + (2 * 1024 + 2 * 128 + 1024) * 4 + 1000 * kv


def test_roofline_bound():
    assert work.roofline_s(393e12, 0, 393e12, 819e9) == (1.0, "compute")
    assert work.roofline_s(0, 819e9, 393e12, 819e9) == (1.0, "memory")


def test_peaks_unknown_kind_is_an_error():
    assert work.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        work.peaks("cpu")
