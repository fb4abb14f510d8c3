"""FLOP and byte counts against hand counts at Yi-6B and Yi-34B widths."""
import pytest

import os

from chipbench.lib import registry, roofline

YI6B = registry.load_json(os.path.join(registry.BENCH_DIR, "configs", "yi-6b.json"))["model"]
YI34B8 = registry.load_json(os.path.join(registry.BENCH_DIR, "configs", "yi-34b-8l.json"))["model"]


def test_yi6b_weights_and_kv():
    # per layer: q 4096x4096, k and v 4096x512 each, o 4096x4096, ffn 3x4096x11008
    per_layer = 4096 * 4096 * 2 + 4096 * 512 * 2 + 3 * 4096 * 11008
    assert roofline.layer_matmul_params(YI6B) == per_layer == 173_015_040
    # weights read by a step: 32 layers + the head (64000x4096), bf16; norms f32
    assert roofline.weight_bytes(YI6B) == 2 * (32 * per_layer + 64000 * 4096) + 4 * (65 * 4096)
    # 32 layers x K and V x 4 heads x 128 x 2 bytes = 64 KiB a token
    assert roofline.kv_bytes_per_token(YI6B) == 65536


def test_yi34b_stage_weights():
    per_layer = 7168 * 7168 * 2 + 7168 * 1024 * 2 + 3 * 7168 * 20480
    assert roofline.layer_matmul_params(YI34B8) == per_layer == 557_842_432
    assert roofline.kv_bytes_per_token(YI34B8) == 8 * 2 * 8 * 128 * 2 == 32768
    # 8 layers of 1.116 GB and a 0.917 GB head: 9.843 GB read per step
    assert roofline.weight_bytes(YI34B8) / 1e9 == pytest.approx(9.843, abs=0.001)


def test_decode_step_counts():
    lengths = [100, 300]
    f, b = roofline.decode_step(YI6B, lengths)
    mm = 2 * 2 * (32 * 173_015_040 + 64000 * 4096)
    attn = 32 * 4 * 32 * 128 * (400 + 2)
    assert f == mm + attn
    assert b == (roofline.weight_bytes(YI6B) + 2 * 4096 * 2 + 400 * 65536 + 2 * 65536
                 + 2 * 64000 * 2)


def test_chunk_step_counts():
    f, b = roofline.chunk_step(YI34B8, tokens=256, ctx0=1024)
    pairs = 256 * 1024 + 256 * 257 / 2
    assert f == 2 * 256 * 8 * 557_842_432 + 2 * 64000 * 7168 + 8 * 4 * 56 * 128 * pairs
    assert b == (roofline.weight_bytes(YI34B8) + 256 * 7168 * 2 + 1024 * 32768
                 + 256 * 32768 + 64000 * 2)


def test_kernel_counts():
    f, b = roofline.decode_kernel(YI6B, [1000])
    assert f == 4 * 32 * 128 * 1001
    assert b == 1000 * 2 * 4 * 128 * 2 + 2 * 32 * 128 * 2 + 2 * 4 * 128 * 2
    f, b = roofline.prefill_kernel(YI34B8, tokens=100, ctx0=0)
    assert f == 4 * 56 * 128 * (100 * 101 / 2)
    assert b == 2 * 100 * 56 * 128 * 2 + 2 * 100 * 8 * 128 * 2


def test_least_time_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert roofline.least_time(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert roofline.least_time(1.0, 819e9, peaks) == pytest.approx(1.0)
    # a Yi-6B decode step of one short sequence is bound by reading the weights
    f, b = roofline.decode_step(YI6B, [10])
    assert roofline.least_time(f, b, peaks) == pytest.approx(b / 819e9)
    assert b / 819e9 * 1e3 == pytest.approx(14.16, abs=0.01)
