"""The operation and byte counts against hand counts at the benchmark's
published shapes."""

import json
import os

from bench import counts, harness
import math

from bench.weights import Dims, layout


def dims(name):
    return Dims.from_config(json.load(open(os.path.join(
        harness.BENCH, "configs", name + ".json"))))


GRANITE, QWEN = dims("granite-3-2b"), dims("qwen1.5-4b")
GRANITE4 = dims("granite-3-2b-l4")


def test_dims_as_published():
    assert (GRANITE.layers, GRANITE.d_model, GRANITE.heads,
            GRANITE.kv_heads, GRANITE.head_dim, GRANITE.d_ff,
            GRANITE.vocab, GRANITE.padded_vocab) == (
        40, 2048, 32, 8, 64, 8192, 49155, 49408)
    assert (QWEN.layers, QWEN.d_model, QWEN.heads, QWEN.kv_heads,
            QWEN.head_dim, QWEN.d_ff, QWEN.vocab, QWEN.qkv_bias) == (
        40, 2560, 20, 20, 128, 6912, 151936, True)
    assert GRANITE4.layers == 4 and GRANITE4.d_model == 2048


def test_parameters():
    # granite: 40 x 60,817,408 matmul + norms, two 49408 x 2048 tables
    n = sum(math.prod(shape) for shape, _, _ in layout(GRANITE).values())
    assert n == (40 * (60_817_408 + 2 * 2048)
                                    + 2 * 49408 * 2048 + 2048)
    assert layout(QWEN)["bq"][0] == (40, 20, 128)


def test_token_flops_by_hand():
    assert counts.layer_matmul_params(GRANITE) == 60_817_408
    assert counts.token_matmul_flops(GRANITE) == 5_066_731_520
    assert counts.layer_matmul_params(QWEN) == 79_298_560
    assert counts.token_matmul_flops(QWEN) == 7_121_797_120
    assert counts.attn_flops(GRANITE, 1) == 327_680
    assert counts.attn_flops(QWEN, 1) == 409_600


def test_p2_step_flops_by_hand():
    fwd = 2048 * 687_878_144 + 32_768 * (2048 * 2049 // 2)
    assert counts.seq_forward_flops(GRANITE4, 2048) == fwd
    assert counts.p2_step_flops(GRANITE4, 4, 2048) == 20 * fwd
    assert abs(counts.p2_step_flops(GRANITE4, 4, 2048) / 29.55e12 - 1) < 1e-3


def test_decode_and_kernel_counts_by_hand():
    assert counts.decode_tick_flops(GRANITE, [17, 1]) == (
        2 * 5_066_731_520 + 327_680 * 18)
    flops, byts = counts.paged_decode_call(GRANITE, [17], 16)
    assert flops == 4 * 32 * 64 * 17
    assert byts == 2 * 2 * 16 * 512 * 2 + 2 * 32 * 64 * 2
    flops, byts = counts.paged_decode_call(QWEN, [16, 33], 16)
    assert byts == 2 * (1 + 3) * 16 * 2560 * 2 + 2 * 2 * 20 * 128 * 2


def test_kd_loss_bytes_by_hand():
    assert counts.kd_loss_fwd_call(GRANITE4, 2048)[1] == 3 * 2048 * 49155 * 4
    assert counts.kd_loss_bwd_call(GRANITE4, 2048)[1] == 4 * 2048 * 49155 * 4
    # a step: four chunks, forward twice (rematerialised) and backward once
    step = (8 * counts.kd_loss_fwd_call(GRANITE4, 2048)[1]
            + 4 * counts.kd_loss_bwd_call(GRANITE4, 2048)[1])
    assert step == 40 * 2048 * 49155 * 4        # 16.1 GB


def test_roofline_takes_the_larger_bound():
    assert counts.roofline_s(197e12, 0, 197e12, 819e9) == 1.0
    assert counts.roofline_s(0, 819e9, 197e12, 819e9) == 1.0
