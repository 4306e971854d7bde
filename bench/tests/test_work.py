"""FLOP and byte counts against hand counts at qwen3-0.6b and FR2 sizes."""
import json

import pytest

from bench import spec, work

QWEN = json.loads((spec.BENCH / "configs" / "qwen3-0.6b.json").read_text())


@pytest.fixture
def lm():
    return work.DenseLM.from_config(QWEN, weight_bits=16, kv_bits=16)


def test_parameter_counts(lm):
    # q 1024x2048, k and v 1024x1024, o 2048x1024, gate/up 1024x3072,
    # down 3072x1024
    per_layer = 2097152 + 2 * 1048576 + 2097152 + 3 * 3145728
    assert lm.layer_params() == per_layer == 15728640
    assert lm.matmul_params() == 28 * per_layer + 1024 * 151936


def test_decode_matmul_calls(lm):
    calls = work.dequant_matmul_calls(lm, rows=16, head_rows=16)
    assert len(calls) == 28 * 7 + 1
    flops = sum(c[0] for c in calls)
    assert flops == 2 * 16 * lm.matmul_params()
    # LM head: 1024x151936 words of 2 B, one f32 scale, bf16 in and out
    head = calls[-1]
    assert head[1] == 1024 * 151936 * 2 + 4 + 16 * (1024 + 151936) * 2


def test_decode_attention_calls(lm):
    calls = work.decode_attention_calls(lm, [100, 300])
    assert len(calls) == 28
    flops, nbytes = calls[0]
    assert flops == 4 * 400 * 16 * 128
    kv = 2 * 400 * 8 * 128 * 2          # K and V words, 2 B each
    assert nbytes == kv + 2 * 400 * 4 + 2 * 2 * 2048 * 2


def test_model_flops(lm):
    # one token at position 99 (100 in context), with logits
    one = work.model_flops(lm, 99, 1, 1)
    assert one == 2 * lm.matmul_params() + 4 * 100 * 2048 * 28
    # a prompt of 3: attention over 1 + 2 + 3 positions, head once
    three = work.model_flops(lm, 0, 3, 1)
    assert three == (2 * 3 * 28 * lm.layer_params() + 2 * 1024 * 151936
                     + 4 * 6 * 2048 * 28)


def test_roofline_takes_the_larger_bound_per_call():
    total, by_f, by_b = work.roofline_seconds(
        [(2e12, 1e9), (1e9, 8.19e9)], 1e12, 8.19e9)
    assert total == pytest.approx(2.0 + 1.0)
    assert (by_f, by_b) == pytest.approx((2.0, 1.0))


def test_equalizer_slot():
    flops, nbytes = work.equalizer_slot(3168, 14, 8, 64)
    n = 3168 * 14
    assert n == 44352
    assert flops == 8 * n * 8 * 64
    assert nbytes == 8 * (3168 * 8 * 64 + n * 64 + n * 8)
    assert nbytes == pytest.approx(38.5e6, rel=0.01)
