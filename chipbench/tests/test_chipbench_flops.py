"""FLOP and byte counts against hand counts at the cells' shapes."""
import pytest

import flops
from common import BENCH_DIR, load_json


def m(name):
    return flops.dims(load_json(BENCH_DIR / "configs" / f"{name}.json"))


def test_qwen_layer_and_kv_sizes():
    q = m("qwen2.5-32b")
    # q 5120x5120, k and v 5120x1024 each, o 5120x5120, mlp 3 x 5120x27648
    assert flops.layer_matmul_params(q) == (2 * 5120 * 5120
                                            + 2 * 5120 * 1024
                                            + 3 * 5120 * 27648)
    # 24,576 B of KV a token over 6 layers; 393,216 B a 16-token block row
    assert 6 * flops.kv_bytes(q, 1) == 24576
    assert flops.row_bytes(q) == 393216


def test_yi_layer_and_kv_sizes():
    y = m("yi-34b")
    assert flops.layer_matmul_params(y) == (2 * 7168 * 7168
                                            + 2 * 7168 * 1024
                                            + 3 * 7168 * 20480)
    assert flops.row_bytes(y) == 393216


def test_decode_token_flops_by_hand():
    q = m("qwen2.5-32b")
    ctx = 2000
    per_layer = 2 * flops.layer_matmul_params(q) + 4 * ctx * 40 * 128
    assert flops.decode_token_flops(q, ctx) == 6 * per_layer + 2 * 5120 * 152064


def test_prefill_flops_by_hand():
    y = m("yi-34b")
    p = 1000
    causal = 4 * (p * (p + 1) // 2) * 56 * 128
    assert flops.prefill_flops(y, p) == (
        6 * (2 * p * flops.layer_matmul_params(y) + causal) + 2 * 7168 * 64000)


def test_decode_attention_bytes_and_roofline():
    q = m("qwen2.5-32b")
    f, b = flops.decode_attention_work(q, [100, 300])
    assert f == 6 * 4 * 400 * 40 * 128
    assert b == 6 * (2 * 400 * 8 * 128 * 2 + 2 * 2 * 40 * 128 * 2)
    t, bound = flops.roofline_seconds(f, b, dict(bf16_flops_per_s=197e12,
                                                 hbm_bytes_per_s=819e9))
    assert bound == "memory" and t == pytest.approx(b / 819e9)
