"""FLOP and byte counts against figures worked out by hand for
granite-3-2b (40 layers, d 2048, 32/8 heads of 64, d_ff 8192, vocab
49155 padded to 49280)."""
import json
from pathlib import Path

import pytest

from bench import costs

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "granite-3-2b.json").read_text())


def test_weights_and_cache_bytes():
    # a layer: q,o 2048x2048 each; k,v 2048x512 each; three 2048x8192
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert costs.layer_matmul_params(CFG) == per_layer == 60_817_408
    whole = (40 * (per_layer + 2 * 2048) + 49280 * 2048 + 5 * 2048) * 2
    assert costs.weight_bytes(CFG) == whole
    assert whole / 1e9 == pytest.approx(5.07, abs=0.005)
    # 40 layers x (K and V) x 8 heads x 64 x 2 bytes
    assert costs.kv_bytes_per_position(CFG) == 81_920
    assert costs.kv_bytes_per_position(CFG, 8) == 16_384


def test_flops():
    per_layer = 60_817_408
    # token at cache position 99 attends to 100 positions, full depth
    assert costs.decode_flops(CFG, 40, 99) == (
        2 * 40 * per_layer + 4 * 40 * 32 * 64 * 100 + 2 * 2048 * 49155)
    assert costs.decode_flops(CFG, 8, 0, head=False) == (
        2 * 8 * per_layer + 4 * 8 * 32 * 64)
    P = 128
    assert costs.prefill_flops(CFG, P) == (
        2 * P * 40 * per_layer + 4 * 40 * 32 * 64 * (P * (P + 1) // 2)
        + 2 * 2048 * 49155)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = costs.least_seconds(costs.prefill_flops(CFG, 1024),
                                   costs.prefill_bytes(CFG, 1024), peaks)
    assert bound == "compute" and t == pytest.approx(0.0262, rel=0.02)
