"""``bench/flops/dense.py`` against a count by hand at starcoder2-3b's
published shapes."""

from __future__ import annotations

import pytest

from bench import harness

FL = harness.flops_for("dense")
CFG = harness.load_json(harness.ROOT / "bench/configs/starcoder2-3b-l8-train.json")
SERVE = harness.load_json(harness.ROOT / "bench/configs/starcoder2-3b-serve.json")

# by hand: q 3072x3072, k and v 3072x256 each, o 3072x3072, MLP in and out
# 3072x12288 each: 95,944,704 a layer; the head 3072 x 49,152
LAYER = 9_437_184 + 2 * 786_432 + 9_437_184 + 2 * 37_748_736
HEAD = 150_994_944


def test_matmul_parameters():
    assert FL.layer_matmul_params(CFG) == LAYER == 95_944_704
    assert FL.matmul_params(CFG) == 8 * LAYER + HEAD == 918_552_576
    assert FL.matmul_params(SERVE) == 30 * LAYER + HEAD


def test_train_flops_per_token_at_2048():
    # causal within the 4096 window: a query at position i sees i + 1 keys
    keys = (2048 + 1) / 2
    attn = 4 * 24 * 128 * keys * 8            # QK^T and PV, 8 layers
    want = 6 * (8 * LAYER + HEAD) + 3 * attn
    assert FL.train_flops_per_token(CFG, 2048) == pytest.approx(want)
    assert FL.train_flops_per_token(CFG, 2048) == pytest.approx(5.8135e9,
                                                                rel=1e-4)


def test_the_window_caps_the_keys():
    assert FL.mean_keys(8, 0) == pytest.approx(4.5)
    assert FL.mean_keys(8, 4) == pytest.approx((1 + 2 + 3 + 4 * 5) / 8)


def test_decode_bytes_at_batch_32():
    keys = 160.5
    kv = 2 * 30 * 32 * 2 * 128 * 2 * (keys + 1)
    weights = (30 * LAYER + HEAD) * 2
    got = FL.decode_bytes(SERVE, 32, keys)
    assert got > weights + kv
    assert got == pytest.approx(weights + kv, rel=2e-3)
