"""Count functions behind ``step_mfu`` and ``paged_decode_roofline``, and
the parameter counts of the two configurations."""
from __future__ import annotations

import numpy as np
import pytest

import _tiny  # noqa: F401
from _tiny import load
from chipbench import counts, peaks
from chipbench.cells import load_module

QWEN = load("configs/qwen2-0.5b.json")["model"]
CODEQWEN = load("configs/codeqwen1.5-7b-tp4.json")["model"]


def n_params(m):
    ref = load_module("references", "qwen2")
    return sum(int(np.prod(s)) for s in ref.param_shapes(m).values())


def test_parameter_counts_match_the_published_models():
    assert n_params(QWEN) == 494_032_768  # Qwen2-0.5B: 0.49B
    assert n_params(CODEQWEN) == 7_250_284_544  # CodeQwen1.5-7B: 7.25B
    # 32 KV heads (multi-head attention) would add 0.94B
    # (K and V weights and biases of 28 more heads in 32 layers)
    assert n_params(dict(CODEQWEN, num_key_value_heads=32)) - \
        n_params(CODEQWEN) == 32 * 2 * (4096 + 1) * (32 - 4) * 128


def test_token_flops():
    L, d, ff, V = 24, 896, 4864, 151936
    layer = d * d + 2 * d * 128 + d * d + 3 * d * ff
    assert counts.layer_matmul_params(QWEN) == layer
    assert counts.token_flops(QWEN, 0, head=False) == 2 * L * layer
    assert counts.token_flops(QWEN, 100) == \
        2 * L * layer + 2 * d * V + 4 * 100 * 64 * 14 * L


def test_paged_decode_work_counts_live_kv_only():
    live = [100, 1000]
    f, b = counts.paged_decode_work(QWEN, live, max_blocks=128)
    assert f == 4 * 1100 * 14 * 64
    kv = 2 * 1100 * 2 * 64 * 2  # K and V, 2 kv heads of 64, bf16
    assert b == kv + 2 * 2 * 14 * 64 * 2 + 2 * 129 * 4
    # at TP=4 a chip holds a quarter of the heads (one of four kv heads)
    f4, b4 = counts.paged_decode_work(CODEQWEN, live, tp=4, max_blocks=128)
    assert f4 == 4 * 1100 * 8 * 128
    assert b4 == 2 * 1100 * 1 * 128 * 2 + 2 * 2 * 8 * 128 * 2 + 2 * 129 * 4


def test_least_time_takes_the_binding_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = counts.least_time(1e9, 819e6, p)
    assert bound == "memory" and t == pytest.approx(1e-3)
    t, bound = counts.least_time(197e12, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
