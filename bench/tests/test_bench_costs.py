"""Cost counts against hand counts at the tiny qwen2 shapes, and peaks."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import costs, harness
from bench.reference import qwen2

BENCH = Path(__file__).resolve().parents[1]
TINY = json.loads((BENCH / "tests" / "data" / "qwen2-tiny.json").read_text())


def test_shapes_hand_counts():
    s = costs.Shapes(TINY)  # L 2, d 64, H 4, KV 2, hd 16, F 160, V 512
    assert s.layer_matmul == 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 160
    assert s.layer_matmul == 43008
    assert s.layer_params == 43008 + 8 * 16 + 2 * 64
    assert s.weight_bytes == 2 * (2 * 43264 + 512 * 64 + 64) == 238720
    assert s.kv_bytes_per_token == 2 * 2 * 2 * 2 * 16 == 256


def test_weight_bytes_match_the_served_weights():
    specs = qwen2.param_specs(TINY)
    n = sum(int(np.prod(shape)) for shape, _ in specs.values())
    assert 2 * n == costs.Shapes(TINY).weight_bytes


def test_decode_step_hand_count():
    s = costs.Shapes(TINY)
    flops, bytes_ = costs.decode_step(s, 3, 100)
    assert flops == 3 * (2 * 2 * 43008 + 2 * 64 * 512) + 4 * 2 * 4 * 16 * 100
    assert flops == 763904
    assert bytes_ == 238720 + 256 * 100


def test_mixed_step_hand_count():
    s = costs.Shapes(TINY)
    flops, bytes_ = costs.mixed_step(s, 32, 10, 1, 50)
    pairs = 10 * 32 + 10 * 11 // 2
    assert flops == (10 * 172032 + 65536 + 512 * pairs
                     + 172032 + 65536 + 512 * 50) == 2241024
    assert bytes_ == 238720 + 256 * (32 + 10 + 50)


def test_least_seconds_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_seconds(1000.0, 10.0, peaks) == (10.0, "compute")
    assert costs.least_seconds(10.0, 1000.0, peaks) == (100.0, "memory")


def test_peaks_known_and_unknown_device_kind():
    root = BENCH.parent
    v5e = harness._peaks(root, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness._peaks(root, "TPU v99")
