"""Percentiles, rates, spreads and the operation counts of the yardstick."""

import math

import numpy as np
import pytest

from benchmarks.lib import costs, harness, peaks, stats


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy(q):
    rng = np.random.default_rng(3)
    values = list(rng.exponential(1.0, 237))
    assert stats.percentile(values, q) == pytest.approx(np.percentile(values, q))


def test_percentile_of_one_and_of_none():
    assert stats.percentile([4.0], 95) == 4.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_rate_and_tpot():
    assert stats.rate(32768 * 20, 25.0) == pytest.approx(26214.4)
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    # 128 tokens: 127 gaps after the first
    assert stats.tpot_s(first_token=1.0, finish=1.0 + 127 * 0.04, tokens=128) == pytest.approx(0.04)
    assert stats.tpot_s(1.0, 2.0, 1) is None


@pytest.mark.parametrize("name,params,matmul", [
    # published sizes: Pythia-410M has 405M parameters, Pythia-1.4B 1.41B
    ("pythia-410m", 405e6, 353.5e6),
    ("pythia-1.4b", 1.415e9, 1.311e9),
])
def test_parameter_counts_of_the_published_configs(name, params, matmul):
    cfg = harness.load_config(name)
    arch = harness.load_architecture(cfg["architecture"])
    assert arch.total_params(cfg) == pytest.approx(params, rel=0.01)
    assert arch.matmul_params(cfg) == pytest.approx(matmul, rel=0.01)


def test_train_flops_per_token():
    cfg = harness.load_config("pythia-410m")
    arch = harness.load_architecture(cfg["architecture"])
    shape = (arch.layers(cfg), arch.heads(cfg), arch.head_dim(cfg))
    assert shape == (24, 16, 64) and arch.kv_heads(cfg) == 16
    want = 3 * (2 * arch.matmul_params(cfg) + 24 * 2 * 2048 * 1024)
    assert costs.train_flops_per_token(arch.matmul_params(cfg), *shape, 2048) == pytest.approx(want)
    assert want == pytest.approx(2.42e9, rel=0.01)


def test_flash_costs_and_bounds():
    v5e = peaks.device_peaks("TPU v5 lite")
    flops, bytes_ = costs.flash_forward_cost(2, 16, 2048, 64)
    assert flops == 4 * 2 * 16 * 2048 * 2048 * 64 / 2
    assert bytes_ == 4 * 2 * 16 * 2048 * 64 * 2
    assert costs.roofline_seconds(flops, bytes_, v5e) == (flops / 197e12, "compute")
    bflops, bbytes = costs.flash_backward_cost(2, 16, 2048, 64)
    assert bflops == 2.5 * flops and bbytes == 2 * bytes_


def test_paged_decode_is_memory_bound():
    v5e = peaks.device_peaks("TPU v5 lite")
    flops, bytes_ = costs.paged_decode_cost(64 * 300, 64, 16, 16, 128)
    assert flops == 4 * 64 * 300 * 16 * 128
    assert bytes_ == 2 * 64 * 300 * 16 * 128 * 2 + 2 * 64 * 16 * 128 * 2
    seconds, bound = costs.roofline_seconds(flops, bytes_, v5e)
    assert bound == "memory" and seconds == bytes_ / 819e9


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.device_peaks("cpu")
    assert math.isclose(peaks.device_peaks("TPU v5 lite").bf16_flops_per_s, 197e12)
