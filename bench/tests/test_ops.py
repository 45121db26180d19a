"""The operation and byte counts behind serve_mfu and cascade_roofline."""
from harness.peaks import PEAKS, peaks
from ops.cascade import cascade_cost, least_seconds
from ops.encoder import encoder_flops
import pytest


def test_encoder_flops_is_two_per_weight_per_token_plus_attention():
    L, d, f, n = 22, 768, 1152, 16
    weights = L * (4 * d * d + 3 * d * f)
    assert encoder_flops(n, L, d, f) == 2 * weights * n + L * 4 * n * n * d
    assert encoder_flops([n, n], L, d, f) == 2 * encoder_flops(n, L, d, f)


def test_cascade_cost_counts_the_gathered_panel():
    f32 = cascade_cost(q=32, dim=768, hot=1024, clusters=64, bucket=256,
                       n_probe=8, tail=256)
    cand = 8 * 256 + 256
    assert f32["flops"] == 2 * 32 * 768 * (1024 + 64 + cand)
    assert f32["bytes"] == (1024 * (768 * 4 + 9) + 64 * 768 * 4
                            + 32 * 8 * 256 * 4 + 32 * cand * (768 * 4 + 9)
                            + 32 * 768 * 4)
    peak = PEAKS["TPU v5 lite"]
    assert least_seconds(f32, peak) == f32["bytes"] / peak["hbm_bytes_per_s"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")
