import pytest

from harness import costs, peaks


def test_v5e_peaks_from_the_published_table():
    p = peaks.peak("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5"])
def test_a_device_missing_from_the_table_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak(kind)


DIMS = dict(d=64, h=4, kv=2, dh=16, ff=128, v=256, layers=2, gated=True,
            tied=False)


def test_gemm_counts_output_at_its_own_dtype():
    f, b = costs.gemm(8, 32, 16)
    assert f == 2 * 8 * 32 * 16
    assert b == (8 * 16 + 16 * 32) * 2 + 8 * 32 * 2
    assert costs.gemm(8, 32, 16, out_bytes=4)[1] == b + 8 * 32 * 2


def test_train_flops_are_six_per_weight_per_token_plus_attention():
    n_weights = 2 * (64 * 64 * 2 + 64 * 32 * 2 + 3 * 64 * 128) + 64 * 256
    b, t = 2, 8
    attn = 4 * 4 * 16 * (t * (t + 1) / 2) * 2      # causal keys, 2 layers
    assert costs.train_flops(DIMS, b, t) == pytest.approx(
        6 * n_weights * b * t + 3 * b * attn)


def test_least_time_is_the_larger_bound():
    peak = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_time(1000.0, 50.0, peak) == 10.0
    assert costs.least_time(100.0, 50.0, peak) == 5.0


def test_decode_cost_reads_weights_and_live_cache_once():
    f, b = costs.decode_cost(DIMS, [10, 20])
    kv = 2 * 2 * 2 * 16 * 2
    assert b == costs.weight_bytes(DIMS) + kv * (30 + 2)
    gemm_f, _ = costs.gemms_cost(costs.forward_gemms(DIMS, 2, 2))
    assert f == gemm_f + 4 * 4 * 16 * (11 + 21) * 2
