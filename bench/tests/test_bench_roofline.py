"""The peaks table and the roofline arithmetic."""
import pytest

from bench import roofline


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_v5e_peaks_from_the_table():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_predict_cost_by_hand():
    # 1,024 queries: 16 flops and 13 float32 words each
    assert roofline.predict_cost(1024) == (16 * 1024.0, 13 * 4 * 1024.0)


def test_fit_cost_by_hand():
    # one task of 10 points: 14*10 + 30*(5*10 + 45) + 30 flops,
    # (3*10 + 13) words
    assert roofline.fit_cost([10]) == (140.0 + 2850.0 + 30.0, 43 * 4.0)


def test_share_and_bound_by_hand():
    # 819 MB moved in 2 ms: the memory term is 1 ms, half the time
    pct, bound = roofline.share(1.0, 819e6, 2e-3, "TPU v5 lite")
    assert bound == "memory" and pct == pytest.approx(50.0)
    pct, bound = roofline.share(197e9, 1.0, 4e-3, "TPU v5 lite")
    assert bound == "compute" and pct == pytest.approx(25.0)
    with pytest.raises(ValueError):
        roofline.share(1.0, 1.0, 0.0, "TPU v5 lite")
