"""The roofline shape function and the table of peaks."""

import json

import pytest

from bench import roofline


def test_flush_work_counts_a_known_flush():
    # 100 rows over both task-kind models of 24 trees x depth 5, 22 features
    ops, nbytes = roofline.flush_work(100, 2, n_trees=24, depth=5,
                                      n_features=22)
    assert ops == 100 * 24 * 6
    model = 24 * (2 * 5 + 32) * 4
    assert nbytes == 100 * 23 * 4 + 2 * model
    assert model == 4032


def test_roofline_share_and_bound():
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    pct, bound = roofline.roofline_pct(1e6, 1e6, 0.01, peak)
    assert bound == "memory" and pct == pytest.approx(10.0)
    pct, bound = roofline.roofline_pct(1e10, 1.0, 0.02, peak)
    assert bound == "compute" and pct == pytest.approx(50.0)


def test_peaks_known_and_unknown_kind(tmp_path):
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    table = json.loads(roofline.PEAKS.read_text())
    assert "Google Cloud" in table["source"]
