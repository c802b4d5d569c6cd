"""Workload-generator determinism and shape pins (clients/workloads.py).
The dintscan helpers feed exp.py artifacts and the StoreClient ladder —
given the same seed they must reproduce bit-for-bit, or a hardware A/B
is not replayable."""
import numpy as np
import pytest

from dint_tpu.clients import workloads as wl


def test_scan_lengths_bounds_and_determinism():
    a = wl.scan_lengths(np.random.default_rng(7), 10_000, 16)
    b = wl.scan_lengths(np.random.default_rng(7), 10_000, 16)
    assert np.array_equal(a, b)
    assert a.dtype == np.uint32
    assert a.min() >= 1 and a.max() <= 16
    # uniform over [1, max]: every length shows up at this sample size
    assert set(np.unique(a)) == set(range(1, 17))
    c = wl.scan_lengths(np.random.default_rng(7), 1000, 8, min_len=4)
    assert c.min() >= 4 and c.max() <= 8
    with pytest.raises(AssertionError):
        wl.scan_lengths(np.random.default_rng(0), 10, 4, min_len=5)


def test_zipf_scan_starts_matches_zipf_keys():
    # rank == key-id alignment with the point workloads: the scan skew
    # touches the same hot head the caches serve
    a = wl.zipf_scan_starts(np.random.default_rng(3), 5_000, 1_000)
    b = wl.zipf_keys(np.random.default_rng(3), 5_000, 1_000)
    assert np.array_equal(a, b)
    assert a.min() >= 1 and a.max() <= 1_000
    # hot head: key 1 strictly more popular than the median key
    assert (a == 1).sum() > (a == 500).sum()


def test_ycsb_e_ops_deterministic_shape():
    r1, r2 = np.random.default_rng(11), np.random.default_rng(11)
    s1, k1, l1 = wl.ycsb_e_ops(r1, 8_000, 10_000)
    s2, k2, l2 = wl.ycsb_e_ops(r2, 8_000, 10_000)
    assert np.array_equal(s1, s2)
    assert np.array_equal(k1, k2)
    assert np.array_equal(l1, l2)
    assert s1.dtype == bool and l1.dtype == np.uint32
    # YCSB-E mix: 95% scans, lengths uniform in [1, 100], zero on writes
    frac = s1.mean()
    assert 0.93 < frac < 0.97
    assert (l1[~s1] == 0).all()
    assert l1[s1].min() >= 1 and l1[s1].max() <= wl.YCSB_E_MAX_SCAN
    assert k1.min() >= 1 and k1.max() <= 10_000


def test_ycsb_e_ops_scan_frac_knob():
    s, _, lens = wl.ycsb_e_ops(np.random.default_rng(5), 4_000, 1_000,
                               scan_frac=0.05, max_len=8)
    assert 0.03 < s.mean() < 0.08
    assert lens[s].max() <= 8
    s0, _, l0 = wl.ycsb_e_ops(np.random.default_rng(5), 1_000, 1_000,
                              scan_frac=0.0)
    assert not s0.any() and (l0 == 0).all()


def test_zipf_keys_hot_head():
    """rank == key id: the Zipfian head concentrates on the smallest ids,
    in range, strongly skewed at theta=0.99."""
    rng = np.random.default_rng(0)
    k = wl.zipf_keys(rng, 100_000, 10_000)
    assert k.min() >= 1 and k.max() <= 10_000
    assert (k <= 400).mean() > 0.5            # 4% of keys, >50% of draws
    # theta=0 degenerates toward uniform
    u = wl.zipf_keys(rng, 100_000, 10_000, theta=0.0)
    assert abs((u <= 400).mean() - 0.04) < 0.01
