"""The traffic generator: seeded, the same work for every seed, the
stated tenant skew and arrival rate."""
import numpy as np

from bench import traffic

ZIPF = {"loop": "open", "rate_per_s": 800, "tenant_dist": "zipf",
        "zipf_s": 0.99, "rhs_pool": 64}


def test_same_seed_same_trace():
    a = traffic.open_schedule(ZIPF, 16, 10.0, 2**31 + 17)
    b = traffic.open_schedule(ZIPF, 16, 10.0, 2**31 + 17)
    for k in ("t", "tenant", "rhs"):
        np.testing.assert_array_equal(a[k], b[k])
    np.testing.assert_array_equal(traffic.rhs_pool(ZIPF, 32, 5),
                                  traffic.rhs_pool(ZIPF, 32, 5))
    np.testing.assert_array_equal(traffic.closed_tenants(ZIPF, 16, 9),
                                  traffic.closed_tenants(ZIPF, 16, 9))


def test_other_seed_same_work_other_order():
    a = traffic.open_schedule(ZIPF, 16, 10.0, 1)
    b = traffic.open_schedule(ZIPF, 16, 10.0, 2)
    assert not np.array_equal(a["tenant"], b["tenant"])
    np.testing.assert_array_equal(np.bincount(a["tenant"], minlength=16),
                                  np.bincount(b["tenant"], minlength=16))
    np.testing.assert_allclose(np.sort(np.diff(a["t"])).sum(),
                               np.sort(np.diff(b["t"])).sum(), rtol=1e-3)


def test_zipf_head_share():
    seq = traffic.open_schedule(ZIPF, 16, 10.0, 3)["tenant"]
    share = np.bincount(seq, minlength=16) / len(seq)
    h = (1.0 / np.arange(1, 17) ** 0.99).sum()
    assert abs(share[0] - 1.0 / h) < 1e-3          # ~0.30 to the head
    assert np.all(np.diff(share) <= 1e-3)           # non-increasing
    hot = traffic.tenant_weights({"tenant_dist": "hot", "hot_tenants": 1}, 16)
    assert hot[0] == 1.0 and hot[1:].sum() == 0.0


def test_poisson_mean_rate_and_spread():
    s = traffic.open_schedule(ZIPF, 16, 10.0, 4)
    t = s["t"]
    assert len(t) == 8000
    assert t[0] == 0.0 and t[-1] < 10.0 and np.all(np.diff(t) > 0)
    gaps = np.diff(t)
    assert abs(gaps.mean() - 1 / 800) < 2e-5
    # exponential gaps: coefficient of variation ~1
    assert 0.95 < gaps.std() / gaps.mean() < 1.05
