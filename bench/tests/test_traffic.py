"""The traffic generator is a function of its seed and keeps to the
parameters its mix file states."""
import numpy as np
import pytest

from bench.lib import spec, traffic

MODEL = {"n_fields": 24, "context_fields": 16, "hash_space": 2 ** 22}


def _mix(name):
    return traffic.load_mix(name)


@pytest.mark.parametrize("name", ["sessions_open", "sessions_max"])
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.generate(mix, MODEL, 2 ** 33 + 5, 400)
    b = traffic.generate(mix, MODEL, 2 ** 33 + 5, 400)
    c = traffic.generate(mix, MODEL, 2 ** 33 + 6, 400)
    for f in ("ctx_idx", "ctx_val", "depth", "session", "offsets", "ads",
              "ad_idx", "due_s"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.ctx_idx, c.ctx_idx)
    for i in (0, 17, 399):
        for x, y in zip(a.get(i), b.get(i)):
            np.testing.assert_array_equal(x, y)


def test_prefix_depth_shares_are_exact_and_shared():
    mix = _mix("sessions_open")
    n = 2000
    r = traffic.generate(mix, MODEL, 11, n)
    for d, share in mix["prefix_share"].items():
        assert np.sum(r.depth == int(d)) == round(n * share)
    # each request keeps the first `depth` fields of its session's last
    # context, and becomes that session's last context
    last = {}
    kept = 0
    for i in range(n):
        s, d = int(r.session[i]), int(r.depth[i])
        if s in last:
            np.testing.assert_array_equal(r.ctx_idx[i, :d], last[s][0][:d])
            np.testing.assert_array_equal(r.ctx_val[i, :d], last[s][1][:d])
            kept += 1
        last[s] = (r.ctx_idx[i], r.ctx_val[i])
    assert kept > n // 4  # sessions recur


def test_slate_lengths_follow_the_lognormal():
    mix = _mix("sessions_open")
    c = mix["candidates"]
    a = traffic.generate(mix, MODEL, 1, 3000)
    b = traffic.generate(mix, MODEL, 2, 3000)
    la = np.diff(a.offsets)
    lb = np.diff(b.offsets)
    # the same multiset for every seed, in another order
    np.testing.assert_array_equal(np.sort(la), np.sort(lb))
    assert not np.array_equal(la, lb)
    assert la.min() >= c["min"] and la.max() <= c["max"]
    assert abs(np.median(la) - c["median"]) <= 1
    # quartiles of a lognormal: median * exp(+-0.6745 sigma)
    q1, q3 = np.percentile(la, [25, 75])
    assert q1 == pytest.approx(c["median"] * np.exp(-0.6745 * c["sigma"]),
                               rel=0.02)
    assert q3 == pytest.approx(c["median"] * np.exp(0.6745 * c["sigma"]),
                               rel=0.02)


def test_zipf_ranks():
    rng = np.random.default_rng(3)
    z = traffic.Zipf(1000, 1.0)
    x = z.sample(rng, 400_000)
    h = np.sum(1.0 / np.arange(1, 1001))
    for r in (0, 1, 9):
        assert np.mean(x == r) == pytest.approx(1.0 / ((r + 1) * h), rel=0.05)
    assert x.min() == 0 and x.max() == 999


def test_ads_popularity_and_inventory():
    mix = _mix("sessions_open")
    r = traffic.generate(mix, MODEL, 5, 2000)
    counts = np.bincount(r.ads, minlength=mix["inventory"])
    # the most popular ad is drawn about 1/H(inventory) of the time
    h = np.sum(1.0 / np.arange(1, mix["inventory"] + 1))
    assert counts[0] / r.ads.size == pytest.approx(1.0 / h, rel=0.1)
    assert r.ad_idx.shape == (mix["inventory"], 8)
    assert r.ad_idx.min() >= 0 and r.ad_idx.max() < MODEL["hash_space"]
    numeric = [f - 16 for f in mix["numeric_fields"] if f >= 16]
    cat = [c for c in range(8) if c not in numeric]
    assert np.all(r.ad_val[:, cat] == 1.0)
    assert np.all(r.ad_val[:, numeric] > 0)


def test_open_loop_schedule():
    mix = _mix("sessions_open")
    n = traffic.request_count(mix, 10)
    assert n == round(mix["rate_per_s"] * 10)
    r = traffic.generate(mix, MODEL, 9, n)
    assert np.all(np.diff(r.due_s) >= 0)
    assert 0 <= r.due_s[0] and r.due_s[-1] <= n / mix["rate_per_s"]
    # exponential gaps: arrivals spread evenly over the window at large
    assert np.mean(r.due_s < 5.0) == pytest.approx(0.5, abs=0.05)
    # every seed gets the same gaps (the last one up to n / rate), in its
    # own order
    other = traffic.generate(mix, MODEL, 2 ** 32 + 11, n)
    span = n / mix["rate_per_s"]
    gaps = [np.sort(np.diff(np.append(x.due_s, span))) for x in (r, other)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9, atol=1e-12)
    assert not np.allclose(other.due_s, r.due_s)


def test_every_mix_file_loads():
    for name in ("sessions", "sessions_open", "sessions_max"):
        mix = traffic.load_mix(name)
        assert mix["max_batch"] >= 1
    bench = spec.load()
    for cell in bench["workloads"]:
        assert traffic.load_mix(cell["traffic"])["loop"] in ("open", "max")
