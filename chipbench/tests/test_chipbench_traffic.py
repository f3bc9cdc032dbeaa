"""The traffic generator repeats exactly from its seed and does not depend
on how the stream is chunked or which thread makes a window."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench_helpers import ROOT

from harness import layout
from harness.traffic import Feed

MIX = json.loads((ROOT / "chipbench/traffic/kv.json").read_text())
GEN = layout.load_module(layout.generator_file(MIX["generator"], ROOT), "generator")
TENANTS = ["redis", "memcached", "redis"]
PAGES = [200_000, 150_000, 200_000]
BIG_SEED = 2**40 + 12345


def traffic(seed=BIG_SEED, mix=MIX):
    return GEN.make(mix, TENANTS, PAGES, seed)


def test_window_shape_range_and_hot_set():
    t = traffic()
    w = t.window(3)
    assert w.shape == (3, 1, t.k) and w.dtype == np.int32
    for g in range(3):
        ids = w[g, 0][w[g, 0] >= 0]
        assert ids.size > 0 and (w[g, 0, ids.size:] == -1).all()
        assert ids.max() < PAGES[g]
        assert (np.diff(ids) > 0).all()  # each touched page once, in order
        hot = t.scatter(g)
        assert np.unique(hot).size == hot.size
        assert np.isin(ids, hot).all()


def test_touched_share_follows_the_law():
    t = traffic()
    for g in range(3):
        p = t._p[g]
        counts = [t.guest_window(g, w).size for w in range(4)]
        mean, sd = p.sum(), np.sqrt((p * (1 - p)).sum())
        assert all(abs(c - mean) < 6 * sd for c in counts), (counts, mean, sd)
        assert max(counts) < t.k
    # the most popular ranks are touched nearly always, the last nearly never
    assert t._p[0][0] > 0.85 and t._p[0][-1] < 0.05


def test_same_seed_same_stream_in_any_order():
    a, b = traffic(), traffic()
    fwd = [a.window(w) for w in range(6)]
    rev = {w: b.window(w) for w in reversed(range(6))}
    for w in range(6):
        np.testing.assert_array_equal(fwd[w], rev[w])


def test_stream_does_not_depend_on_chunking_or_threads():
    t = traffic()
    seq = [t.window(w) for w in range(10, 30)]
    with Feed(traffic().window, start=10, workers=5, depth=7) as feed:
        got = [feed.get() for _ in range(20)]
    assert [w for w, _, _ in got] == list(range(10, 30))
    for (w, acc, waited), want in zip(got, seq):
        np.testing.assert_array_equal(acc, want)
        assert waited >= 0


def test_seeds_and_windows_differ():
    a = traffic(seed=1).window(0)
    assert not np.array_equal(a, traffic(seed=2).window(0))
    assert not np.array_equal(a, traffic(seed=1).window(1))


def test_redis_drifts_and_memcached_does_not():
    t = traffic()
    assert MIX["tenants"]["redis"]["drift_per_window"] > 0
    assert MIX["tenants"]["memcached"]["drift_per_window"] == 0
    law = MIX["tenants"]["redis"]
    n_hot = int(PAGES[0] * law["hot_fraction"])
    hot = t.scatter(0)
    pos = {int(p): i for i, p in enumerate(hot)}

    def centre(w):  # circular mean of the touched ranks
        a = 2 * np.pi * np.array([pos[int(p)] for p in t.guest_window(0, w)]) / n_hot
        return np.angle(np.exp(1j * a).mean()) * n_hot / (2 * np.pi)

    moved = (centre(40) - centre(0)) % n_hot
    assert abs(moved - int(40 * law["drift_per_window"] * n_hot)) < 0.01 * n_hot


def test_unknown_tenant_and_negative_seed_are_errors():
    with pytest.raises(ValueError, match="no tenant kind"):
        GEN.make(MIX, ["redis", "nosuch"], [10, 10], 1)
    with pytest.raises(ValueError, match="seed"):
        GEN.make(MIX, ["redis"], [10], -1)
