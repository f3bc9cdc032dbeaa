"""Idle-page-tracking telemetry of tenants that each touch a hot set.

The generator of mixes such as ``kv.json``. A mix gives, per tenant kind,
a hot set of ``hot_fraction`` of the guest's pages, which a fixed
permutation of the seed scatters uniformly over the guest's logical space,
a popularity over the hot set's ranks and an optional drift of that
popularity per window. The popularity law is the program's
``repro.data.traces`` redis/memcached law, copied so that the yardstick
cannot move when the program's generators do: a draw is ``|N(0, sigma)|``
with ``sigma = n_hot / sigma_divisor``, folded onto the hot set.

What a window holds is what an idle-page-tracking scan reports: every page
the tenant touched in the window, once, in page order. The tenant makes
``touches_per_hot_page * n_hot`` accesses per window, so a page of
popularity ``f`` is touched with probability ``1 - exp(-touches * n_hot *
f)`` (the Poisson form of that many independent draws), each page apart.
Guests touch different numbers of pages, so each row is padded with -1 to
the width ``k`` of the mix, which lies ten standard deviations above the
largest tenant's mean count; a window that would exceed it is an error,
never cut short.

Every draw is keyed on ``(seed, guest, absolute window)``, so a stream does
not depend on how it is chunked or on which thread made it.
"""
from __future__ import annotations

import math

import numpy as np

_SCATTER_STREAM = 1
_WINDOW_STREAM = 0
_FOLDS = 8  # folds of the half-normal onto the hot set; past 8 sigma nothing
_PAD_SIGMAS = 10.0
_K_ALIGN = 1024


def touch_probability(law: dict, n_hot: int, touches: float) -> np.ndarray:
    """float64[n_hot]: the chance that the page of each popularity rank is
    touched in one window."""
    sigma = n_hot / float(law["sigma_divisor"])
    r = np.arange(n_hot, dtype=np.float64) + 0.5
    w = np.zeros(n_hot)
    for m in range(_FOLDS):
        w += np.exp(-((r + m * n_hot) / sigma) ** 2 / 2)
    f = w / w.sum()
    return -np.expm1(-touches * n_hot * f)


class Traffic:
    """Telemetry of one fleet: ``window(w)`` is ``int32[n_guests, 1, k]``
    guest-local page ids, -1 padded, the engine's ``ArrayTrace`` of one
    window."""

    def __init__(self, mix: dict, tenants: list[str], pages: list[int],
                 seed: int, pool=None):
        if seed < 0:
            raise ValueError(f"seed must be >= 0, got {seed}")
        missing = sorted(set(tenants) - set(mix["tenants"]))
        if missing:
            raise ValueError(
                f"traffic mix has no tenant kind {missing} (has "
                f"{sorted(mix['tenants'])})")
        self.seed = int(seed)
        self.laws = [mix["tenants"][t] for t in tenants]
        self.pages = [int(p) for p in pages]
        self.n_hot = [max(1, int(p * law["hot_fraction"]))
                      for p, law in zip(self.pages, self.laws)]
        touches = float(mix["touches_per_hot_page"])
        # one table of touch chances per distinct (tenant kind, hot set size)
        kinds = {(t, n) for t, n in zip(tenants, self.n_hot)}
        table = {(t, n): touch_probability(mix["tenants"][t], n, touches)
                 for t, n in sorted(kinds)}
        self._p = [table[(t, n)] for t, n in zip(tenants, self.n_hot)]
        widest = max(p.sum() + _PAD_SIGMAS * math.sqrt((p * (1 - p)).sum())
                     for p in table.values())
        self.k = int(-(-math.ceil(widest) // _K_ALIGN) * _K_ALIGN)
        # each guest's hot set, built up front (in ``pool`` when given)
        run = map if pool is None else pool.map
        self._scatter = list(run(self._hot_set, range(len(tenants))))

    @property
    def n_guests(self) -> int:
        return len(self.laws)

    def _hot_set(self, g: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, g, _SCATTER_STREAM])
        return rng.permutation(self.pages[g])[: self.n_hot[g]].astype(np.int32)

    def scatter(self, g: int) -> np.ndarray:
        """Guest g's hot set by popularity rank: ``n_hot`` distinct pages
        spread uniformly over its logical space (a prefix of a seeded
        permutation)."""
        return self._scatter[g]

    def guest_window(self, g: int, w: int) -> np.ndarray:
        """The pages guest g touched in window w, ascending."""
        law, n_hot = self.laws[g], self.n_hot[g]
        rng = np.random.default_rng([self.seed, g, w, _WINDOW_STREAM])
        ranks = np.flatnonzero(rng.random(n_hot) < self._p[g])
        shift = int(w * float(law.get("drift_per_window", 0.0)) * n_hot)
        touched = np.zeros(self.pages[g], bool)
        touched[self.scatter(g)[(ranks + shift) % n_hot]] = True
        return np.flatnonzero(touched).astype(np.int32)

    def window(self, w: int) -> np.ndarray:
        out = np.full((self.n_guests, 1, self.k), -1, np.int32)
        for g in range(self.n_guests):
            ids = self.guest_window(g, w)
            if ids.size > self.k:
                raise RuntimeError(
                    f"guest {g} touched {ids.size} pages in window {w}, more "
                    f"than the mix's width {self.k}")
            out[g, 0, : ids.size] = ids
        return out


def make(mix: dict, tenants: list[str], pages: list[int], seed: int,
         pool=None) -> Traffic:
    return Traffic(mix, tenants, pages, seed, pool)
