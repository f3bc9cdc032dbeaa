"""The Scattered Page Filter's candidate rule against a plain numpy oracle.

``engine.run_reference`` calls the same ``candidate_mask`` as the engine,
so comparing the two cannot catch a wrong rule. Here ``candidate_mask`` and
``candidate_score`` are held bit for bit to :func:`oracle_mask`, which
counts hot pages per huge page with ``np.bincount`` over ``gpt // hp_ratio``
(as ``chipbench/reference/tiering.py`` does) and uses no filter or
telemetry code. The states come from a few GPAC windows of ``engine.run``
on a two-tenant fleet, so ``gpt`` is scrambled and recent regions cool.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import address_space as asp
from repro.core import engine
from repro.core import filter as pfilter

R = 32  # hp_ratio
CLS = tuple(max(1, round(c * R / 512)) for c in (50, 100))  # paper CLs, scaled
_POP8 = np.unpackbits(np.arange(256, dtype=np.uint8)[:, None], axis=1).sum(1)


def oracle_mask(gpt, region_epoch, epoch, hot, cooldown, cl, allow=None):
    """bool[n_logical]: hot pages whose huge page holds 1..CL-1 hot pages and
    was not consolidated within the last ``cooldown`` epochs."""
    hp_of = gpt // R
    per_hp = np.bincount(hp_of[hot], minlength=region_epoch.size)
    re = region_epoch[hp_of]
    cooling = (re >= 0) & (epoch - re < cooldown)
    out = hot & (per_hp[hp_of] > 0) & (per_hp[hp_of] < cl) & ~cooling
    return out if allow is None else out & allow


@pytest.fixture(scope="module", params=[0, 1, 2], ids=lambda c: f"cooldown{c}")
def fleet(request):
    """Cooldown, spec and state after 4 GPAC windows with that cooldown, plus
    the next window's accesses recorded (the state score sees in window 5)."""
    cooldown = request.param
    guests = (
        engine.GuestSpec(n_logical=40 * R, cl=CLS[0], workload="redis", seed=3),
        engine.GuestSpec(n_logical=24 * R, cl=CLS[1], workload="memcached",
                         seed=4),
    )
    host = engine.HostSpec(hp_ratio=R, near_fraction=0.4, base_elems=1, cl=4,
                           reconsolidate_cooldown=cooldown)
    spec, s0 = engine.build(guests, host)
    traces = engine.guest_traces(spec, n_windows=4, accesses_per_window=400)
    state, _ = engine.run(spec, s0, traces, max_batches=2)
    ids = np.random.default_rng(cooldown).integers(0, spec.cfg.n_logical, 300)
    state = asp.record_accesses(spec.cfg, state, jnp.asarray(ids, jnp.int32))
    return cooldown, spec, state


def _plant(hot, gpt, hps, cl_of_page, want):
    """Rewrite ``hot`` inside the first huge page of ``hps`` for which
    ``want(cl, mapped pages)`` is in 1..mapped pages, so that it holds that
    many hot pages; True if one was found."""
    for h in np.flatnonzero(hps):
        pages = np.flatnonzero(gpt // R == h)
        if not pages.size:
            continue
        k = want(int(cl_of_page[pages[0]]), pages.size)
        if 0 < k <= pages.size:
            hot[pages] = False
            hot[pages[:k]] = True
            return True
    return False


@pytest.mark.parametrize("at", ["next", "last"])
@pytest.mark.parametrize("per_logical_cl", [False, True])
@pytest.mark.parametrize("with_allow", [False, True])
def test_candidates_match_numpy_oracle(fleet, at, per_logical_cl, with_allow):
    cooldown, spec, state = fleet
    cfg = spec.cfg
    if at == "last":  # as seen in the epoch of the newest consolidation
        state = dataclasses.replace(state, epoch=state.epoch - 1)
    gpt = np.asarray(state.gpt)
    region_epoch = np.asarray(state.region_epoch)
    epoch = int(state.epoch)
    assert (gpt != np.arange(gpt.size)).any()

    cl = np.asarray(spec.cl_per_logical()) if per_logical_cl else cfg.cl
    cl_of_page = np.broadcast_to(cl, gpt.shape)
    hist = _POP8[np.asarray(state.ipt_hist)]
    counts = np.asarray(state.guest_counts)
    hot = hist + (counts > 0) >= cfg.ipt_min_hits

    cooling_hp = (region_epoch >= 0) & (epoch - region_epoch < cooldown)
    if cooldown == 2 or (cooldown == 1 and at == "last"):
        assert cooling_hp.any()
        # a cooling huge page that the CL test alone would admit
        assert _plant(hot, gpt, cooling_hp, cl_of_page,
                      lambda c, n: min(c - 1, n))
    else:
        assert not cooling_hp.any()
    # a huge page at exactly CL hot pages: never a candidate
    assert _plant(hot, gpt, ~cooling_hp & (region_epoch < 0), cl_of_page,
                  lambda c, n: c)

    allow = None
    if with_allow:
        lo, hi = spec.logical_range(1)
        allow = np.zeros(gpt.size, bool)
        allow[lo:hi] = True

    want = oracle_mask(gpt, region_epoch, epoch, hot, cooldown, cl, allow)
    per_hp = np.bincount(gpt[hot] // R, minlength=cfg.n_gpa_hp)
    assert (hot & (per_hp[gpt // R] == cl_of_page)).any()
    assert want.any() and (hot & ~want).any()

    got = pfilter.candidate_mask(
        cfg, state, jnp.asarray(hot), jnp.asarray(cl),
        None if allow is None else jnp.asarray(allow))
    np.testing.assert_array_equal(np.asarray(got), want)
    if allow is None:
        score = counts.astype(np.int32) * 256 + hist.astype(np.int32)
        got_score = pfilter.candidate_score(
            cfg, state, jnp.asarray(hot), jnp.asarray(cl))
        np.testing.assert_array_equal(
            np.asarray(got_score), np.where(want, score, -1))
