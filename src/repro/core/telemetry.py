"""Guest-side telemetry (paper §2.2) -- pluggable hotness classifiers.

GPAC is *telemetry-agnostic* (design goal 4): every backend here consumes raw
per-window access counts and produces the same artifact, a ``bool[n_logical]``
hot mask. The host never sees any of this -- it only gets huge-page counts.

Built-in backends:
  * ``ipt``   -- Idle Page Tracking-like: per-window accessed bit, hot if the
                 bit is set in >= ``ipt_min_hits`` of the last ``ipt_windows``
                 windows (the paper's prototype telemetry).
  * ``pebs``  -- PEBS-like sampling: Bernoulli-subsampled counts with a
                 threshold (hardware-counter flavour).
  * ``damon`` -- DAMON-like region estimate: hotness smeared over adaptive
                 power-of-two regions (cheap, coarse).

New hotness sources plug in without editing this module:
:func:`register_backend` adds a ``fn(cfg, state, **kw) -> bool[n_logical]``
to the registry and every ``hot_mask(...)`` call site (the engine, GPAC, the
benchmarks) can name it (DESIGN.md §8).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.address_space import dataclasses_replace
from repro.core.types import GpacConfig, TieredState

# builtin names (kept for back-compat; the live set is backends())
BACKENDS = ("ipt", "pebs", "damon")

_BACKENDS: dict[str, Callable] = {}
_PAGE_LOCAL: set[str] = set()


def register_backend(name: str, fn: Callable | None = None, *,
                     page_local: bool = False):
    """Register a hotness classifier ``fn(cfg, state, **kw) ->
    bool[n_logical]``; usable as ``@register_backend("name")``. The name
    becomes valid everywhere a ``backend=`` string is accepted.
    ``page_local`` declares that a page's verdict reads only that page's
    own counters, so any slice of the pages classifies on its own."""
    if fn is None:
        return lambda f: register_backend(name, f, page_local=page_local)
    if name in _BACKENDS:
        raise ValueError(f"telemetry backend {name!r} already registered")
    _BACKENDS[name] = fn
    if page_local:
        _PAGE_LOCAL.add(name)
    return fn


def backends() -> tuple[str, ...]:
    """Names of all registered telemetry backends."""
    return tuple(_BACKENDS)


def is_page_local(name: str) -> bool:
    """Whether backend ``name`` classifies each page from its own counters
    alone (see :func:`register_backend`)."""
    return name in _PAGE_LOCAL


def end_window(cfg: GpacConfig, state: TieredState) -> TieredState:
    """Roll the telemetry window: fold current counts into bit history and
    clear them (the paper's daemon clearing ACCESSED bits)."""
    accessed = (state.guest_counts > 0).astype(jnp.uint8)
    hist = ((state.ipt_hist << 1) | accessed).astype(jnp.uint8)
    h_accessed = (state.host_counts > 0).astype(jnp.uint8)
    h_hist = ((state.host_hist << 1) | h_accessed).astype(jnp.uint8)
    return dataclasses_replace(
        state,
        ipt_hist=hist,
        host_hist=h_hist,
        guest_counts=jnp.zeros_like(state.guest_counts),
        host_counts=jnp.zeros_like(state.host_counts),
        epoch=state.epoch + 1,
    )


def _popcount_u8(x: jax.Array) -> jax.Array:
    """Set bits per uint8 history word, as int32 (single hardware popcount
    instead of an 8-step shift/mask loop -- this runs on every window in both
    the IPT hot mask and the host block score)."""
    return jax.lax.population_count(x).astype(jnp.int32)


def hot_mask_ipt(cfg: GpacConfig, state: TieredState) -> jax.Array:
    """Hot iff accessed in >= ipt_min_hits of the last ipt_windows windows
    (including the in-flight window)."""
    mask = jnp.uint8((1 << min(cfg.ipt_windows, 8)) - 1)
    hits = _popcount_u8(state.ipt_hist & mask)
    hits = hits + (state.guest_counts > 0).astype(jnp.int32)
    return hits >= cfg.ipt_min_hits


def hot_mask_pebs(
    cfg: GpacConfig, state: TieredState, key: jax.Array | None = None, rate: float = 0.25
) -> jax.Array:
    """Sampled-counter hotness: subsample this window's counts and threshold.

    Deterministic given ``key``; defaults to a fold of the epoch so simulation
    runs are reproducible.
    """
    if key is None:
        key = jax.random.fold_in(jax.random.PRNGKey(0), state.epoch)
    sampled = jax.random.binomial(
        key, state.guest_counts.astype(jnp.float32), rate
    ).astype(jnp.int32)
    return sampled >= jnp.maximum(1, jnp.int32(cfg.hot_threshold * rate))


def hot_mask_damon(
    cfg: GpacConfig, state: TieredState, region_pages: int = 64
) -> jax.Array:
    """Region-granular estimate: a region is hot if its mean count crosses the
    threshold; every page inherits its region's verdict (DAMON's trade-off)."""
    n = state.guest_counts.shape[0]
    pad = (-n) % region_pages
    c = jnp.pad(state.guest_counts, (0, pad)).reshape(-1, region_pages)
    region_hot = c.mean(axis=1) >= cfg.hot_threshold
    return jnp.repeat(region_hot, region_pages)[:n]


register_backend("ipt", hot_mask_ipt, page_local=True)
register_backend("pebs", hot_mask_pebs)
register_backend("damon", hot_mask_damon)


def hot_mask(cfg: GpacConfig, state: TieredState, backend: str = "ipt", **kw) -> jax.Array:
    """Dispatch to a registered hotness classifier by name."""
    try:
        fn = _BACKENDS[backend]
    except KeyError:
        raise ValueError(
            f"unknown telemetry backend {backend!r} (have {backends()})"
        ) from None
    return fn(cfg, state, **kw)


# --------------------------------------------------------------------------
# skew statistics (paper Fig. 2 / Fig. 16) -- guest-side views
# --------------------------------------------------------------------------
def hot_subpages_per_hp(
    cfg: GpacConfig, state: TieredState, hot: jax.Array,
    kernel_backend: str = "auto",
) -> jax.Array:
    """int32[n_gpa_hp]: number of hot base pages inside each huge page.

    This is the quantity the Scattered Page Filter compares against CL, and
    the x-axis of the paper's skew CDFs. Computed via rmap so unallocated gpa
    pages never count. The strided reduction dispatches to the hotness_scan
    kernel through the registry (``kernel_backend``, DESIGN.md §16); tests
    pin kernel == jnp path bit-for-bit.
    """
    from repro.kernels import registry as kernels

    hot_gpa = jnp.where(state.rmap >= 0, hot[jnp.maximum(state.rmap, 0)], False)
    return kernels.dispatch(
        "hot_count", kernel_backend, hot_gpa, cfg.hp_ratio)


def accessed_subpages_per_hp(
    cfg: GpacConfig, state: TieredState, kernel_backend: str = "auto",
) -> jax.Array:
    """int32[n_gpa_hp]: accessed (count>0) base pages per huge page -- the
    exact statistic of paper Fig. 2. Dispatches through the same
    ``hot_count`` registry entry as :func:`hot_subpages_per_hp`."""
    from repro.kernels import registry as kernels

    acc = state.guest_counts > 0
    acc_gpa = jnp.where(state.rmap >= 0, acc[jnp.maximum(state.rmap, 0)], False)
    return kernels.dispatch(
        "hot_count", kernel_backend, acc_gpa, cfg.hp_ratio)
