"""Scattered Page Filter (paper §4.3.1) -- guest user-space policy layer.

Input: the telemetry hot mask. Output: fixed-shape batches of logical page
ids (each batch <= hp_ratio) to hand to ``consolidate_pages()``.

Selection rule (paper): a hot base page is a consolidation candidate iff the
huge page it currently occupies has fewer than CL hot subpages. Freshly
consolidated regions are exempt for ``reconsolidate_cooldown`` epochs to stop
ping-ponging of partially filled regions (implementation detail the paper
leaves open; documented in DESIGN.md).

``select_batches`` serves one daemon; ``select_batches_ragged`` is the
batched multi-tenant form -- one row-wise top-k over the padded
``[n_guests, max_logical]`` score matrix built from the engine's
segment-offset tables (guests may have distinct sizes and CLs) instead of N
full-space sorts. ``select_batches_per_guest`` is the deprecated symmetric
wrapper kept for the old ``MultiGuest`` entry points.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import telemetry
from repro.core.types import GpacConfig, TieredState
from repro.kernels.topk.ref import top_k


def candidate_mask(
    cfg: GpacConfig,
    state: TieredState,
    hot: jax.Array,
    cl: int | jax.Array | None = None,
    allow: jax.Array | None = None,
    kernel_backend: str = "auto",
) -> jax.Array:
    """bool[n_logical]: hot pages living in skewed (< CL hot subpages) huge
    pages that are not inside a cooldown region. ``allow`` optionally
    restricts candidates to one guest's logical pages (multi-tenant).

    Cooling is folded into the per-huge-page hot count (a cooling region
    reads 0) before the single per-page lookup, since a cooling or empty
    huge page is never a candidate: one gather of ``n_logical`` indices
    instead of two."""
    cl = cfg.cl if cl is None else cl
    per_hp = telemetry.hot_subpages_per_hp(cfg, state, hot, kernel_backend)
    cooling = (state.region_epoch >= 0) & (
        state.epoch - state.region_epoch < cfg.reconsolidate_cooldown
    )
    per_hp = jnp.where(cooling, 0, per_hp)
    n_hot = per_hp[state.gpt // cfg.hp_ratio]
    out = hot & (n_hot > 0) & (n_hot < cl)
    if allow is not None:
        out = out & allow
    return out


def select_batches(
    cfg: GpacConfig,
    state: TieredState,
    hot: jax.Array,
    max_batches: int,
    cl: int | jax.Array | None = None,
    allow: jax.Array | None = None,
):
    """Pick up to ``max_batches * hp_ratio`` candidates, hottest first, and
    shape them into ``(max_batches, hp_ratio)`` id batches padded with -1.

    Ordering matters: consolidating the hottest scattered pages first densifies
    the regions the host is most likely to promote. Candidates are ranked by
    (current-window count, history popcount).
    """
    cand = candidate_mask(cfg, state, hot, cl, allow)
    score = jnp.where(cand, _hotness_score(state), -1)
    k = max_batches * cfg.hp_ratio
    k = min(k, cfg.n_logical)
    _, top_ids = top_k(score, k)
    top_valid = score[top_ids] >= 0
    ids = jnp.where(top_valid, top_ids.astype(jnp.int32), -1)
    pad = max_batches * cfg.hp_ratio - k
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), -1, jnp.int32)])
    batches = ids.reshape(max_batches, cfg.hp_ratio)
    counts = (batches >= 0).sum(axis=1).astype(jnp.int32)
    return batches, counts


def _hotness_score(state: TieredState) -> jax.Array:
    """Candidate ranking: hotter first; stable by page id for determinism
    (current-window count dominates, history popcount breaks ties)."""
    return (
        state.guest_counts.astype(jnp.int32) * 256
        + telemetry._popcount_u8(state.ipt_hist).astype(jnp.int32)
    )


def select_batches_from_rows(
    cfg: GpacConfig,
    score: jax.Array,  # int32[n_logical] candidate score, -1 = not a candidate
    pad_idx: jax.Array,  # int32[n_rows, max_logical] segment table rows, -1 padded
    max_batches: int,
    kernel_backend: str = "auto",
) -> jax.Array:
    """Row-wise batch selection over any slice of segment-table rows: one
    ``top_k`` per row of the padded score matrix gathered from the global
    ``score``. This is the shared core of :func:`select_batches_ragged`
    (all guests at once) and the device-sharded engine (each device passes
    only its own guests' rows). Returns ``int32[n_rows, max_batches,
    hp_ratio]`` logical-id batches, -1 padded."""
    from repro.kernels import registry as kernels

    mat = jnp.where(pad_idx >= 0, score[jnp.maximum(pad_idx, 0)], -1)
    k = min(max_batches * cfg.hp_ratio, mat.shape[1])
    # row-wise, ties -> lowest column (lax.top_k semantics on both backends;
    # scores are >= -1, safely above the kernel's INT32_MIN mask value)
    vals, col = kernels.dispatch("topk_rows", kernel_backend, mat, k)
    ids = jnp.where(vals >= 0, jnp.take_along_axis(pad_idx, col, axis=1), -1)
    pad = max_batches * cfg.hp_ratio - k
    if pad:
        ids = jnp.concatenate(
            [ids, jnp.full((mat.shape[0], pad), -1, jnp.int32)], axis=1
        )
    return ids.reshape(mat.shape[0], max_batches, cfg.hp_ratio)


def candidate_score(
    cfg: GpacConfig,
    state: TieredState,
    hot: jax.Array,
    cl_per_logical: jax.Array,
    kernel_backend: str = "auto",
) -> jax.Array:
    """int32[n_logical] filter ranking: the hotness score where
    :func:`candidate_mask` holds (per-guest CLs via ``cl_per_logical``),
    -1 elsewhere."""
    cand = candidate_mask(
        cfg, state, hot, cl_per_logical, kernel_backend=kernel_backend)
    return jnp.where(cand, _hotness_score(state), -1)


def select_batches_ragged(
    spec,  # repro.core.engine.EngineSpec
    state: TieredState,
    hot: jax.Array,
    max_batches: int,
) -> jax.Array:
    """Batched :func:`select_batches` for N **ragged** guests: one row-wise
    ``top_k`` over the padded ``[n_guests, max_logical]`` score matrix built
    from the spec's segment-offset tables replaces ``n_guests`` full-space
    sorts (each O(n_logical)), so the filter's work no longer grows
    quadratically with guest count -- and guests may have distinct sizes and
    per-guest Consolidation Limits.

    Returns ``int32[n_guests, max_batches, hp_ratio]`` logical-id batches,
    padded with -1 -- row ``g`` is exactly what ``select_batches(...,
    cl=guest g's CL, allow=guest g's segment)`` would produce, because a
    guest's candidate mask, score, and in-segment ordering are all unaffected
    by the other guests' segments, and row-wise ``top_k`` tie-breaking by
    column index preserves the global id order inside each segment.
    """
    cfg = spec.cfg
    kb = spec.kernel_backend
    score = candidate_score(
        cfg, state, hot, jnp.asarray(spec.cl_per_logical()), kb
    )
    pad_idx = jnp.asarray(spec.logical_pad_index())  # [n_guests, max_logical]
    return select_batches_from_rows(cfg, score, pad_idx, max_batches, kb)


def select_batches_per_guest(
    cfg: GpacConfig,
    state: TieredState,
    hot: jax.Array,
    max_batches: int,
    cl: int | None,
    n_guests: int,
    logical_per_guest: int,
) -> jax.Array:
    """Deprecated symmetric wrapper over :func:`select_batches_ragged` (kept
    for the old ``MultiGuest`` entry points)."""
    from repro.core.engine import symmetric_spec

    if n_guests * logical_per_guest != cfg.n_logical:
        raise ValueError("guest logical segments must tile the logical space")
    return select_batches_ragged(
        symmetric_spec(cfg, n_guests, cl=cl), state, hot, max_batches
    )
