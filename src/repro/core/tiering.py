"""Host-side memory tiering (paper §2.3, §5) -- block-granular policies.

The host sees only huge-page-granular telemetry (``host_counts``,
``host_hist``, ``last_touch_epoch``) and moves whole blocks between the near
and far pools. GPAC never modifies anything here -- that is the paper's
host-agnosticism, and the test matrix runs every policy against the same
guest-side GPAC unchanged.

Three faithful built-in policy flavours:
  * ``memtierd`` -- proactive userspace ranking: keep the globally hottest
    blocks near, even without memory pressure (paper §5.2 uses this).
  * ``autonuma`` -- hint-fault-style promotion (>=2 touches while far) and
    demotion only under near-pool pressure, LRU victims.
  * ``tpp``      -- fault promotion with a free-page watermark: demote coldest
    blocks until a headroom fraction of near is kept free.

New placement policies plug in without editing this module:
:func:`register_policy` adds a ``fn(cfg, state, **kw) -> TieredState`` to the
registry and every ``policy=`` string (the engine driver, ``tick``, the
benchmarks) can name it (DESIGN.md §8).

Migration primitive: ``swap_blocks`` -- exchange the placement of a far block
and a near block (data + block_table + slot_owner), the functional analogue of
NUMA page migration at block granularity.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.address_space import dataclasses_replace
from repro.core.telemetry import _popcount_u8
from repro.core.types import GpacConfig, TieredState, allocated_hp_mask
from repro.kernels.topk.ref import top_k

# builtin names (kept for back-compat; the live set is policies())
POLICIES = ("memtierd", "autonuma", "tpp")
NEG = jnp.int32(-(2**31) + 1)

_POLICIES: dict[str, Callable] = {}


def register_policy(name: str, fn: Callable | None = None):
    """Register a host tiering policy ``fn(cfg, state, **kw) -> TieredState``
    (keyword args include at least ``budget``); usable as
    ``@register_policy("name")``. The name becomes valid everywhere a
    ``policy=`` string is accepted."""
    if fn is None:
        return lambda f: register_policy(name, f)
    if name in _POLICIES:
        raise ValueError(f"tiering policy {name!r} already registered")
    _POLICIES[name] = fn
    return fn


def policies() -> tuple[str, ...]:
    """Names of all registered tiering policies."""
    return tuple(_POLICIES)


def swap_blocks(
    cfg: GpacConfig,
    state: TieredState,
    far_hps: jax.Array,
    near_hps: jax.Array,
    k: jax.Array,
) -> TieredState:
    """Promote ``far_hps[i]`` and demote ``near_hps[i]`` for i < k.

    Pairs where either id is -1, i >= k, or tiers don't match are dropped.
    Vectorized: one gather + two drop-mode scatters per pool.
    """
    m = far_hps.shape[0]
    i = jnp.arange(m)
    fa = jnp.maximum(far_hps, 0)
    ne = jnp.maximum(near_hps, 0)
    s_far = state.block_table[fa]
    s_near = state.block_table[ne]
    ok = (
        (i < k)
        & (far_hps >= 0)
        & (near_hps >= 0)
        & (s_far >= cfg.n_near)
        & (s_near < cfg.n_near)
    )
    far_row = jnp.where(ok, s_far - cfg.n_near, cfg.n_far)
    near_row = jnp.where(ok, s_near, cfg.n_near)

    data_far = state.far_pool[jnp.where(ok, s_far - cfg.n_near, 0)]
    data_near = state.near_pool[jnp.where(ok, s_near, 0)]
    near_pool = state.near_pool.at[near_row].set(data_far, mode="drop")
    far_pool = state.far_pool.at[far_row].set(data_near, mode="drop")

    bt = state.block_table
    bt = bt.at[jnp.where(ok, far_hps, cfg.n_gpa_hp)].set(s_near, mode="drop")
    bt = bt.at[jnp.where(ok, near_hps, cfg.n_gpa_hp)].set(s_far, mode="drop")
    so = state.slot_owner
    so = so.at[jnp.where(ok, s_near, cfg.n_slots)].set(fa, mode="drop")
    so = so.at[jnp.where(ok, s_far, cfg.n_slots)].set(ne, mode="drop")

    n_swaps = ok.sum().astype(jnp.int32)
    alloc = allocated_hp_mask(cfg, state)
    promoted = (ok & alloc[fa]).sum().astype(jnp.int32)
    demoted = (ok & alloc[ne]).sum().astype(jnp.int32)
    stats = dict(state.stats)
    stats["promoted_blocks"] = stats["promoted_blocks"] + promoted
    stats["demoted_blocks"] = stats["demoted_blocks"] + demoted
    stats["tlb_shootdowns"] = stats["tlb_shootdowns"] + (n_swaps > 0).astype(jnp.int32)
    return dataclasses_replace(
        state,
        block_table=bt,
        slot_owner=so,
        near_pool=near_pool,
        far_pool=far_pool,
        stats=stats,
    )


def block_score_arrays(host_counts: jax.Array, host_hist: jax.Array) -> jax.Array:
    """The host block score from its raw telemetry arrays (shared by the
    replicated tick and the host-partitioned tick, which scores only a
    device's local block range)."""
    return (
        host_counts.astype(jnp.int32) * 256
        + _popcount_u8(host_hist).astype(jnp.int32)
    )


def _block_score(cfg: GpacConfig, state: TieredState) -> jax.Array:
    """Host's only view: current-window count + access-bit history."""
    return block_score_arrays(state.host_counts, state.host_hist)


def _paired_ids(mask_a, score_a, mask_b, score_b, budget):
    """Top-``budget`` ids of a (desc score) paired with top ids of b
    (asc score); -1 padded. Returns (ids_a, ids_b, k)."""
    budget = min(budget, mask_a.shape[0])
    sa = jnp.where(mask_a, score_a, NEG)
    sb = jnp.where(mask_b, -score_b, NEG)
    va, ia = top_k(sa, budget)
    vb, ib = top_k(sb, budget)
    ids_a = jnp.where(va > NEG, ia.astype(jnp.int32), -1)
    ids_b = jnp.where(vb > NEG, ib.astype(jnp.int32), -1)
    k = jnp.minimum((ids_a >= 0).sum(), (ids_b >= 0).sum())
    return ids_a, ids_b, k


# --------------------------------------------------------------------------
# policies
# --------------------------------------------------------------------------
def _flow(cfg, state, tiers, pair_name, **kw):
    """Dispatch a builtin policy over an N-tier vector as adjacent-pair
    flows (``core.tiers``); the 2-tier vector is pinned bit-for-bit against
    the legacy body below (INV-TIER-2SPECIALCASE-EXACT)."""
    from repro.core import tiers as tiers_mod

    return tiers_mod.flow_tick(
        cfg, state, tiers, tiers_mod._PAIR_FNS[pair_name], **kw)


def memtierd_tick(
    cfg: GpacConfig, state: TieredState, budget: int = 64, tiers=None
) -> TieredState:
    """Proactive ranking: the hottest allocated blocks belong near.

    Promote the hottest far blocks whose score beats the coldest near blocks
    (swap pairs), up to ``budget`` migrations per tick. With an N-tier
    ``tiers`` vector, runs as adjacent-pair flows instead.
    """
    if tiers is not None:
        return _flow(cfg, state, tiers, "memtierd", budget=budget)
    score = _block_score(cfg, state)
    alloc = allocated_hp_mask(cfg, state)
    in_near = state.block_table < cfg.n_near
    # promotion candidates: *identified hot* far blocks only (score > 0) --
    # Memtierd migrates hot pages, it does not prefetch cold data near.
    # victims: near blocks, coldest first (unallocated near blocks score NEG+1
    # so they are always preferred victims)
    victim_score = jnp.where(alloc, score, NEG + 1)
    far_ids, near_ids, k = _paired_ids(
        alloc & ~in_near & (score > 0), score, in_near, victim_score, budget
    )
    # only swap pairs that strictly improve: promote score > victim score
    gain = jnp.where(
        (far_ids >= 0) & (near_ids >= 0),
        score[jnp.maximum(far_ids, 0)] > victim_score[jnp.maximum(near_ids, 0)],
        False,
    )
    # pairs are sorted best-first, so the improving prefix is contiguous
    k = jnp.minimum(k, gain.astype(jnp.int32).cumprod().sum())
    state = swap_blocks(cfg, state, far_ids, near_ids, k)

    # proactive demotion: cold allocated near blocks move out into free far
    # blocks even with no promotion pressure (Memtierd relocates cold data).
    alloc = allocated_hp_mask(cfg, state)
    in_near = state.block_table < cfg.n_near
    score = _block_score(cfg, state)
    cold_near = alloc & in_near & (score == 0)
    free_far = ~alloc & ~in_near
    far_ids, near_ids, k = _paired_ids(
        free_far, jnp.zeros_like(score), cold_near, score, budget
    )
    return swap_blocks(cfg, state, far_ids, near_ids, k)


def autonuma_tick(
    cfg: GpacConfig,
    state: TieredState,
    budget: int = 16,
    pressure: float = 0.95,
    tiers=None,
) -> TieredState:
    """Hint-fault promotion; demote only under pressure (LRU victims)."""
    if tiers is not None:
        return _flow(cfg, state, tiers, "autonuma", budget=budget,
                     pressure=pressure)
    alloc = allocated_hp_mask(cfg, state)
    in_near = state.block_table < cfg.n_near
    faulting = alloc & ~in_near & (state.host_counts >= 2)
    near_used = (alloc & in_near).sum()
    pressured = near_used >= jnp.int32(pressure * cfg.n_near)
    # victims: free near blocks always; allocated LRU blocks only if pressured
    lru = state.last_touch_epoch.astype(jnp.int32)
    victim_ok = in_near & (~alloc | pressured)
    victim_score = jnp.where(alloc, lru, NEG + 1)  # free blocks first, then LRU
    far_ids, near_ids, k = _paired_ids(
        faulting, state.host_counts.astype(jnp.int32), victim_ok, victim_score, budget
    )
    return swap_blocks(cfg, state, far_ids, near_ids, k)


def tpp_tick(
    cfg: GpacConfig,
    state: TieredState,
    budget: int = 16,
    watermark: float = 0.1,
    tiers=None,
) -> TieredState:
    """Fault promotion + watermark demotion under allocation pressure
    (TPP's two loops).

    1. if promotion demand exists, demote coldest allocated near blocks into
       free far blocks until >= watermark * n_near near blocks are free --
       demotion only runs under pressure (faulting blocks waiting), like
       TPP's wmark_demote path;
    2. promote blocks with >=2 faults this window into the freed space.
    """
    if tiers is not None:
        return _flow(cfg, state, tiers, "tpp", budget=budget,
                     watermark=watermark)
    alloc = allocated_hp_mask(cfg, state)
    in_near = state.block_table < cfg.n_near
    free_near = (in_near & ~alloc).sum()
    want_free = jnp.int32(watermark * cfg.n_near)
    demand = (alloc & ~in_near & (state.host_counts >= 2)).sum()
    # demotion keeps the free watermark AND keeps up with promotion demand
    # (TPP's wmark_demote runs ahead of the promotion path) -- but only under
    # pressure: with no faulting pages, nothing is demoted.
    need = jnp.maximum(jnp.minimum(want_free, demand),
                       jnp.minimum(demand, budget))
    n_demote = jnp.clip(need - free_near, 0, budget)
    lru = state.last_touch_epoch.astype(jnp.int32)
    # demotion: coldest allocated near <-> unallocated far
    far_free_ids, near_cold_ids, k_d = _paired_ids(
        ~in_near & ~alloc,
        jnp.zeros_like(lru),
        in_near & alloc,
        lru,
        budget,
    )
    state = swap_blocks(cfg, state, far_free_ids, near_cold_ids, jnp.minimum(k_d, n_demote))
    # promotion: 2-fault blocks <-> free near
    alloc = allocated_hp_mask(cfg, state)
    in_near = state.block_table < cfg.n_near
    faulting = alloc & ~in_near & (state.host_counts >= 2)
    far_ids, near_ids, k_p = _paired_ids(
        faulting,
        state.host_counts.astype(jnp.int32),
        in_near & ~alloc,
        jnp.zeros_like(lru),
        budget,
    )
    return swap_blocks(cfg, state, far_ids, near_ids, k_p)


register_policy("memtierd", memtierd_tick)
register_policy("autonuma", autonuma_tick)
register_policy("tpp", tpp_tick)


def tick(
    cfg: GpacConfig, state: TieredState, policy: str, tiers=None, **kw
) -> TieredState:
    """Dispatch to a registered host tiering policy by name. ``tiers`` (a
    ``core.tiers.TierVector``) is forwarded only when set, so policies
    registered before the tier subsystem keep their signatures."""
    try:
        fn = _POLICIES[policy]
    except KeyError:
        raise ValueError(
            f"unknown tiering policy {policy!r} (have {policies()})"
        ) from None
    if tiers is not None:
        kw["tiers"] = tiers
    return fn(cfg, state, **kw)


def strided_tick(
    cfg: GpacConfig, state: TieredState, policy: str, *, stride: int,
    budget: int, tiers=None,
) -> TieredState:
    """:func:`tick`, gated by ``EngineSpec.arbitration_stride``: the
    arbitration runs only on windows whose post-window telemetry epoch is a
    multiple of ``stride`` (``(state.epoch + 1) % stride == 0`` at tick
    time, so the gate is chunking- and resume-invariant -- the epoch rides
    the carry). ``stride=1`` is a static branch compiling to exactly
    :func:`tick`, keeping the default path's program unchanged. The skipped
    branch is the identity, so telemetry keeps accumulating across the
    stride and the batched tick arbitrates on the longer history (DESIGN.md
    §17)."""
    if stride == 1:
        return tick(cfg, state, policy, budget=budget, tiers=tiers)
    return jax.lax.cond(
        (state.epoch + 1) % stride == 0,
        lambda s: tick(cfg, s, policy, budget=budget, tiers=tiers),
        lambda s: s,
        state,
    )


# --------------------------------------------------------------------------
# near-memory pressure controller (graceful degradation under churn/shrink)
# --------------------------------------------------------------------------
def pressure_tick(
    cfg: GpacConfig,
    state: TieredState,
    near_cap: jax.Array,  # int32[] effective near capacity (<= n_near)
    engaged: jax.Array,  # bool[]  hysteresis latch carried between windows
    pressure: jax.Array,  # int32[] consecutive engaged windows (backoff signal)
    budget: int = 64,
    slack: int = 1,
    tiers=None,
) -> tuple[TieredState, jax.Array, jax.Array]:
    """Enforce an injected effective near capacity with two watermarks.

    Runs after the policy tick (which only knows the physical ``n_near``):
    when allocated near usage breaches the **high watermark** ``near_cap``
    (a fault-injected shrink, or churn overcommitting the near tier) the
    controller engages and demotes coldest-first -- allocated near blocks
    paired with unallocated far blocks -- down to the **low watermark**
    ``near_cap - slack``, up to ``budget`` blocks per window (TPP's
    ``wmark_demote`` shape: reclaiming past the trigger point by ``slack``
    keeps small fluctuations from re-breaching every window). The previous
    window's ``engaged`` only feeds observability; engagement re-evaluates
    from this window's usage, so a capacity grow-back disengages
    immediately instead of latching into a demote/promote flap against the
    policy tick.

    Returns ``(state, engaged', pressure')``: ``pressure`` counts
    consecutive engaged windows -- the backoff signal the serving layer's
    admission control reads (``serve.scheduler.AdmissionQueue``). It keeps
    growing while demand exceeds the effective capacity: either the policy
    tick re-promotes a working set bigger than ``near_cap`` every window,
    or no free far block exists to demote into (the fleet genuinely
    overcommits the far tier) -- both are exactly the conditions under
    which admission should back off. The controller never promotes and
    never exceeds the physical ``n_near``, so with ``near_cap == n_near``
    (no fault injected) usage can never breach the cap and the whole
    function is a value-exact no-op (INV-CHURN-NOOP-EXACT relies on this).

    With an N-tier ``tiers`` vector the controller becomes a per-tier
    cascade (``core.tiers.pressure_cascade``): every tier enforces its own
    watermark by demoting into the tier below, and the returned
    ``engaged``/``pressure`` track tier 0 (the admission signal).
    """
    del engaged  # previous-window breach: carried for observers, not logic
    if tiers is not None:
        from repro.core import tiers as tiers_mod

        return tiers_mod.pressure_cascade(
            cfg, state, tiers, near_cap, pressure, budget=budget, slack=slack)
    alloc = allocated_hp_mask(cfg, state)
    in_near = state.block_table < cfg.n_near
    usage = (alloc & in_near).sum().astype(jnp.int32)
    low = jnp.maximum(near_cap - slack, 0)
    engaged = usage > near_cap
    n_demote = jnp.where(engaged, jnp.clip(usage - low, 0, budget), 0)
    score = _block_score(cfg, state)
    far_ids, near_ids, k = _paired_ids(
        ~alloc & ~in_near, jnp.zeros_like(score), alloc & in_near, score,
        budget,
    )
    state = swap_blocks(cfg, state, far_ids, near_ids,
                        jnp.minimum(k, n_demote))
    pressure = jnp.where(engaged, pressure + 1, 0).astype(jnp.int32)
    return state, engaged, pressure


# ==========================================================================
# host-partitioned tick (DESIGN.md §11)
#
# Each device holds one contiguous block range of the host state and runs the
# promotion/demotion *scoring* only over it; one global arbitration round per
# window -- a psum'd exchange of per-partition candidate sets plus a few
# scalar sums -- resolves cross-partition near-memory contention bit-for-bit
# against the replicated tick. A sharded tick is a (prepare, apply) pair:
#
#   prepare(cfg, L, budget) -> {"cands": {name: candidate dict}, "sums": {..}}
#       runs pre-collective on the local block range, nominating per-side
#       top-`budget` candidate sets (Nimble-style: placement decisions are
#       local, reconciliation is a small global exchange).
#   apply(cfg, L, merged, budget) -> (block_table', stats_delta, swaps)
#       runs post-collective: arbitrates the merged candidate sets with the
#       exact (score desc, block id asc) order `jax.lax.top_k` would give on
#       the full array, then writes the winning swaps into this device's own
#       block-table rows. `stats_delta` is replicated (the engine adds it on
#       one device only); `swaps` is the arbitrated ((far, near, ok), ...)
#       per-round tuple the collectors use to update per-guest block counts.
#
# `L` is the local-range context: {"hp_ids": int32[H] global block ids (-1
# padded), "hp_lo"/"hp_hi": this device's contiguous range, "bt": local
# block_table rows, "hc"/"hh"/"lt": local host telemetry, "alloc": bool[H]}.
#
# Bit-for-bit argument: the global top-k of any score contains at most k
# entries from one partition, so per-partition top-k nominations cover it;
# `rank_select` then reproduces top_k's tie order exactly because within a
# partition top_k breaks ties by ascending local row == ascending block id,
# and the pairwise rank uses (score desc, id asc) explicitly. Policies with
# two rounds (memtierd, tpp) nominate round-2 candidates pessimistically
# pre-swap; every block whose tier round 1 changed is itself an arbitrated
# candidate, so round-2 masks are recomputed replicated via
# `slots_after_swaps` -- still one collective per window.
# ==========================================================================
def _b(cfg: GpacConfig, budget: int) -> int:
    """Effective per-side budget (matches ``_paired_ids``'s shape clamp)."""
    return min(budget, cfg.n_gpa_hp)


def _check_two_tier(cfg: GpacConfig, tiers) -> None:
    """The builtin sharded ticks arbitrate exactly one near/far pair: they
    accept a tier vector only when it IS the legacy 2-tier split (the
    ``compressed`` policy in ``core.tiers`` handles N > 2 on this path)."""
    if tiers is None or tiers.boundaries == (0, cfg.n_near, cfg.n_slots):
        return
    raise ValueError(
        f"builtin sharded ticks support only the 2-tier split "
        f"(0, {cfg.n_near}, {cfg.n_slots}); got boundaries "
        f"{tiers.boundaries} -- use policy='compressed' or "
        f"host_sharded=False")


def _cand_kw(L: dict) -> dict:
    return dict(
        hp_ids=L["hp_ids"], slot=L["bt"],
        alloc=L["alloc"].astype(jnp.int32), cnt=L["hc"].astype(jnp.int32),
    )


def nominate(
    mask: jax.Array, val: jax.Array, b: int,
    *, hp_ids: jax.Array, slot: jax.Array, alloc: jax.Array, cnt: jax.Array,
) -> dict:
    """Local top-``b`` candidate nomination over this device's block range.

    Returns int32[b] fields: ``val`` (NEG past the valid tail), ``id``
    (global block id, -1 padded), and the per-candidate metadata the
    arbitration needs -- current ``slot``, ``alloc`` bit and raw ``cnt``
    (host_counts). Local rows are in ascending-block-id order, so top_k's
    tie-break by lowest row preserves the replicated tick's id order.
    """
    k = min(b, mask.shape[0])
    v, i = top_k(jnp.where(mask & (hp_ids >= 0), val, NEG), k)
    ok = v > NEG
    out = dict(
        val=v,
        id=jnp.where(ok, hp_ids[i], -1),
        slot=jnp.where(ok, slot[i], 0),
        alloc=jnp.where(ok, alloc[i], 0),
        cnt=jnp.where(ok, cnt[i], 0),
    )
    if k < b:
        fill = dict(val=NEG, id=-1, slot=0, alloc=0, cnt=0)
        out = {
            f: jnp.concatenate(
                [x, jnp.full((b - k,), fill[f], jnp.int32)]
            ) for f, x in out.items()
        }
    return out


def _flat_cands(c: dict) -> dict:
    """Merged candidate blocks ``[n_shards, b]`` -> one flat candidate set."""
    return {f: x.reshape(-1) for f, x in c.items()}


def _concat_cands(a: dict, b: dict) -> dict:
    return {f: jnp.concatenate([a[f], b[f]]) for f in a}


def rank_select(c: dict, b: int) -> dict:
    """Arbitrate a merged candidate set: the top-``b`` by (val desc, id asc).

    Reproduces ``jax.lax.top_k`` over the full per-block array bit-for-bit
    (top_k breaks ties by lowest index == lowest block id) provided the
    candidate ids are unique and the set covers the global top-``b`` --
    which per-partition top-``b`` nominations guarantee. Output slot ``j``
    holds the rank-``j`` candidate; invalid tail is (NEG, -1, 0, 0, 0).
    """
    val, cid = c["val"], c["id"]
    valid = (val > NEG) & (cid >= 0)
    beats = valid[None, :] & (
        (val[None, :] > val[:, None])
        | ((val[None, :] == val[:, None]) & (cid[None, :] < cid[:, None]))
    )
    rank = beats.sum(axis=1)
    pos = jnp.where(valid & (rank < b), rank, b)
    fill = dict(val=NEG, id=-1, slot=0, alloc=0, cnt=0)
    return {
        f: jnp.full((b,), fill[f], jnp.int32).at[pos].set(x, mode="drop")
        for f, x in c.items()
    }


def _pair_k(far: dict, near: dict) -> jax.Array:
    return jnp.minimum((far["id"] >= 0).sum(), (near["id"] >= 0).sum())


def swap_outcome(cfg: GpacConfig, far: dict, near: dict, k: jax.Array):
    """Replicated outcome of one arbitrated swap round: which pairs commit
    (same predicate as :func:`swap_blocks`) and the stats deltas."""
    i = jnp.arange(far["id"].shape[0])
    ok = (
        (i < k)
        & (far["id"] >= 0)
        & (near["id"] >= 0)
        & (far["slot"] >= cfg.n_near)
        & (near["slot"] < cfg.n_near)
    )
    stats = dict(
        promoted_blocks=(ok & (far["alloc"] > 0)).sum().astype(jnp.int32),
        demoted_blocks=(ok & (near["alloc"] > 0)).sum().astype(jnp.int32),
        tlb_shootdowns=(ok.sum() > 0).astype(jnp.int32),
    )
    return ok, stats


def slots_after_swaps(
    ids: jax.Array, slots: jax.Array, far: dict, near: dict, ok: jax.Array
) -> jax.Array:
    """Current slot of each candidate after a committed swap round (the
    replicated slot ledger two-round policies consult for round 2)."""
    fa = jnp.where(ok, far["id"], -2)
    ne = jnp.where(ok, near["id"], -2)
    mf = ids[:, None] == fa[None, :]
    mn = ids[:, None] == ne[None, :]
    out = jnp.where(mf.any(axis=1), (mf * near["slot"][None, :]).sum(axis=1), slots)
    return jnp.where(mn.any(axis=1), (mn * far["slot"][None, :]).sum(axis=1), out)


def apply_swaps_local(
    bt: jax.Array, hp_lo: jax.Array, hp_hi: jax.Array,
    far: dict, near: dict, ok: jax.Array,
) -> jax.Array:
    """Write an arbitrated swap round into this device's block-table rows.

    Only the slot labels move -- in the hp-owned payload layout the data
    already lives with its huge page, so cross-partition migration is free.
    """
    drop = bt.shape[0]

    def upd(bt, ids, new_slot):
        row = jnp.where(ok & (ids >= hp_lo) & (ids < hp_hi), ids - hp_lo, drop)
        return bt.at[row].set(new_slot, mode="drop")

    return upd(upd(bt, far["id"], near["slot"]), near["id"], far["slot"])


# --------------------------------------------------------------------------
# per-policy (prepare, apply) pairs
# --------------------------------------------------------------------------
def _memtierd_prepare(cfg: GpacConfig, L: dict, budget: int, tiers=None) -> dict:
    _check_two_tier(cfg, tiers)
    b = _b(cfg, budget)
    kw = _cand_kw(L)
    valid = L["hp_ids"] >= 0
    score = block_score_arrays(L["hc"], L["hh"])
    alloc, in_near = L["alloc"], L["bt"] < cfg.n_near
    victim = jnp.where(alloc, score, NEG + 1)
    zero = jnp.zeros_like(score)
    return dict(cands=dict(
        hot_far=nominate(valid & alloc & ~in_near & (score > 0), score, b, **kw),
        victim=nominate(valid & in_near, -victim, b, **kw),
        free_far=nominate(valid & ~alloc & ~in_near, zero, b, **kw),
        # round 1 can demote up to b cold blocks out of the near tier, so
        # nominate 2b to keep covering the post-swap global top-b
        cold_near=nominate(valid & alloc & in_near & (score == 0), zero, 2 * b, **kw),
    ), sums=dict())


def _memtierd_apply(
    cfg: GpacConfig, L: dict, merged: dict, budget: int, tiers=None
):
    _check_two_tier(cfg, tiers)
    b = _b(cfg, budget)
    C = {k: _flat_cands(v) for k, v in merged["cands"].items()}
    # round 1: hottest far vs coldest near, only strictly-improving pairs
    far = rank_select(C["hot_far"], b)
    near = rank_select(C["victim"], b)
    gain = jnp.where(
        (far["id"] >= 0) & (near["id"] >= 0), far["val"] > -near["val"], False
    )
    k = jnp.minimum(_pair_k(far, near), gain.astype(jnp.int32).cumprod().sum())
    ok1, d1 = swap_outcome(cfg, far, near, k)
    bt = apply_swaps_local(L["bt"], L["hp_lo"], L["hp_hi"], far, near, ok1)

    # round 2: proactive demotion of cold near blocks into free far blocks,
    # masks recomputed on the post-round-1 placement via the slot ledger
    def after(c):
        return {**c, "slot": slots_after_swaps(c["id"], c["slot"], far, near, ok1)}

    A2 = _concat_cands(after(C["free_far"]), after(near))
    A2 = {**A2, "val": jnp.where(
        (A2["id"] >= 0) & (A2["alloc"] == 0) & (A2["slot"] >= cfg.n_near), 0, NEG
    )}
    cn = after(C["cold_near"])
    B2 = {**cn, "val": jnp.where(
        (cn["id"] >= 0) & (cn["alloc"] > 0) & (cn["slot"] < cfg.n_near), 0, NEG
    )}
    far2 = rank_select(A2, b)
    near2 = rank_select(B2, b)
    ok2, d2 = swap_outcome(cfg, far2, near2, _pair_k(far2, near2))
    bt = apply_swaps_local(bt, L["hp_lo"], L["hp_hi"], far2, near2, ok2)
    return bt, {s: d1[s] + d2[s] for s in d1}, ((far, near, ok1), (far2, near2, ok2))


def _autonuma_prepare(cfg: GpacConfig, L: dict, budget: int, tiers=None) -> dict:
    _check_two_tier(cfg, tiers)
    b = _b(cfg, budget)
    kw = _cand_kw(L)
    valid = L["hp_ids"] >= 0
    alloc, in_near = L["alloc"], L["bt"] < cfg.n_near
    cnt = L["hc"].astype(jnp.int32)
    victim = jnp.where(alloc, L["lt"].astype(jnp.int32), NEG + 1)
    return dict(cands=dict(
        fault=nominate(valid & alloc & ~in_near & (cnt >= 2), cnt, b, **kw),
        # nominate under the pressured superset mask (free-near victims sort
        # first either way); `apply` re-filters once `pressured` is known
        victim=nominate(valid & in_near, -victim, b, **kw),
    ), sums=dict(near_used=(valid & alloc & in_near).sum()))


def _autonuma_apply(
    cfg: GpacConfig, L: dict, merged: dict, budget: int,
    pressure: float = 0.95, tiers=None,
):
    _check_two_tier(cfg, tiers)
    b = _b(cfg, budget)
    C = {k: _flat_cands(v) for k, v in merged["cands"].items()}
    pressured = merged["sums"]["near_used"] >= jnp.int32(pressure * cfg.n_near)
    far = rank_select(C["fault"], b)
    vic = C["victim"]
    vv = jnp.where(
        (vic["id"] >= 0) & ((vic["alloc"] == 0) | pressured), vic["val"], NEG
    )
    near = rank_select({**vic, "val": vv}, b)
    ok, d = swap_outcome(cfg, far, near, _pair_k(far, near))
    bt = apply_swaps_local(L["bt"], L["hp_lo"], L["hp_hi"], far, near, ok)
    return bt, d, ((far, near, ok),)


def _tpp_prepare(cfg: GpacConfig, L: dict, budget: int, tiers=None) -> dict:
    _check_two_tier(cfg, tiers)
    b = _b(cfg, budget)
    kw = _cand_kw(L)
    valid = L["hp_ids"] >= 0
    alloc, in_near = L["alloc"], L["bt"] < cfg.n_near
    cnt = L["hc"].astype(jnp.int32)
    lru = L["lt"].astype(jnp.int32)
    zero = jnp.zeros_like(cnt)
    return dict(cands=dict(
        free_far=nominate(valid & ~in_near & ~alloc, zero, b, **kw),
        near_lru=nominate(valid & in_near & alloc, -lru, b, **kw),
        fault=nominate(valid & alloc & ~in_near & (cnt >= 2), cnt, b, **kw),
        free_near=nominate(valid & in_near & ~alloc, zero, b, **kw),
    ), sums=dict(
        free_near=(valid & in_near & ~alloc).sum(),
        demand=(valid & alloc & ~in_near & (cnt >= 2)).sum(),
    ))


def _tpp_apply(
    cfg: GpacConfig, L: dict, merged: dict, budget: int,
    watermark: float = 0.1, tiers=None,
):
    _check_two_tier(cfg, tiers)
    b = _b(cfg, budget)
    C = {k: _flat_cands(v) for k, v in merged["cands"].items()}
    want_free = jnp.int32(watermark * cfg.n_near)
    demand = merged["sums"]["demand"]
    need = jnp.maximum(jnp.minimum(want_free, demand),
                       jnp.minimum(demand, budget))
    n_demote = jnp.clip(need - merged["sums"]["free_near"], 0, budget)
    # round 1: watermark demotion (coldest allocated near <-> free far)
    farD = rank_select(C["free_far"], b)
    nearD = rank_select(C["near_lru"], b)
    ok1, d1 = swap_outcome(
        cfg, farD, nearD, jnp.minimum(_pair_k(farD, nearD), n_demote)
    )
    bt = apply_swaps_local(L["bt"], L["hp_lo"], L["hp_hi"], farD, nearD, ok1)

    # round 2: fault promotion into the freed space (post-swap masks)
    def after(c):
        return {**c, "slot": slots_after_swaps(c["id"], c["slot"], farD, nearD, ok1)}

    A2 = _concat_cands(after(C["fault"]), after(nearD))
    A2 = {**A2, "val": jnp.where(
        (A2["id"] >= 0) & (A2["alloc"] > 0) & (A2["slot"] >= cfg.n_near)
        & (A2["cnt"] >= 2), A2["cnt"], NEG
    )}
    B2 = _concat_cands(after(C["free_near"]), after(farD))
    B2 = {**B2, "val": jnp.where(
        (B2["id"] >= 0) & (B2["alloc"] == 0) & (B2["slot"] < cfg.n_near), 0, NEG
    )}
    far2 = rank_select(A2, b)
    near2 = rank_select(B2, b)
    ok2, d2 = swap_outcome(cfg, far2, near2, _pair_k(far2, near2))
    bt = apply_swaps_local(bt, L["hp_lo"], L["hp_hi"], far2, near2, ok2)
    return bt, {s: d1[s] + d2[s] for s in d1}, ((farD, nearD, ok1), (far2, near2, ok2))


_SHARDED_TICKS: dict[str, tuple[Callable, Callable]] = {}


def register_sharded_tick(name: str, prepare: Callable, apply: Callable):
    """Register a host-partitioned (prepare, apply) tick for policy ``name``
    (see the section comment above for the contract). Policies without one
    still run everywhere except ``engine.run_sharded(host_sharded=True)``."""
    if name in _SHARDED_TICKS:
        raise ValueError(f"sharded tick for policy {name!r} already registered")
    _SHARDED_TICKS[name] = (prepare, apply)


def sharded_ticks() -> tuple[str, ...]:
    """Names of policies with a host-partitioned tick."""
    return tuple(_SHARDED_TICKS)


def sharded_tick_fns(name: str) -> tuple[Callable, Callable]:
    try:
        return _SHARDED_TICKS[name]
    except KeyError:
        raise ValueError(
            f"tiering policy {name!r} has no host-partitioned tick (have "
            f"{sharded_ticks()}); register one with tiering."
            f"register_sharded_tick or run with host_sharded=False"
        ) from None


register_sharded_tick("memtierd", _memtierd_prepare, _memtierd_apply)
register_sharded_tick("autonuma", _autonuma_prepare, _autonuma_apply)
register_sharded_tick("tpp", _tpp_prepare, _tpp_apply)
