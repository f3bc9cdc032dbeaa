"""Static configuration and pytree state types for the GPAC tiered-memory core.

Terminology maps 1:1 onto the paper (see DESIGN.md §2):

* logical page  == guest virtual (GVA) base page      -- what the workload addresses
* gpa page      == guest physical (GPA) base page     -- slot in the guest's paged space
* huge page     == ``hp_ratio`` contiguous gpa pages  -- the host's placement granule
* host slot     == physical block location; slots ``< n_near`` live in the near
  tier (HBM / DRAM), the rest in the far tier (host DRAM / CXL / NVMM).

Everything traced is fixed-shape; all state is a registered dataclass pytree so
the whole tiering state machine jits and shards.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

FREE = jnp.int32(-1)  # sentinel for unallocated rmap / owner entries


def static_field(**kw):
    return dataclasses.field(metadata=dict(static=True), **kw)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=(),
    meta_fields=(
        "n_logical",
        "hp_ratio",
        "n_gpa_hp",
        "n_near",
        "base_elems",
        "hot_threshold",
        "cl",
        "ipt_windows",
        "ipt_min_hits",
        "reconsolidate_cooldown",
        "dtype",
    ),
)
@dataclasses.dataclass(frozen=True)
class GpacConfig:
    """Static geometry + policy knobs of one guest's tiered address space.

    Defaults follow the paper: 4 KB base pages inside 2 MB huge pages gives
    ``hp_ratio=512``; ``cl`` is the paper's Consolidation Limit.
    """

    n_logical: int  # logical (GVA) base pages addressable by the workload
    hp_ratio: int = 512  # base pages per huge page (2 MB / 4 KB)
    n_gpa_hp: int = 0  # GPA huge pages (0 -> derived with 25% slack)
    n_near: int = 0  # near-tier blocks (0 -> half of n_gpa_hp)
    base_elems: int = 8  # payload elements per base page (simulation granularity)
    hot_threshold: int = 1  # accesses/window for a page to count as hot
    cl: int = 64  # Consolidation Limit (paper §4.3.1)
    ipt_windows: int = 8  # history depth of the IPT-like bit telemetry
    ipt_min_hits: int = 1  # windows-with-access required for hotness
    reconsolidate_cooldown: int = 2  # epochs a fresh region is filter-exempt
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.n_logical < 1:
            raise ValueError(f"n_logical must be >= 1, got {self.n_logical}")
        if self.hp_ratio < 1:
            raise ValueError(f"hp_ratio must be >= 1, got {self.hp_ratio}")
        need = -(-self.n_logical // self.hp_ratio)  # ceil
        if self.n_gpa_hp == 0:
            object.__setattr__(self, "n_gpa_hp", need + max(2, need // 4))
        if self.n_near == 0:
            object.__setattr__(self, "n_near", max(1, self.n_gpa_hp // 2))
        if self.n_gpa_hp * self.hp_ratio < self.n_logical:
            raise ValueError(
                f"GPA space smaller than logical space: n_gpa_hp={self.n_gpa_hp}"
                f" x hp_ratio={self.hp_ratio} = {self.n_gpa_hp * self.hp_ratio}"
                f" gpa pages cannot cover n_logical={self.n_logical}"
            )
        if not (0 < self.n_near < self.n_gpa_hp):
            raise ValueError(
                f"need 0 < n_near < n_gpa_hp (a non-empty far tier), got "
                f"n_near={self.n_near}, n_gpa_hp={self.n_gpa_hp}"
            )
        if not (1 <= self.cl <= self.hp_ratio):
            raise ValueError(
                f"Consolidation Limit must be in [1, hp_ratio={self.hp_ratio}]"
                f", got cl={self.cl}"
            )

    # ---- derived sizes -------------------------------------------------
    @property
    def n_gpa(self) -> int:
        return self.n_gpa_hp * self.hp_ratio

    @property
    def n_far(self) -> int:
        return self.n_gpa_hp - self.n_near

    @property
    def n_slots(self) -> int:
        return self.n_gpa_hp  # block_table is a permutation of slots

    @property
    def base_bytes(self) -> int:
        return self.base_elems * jnp.dtype(self.dtype).itemsize

    @property
    def hp_bytes(self) -> int:
        return self.base_bytes * self.hp_ratio


@partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "gpt",
        "rmap",
        "block_table",
        "slot_owner",
        "near_pool",
        "far_pool",
        "guest_counts",
        "ipt_hist",
        "host_counts",
        "host_hist",
        "last_touch_epoch",
        "region_epoch",
        "epoch",
        "stats",
    ),
    meta_fields=(),
)
@dataclasses.dataclass
class TieredState:
    """One guest's full two-level address-space + host-placement state.

    Invariants (enforced by tests/test_core_invariants.py):
      * ``gpt`` restricted to allocated logical pages is injective and
        ``rmap[gpt[l]] == l``; unallocated gpa pages have ``rmap == FREE``.
      * ``block_table`` is a permutation of ``[0, n_slots)`` and
        ``slot_owner[block_table[hp]] == hp``.
      * data read through the logical view is preserved by consolidation and
        by tier migrations (both only move bytes + rewrite mappings).
    """

    # guest level -------------------------------------------------------
    gpt: jax.Array  # int32[n_logical]  logical -> gpa page
    rmap: jax.Array  # int32[n_gpa]      gpa page -> logical | FREE
    # host level --------------------------------------------------------
    block_table: jax.Array  # int32[n_gpa_hp]  huge page -> slot (permutation)
    slot_owner: jax.Array  # int32[n_slots]   slot -> huge page (inverse)
    near_pool: jax.Array  # dtype[n_near, hp_ratio, base_elems]
    far_pool: jax.Array  # dtype[n_far,  hp_ratio, base_elems]
    # guest telemetry (base-page granularity; the host never reads these)
    guest_counts: jax.Array  # int32[n_logical] accesses this window
    ipt_hist: jax.Array  # uint8[n_logical] per-window accessed-bit history
    # host telemetry (huge-page granularity only -- the information asymmetry)
    host_counts: jax.Array  # int32[n_gpa_hp] accesses this window (EWMA'd by policies)
    host_hist: jax.Array  # uint8[n_gpa_hp] per-window accessed-bit history
    last_touch_epoch: jax.Array  # int32[n_gpa_hp] for LRU-style policies
    # consolidation bookkeeping ------------------------------------------
    region_epoch: jax.Array  # int32[n_gpa_hp] epoch a region was consolidated (-1 never)
    epoch: jax.Array  # int32[] telemetry window counter
    stats: dict  # running counters (see init_state)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "gpt",
        "rmap",
        "guest_counts",
        "ipt_hist",
        "block_table",
        "host_counts",
        "host_hist",
        "last_touch_epoch",
        "region_epoch",
        "payload",
        "epoch",
        "stats",
    ),
    meta_fields=(),
)
@dataclasses.dataclass
class PartitionedState:
    """The host-partitioned engine's state, kept partitioned over a 1-D
    ``"guest"`` mesh between calls (``engine.run_sharded``, DESIGN.md §11).

    Every array's leading axis is ``n_shards`` equal blocks, one per device
    (``repro.core.sharding.HostPartition`` gives their sizes); device ``d``
    holds only its own guests. Guest-major arrays are in the device's own
    coordinates: logical page ``l_lo[d] + i`` is row ``i`` and ``gpt`` holds
    GPA pages less ``hp_lo[d] * hp_ratio`` (``rmap`` the inverse), ``-1`` /
    ``FREE`` in padding rows. Host arrays hold the device's block range
    ``[hp_lo[d], hp_hi[d])``; ``block_table`` keeps global slot ids (the
    near tier is shared), ``n_gpa_hp`` in padding rows, and ``payload`` row
    ``h - hp_lo[d]`` holds huge page ``h``'s bytes, which equal the
    replicated ``pools[block_table[h]]``. There is no ``slot_owner``: it is
    the block table's inverse, derived where it is needed. ``epoch`` holds
    the same value on every device; ``stats`` holds each device's share,
    summing to the replicated counters.
    """

    gpt: jax.Array  # int32[n_shards * l_loc]
    rmap: jax.Array  # int32[n_shards * h_loc * hp_ratio]
    guest_counts: jax.Array  # int32[n_shards * l_loc]
    ipt_hist: jax.Array  # uint8[n_shards * l_loc]
    block_table: jax.Array  # int32[n_shards * h_loc]
    host_counts: jax.Array  # int32[n_shards * h_loc]
    host_hist: jax.Array  # uint8[n_shards * h_loc]
    last_touch_epoch: jax.Array  # int32[n_shards * h_loc]
    region_epoch: jax.Array  # int32[n_shards * h_loc]
    payload: jax.Array  # dtype[n_shards * h_loc, hp_ratio, base_elems]
    epoch: jax.Array  # int32[n_shards]
    stats: dict  # int32[n_shards] each


# the running int32 counters of ``TieredState.stats``
STATS = (
    "consolidated_pages",
    "consolidation_calls",
    "consolidation_enomem",
    "copied_bytes",
    "promoted_blocks",
    "demoted_blocks",
    "near_hits",
    "far_hits",
    "tlb_shootdowns",
)


def init_state(cfg: GpacConfig, fill: jax.Array | None = None) -> TieredState:
    """Fresh identity-mapped state.

    Logical page ``l`` starts at gpa page ``l``; huge page ``h`` starts at
    slot ``h`` (so huge pages ``< n_near`` begin in the near tier, the rest
    far -- benchmarks that model "start everything in far memory" permute
    this, see :func:`start_all_far`).

    ``fill``: optional dtype[n_logical, base_elems] initial payload.
    """
    gpt = jnp.arange(cfg.n_logical, dtype=jnp.int32)
    rmap = jnp.full((cfg.n_gpa,), FREE, dtype=jnp.int32)
    rmap = rmap.at[: cfg.n_logical].set(jnp.arange(cfg.n_logical, dtype=jnp.int32))
    block_table = jnp.arange(cfg.n_gpa_hp, dtype=jnp.int32)
    slot_owner = jnp.arange(cfg.n_slots, dtype=jnp.int32)
    near = jnp.zeros((cfg.n_near, cfg.hp_ratio, cfg.base_elems), cfg.dtype)
    far = jnp.zeros((cfg.n_far, cfg.hp_ratio, cfg.base_elems), cfg.dtype)
    state = TieredState(
        gpt=gpt,
        rmap=rmap,
        block_table=block_table,
        slot_owner=slot_owner,
        near_pool=near,
        far_pool=far,
        guest_counts=jnp.zeros((cfg.n_logical,), jnp.int32),
        ipt_hist=jnp.zeros((cfg.n_logical,), jnp.uint8),
        host_counts=jnp.zeros((cfg.n_gpa_hp,), jnp.int32),
        host_hist=jnp.zeros((cfg.n_gpa_hp,), jnp.uint8),
        last_touch_epoch=jnp.zeros((cfg.n_gpa_hp,), jnp.int32),
        region_epoch=jnp.full((cfg.n_gpa_hp,), -1, jnp.int32),
        epoch=jnp.zeros((), jnp.int32),
        stats={k: jnp.zeros((), jnp.int32) for k in STATS},
    )
    if fill is not None:
        from repro.core import address_space as asp

        state = asp.write_logical(
            cfg, state, jnp.arange(cfg.n_logical, dtype=jnp.int32), fill
        )
    return state


def start_all_far(cfg: GpacConfig, state: TieredState) -> TieredState:
    """Re-home every *allocated* huge page to the far tier (paper §5.2 starts
    guests with far memory preferred). Implemented as block-table swaps so all
    invariants hold; data moves with the blocks."""
    from repro.core import tiering

    # Demote allocated huge pages currently in near, swapping with unallocated
    # huge pages currently in far (which hold no data).
    hp_alloc = allocated_hp_mask(cfg, state)
    in_near = state.block_table < cfg.n_near
    demote = hp_alloc & in_near
    victim = (~hp_alloc) & (~in_near)
    n = min(cfg.n_near, cfg.n_far)
    d_idx = jnp.nonzero(demote, size=n, fill_value=-1)[0].astype(jnp.int32)
    v_idx = jnp.nonzero(victim, size=n, fill_value=-1)[0].astype(jnp.int32)
    k = jnp.minimum((d_idx >= 0).sum(), (v_idx >= 0).sum())
    return tiering.swap_blocks(cfg, state, v_idx, d_idx, k)


def allocated_hp_mask(cfg: GpacConfig, state: TieredState) -> jax.Array:
    """bool[n_gpa_hp] -- huge page contains >=1 allocated base page."""
    return (state.rmap.reshape(cfg.n_gpa_hp, cfg.hp_ratio) != FREE).any(axis=1)
