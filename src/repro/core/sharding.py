"""Guest-axis device sharding for the unified engine driver (DESIGN.md §9).

The batched engine's guest axis is embarrassingly parallel: the
:class:`repro.core.engine.EngineSpec` segment-offset tables give every guest
disjoint logical and GPA segments, so the padded per-guest matrices (access
batches, ragged filter top-k rows, consolidation rounds, metric rows) shard
cleanly over a 1-D ``"guest"`` mesh axis via ``shard_map``. The shared host
state stays **replicated**; per-window phases alternate between sharded and
replicated computation:

1. **access phase** (sharded): each device translates and histograms its own
   guests' accesses and applies the histogram *locally* (guest g's counts,
   huge-page counts and touch epochs all live inside g's own segments).
2. **GPAC phase** (sharded): each device runs the filter top-k and the
   round-major Algorithm-1 consolidation only for its own guests' segment
   rows (``gpac.gpac_maintenance_rows``) on its local state copy. Both
   phases diverge *only inside that device's own segments*: hot masks,
   candidate scores, region allocation and the data copy never read another
   guest's telemetry or mappings.
3. **merge** (one collective): the diverged arrays are recombined by
   ownership: every logical page / GPA page / huge page / host slot is
   owned by exactly one guest, hence written by exactly one device, so
   ``psum(where(own, local, 0))`` reconstructs each array exactly (integer
   sums with one non-zero contributor). Payload pools are combined in their
   *bit patterns* (``bitcast``) so the merge is bit-exact for every dtype.
   Per-guest hit vectors ride in the same psum -- cross-device sync points
   dominate the sharded overhead on CPU meshes, so each window performs
   exactly **one** collective.
4. **host tick** (replicated): the merged state is identical on all devices,
   so the shared near-tier arbitration (``tiering.tick``: global top-k over
   block scores) runs replicated and deterministically -- the paper's single
   host daemon, not N partitioned ones.

Guest counts that do not divide the mesh are padded with empty segment rows
(all ``-1``): padded rows translate nothing, select nothing, allocate
nothing, and own nothing, so they are end-to-end no-ops.

Everything degrades to a no-op without a mesh, as ``repro.models.dist.Dist``
does: :func:`guest_mesh` returns ``None`` on a single-device host and
``engine.run_sharded`` falls back to ``engine.run``.

The second half of this module is the **host-partitioned near tier**
(DESIGN.md §11, ``engine.run_sharded(host_sharded=True)``): instead of
replicating the host state, each device carries only its own contiguous
block range (:class:`HostPartition`) with the payload stored per huge page,
scores promotion/demotion locally, and one arbitration exchange per window
(``repro.core.tiering``'s sharded ticks) resolves cross-partition
contention bit-for-bit against the replicated tick -- per-device host-state
bytes scale ~1/n_devices.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import address_space as asp
from repro.core import faults as faults_mod
from repro.core import gpac, telemetry, tiering
from repro.core import tiers as tiers_mod
from repro.core.scopes import layer
from repro.core.types import FREE, GpacConfig, TieredState

AXIS = "guest"


# --------------------------------------------------------------------------
# collective-volume accounting (DESIGN.md §17)
# --------------------------------------------------------------------------
# Per-site psum payload bytes, recorded as a plain-Python side effect while
# the chunk function is *traced* -- tracer shapes/dtypes are concrete, so
# the numbers are the exact per-call payloads of the compiled program.
# Sizes persist until the next reset; a fully cache-hit rerun does not
# retrace and therefore leaves previously recorded sites in place, so reset
# before the run whose volume you want to attribute.
_COLLECTIVE_BYTES: dict[str, int] = {}


def _psum_counted(site: str, tree):
    """``jax.lax.psum`` plus trace-time byte accounting of the payload."""
    _COLLECTIVE_BYTES[site] = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
    )
    return jax.lax.psum(tree, AXIS)


def reset_collective_bytes() -> None:
    """Clear the per-site psum payload record (call before the run)."""
    _COLLECTIVE_BYTES.clear()


def collective_bytes() -> dict[str, int]:
    """Per-site psum payload bytes from the most recent trace.

    Sites: ``merge_window`` (replicated-host paths, one psum per window;
    the only collective the churn driver issues), ``host_exchange`` (the
    host-partitioned arbitration exchange, one psum per stride group) and
    ``host_chunk_exit`` (host-partitioned chunk-boundary reconstruction,
    one psum per chunk).
    """
    return dict(_COLLECTIVE_BYTES)


# --------------------------------------------------------------------------
# mesh + padding helpers
# --------------------------------------------------------------------------
def guest_mesh(n_devices: int | None = None):
    """1-D mesh over ``n_devices`` devices along the ``"guest"`` axis.

    ``n_devices=None`` uses every device and returns ``None`` when only one
    is available (the no-mesh degradation: callers fall back to the unsharded
    driver). Pass an explicit count to force a mesh -- including a 1-device
    mesh, which exercises the full shard_map path.

    In a multi-process job (``launch.multihost.initialize``,
    ``jax.process_count() > 1``) the mesh spans every process's devices and
    must cover all of them: a partial mesh would leave some processes holding
    no shard of the SPMD program, so any ``n_devices`` below the global count
    is rejected.
    """
    avail = jax.device_count()
    multiproc = jax.process_count() > 1
    if n_devices is None:
        if avail == 1:
            return None
        n_devices = avail
    if n_devices > avail:
        raise ValueError(
            f"guest_mesh: asked for {n_devices} devices, have {avail}"
        )
    if multiproc and n_devices != avail:
        raise ValueError(
            f"guest_mesh: a multi-process mesh must span all "
            f"{avail} global devices ({jax.process_count()} processes), "
            f"got n_devices={n_devices}"
        )
    return jax.make_mesh(
        (n_devices,), (AXIS,), axis_types=(jax.sharding.AxisType.Auto,))


def mesh_size(mesh) -> int:
    return mesh.shape[AXIS]


def padded_guest_count(n_guests: int, n_shards: int) -> int:
    """Smallest multiple of ``n_shards`` >= ``n_guests``."""
    return -(-n_guests // n_shards) * n_shards


def pad_guest_rows(rows: np.ndarray, n_shards: int, fill=-1) -> np.ndarray:
    """Pad a per-guest matrix ``[n_guests, ...]`` with ``fill`` rows up to a
    multiple of ``n_shards`` (empty segments: -1 everywhere is a no-op row
    through the whole engine)."""
    n_g = rows.shape[0]
    g_pad = padded_guest_count(n_g, n_shards)
    if g_pad == n_g:
        return rows
    pad = np.full((g_pad - n_g, *rows.shape[1:]), fill, rows.dtype)
    return np.concatenate([rows, pad], axis=0)


def guest_tables(spec, n_shards: int) -> dict[str, np.ndarray]:
    """The spec's per-guest segment tables, padded to the mesh: trace-time
    numpy constants that enter the shard-mapped driver as ``P("guest", ...)``
    sharded arrays (each device sees only its own guests' rows)."""
    return dict(
        logical_lo=pad_guest_rows(
            np.asarray(spec.logical_offsets[:-1], np.int32), n_shards, fill=0
        ),
        logical_pad=pad_guest_rows(spec.logical_pad_index(), n_shards),
        hp_pad=pad_guest_rows(spec.hp_pad_index(), n_shards),
    )


# --------------------------------------------------------------------------
# bit-exact ownership merge
# --------------------------------------------------------------------------
def _own_mask(idx_rows: jax.Array, n: int) -> jax.Array:
    """bool[n]: ids covered by these (padded, -1 filled) segment-table rows."""
    flat = idx_rows.reshape(-1)
    safe = jnp.where(flat >= 0, flat, n)
    return jnp.zeros((n + 1,), bool).at[safe].set(True, mode="drop")[:n]


def _owned_bits(x: jax.Array, own: jax.Array) -> jax.Array:
    """This device's contribution to the bit-exact combine: the owned
    elements' *bit patterns*, 0 elsewhere. Summed across devices, every
    element has exactly one non-zero contributor, so the (integer) psum *is*
    that contributor's bit pattern -- no float rounding, -0.0 survives.
    4-byte dtypes view as int32 directly; anything else goes through the
    uint8 view (one trailing byte axis)."""
    if jnp.issubdtype(x.dtype, jnp.integer) and x.dtype.itemsize <= 4:
        return jnp.where(own, x, 0)
    if x.dtype.itemsize == 4:
        return jnp.where(own, jax.lax.bitcast_convert_type(x, jnp.int32), 0)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint8)  # [..., itemsize]
    return jnp.where(own[..., None], bits, 0)


def _from_bits(bits: jax.Array, like: jax.Array) -> jax.Array:
    if bits.dtype == like.dtype:
        return bits
    return jax.lax.bitcast_convert_type(bits, like.dtype)


def merge_window(
    cfg: GpacConfig,
    base: TieredState,  # replicated pre-window state
    local: TieredState,  # after this device's local access + GPAC phases
    logical_pad: jax.Array,  # int32[G_loc, max_logical] local segment rows
    hp_pad: jax.Array,  # int32[G_loc, max_hp] local segment rows
    extras: tuple[jax.Array, ...],  # per-guest vectors riding the collective
    merged_gpac: bool,
) -> tuple[TieredState, tuple[jax.Array, ...]]:
    """Recombine per-device window phases into one replicated state with a
    **single** psum.

    The access phase writes ``guest_counts`` / ``host_counts`` /
    ``last_touch_epoch``; the GPAC phase writes ``gpt`` / ``rmap`` /
    ``region_epoch`` / the payload pools; both bump ``stats``. Each array is
    recombined by static segment ownership (logical pages, GPA pages, huge
    pages) or dynamic slot ownership (``slot_owner`` is unchanged during
    both phases, so slot ``s`` belongs to the guest owning huge page
    ``slot_owner[s]``). Stats are int32 counters: replicated base + psum of
    per-device deltas is exact. ``merged_gpac=False`` (GPAC off) skips the
    mapping/pool arrays entirely -- they equal ``base``.
    """
    own_logical = _own_mask(logical_pad, cfg.n_logical)
    own_hp = _own_mask(hp_pad, cfg.n_gpa_hp)
    contrib = dict(
        guest_counts=_owned_bits(local.guest_counts, own_logical),
        host_counts=_owned_bits(local.host_counts, own_hp),
        last_touch_epoch=_owned_bits(local.last_touch_epoch, own_hp),
        stats={k: local.stats[k] - base.stats[k] for k in base.stats},
        extras=extras,
    )
    if merged_gpac:
        own_gpa = jnp.repeat(own_hp, cfg.hp_ratio)
        own_slot = own_hp[base.slot_owner]  # slot -> owning hp -> owned here?
        contrib.update(
            gpt=_owned_bits(local.gpt, own_logical),
            rmap=_owned_bits(local.rmap, own_gpa),
            region_epoch=_owned_bits(local.region_epoch, own_hp),
            near_pool=_owned_bits(
                local.near_pool, own_slot[: cfg.n_near][:, None, None]
            ),
            far_pool=_owned_bits(
                local.far_pool, own_slot[cfg.n_near :][:, None, None]
            ),
        )
    merged = _psum_counted("merge_window", contrib)
    state = dataclasses.replace(
        base,
        guest_counts=merged["guest_counts"],
        host_counts=merged["host_counts"],
        last_touch_epoch=merged["last_touch_epoch"],
        stats={k: base.stats[k] + merged["stats"][k] for k in base.stats},
    )
    if merged_gpac:
        state = dataclasses.replace(
            state,
            gpt=merged["gpt"],
            rmap=merged["rmap"],
            region_epoch=merged["region_epoch"],
            near_pool=_from_bits(merged["near_pool"], base.near_pool),
            far_pool=_from_bits(merged["far_pool"], base.far_pool),
        )
    return state, merged["extras"]


# --------------------------------------------------------------------------
# the shard-mapped window body
# --------------------------------------------------------------------------
def _spread_rows(x_loc: jax.Array, n_shards: int) -> jax.Array:
    """Place this device's per-local-guest row vector at its global guest
    positions in a zero ``[G_pad]`` vector: rows are contiguous per device,
    so summed across devices (inside an existing psum) this reconstructs the
    full per-guest vector without a separate all-gather."""
    g_loc = x_loc.shape[0]
    pos = jax.lax.axis_index(AXIS) * g_loc + jnp.arange(g_loc)
    return jnp.zeros((g_loc * n_shards,), x_loc.dtype).at[pos].set(x_loc)


def _sharded_window(
    spec,  # repro.core.engine.EngineSpec (static)
    n_shards: int,
    state: TieredState,  # replicated
    accesses: jax.Array,  # int32[G_loc, k] guest-local ids of local guests
    logical_lo: jax.Array,  # int32[G_loc]
    logical_pad: jax.Array,  # int32[G_loc, max_logical]
    hp_pad: jax.Array,  # int32[G_loc, max_hp]
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
) -> tuple[TieredState, dict]:
    """One engine window on one device: sharded access + GPAC phases around
    the replicated host tick (see the module docstring for the phase plan).
    Bit-for-bit equal to ``engine._window`` on the unpadded guests.

    Collective budget: cross-device sync points dominate the sharded
    overhead (every psum is a device rendezvous), so both sharded phases run
    on the device's *local* state copy -- a guest's telemetry, hot mask,
    candidate scores and consolidation regions all live inside its own
    segments, so the local copy agrees with the would-be merged state
    everywhere the GPAC phase reads it -- and a **single** psum per window
    (:func:`merge_window`) recombines everything, per-guest hit vectors
    included.
    """
    from repro.core.engine import run_collectors

    cfg = spec.cfg
    base = state
    # ---- 1. access phase (sharded, applied locally) ----------------------
    with layer("tier.translate"):
        ids = jnp.where(accesses >= 0, accesses + logical_lo[:, None], -1)
        slot, _, valid = asp.translate(cfg, state, ids)
        near_loc = (valid & (slot < cfg.n_near)).sum(axis=1)
        far_loc = (valid & (slot >= cfg.n_near)).sum(axis=1)
    kb = spec.kernel_backend
    with layer("tier.record"):
        local = asp.apply_access_histogram(
            cfg, state, asp.access_histogram(cfg, ids, valid, kb), kb
        )
    # ---- 2. GPAC phase (sharded: this device's segment rows only) --------
    if use_gpac:
        with layer("tier.score"):
            cl_per_logical = jnp.asarray(spec.cl_per_logical())
        local = gpac.gpac_maintenance_rows(
            cfg, local, backend, max_batches, cl_per_logical, logical_pad,
            hp_pad, kb,
        )
    # ---- 3. one-collective ownership merge -------------------------------
    extras = [
        _spread_rows(near_loc, n_shards), _spread_rows(far_loc, n_shards),
    ]
    if "tco" in collect:
        # local per-tier hit vector; the psum of int counts reproduces the
        # replicated tier_hit_counts exactly
        with layer("tier.roll"):
            extras.append(
                tiers_mod.tier_hit_counts(spec.tier_vector, slot, valid))
    state, merged_extras = merge_window(
        cfg, base, local, logical_pad, hp_pad, tuple(extras),
        merged_gpac=use_gpac,
    )
    near_all, far_all = merged_extras[0], merged_extras[1]
    # ---- 4. host tick + window roll (replicated) ------------------------
    with layer("tier.tick"):
        state = tiering.strided_tick(
            cfg, state, policy, stride=spec.arbitration_stride, budget=budget,
            tiers=spec.tiers,
        )
    with layer("tier.roll"):
        state = telemetry.end_window(cfg, state)
        window = dict(
            near_hits=near_all[: spec.n_guests],
            far_hits=far_all[: spec.n_guests],
        )
        if "tco" in collect:
            window["tier_hits"] = merged_extras[2]
        return state, run_collectors(spec, state, window, collect)


# per-guest synthesis-table keys, in the order the chunk drivers append
# them as trailing (guest-sharded) arguments
_SYNTH_KEYS = ("seeds", "gids", "wid", "n_logical")


def _synth_args(synth_tables: dict) -> tuple:
    return tuple(jnp.asarray(synth_tables[k]) for k in _SYNTH_KEYS)


@lru_cache(maxsize=64)
def _chunk_fn(
    spec,  # canonical EngineSpec
    mesh,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
    plan=None,  # repro.data.traces.SynthPlan for on-device synthesis
):
    """Compiled sharded chunk driver for one (spec, mesh, knobs) key: a
    ``shard_map`` over the scan of windows. State and series are replicated
    out-specs; the traces and segment tables shard over the guest axis.

    With a ``plan`` the scan carries absolute window *indices* (replicated)
    instead of trace slices, and each device synthesizes only its own
    guests' accesses inside the window body from its sharded table rows --
    per-device trace residency is O(local guests x accesses_per_window).
    Per-guest RNG keys fold in the *global* guest id, so the generated
    streams are bit-identical to the unsharded driver's.
    """
    n_shards = mesh_size(mesh)

    def window_body(st, acc, logical_lo, logical_pad, hp_pad):
        return _sharded_window(
            spec, n_shards, st, acc, logical_lo, logical_pad, hp_pad,
            policy, backend, use_gpac, max_batches, budget, collect,
        )

    if plan is None:

        def body(state, chunk, logical_lo, logical_pad, hp_pad):
            def window(st, acc):
                return window_body(st, acc, logical_lo, logical_pad, hp_pad)

            return jax.lax.scan(window, state, chunk)

        in_specs = (
            P(), P(None, AXIS, None), P(AXIS), P(AXIS, None), P(AXIS, None),
        )
    else:
        from repro.data import traces as tr

        def body(state, widx, logical_lo, logical_pad, hp_pad,
                 seeds, gids, wid, n_logical):
            setup = tr.synth_setup(plan, dict(
                seeds=seeds, gids=gids, wid=wid, n_logical=n_logical))

            def window(st, w):
                acc = tr.synth_accesses(plan, setup, w)
                return window_body(st, acc, logical_lo, logical_pad, hp_pad)

            return jax.lax.scan(window, state, widx)

        in_specs = (
            P(), P(None), P(AXIS), P(AXIS, None), P(AXIS, None),
            P(AXIS), P(AXIS), P(AXIS), P(AXIS),
        )

    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(),
        # psum results are replicated but the replication check cannot
        # always infer it; correctness is pinned by the equivalence tests
        check_vma=False,
    )
    return jax.jit(sharded)


def run_chunk_sharded(
    spec,
    mesh,
    state: TieredState,
    chunk: jax.Array,  # int32[n_windows, G_pad, k], or int32[n_windows]
    tables: dict,      # window indices when plan is given
    *,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
    plan=None,
    synth_tables: dict | None = None,
) -> tuple[TieredState, dict]:
    """One scan-fused chunk of the sharded engine (``engine.run_sharded``'s
    inner loop)."""
    fn = _chunk_fn(
        spec, mesh, policy, backend, use_gpac, max_batches, budget, collect,
        plan,
    )
    args = (
        state,
        chunk,
        jnp.asarray(tables["logical_lo"]),
        jnp.asarray(tables["logical_pad"]),
        jnp.asarray(tables["hp_pad"]),
    )
    if plan is not None:
        args += _synth_args(synth_tables)
    return fn(*args)


# --------------------------------------------------------------------------
# sharded churn window (engine.run_churn's mesh path, DESIGN.md §13)
# --------------------------------------------------------------------------
def _churn_sharded_window(
    spec,  # canonical EngineSpec (static)
    n_shards: int,
    cs,  # repro.core.engine.ChurnState (replicated carry)
    accesses: jax.Array,  # int32[G_loc, k] guest-local ids of local guests
    frow: dict,  # replicated fault row (crash/restart/near_cap/drop)
    logical_lo: jax.Array,
    logical_pad: jax.Array,
    hp_pad: jax.Array,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    slack: int,
    collect: tuple[str, ...],
):
    """:func:`_sharded_window` with the churn carry: the fault row and the
    activity mask are replicated, so ``apply_guest_faults`` and both
    replicated ticks compute identically on every device; only the access
    masking is per-device (each device silences its own local guests'
    rows). Still exactly **one** collective per window -- bit-for-bit equal
    to ``engine._churn_window`` on the unpadded guests."""
    from repro.core.engine import _CHURN_SERIES, ChurnState, run_collectors

    cfg = spec.cfg
    n_g = spec.n_guests
    # ---- 0. fault row (replicated inputs -> replicated transforms) -------
    state, active = faults_mod.apply_guest_faults(
        spec, cs.state, cs.active, frow["crash"], frow["restart"]
    )
    near_cap = jnp.minimum(frow["near_cap"], jnp.int32(cfg.n_near))
    base = state
    # ---- 1. access phase (sharded; inactive + padding lanes emit -1) -----
    with layer("tier.translate"):
        g_loc = accesses.shape[0]
        pos = jax.lax.axis_index(AXIS) * g_loc + jnp.arange(g_loc)
        act_loc = jnp.where(
            pos < n_g, active[jnp.minimum(pos, n_g - 1)], False)
        acc = jnp.where(act_loc[:, None], accesses, -1)
        ids = jnp.where(acc >= 0, acc + logical_lo[:, None], -1)
        slot, _, valid = asp.translate(cfg, state, ids)
        near_loc = (valid & (slot < cfg.n_near)).sum(axis=1)
        far_loc = (valid & (slot >= cfg.n_near)).sum(axis=1)
    kb = spec.kernel_backend
    with layer("tier.record"):
        keep = jnp.where(frow["drop"], 0, 1).astype(jnp.int32)
        local = asp.apply_access_histogram(
            cfg, state, asp.access_histogram(cfg, ids, valid, kb) * keep, kb
        )
    # ---- 2. GPAC phase (sharded: this device's segment rows only) --------
    if use_gpac:
        with layer("tier.score"):
            cl_per_logical = jnp.asarray(spec.cl_per_logical())
        local = gpac.gpac_maintenance_rows(
            cfg, local, backend, max_batches, cl_per_logical, logical_pad,
            hp_pad, kb,
        )
    # ---- 3. one-collective ownership merge -------------------------------
    extras = [
        _spread_rows(near_loc, n_shards), _spread_rows(far_loc, n_shards),
    ]
    if "tco" in collect:
        with layer("tier.roll"):
            extras.append(
                tiers_mod.tier_hit_counts(spec.tier_vector, slot, valid))
    state, merged_extras = merge_window(
        cfg, base, local, logical_pad, hp_pad, tuple(extras),
        merged_gpac=use_gpac,
    )
    near_all, far_all = merged_extras[0], merged_extras[1]
    # ---- 4. host + pressure ticks, window roll (replicated) --------------
    with layer("tier.tick"):
        state = tiering.strided_tick(
            cfg, state, policy, stride=spec.arbitration_stride, budget=budget,
            tiers=spec.tiers,
        )
        state, engaged, press = tiering.pressure_tick(
            cfg, state, near_cap, cs.engaged, cs.pressure,
            budget=budget, slack=slack, tiers=spec.tiers,
        )
    with layer("tier.roll"):
        state = telemetry.end_window(cfg, state)
        window = dict(near_hits=near_all[:n_g], far_hits=far_all[:n_g])
        if "tco" in collect:
            window["tier_hits"] = merged_extras[2]
        out = run_collectors(spec, state, window, collect)
    clash = set(out) & set(_CHURN_SERIES)
    if clash:
        raise ValueError(
            f"collectors {collect} emit keys {sorted(clash)} reserved for "
            f"the churn series {_CHURN_SERIES}"
        )
    out.update(active=active, near_cap=near_cap, pressure=press)
    cs = ChurnState(
        state=state, active=active, window=cs.window + 1,
        near_cap=near_cap, pressure=press, engaged=engaged,
    )
    return cs, out


@lru_cache(maxsize=64)
def _churn_chunk_fn(
    spec,  # canonical EngineSpec
    mesh,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    slack: int,
    collect: tuple[str, ...],
    plan=None,
):
    """Compiled sharded churn chunk driver: :func:`_chunk_fn` with the
    ChurnState carry and the replicated fault rows threaded through the
    scan as extra (window-axis) xs."""
    n_shards = mesh_size(mesh)

    def window_body(c, acc, frow, logical_lo, logical_pad, hp_pad):
        return _churn_sharded_window(
            spec, n_shards, c, acc, frow, logical_lo, logical_pad, hp_pad,
            policy, backend, use_gpac, max_batches, budget, slack, collect,
        )

    if plan is None:

        def body(cs, chunk, crash, restart, near_cap, drop,
                 logical_lo, logical_pad, hp_pad):
            def window(c, xs):
                acc, frow = xs
                return window_body(c, acc, frow, logical_lo, logical_pad, hp_pad)

            xs = (chunk, dict(
                crash=crash, restart=restart, near_cap=near_cap, drop=drop))
            return jax.lax.scan(window, cs, xs)

        in_specs = (
            P(), P(None, AXIS, None), P(None, None), P(None, None), P(None),
            P(None), P(AXIS), P(AXIS, None), P(AXIS, None),
        )
    else:
        from repro.data import traces as tr

        def body(cs, widx, crash, restart, near_cap, drop,
                 logical_lo, logical_pad, hp_pad, seeds, gids, wid, n_logical):
            setup = tr.synth_setup(plan, dict(
                seeds=seeds, gids=gids, wid=wid, n_logical=n_logical))

            def window(c, xs):
                w, frow = xs
                acc = tr.synth_accesses(plan, setup, w)
                return window_body(c, acc, frow, logical_lo, logical_pad, hp_pad)

            xs = (widx, dict(
                crash=crash, restart=restart, near_cap=near_cap, drop=drop))
            return jax.lax.scan(window, cs, xs)

        in_specs = (
            P(), P(None), P(None, None), P(None, None), P(None), P(None),
            P(AXIS), P(AXIS, None), P(AXIS, None),
            P(AXIS), P(AXIS), P(AXIS), P(AXIS),
        )

    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)


def run_chunk_churn_sharded(
    spec,
    mesh,
    cs,
    chunk: jax.Array,  # int32[n_windows, G_pad, k], or int32[n_windows]
    tables: dict,      # window indices when plan is given
    *,
    crash,
    restart,
    near_cap,
    drop,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    slack: int,
    collect: tuple[str, ...],
    plan=None,
    synth_tables: dict | None = None,
):
    """One scan-fused chunk of the sharded churn engine
    (``engine.run_churn``'s mesh path)."""
    fn = _churn_chunk_fn(
        spec, mesh, policy, backend, use_gpac, max_batches, budget, slack,
        collect, plan,
    )
    args = (
        cs,
        chunk,
        jnp.asarray(crash),
        jnp.asarray(restart),
        jnp.asarray(near_cap),
        jnp.asarray(drop),
        jnp.asarray(tables["logical_lo"]),
        jnp.asarray(tables["logical_pad"]),
        jnp.asarray(tables["hp_pad"]),
    )
    if plan is not None:
        args += _synth_args(synth_tables)
    return fn(*args)


# ==========================================================================
# host-partitioned near tier (DESIGN.md §11)
#
# The replicated-host path above still gives every device the full host
# state (block_table, slot pools, host telemetry), so per-device memory does
# not scale with the mesh. The host-partitioned path carries the host state
# **partitioned by contiguous block ranges**: device d owns exactly the huge
# pages of its own guests' GPA segments (guest blocks are contiguous and
# guests are dealt to devices in contiguous blocks, so guest ownership and
# range ownership coincide), holding only
#
#   * its local rows of block_table / host_counts / host_hist /
#     last_touch_epoch / region_epoch, and
#   * the **hp-owned payload** ``data[h - hp_lo]`` -- huge page h's bytes,
#     which equal the replicated ``pools[block_table[h]]`` row. Data follows
#     the huge page, so an arbitrated promotion/demotion only relabels slots
#     (block_table writes); no payload crosses devices, and ``slot_owner``
#     (the label inverse) is reconstructed once per chunk.
#
# Per arbitration group (``EngineSpec.arbitration_stride`` windows; one
# window by default) there is exactly ONE collective: per-partition tick
# candidate sets (repro.core.tiering's sharded (prepare, apply) pairs), a
# few scalar sums, and the stacked per-window collector rows share one
# psum. The full TieredState
# is materialized only at chunk boundaries (slice on entry, ownership-psum on
# exit), so per-device host-state bytes scale ~1/n_shards for the whole scan.
# ==========================================================================
# replicated host-state bytes per huge page: block_table + slot_owner +
# host_counts + last_touch_epoch + region_epoch (int32) + host_hist (uint8)
HOST_META_BYTES = 4 * 5 + 1
# the partitioned carry drops slot_owner (reconstructed at chunk exit)
LOCAL_META_BYTES = 4 * 4 + 1


@dataclasses.dataclass(frozen=True)
class HostPartition:
    """Contiguous per-device block ranges of the host near-tier state.

    Device ``d`` owns huge pages ``[hp_lo[d], hp_hi[d])`` -- its own guests'
    GPA segments. Ranges tile ``[0, n_gpa_hp)``; devices holding only
    padding guests own an empty range. ``h_loc`` is the widest range (every
    device's local arrays are padded to it with -1 rows)."""

    hp_lo: tuple[int, ...]
    hp_hi: tuple[int, ...]
    h_loc: int

    @property
    def n_shards(self) -> int:
        return len(self.hp_lo)

    def hp_ids(self) -> np.ndarray:
        """int32[n_shards, h_loc]: global block ids per device, -1 padded."""
        out = np.full((self.n_shards, self.h_loc), -1, np.int32)
        for d, (lo, hi) in enumerate(zip(self.hp_lo, self.hp_hi)):
            out[d, : hi - lo] = np.arange(lo, hi, dtype=np.int32)
        return out


def host_partition(spec, n_shards: int) -> HostPartition:
    """Partition the host block space by the device-contiguous guest blocks
    of ``engine.run_sharded``'s guest dealing."""
    g_loc = padded_guest_count(spec.n_guests, n_shards) // n_shards
    lo, hi = [], []
    for d in range(n_shards):
        a = min(d * g_loc, spec.n_guests)
        z = min((d + 1) * g_loc, spec.n_guests)
        lo.append(spec.hp_offsets[a])
        hi.append(spec.hp_offsets[z])
    h_loc = max(1, max(h - l for l, h in zip(lo, hi)))
    return HostPartition(tuple(lo), tuple(hi), h_loc)


def host_state_bytes(cfg: GpacConfig) -> int:
    """Bytes of host near-tier state each device holds on the replicated
    path: block/slot tables, host telemetry, and both payload pools."""
    payload = cfg.hp_ratio * cfg.base_elems * jnp.dtype(cfg.dtype).itemsize
    return cfg.n_gpa_hp * (HOST_META_BYTES + payload)


def host_state_bytes_sharded(cfg: GpacConfig, part: HostPartition) -> int:
    """Bytes of the partitioned host-state carry per device (uniform: every
    device pads its range to the widest one)."""
    payload = cfg.hp_ratio * cfg.base_elems * jnp.dtype(cfg.dtype).itemsize
    return part.h_loc * (LOCAL_META_BYTES + payload)


def host_tables(spec, n_shards: int) -> tuple[HostPartition, dict]:
    """Guest segment tables plus the host-partition tables the partitioned
    chunk driver shards over the mesh."""
    part = host_partition(spec, n_shards)
    tables = guest_tables(spec, n_shards)
    tables.update(
        hp_ids=part.hp_ids(),
        hp_lo=np.asarray(part.hp_lo, np.int32),
        hp_hi=np.asarray(part.hp_hi, np.int32),
    )
    return part, tables


def _slice_host_local(cfg: GpacConfig, state: TieredState, hp_ids: jax.Array) -> dict:
    """Gather this device's host-state rows out of a replicated state.

    Padded rows get inert sentinels (slot ``n_gpa_hp`` classifies as far and
    scatters off every table). The payload row of huge page ``h`` is pulled
    through its current slot -- ``data[row(h)] == pools[block_table[h]]`` is
    the layout invariant the whole partitioned path maintains."""
    v = hp_ids >= 0
    t = jnp.maximum(hp_ids, 0)
    bt = jnp.where(v, state.block_table[t], cfg.n_gpa_hp)
    slot = jnp.where(v, bt, 0)
    flat = slot[:, None] * cfg.hp_ratio + jnp.arange(cfg.hp_ratio)[None, :]
    near_rows = state.near_pool.reshape(-1, cfg.base_elems)
    far_rows = state.far_pool.reshape(-1, cfg.base_elems)
    is_near = flat < cfg.n_near * cfg.hp_ratio
    data = jnp.where(
        is_near[..., None],
        near_rows[jnp.where(is_near, flat, 0)],
        far_rows[jnp.where(is_near, 0, flat - cfg.n_near * cfg.hp_ratio)],
    )
    return dict(
        bt=bt,
        hc=jnp.where(v, state.host_counts[t], 0),
        hh=jnp.where(v, state.host_hist[t], 0).astype(jnp.uint8),
        lt=jnp.where(v, state.last_touch_epoch[t], 0),
        re=jnp.where(v, state.region_epoch[t], -1),
        data=jnp.where(v[:, None, None], data, 0),
    )


def _spread_hp(x_loc: jax.Array, hp_ids: jax.Array, n: int, fill) -> jax.Array:
    """Scatter local block rows into a full-shape view filled with ``fill``
    elsewhere (only ever *read* at this device's own blocks)."""
    safe = jnp.where(hp_ids >= 0, hp_ids, n)
    return jnp.full((n + 1,), fill, x_loc.dtype).at[safe].set(x_loc)[:n]


def _scatter_zero(x_loc: jax.Array, hp_ids: jax.Array, n: int) -> jax.Array:
    """Local rows placed at their global positions in zeros: summed across
    devices (ranges tile the space) this reconstructs the full array."""
    safe = jnp.where(hp_ids >= 0, hp_ids, n)
    return jnp.zeros((n + 1,), x_loc.dtype).at[safe].set(x_loc)[:n]


def _bits(x: jax.Array) -> jax.Array:
    """Bit-pattern view for exact integer collectives (see _owned_bits)."""
    if jnp.issubdtype(x.dtype, jnp.integer) and x.dtype.itemsize <= 4:
        return x
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return jax.lax.bitcast_convert_type(x, jnp.uint8)


def _pool_contrib(cfg: GpacConfig, loc: dict, hp_ids: jax.Array, near: bool) -> jax.Array:
    """This device's bit-pattern contribution to one slot pool: its hp-owned
    payload rows scattered to their current slots. block_table is a
    permutation, so across devices every pool row has exactly one
    contributor and the psum is bit-exact."""
    bits = _bits(loc["data"])
    valid = hp_ids >= 0
    slot = loc["bt"]
    if near:
        row = jnp.where(valid & (slot < cfg.n_near), slot, cfg.n_near)
        n_rows = cfg.n_near
    else:
        row = jnp.where(valid & (slot >= cfg.n_near), slot - cfg.n_near, cfg.n_far)
        n_rows = cfg.n_far
    out = jnp.zeros((n_rows,) + bits.shape[1:], bits.dtype)
    return out.at[row].set(bits, mode="drop")


def _place_block(x: jax.Array, n_shards: int) -> jax.Array:
    """This device's candidate block at its mesh position in zeros: the
    shared psum concatenates all devices' nominations."""
    return jnp.zeros((n_shards,) + x.shape, x.dtype).at[
        jax.lax.axis_index(AXIS)
    ].set(x)


def _view_state(cfg, gpt, rmap, gc, ih, re_view, epoch, stats) -> TieredState:
    """A TieredState view for the guest-side GPAC classifiers: real guest
    arrays + the local region_epoch spread, placeholder host arrays (the
    telemetry/filter path never reads block tables or pools)."""
    z = jnp.zeros((1,), jnp.int32)
    zp = jnp.zeros((1, 1, 1), cfg.dtype)
    return TieredState(
        gpt=gpt, rmap=rmap, block_table=z, slot_owner=z, near_pool=zp,
        far_pool=zp, guest_counts=gc, ipt_hist=ih, host_counts=z,
        host_hist=jnp.zeros((1,), jnp.uint8), last_touch_epoch=z,
        region_epoch=re_view, epoch=epoch, stats=stats,
    )


def _near_blocks_local(cfg: GpacConfig, alloc: jax.Array, bt: jax.Array,
                       hp_lo: jax.Array, hp_pad: jax.Array) -> jax.Array:
    """Per own guest: allocated blocks currently in the near tier, counted
    over this device's local block rows (pre-tick; the arbitrated swap
    deltas correct it to post-tick replicatedly)."""
    h_loc = bt.shape[0]
    row = jnp.clip(jnp.where(hp_pad >= 0, hp_pad - hp_lo, 0), 0, h_loc - 1)
    good = alloc & (bt < cfg.n_near)
    seg = (hp_pad >= 0) & good[row]
    return seg.sum(axis=1).astype(jnp.int32)


def _near_blocks_delta(spec, swaps, g_pad: int) -> jax.Array:
    """Replicated per-guest near-block delta of the arbitrated swap rounds.

    Slot-aware: each committed candidate moves from its own slot to its
    partner's, so its near-count contribution is ``(partner in near) - (self
    in near)``. For the builtin 2-tier rounds that is exactly the old
    +1/-1 per promoted/demoted block; for N-tier flows (the ``compressed``
    policy) a swap deeper than the near boundary contributes 0.
    """
    n_near = spec.cfg.n_near
    hp_off = jnp.asarray(spec.hp_offsets, jnp.int32)
    delta = jnp.zeros((g_pad,), jnp.int32)
    for far, near, ok in swaps:
        for cand, other in ((far, near), (near, far)):
            g = jnp.searchsorted(hp_off, cand["id"], side="right") - 1
            w = jnp.where(
                ok & (cand["alloc"] > 0),
                (other["slot"] < n_near).astype(jnp.int32)
                - (cand["slot"] < n_near).astype(jnp.int32),
                0,
            )
            delta = delta.at[jnp.where(ok, g, g_pad)].add(w, mode="drop")
    return delta


def _near_scalar_delta(cfg: GpacConfig, swaps) -> jax.Array:
    """Replicated host-wide delta of allocated near blocks from the
    arbitrated swaps (the scalar form of :func:`_near_blocks_delta`, for the
    host-sharded ``snapshot`` collector); slot-aware like it."""
    d = jnp.int32(0)
    for far, near, ok in swaps:
        for cand, other in ((far, near), (near, far)):
            d = d + jnp.where(
                ok & (cand["alloc"] > 0),
                (other["slot"] < cfg.n_near).astype(jnp.int32)
                - (cand["slot"] < cfg.n_near).astype(jnp.int32),
                0,
            ).sum()
    return d


def _host_sharded_group(
    spec,
    n_shards: int,
    carry: dict,
    accs: jax.Array,  # int32[stride, G_loc, k]
    logical_lo: jax.Array,
    logical_pad: jax.Array,
    hp_pad: jax.Array,
    hp_ids: jax.Array,
    hp_lo: jax.Array,
    hp_hi: jax.Array,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
    prefetch=None,  # SynthTrace overlap: ws -> int32[stride, G_loc, k]
    w_next: jax.Array | None = None,
) -> tuple[dict, dict]:
    """One arbitration *group* -- ``spec.arbitration_stride`` engine windows
    -- on the partitioned host state, with exactly ONE collective for the
    whole group. Bit-for-bit equal to ``engine._window`` at the same stride
    on the unpadded guests (stride 1 is the classic one-window body).

    Every window runs its access + GPAC phases and its telemetry roll
    locally; only the group's last window nominates tick candidates
    (arbitrating on the stride's accumulated telemetry). The per-window
    collector rows -- hits, pre-tick near-block counts, tier vectors,
    snapshot deltas -- are stacked and ride the last window's candidate
    exchange, so ``stride`` windows cost one psum instead of ``stride``.
    The arbitrated swap deltas correct only the last window's emissions:
    the earlier windows ran no tick, so their pre-tick counts *are* their
    post-window placement.

    ``prefetch`` overlaps the collective with trace synthesis (DESIGN.md
    §17): issued right after the psum, the next group's accesses
    (``prefetch(w_next)``) depend only on replicated window indices --
    never on the merged result -- so XLA can schedule the synthesis while
    the exchange is in flight. Streams are counter-based on absolute
    indices, so the overlap is bit-invisible.
    """
    from repro.core import consolidator
    from repro.core import filter as pfilter

    cfg = spec.cfg
    stride = spec.arbitration_stride
    gpt, rmap = carry["gpt"], carry["rmap"]
    gc, ih = carry["guest_counts"], carry["ipt_hist"]
    epoch, stats = carry["epoch"], dict(carry["stats"])
    loc = dict(carry["loc"])
    # replicated cumulative stats for the snapshot collector: per-device
    # deltas ride the arbitration psum, replicated tick deltas add directly
    gstats = dict(carry["gstats"]) if "gstats" in carry else None
    epoch_in = epoch
    kb = spec.kernel_backend
    tv = spec.tier_vector if "tco" in collect else None
    prepare, apply = tiering.sharded_tick_fns(policy)
    if spec.tiers is not None:
        prepare = partial(prepare, tiers=spec.tiers)
        apply = partial(apply, tiers=spec.tiers)

    per_win = []
    L = payload = None
    for j in range(stride):
        stats0 = dict(stats)
        accesses = accs[j]
        # ---- 1. access phase (local: own guests touch own blocks) -------
        with layer("tier.translate"):
            ids = jnp.where(accesses >= 0, accesses + logical_lo[:, None], -1)
            valid = (ids >= 0) & (ids < cfg.n_logical)
            hp = gpt[jnp.where(valid, ids, 0)] // cfg.hp_ratio
            bt_view = _spread_hp(
                loc["bt"], hp_ids, cfg.n_gpa_hp, jnp.int32(cfg.n_gpa_hp))
            slot = bt_view[hp]
            near_loc = (
                (valid & (slot < cfg.n_near)).sum(axis=1).astype(jnp.int32))
            far_loc = (
                (valid & (slot >= cfg.n_near)).sum(axis=1).astype(jnp.int32))
        with layer("tier.record"):
            h = asp.access_histogram(cfg, ids, valid, kb)
            gc = gc + h
            inc_full = asp.host_histogram(cfg, gpt, h, kb)
            inc_loc = jnp.where(
                hp_ids >= 0, inc_full[jnp.maximum(hp_ids, 0)], 0)
            loc["hc"] = loc["hc"] + inc_loc
            loc["lt"] = jnp.where(
                inc_loc > 0, jnp.maximum(loc["lt"], epoch), loc["lt"])
        with layer("tier.translate"):
            stats["near_hits"] = stats["near_hits"] + near_loc.sum()
            stats["far_hits"] = stats["far_hits"] + far_loc.sum()

        # ---- 2. GPAC phase (own segment rows, hp-owned payload) ---------
        if use_gpac:
            with layer("tier.classify"):
                re_view = _spread_hp(
                    loc["re"], hp_ids, cfg.n_gpa_hp, jnp.int32(-1))
                view = _view_state(
                    cfg, gpt, rmap, gc, ih, re_view, epoch, stats)
                hot = telemetry.hot_mask(cfg, view, backend)
            with layer("tier.score"):
                score = pfilter.candidate_score(
                    cfg, view, hot, jnp.asarray(spec.cl_per_logical()), kb
                )
            with layer("tier.select"):
                batches = pfilter.select_batches_from_rows(
                    cfg, score, logical_pad, max_batches, kb
                )
            with layer("tier.copy"):
                gpt, rmap, loc["data"], loc["re"], stats = (
                    consolidator.consolidate_rounds_local(
                        cfg, gpt, rmap, loc["data"], loc["re"], epoch, stats,
                        batches, hp_pad, hp_lo, kb,
                    )
                )

        # ---- 3a. this window's share of the group collective ------------
        # local per-tier access and pre-tick block counts ride the group
        # psum; the arbitrated swap deltas correct the last window's blocks
        # to post-tick replicatedly, so the priced placement is
        # bit-identical to the replicated collector's. Snapshot scalars
        # likewise: this device's window stat deltas (access + GPAC phases;
        # the tick's are replicated and added after arbitration) and its
        # local allocated / allocated-near block counts.
        with layer("tier.roll"):
            alloc_full = (
                rmap.reshape(cfg.n_gpa_hp, cfg.hp_ratio) != FREE).any(axis=1)
            alloc_loc = jnp.where(
                hp_ids >= 0, alloc_full[jnp.maximum(hp_ids, 0)], False)
            contrib = dict(
                near=_spread_rows(near_loc, n_shards),
                far=_spread_rows(far_loc, n_shards),
            )
            if "near_blocks" in collect:
                contrib["near_blocks"] = _spread_rows(
                    _near_blocks_local(
                        cfg, alloc_loc, loc["bt"], hp_lo, hp_pad),
                    n_shards,
                )
            if "tco" in collect:
                contrib["tier_hits"] = tiers_mod.tier_hit_counts(
                    tv, slot, valid)
                contrib["tier_blocks"] = tiers_mod.tier_block_counts(
                    tv, loc["bt"], alloc_loc)
            if gstats is not None:
                contrib["stat_delta"] = {
                    k: stats[k] - stats0[k] for k in stats}
                contrib["alloc_near"] = (
                    alloc_loc & (loc["bt"] < cfg.n_near)).sum()
                contrib["alloc_tot"] = alloc_loc.sum()
        per_win.append(contrib)

        if j < stride - 1:
            # tick-less window roll: arbitration waits for the group's last
            # window, telemetry keeps accumulating across the stride
            with layer("tier.roll"):
                ih = ((ih << 1) | (gc > 0).astype(jnp.uint8)).astype(jnp.uint8)
                loc["hh"] = (
                    (loc["hh"] << 1) | (loc["hc"] > 0).astype(jnp.uint8)
                ).astype(jnp.uint8)
                gc = jnp.zeros_like(gc)
                loc["hc"] = jnp.zeros_like(loc["hc"])
                epoch = epoch + 1
        else:
            # ---- 3b. nominate on the stride's accumulated telemetry -----
            L = dict(
                hp_ids=hp_ids, hp_lo=hp_lo, hp_hi=hp_hi, bt=loc["bt"],
                hc=loc["hc"], hh=loc["hh"], lt=loc["lt"], alloc=alloc_loc,
            )
            with layer("tier.tick"):
                payload = prepare(cfg, L, budget)

    # ---- 3c. the group's single collective ------------------------------
    exchange = dict(
        cands=jax.tree_util.tree_map(
            lambda x: _place_block(x, n_shards), payload["cands"]
        ),
        sums=payload["sums"],
        win=jax.tree_util.tree_map(lambda *x: jnp.stack(x), *per_win),
    )
    merged = _psum_counted("host_exchange", exchange)
    if prefetch is not None:
        # next group's accesses: no data dependency on ``merged``, so the
        # synthesis can run while the exchange is in flight
        acc_next = prefetch(w_next)
    mwin = merged["win"]

    # ---- 4. arbitration: replicated decisions, local block-table writes -
    with layer("tier.tick"):
        loc["bt"], tick_stats, swaps = apply(
            cfg, L, dict(cands=merged["cands"], sums=merged["sums"]), budget
        )
        on_d0 = jax.lax.axis_index(AXIS) == 0
        for s in tick_stats:  # replicated deltas: count them on one device
            stats[s] = stats[s] + jnp.where(on_d0, tick_stats[s], 0)

    # ---- 5. last window's roll (telemetry.end_window, by residency) -----
    with layer("tier.roll"):
        ih = ((ih << 1) | (gc > 0).astype(jnp.uint8)).astype(jnp.uint8)
        loc["hh"] = ((loc["hh"] << 1)
                     | (loc["hc"] > 0).astype(jnp.uint8)).astype(jnp.uint8)
        gc = jnp.zeros_like(gc)
        loc["hc"] = jnp.zeros_like(loc["hc"])
        epoch = epoch + 1

    # ---- 6. per-window collector outputs, stacked [stride, ...] ---------
    with layer("tier.roll"):
        n_g = spec.n_guests
        emits = []
        for j in range(stride):
            last = j == stride - 1
            out_j = {}
            for name in collect:
                if name == "hits":
                    emitted = dict(
                        near_hits=mwin["near"][j][:n_g],
                        far_hits=mwin["far"][j][:n_g],
                    )
                elif name == "near_blocks":
                    pre = mwin["near_blocks"][j]
                    if last:
                        pre = pre + _near_blocks_delta(
                            spec, swaps, pre.shape[0])
                    emitted = dict(near_blocks=pre[:n_g])
                elif name == "snapshot":
                    # metrics.device_snapshot reconstructed from the exchange:
                    # same int sums -> bit-identical float divisions
                    gstats = {
                        k: gstats[k] + mwin["stat_delta"][k][j]
                        + (tick_stats.get(k, 0) if last else 0)
                        for k in gstats
                    }
                    alloc_near = mwin["alloc_near"][j] + (
                        _near_scalar_delta(cfg, swaps) if last else 0)
                    rss = jnp.maximum(mwin["alloc_tot"][j], 1)
                    emitted = dict(
                        epoch=epoch_in + j + 1,
                        near_usage=alloc_near / rss,
                        near_capacity_used=alloc_near / cfg.n_near,
                        hit_rate=gstats["near_hits"] / jnp.maximum(
                            gstats["near_hits"] + gstats["far_hits"], 1),
                        **gstats,
                    )
                elif name == "tco":
                    blocks = mwin["tier_blocks"][j]
                    if last:
                        blocks = blocks + tiers_mod.tier_count_delta(tv, swaps)
                    emitted = tiers_mod.tco_metrics(cfg, tv, blocks,
                                                    mwin["tier_hits"][j])
                else:  # pragma: no cover - run_sharded validates upfront
                    raise ValueError(
                        f"collector {name!r} has no host-sharded form")
                clash = set(emitted) & set(out_j)
                if clash:
                    raise ValueError(
                        f"collector {name!r} emits keys {sorted(clash)} "
                        f"already produced by an earlier collector in "
                        f"{collect}"
                    )
                out_j.update(emitted)
            emits.append(out_j)
        out = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *emits)

    new_carry = dict(
        gpt=gpt, rmap=rmap, guest_counts=gc, ipt_hist=ih, epoch=epoch,
        stats=stats, loc=loc,
    )
    if gstats is not None:
        new_carry["gstats"] = gstats
    if prefetch is not None:
        new_carry["acc"] = acc_next
    return new_carry, out


def _merge_host_final(
    cfg: GpacConfig,
    base: TieredState,
    carry: dict,
    logical_pad: jax.Array,
    hp_pad: jax.Array,
    hp_ids: jax.Array,
) -> TieredState:
    """Chunk-exit reconstruction of the replicated TieredState: one psum of
    ownership-placed contributions (segment rows for guest arrays, block
    ranges for host arrays, bit patterns for the pools), then ``slot_owner``
    recomputed as the merged block table's inverse -- exactly the inverse
    :func:`tiering.swap_blocks` maintains."""
    loc = carry["loc"]
    own_logical = _own_mask(logical_pad, cfg.n_logical)
    own_gpa = jnp.repeat(_own_mask(hp_pad, cfg.n_gpa_hp), cfg.hp_ratio)
    d0 = (jax.lax.axis_index(AXIS) == 0).astype(jnp.int32)
    contrib = dict(
        gpt=_owned_bits(carry["gpt"], own_logical),
        rmap=_owned_bits(carry["rmap"], own_gpa),
        guest_counts=_owned_bits(carry["guest_counts"], own_logical),
        ipt_hist=_owned_bits(carry["ipt_hist"], own_logical),
        bt=_scatter_zero(loc["bt"], hp_ids, cfg.n_gpa_hp),
        hc=_scatter_zero(loc["hc"], hp_ids, cfg.n_gpa_hp),
        hh=_scatter_zero(loc["hh"], hp_ids, cfg.n_gpa_hp),
        lt=_scatter_zero(loc["lt"], hp_ids, cfg.n_gpa_hp),
        re=_scatter_zero(loc["re"], hp_ids, cfg.n_gpa_hp),
        near=_pool_contrib(cfg, loc, hp_ids, near=True),
        far=_pool_contrib(cfg, loc, hp_ids, near=False),
        stats={k: carry["stats"][k] - base.stats[k] for k in base.stats},
        epoch=(carry["epoch"] - base.epoch) * d0,
    )
    m = _psum_counted("host_chunk_exit", contrib)
    slot_owner = jnp.zeros((cfg.n_slots,), jnp.int32).at[m["bt"]].set(
        jnp.arange(cfg.n_gpa_hp, dtype=jnp.int32)
    )
    return dataclasses.replace(
        base,
        gpt=m["gpt"],
        rmap=m["rmap"],
        guest_counts=m["guest_counts"],
        ipt_hist=m["ipt_hist"],
        block_table=m["bt"],
        slot_owner=slot_owner,
        host_counts=m["hc"],
        host_hist=m["hh"],
        last_touch_epoch=m["lt"],
        region_epoch=m["re"],
        near_pool=_from_bits(m["near"], base.near_pool),
        far_pool=_from_bits(m["far"], base.far_pool),
        stats={k: base.stats[k] + m["stats"][k] for k in base.stats},
        epoch=base.epoch + m["epoch"],
    )


@lru_cache(maxsize=64)
def _host_chunk_fn(
    spec,  # canonical EngineSpec
    mesh,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
    plan=None,  # repro.data.traces.SynthPlan for on-device synthesis
):
    """Compiled host-partitioned chunk driver: slice the replicated state
    into per-device ranges, scan the *arbitration groups* (``spec.
    arbitration_stride`` windows each; stride 1 = one window per group) on
    the partitioned carry, merge back once at the chunk boundary. With a
    ``plan``, each device synthesizes its local guests' accesses inside the
    group (same gid-folded key discipline as :func:`_chunk_fn`) -- one
    group *ahead*, so the synthesis of the next group's accesses overlaps
    the in-flight candidate exchange (DESIGN.md §17)."""
    n_shards = mesh_size(mesh)
    cfg = spec.cfg
    stride = spec.arbitration_stride

    def scan_chunk(state, xs, window, hp_ids, acc0=None):
        carry = dict(
            gpt=state.gpt, rmap=state.rmap, guest_counts=state.guest_counts,
            ipt_hist=state.ipt_hist, epoch=state.epoch, stats=state.stats,
            loc=_slice_host_local(cfg, state, hp_ids),
        )
        if "snapshot" in collect:
            carry["gstats"] = dict(state.stats)
        if acc0 is not None:
            carry["acc"] = acc0
        carry, ys = jax.lax.scan(window, carry, xs)
        # [n_groups, stride, ...] -> [n_windows, ...]
        ys = jax.tree_util.tree_map(
            lambda y: y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:]), ys)
        return carry, ys

    if plan is None:

        def body(state, chunk, logical_lo, logical_pad, hp_pad,
                 hp_ids, hp_lo, hp_hi):
            hp_ids, hp_lo, hp_hi = hp_ids[0], hp_lo[0], hp_hi[0]
            groups = chunk.reshape(
                (chunk.shape[0] // stride, stride) + chunk.shape[1:])

            def window(c, accs):
                return _host_sharded_group(
                    spec, n_shards, c, accs, logical_lo, logical_pad, hp_pad,
                    hp_ids, hp_lo, hp_hi, policy, backend, use_gpac,
                    max_batches, budget, collect,
                )

            carry, ys = scan_chunk(state, groups, window, hp_ids)
            return (
                _merge_host_final(cfg, state, carry, logical_pad, hp_pad, hp_ids),
                ys,
            )

        in_specs = (
            P(), P(None, AXIS, None), P(AXIS), P(AXIS, None), P(AXIS, None),
            P(AXIS, None), P(AXIS), P(AXIS),
        )
    else:
        from repro.data import traces as tr

        def body(state, widx, logical_lo, logical_pad, hp_pad,
                 hp_ids, hp_lo, hp_hi, seeds, gids, wid, n_logical):
            hp_ids, hp_lo, hp_hi = hp_ids[0], hp_lo[0], hp_hi[0]
            setup = tr.synth_setup(plan, dict(
                seeds=seeds, gids=gids, wid=wid, n_logical=n_logical))
            wg = widx.reshape(widx.shape[0] // stride, stride)

            def synth_group(ws):
                return jnp.stack([
                    tr.synth_accesses(plan, setup, ws[j])
                    for j in range(stride)
                ])

            def window(c, ws_next):
                return _host_sharded_group(
                    spec, n_shards, c, c["acc"], logical_lo, logical_pad,
                    hp_pad, hp_ids, hp_lo, hp_hi, policy, backend, use_gpac,
                    max_batches, budget, collect,
                    prefetch=synth_group, w_next=ws_next,
                )

            # the scan consumes the carry's pre-synthesized group and
            # prefetches the *next* one behind the psum; the trailing dummy
            # indices (last group + stride) synthesize one discarded group
            w_next = jnp.concatenate([wg[1:], wg[-1:] + stride], axis=0)
            carry, ys = scan_chunk(
                state, w_next, window, hp_ids, acc0=synth_group(wg[0]))
            return (
                _merge_host_final(cfg, state, carry, logical_pad, hp_pad, hp_ids),
                ys,
            )

        in_specs = (
            P(), P(None), P(AXIS), P(AXIS, None), P(AXIS, None),
            P(AXIS, None), P(AXIS), P(AXIS),
            P(AXIS), P(AXIS), P(AXIS), P(AXIS),
        )

    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)


def run_chunk_host_sharded(
    spec,
    mesh,
    state: TieredState,
    chunk: jax.Array,  # int32[n_windows, G_pad, k], or int32[n_windows]
    tables: dict,      # window indices when plan is given
    *,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
    plan=None,
    synth_tables: dict | None = None,
) -> tuple[TieredState, dict]:
    """One scan-fused chunk of the host-partitioned engine
    (``engine.run_sharded(host_sharded=True)``'s inner loop)."""
    fn = _host_chunk_fn(
        spec, mesh, policy, backend, use_gpac, max_batches, budget, collect,
        plan,
    )
    args = (
        state,
        chunk,
        jnp.asarray(tables["logical_lo"]),
        jnp.asarray(tables["logical_pad"]),
        jnp.asarray(tables["hp_pad"]),
        jnp.asarray(tables["hp_ids"]),
        jnp.asarray(tables["hp_lo"]),
        jnp.asarray(tables["hp_hi"]),
    )
    if plan is not None:
        args += _synth_args(synth_tables)
    return fn(*args)
