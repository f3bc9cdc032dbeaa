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
(DESIGN.md §11, ``engine.run_sharded(host_sharded=True)``): nothing is
replicated. The state stays partitioned between calls
(:class:`repro.core.types.PartitionedState`): each device holds only its own
guests and their contiguous block range (:class:`HostPartition`), with the
payload stored per huge page, scores promotion/demotion locally, and one
arbitration exchange per window (``repro.core.tiering``'s sharded ticks)
resolves cross-partition contention bit-for-bit against the replicated
tick -- per-device state bytes scale ~1/n_devices.
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
from repro.core.scopes import exchange_scope, layer
from repro.core.types import FREE, STATS, GpacConfig, PartitionedState, TieredState

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
    ``host_gather`` (:func:`gather_state`, the explicit gather of a
    partitioned state into a replicated one).
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
# The replicated-host path above still gives every device the full state,
# so per-device memory does not scale with the mesh. The host-partitioned
# path keeps the whole state **partitioned** between calls
# (:class:`repro.core.types.PartitionedState`): device d holds only its own
# guests -- their logical and GPA segments, in the device's own coordinates,
# and the huge pages of those segments (guest blocks are contiguous and
# guests are dealt to devices in contiguous blocks, so guest ownership and
# block-range ownership coincide):
#
#   * its guests' gpt / rmap / guest_counts / ipt_hist, as one small fleet
#     of its own (``_local_cfg``: the same stage functions run on it);
#   * its rows of block_table / host_counts / host_hist / last_touch_epoch /
#     region_epoch, and
#   * the **hp-owned payload** ``payload[h - hp_lo]`` -- huge page h's bytes,
#     which equal the replicated ``pools[block_table[h]]`` row. Data follows
#     the huge page, so an arbitrated promotion/demotion only relabels slots
#     (block_table writes); no payload crosses devices, and ``slot_owner``
#     (the label inverse) is derived only where a replicated state is asked
#     for.
#
# Per arbitration group (``EngineSpec.arbitration_stride`` windows; one
# window by default) there is exactly ONE collective: per-partition tick
# candidate sets (repro.core.tiering's sharded (prepare, apply) pairs), a
# few scalar sums, and the stacked per-window collector rows share one
# psum. A replicated TieredState exists only where a caller asks for one
# (:func:`partition_state` / :func:`gather_state`).
# ==========================================================================
# replicated host-state bytes per huge page: block_table + slot_owner +
# host_counts + last_touch_epoch + region_epoch (int32) + host_hist (uint8)
HOST_META_BYTES = 4 * 5 + 1
# the partitioned carry drops slot_owner (derived from the block table)
LOCAL_META_BYTES = 4 * 4 + 1


@dataclasses.dataclass(frozen=True)
class HostPartition:
    """Contiguous per-device ranges of the partitioned state.

    Device ``d`` owns huge pages ``[hp_lo[d], hp_hi[d])`` and logical pages
    ``[l_lo[d], l_hi[d])`` -- its own guests' segments (``g_loc`` guest rows
    per device). Ranges tile both spaces; devices holding only padding
    guests own empty ranges. ``h_loc`` / ``l_loc`` are the widest ranges:
    every device's arrays are padded to them."""

    hp_lo: tuple[int, ...]
    hp_hi: tuple[int, ...]
    h_loc: int
    l_lo: tuple[int, ...]
    l_hi: tuple[int, ...]
    l_loc: int
    g_loc: int

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
    """Partition the host block space and the logical space by the
    device-contiguous guest blocks of ``engine.run_sharded``'s guest
    dealing."""
    g_loc = padded_guest_count(spec.n_guests, n_shards) // n_shards
    hp_lo, hp_hi, l_lo, l_hi = [], [], [], []
    for d in range(n_shards):
        a = min(d * g_loc, spec.n_guests)
        z = min((d + 1) * g_loc, spec.n_guests)
        hp_lo.append(spec.hp_offsets[a])
        hp_hi.append(spec.hp_offsets[z])
        l_lo.append(spec.logical_offsets[a])
        l_hi.append(spec.logical_offsets[z])
    h_loc = max(1, max(h - l for l, h in zip(hp_lo, hp_hi)))
    l_loc = max(1, max(h - l for l, h in zip(l_lo, l_hi)))
    return HostPartition(tuple(hp_lo), tuple(hp_hi), h_loc, tuple(l_lo),
                         tuple(l_hi), l_loc, g_loc)


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
    """The partition and the tables the partitioned drivers shard over the
    mesh: per guest row (``[G_pad]``) its logical and huge-page segment in
    its device's coordinates (start, size) and its Consolidation Limit; per
    device (``[n_shards]``) its global ranges. Padding rows start at their
    device's end with size 0."""
    part = host_partition(spec, n_shards)
    g_pad = part.g_loc * n_shards
    dev = np.arange(g_pad) // part.g_loc
    real = np.arange(g_pad) < spec.n_guests
    rows = np.minimum(np.arange(g_pad), spec.n_guests - 1)
    lo_l = np.asarray(spec.logical_offsets, np.int64)
    lo_h = np.asarray(spec.hp_offsets, np.int64)
    l_lo, l_hi = np.asarray(part.l_lo), np.asarray(part.l_hi)
    hp_lo, hp_hi = np.asarray(part.hp_lo), np.asarray(part.hp_hi)

    def seg(lo, dev_lo, dev_hi):
        start = np.where(real, lo[rows] - dev_lo[dev], dev_hi[dev] - dev_lo[dev])
        size = np.where(real, lo[rows + 1] - lo[rows], 0)
        return start.astype(np.int32), size.astype(np.int32)

    l_start, l_size = seg(lo_l, l_lo, l_hi)
    h_start, h_size = seg(lo_h, hp_lo, hp_hi)
    cls = np.asarray([spec.guest_cl(g) for g in range(spec.n_guests)], np.int32)
    tables = dict(
        l_start=l_start, l_size=l_size, h_start=h_start, h_size=h_size,
        cl=np.where(real, cls[rows], 1).astype(np.int32),
        hp_lo=hp_lo.astype(np.int32), hp_hi=hp_hi.astype(np.int32),
        l_lo=l_lo.astype(np.int32), l_hi=l_hi.astype(np.int32),
    )
    return part, tables


def _mesh_tables(spec, mesh) -> dict:
    """:func:`host_tables` for ``mesh``, as arrays."""
    _, tables = host_tables(spec, mesh_size(mesh))
    return {k: jnp.asarray(v) for k, v in tables.items()}


def _local_cfg(cfg: GpacConfig, part: HostPartition) -> GpacConfig:
    """One device's guest-major geometry: its guests as a fleet of their
    own. Slots stay the host's, so every slot test uses ``cfg``; the local
    ``n_near`` is a placeholder no stage reads."""
    return dataclasses.replace(
        cfg, n_logical=part.l_loc, n_gpa_hp=part.h_loc, n_near=1)


def _pad_rows(start: jax.Array, size: jax.Array, width: int) -> jax.Array:
    """int32[rows, width]: row g = ``start[g] + col`` where ``col <
    size[g]``, -1 past it (a device's segment-table rows, made in place)."""
    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    return jnp.where(col < size[:, None], start[:, None] + col, -1)


def _device_tables(part: HostPartition, tab: dict) -> dict:
    """This device's blocks of :func:`host_tables` as the window bodies use
    them: per-device scalars, and the global id of each block row."""
    out = dict(tab)
    for k in ("hp_lo", "hp_hi", "l_lo", "l_hi"):
        out[k] = tab[k][0]
    col = jnp.arange(part.h_loc, dtype=jnp.int32)
    out["hp_ids"] = jnp.where(
        col < out["hp_hi"] - out["hp_lo"], out["hp_lo"] + col, -1)
    return out


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


def _scatter_zero(x_loc: jax.Array, ids: jax.Array, n: int) -> jax.Array:
    """Local rows placed at their global positions ``ids`` (-1 = none) in
    zeros: summed across devices (ranges tile the space) this reconstructs
    the full array."""
    safe = jnp.where(ids >= 0, ids, n)
    return jnp.zeros((n + 1,) + x_loc.shape[1:], x_loc.dtype).at[safe].set(
        x_loc)[:n]


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


def _view_state(cfg, gpt, rmap, gc, ih, re_loc, epoch, stats) -> TieredState:
    """A TieredState view for the guest-side GPAC classifiers: the device's
    guest arrays and block-range region_epoch, placeholder host arrays (the
    telemetry/filter path never reads block tables or pools)."""
    z = jnp.zeros((1,), jnp.int32)
    zp = jnp.zeros((1, 1, 1), cfg.dtype)
    return TieredState(
        gpt=gpt, rmap=rmap, block_table=z, slot_owner=z, near_pool=zp,
        far_pool=zp, guest_counts=gc, ipt_hist=ih, host_counts=z,
        host_hist=jnp.zeros((1,), jnp.uint8), last_touch_epoch=z,
        region_epoch=re_loc, epoch=epoch, stats=stats,
    )


def _hot_mask(cfg: GpacConfig, lcfg: GpacConfig, view: TieredState,
              backend: str, l_lo: jax.Array) -> jax.Array:
    """The classifier on the device's own pages. A page-local classifier
    (``ipt``) is exact on the local view. The others (the sampled and
    region ones) draw and group by global page position: they classify a
    global-shaped view with this device's counts in place (zeros elsewhere)
    and read back this device's rows."""
    if telemetry.is_page_local(backend):
        return telemetry.hot_mask(lcfg, view, backend)
    n, w = cfg.n_logical, lcfg.n_logical

    def spread(x):
        buf = jnp.zeros((n + w,), x.dtype)
        return jax.lax.dynamic_update_slice(buf, x, (l_lo,))[:n]

    glob = dataclasses.replace(
        view, guest_counts=spread(view.guest_counts),
        ipt_hist=spread(view.ipt_hist))
    hot = telemetry.hot_mask(cfg, glob, backend)
    return jax.lax.dynamic_slice(
        jnp.concatenate([hot, jnp.zeros((w,), hot.dtype)]), (l_lo,), (w,))


def _cl_rows(lcfg: GpacConfig, T: dict) -> jax.Array:
    """int32[l_loc]: the CL of the local guest owning each local page."""
    ids = jnp.arange(lcfg.n_logical, dtype=jnp.int32)
    return T["cl"][jnp.searchsorted(T["l_start"][1:], ids, side="right")]


def _near_blocks_local(cfg: GpacConfig, alloc: jax.Array, bt: jax.Array,
                       hp_pad: jax.Array) -> jax.Array:
    """Per own guest: allocated blocks currently in the near tier, counted
    over this device's block rows (pre-tick; the arbitrated swap deltas
    correct it to post-tick replicatedly)."""
    row = jnp.maximum(hp_pad, 0)
    seg = (hp_pad >= 0) & (alloc & (bt < cfg.n_near))[row]
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
    lcfg: GpacConfig,  # this device's guest-major geometry (_local_cfg)
    n_shards: int,
    carry: dict,
    accs: jax.Array,  # int32[stride, G_loc, k]
    T: dict,  # this device's tables (_device_tables)
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
    -- on the partitioned state, with exactly ONE collective for the whole
    group. Bit-for-bit equal to ``engine._window`` at the same stride on the
    unpadded guests (stride 1 is the classic one-window body).

    Every window runs its access + GPAC phases and its telemetry roll
    locally, in the device's own coordinates; only the group's last window
    nominates tick candidates (arbitrating on the stride's accumulated
    telemetry). The per-window collector rows -- hits, pre-tick near-block
    counts, tier vectors, snapshot deltas -- are stacked and ride the last
    window's candidate exchange, so ``stride`` windows cost one psum
    instead of ``stride``. The arbitrated swap deltas correct only the last
    window's emissions: the earlier windows ran no tick, so their pre-tick
    counts *are* their post-window placement.

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
    hp_ids = T["hp_ids"]
    hp_pad = _pad_rows(T["h_start"], T["h_size"], spec.max_hp)
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
            ids = jnp.where(accesses >= 0, accesses + T["l_start"][:, None], -1)
            valid = (ids >= 0) & (ids < lcfg.n_logical)
            hp = gpt[jnp.where(valid, ids, 0)] // cfg.hp_ratio
            slot = loc["bt"][hp]
            near_loc = (
                (valid & (slot < cfg.n_near)).sum(axis=1).astype(jnp.int32))
            far_loc = (
                (valid & (slot >= cfg.n_near)).sum(axis=1).astype(jnp.int32))
        with layer("tier.record"):
            h = asp.access_histogram(lcfg, ids, valid, kb)
            gc = gc + h
            inc = asp.host_histogram(lcfg, gpt, h, kb)
            loc["hc"] = loc["hc"] + inc
            loc["lt"] = jnp.where(
                inc > 0, jnp.maximum(loc["lt"], epoch), loc["lt"])
        with layer("tier.translate"):
            stats["near_hits"] = stats["near_hits"] + near_loc.sum()
            stats["far_hits"] = stats["far_hits"] + far_loc.sum()

        # ---- 2. GPAC phase (own segment rows, hp-owned payload) ---------
        if use_gpac:
            with layer("tier.classify"):
                view = _view_state(
                    lcfg, gpt, rmap, gc, ih, loc["re"], epoch, stats)
                hot = _hot_mask(cfg, lcfg, view, backend, T["l_lo"])
            with layer("tier.score"):
                score = pfilter.candidate_score(
                    lcfg, view, hot, _cl_rows(lcfg, T), kb)
            with layer("tier.select"):
                batches = pfilter.select_batches_from_rows(
                    lcfg, score,
                    _pad_rows(T["l_start"], T["l_size"], spec.max_logical),
                    max_batches, kb,
                )
            with layer("tier.copy"):
                gpt, rmap, loc["data"], loc["re"], stats = (
                    consolidator.consolidate_rounds_local(
                        lcfg, gpt, rmap, loc["data"], loc["re"], epoch, stats,
                        batches, hp_pad, jnp.int32(0), kb,
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
            alloc_loc = (
                rmap.reshape(lcfg.n_gpa_hp, cfg.hp_ratio) != FREE).any(axis=1)
            contrib = dict(
                near=_spread_rows(near_loc, n_shards),
                far=_spread_rows(far_loc, n_shards),
            )
            if "near_blocks" in collect:
                contrib["near_blocks"] = _spread_rows(
                    _near_blocks_local(cfg, alloc_loc, loc["bt"], hp_pad),
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
                hp_ids=hp_ids, hp_lo=T["hp_lo"], hp_hi=T["hp_hi"],
                bt=loc["bt"], hc=loc["hc"], hh=loc["hh"], lt=loc["lt"],
                alloc=alloc_loc,
            )
            with layer("tier.tick"):
                payload = prepare(cfg, L, budget)

    # ---- 3c. the group's single collective ------------------------------
    with layer("tier.tick"), exchange_scope():
        merged = _psum_counted("host_exchange", dict(
            cands=jax.tree_util.tree_map(
                lambda x: _place_block(x, n_shards), payload["cands"]
            ),
            sums=payload["sums"],
            win=jax.tree_util.tree_map(lambda *x: jnp.stack(x), *per_win),
        ))
        mwin = merged["win"]
        nominated = dict(cands=merged["cands"], sums=merged["sums"])
    if prefetch is not None:
        # next group's accesses: no data dependency on ``merged``, so the
        # synthesis can run while the exchange is in flight
        acc_next = prefetch(w_next)

    # ---- 4. arbitration: replicated decisions, local block-table writes -
    with layer("tier.tick"):
        loc["bt"], tick_stats, swaps = apply(cfg, L, nominated, budget)
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


def _carry_in(ps: PartitionedState) -> dict:
    """A device's block of the partitioned state as the group body's carry."""
    return dict(
        gpt=ps.gpt, rmap=ps.rmap, guest_counts=ps.guest_counts,
        ipt_hist=ps.ipt_hist, epoch=ps.epoch[0],
        stats={k: v[0] for k, v in ps.stats.items()},
        loc=dict(bt=ps.block_table, hc=ps.host_counts, hh=ps.host_hist,
                 lt=ps.last_touch_epoch, re=ps.region_epoch, data=ps.payload),
    )


def _carry_out(c: dict) -> PartitionedState:
    loc = c["loc"]
    return PartitionedState(
        gpt=c["gpt"], rmap=c["rmap"], guest_counts=c["guest_counts"],
        ipt_hist=c["ipt_hist"], block_table=loc["bt"], host_counts=loc["hc"],
        host_hist=loc["hh"], last_touch_epoch=loc["lt"],
        region_epoch=loc["re"], payload=loc["data"], epoch=c["epoch"][None],
        stats={k: v[None] for k, v in c["stats"].items()},
    )


def _partitioned_fn(mesh, body, in_specs, out_specs):
    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        # psum results are replicated but the replication check cannot
        # always infer it; correctness is pinned by the equivalence tests
        check_vma=False,
    ))


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
    """Compiled host-partitioned chunk driver: scan the *arbitration
    groups* (``spec.arbitration_stride`` windows each; stride 1 = one window
    per group) on the partitioned state, which comes in and goes out
    partitioned. With a ``plan``, each device synthesizes its local guests'
    accesses inside the group (same gid-folded key discipline as
    :func:`_chunk_fn`) -- one group *ahead*, so the synthesis of the next
    group's accesses overlaps the in-flight candidate exchange (DESIGN.md
    §17)."""
    n_shards = mesh_size(mesh)
    part = host_partition(spec, n_shards)
    lcfg = _local_cfg(spec.cfg, part)
    stride = spec.arbitration_stride

    def group(c, accs, T, **kw):
        return _host_sharded_group(
            spec, lcfg, n_shards, c, accs, T, policy, backend, use_gpac,
            max_batches, budget, collect, **kw)

    def scan_chunk(ps, xs, window, acc0=None):
        carry = _carry_in(ps)
        if "snapshot" in collect:
            carry["gstats"] = jax.lax.psum(carry["stats"], AXIS)
        if acc0 is not None:
            carry["acc"] = acc0
        carry, ys = jax.lax.scan(window, carry, xs)
        # [n_groups, stride, ...] -> [n_windows, ...]
        ys = jax.tree_util.tree_map(
            lambda y: y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:]), ys)
        return _carry_out(carry), ys

    if plan is None:

        def body(ps, chunk, tab):
            T = _device_tables(part, tab)
            groups = chunk.reshape(
                (chunk.shape[0] // stride, stride) + chunk.shape[1:])
            return scan_chunk(ps, groups, lambda c, accs: group(c, accs, T))

        in_specs = (P(AXIS), P(None, AXIS, None), P(AXIS))
    else:
        from repro.data import traces as tr

        def body(ps, widx, tab, seeds, gids, wid, n_logical):
            T = _device_tables(part, tab)
            setup = tr.synth_setup(plan, dict(
                seeds=seeds, gids=gids, wid=wid, n_logical=n_logical))
            wg = widx.reshape(widx.shape[0] // stride, stride)

            def synth_group(ws):
                return jnp.stack([
                    tr.synth_accesses(plan, setup, ws[j])
                    for j in range(stride)
                ])

            def window(c, ws_next):
                return group(c, c["acc"], T, prefetch=synth_group,
                             w_next=ws_next)

            # the scan consumes the carry's pre-synthesized group and
            # prefetches the *next* one behind the psum; the trailing dummy
            # indices (last group + stride) synthesize one discarded group
            w_next = jnp.concatenate([wg[1:], wg[-1:] + stride], axis=0)
            return scan_chunk(ps, w_next, window, acc0=synth_group(wg[0]))

        in_specs = (P(AXIS), P(None), P(AXIS), P(AXIS), P(AXIS), P(AXIS),
                    P(AXIS))

    return _partitioned_fn(mesh, body, in_specs, (P(AXIS), P()))


def run_chunk_host_sharded(
    spec,
    mesh,
    state: PartitionedState,
    chunk: jax.Array,  # int32[n_windows, G_pad, k], or int32[n_windows]
    tables: dict,      # host_tables(spec, n_shards)[1]
    *,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
    plan=None,
    synth_tables: dict | None = None,
) -> tuple[PartitionedState, dict]:
    """One scan-fused chunk of the host-partitioned engine
    (``engine.run_sharded(host_sharded=True)``'s inner loop): the state
    comes in and goes out partitioned."""
    fn = _host_chunk_fn(
        spec, mesh, policy, backend, use_gpac, max_batches, budget, collect,
        plan,
    )
    args = (state, chunk, {k: jnp.asarray(v) for k, v in tables.items()})
    if plan is not None:
        args += _synth_args(synth_tables)
    return fn(*args)


# --------------------------------------------------------------------------
# the partitioned state: built in place, or converted on request
# --------------------------------------------------------------------------
def n_shards_of(state: PartitionedState) -> int:
    """The mesh size a partitioned state was laid out for."""
    return state.epoch.shape[0]


@lru_cache(maxsize=16)
def _init_fn(spec, mesh):
    cfg = spec.cfg
    R = cfg.hp_ratio
    part = host_partition(spec, mesh_size(mesh))

    def body(tab):
        T = _device_tables(part, tab)
        i = jnp.arange(part.l_loc, dtype=jnp.int32)
        g = jnp.searchsorted(T["l_start"][1:], i, side="right")
        gpt = jnp.where(i < T["l_hi"] - T["l_lo"],
                        T["h_start"][g] * R + i - T["l_start"][g], -1)
        j = jnp.arange(part.h_loc * R, dtype=jnp.int32)
        gh = jnp.searchsorted(T["h_start"][1:], j // R, side="right")
        off = j - T["h_start"][gh] * R
        rmap = jnp.where((j // R < T["hp_hi"] - T["hp_lo"])
                         & (off < T["l_size"][gh]),
                         T["l_start"][gh] + off, FREE)
        zl = jnp.zeros((part.l_loc,), jnp.int32)
        zh = jnp.zeros((part.h_loc,), jnp.int32)
        one = jnp.zeros((1,), jnp.int32)
        return PartitionedState(
            gpt=gpt, rmap=rmap, guest_counts=zl,
            ipt_hist=zl.astype(jnp.uint8),
            block_table=jnp.where(T["hp_ids"] >= 0, T["hp_ids"], cfg.n_gpa_hp),
            host_counts=zh, host_hist=zh.astype(jnp.uint8),
            last_touch_epoch=zh, region_epoch=zh - 1,
            payload=jnp.zeros((part.h_loc, R, cfg.base_elems), cfg.dtype),
            epoch=one,
            stats={k: one for k in STATS},
        )

    return _partitioned_fn(mesh, body, (P(AXIS),), P(AXIS))


def init_partitioned(spec, mesh) -> PartitionedState:
    """The fresh engine state of ``engine.init_engine_state``, built on the
    mesh in its partitioned layout: each device builds only its own
    guests' part, so the whole state never exists on one device."""
    return _init_fn(spec.canonical(), mesh)(_mesh_tables(spec, mesh))


@lru_cache(maxsize=16)
def _partition_fn(spec, mesh):
    cfg = spec.cfg
    R = cfg.hp_ratio
    part = host_partition(spec, mesh_size(mesh))

    def body(state, tab):
        T = _device_tables(part, tab)
        i = jnp.arange(part.l_loc, dtype=jnp.int32)
        lv = i < T["l_hi"] - T["l_lo"]
        li = jnp.where(lv, T["l_lo"] + i, 0)

        def rows(x, fill):
            return jnp.where(lv, x[li], fill).astype(x.dtype)

        j = jnp.arange(part.h_loc * R, dtype=jnp.int32)
        hv = j < (T["hp_hi"] - T["hp_lo"]) * R
        r = state.rmap[jnp.where(hv, T["hp_lo"] * R + j, 0)]
        loc = _slice_host_local(cfg, state, T["hp_ids"])
        d0 = jax.lax.axis_index(AXIS) == 0
        return PartitionedState(
            gpt=rows(state.gpt - T["hp_lo"] * R, -1),
            rmap=jnp.where(hv & (r != FREE), r - T["l_lo"], FREE),
            guest_counts=rows(state.guest_counts, 0),
            ipt_hist=rows(state.ipt_hist, 0),
            block_table=loc["bt"], host_counts=loc["hc"], host_hist=loc["hh"],
            last_touch_epoch=loc["lt"], region_epoch=loc["re"],
            payload=loc["data"], epoch=state.epoch[None],
            stats={k: jnp.where(d0, v, 0)[None]
                   for k, v in state.stats.items()},
        )

    return _partitioned_fn(mesh, body, (P(), P(AXIS)), P(AXIS))


def partition_state(spec, mesh, state: TieredState) -> PartitionedState:
    """Lay a replicated state out partitioned over ``mesh`` (for small
    fleets and tests: the whole state is an input on every device)."""
    return _partition_fn(spec.canonical(), mesh)(
        state, _mesh_tables(spec, mesh))


@lru_cache(maxsize=16)
def _gather_fn(spec, mesh):
    cfg = spec.cfg
    R = cfg.hp_ratio
    part = host_partition(spec, mesh_size(mesh))

    def body(ps, tab):
        T = _device_tables(part, tab)
        i = jnp.arange(part.l_loc, dtype=jnp.int32)
        l_ids = jnp.where(i < T["l_hi"] - T["l_lo"], T["l_lo"] + i, -1)
        j = jnp.arange(part.h_loc * R, dtype=jnp.int32)
        g_ids = jnp.where(j < (T["hp_hi"] - T["hp_lo"]) * R,
                          T["hp_lo"] * R + j, -1)
        loc = _carry_in(ps)["loc"]
        hp_ids = T["hp_ids"]
        contrib = dict(
            gpt=_scatter_zero(ps.gpt + T["hp_lo"] * R, l_ids, cfg.n_logical),
            rmap=_scatter_zero(
                jnp.where(ps.rmap != FREE, ps.rmap + T["l_lo"], FREE), g_ids,
                cfg.n_gpa),
            guest_counts=_scatter_zero(ps.guest_counts, l_ids, cfg.n_logical),
            ipt_hist=_scatter_zero(ps.ipt_hist, l_ids, cfg.n_logical),
            bt=_scatter_zero(loc["bt"], hp_ids, cfg.n_gpa_hp),
            hc=_scatter_zero(loc["hc"], hp_ids, cfg.n_gpa_hp),
            hh=_scatter_zero(loc["hh"], hp_ids, cfg.n_gpa_hp),
            lt=_scatter_zero(loc["lt"], hp_ids, cfg.n_gpa_hp),
            re=_scatter_zero(loc["re"], hp_ids, cfg.n_gpa_hp),
            near=_pool_contrib(cfg, loc, hp_ids, near=True),
            far=_pool_contrib(cfg, loc, hp_ids, near=False),
            stats={k: v[0] for k, v in ps.stats.items()},
        )
        m = _psum_counted("host_gather", contrib)
        slot_owner = jnp.zeros((cfg.n_slots,), jnp.int32).at[m["bt"]].set(
            jnp.arange(cfg.n_gpa_hp, dtype=jnp.int32))
        return TieredState(
            gpt=m["gpt"], rmap=m["rmap"], block_table=m["bt"],
            slot_owner=slot_owner,
            near_pool=_from_bits(m["near"], jnp.zeros((), cfg.dtype)),
            far_pool=_from_bits(m["far"], jnp.zeros((), cfg.dtype)),
            guest_counts=m["guest_counts"], ipt_hist=m["ipt_hist"],
            host_counts=m["hc"], host_hist=m["hh"], last_touch_epoch=m["lt"],
            region_epoch=m["re"], epoch=ps.epoch[0], stats=m["stats"],
        )

    return _partitioned_fn(mesh, body, (P(AXIS), P(AXIS)), P())


def gather_state(spec, mesh, state: PartitionedState) -> TieredState:
    """The explicit gather: the replicated TieredState of a partitioned one,
    on every device, with ``slot_owner`` derived as the block table's
    inverse -- exactly the inverse :func:`tiering.swap_blocks` maintains.
    One psum of ownership-placed contributions (segments for guest arrays,
    block ranges for host arrays, bit patterns for the pools). For tests
    and small fleets: it holds the whole state on every device."""
    with jax.profiler.TraceAnnotation("tier.gather"):
        return _gather_fn(spec.canonical(), mesh)(
            state, _mesh_tables(spec, mesh))


def host_arrays(spec, state: PartitionedState) -> dict[str, np.ndarray]:
    """The global guest page table and host block table of a partitioned
    state, assembled on the host from each device's block (no device ever
    holds them whole)."""
    with jax.profiler.TraceAnnotation("tier.gather"):
        n = n_shards_of(state)
        part = host_partition(spec, n)
        R = spec.cfg.hp_ratio
        gpt = np.asarray(state.gpt).reshape(n, part.l_loc)
        bt = np.asarray(state.block_table).reshape(n, part.h_loc)
        return dict(
            gpt=np.concatenate([
                gpt[d, : part.l_hi[d] - part.l_lo[d]] + part.hp_lo[d] * R
                for d in range(n)]).astype(np.int32),
            block_table=np.concatenate([
                bt[d, : part.hp_hi[d] - part.hp_lo[d]] for d in range(n)]),
        )


@lru_cache(maxsize=16)
def _read_fn(spec, mesh):
    cfg = spec.cfg
    R = cfg.hp_ratio
    part = host_partition(spec, mesh_size(mesh))

    def body(payload, gpa, tab):
        T = _device_tables(part, tab)
        h = gpa // R
        own = (h >= T["hp_lo"]) & (h < T["hp_hi"])
        row = jnp.where(own, (h - T["hp_lo"]) * R + gpa % R, 0)
        vals = payload.reshape(-1, cfg.base_elems)[row]
        return jax.lax.psum(jnp.where(own[:, None], _bits(vals), 0), AXIS)

    return _partitioned_fn(mesh, body, (P(AXIS), P(), P(AXIS)), P())


def read_pages(spec, mesh, state: PartitionedState, gpa) -> np.ndarray:
    """``dtype[len(gpa), base_elems]``: the payload of global GPA pages
    ``gpa``, each read on the device that owns its huge page."""
    bits = _read_fn(spec.canonical(), mesh)(
        state.payload, jnp.asarray(gpa, jnp.int32), _mesh_tables(spec, mesh))
    return np.asarray(_from_bits(bits, jnp.zeros((), spec.cfg.dtype)))
