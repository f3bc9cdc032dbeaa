"""Unified simulation engine: ragged multi-tenant guests on one shared driver.

This module is the single entry point for every paper-figure simulation,
single- or multi-guest (DESIGN.md §7). It replaces the old symmetric-only
``simulate.MultiGuest`` surface with explicit geometry specs:

* :class:`GuestSpec` -- one guest's shape: ``n_logical`` base pages, an
  optional per-guest Consolidation Limit, GPA slack, and the trace
  workload/seed the helpers use to synthesize its accesses.
* :class:`HostSpec` -- the shared host: huge-page ratio, near-tier sizing,
  telemetry/policy knobs that fill the combined :class:`GpacConfig`.
* :class:`EngineSpec` -- the compiled-in geometry: the combined config plus
  **segment-offset tables** mapping each guest to its logical and GPA huge
  page ranges. Guests may be *ragged* (distinct sizes, slacks and CLs);
  nothing assumes the uniform tiling the old reshape-based reductions needed.

On top of the geometry sits **one** scan-fused driver, :func:`run`: the
window loop of the old ``gpac.run_windows`` and ``simulate.run_multi_guest``
(both now thin deprecation shims over this function) runs as a device-side
``lax.scan`` chunked by ``windows_per_step``, with one host transfer per
chunk. Per-window measurement is pluggable: on-device **metric collectors**
registered via :func:`register_collector` run inside the scan and their
stacked outputs cross to the host once per chunk.

Equivalence: :func:`run_reference` preserves the sequential per-guest /
per-window formulation (guest g's GPAC daemon confined to its own segment
via ``allow``/``hp_range``); tests pin the ragged engine bit-for-bit against
it across every registered policy.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import address_space as asp
from repro.core import faults as faults_mod
from repro.core import gpac, metrics, telemetry, tiering
from repro.core import tiers as tiers_mod
from repro.core.scopes import LAYERS, layer  # noqa: F401  (LAYERS re-exported)
from repro.core.types import (
    GpacConfig, PartitionedState, TieredState, allocated_hp_mask, init_state)
from repro.kernels import registry as kernels_registry


# --------------------------------------------------------------------------
# geometry specs
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GuestSpec:
    """One guest's geometry and trace identity.

    ``cl=None`` inherits the host default (``GpacConfig.cl``); a value gives
    this guest its own Consolidation Limit (paper §4.3.1 -- Table 3 tunes CL
    per workload, so heterogeneous tenants need per-guest CLs).
    ``gpa_slack`` is the extra GPA huge-page headroom beyond the minimum
    ``ceil(n_logical / hp_ratio)`` (the paper's far tier is much larger than
    the guests, so consolidation never starves for free regions).
    """

    n_logical: int
    cl: int | None = None
    gpa_slack: float = 0.25
    workload: str = "redis"
    seed: int = 0

    def hp_need(self, hp_ratio: int) -> int:
        return -(-self.n_logical // hp_ratio)

    def hp_size(self, hp_ratio: int) -> int:
        need = self.hp_need(hp_ratio)
        return need + max(2, int(need * self.gpa_slack))


@dataclasses.dataclass(frozen=True)
class HostSpec:
    """Shared host geometry + default policy knobs for the combined config.

    ``near_fraction`` sizes the near tier as a fraction of the guests' total
    *needed* huge pages (the paper's DRAM:NVMM ratio knob, Fig. 17);
    ``n_near`` overrides it with an explicit block count. ``tiers`` replaces
    both with an N-tier hierarchy: a tuple of ``core.tiers.TierSpec`` whose
    capacity fractions ``build`` resolves into slot boundaries (tier 0
    becomes the near pool); it is mutually exclusive with ``n_near``.
    """

    hp_ratio: int = 512
    near_fraction: float = 0.5
    n_near: int = 0
    base_elems: int = 8
    cl: int = 64
    hot_threshold: int = 1
    ipt_windows: int = 8
    ipt_min_hits: int = 1
    reconsolidate_cooldown: int = 2
    dtype: Any = jnp.float32
    tiers: tuple | None = None

    def __post_init__(self):
        if self.hp_ratio < 1:
            raise ValueError(
                f"HostSpec: hp_ratio must be >= 1, got {self.hp_ratio}")
        if not 0.0 < self.near_fraction <= 1.0:
            raise ValueError(
                f"HostSpec: near_fraction must be in (0, 1], got "
                f"{self.near_fraction}")
        if self.n_near < 0:
            raise ValueError(
                f"HostSpec: n_near must be >= 0 (0 means derive from "
                f"near_fraction), got {self.n_near}")
        if self.base_elems < 1:
            raise ValueError(
                f"HostSpec: base_elems must be >= 1, got {self.base_elems}")
        if not 1 <= self.cl <= self.hp_ratio:
            raise ValueError(
                f"HostSpec: Consolidation Limit must be in [1, hp_ratio="
                f"{self.hp_ratio}], got cl={self.cl}")
        if self.tiers is not None:
            if self.n_near:
                raise ValueError(
                    f"HostSpec: tiers and n_near are mutually exclusive "
                    f"(tier 0's capacity sizes the near pool), got n_near="
                    f"{self.n_near} with {len(self.tiers)} tiers")
            object.__setattr__(self, "tiers", tuple(self.tiers))
            if len(self.tiers) < 2:
                raise ValueError(
                    f"HostSpec: tiers needs >= 2 entries, got "
                    f"{len(self.tiers)}")
            for t in self.tiers:
                if not isinstance(t, tiers_mod.TierSpec):
                    raise ValueError(
                        f"HostSpec: tiers entries must be TierSpec, got "
                        f"{type(t).__name__}: {t!r}")


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Static engine geometry: combined config + per-guest segment offsets.

    ``logical_offsets`` / ``hp_offsets`` are cumulative: guest ``g`` owns
    logical pages ``[logical_offsets[g], logical_offsets[g+1])`` and GPA huge
    pages ``[hp_offsets[g], hp_offsets[g+1])``. Segments are disjoint and
    tile their spaces, which is what lets N per-guest GPAC daemons run as one
    batched pass bit-for-bit (DESIGN.md §7). Hashable, so it jits as a static
    argument; the padded index tables below are numpy constants baked in at
    trace time.
    """

    cfg: GpacConfig
    guests: tuple[GuestSpec, ...]
    logical_offsets: tuple[int, ...]  # len n_guests+1
    hp_offsets: tuple[int, ...]  # len n_guests+1
    # resolved core.tiers.TierVector when built from HostSpec.tiers; None
    # keeps every legacy path on the 2-tier near/far special case
    tiers: Any = None
    # hot-path kernel dispatch ("xla" | "pallas" | "auto", DESIGN.md §16);
    # static, so it rides every jit cache key with the rest of the spec
    kernel_backend: str = "auto"
    # run the host arbitration tick only every this-many windows (DESIGN.md
    # §17): telemetry, GPAC and the pressure controller still run every
    # window, but promotion/demotion arbitration -- and on the
    # host-partitioned path its candidate-exchange collective -- is batched
    # over the stride. 1 (the default) is the paper's per-window tick,
    # bit-identical to the pre-knob engine on every driver; >1 trades
    # arbitration latency for collective count (the HybridTier-style
    # coarse-signal trade-off). Static, like kernel_backend.
    arbitration_stride: int = 1

    @property
    def n_guests(self) -> int:
        return len(self.guests)

    @property
    def tier_vector(self):
        """The resolved hierarchy, defaulting to the legacy 2-tier split."""
        return tiers_mod.as_vector(self.cfg, self.tiers)

    def logical_range(self, g: int) -> tuple[int, int]:
        return self.logical_offsets[g], self.logical_offsets[g + 1]

    def hp_range(self, g: int) -> tuple[int, int]:
        return self.hp_offsets[g], self.hp_offsets[g + 1]

    def guest_cl(self, g: int) -> int:
        cl = self.guests[g].cl
        return self.cfg.cl if cl is None else cl

    @property
    def max_logical(self) -> int:
        return max(hi - lo for lo, hi in zip(self.logical_offsets, self.logical_offsets[1:]))

    @property
    def max_hp(self) -> int:
        return max(hi - lo for lo, hi in zip(self.hp_offsets, self.hp_offsets[1:]))

    # ---- segment-offset tables ---------------------------------------
    # The two n_logical-sized tables are computed from the per-guest offsets
    # with jnp (an iota inside jit), never baked in as constants: at a
    # deployment fleet they would put hundreds of MB into every executable.
    def logical_pad_index(self) -> jax.Array:
        """int32[n_guests, max_logical]: row g = guest g's global logical ids,
        -1 padded past its segment (the ragged replacement for the old
        ``score.reshape(n_guests, logical_per_guest)``)."""
        lo = np.asarray(self.logical_offsets[:-1], np.int32)[:, None]
        n = np.diff(np.asarray(self.logical_offsets, np.int32))[:, None]
        col = jnp.arange(self.max_logical, dtype=jnp.int32)[None, :]
        return jnp.where(col < n, lo + col, -1)

    def hp_pad_index(self) -> np.ndarray:
        """int32[n_guests, max_hp]: row g = guest g's global GPA huge-page
        ids, -1 padded (a numpy trace-time constant)."""
        out = np.full((self.n_guests, self.max_hp), -1, np.int32)
        for g in range(self.n_guests):
            lo, hi = self.hp_range(g)
            out[g, : hi - lo] = np.arange(lo, hi, dtype=np.int32)
        return out

    def cl_per_logical(self) -> jax.Array:
        """int32[n_logical]: the effective CL of the guest owning each
        logical page (lets one global candidate mask honour per-guest CLs)."""
        cls = np.asarray(
            [self.guest_cl(g) for g in range(self.n_guests)], np.int32)
        starts = np.asarray(self.logical_offsets[1:-1], np.int32)
        ids = jnp.arange(self.cfg.n_logical, dtype=jnp.int32)
        return jnp.asarray(cls)[jnp.searchsorted(starts, ids, side="right")]

    def localize(self, local_ids: jax.Array) -> jax.Array:
        """Guest-local page ids ``int32[n_guests, k]`` -> combined-space ids
        (-1 padding passes through), via the per-guest segment offsets."""
        lo = jnp.asarray(
            np.asarray(self.logical_offsets[:-1], np.int32)
        )[:, None]
        return jnp.where(local_ids >= 0, local_ids + lo, -1)

    def canonical(self) -> "EngineSpec":
        """The spec with trace-identity fields (workload, seed) normalized
        away. Those fields never enter traced computation, but as part of the
        static jit key they would force a full recompile per seed/workload
        sweep -- the drivers dispatch on this canonical form instead."""
        guests = tuple(
            dataclasses.replace(g, workload="", seed=0) for g in self.guests
        )
        return dataclasses.replace(self, guests=guests)


# --------------------------------------------------------------------------
# construction
# --------------------------------------------------------------------------
def build(
    guests: tuple[GuestSpec, ...] | list,
    host: HostSpec = HostSpec(),
    *,
    mesh=None,
) -> tuple[EngineSpec, TieredState | PartitionedState]:
    """Build N (possibly ragged) guests over one shared host space.

    Returns the static :class:`EngineSpec` and the initial state: guest g's
    logical pages are identity-placed at the start of its own GPA segment
    (same layout the old ``make_multi_guest`` produced for symmetric guests).

    With a ``mesh`` (:func:`repro.core.sharding.guest_mesh`) the state is
    built in place in the host-partitioned layout that
    :func:`run_sharded` keeps: each device builds only its own guests' part,
    so a fleet larger than one device's memory never exists on one device.
    """
    guests = tuple(
        GuestSpec(n_logical=g) if isinstance(g, int) else g for g in guests
    )
    if not guests:
        raise ValueError("need at least one GuestSpec")
    hp_sizes = [g.hp_size(host.hp_ratio) for g in guests]
    logical_offsets = tuple(np.cumsum([0] + [g.n_logical for g in guests]).tolist())
    hp_offsets = tuple(np.cumsum([0] + hp_sizes).tolist())
    n_hp = hp_offsets[-1]
    total_need = sum(g.hp_need(host.hp_ratio) for g in guests)
    tv = None
    if host.tiers is not None:
        tv = tiers_mod.resolve(host.tiers, n_slots=n_hp, total_need=total_need)
        n_near = tv.boundaries[1]
    else:
        n_near = host.n_near or max(1, int(host.near_fraction * total_need))
    cfg = GpacConfig(
        n_logical=logical_offsets[-1],
        hp_ratio=host.hp_ratio,
        n_gpa_hp=n_hp,
        n_near=min(n_near, n_hp - 1),
        base_elems=host.base_elems,
        cl=host.cl,
        hot_threshold=host.hot_threshold,
        ipt_windows=host.ipt_windows,
        ipt_min_hits=host.ipt_min_hits,
        reconsolidate_cooldown=host.reconsolidate_cooldown,
        dtype=host.dtype,
    )
    spec = EngineSpec(cfg, guests, logical_offsets, hp_offsets, tiers=tv)
    if mesh is not None:
        from repro.core import sharding

        with jax.profiler.TraceAnnotation("tier.build"):
            return spec, sharding.init_partitioned(spec, mesh)
    return spec, init_engine_state(spec)


def init_engine_state(spec: EngineSpec) -> TieredState:
    """Identity-map each guest's logical pages into its own GPA segment."""
    cfg = spec.cfg
    gpt = np.full((cfg.n_logical,), -1, np.int64)
    rmap = np.full((cfg.n_gpa,), -1, np.int64)
    for g, guest in enumerate(spec.guests):
        lo, hi = spec.logical_range(g)
        hp_lo, _ = spec.hp_range(g)
        gpa = hp_lo * cfg.hp_ratio + np.arange(guest.n_logical)
        gpt[lo:hi] = gpa
        rmap[gpa] = np.arange(lo, hi)
    state = init_state(cfg)
    return asp.dataclasses_replace(
        state,
        gpt=jnp.asarray(gpt, jnp.int32),
        rmap=jnp.asarray(rmap, jnp.int32),
    )


def spec_from_config(
    cfg: GpacConfig, workload: str = "redis", seed: int = 0
) -> EngineSpec:
    """Single-guest spec spanning an existing config's whole space (the
    ``n_guests=1`` port of the old ``gpac.window_step`` callers)."""
    guest = GuestSpec(
        n_logical=cfg.n_logical, cl=cfg.cl, workload=workload, seed=seed
    )
    return EngineSpec(cfg, (guest,), (0, cfg.n_logical), (0, cfg.n_gpa_hp))


def symmetric_spec(
    cfg: GpacConfig, n_guests: int, cl: int | None = None
) -> EngineSpec:
    """Spec for N equal guests tiling an existing combined config (backs the
    deprecated ``MultiGuest``-era entry points)."""
    if cfg.n_logical % n_guests or cfg.n_gpa_hp % n_guests:
        raise ValueError(
            f"symmetric_spec: n_logical={cfg.n_logical} / n_gpa_hp="
            f"{cfg.n_gpa_hp} not divisible by n_guests={n_guests}"
        )
    lpg = cfg.n_logical // n_guests
    hpg = cfg.n_gpa_hp // n_guests
    guests = tuple(GuestSpec(n_logical=lpg, cl=cl) for _ in range(n_guests))
    return EngineSpec(
        cfg,
        guests,
        tuple(range(0, cfg.n_logical + 1, lpg)),
        tuple(range(0, cfg.n_gpa_hp + 1, hpg)),
    )


# --------------------------------------------------------------------------
# trace sources (the engine's input API, DESIGN.md §12)
# --------------------------------------------------------------------------
class TraceSource:
    """What drives the engine's windows. Two implementations:

    * :class:`ArrayTrace` -- a host-materialized packed trace
      ``int32[n_guests, n_windows, k]`` (the original input form; raw
      ndarrays passed to the drivers are wrapped in one automatically).
    * :class:`SynthTrace` -- on-device workload synthesis: each window's
      accesses are generated *inside* the scan body from the guests'
      (workload, seed) identities via ``repro.data.traces``' JAX window
      functions, so no ``[n_guests, n_windows, k]`` array ever exists --
      host or device. Per-device residency on a mesh is
      O(n_local_guests * accesses_per_window) plus the per-guest scatter
      tables, which is what lets pod-size guest counts run at all.

    Every source exposes ``n_windows`` (attribute or property).
    """


@dataclasses.dataclass(frozen=True)
class ArrayTrace(TraceSource):
    """A packed per-guest trace array (``pack_traces`` / ``guest_traces``
    output): ``int32[n_guests, n_windows, k]`` guest-local ids, -1 padded."""

    traces: np.ndarray

    def __post_init__(self):
        object.__setattr__(self, "traces", np.asarray(self.traces))

    @property
    def n_windows(self) -> int:
        return self.traces.shape[1]


@dataclasses.dataclass(frozen=True)
class SynthTrace(TraceSource):
    """On-device workload synthesis for ``n_windows`` windows of
    ``accesses_per_window`` accesses each.

    ``workloads`` / ``seeds`` default to the guests' own
    :class:`GuestSpec` identities at bind time; pass explicit tuples (one
    entry per guest) to override without rebuilding the spec. The distinct
    workload *set* is a static compile key (it selects the generator code);
    seeds and the per-guest workload assignment are traced, so sweeping
    them never recompiles.
    """

    n_windows: int
    accesses_per_window: int
    workloads: tuple[str, ...] | None = None
    seeds: tuple[int, ...] | None = None

    def __post_init__(self):
        if self.n_windows < 0:
            raise ValueError(f"n_windows must be >= 0, got {self.n_windows}")
        if self.accesses_per_window < 1:
            raise ValueError(
                f"accesses_per_window must be >= 1, got "
                f"{self.accesses_per_window}"
            )
        if self.workloads is not None:
            object.__setattr__(self, "workloads", tuple(self.workloads))
        if self.seeds is not None:
            object.__setattr__(self, "seeds", tuple(self.seeds))


def as_trace_source(x) -> TraceSource:
    """Coerce a driver input to a :class:`TraceSource` (arrays/lists wrap as
    :class:`ArrayTrace`)."""
    if isinstance(x, TraceSource):
        return x
    if isinstance(x, (np.ndarray, list, tuple)) or hasattr(x, "__array__"):
        return ArrayTrace(np.asarray(x))
    raise TypeError(
        f"expected a TraceSource or a packed trace array, got {type(x).__name__}"
    )


def _coerce_source(source, traces) -> TraceSource:
    """Resolve the driver input: the ``source`` positional (TraceSource or
    raw array) or the deprecated ``traces=`` keyword (warns and wraps)."""
    if traces is not None:
        if source is not None:
            raise TypeError("pass either a source or traces=, not both")
        import warnings

        warnings.warn(
            "the traces= keyword is deprecated; pass the trace source "
            "positionally (ArrayTrace(traces) or a SynthTrace)",
            DeprecationWarning,
            stacklevel=3,
        )
        return ArrayTrace(np.asarray(traces))
    if source is None:
        raise TypeError("run() needs a trace source (ArrayTrace / SynthTrace)")
    return as_trace_source(source)


def _bind_synth(spec: EngineSpec, synth: SynthTrace, n_shards: int = 1):
    """Bind a :class:`SynthTrace` to a spec's guests: the static
    :class:`repro.data.traces.SynthPlan` (distinct workload set + shapes)
    and the traced per-guest tables (seed, global guest id, workload index,
    size), padded to the mesh with no-op rows (``gid=-1`` emits -1
    accesses). Must run on the *pre-canonical* spec -- ``canonical()``
    blanks the workload/seed identities this reads."""
    from repro.data import traces as tr

    n_g = spec.n_guests
    workloads = synth.workloads or tuple(g.workload for g in spec.guests)
    seeds = synth.seeds if synth.seeds is not None else tuple(
        g.seed for g in spec.guests)
    if len(workloads) != n_g or len(seeds) != n_g:
        raise ValueError(
            f"SynthTrace workloads/seeds must have one entry per guest "
            f"(n_guests={n_g}), got {len(workloads)}/{len(seeds)}"
        )
    for name in workloads:
        tr.get_workload(name)  # fail fast, listing the live set
    wset = tuple(sorted(set(workloads)))
    plan = tr.SynthPlan(
        workload_set=wset,
        accesses_per_window=synth.accesses_per_window,
        hp_ratio=spec.cfg.hp_ratio,
        max_logical=spec.max_logical,
    )
    tables = dict(
        seeds=np.asarray(seeds, np.int32),
        gids=np.arange(n_g, dtype=np.int32),
        wid=np.asarray([wset.index(w) for w in workloads], np.int32),
        n_logical=np.asarray([g.n_logical for g in spec.guests], np.int32),
    )
    if n_shards > 1:
        from repro.core import sharding

        fills = dict(seeds=0, gids=-1, wid=-1, n_logical=1)
        tables = {
            k: sharding.pad_guest_rows(v, n_shards, fill=fills[k])
            for k, v in tables.items()
        }
    return plan, tables


# --------------------------------------------------------------------------
# trace helpers
# --------------------------------------------------------------------------
def pack_traces(per_guest: list[np.ndarray]) -> np.ndarray:
    """Stack ragged per-guest traces ``[n_windows, k_g]`` into one padded
    ``int32[n_guests, n_windows, k_max]`` array (-1 padding -- the engine
    treats negative ids as no-ops end to end)."""
    n_w = {t.shape[0] for t in per_guest}
    if len(n_w) != 1:
        raise ValueError(f"guests disagree on n_windows: {sorted(n_w)}")
    k = max(t.shape[1] for t in per_guest)
    out = np.full((len(per_guest), n_w.pop(), k), -1, np.int32)
    for g, t in enumerate(per_guest):
        out[g, :, : t.shape[1]] = t
    return out


def guest_traces(
    spec: EngineSpec,
    n_windows: int,
    accesses_per_window: int,
) -> np.ndarray:
    """Synthesize each guest's trace from its :class:`GuestSpec`
    workload/seed and pack them (``repro.data.traces`` numpy generators).

    Memoized over identical ``(workload, seed, n_logical)`` guests within
    the call: a symmetric fleet of N clones generates its trace once, not N
    times (the generators are deterministic per :class:`TraceSpec`, so
    sharing the array is exact). For pod-size fleets prefer
    :class:`SynthTrace` -- this host array is O(n_guests * n_windows * k).
    """
    from repro.data import traces as tr

    cache: dict[tr.TraceSpec, np.ndarray] = {}

    def one(g: GuestSpec) -> np.ndarray:
        ts = tr.TraceSpec(
            g.workload, n_logical=g.n_logical, hp_ratio=spec.cfg.hp_ratio,
            n_windows=n_windows, accesses_per_window=accesses_per_window,
            seed=g.seed)
        if ts not in cache:
            cache[ts] = tr.generate(ts)
        return cache[ts]

    return pack_traces([one(g) for g in spec.guests])


# --------------------------------------------------------------------------
# metric collector registry (on-device, runs inside the scan)
# --------------------------------------------------------------------------
_COLLECTORS: dict[str, Callable] = {}


def register_collector(name: str, fn: Callable | None = None):
    """Register an on-device metric collector ``fn(spec, state, window) ->
    dict[str, jax.Array]``; usable as ``@register_collector("name")``.

    ``window`` carries access-time values (``near_hits``/``far_hits`` per
    guest, resolved against the placement in effect when the access happened,
    like PEBS); ``state`` is the post-window state. Outputs are stacked along
    the window axis on device and cross to the host once per chunk.
    """
    if fn is None:
        return lambda f: register_collector(name, f)
    if name in _COLLECTORS:
        raise ValueError(f"metric collector {name!r} already registered")
    _COLLECTORS[name] = fn
    return fn


def get_collector(name: str) -> Callable:
    try:
        return _COLLECTORS[name]
    except KeyError:
        raise ValueError(
            f"unknown metric collector {name!r} (have {collectors()})"
        ) from None


def collectors() -> tuple[str, ...]:
    return tuple(_COLLECTORS)


def run_collectors(
    spec: "EngineSpec", state: "TieredState", window: dict,
    collect: tuple[str, ...],
) -> dict:
    """Run the requested collectors on a post-window state, rejecting
    colliding output keys (shared by the unsharded and sharded window
    bodies so both emit identical series and errors)."""
    out = {}
    for name in collect:
        emitted = get_collector(name)(spec, state, window)
        clash = set(emitted) & set(out)
        if clash:
            raise ValueError(
                f"collector {name!r} emits keys {sorted(clash)} already "
                f"produced by an earlier collector in {collect}"
            )
        out.update(emitted)
    return out


@register_collector("hits")
def _collect_hits(spec: EngineSpec, state: TieredState, window: dict) -> dict:
    """Per-guest near/far hit counts for this window (access-time tiers)."""
    return dict(near_hits=window["near_hits"], far_hits=window["far_hits"])


@register_collector("near_blocks")
def _collect_near_blocks(spec, state, window) -> dict:
    """Per-guest allocated blocks currently in the near tier: one padded
    segment gather-reduce (ragged replacement for the old uniform
    ``reshape(n_guests, hp_per_guest)`` sum)."""
    cfg = spec.cfg
    alloc = allocated_hp_mask(cfg, state)
    in_near = state.block_table < cfg.n_near
    hp_pad = jnp.asarray(spec.hp_pad_index())
    seg = (hp_pad >= 0) & (alloc & in_near)[jnp.maximum(hp_pad, 0)]
    return dict(near_blocks=seg.sum(axis=1))


@register_collector("snapshot")
def _collect_snapshot(spec, state, window) -> dict:
    """Host-space scalar metrics (``metrics.device_snapshot``): near usage,
    cumulative hit rate, and every running stats counter.

    Not composable with the ``hits`` collector: both emit ``near_hits`` /
    ``far_hits`` (cumulative host-wide here, per-guest per-window there) and
    the driver rejects colliding keys rather than silently overwrite. The
    key names are pinned by the ``gpac.run_windows`` shim's bit-for-bit
    contract with ``metrics.snapshot``; register a custom collector with
    prefixed names to combine both views.
    """
    return metrics.device_snapshot(spec.cfg, state)


@register_collector("tco")
def _collect_tco(spec, state, window) -> dict:
    """The TCO objective per window (``core.tiers.tco_metrics``): $-weighted
    resident GB of the post-tick placement, the per-tier AMAT of this
    window's accesses, and the raw per-tier block/hit vectors. Works on any
    spec -- without ``HostSpec.tiers`` it prices the legacy near/far split
    as a DRAM/NVMM pair."""
    tv = spec.tier_vector
    blocks = tiers_mod.tier_alloc_counts(spec.cfg, state, tv)
    return tiers_mod.tco_metrics(spec.cfg, tv, blocks, window["tier_hits"])


# --------------------------------------------------------------------------
# the one shared driver
# --------------------------------------------------------------------------
def _window(
    spec: EngineSpec,
    state: TieredState,
    accesses: jax.Array,  # int32[n_guests, k] guest-local ids, -1 padded
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
) -> tuple[TieredState, dict]:
    """Traceable body of one engine window: batched translate/record over all
    guests, one ragged batched GPAC pass, one host tier tick, window roll,
    then the requested collectors."""
    cfg = spec.cfg
    with layer("tier.translate"):
        ids = spec.localize(accesses)
        slot, _, valid = asp.translate(cfg, state, ids)
        window = dict(
            near_hits=(valid & (slot < cfg.n_near)).sum(axis=1),
            far_hits=(valid & (slot >= cfg.n_near)).sum(axis=1),
        )
    if "tco" in collect:
        with layer("tier.roll"):
            window["tier_hits"] = tiers_mod.tier_hit_counts(
                spec.tier_vector, slot, valid)
    with layer("tier.record"):
        state = asp.record_accesses(
            cfg, state, ids.reshape(-1), kernel_backend=spec.kernel_backend)
    if use_gpac:
        # all N guest daemons in one batched pass over the segment-offset
        # tables; disjoint segments make this bit-equal to N sequential
        # per-guest gpac_maintenance calls (see run_reference); the pass
        # scopes its own layers
        state = gpac.gpac_maintenance_ragged(spec, state, backend, max_batches)
    with layer("tier.tick"):
        state = tiering.strided_tick(
            cfg, state, policy, stride=spec.arbitration_stride, budget=budget,
            tiers=spec.tiers,
        )
    with layer("tier.roll"):
        state = telemetry.end_window(cfg, state)
        return state, run_collectors(spec, state, window, collect)


@partial(
    jax.jit,
    static_argnames=(
        "spec", "policy", "backend", "use_gpac", "max_batches", "budget", "collect",
    ),
)
def _step_impl(
    spec: EngineSpec,
    state: TieredState,
    accesses: jax.Array,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
) -> tuple[TieredState, dict]:
    return _window(
        spec, state, accesses, policy, backend, use_gpac, max_batches, budget, collect
    )


def step(
    spec: EngineSpec,
    state,  # TieredState, or a ChurnState for the steady-state stepper
    accesses: jax.Array,
    policy: str = "memtierd",
    backend: str = "ipt",
    use_gpac: bool = True,
    max_batches: int = 4,
    budget: int = 64,
    collect: tuple[str, ...] = ("hits", "near_blocks"),
    *,
    faults_row: dict | None = None,
    mesh=None,
    slack: int = 1,
    arbitration_stride: int | None = None,
) -> tuple:
    """One engine window (jitted single-window entry point).

    Handed a :class:`ChurnState` (from :func:`init_churn`) this dispatches
    to the steady-state stepper :func:`step_churn`: the carry persists the
    activity mask and pressure-controller state between calls, and
    ``faults_row`` injects this window's faults. A no-fault step loop over a
    ChurnState reproduces :func:`run` bit-for-bit."""
    if isinstance(state, ChurnState):
        return step_churn(
            spec, state, accesses, faults_row=faults_row, mesh=mesh,
            policy=policy, backend=backend, use_gpac=use_gpac,
            max_batches=max_batches, budget=budget, slack=slack,
            collect=tuple(collect), arbitration_stride=arbitration_stride,
        )
    if faults_row is not None or mesh is not None:
        raise TypeError(
            "faults_row/mesh need the steady-state stepper: pass a "
            "ChurnState carry (engine.init_churn)"
        )
    spec = _with_arbitration_stride(spec, arbitration_stride)
    return _step_impl(
        spec.canonical(), state, accesses, policy, backend, use_gpac,
        max_batches, budget, tuple(collect),
    )


@partial(
    jax.jit,
    static_argnames=(
        "spec", "policy", "backend", "use_gpac", "max_batches", "budget", "collect",
    ),
)
def _run_chunk(
    spec: EngineSpec,
    state: TieredState,
    chunk: jax.Array,  # int32[n_windows, n_guests, k]
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
) -> tuple[TieredState, dict]:
    def body(st, acc):
        return _window(
            spec, st, acc, policy, backend, use_gpac, max_batches, budget, collect
        )

    return jax.lax.scan(body, state, chunk)


@partial(
    jax.jit,
    static_argnames=(
        "spec", "plan", "policy", "backend", "use_gpac", "max_batches",
        "budget", "collect",
    ),
)
def _run_chunk_synth(
    spec: EngineSpec,
    plan,  # repro.data.traces.SynthPlan (static)
    state: TieredState,
    widx: jax.Array,  # int32[n_windows] absolute window indices
    tables: dict,  # traced per-guest rows (seeds/gids/wid/n_logical)
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    collect: tuple[str, ...],
) -> tuple[TieredState, dict]:
    """Scan-fused chunk with on-device synthesis: the scan carries window
    *indices*, and each window's accesses are generated inside the body
    (counter-based RNG keyed on the absolute index, so any chunking yields
    identical streams). No trace array exists at any scope wider than one
    window."""
    from repro.data import traces as tr

    setup = tr.synth_setup(plan, tables)

    def body(st, w):
        acc = tr.synth_accesses(plan, setup, w)
        return _window(
            spec, st, acc, policy, backend, use_gpac, max_batches, budget,
            collect,
        )

    return jax.lax.scan(body, state, widx)


def _round_wps(n_windows: int, windows_per_step: int, strict: bool) -> int:
    """Effective chunk size: ``windows_per_step`` rounded *down* to the
    nearest divisor of ``n_windows`` (0 or oversized = the whole run). A
    non-dividing chunk size would leave a shorter trailing chunk whose scan
    has a different shape -- one silent extra trace/compile per fresh
    process; ``strict=True`` keeps the requested size and pays it.

    Guard rail: when the best divisor is so small that rounding would more
    than double the number of chunks (worst case ``n_windows`` prime ->
    divisor 1 -> one dispatch/transfer per window), the requested size is
    kept instead -- the one extra compile is far cheaper than per-window
    host round-trips."""
    wps = n_windows if windows_per_step <= 0 else min(windows_per_step, n_windows)
    if strict:
        return wps
    div = wps
    while n_windows % div:
        div -= 1
    if n_windows // div > 2 * (-(-n_windows // wps)):
        return wps
    return div


def _validate_run_args(spec: EngineSpec, source: TraceSource, collect) -> tuple:
    if isinstance(source, ArrayTrace):
        traces = source.traces
        if traces.ndim != 3 or traces.shape[0] != spec.n_guests:
            raise ValueError(
                f"traces must be [n_guests={spec.n_guests}, n_windows, k], "
                f"got {traces.shape}"
            )
    collect = tuple(collect)
    for name in collect:
        get_collector(name)  # fail fast on unknown collectors
    return collect


def _drive_chunks(
    chunk_fn, state: TieredState, by_window: np.ndarray, wps: int,
    collect: tuple[str, ...],
) -> tuple[TieredState, dict]:
    """Shared chunk loop of :func:`run` / :func:`run_sharded`: one jitted
    scan per chunk, one host transfer per chunk, concatenated host series.
    ``collect=()`` is explicit: the simulation still runs (the state
    advances) but no collectors execute and the series is ``{}``."""
    n_w = by_window.shape[0]
    chunks = []
    for s in range(0, n_w, wps):
        with jax.profiler.TraceAnnotation("tier.prepare"):
            chunk = jnp.asarray(by_window[s : s + wps])
        with jax.profiler.TraceAnnotation("tier.dispatch"):
            state, out = chunk_fn(state, chunk)
        chunks.append(out)
    if not collect:
        return state, {}
    with jax.profiler.TraceAnnotation("tier.fetch"):
        series = {
            k: np.concatenate([np.asarray(c[k]) for c in chunks])
            for k in chunks[0]
        }
    return state, series


def _with_kernel_backend(spec: EngineSpec, kernel_backend: str | None) -> EngineSpec:
    """Fold a driver-level ``kernel_backend=`` override into the spec (the
    field is static, so the override keys its own jit cache entries).
    ``None`` keeps the spec's own choice; names validate eagerly."""
    if kernel_backend is None:
        return spec
    kernels_registry.resolve_backend(kernel_backend)  # fail fast on typos
    return dataclasses.replace(spec, kernel_backend=kernel_backend)


def _with_arbitration_stride(
    spec: EngineSpec, arbitration_stride: int | None,
) -> EngineSpec:
    """Fold a driver-level ``arbitration_stride=`` override into the spec
    (static field -> its own jit cache entries, like ``kernel_backend``).
    ``None`` keeps the spec's own stride; the result always validates, so a
    spec hand-built with a bad stride fails fast at any driver."""
    if arbitration_stride is not None:
        spec = dataclasses.replace(
            spec, arbitration_stride=int(arbitration_stride))
    s = spec.arbitration_stride
    if not isinstance(s, int) or isinstance(s, bool) or s < 1:
        raise ValueError(
            f"arbitration_stride must be an int >= 1, got {s!r}")
    return spec


def run(
    spec: EngineSpec,
    state: TieredState,
    source: TraceSource | np.ndarray | None = None,
    *,
    traces: np.ndarray | None = None,  # deprecated keyword (warns and wraps)
    policy: str = "memtierd",
    backend: str = "ipt",
    use_gpac: bool = True,
    max_batches: int = 4,
    budget: int = 64,
    windows_per_step: int = 0,
    strict_wps: bool = False,
    collect: tuple[str, ...] = ("hits", "near_blocks"),
    kernel_backend: str | None = None,
    arbitration_stride: int | None = None,
) -> tuple[TieredState, dict]:
    """Drive every window through the scan-fused engine.

    ``source`` is a :class:`TraceSource`: an :class:`ArrayTrace` (raw packed
    arrays are wrapped automatically) replays a host-materialized trace; a
    :class:`SynthTrace` generates each window's accesses on device inside
    the scan, so nothing of shape ``[n_guests, n_windows, k]`` ever exists.
    The deprecated ``traces=`` keyword still works (warns and wraps).

    The window loop is a device-side ``lax.scan``; ``windows_per_step``
    bounds how many windows each jitted step fuses (0 = the whole run in one
    step) and the stacked collector series cross to the host **once per
    chunk**. A ``windows_per_step`` that does not divide ``n_windows`` is
    rounded down to the nearest divisor, so every chunk shares one scan
    shape and one compilation (unless that would more than double the chunk
    count -- e.g. a prime ``n_windows`` -- where the requested size wins);
    pass ``strict_wps=True`` to always keep the exact requested size (the
    shorter trailing chunk then pays one extra trace/compile per fresh
    process).

    Returns ``(state, series)`` where ``series[k]`` is a host numpy array of
    shape ``[n_windows, ...]`` per collector output; empty dict when the
    source has no windows or ``collect`` is empty.
    """
    with jax.profiler.TraceAnnotation("tier.prepare"):
        source = _coerce_source(source, traces)
    spec = _with_kernel_backend(spec, kernel_backend)
    spec = _with_arbitration_stride(spec, arbitration_stride)
    collect = _validate_run_args(spec, source, collect)
    n_w = source.n_windows
    if n_w == 0:
        return state, {}
    if isinstance(source, SynthTrace):
        plan, tables = _bind_synth(spec, source)  # pre-canonical: reads ids
        spec = spec.canonical()
        jt = {k: jnp.asarray(v) for k, v in tables.items()}
        by_window = np.arange(n_w, dtype=np.int32)

        def chunk_fn(st, widx):
            return _run_chunk_synth(
                spec, plan, st, widx, jt, policy, backend, use_gpac,
                max_batches, budget, collect,
            )
    else:
        spec = spec.canonical()  # don't recompile across seed/workload sweeps
        with jax.profiler.TraceAnnotation("tier.prepare"):
            by_window = np.ascontiguousarray(
                np.transpose(source.traces, (1, 0, 2)))

        def chunk_fn(st, chunk):
            return _run_chunk(
                spec, st, chunk, policy, backend, use_gpac, max_batches,
                budget, collect,
            )

    wps = _round_wps(n_w, windows_per_step, strict_wps)
    return _drive_chunks(chunk_fn, state, by_window, wps, collect)


# collectors with a host-partitioned implementation (repro.core.sharding
# computes them from the per-window candidate exchange without ever
# materializing the replicated host state)
HOST_SHARDED_COLLECTORS = ("hits", "near_blocks", "snapshot", "tco")


def run_sharded(
    spec: EngineSpec,
    state: TieredState | PartitionedState,
    source: TraceSource | np.ndarray | None = None,
    *,
    traces: np.ndarray | None = None,  # deprecated keyword (warns and wraps)
    mesh=None,
    host_sharded: bool = True,
    policy: str = "memtierd",
    backend: str = "ipt",
    use_gpac: bool = True,
    max_batches: int = 4,
    budget: int = 64,
    windows_per_step: int = 0,
    strict_wps: bool = False,
    collect: tuple[str, ...] = ("hits", "near_blocks"),
    kernel_backend: str | None = None,
    arbitration_stride: int | None = None,
) -> tuple[TieredState | PartitionedState, dict]:
    """:func:`run`, device-sharded over the guest axis (DESIGN.md §9, §11).

    ``mesh`` is a 1-D ``"guest"`` mesh (:func:`repro.core.sharding.
    guest_mesh`); ``None`` builds one over every local device and **falls
    back to** :func:`run` on a single-device host -- the same no-mesh
    degradation as ``models.dist.Dist``. Guest counts that do not divide the
    mesh are padded with no-op segment rows. Results are bit-for-bit equal
    to :func:`run` on any mesh size: per-guest phases shard over disjoint
    segments, the access histograms and GPAC writes merge through exact
    integer / bit-pattern collectives, and the host near-tier tick is
    deterministic either way.

    ``host_sharded=True`` (the default) partitions the state itself
    (DESIGN.md §11): each device holds only its own guests and its own
    range of the block table, host telemetry and payload, scores
    promotion/demotion locally, and one arbitration exchange per window
    resolves cross-partition contention bit-for-bit against the replicated
    tick -- per-device state bytes scale ~1/n_devices. Handed a
    :class:`PartitionedState` (``build(..., mesh=mesh)``, or a previous
    call's result) it returns one, and nothing replicated is ever built:
    the path for fleets larger than one device. Handed a
    :class:`TieredState` it lays it out partitioned once on entry
    (``sharding.partition_state``) and gathers the result once on exit
    (``sharding.gather_state``), for small fleets and tests. It requires a
    host-partitioned tick for ``policy`` (``tiering.sharded_ticks()``) and
    host-sharded collectors (:data:`HOST_SHARDED_COLLECTORS`);
    ``host_sharded=False`` keeps the replicated host state and supports any
    registered policy/collector.

    Accepts any :class:`TraceSource`. A :class:`SynthTrace` synthesizes each
    device's *local* guests' accesses on that device (keys fold in the
    global guest id, so the streams are bit-identical to the single-device
    driver and mesh-padding rows emit -1 no-ops); an :class:`ArrayTrace` is
    padded and sharded over the guest axis as before.
    """
    from repro.core import sharding

    source = _coerce_source(source, traces)
    spec = _with_kernel_backend(spec, kernel_backend)
    spec = _with_arbitration_stride(spec, arbitration_stride)
    partitioned = isinstance(state, PartitionedState)
    if partitioned and (mesh is None or not host_sharded):
        raise TypeError(
            "a PartitionedState runs only host_sharded, on the mesh it was "
            "laid out for: pass mesh=")
    if mesh is None:
        mesh = sharding.guest_mesh()
    if mesh is None:
        return run(
            spec, state, source, policy=policy, backend=backend,
            use_gpac=use_gpac, max_batches=max_batches, budget=budget,
            windows_per_step=windows_per_step, strict_wps=strict_wps,
            collect=collect,
        )
    collect = _validate_run_args(spec, source, collect)
    n_w = source.n_windows
    if n_w == 0:
        return state, {}
    n_shards = sharding.mesh_size(mesh)
    if isinstance(source, SynthTrace):
        plan, synth_tables = _bind_synth(spec, source, n_shards)
        by_window = np.arange(n_w, dtype=np.int32)
    else:
        plan, synth_tables = None, None
        padded = sharding.pad_guest_rows(source.traces, n_shards)
        by_window = np.ascontiguousarray(np.transpose(padded, (1, 0, 2)))
    spec = spec.canonical()

    if host_sharded:
        unsupported = tuple(
            c for c in collect if c not in HOST_SHARDED_COLLECTORS
        )
        if unsupported:
            raise ValueError(
                f"collectors {unsupported} have no host-sharded "
                f"implementation (host-sharded collectors: "
                f"{HOST_SHARDED_COLLECTORS}); pass host_sharded=False to "
                f"run them on the replicated host state"
            )
        tiering.sharded_tick_fns(policy)  # fail fast on unsupported policies
        stride = spec.arbitration_stride
        if stride > 1:
            # the host-partitioned driver batches the candidate exchange to
            # one collective per stride *group*, so groups must tile every
            # chunk and start on an arbitration boundary (fresh states do:
            # epoch 0); the replicated paths gate on the carried epoch and
            # have no such constraint
            if n_w % stride:
                raise ValueError(
                    f"host_sharded arbitration_stride={stride} must divide "
                    f"the run's n_windows={n_w}")
            epoch = int(np.asarray(state.epoch).reshape(-1)[0])
            if epoch % stride:
                raise ValueError(
                    f"host_sharded arbitration_stride={stride} needs the "
                    f"state's epoch ({epoch}) on an arbitration boundary "
                    f"(epoch % stride == 0); pass host_sharded=False to "
                    f"resume mid-stride")
        part, tables = sharding.host_tables(spec, n_shards)
        if partitioned:
            if sharding.n_shards_of(state) != n_shards or (
                    state.gpt.shape[0] != n_shards * part.l_loc):
                raise ValueError(
                    "the PartitionedState was laid out for another spec or "
                    "mesh")
        else:
            state = sharding.partition_state(spec, mesh, state)

        def chunk_fn(st, chunk):
            return sharding.run_chunk_host_sharded(
                spec, mesh, st, chunk, tables, policy=policy,
                backend=backend, use_gpac=use_gpac, max_batches=max_batches,
                budget=budget, collect=collect, plan=plan,
                synth_tables=synth_tables,
            )
    else:
        tables = sharding.guest_tables(spec, n_shards)

        def chunk_fn(st, chunk):
            return sharding.run_chunk_sharded(
                spec, mesh, st, chunk, tables, policy=policy,
                backend=backend, use_gpac=use_gpac, max_batches=max_batches,
                budget=budget, collect=collect, plan=plan,
                synth_tables=synth_tables,
            )

    wps = _round_wps(n_w, windows_per_step, strict_wps)
    if host_sharded and spec.arbitration_stride > 1 and (
            wps % spec.arbitration_stride):
        raise ValueError(
            f"host_sharded arbitration_stride={spec.arbitration_stride} "
            f"must divide the chunk size (windows_per_step resolved to "
            f"{wps}); pick a multiple of the stride")
    state, series = _drive_chunks(chunk_fn, state, by_window, wps, collect)
    if host_sharded and not partitioned:
        state = sharding.gather_state(spec, mesh, state)
    return state, series


def run_series(
    spec: EngineSpec,
    state: TieredState,
    source: TraceSource | np.ndarray | None = None,
    tier_pair: str = "dram_nvmm",
    mesh=None,
    *,
    traces: np.ndarray | None = None,  # deprecated keyword (warns and wraps)
    **kw,
) -> tuple[TieredState, dict]:
    """:func:`run` + the per-VM time series the at-scale figures plot
    (near blocks, per-window hit rate, modeled throughput). Accepts any
    :class:`TraceSource` (raw packed arrays wrap as :class:`ArrayTrace`;
    the deprecated ``traces=`` keyword warns and wraps, as in :func:`run`).
    Passing a ``mesh`` drives the windows through :func:`run_sharded`
    instead (the at-scale figures shard their guest axis end-to-end this
    way; ``host_sharded=`` threads through and is dropped on the no-mesh
    path)."""
    n_g = spec.n_guests
    source = _coerce_source(source, traces)
    _validate_run_args(spec, source, ())  # shape errors before n_windows
    host_sharded = kw.pop("host_sharded", True)
    if source.n_windows == 0:
        return state, dict(
            near_blocks=np.zeros((0, n_g), np.int64),
            hit_rate=np.zeros((0, n_g)),
            throughput=np.zeros((0, n_g)),
        )
    driver = (
        run if mesh is None
        else partial(run_sharded, mesh=mesh, host_sharded=host_sharded)
    )
    state, out = driver(
        spec, state, source, collect=("hits", "near_blocks"), **kw
    )
    nh = out["near_hits"].astype(np.float64)
    fh = out["far_hits"].astype(np.float64)
    hit_rate, throughput = metrics.throughput_from_hits(nh, fh, tier_pair)
    return state, dict(
        near_blocks=out["near_blocks"].astype(np.int64),
        hit_rate=hit_rate,
        throughput=throughput,
    )


# --------------------------------------------------------------------------
# steady-state churn engine (DESIGN.md §13)
# --------------------------------------------------------------------------
# per-window series every churn driver emits alongside the collectors
_CHURN_SERIES = ("active", "near_cap", "pressure")


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("state", "active", "window", "near_cap", "pressure", "engaged"),
    meta_fields=(),
)
@dataclasses.dataclass
class ChurnState:
    """The steady-state stepper's carry: the tiered state plus the ring of
    churn bookkeeping that persists *between* driver calls (DESIGN.md §13).

    ``active`` is the guest-axis activity mask: the compiled geometry never
    changes, lanes just flip active/inactive -- an inactive lane contributes
    zero accesses, holds zero blocks and is excluded from arbitration.
    ``window`` is the absolute index of the next window to run (the synth
    RNG and fault schedules are keyed on it, so a stepper resumed at window
    ``w`` continues the exact streams a straight run would produce).
    ``near_cap`` / ``pressure`` / ``engaged`` carry the pressure controller
    (``tiering.pressure_tick``) across windows.
    """

    state: TieredState
    active: jax.Array  # bool[n_guests] lane activity mask
    window: jax.Array  # int32[] absolute index of the next window
    near_cap: jax.Array  # int32[] effective near capacity in force
    pressure: jax.Array  # int32[] consecutive pressure-engaged windows
    engaged: jax.Array  # bool[] pressure-controller hysteresis latch


def init_churn(
    spec: EngineSpec,
    state: TieredState | None = None,
    active: np.ndarray | None = None,
    window: int = 0,
) -> ChurnState:
    """Wrap an engine state for the steady-state stepper.

    With the defaults (fresh identity state, all lanes active, window 0)
    a no-fault churn run is bit-identical to :func:`run` from the same
    state (INV-CHURN-NOOP-EXACT). ``active`` may mark lanes inactive at
    boot -- their segments are reclaimed immediately (crash semantics), so
    they hold no blocks until a restart fault boots them.
    """
    if state is None:
        state = init_engine_state(spec)
    n_g = spec.n_guests
    act = (np.ones((n_g,), bool) if active is None
           else np.asarray(active, bool))
    if act.shape != (n_g,):
        raise ValueError(
            f"active mask must be bool[n_guests={n_g}], got shape {act.shape}"
        )
    cs = ChurnState(
        state=state,
        active=jnp.asarray(act),
        window=jnp.asarray(int(window), jnp.int32),
        near_cap=jnp.asarray(spec.cfg.n_near, jnp.int32),
        pressure=jnp.zeros((), jnp.int32),
        engaged=jnp.zeros((), bool),
    )
    if not act.all():
        st, act2 = faults_mod.apply_guest_faults(
            spec.canonical(), cs.state, jnp.ones((n_g,), bool),
            jnp.asarray(~act), jnp.zeros((n_g,), bool),
        )
        cs = dataclasses.replace(cs, state=st, active=act2)
    return cs


def _churn_window(
    spec: EngineSpec,
    cs: ChurnState,
    accesses: jax.Array,  # int32[n_guests, k] guest-local ids, -1 padded
    frow: dict,  # this window's fault row (crash/restart/near_cap/drop)
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    slack: int,
    collect: tuple[str, ...],
) -> tuple[ChurnState, dict]:
    """Traceable body of one churn window: :func:`_window` with the fault
    row applied first, inactive lanes' accesses masked to -1 (value-exact:
    the engine treats negative ids as no-ops end to end), telemetry gated by
    the dropout bit, and the pressure controller run after the policy tick.

    With an all-no-op fault row and an all-active mask every extra operation
    is value-exact identity, so the scan over windows stays bit-identical to
    :func:`run` (INV-CHURN-NOOP-EXACT). The telemetry write uses the
    histogram formulation unconditionally (``asp.access_histogram`` +
    ``asp.apply_access_histogram``), the same bit-identical path the sharded
    driver always takes, so the dropout gate is a single integer multiply.
    """
    cfg = spec.cfg
    state, active = faults_mod.apply_guest_faults(
        spec, cs.state, cs.active, frow["crash"], frow["restart"]
    )
    near_cap = jnp.minimum(frow["near_cap"], jnp.int32(cfg.n_near))
    with layer("tier.translate"):
        acc = jnp.where(active[:, None], accesses, -1)
        ids = spec.localize(acc)
        slot, _, valid = asp.translate(cfg, state, ids)
        window = dict(
            near_hits=(valid & (slot < cfg.n_near)).sum(axis=1),
            far_hits=(valid & (slot >= cfg.n_near)).sum(axis=1),
        )
    if "tco" in collect:
        with layer("tier.roll"):
            window["tier_hits"] = tiers_mod.tier_hit_counts(
                spec.tier_vector, slot, valid)
    kb = spec.kernel_backend
    with layer("tier.record"):
        keep = jnp.where(frow["drop"], 0, 1).astype(jnp.int32)
        state = asp.apply_access_histogram(
            cfg, state, asp.access_histogram(cfg, ids, valid, kb) * keep, kb
        )
    if use_gpac:
        state = gpac.gpac_maintenance_ragged(spec, state, backend, max_batches)
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


@partial(
    jax.jit,
    static_argnames=(
        "spec", "policy", "backend", "use_gpac", "max_batches", "budget",
        "slack", "collect",
    ),
)
def _churn_chunk(
    spec: EngineSpec,
    cs: ChurnState,
    chunk: jax.Array,  # int32[n_windows, n_guests, k]
    crash: jax.Array,  # bool[n_windows, n_guests]
    restart: jax.Array,  # bool[n_windows, n_guests]
    near_cap: jax.Array,  # int32[n_windows]
    drop: jax.Array,  # bool[n_windows]
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    slack: int,
    collect: tuple[str, ...],
) -> tuple[ChurnState, dict]:
    def body(c, xs):
        acc, frow = xs
        return _churn_window(
            spec, c, acc, frow, policy, backend, use_gpac, max_batches,
            budget, slack, collect,
        )

    xs = (chunk, dict(crash=crash, restart=restart, near_cap=near_cap, drop=drop))
    return jax.lax.scan(body, cs, xs)


@partial(
    jax.jit,
    static_argnames=(
        "spec", "plan", "policy", "backend", "use_gpac", "max_batches",
        "budget", "slack", "collect",
    ),
)
def _churn_chunk_synth(
    spec: EngineSpec,
    plan,  # repro.data.traces.SynthPlan (static)
    cs: ChurnState,
    widx: jax.Array,  # int32[n_windows] absolute window indices
    tables: dict,  # traced per-guest rows (seeds/gids/wid/n_logical)
    crash: jax.Array,
    restart: jax.Array,
    near_cap: jax.Array,
    drop: jax.Array,
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
    slack: int,
    collect: tuple[str, ...],
) -> tuple[ChurnState, dict]:
    """Churn chunk with on-device synthesis: window accesses are generated
    inside the scan from the *absolute* window index, so a stepper resumed
    at any window continues the exact access streams (counter-based RNG)."""
    from repro.data import traces as tr

    setup = tr.synth_setup(plan, tables)

    def body(c, xs):
        w, frow = xs
        acc = tr.synth_accesses(plan, setup, w)
        return _churn_window(
            spec, c, acc, frow, policy, backend, use_gpac, max_batches,
            budget, slack, collect,
        )

    xs = (widx, dict(crash=crash, restart=restart, near_cap=near_cap, drop=drop))
    return jax.lax.scan(body, cs, xs)


def _resolve_fault_tables(
    spec: EngineSpec, cs: ChurnState, faults, n_windows: int, start: int,
):
    """The dense fault rows for this driver call: an explicit schedule
    compiles against the physical ``n_near`` (its capacity step function is
    absolute); ``faults=None`` keeps the carried effective capacity (a
    shrink injected by an earlier call stays in force across no-fault
    calls); precompiled :class:`repro.core.faults.FaultTables` must match
    the run's exact window range (replayability guard)."""
    cfg = spec.cfg
    if faults is None:
        return faults_mod.no_faults(spec.n_guests).tables(
            n_windows, int(np.asarray(cs.near_cap)), start=start
        )
    if isinstance(faults, faults_mod.FaultSchedule):
        if faults.n_guests != spec.n_guests:
            raise ValueError(
                f"fault schedule is for {faults.n_guests} guests, spec has "
                f"{spec.n_guests}"
            )
        return faults.tables(n_windows, cfg.n_near, start=start)
    if isinstance(faults, faults_mod.FaultTables):
        if (faults.n_windows != n_windows or faults.n_guests != spec.n_guests
                or faults.start != start):
            raise ValueError(
                f"fault tables cover windows [{faults.start}, "
                f"{faults.start + faults.n_windows}) x {faults.n_guests} "
                f"guests; this run is windows [{start}, {start + n_windows})"
                f" x {spec.n_guests}"
            )
        return faults
    raise TypeError(
        f"faults must be a FaultSchedule, FaultTables or None, got "
        f"{type(faults).__name__}"
    )


def run_churn(
    spec: EngineSpec,
    cs: ChurnState,
    source: TraceSource | np.ndarray | None = None,
    *,
    faults=None,  # FaultSchedule | FaultTables | None
    mesh=None,
    policy: str = "memtierd",
    backend: str = "ipt",
    use_gpac: bool = True,
    max_batches: int = 4,
    budget: int = 64,
    slack: int = 1,
    windows_per_step: int = 0,
    strict_wps: bool = False,
    collect: tuple[str, ...] = ("hits", "near_blocks"),
    kernel_backend: str | None = None,
    arbitration_stride: int | None = None,
) -> tuple[ChurnState, dict]:
    """Drive ``source.n_windows`` windows of the steady-state churn engine.

    Same scan-fused driver as :func:`run`, but the carry is a
    :class:`ChurnState` and a deterministic fault schedule rides the scan as
    dense per-window rows (``repro.core.faults``): guests crash/restart
    mid-run through the activity mask (no recompile -- the compiled
    geometry is static), the near tier shrinks via the pressure controller,
    and telemetry windows drop. Fault scenarios are bit-reproducible across
    ``windows_per_step`` chunkings and meshes; with ``faults=None`` and an
    all-active mask the run is bit-identical to :func:`run`
    (INV-CHURN-NOOP-EXACT).

    A :class:`SynthTrace` source keys each window's synthesis on the
    *absolute* window index carried in ``cs.window``, so repeated
    ``run_churn`` calls continue the exact access streams of one long run.
    ``mesh`` shards the guest axis exactly like :func:`run_sharded` with
    ``host_sharded=False`` (fault rows are replicated; the host-partitioned
    near tier does not support the churn carry -- pass ``mesh=None`` or a
    plain mesh, never ``host_sharded=True``).

    Returns ``(cs, series)``; beyond the collectors the series always
    carries the churn channels ``active`` (bool[n_windows, n_guests]),
    ``near_cap`` and ``pressure`` (per window).
    """
    if not isinstance(cs, ChurnState):
        raise TypeError(
            f"run_churn needs a ChurnState carry (init_churn), got "
            f"{type(cs).__name__}"
        )
    source = _coerce_source(source, None)
    spec = _with_kernel_backend(spec, kernel_backend)
    spec = _with_arbitration_stride(spec, arbitration_stride)
    collect = _validate_run_args(spec, source, collect)
    n_w = source.n_windows
    if n_w == 0:
        return cs, {}
    w0 = int(np.asarray(cs.window))
    ft = _resolve_fault_tables(spec, cs, faults, n_w, w0)
    if mesh is not None:
        from repro.core import sharding

        n_shards = sharding.mesh_size(mesh)
    if isinstance(source, SynthTrace):
        plan, synth_tables = _bind_synth(
            spec, source, n_shards if mesh is not None else 1
        )
        by_window = np.arange(w0, w0 + n_w, dtype=np.int32)
    else:
        plan, synth_tables = None, None
        traces = source.traces
        if mesh is not None:
            traces = sharding.pad_guest_rows(traces, n_shards)
        by_window = np.ascontiguousarray(np.transpose(traces, (1, 0, 2)))
    spec = spec.canonical()

    if mesh is not None:
        tables = sharding.guest_tables(spec, n_shards)

        def chunk_fn(c, win, crash, restart, cap, drop):
            return sharding.run_chunk_churn_sharded(
                spec, mesh, c, win, tables, crash=crash, restart=restart,
                near_cap=cap, drop=drop, policy=policy, backend=backend,
                use_gpac=use_gpac, max_batches=max_batches, budget=budget,
                slack=slack, collect=collect, plan=plan,
                synth_tables=synth_tables,
            )
    elif plan is not None:
        jt = {k: jnp.asarray(v) for k, v in synth_tables.items()}

        def chunk_fn(c, win, crash, restart, cap, drop):
            return _churn_chunk_synth(
                spec, plan, c, win, jt, crash, restart, cap, drop, policy,
                backend, use_gpac, max_batches, budget, slack, collect,
            )
    else:

        def chunk_fn(c, win, crash, restart, cap, drop):
            return _churn_chunk(
                spec, c, win, crash, restart, cap, drop, policy, backend,
                use_gpac, max_batches, budget, slack, collect,
            )

    wps = _round_wps(n_w, windows_per_step, strict_wps)
    chunks = []
    for s in range(0, n_w, wps):
        sl = slice(s, s + wps)
        cs, out = chunk_fn(
            cs, jnp.asarray(by_window[sl]), jnp.asarray(ft.crash[sl]),
            jnp.asarray(ft.restart[sl]), jnp.asarray(ft.near_cap[sl]),
            jnp.asarray(ft.drop[sl]),
        )
        chunks.append(out)
    series = {
        k: np.concatenate([np.asarray(c[k]) for c in chunks]) for k in chunks[0]
    }
    return cs, series


def step_churn(
    spec: EngineSpec,
    cs: ChurnState,
    accesses: jax.Array,  # int32[n_guests, k] guest-local ids, -1 padded
    *,
    faults_row: dict | None = None,
    mesh=None,
    policy: str = "memtierd",
    backend: str = "ipt",
    use_gpac: bool = True,
    max_batches: int = 4,
    budget: int = 64,
    slack: int = 1,
    collect: tuple[str, ...] = ("hits", "near_blocks"),
    arbitration_stride: int | None = None,
) -> tuple[ChurnState, dict]:
    """One churn window (the steady-state single-step entry point;
    :func:`step` dispatches here when handed a :class:`ChurnState`).

    ``faults_row`` injects this window's faults: optional keys ``crash`` /
    ``restart`` (bool[n_guests]), ``near_cap`` (int; defaults to the
    capacity already in force) and ``drop`` (bool). A no-fault step loop is
    bit-identical to :func:`run` / a single :func:`run_churn` call.
    """
    acc = np.asarray(accesses)
    if acc.ndim != 2 or acc.shape[0] != spec.n_guests:
        raise ValueError(
            f"accesses must be [n_guests={spec.n_guests}, k], got {acc.shape}"
        )
    row = dict(faults_row or {})
    unknown = set(row) - {"crash", "restart", "near_cap", "drop"}
    if unknown:
        raise ValueError(
            f"unknown faults_row keys {sorted(unknown)} (valid: crash, "
            f"restart, near_cap, drop)"
        )
    n_g = spec.n_guests
    crash = np.zeros((1, n_g), bool)
    crash[0] = np.asarray(row.get("crash", False), bool)
    restart = np.zeros((1, n_g), bool)
    restart[0] = np.asarray(row.get("restart", False), bool)
    cap = int(row.get("near_cap", np.asarray(cs.near_cap)))
    ft = faults_mod.FaultTables(
        start=int(np.asarray(cs.window)),
        crash=crash,
        restart=restart,
        near_cap=np.asarray([cap], np.int32),
        drop=np.asarray([bool(row.get("drop", False))]),
    )
    cs, series = run_churn(
        spec, cs, ArrayTrace(acc[:, None, :]), faults=ft, mesh=mesh,
        policy=policy, backend=backend, use_gpac=use_gpac,
        max_batches=max_batches, budget=budget, slack=slack, collect=collect,
        arbitration_stride=arbitration_stride,
    )
    return cs, {k: v[0] for k, v in series.items()}


# --------------------------------------------------------------------------
# sequential per-guest reference (the ragged equivalence oracle)
# --------------------------------------------------------------------------
def step_reference(
    spec: EngineSpec,
    state: TieredState,
    accesses: jax.Array,
    policy: str = "memtierd",
    backend: str = "ipt",
    use_gpac: bool = True,
    max_batches: int = 4,
    budget: int = 64,
) -> tuple[TieredState, dict]:
    """One window in the sequential formulation: each guest translates,
    records and runs its own GPAC daemon (confined via ``allow``/``hp_range``
    and its own CL) one after another. O(n_guests) trace cost -- kept only as
    the equivalence oracle for :func:`step` / :func:`run`."""
    return _step_reference_impl(
        spec.canonical(), state, accesses, policy, backend, use_gpac,
        max_batches, budget,
    )


@partial(
    jax.jit,
    static_argnames=("spec", "policy", "backend", "use_gpac", "max_batches", "budget"),
)
def _step_reference_impl(
    spec: EngineSpec,
    state: TieredState,
    accesses: jax.Array,  # int32[n_guests, k] guest-local ids, -1 padded
    policy: str,
    backend: str,
    use_gpac: bool,
    max_batches: int,
    budget: int,
) -> tuple[TieredState, dict]:
    cfg = spec.cfg
    near_hits, far_hits = [], []
    logical_idx = jnp.arange(cfg.n_logical, dtype=jnp.int32)
    hp_idx = jnp.arange(cfg.n_gpa_hp)
    for g in range(spec.n_guests):
        lo, _ = spec.logical_range(g)
        ids = jnp.where(accesses[g] >= 0, accesses[g] + lo, -1)
        slot, _, valid = asp.translate(cfg, state, ids)
        near_hits.append(jnp.where(valid & (slot < cfg.n_near), 1, 0).sum())
        far_hits.append(jnp.where(valid & (slot >= cfg.n_near), 1, 0).sum())
        state = asp.record_accesses(cfg, state, ids)
    if use_gpac:
        for g in range(spec.n_guests):
            lo, hi = spec.logical_range(g)
            allow = (logical_idx >= lo) & (logical_idx < hi)
            state = gpac.gpac_maintenance(
                cfg, state, backend, max_batches, spec.guest_cl(g),
                allow=allow, hp_range=spec.hp_range(g),
            )
    state = tiering.tick(cfg, state, policy, budget=budget, tiers=spec.tiers)
    alloc = allocated_hp_mask(cfg, state)
    in_near = state.block_table < cfg.n_near
    near_blocks = []
    for g in range(spec.n_guests):
        hp_lo, hp_hi = spec.hp_range(g)
        seg = (hp_idx >= hp_lo) & (hp_idx < hp_hi)
        near_blocks.append((seg & alloc & in_near).sum())
    out = dict(
        near_hits=jnp.stack(near_hits),
        far_hits=jnp.stack(far_hits),
        near_blocks=jnp.stack(near_blocks),
    )
    state = telemetry.end_window(cfg, state)
    return state, out


def run_reference(
    spec: EngineSpec,
    state: TieredState,
    traces: np.ndarray,
    **kw,
) -> tuple[TieredState, dict]:
    """Per-window python driver over :func:`step_reference` (one host sync
    per window): the equivalence oracle for :func:`run` with the default
    ``("hits", "near_blocks")`` collectors."""
    traces = np.asarray(traces)
    n_g, n_w, _ = traces.shape
    series = dict(
        near_hits=np.zeros((n_w, n_g), np.int32),
        far_hits=np.zeros((n_w, n_g), np.int32),
        near_blocks=np.zeros((n_w, n_g), np.int32),
    )
    for w in range(n_w):
        state, out = step_reference(spec, state, jnp.asarray(traces[:, w]), **kw)
        for k in series:
            series[k][w] = np.asarray(out[k])
    return state, series
