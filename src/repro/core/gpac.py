"""GPAC orchestration (paper Fig. 5): telemetry -> filter -> consolidate.

``gpac_maintenance`` is one guest daemon's periodic pass; ``window_step`` is
the full single-guest simulation step: accesses -> (optional GPAC) -> host
tier tick -> window roll. Host and guest layers only communicate through the
address space itself -- there is no API between them (design goal 1).

``gpac_maintenance_ragged`` runs N (possibly asymmetric) guest daemons as one
batched pass over an :class:`repro.core.engine.EngineSpec`'s segment-offset
tables. ``run_windows`` is now a thin shim over the one shared scan-fused
driver, :func:`repro.core.engine.run` (``run_windows_reference`` keeps the
seed per-window loop as the equivalence oracle).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import address_space as asp
from repro.core import consolidator, filter as pfilter, telemetry, tiering
from repro.core.scopes import layer
from repro.core.types import GpacConfig, TieredState


@partial(jax.jit, static_argnames=("cfg", "backend", "max_batches", "cl"))
def gpac_maintenance(
    cfg: GpacConfig,
    state: TieredState,
    backend: str = "ipt",
    max_batches: int = 8,
    cl: int | None = None,
    allow: jax.Array | None = None,
    hp_range: tuple | None = None,
) -> TieredState:
    """One guest-side GPAC pass: classify hotness, filter scattered hot pages,
    consolidate them batch-by-batch (<= hp_ratio pages per Algorithm-1 call).

    ``allow``/``hp_range`` confine the pass to one guest's logical pages and
    GPA segment in the multi-tenant simulation (each guest runs its own GPAC
    daemon over its own address space, as in the paper).
    """
    hot = telemetry.hot_mask(cfg, state, backend)
    batches, _ = pfilter.select_batches(cfg, state, hot, max_batches, cl, allow)
    return consolidator.consolidate_batches(cfg, state, batches, hp_range)


@partial(jax.jit, static_argnames=("spec", "backend", "max_batches"))
def gpac_maintenance_ragged(
    spec,  # repro.core.engine.EngineSpec
    state: TieredState,
    backend: str = "ipt",
    max_batches: int = 8,
) -> TieredState:
    """All N guest daemons' GPAC passes in one batched invocation, for
    ragged/asymmetric guests.

    The guests' logical and GPA segments (the spec's segment-offset tables)
    are disjoint and tile their spaces, so one hot-mask classification, one
    row-wise batched filter (:func:`repro.core.filter.select_batches_ragged`,
    honouring per-guest CLs) and ``max_batches`` guest-wide consolidation
    rounds (:func:`repro.core.consolidator.consolidate_batches_ragged`)
    reproduce N sequential :func:`gpac_maintenance` calls bit-for-bit -- with
    O(1) trace cost and ~n_guests x less classification/sort work."""
    cfg = spec.cfg
    # the segment tables are computed in the program: each in the layer
    # that reads it
    with layer("tier.score"):
        cl_per_logical = jnp.asarray(spec.cl_per_logical())
    with layer("tier.select"):
        pad_idx = jnp.asarray(spec.logical_pad_index())
    return gpac_maintenance_rows(
        cfg,
        state,
        backend,
        max_batches,
        cl_per_logical,
        pad_idx,
        jnp.asarray(spec.hp_pad_index()),
        spec.kernel_backend,
    )


def gpac_maintenance_rows(
    cfg: GpacConfig,
    state: TieredState,
    backend: str,
    max_batches: int,
    cl_per_logical: jax.Array,  # int32[n_logical]
    pad_idx: jax.Array,  # int32[n_rows, max_logical] logical segment rows
    hp_pad_idx: jax.Array,  # int32[n_rows, max_hp] GPA segment rows
    kernel_backend: str = "auto",
) -> TieredState:
    """GPAC passes for an arbitrary slice of guest segment rows.

    The hot-mask classification and candidate scoring are cheap elementwise
    passes over the **whole** logical space (in the sharded engine every
    device redoes them -- a deliberate trade: O(n_logical) elementwise work
    vs. an extra collective); only their values inside the given rows are
    ever *read*, and the expensive parts -- the row-wise top-k selection and
    the round-major consolidation -- are confined to those rows. The
    all-guests call (:func:`gpac_maintenance_ragged`) passes every row; the
    device-sharded engine passes only the rows a device owns -- segments are
    disjoint, so each device's pass *writes* disjoint state and the shard
    merge is exact. Each stage runs in its engine layer scope."""
    with layer("tier.classify"):
        hot = telemetry.hot_mask(cfg, state, backend)
    with layer("tier.score"):
        score = pfilter.candidate_score(
            cfg, state, hot, cl_per_logical, kernel_backend)
    with layer("tier.select"):
        batches = pfilter.select_batches_from_rows(
            cfg, score, pad_idx, max_batches, kernel_backend)
    with layer("tier.copy"):
        return consolidator.consolidate_rounds(
            cfg, state, batches, hp_pad_idx, kernel_backend)


def gpac_maintenance_batched(
    cfg: GpacConfig,
    state: TieredState,
    backend: str,
    max_batches: int,
    cl: int | None,
    n_guests: int,
    logical_per_guest: int,
    hp_per_guest: int,
) -> TieredState:
    """Deprecated symmetric wrapper over :func:`gpac_maintenance_ragged`."""
    from repro.core.engine import symmetric_spec

    if n_guests * logical_per_guest != cfg.n_logical:
        raise ValueError("guest logical segments must tile the logical space")
    if n_guests * hp_per_guest != cfg.n_gpa_hp:
        raise ValueError("guest GPA segments must tile the GPA space")
    spec = symmetric_spec(cfg, n_guests, cl=cl)
    return gpac_maintenance_ragged(spec, state, backend, max_batches)


@partial(
    jax.jit,
    static_argnames=("cfg", "policy", "backend", "use_gpac", "max_batches", "budget"),
)
def window_step(
    cfg: GpacConfig,
    state: TieredState,
    accesses: jax.Array,
    policy: str = "memtierd",
    backend: str = "ipt",
    use_gpac: bool = True,
    max_batches: int = 8,
    budget: int = 64,
) -> TieredState:
    """One telemetry window: record accesses, run GPAC (guest), run the host
    tiering tick (block-granular, GPAC-oblivious), roll the window."""
    state = asp.record_accesses(cfg, state, accesses)
    if use_gpac:
        state = gpac_maintenance(cfg, state, backend, max_batches)
    state = tiering.tick(cfg, state, policy, budget=budget)
    return telemetry.end_window(cfg, state)


def run_windows(
    cfg: GpacConfig,
    state: TieredState,
    trace: jax.Array,
    policy: str = "memtierd",
    backend: str = "ipt",
    use_gpac: bool = True,
    max_batches: int = 8,
    budget: int = 64,
    windows_per_step: int = 0,
) -> tuple[TieredState, list[dict]]:
    """Drive a (n_windows, accesses_per_window) single-guest trace on the
    shared scan-fused engine driver, collecting per-window metric snapshots.

    Deprecation shim: new code should call :func:`repro.core.engine.run`
    directly (``spec = engine.spec_from_config(cfg)``; the ``snapshot``
    collector reproduces this function's series). Semantics and chunking
    (``windows_per_step``, one host transfer per chunk) are the engine's;
    bit-for-bit equivalent to :func:`run_windows_reference` (the seed
    per-window loop).
    """
    import warnings

    import numpy as np

    from repro.core import engine, metrics

    warnings.warn(
        "gpac.run_windows is deprecated; use repro.core.engine.run with"
        " engine.spec_from_config(cfg) and the 'snapshot' collector",
        DeprecationWarning,
        stacklevel=2,
    )

    trace = np.asarray(trace)
    n_w = trace.shape[0]
    if n_w == 0:
        return state, []
    state, host = engine.run(
        engine.spec_from_config(cfg), state, trace[None],
        policy=policy, backend=backend, use_gpac=use_gpac,
        max_batches=max_batches, budget=budget,
        windows_per_step=windows_per_step, collect=("snapshot",),
    )
    series = [
        {
            k: (float(v[w]) if k in metrics.FLOAT_METRICS else int(v[w]))
            for k, v in host.items()
        }
        for w in range(n_w)
    ]
    return state, series


def run_windows_reference(
    cfg: GpacConfig,
    state: TieredState,
    trace: jax.Array,
    **kw,
) -> tuple[TieredState, list[dict]]:
    """Original python window loop (one host sync per window): the
    equivalence oracle for the scan-fused :func:`run_windows`."""
    from repro.core import metrics

    series = []
    for w in range(trace.shape[0]):
        state = window_step(cfg, state, trace[w], **kw)
        series.append(metrics.snapshot(cfg, state))
    return state, series
