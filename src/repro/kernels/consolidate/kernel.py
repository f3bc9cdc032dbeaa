"""Pallas TPU kernels for Algorithm 1's data copy: gather scattered base pages
into one dense huge-page region, and scatter a region back out.

TPU adaptation (DESIGN.md §2): the paper's per-page ``memcpy`` loop becomes a
scalar-prefetched grid over the transposed, lane-dense row table (see
``tiered_lookup.kernel`` for why the kernels work on ``rows.T``). The gather
is the embedding store's row gather. The scatter visits the region's slots
sorted by destination lane block, so each ``(d, 128)`` block of the
destination is loaded once, patched lane by lane in slot order, and written
back once; when two slots name the same row the later slot wins, as in a
sequential ``memcpy`` loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.tiered_lookup.kernel import LANES, gather_rows, select_lane


def consolidate_gather(
    src_rows: jax.Array,  # (n_rows, base_elems) flat [near;far] row space
    ids: jax.Array,  # int32 (hp_ratio,) source row per region slot (clamped)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Return dtype[hp_ratio, base_elems]: the dense region payload.

    ``ids`` must be pre-clamped to [0, n_rows); masking of padded slots is the
    wrapper's job (ops.consolidate_region), keeping the kernel branch-free.
    """
    return gather_rows(src_rows, ids, interpret=interpret)


def _scatter_kernel(slot_ref, dst_id_ref, blk_ref, region_ref, dst_ref, o_ref):
    s = pl.program_id(0)

    @pl.when((s == 0) | (blk_ref[s] != blk_ref[jnp.maximum(s - 1, 0)]))
    def _():  # first visit of this destination block
        o_ref[...] = dst_ref[...]

    dst = dst_id_ref[s]
    col = select_lane(region_ref[...], slot_ref[s] % LANES)
    iota = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    hit = (iota == dst % LANES) & (dst >= 0)
    o_ref[...] = jnp.where(hit, col, o_ref[...])


def consolidate_scatter(
    dst_rows: jax.Array,  # (n_rows, base_elems) flat row space to update
    region: jax.Array,  # (hp_ratio, base_elems) dense payload
    ids: jax.Array,  # int32 (hp_ratio,) destination row per slot, OOB = skip
    *,
    interpret: bool = False,
) -> jax.Array:
    """Scatter a dense region's rows back out to ``ids`` (the reverse move,
    used when a consolidated region is broken up again). Slots whose id is
    outside ``[0, n_rows)`` are skipped. Input/output aliased so the update
    is in-place on TPU."""
    n_rows, d = dst_rows.shape
    ids = ids.astype(jnp.int32)
    valid = (ids >= 0) & (ids < n_rows)
    # skipped slots (dst_id -1) sort first, onto block 0, and write nothing;
    # the stable sort keeps slot order among writes to one block
    blk = jnp.where(valid, ids // LANES, -1)
    slot = jnp.argsort(blk, stable=True).astype(jnp.int32)
    dst_id = jnp.where(valid, ids, -1)[slot]
    blk = jnp.maximum(blk[slot], 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(region.shape[0],),
        in_specs=[
            pl.BlockSpec((d, LANES), lambda s, sl, di, bl: (0, sl[s] // LANES)),
            pl.BlockSpec((d, LANES), lambda s, sl, di, bl: (0, bl[s])),
        ],
        out_specs=pl.BlockSpec((d, LANES), lambda s, sl, di, bl: (0, bl[s])),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, n_rows), dst_rows.dtype),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slot, dst_id, blk, region.T, dst_rows.T)
    return out.T
