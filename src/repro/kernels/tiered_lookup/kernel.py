"""Pallas TPU kernel: embedding-row gather through the GPAC translation.

The tiered embedding store keeps vocab rows in paged pools behind the
``gpt ∘ block_table`` two-level translation. At lookup time the *translation*
is two tiny int32 gathers (done in the wrapper, fused by XLA); the *payload*
gather is the hot spot: ``batch*seq`` rows of ``d_model`` floats streamed from
scattered HBM rows.

Layout: XLA lays a narrow ``(n_rows, d)`` array out column-major on TPU (the
row axis is the minor, lane axis), and the engine's payload pools
``(slots, hp_ratio, d)`` keep ``hp_ratio`` minor. So the kernel works on the
transposed view ``rows.T`` of shape ``(d, n_rows)``, which is the same bytes:
no relayout copy of the pool is made. The row ids are scalar-prefetched into
SMEM; grid step ``(i, j)`` fetches the ``(d, 128)`` lane block holding row
``ids[i*128 + j]`` (consecutive ids in one block fetch it once), selects that
lane, and writes it into lane ``j`` of the resident ``(d, 128)`` output block.
The selection runs on the integer bit pattern, so every value -- ``-0.0`` and
NaN payloads included -- is copied bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

LANES = 128


def _bits(x: jax.Array) -> jax.Array:
    width = jnp.dtype(x.dtype).itemsize * 8
    return jax.lax.bitcast_convert_type(x, jnp.dtype(f"int{width}"))


def select_lane(block: jax.Array, lane: jax.Array) -> jax.Array:
    """Column ``lane`` of a ``(d, 128)`` block as ``(d, 1)``, bit for bit."""
    bits = _bits(block)
    iota = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    col = jnp.where(iota == lane, bits, 0).sum(axis=1, keepdims=True)
    return jax.lax.bitcast_convert_type(col.astype(bits.dtype), block.dtype)


def _gather_kernel(ids_ref, x_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    col = select_lane(x_ref[...], ids_ref[i * LANES + j] % LANES)
    iota = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    o_ref[...] = jnp.where(iota == j, col, o_ref[...])


def gather_columns(
    rows_t: jax.Array,  # (d, n_rows): the transposed row table
    ids: jax.Array,  # int32 (k,) pre-clamped to [0, n_rows)
    *,
    interpret: bool = False,
) -> jax.Array:
    """dtype[d, k] = rows_t[:, ids]."""
    d = rows_t.shape[0]
    k = ids.shape[0]
    pad = (-k) % LANES
    ids = jnp.pad(ids.astype(jnp.int32), (0, pad))  # row 0: always in range
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((k + pad) // LANES, LANES),
        in_specs=[pl.BlockSpec(
            (d, LANES), lambda i, j, ids: (0, ids[i * LANES + j] // LANES))],
        out_specs=pl.BlockSpec((d, LANES), lambda i, j, ids: (0, i)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, k + pad), rows_t.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids, rows_t)
    return out[:, :k]


def gather_rows(
    rows: jax.Array,  # (n_rows, d)
    ids: jax.Array,  # int32 (k,) pre-clamped to [0, n_rows)
    *,
    interpret: bool = False,
) -> jax.Array:
    """dtype[k, d] = rows[ids] (see :func:`gather_columns`)."""
    return gather_columns(rows.T, ids, interpret=interpret).T
