"""Pallas TPU kernel: row-wise top-k with ``jax.lax.top_k`` tie-breaking.

The ragged batch filter (``filter.select_batches_from_rows``) ranks every
guest's candidate subpages each maintenance tick -- a row-wise top-k over an
int32 score matrix. The kernel runs one grid step per row. The row is laid
out lane-dense as ``(width / 128, 128)`` and copied into a VMEM scratch,
then the maximum is peeled ``k`` times: take the row max, find its *first*
position (min index among ties -- exactly ``lax.top_k``'s tie-break),
record ``(val, idx)``, mask that one element to INT32_MIN, repeat. Each
peel is two streaming passes over the row, one ``(8, 128)`` tile at a time,
so the cost is ``O(k * width)``.

Bit-exactness precondition: inputs must be > INT32_MIN (the mask value).
Engine scores are ``>= -1`` by construction, and column padding (also
INT32_MIN) then loses every comparison, so real lanes always win while
``k <= row_len``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

_NEG = jnp.iinfo(jnp.int32).min
_LANES = 128
_TILE = 8 * _LANES  # one (8, 128) int32 tile


def _set(ref, pos, value):
    """``ref.flat[pos] = value`` on a ``(rows, 128)`` VMEM ref: rewrite the
    one sublane row that holds ``pos``."""
    r = pos // _LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    row = ref[pl.ds(r, 1), :]
    ref[pl.ds(r, 1), :] = jnp.where(lane == pos % _LANES, value, row)


def _topk_kernel(x_ref, vals_ref, idx_ref, row_ref, *, k: int):
    n_tiles = x_ref.shape[0] // 8
    row_ref[...] = x_ref[...]
    vals_ref[...] = jnp.zeros_like(vals_ref)
    idx_ref[...] = jnp.zeros_like(idx_ref)
    tile_iota = (
        jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 1)
    )

    def tile(t):
        return row_ref[pl.ds(pl.multiple_of(t * 8, 8), 8), :]

    def stream(step, init):
        # one pass over the row, one (8, 128) tile at a time
        return jax.lax.fori_loop(
            0, n_tiles, step, jnp.full((8, _LANES), init, jnp.int32))

    def body(j, carry):
        mx = stream(lambda t, a: jnp.maximum(a, tile(t)), _NEG).max()
        first = stream(
            lambda t, a: jnp.minimum(a, jnp.where(
                tile(t) == mx, tile_iota + t * _TILE, n_tiles * _TILE)),
            n_tiles * _TILE).min()
        _set(vals_ref, j, mx)
        _set(idx_ref, j, first)
        _set(row_ref, first, _NEG)
        return carry

    jax.lax.fori_loop(0, k, body, 0)


def topk_rows(
    mat: jax.Array,  # int32[rows, width], entries > INT32_MIN
    k: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(int32[rows, k] values desc, int32[rows, k] first-index ties)."""
    rows, width = mat.shape
    assert 0 < k <= width, (k, width)
    pad = (-width) % _TILE
    x = mat.astype(jnp.int32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=_NEG)
    w_rows = (width + pad) // _LANES
    k_rows = -(-k // _TILE) * 8
    # input block (double-buffered) + the working copy + both outputs
    vmem = 4 * (3 * w_rows + 4 * k_rows) * _LANES
    out_spec = pl.BlockSpec((None, k_rows, _LANES), lambda i: (i, 0, 0))
    vals, idx = pl.pallas_call(
        partial(_topk_kernel, k=k),
        grid=(rows,),
        in_specs=[pl.BlockSpec((None, w_rows, _LANES), lambda i: (i, 0, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, k_rows, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, k_rows, _LANES), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((w_rows, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(vmem + (4 << 20), 16 << 20)),
        interpret=interpret,
    )(x.reshape(rows, w_rows, _LANES))
    return (vals.reshape(rows, -1)[:, :k], idx.reshape(rows, -1)[:, :k])
