"""Pallas TPU kernel: weighted bincount (the per-window access histogram).

Both engine histograms are this one primitive: the per-window access
histogram (unit weights over flattened logical ids,
``address_space.access_histogram``) and the huge-page roll-up (guest hit
counts summed by ``gpt // hp_ratio``, ``address_space.host_histogram``).
XLA lowers them as serialized scatter-adds over the id stream; here the
histogram is computed bin-major instead. The grid is ``(bin blocks, id
chunks)``: each step holds one lane-dense ``(blk_rows, 128)`` tile of bins
in the output block and streams one ``chunk`` of ids and weights through
SMEM, adding ``where(bins == id, w, 0)`` for every id in the chunk. The id
axis is a reduction axis that accumulates into the resident output block,
so VMEM/SMEM use is fixed by ``blk_rows`` and ``chunk``, not by the length
of the id stream. The work is ``O(n_ids * n_bins)`` vector lanes, which
only pays off while the bin count is small (``registry`` resolves ``auto``
to XLA on TPU for this reason).

Bit-exactness: each id matches at most one bin and int32 addition is
associative/commutative mod 2^32, so any accumulation order equals the
scatter-add result exactly. Ids must be pre-wrapped/pre-masked by the ops
wrapper; ids outside ``[0, n_bins)`` (e.g. the ``-1`` chunk padding) match
no bin and drop out, mirroring XLA's drop semantics.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

_LANES = 128


def _bincount_kernel(ids_ref, w_ref, o_ref, *, blk_rows: int, chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    base = pl.program_id(0) * (blk_rows * _LANES)
    bins = (
        base
        + jax.lax.broadcasted_iota(jnp.int32, (blk_rows, _LANES), 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, (blk_rows, _LANES), 1)
    )

    def body(t, acc):
        for u in range(8):  # unrolled by hand: Mosaic only takes unroll=1
            acc = acc + jnp.where(
                bins == ids_ref[t * 8 + u], w_ref[t * 8 + u], 0)
        return acc

    o_ref[...] += jax.lax.fori_loop(
        0, chunk // 8, body, jnp.zeros((blk_rows, _LANES), jnp.int32))


def bincount(
    ids: jax.Array,      # int32[k] bin id per sample; OOB ids are dropped
    weights: jax.Array,  # int32[k] weight per sample
    n_bins: int,
    blk_rows: int = 32,
    chunk: int = 2048,
    *,
    interpret: bool = False,
) -> jax.Array:
    """int32[n_bins]: sum of ``weights`` per bin (OOB ids contribute nothing)."""
    k = ids.shape[0]
    chunk = max(8, min(chunk, -(-k // 8) * 8))
    ids = ids.astype(jnp.int32)
    weights = weights.astype(jnp.int32)
    pad_k = (-k) % chunk if k else chunk  # the grid needs >= 1 id chunk
    if pad_k:
        # -1 never matches a bin in [0, n_bins), so padding is weightless
        ids = jnp.pad(ids, (0, pad_k), constant_values=-1)
        weights = jnp.pad(weights, (0, pad_k))
    blk = blk_rows * _LANES
    n_rows = -(-n_bins // blk) * blk_rows
    smem = partial(pl.BlockSpec, (chunk,), lambda i, j: (j,),
                   memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        partial(_bincount_kernel, blk_rows=blk_rows, chunk=chunk),
        grid=(n_rows // blk_rows, (k + pad_k) // chunk),
        in_specs=[smem(), smem()],
        out_specs=pl.BlockSpec((blk_rows, _LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, _LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids, weights)
    return out.reshape(-1)[:n_bins]
