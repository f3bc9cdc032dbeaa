"""Pure-jnp reference for the row-wise top-k: ``jax.lax.top_k``'s order.

The order is values descending, equal values by ascending index. The
``"xla"`` backend and every other top-k of the engine (the reference
filter, the host tick's nominations) go through :func:`top_k`, so one place
holds that order on every platform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import runtime


def _top_k_tie_exact(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Integer top-k over the last axis in (value desc, index asc) order,
    using XLA's top_k only for values and for keys that never tie."""
    width = x.shape[-1]
    kth = jax.lax.top_k(x, k)[0][..., -1:]
    above = x > kth
    tied = x == kth
    quota = k - above.sum(axis=-1, keepdims=True, dtype=jnp.int32)
    take = above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= quota))
    # exactly k entries are taken; a top_k over distinct keys lists them by
    # ascending index, then a two-key sort orders them by (value desc, index)
    col = jnp.arange(width, dtype=jnp.int32)
    _, idx = jax.lax.top_k(jnp.where(take, width - col, -1), k)
    vals = jnp.take_along_axis(x, idx, axis=-1)
    inv, idx = jax.lax.sort((~vals, idx), num_keys=2)
    return ~inv, idx


def top_k(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """``jax.lax.top_k`` over the last axis of an integer array, ties to the
    lowest index on every platform.

    On a TPU v5e, XLA's top_k returns the right values but, over rows of
    262,144 entries and more, not the lowest-index members of a tie. There
    the order is rebuilt from values alone (:func:`_top_k_tie_exact`);
    elsewhere ``lax.top_k`` already keeps it.
    """
    if runtime.on_tpu():
        return _top_k_tie_exact(x, k)
    return jax.lax.top_k(x, k)


def topk_rows_ref(mat: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    return top_k(mat, k)
