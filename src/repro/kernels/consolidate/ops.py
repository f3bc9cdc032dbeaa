"""Jitted wrappers + registry entries for the consolidation-copy kernels.

The pallas entries own the masking semantics (padded ids produce zero rows /
dropped writes) so the kernels stay branch-free; the refs are the pure-jnp
gather/scatter the engine ran before the registry existed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.consolidate import kernel as _k
from repro.kernels.consolidate import ref as _ref


def _consolidate_region_pallas(
    src_rows: jax.Array, ids: jax.Array, *, interpret: bool = False
) -> jax.Array:
    valid = ids >= 0
    clamped = jnp.where(valid, ids, 0).astype(jnp.int32)
    out = _k.consolidate_gather(src_rows, clamped, interpret=interpret)
    return jnp.where(valid[:, None], out, 0)


def _scatter_region_pallas(
    dst_rows: jax.Array, region: jax.Array, ids: jax.Array,
    *, interpret: bool = False,
) -> jax.Array:
    # the kernel skips out-of-range slots and copies in slot order, so a
    # repeated destination keeps the last slot's row (the reference's drop
    # and last-writer semantics)
    return _k.consolidate_scatter(dst_rows, region, ids, interpret=interpret)


def _region_oracle(src_rows, ids):
    import numpy as np

    src, ids = np.asarray(src_rows), np.asarray(ids)
    out = np.zeros((ids.shape[0], src.shape[1]), src.dtype)
    for slot, i in enumerate(ids):
        if i >= 0:
            out[slot] = src[i]
    return out


def _scatter_oracle(dst_rows, region, ids):
    import numpy as np

    out = np.asarray(dst_rows).copy()
    for slot, i in enumerate(np.asarray(ids)):
        if 0 <= i < out.shape[0]:
            out[i] = np.asarray(region)[slot]
    return out


def _region_example():
    import numpy as np

    rng = np.random.default_rng(0)
    src = rng.standard_normal((8192, 8)).astype(np.float32)
    ids = rng.integers(-1, 8192, size=512).astype(np.int32)
    return (jnp.asarray(src), jnp.asarray(ids)), {}


def _scatter_example():
    import numpy as np

    rng = np.random.default_rng(0)
    dst = rng.standard_normal((8192, 8)).astype(np.float32)
    region = rng.standard_normal((512, 8)).astype(np.float32)
    ids = rng.permutation(8192)[:512].astype(np.int32)
    return (jnp.asarray(dst), jnp.asarray(region), jnp.asarray(ids)), {}


registry.register_kernel(
    "consolidate_region", pallas=_consolidate_region_pallas,
    ref=_ref.consolidate_region_ref,
    oracle=_region_oracle, example=_region_example,
    description="dense region gather for Algorithm-1 consolidation",
)
registry.register_kernel(
    "scatter_region", pallas=_scatter_region_pallas,
    ref=_ref.scatter_region_ref,
    oracle=_scatter_oracle, example=_scatter_example,
    description="region write-back scatter (padded ids dropped)",
)


def consolidate_region(
    src_rows: jax.Array,  # (n_rows, base_elems)
    ids: jax.Array,  # int32 (hp_ratio,) source row per region slot, -1 padded
    use_pallas=registry._UNSET,
    *,
    kernel_backend: str = "auto",
) -> jax.Array:
    """dtype[hp_ratio, base_elems]: dense region payload, zeros at padded slots."""
    if use_pallas is not registry._UNSET:
        kernel_backend = registry.backend_from_use_pallas(use_pallas)
    return _consolidate_region(src_rows, ids, kernel_backend)


@partial(jax.jit, static_argnames=("kernel_backend",))
def _consolidate_region(src_rows, ids, kernel_backend):
    return registry.dispatch(
        "consolidate_region", kernel_backend, src_rows, ids)


def scatter_region(
    dst_rows: jax.Array,
    region: jax.Array,
    ids: jax.Array,
    use_pallas=registry._UNSET,
    *,
    kernel_backend: str = "auto",
) -> jax.Array:
    """Write region rows to ``dst_rows[ids]`` (ids -1 dropped)."""
    if use_pallas is not registry._UNSET:
        kernel_backend = registry.backend_from_use_pallas(use_pallas)
    return _scatter_region(dst_rows, region, ids, kernel_backend)


@partial(jax.jit, static_argnames=("kernel_backend",))
def _scatter_region(dst_rows, region, ids, kernel_backend):
    return registry.dispatch(
        "scatter_region", kernel_backend, dst_rows, region, ids)
