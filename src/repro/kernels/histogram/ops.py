"""Jitted wrapper + registry entry for the weighted bincount kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import registry, runtime  # noqa: F401  (runtime re-export)
from repro.kernels.histogram import kernel as _k
from repro.kernels.histogram import ref as _ref


def _bincount_pallas(
    ids: jax.Array, weights: jax.Array, n_bins: int, *, interpret: bool = False
) -> jax.Array:
    """Kernel entry with the reference's indexing semantics.

    XLA's ``.at[ids].add`` wraps negative ids numpy-style (once) and drops
    anything still out of range; mirror that here so the two backends agree
    bit-for-bit on any input, not just the engine's in-range ids.
    """
    flat = ids.reshape(-1).astype(jnp.int32)
    flat = jnp.where(flat < 0, flat + n_bins, flat)
    return _k.bincount(
        flat, weights.reshape(-1), n_bins, interpret=interpret)


def _oracle(ids, weights, n_bins):
    import numpy as np

    out = np.zeros(n_bins, np.int64)
    for i, w in zip(np.asarray(ids).reshape(-1), np.asarray(weights).reshape(-1)):
        i = i + n_bins if i < 0 else i
        if 0 <= i < n_bins:
            out[i] += int(w)
    return out.astype(np.int32)


def _example():
    import numpy as np

    rng = np.random.default_rng(0)
    n_bins = 4096
    ids = rng.integers(0, n_bins, size=16384).astype(np.int32)
    w = rng.integers(0, 8, size=16384).astype(np.int32)
    return (jnp.asarray(ids), jnp.asarray(w), n_bins), {}


registry.register_kernel(
    "bincount", pallas=_bincount_pallas, ref=_ref.bincount_ref,
    oracle=_oracle, example=_example,
    description="weighted bincount (per-window access/host histograms)",
    tpu_auto="xla",
    tpu_auto_why=(
        "O(ids x bins) one-hot: 28.5 ms vs XLA 1.2 ms for 65,536 ids into "
        "524,289 bins on v5e; the engine's histograms have up to n_logical "
        "bins (33.5M per chip at the deployment fleet)"),
)


@partial(jax.jit, static_argnames=("n_bins", "kernel_backend"))
def bincount(
    ids: jax.Array,
    weights: jax.Array,
    n_bins: int,
    *,
    kernel_backend: str = "auto",
) -> jax.Array:
    """int32[n_bins] weighted histogram of ``ids`` (XLA scatter-add semantics)."""
    return registry.dispatch("bincount", kernel_backend, ids, weights, n_bins)
