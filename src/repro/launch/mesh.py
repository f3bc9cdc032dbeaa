"""Mesh construction for launch entry points (system prompt contract).

FUNCTIONS, not module-level constants: importing this module never touches
jax device state. Two families:

* :func:`guest_mesh` -- the engine's 1-D ``"guest"``-axis mesh (DESIGN.md
  §9/§17). The shared constructor behind ``benchmarks.common.
  default_guest_mesh`` and the multi-host workers: single-process it spans
  every local device (``None`` on a 1-device host, the no-mesh
  degradation); after ``launch.multihost.initialize`` it spans every
  process's devices, making ``engine.run_sharded``/``run_churn`` a
  cross-host SPMD program whose only collective is the per-window
  candidate-exchange psum.
* :func:`make_production_mesh` -- train-style pod/data/model geometry for
  the model-layer recipes, now a thin special case of :func:`train_mesh`:
  single-pod ``(data=16, model=16)`` = 256 chips; multi-pod adds a leading
  pod axis -> ``(pod=2, data=16, model=16)`` = 512 chips. DP runs over
  ``("pod", "data")``; TP/EP over ``"model"`` (DESIGN.md §5).
"""
from __future__ import annotations

import jax

DEFAULT_DATA = 16
DEFAULT_MODEL = 16
DEFAULT_PODS = 2


def guest_mesh(n_devices: int | None = None):
    """1-D ``"guest"``-axis mesh over ``n_devices`` devices (every device in
    the job when ``None``; ``None`` result on a single-device host). In a
    multi-process job the mesh must span all global devices -- see
    ``repro.core.sharding.guest_mesh``, which this delegates to."""
    from repro.core import sharding

    return sharding.guest_mesh(n_devices)


def train_mesh(data: int = DEFAULT_DATA, model: int = DEFAULT_MODEL,
               pods: int | None = None):
    """Train-style mesh of ``data x model`` chips per pod, with an optional
    leading ``pod`` axis when ``pods`` is given (``pods=1`` still carries the
    axis -- callers that want the flat 2-D geometry pass ``pods=None``)."""
    if data < 1 or model < 1 or (pods is not None and pods < 1):
        raise ValueError(
            f"train_mesh: axis sizes must be >= 1, got "
            f"data={data}, model={model}, pods={pods}")
    if pods is None:
        return jax.make_mesh((data, model), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    return jax.make_mesh((pods, data, model), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)


def make_production_mesh(*, multi_pod: bool = False):
    """The production geometry as a thin :func:`train_mesh` special case."""
    return train_mesh(pods=DEFAULT_PODS if multi_pod else None)


def dp_axes(mesh) -> tuple:
    """Batch/token axes of a mesh made by make_production_mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_dist(mesh):
    from repro.models.dist import Dist

    return Dist(mesh=mesh, dp=dp_axes(mesh), tp="model")
