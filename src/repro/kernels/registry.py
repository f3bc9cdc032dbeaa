"""The kernel-backend registry (DESIGN.md §4/§16).

The eighth string-keyed registry (§8): one table of :class:`KernelSpec`
entries, each naming a Pallas implementation, the pure-jnp reference it is
pinned against, and (where one exists) an independent numpy oracle for
tests plus a nullary ``example`` for the micro-benchmark suite. Mirrors the
PR-2 registry idiom: duplicates raise, unknown names raise listing the live
set.

Dispatch discipline: core modules never compare backend strings themselves
(that is REPRO-L002 territory) — they pass the engine's ``kernel_backend``
knob down to :func:`dispatch`, which resolves the tri-state here:

* ``"xla"``    — run the jnp reference (the pre-registry engine path).
* ``"pallas"`` — run the Pallas kernel; interpret mode off-TPU
  (``runtime.interpret()``), native lowering on TPU.
* ``"auto"``   — honor ``REPRO_KERNEL_BACKEND`` if set (read once at import
  so jit caches cannot go stale mid-process), else the entry's fixed TPU
  rule (``KernelSpec.tpu_auto``) on TPU and the reference elsewhere —
  interpretation is slower than XLA on CPU, and the two are bit-identical
  (INV-KERNEL-BACKEND-EXACT). An entry whose Pallas form is an order of
  magnitude or more slower than XLA at the engine's deployment shapes on the
  chip sets ``tpu_auto="xla"`` and gives the measurement in
  ``tpu_auto_why``; ``"pallas"`` still runs its kernel natively.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Callable

from repro.kernels import runtime

BACKENDS = ("xla", "pallas", "auto")

# Read once at import: the resolved backend is baked into jit cache keys via
# EngineSpec, so a mid-process env flip must not silently change dispatch.
_ENV_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND", "")


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: the Pallas impl, its jnp reference, and the
    test/bench metadata.

    ``pallas`` must accept an ``interpret=`` keyword (forwarded from
    ``runtime.interpret()``); ``ref`` is the pure-jnp function the engine ran
    before the registry existed and stays the ``"xla"`` backend verbatim.
    ``oracle`` (optional) is an independent numpy implementation for tests;
    ``example`` (optional) is a nullary callable returning ``(args, kwargs)``
    for generic micro-benchmarks (``benchmarks/bench_kernels.py``).
    ``tpu_auto`` is what ``"auto"`` runs on a TPU; an entry that sets it to
    ``"xla"`` states the measured reason in ``tpu_auto_why``.
    """

    name: str
    pallas: Callable
    ref: Callable
    oracle: Callable | None = None
    example: Callable | None = None
    description: str = ""
    tpu_auto: str = "pallas"
    tpu_auto_why: str = ""


_KERNELS: dict[str, KernelSpec] = {}


def register_kernel(
    name: str,
    pallas: Callable,
    ref: Callable,
    *,
    oracle: Callable | None = None,
    example: Callable | None = None,
    description: str = "",
    tpu_auto: str = "pallas",
    tpu_auto_why: str = "",
) -> KernelSpec:
    """Register a kernel under a unique name; duplicates raise."""
    if name in _KERNELS:
        raise ValueError(f"kernel {name!r} already registered")
    if tpu_auto not in ("xla", "pallas") or (tpu_auto == "xla") != bool(
            tpu_auto_why):
        raise ValueError(
            f"kernel {name!r}: tpu_auto must be 'pallas', or 'xla' with a "
            f"tpu_auto_why, got {tpu_auto!r}")
    spec = KernelSpec(
        name=name, pallas=pallas, ref=ref, oracle=oracle,
        example=example, description=description,
        tpu_auto=tpu_auto, tpu_auto_why=tpu_auto_why,
    )
    _KERNELS[name] = spec
    return spec


def get_kernel(name: str) -> KernelSpec:
    try:
        return _KERNELS[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel {name!r} (have {kernel_names()})"
        ) from None


def kernel_names() -> tuple[str, ...]:
    """Names of all registered kernels, sorted for stable listings."""
    return tuple(sorted(_KERNELS))


def all_kernels() -> tuple[KernelSpec, ...]:
    return tuple(_KERNELS[n] for n in kernel_names())


def resolve_backend(choice: str = "auto", name: str | None = None) -> str:
    """Resolve a backend knob to a concrete ``"xla"`` or ``"pallas"``, for
    kernel ``name`` (whose TPU rule applies under ``"auto"``) or for any
    kernel without a rule when ``name`` is None."""
    if choice not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {choice!r} (have {BACKENDS})"
        )
    if choice != "auto":
        return choice
    env = _ENV_BACKEND
    if env:
        if env not in BACKENDS or env == "auto":
            raise ValueError(
                f"REPRO_KERNEL_BACKEND={env!r} must be 'xla' or 'pallas'"
            )
        return env
    if not runtime.on_tpu():
        return "xla"
    return get_kernel(name).tpu_auto if name is not None else "pallas"


def resolved_backends(choice: str = "auto") -> dict[str, str]:
    """``{kernel name: backend}`` that ``choice`` runs on this platform."""
    return {n: resolve_backend(choice, n) for n in kernel_names()}


def dispatch(name: str, choice: str, *args, **kwargs):
    """Run the named kernel on the resolved backend.

    This is the only place backend strings are compared; core modules thread
    the engine's ``kernel_backend`` knob here untouched. Called inside jit:
    the branch is a trace-time python decision, so each resolved backend gets
    its own cached executable (the knob rides EngineSpec, a static argument).
    """
    spec = get_kernel(name)
    resolved = resolve_backend(choice, name)
    if resolved == "pallas":
        return spec.pallas(*args, interpret=runtime.interpret(), **kwargs)
    return spec.ref(*args, **kwargs)


_UNSET = object()  # sentinel: distinguishes "not passed" from use_pallas=None


def backend_from_use_pallas(use_pallas, *, stacklevel: int = 3) -> str:
    """Map the deprecated ``use_pallas`` tri-state onto a backend name.

    Emits ``DeprecationWarning`` at python call time (the public wrappers
    resolve the shim before entering jit, so the warning always fires).
    """
    warnings.warn(
        "use_pallas= is deprecated; pass kernel_backend='xla'|'pallas'|"
        "'auto' instead (see repro.kernels.registry)",
        DeprecationWarning, stacklevel=stacklevel,
    )
    if use_pallas is None:
        return "auto"
    return "pallas" if use_pallas else "xla"
