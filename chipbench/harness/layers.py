"""Device time per engine layer, from the layer scopes the program puts on
its ops.

Each window body of the engine wraps its layers' calls in
``jax.named_scope("tier.<layer>")``; the scope is a component of every op's
HLO op-name path, which the trace carries as the op's ``tf_op``. An op's
layer is the outermost ``tier.*`` component of that path (a layer function
called inside another layer counts as the enclosing one); an op with none,
such as a copy XLA adds or a sort it emits without metadata, is
``(unscoped)``.

Self time: a ``while``, ``conditional`` or ``call`` event inside which
another op starts is a container whose time its body's ops already hold,
and is dropped; what is left sums to the device's busy time.

The driver's host spans (``tier.prepare``, ``tier.dispatch``,
``tier.fetch``) name the idle gaps the program leaves the device; the
benchmark's own spans name the rest.

The layer names are a copy of ``repro.core.engine.LAYERS`` (the benchmark
imports nothing of the program; a test holds the two equal).
"""
from __future__ import annotations

LAYERS = ("tier.translate", "tier.record", "tier.classify", "tier.score",
          "tier.select", "tier.copy", "tier.tick", "tier.roll")
UNSCOPED = "(unscoped)"
CONTAINERS = ("while", "conditional", "call")
# host spans of the benchmark itself (harness.cell) and the reduction's name
# for a gap under none of them
BENCH_SPANS = ("bench.window", "traffic_wait", "generate", "engine.run",
               "(no span)")


def layer_of(ev) -> str:
    """The outermost ``tier.*`` component of the op's op-name path."""
    path = str(ev.stats.get("tf_op") or "").rstrip(":")
    return next((c for c in path.split("/") if c.startswith("tier.")),
                UNSCOPED)


def _kind(ev) -> str:
    """The HLO opcode an instruction is named after (``%while.63 = ...``)."""
    return ev.name.split(" ", 1)[0].lstrip("%").split(".", 1)[0]


def leaf_ops(ops: list) -> list:
    """The ops less the containers: a while, conditional or call event whose
    interval holds another op's start."""
    evs = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    out = []
    for i, ev in enumerate(evs):
        kind = str(ev.stats.get("hlo_category") or "")
        if _kind(ev) in CONTAINERS or kind in CONTAINERS:
            end = ev.start_ns + ev.dur_ns
            nxt = evs[i + 1] if i + 1 < len(evs) else None
            if nxt is not None and nxt.start_ns < end:
                continue
        out.append(ev)
    return out


def ms_per_window(red) -> dict | None:
    """Device self ms per traced window of each layer and of
    ``(unscoped)``; None where the trace has no window."""
    if red.windows == 0:
        return None
    out: dict[str, float] = {}
    for ev in leaf_ops(red.ops):
        name = layer_of(ev)
        out[name] = out.get(name, 0.0) + ev.dur_ns
    scale = 1e-6 / red.chips / red.windows
    return {k: v * scale for k, v in out.items()}


def _scoped(red) -> dict | None:
    """:func:`ms_per_window`, or None where no op carries a layer scope
    (a program without the scopes: nothing to read)."""
    per = ms_per_window(red)
    return None if per is None or set(per) <= {UNSCOPED} else per


def layer_ms(ctx, layer: str) -> float | None:
    """Metric reader body: ``layer``'s device ms per traced window; None
    where the program has no scopes or the layer ran nothing."""
    per = _scoped(ctx["reduction"])
    if per is None or (layer != UNSCOPED and layer not in per):
        return None
    return per.get(layer, 0.0)


def driver_idle_ms(ctx) -> float | None:
    """Idle ms per traced window in gaps that no span of the benchmark's
    own names: gaps under the driver's spans, or under JAX's events nested
    in them. None where the program has no scopes."""
    red = ctx["reduction"]
    if _scoped(red) is None:
        return None
    idle = sum(s for name, s in red.gaps.items() if name not in BENCH_SPANS)
    return 1e3 * idle / red.windows
