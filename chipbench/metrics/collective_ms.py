"""collective_ms: device self time of the host-partitioned engine's
arbitration exchange, in ms per traced window, mean over the chips.

The exchange is the scope ``tier.exchange`` (nested in ``tier.tick``): the
one collective of a window -- each chip's tick nominations and per-window
collector rows summed across the chips -- and the packing and unpacking of
its payload. A chip that reaches the exchange before the others waits in it
for them, so this is also what the slowest chip costs the rest. Nothing to
read where no op carries the scope: one chip, or a program without it."""
from harness import layers

SCOPE = "tier.exchange"


def read(ctx):
    red = ctx["reduction"]
    if red.windows == 0:
        return None
    ns = [ev.dur_ns for ev in layers.leaf_ops(red.ops)
          if SCOPE in str(ev.stats.get("tf_op") or "").rstrip(":").split("/")]
    if not ns:
        return None
    return sum(ns) * 1e-6 / red.chips / red.windows
