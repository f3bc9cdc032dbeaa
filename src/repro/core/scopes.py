"""Layer scopes: what a profiler trace attributes device time to.

Every window body wraps each layer's calls in ``jax.named_scope(name)``.
A device op's layer is the outermost ``tier.*`` component of its HLO
op-name path, so a layer function called from inside another layer (say
``asp.translate`` inside the copy) counts as the enclosing one.
"""
from __future__ import annotations

import jax

LAYERS = (
    "tier.translate",  # spec.localize, asp.translate, the near/far hit sums
    "tier.record",  # asp.record_accesses (or its histogram form)
    "tier.classify",  # telemetry.hot_mask
    "tier.score",  # filter.candidate_score
    "tier.select",  # filter.select_batches_from_rows
    "tier.copy",  # consolidator.consolidate_rounds
    "tier.tick",  # tiering.strided_tick (and the churn pressure tick)
    "tier.roll",  # telemetry.end_window, the collectors
)


def layer(name: str):
    """``jax.named_scope`` of one of :data:`LAYERS`. Scopes change op
    metadata only: no buffer, fusion or shape."""
    if name not in LAYERS:
        raise ValueError(f"unknown engine layer {name!r} (have {LAYERS})")
    return jax.named_scope(name)


# A scope inside ``tier.tick``, not a layer of its own: the host-partitioned
# engine's arbitration exchange (the psum and the packing and unpacking of
# its payload). Its ops keep ``tier.tick`` as their outermost layer; a trace
# reader finds the exchange by this component of the op-name path.
EXCHANGE = "tier.exchange"


def exchange_scope():
    """``jax.named_scope`` of :data:`EXCHANGE`; open it inside
    ``layer("tier.tick")``."""
    return jax.named_scope(EXCHANGE)
