"""select_ms: device self time of GPAC's select layer (scope ``tier.select``:
``filter.select_batches_from_rows``, the per-guest top-k of the scores into
consolidation batches), in ms per traced window. Nothing to read where the
program carries no layer scopes or GPAC is off."""
from harness import layers


def read(ctx):
    return layers.layer_ms(ctx, "tier.select")
