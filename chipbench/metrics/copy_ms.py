"""copy_ms: device self time of GPAC's copy layer (scope ``tier.copy``:
``consolidator.consolidate_rounds``, region allocation, the page moves and
the mapping updates), in ms per traced window. Nothing to read where the
program carries no layer scopes or GPAC is off."""
from harness import layers


def read(ctx):
    return layers.layer_ms(ctx, "tier.copy")
