"""classify_ms: device self time of GPAC's classify layer (scope
``tier.classify``: ``telemetry.hot_mask``, the hot pages of the window), in
ms per traced window. Nothing to read where the program carries no layer
scopes or GPAC is off."""
from harness import layers


def read(ctx):
    return layers.layer_ms(ctx, "tier.classify")
