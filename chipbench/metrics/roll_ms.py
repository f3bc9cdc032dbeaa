"""roll_ms: device self time of the window roll layer (scope ``tier.roll``:
``telemetry.end_window`` and the collectors), in ms per traced window.
Nothing to read where the program carries no layer scopes."""
from harness import layers


def read(ctx):
    return layers.layer_ms(ctx, "tier.roll")
