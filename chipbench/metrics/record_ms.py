"""record_ms: device self time of the record layer (scope ``tier.record``:
``asp.record_accesses``, the guest and host access counts and touch
epochs), in ms per traced window. Nothing to read where the program carries
no layer scopes."""
from harness import layers


def read(ctx):
    return layers.layer_ms(ctx, "tier.record")
