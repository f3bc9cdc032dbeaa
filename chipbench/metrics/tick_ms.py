"""tick_ms: device self time of the host tick layer (scope ``tier.tick``:
``tiering.strided_tick``, the Memtierd promotion and demotion of huge
pages), in ms per traced window. Nothing to read where the program carries
no layer scopes."""
from harness import layers


def read(ctx):
    return layers.layer_ms(ctx, "tier.tick")
