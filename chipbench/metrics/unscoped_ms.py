"""unscoped_ms: device self time of the ops outside every engine layer
scope, in ms per traced window: copies XLA adds (of the state the window
does not donate), ops it emits without metadata, the sharded exchange.
Nothing to read where the program carries no layer scopes."""
from harness import layers


def read(ctx):
    return layers.layer_ms(ctx, layers.UNSCOPED)
