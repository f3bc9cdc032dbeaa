"""translate_ms: device self time of the translate layer (scope
``tier.translate``: guest-local ids to combined ids, ``asp.translate``
through the guest page table and the block table, the window's near and far
hit sums), in ms per traced window. Nothing to read where the program
carries no layer scopes."""
from harness import layers


def read(ctx):
    return layers.layer_ms(ctx, "tier.translate")
