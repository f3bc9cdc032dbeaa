"""score_ms: device self time of GPAC's score layer (scope ``tier.score``:
``filter.candidate_score``, hot subpages per huge page and each page's
candidate score), in ms per traced window. Nothing to read where the
program carries no layer scopes or GPAC is off."""
from harness import layers


def read(ctx):
    return layers.layer_ms(ctx, "tier.score")
