"""driver_idle_ms: device idle time under the engine's driver, in ms per
traced window: the idle gaps in the profiler trace less those under the
benchmark's own host spans (``bench.window``, ``traffic_wait``,
``generate``, ``engine.run``, or none). What remains falls under the
driver's spans (``tier.prepare``, ``tier.dispatch``, ``tier.fetch``) or
under JAX's own events inside them (dispatch, transfers, garbage
collection). Nothing to read where the program carries no layer scopes."""
from harness import layers


def read(ctx):
    return layers.driver_idle_ms(ctx)
