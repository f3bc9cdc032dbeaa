"""gather_rows_roofline: the share of its roofline that the consolidation
copy's row-gather kernel reaches.

Work: the base pages consolidation moved in the traced windows, each read
once and written once at ``base_elems`` f32 (the reference's count). The
least time is those bytes over the chip's HBM bandwidth. Device time: the
trace's ops made by the program's ``kernels/tiered_lookup`` package, where
the ``gather_rows`` kernel lives (its Pallas form, or the XLA gather of its
reference form). Nothing to read where GPAC is off or moved nothing."""


def read(ctx):
    red = ctx["reduction"]
    secs = red.seconds_of("tiered_lookup")
    moved = ctx["moved_traced"]
    if red.windows == 0 or secs <= 0 or moved <= 0:
        return None
    nbytes = 2 * moved * int(ctx["config"]["fleet"]["base_elems"]) * 4
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / secs
