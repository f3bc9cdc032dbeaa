"""topk_roofline: the share of its roofline that the engine's top-k work
reaches, per window.

Work from shapes (the same whichever top-k implementation runs): every
top-k of a window reads its input row once and writes k values and k
indices, int32. With GPAC on, the filter runs one top-k of k =
max_batches * hp_ratio over each guest's row of pages; every window, the
host tick runs four top-k of k = budget over the fleet's huge pages. The
least time is those bytes over the chip's HBM bandwidth (top-k does no
arithmetic worth counting).

Device time: the trace's ops whose HLO metadata ties them to the ``top_k``
primitive or to a source line of the program's top-k module
(``kernels/topk``), summed over the traced windows.
"""


def _bytes_per_window(ctx) -> int:
    geo, cfg = ctx["geometry"], ctx["config"]
    pol, fleet = cfg["policy"], cfg["fleet"]
    H = geo["n_gpa_hp"]
    b = min(int(pol["budget"]), H)
    total = 4 * (4 * H + 8 * b)  # the tick: 4 calls over [H], k = budget
    if pol["use_gpac"]:
        rows, width = int(fleet["guests"]), int(fleet["pages_per_guest"])
        k = min(int(pol["max_batches"]) * geo["hp_ratio"], width)
        total += rows * (4 * width + 8 * k)
    return total


def read(ctx):
    red = ctx["reduction"]
    secs = red.seconds_of("topk")
    if red.windows == 0 or secs <= 0:
        return None
    least = _bytes_per_window(ctx) * red.windows / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
