"""device_idle_pct: the share of the traced window in which no operation
ran on the device, 100 * (1 - busy / window). Busy is the union of the
device's op intervals in the profiler trace; the window runs from the start
of the first traced engine window to the end of the last, on the trace's
clock."""


def read(ctx):
    red = ctx["reduction"]
    if red.windows == 0 or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
