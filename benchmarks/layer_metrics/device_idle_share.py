"""Share of the traced window in which no operation ran on the device:
1 - union of the device's operation intervals over the window."""

LAYER = "Device"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
