"""Peak device memory in use, in GB (``memory_stats()`` after the window)."""

LAYER = "Device"
UNIT = "GB"
MOVES = "gap_ms_p50"


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
