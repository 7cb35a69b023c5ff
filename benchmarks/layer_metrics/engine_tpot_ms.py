"""Milliseconds per output token after the first, as the engine itself
timed them: ``engine.tpot`` (one observation per request retired in the
window, ``(t_last - t_first) / (tokens - 1)`` on the engine's clock), its
``total_s`` over its ``count``.  A mean over requests where ``gap_ms_p50`` is
a median, and over each request's whole life, not only its ticks inside the
window.  None where the program records no such timer."""

LAYER = "Serve (serve/api.py, serve/backend.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"


def read(ctx):
    n = ctx.counters.get("engine.tpot.count", 0.0)
    return 1e3 * ctx.counters["engine.tpot.total_s"] / n if n else None
