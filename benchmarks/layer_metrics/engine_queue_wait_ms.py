"""Milliseconds from ``submit`` to the first slot grant, as the engine
itself timed them: ``engine.queue_wait`` (one observation per request retired
in the window), its ``total_s`` over its ``count``.  None where the program
records no such timer."""

LAYER = "Serve (serve/api.py, serve/backend.py)"
UNIT = "ms"
MOVES = "out_tokens_per_s"


def read(ctx):
    n = ctx.counters.get("engine.queue_wait.count", 0.0)
    return 1e3 * ctx.counters["engine.queue_wait.total_s"] / n if n else None
