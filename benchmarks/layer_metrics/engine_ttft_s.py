"""Seconds from ``submit`` to the host commit of the first token, as the
engine itself timed them: ``engine.ttft`` (one observation per request
retired in the window), its ``total_s`` over its ``count``.  Starts at the
engine's door, so it lacks the serve layer's tokenizing and the wait for the
tick to end that ``ttft_s_p50`` includes.  None where the program records no
such timer."""

LAYER = "Serve (serve/api.py, serve/backend.py)"
UNIT = "s"
MOVES = "out_tokens_per_s"


def read(ctx):
    n = ctx.counters.get("engine.ttft.count", 0.0)
    return ctx.counters["engine.ttft.total_s"] / n if n else None
