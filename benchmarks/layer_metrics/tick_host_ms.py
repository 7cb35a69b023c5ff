"""Milliseconds of a tick the host does not spend blocked on the chip:
``engine.tick`` seconds less ``engine.fetch`` seconds, over the ticks.  What
is left is admission, eviction, the dispatches, grammar masks and the commit
loop: the floor under a tick once device time shrinks.  None where the
program records no ``engine.tick`` timer."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"


def read(ctx):
    n = ctx.counters.get("engine.tick.count", 0.0)
    if not n:
        return None
    host_s = (ctx.counters["engine.tick.total_s"]
              - ctx.counters.get("engine.fetch.total_s", 0.0))
    return 1e3 * host_s / n
