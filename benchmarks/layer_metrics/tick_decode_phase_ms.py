"""Milliseconds of a tick spent in its decode phase (``engine.tick.decode``:
whichever decode program runs, from its set-up through the dispatch and the
fetch that waits for it to the host commit), its ``total_s`` over the ticks.
Over ``scan_steps_per_dispatch`` it is what one model step costs a live
sequence, the host's share included.  None where the program records no such
timer."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"


def read(ctx):
    c = ctx.counters
    n = c.get("engine.tick.count", 0.0)
    if not n or not c.get("engine.tick.decode.count", 0.0):
        return None
    return 1e3 * c.get("engine.tick.decode.total_s", 0.0) / n
