"""Milliseconds of a tick the host spends activating admissions, after each
group's prefill dispatch: the work counters, the slot's registration and its
mirrors, the first token's slice and edit into the resident state, the
deferred fetch's record (``engine.admission.activate``, one span a group),
its ``total_s`` over the ticks.  None where the program records no such
timer."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"


def read(ctx):
    c = ctx.counters
    n = c.get("engine.tick.count", 0.0)
    if not n or not c.get("engine.admission.activate.count", 0.0):
        return None
    return 1e3 * c.get("engine.admission.activate.total_s", 0.0) / n
