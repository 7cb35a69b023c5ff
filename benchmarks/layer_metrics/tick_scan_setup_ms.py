"""Milliseconds of a tick between the start of its decode phase and the
decode dispatch: DFA tables or masks, the key split, the upload of the host
mirrors (three ``jnp.asarray`` where they are not resident) and the work
counters (``engine.scan_setup``), its ``total_s`` over the ticks.  None where
the program records no such timer."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"


def read(ctx):
    c = ctx.counters
    n = c.get("engine.tick.count", 0.0)
    if not n or not c.get("engine.scan_setup.count", 0.0):
        return None
    return 1e3 * c.get("engine.scan_setup.total_s", 0.0) / n
