"""Milliseconds of a tick the host spends staging admissions, before each
group's prefill dispatch: the prefix match, page allocation, the slot, the
block-table rows and the padded numpy rows (``engine.admission.stage``, one
span a group), its ``total_s`` over the ticks.  None where the program
records no such timer."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"


def read(ctx):
    c = ctx.counters
    n = c.get("engine.tick.count", 0.0)
    if not n or not c.get("engine.admission.stage.count", 0.0):
        return None
    return 1e3 * c.get("engine.admission.stage.total_s", 0.0) / n
