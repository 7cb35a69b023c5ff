"""Share of decode ticks whose scan was cut short by a slot's allocated
pages: ``engine.scan_limit.pages`` over the sum of ``engine.scan_limit.*``
(one increment per decode tick for the bound that set the chunk: ``full``,
``pages``, ``headroom``, ``grammar``, ``admission``).  None where the program
names no bound."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "%"
MOVES = "gap_ms_p50"

PREFIX = "engine.scan_limit."


def read(ctx):
    ticks = sum(v for k, v in ctx.counters.items() if k.startswith(PREFIX))
    if not ticks:
        return None
    return 100.0 * ctx.counters.get(PREFIX + "pages", 0.0) / ticks
