"""Share of the page slots the decode kernel's grid visits that hold live
context: ``engine.attn_pages_live`` (steps x sum over live slots of
``ceil(length / page_size)``) over ``engine.attn_pages_grid`` (steps x slots x
pages per sequence, per layer).  The rest of the grid is visited for nothing.
None where the program does not count pages."""

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    grid = ctx.counters.get("engine.attn_pages_grid", 0.0)
    if not grid:
        return None
    return 100.0 * ctx.counters.get("engine.attn_pages_live", 0.0) / grid
