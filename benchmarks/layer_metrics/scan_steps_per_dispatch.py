"""Model steps per decode dispatch: ``engine.decode_steps`` (a scan's chunk,
1 for the stepwise program) over ``engine.decode_step.count`` (dispatches).
The ceiling is ``decode_chunk``; a tick costs a host round trip whatever its
length.  None where the program does not count its steps."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "steps"
MOVES = "gap_ms_p50"


def read(ctx):
    n = ctx.counters.get("engine.decode_step.count", 0.0)
    if not n or "engine.decode_steps" not in ctx.counters:
        return None
    return ctx.counters["engine.decode_steps"] / n
