"""Decode tokens committed per decode dispatch: ``engine.decode_tokens`` over
``engine.decode_step.count``.  The ceiling is slots x ``decode_chunk``."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "tokens"
MOVES = "gap_ms_p50"


def read(ctx):
    steps = ctx.counters.get("engine.decode_step.count", 0.0)
    return ctx.counters.get("engine.decode_tokens", 0.0) / steps \
        if steps else None
