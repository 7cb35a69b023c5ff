"""Share of prompt tokens served from the prefix cache:
``engine.prefix_hit_tokens`` over hit + ``engine.prefill_tokens``."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    hit = ctx.counters.get("engine.prefix_hit_tokens", 0.0)
    miss = ctx.counters.get("engine.prefill_tokens", 0.0)
    return 100.0 * hit / (hit + miss) if hit + miss else None
