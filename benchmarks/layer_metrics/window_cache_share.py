"""What the cache of the live sequences holds, as a share of what it would
hold were every layer a full one: ``engine.cache_bytes_window`` (the live
slots' rings) plus ``engine.cache_bytes_full`` (the pages the allocator has
given out, in the full layers) over the latter scaled to every layer
(``mixed_attn_costs.all_full_bytes``).  Both are levels (gauges), read as
they stand when the window has ended, the callers' sequences still in
flight.  None where the program has no such gauge."""

from benchmarks.trace import mixed_attn_costs

LAYER = "Engine tick (engine/paged.py)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    from k8s_llm_rca_tpu.utils.logging import METRICS

    ring = METRICS.count("engine.cache_bytes_window")
    full = METRICS.count("engine.cache_bytes_full")
    if not ring or not full:
        return None
    whole = mixed_attn_costs.all_full_bytes(ctx.engine.model_cfg, full)
    return 100.0 * (ring + full) / whole if whole else None
