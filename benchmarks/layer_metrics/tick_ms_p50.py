"""Median milliseconds of one engine tick: the benchmark's span around
``pump`` (admission, eviction, the dispatch and its fetch, the commit)."""

from benchmarks.lib import stats

LAYER = "Engine tick (engine/paged.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"


def read(ctx):
    return stats.median([1e3 * (t[1] - t[0]) for t in ctx.ticks])
