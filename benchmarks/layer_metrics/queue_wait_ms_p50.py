"""Milliseconds from a request's due time (open loop) or submission (closed
loop) to the start of the engine tick that admitted it, median over the
requests admitted inside the window."""

from benchmarks.lib import stats

LAYER = "Serve (serve/api.py, serve/backend.py)"
UNIT = "ms"
MOVES = "out_tokens_per_s"


def read(ctx):
    return stats.median([1e3 * max(0.0, r.t_admit_tick - r.t_due)
                         for r in ctx.admitted_in_window])
