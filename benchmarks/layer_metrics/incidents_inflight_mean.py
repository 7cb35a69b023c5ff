"""Mean number of incidents in flight at each shared pump
(``SweepStats.inflight_mean``, sampled by the scheduler itself)."""

LAYER = "Sweep driver (rca/scheduler.py)"
UNIT = "incidents"
MOVES = "gap_ms_p50"


def read(ctx):
    sweep = ctx.extras.get("sweep")
    return None if sweep is None else sweep.inflight_mean()
