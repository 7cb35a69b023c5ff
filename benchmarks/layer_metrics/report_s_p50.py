"""Seconds from an incident's first submitted stage to its finished report,
median over the incidents that finished inside the window (benchmark clock,
taken in the sweep generator around each ``incident_steps`` machine)."""

from benchmarks.lib import stats

LAYER = "Sweep driver (rca/scheduler.py)"
UNIT = "s"
MOVES = "gap_ms_p50"


def read(ctx):
    return stats.median(ctx.incident_seconds())
