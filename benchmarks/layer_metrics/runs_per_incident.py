"""LLM runs started per incident finished inside the window
(``serve.runs_started`` over the benchmark's incident count): retries and
audit fan-out show here."""

LAYER = "RCA stages (rca/pipeline.py)"
UNIT = "runs"
MOVES = "gap_ms_p50"


def read(ctx):
    done = len(ctx.incident_seconds())
    return ctx.counters.get("serve.runs_started", 0.0) / done if done else None
