"""Device milliseconds per decode step: time of the decode programs in the
trace (by XLA module name) over the model steps the engine dispatched while
traced (``engine.decode_steps``, counted where a step or a scan of 1 to
``decode_chunk`` steps is dispatched; ``measure.Tracer`` takes the counters
at the trace's start and stop).  Not the calls of a kernel over the layers:
that count holds only while every layer of every model calls that kernel
(where they all do, the two are equal to the unit: PERF.md section 6, PR 23
to 25)."""

from benchmarks.trace import costs

LAYER = "Model step (models/llama.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = costs.decode_program_time(ctx.trace["programs"])
    steps = ctx.trace["counters"].get("engine.decode_steps")
    return 1e3 * seconds / steps if steps and seconds else None
