"""Device milliseconds of the prefill programs (by XLA module name) per 1,000
prompt tokens they prefilled (``engine.prefill_tokens`` over the traced part
of the window)."""

from benchmarks.trace import costs

LAYER = "Model step (models/llama.py)"
UNIT = "ms"
MOVES = "out_tokens_per_s"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = costs.prefill_program_time(ctx.trace["programs"])
    tokens = ctx.trace["counters"].get("engine.prefill_tokens", 0.0)
    return 1e3 * seconds / (tokens / 1e3) if tokens and seconds else None
