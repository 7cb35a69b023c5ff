"""Device milliseconds per decode step: time of the decode programs in the
trace (by XLA module name) over the steps they ran, counted from the calls
of the paged-attention kernel (one per layer and step): a scan program runs
1 to ``decode_chunk`` steps under one name."""

from benchmarks.trace import costs

LAYER = "Model step (models/llama.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, steps = costs.decode_program_time(
        ctx.trace["programs"], ctx.trace["op_counts"],
        ctx.engine.model_cfg.n_layers)
    return 1e3 * seconds / steps if steps and seconds else None
