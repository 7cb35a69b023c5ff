"""Share of the device's busy time spent in the four kinds of attention call
of a model with window layers beside full ones: the window and the full
layers' decode kernels and the banded and the full prefill calls, by the
kernels' names (``mixed_attn_costs.ATTENTION``).  The projections around them
are not in it.  None where the model has no window layer."""

from benchmarks.trace import mixed_attn_costs

LAYER = "Model step (models/llama.py)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    if ctx.trace is None or not mixed_attn_costs.has_window(
            ctx.engine.model_cfg):
        return None
    seconds = sum(mixed_attn_costs.seconds_of(ctx.trace, p)
                  for p in mixed_attn_costs.ATTENTION)
    busy = ctx.trace["busy_s"]
    return 100.0 * seconds / busy if busy and seconds else None
