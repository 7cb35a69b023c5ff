"""Share of the device's busy time spent in the routed experts of the
Llama block's expert layers: operations whose HLO text carries the held
experts' stacked weights or a per-expert activation at their width, and XLA's
grouped matmul kernel (``mixed_attn_costs.routed_expert_pattern``).  The
router, the shared expert and the leading dense layer's MLP are not in it.
None where the model has no such layer."""

from benchmarks.trace import mixed_attn_costs

LAYER = "Model step (models/llama.py)"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(ctx):
    pattern = mixed_attn_costs.routed_expert_pattern(ctx.engine.model_cfg)
    seconds = mixed_attn_costs.seconds_of(ctx.trace, pattern)
    if not seconds:
        return None
    busy = ctx.trace["busy_s"]
    return 100.0 * seconds / busy if busy else None
