"""Share of the device's busy time spent in the routed experts of the latent
expert layers: operations whose HLO text carries the held experts' stacked
weights or a per-expert activation, and XLA's grouped matmul kernel
(``ssm_costs.latent_moe_pattern``).  The router, the latent projections and
the shared expert are not in it.  None where the model has no such layer."""

from benchmarks.trace import ssm_costs

LAYER = "Model step (models/llama.py)"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(ctx):
    pattern = ssm_costs.latent_moe_pattern(ctx.engine.model_cfg)
    seconds = ssm_costs.seconds_of(ctx.trace, pattern)
    if not seconds:
        return None
    busy = ctx.trace["busy_s"]
    return 100.0 * seconds / busy if busy else None
