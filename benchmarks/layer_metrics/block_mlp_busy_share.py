"""Share of the device's busy time spent in the gated MLP behind every mixer
of a layer table whose layers are blocks of two sublayers: operations whose
HLO text carries the MLP's weights or an activation at its width
(``hybrid_block_costs.block_mlp_pattern``).  Beside ``ssm_busy_share``, the
other half of such a block.  None where the model has no such sublayer, the
program's ``ModelConfig`` no such field or the trace no such operation."""

from benchmarks.trace import hybrid_block_costs, ssm_costs

LAYER = "Model step (models/llama.py)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    pattern = hybrid_block_costs.block_mlp_pattern(ctx.engine.model_cfg)
    seconds = ssm_costs.seconds_of(ctx.trace, pattern)
    if not seconds:
        return None
    busy = ctx.trace["busy_s"]
    return 100.0 * seconds / busy if busy else None
