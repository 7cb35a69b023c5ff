"""Share of the device's busy time spent in the Mamba-2 layers' own
operations: the state update, the chunked scan, the convolution and the in-
and out-projections, found in the trace by name or by the shapes only they
have (``ssm_costs``).  None where the model has no such layer."""

from benchmarks.trace import ssm_costs

LAYER = "Model step (models/llama.py)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    cfg = ctx.engine.model_cfg
    seconds = ssm_costs.seconds_of(
        ctx.trace,
        ssm_costs.state_update_pattern(cfg, ctx.engine.engine_cfg.max_batch),
        ssm_costs.chunk_scan_pattern(cfg), ssm_costs.mamba_rest_pattern(cfg))
    if not seconds:
        return None
    busy = ctx.trace["busy_s"]
    return 100.0 * seconds / busy if busy else None
