"""The decode step's state update (``ssm_state_update``) as a share of its
roofline: the least time the chip could take to move what the updates had to
move (each slot's state in and out, that position's x, B, C and dt, by
``ssm_costs.state_update_bytes``, for the ``engine.ssm_decode_slot_steps``
counted while traced, over the chip's peak bandwidth), over the update's self
time in the trace.  Bound by memory bandwidth.  None where the model has no
such layer, the program no such counter or the trace no such operation."""

from benchmarks.trace import costs, ssm_costs

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    cfg = ctx.engine.model_cfg
    if ctx.trace is None or not ssm_costs.has_ssm(cfg):
        return None
    seconds = ssm_costs.seconds_of(ctx.trace, ssm_costs.state_update_pattern(
        cfg, ctx.engine.engine_cfg.max_batch))
    slot_steps = ctx.trace["counters"].get("engine.ssm_decode_slot_steps")
    if not seconds or not slot_steps:
        return None
    peak = costs.peaks(ctx.device["kind"])
    least = ssm_costs.state_update_bytes(cfg, slot_steps) \
        / (peak["hbm_gbps"] * 1e9)
    return 100.0 * least / seconds
