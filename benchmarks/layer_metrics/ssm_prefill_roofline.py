"""The prefill's chunked scan (``ssm_chunk_scan``) as a share of its
roofline: the larger of the least times the chip could take for the chunked
form's operations and for the bytes it has to move
(``ssm_costs.chunk_scan_work``, for the ``engine.ssm_prefill_tokens`` counted
while traced: positions x Mamba layers, pad included), over the scan's self
time in the trace.  None where the model has no such layer, the program no
such counter or the trace no such operation."""

from benchmarks.trace import costs, ssm_costs

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(ctx):
    cfg = ctx.engine.model_cfg
    if ctx.trace is None or not ssm_costs.has_ssm(cfg):
        return None
    seconds = ssm_costs.seconds_of(ctx.trace,
                                   ssm_costs.chunk_scan_pattern(cfg))
    tokens = ctx.trace["counters"].get("engine.ssm_prefill_tokens")
    if not seconds or not tokens:
        return None
    peak = costs.peaks(ctx.device["kind"])
    ops, nbytes = ssm_costs.chunk_scan_work(cfg, tokens)
    least = max(ops / (peak["bf16_tflops"] * 1e12),
                nbytes / (peak["hbm_gbps"] * 1e9))
    return 100.0 * least / seconds
