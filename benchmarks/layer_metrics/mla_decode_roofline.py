"""The absorbed decode walk over the latent cache (``mla_paged_attention``)
as a share of its roofline: the least time the chip could take for the rows
the walk read (``engine.mla_decode_row_reads`` counted while traced), which is
the LARGER of their bytes over the chip's peak bandwidth and their operations
over its bf16 peak (``mla_costs``: at 60 operations a byte the walk is near
the ridge), over the kernel's self time in the trace.  None where the model
has no latent attention, the program no such counter or the trace no such
operation."""

from benchmarks.trace import costs, mla_costs

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    cfg = ctx.engine.model_cfg
    if ctx.trace is None or not mla_costs.has_latent(cfg):
        return None
    seconds = mla_costs.seconds_of(ctx.trace, mla_costs.DECODE)
    rows = ctx.trace["counters"].get("engine.mla_decode_row_reads")
    if not seconds or not rows:
        return None
    peak = costs.peaks(ctx.device["kind"])
    least = max(mla_costs.decode_bytes(cfg, rows) / (peak["hbm_gbps"] * 1e9),
                mla_costs.decode_ops(cfg, rows)
                / (peak["bf16_tflops"] * 1e12))
    return 100.0 * least / seconds
