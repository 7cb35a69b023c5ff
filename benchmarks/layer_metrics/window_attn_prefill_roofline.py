"""The prefill's banded attention calls (``flash_attention_window``) as a
share of their roofline: the least time the chip could take for the band's
operations (``mixed_attn_costs.band_ops`` for the
``engine.attn_window_prefill_tokens`` counted while traced: positions x window
layers, pad included) at the chip's bf16 peak, over the calls' self time in
the trace.  None where the model has no such layer, the program no such
counter or the trace no such operation."""

from benchmarks.trace import costs, mixed_attn_costs

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(ctx):
    cfg = ctx.engine.model_cfg
    if ctx.trace is None or not mixed_attn_costs.has_window(cfg):
        return None
    seconds = mixed_attn_costs.seconds_of(ctx.trace,
                                          mixed_attn_costs.WINDOW_PREFILL)
    positions = ctx.trace["counters"].get("engine.attn_window_prefill_tokens")
    if not seconds or not positions:
        return None
    peak = costs.peaks(ctx.device["kind"])
    least = mixed_attn_costs.band_ops(cfg, positions) \
        / (peak["bf16_tflops"] * 1e12)
    return 100.0 * least / seconds
