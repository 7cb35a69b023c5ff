"""The window layers' decode kernel (``window_paged_attention``) as a share
of its roofline: the least time the chip could take to read the keys and
values the windows' queries see (``min(length, window)`` tokens a live slot,
layer and step, by ``mixed_attn_costs.decode_bytes`` for the
``engine.attn_window_tokens`` counted while traced, over the chip's peak
bandwidth), over the kernel's self time in the trace.  Bound by memory
bandwidth.  None where the model has no such layer, the program no such
counter or the trace no such operation."""

from benchmarks.trace import costs, mixed_attn_costs

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "gap_ms_p50"
KERNEL = mixed_attn_costs.WINDOW_DECODE
COUNTER = "engine.attn_window_tokens"


def read(ctx, kernel=KERNEL, counter=COUNTER):
    cfg = ctx.engine.model_cfg
    if ctx.trace is None or not mixed_attn_costs.has_window(cfg):
        return None
    seconds = mixed_attn_costs.seconds_of(ctx.trace, kernel)
    tokens = ctx.trace["counters"].get(counter)
    if not seconds or not tokens:
        return None
    peak = costs.peaks(ctx.device["kind"])
    least = mixed_attn_costs.decode_bytes(
        cfg, ctx.engine.engine_cfg, tokens) / (peak["hbm_gbps"] * 1e9)
    return 100.0 * least / seconds
