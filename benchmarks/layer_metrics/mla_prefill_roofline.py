"""The prefill's attention calls of a model with latent attention
(``flash_attention`` at a key width of ``qk_head_dim`` and a value width of
``v_head_dim``) as a share of their roofline: the least time the chip could
take for the causal pairs' operations (``mla_costs.prefill_ops`` for the
``engine.mla_prefill_pairs`` counted while traced) at its bf16 peak, over the
calls' self time in the trace.  None where the model has no latent attention,
the program no such counter or the trace no such operation."""

from benchmarks.trace import costs, mla_costs

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(ctx):
    cfg = ctx.engine.model_cfg
    if ctx.trace is None or not mla_costs.has_latent(cfg):
        return None
    seconds = mla_costs.seconds_of(ctx.trace, mla_costs.PREFILL)
    pairs = ctx.trace["counters"].get("engine.mla_prefill_pairs")
    if not seconds or not pairs:
        return None
    peak = costs.peaks(ctx.device["kind"])
    least = mla_costs.prefill_ops(cfg, pairs) / (peak["bf16_tflops"] * 1e12)
    return 100.0 * least / seconds
