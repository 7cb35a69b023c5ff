"""Share of the device's busy time spent in the attention of a model with
latent attention: the absorbed decode walk (``mla_paged_attention``), the
prefill's calls (``flash_attention``) and the two einsums that carry a decode
query into the latent's space and the attended latents out of it
(``mla_costs.absorb_pattern``).  The projections around them are not in it.
None where the model has no latent attention or the trace none of them."""

from benchmarks.trace import mla_costs

LAYER = "Model step (models/llama.py)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    cfg = ctx.engine.model_cfg
    if ctx.trace is None or not mla_costs.has_latent(cfg):
        return None
    seconds = mla_costs.seconds_of(ctx.trace, mla_costs.DECODE,
                                   mla_costs.PREFILL,
                                   mla_costs.absorb_pattern(cfg))
    busy = ctx.trace["busy_s"]
    return 100.0 * seconds / busy if busy and seconds else None
