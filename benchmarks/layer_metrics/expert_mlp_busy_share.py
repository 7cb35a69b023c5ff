"""Share of the device's busy time spent in the expert MLP's operations (the
three expert einsums and the dequantization feeding them), found in the
trace by the shapes in their HLO text (``costs.expert_mlp_pattern``); the
shapes are the built model's (``engine.model_cfg``), whatever its
configuration file calls them.  With dense soft dispatch every expert runs
on every token, so three quarters of it is work on unrouted experts."""

from benchmarks.trace import costs

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(ctx):
    cfg = ctx.engine.model_cfg
    if ctx.trace is None or not cfg.n_experts:
        return None
    pattern = costs.expert_mlp_pattern(cfg.n_experts, cfg.hidden_size,
                                       cfg.intermediate_size)
    seconds = costs.kernel_time(ctx.trace["op_seconds"], pattern,
                                ctx.trace["op_text"])
    busy = ctx.trace["busy_s"]
    return 100.0 * seconds / busy if busy and seconds else None
