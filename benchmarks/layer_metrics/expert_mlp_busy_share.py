"""Share of the device's busy time spent in the expert MLP's operations (the
three expert einsums and the dequantization feeding them), found in the
trace by the shapes in their HLO text (``costs.expert_mlp_pattern``).  With dense soft dispatch every expert
runs on every token, so three quarters of it is work on unrouted experts."""

from benchmarks.trace import costs

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(ctx):
    if ctx.trace is None or not ctx.conf.get("num_local_experts"):
        return None
    pattern = costs.expert_mlp_pattern(ctx.conf["num_local_experts"],
                                       ctx.conf["hidden_size"],
                                       ctx.conf["intermediate_size"])
    seconds = costs.kernel_time(ctx.trace["op_seconds"], pattern,
                                ctx.trace["op_text"])
    busy = ctx.trace["busy_s"]
    return 100.0 * seconds / busy if busy and seconds else None
