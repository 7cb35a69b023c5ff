"""The paged-attention decode kernel's share of its roofline: the least time
the chip could take to read the keys and values the kernel had to read
(bytes from the live context lengths of each traced tick, by
``costs.paged_attention_bytes``, over the chip's peak bandwidth), over the
kernel's time in the trace.  Bound by memory bandwidth: a decode query does
two operations per cached byte.

ASSUMES a cache of keys and values in every layer for every token, all live
ones read at every step (``costs.kv_bytes_per_token``), so ``BENCHMARK.json``
lists the cells it holds in (``workloads``).  A model whose layers cache
something else is not added to that list: its PR adds a cost module and a
roofline reader of its own as new files, listing its own cells."""

from benchmarks.trace import costs

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = costs.kernel_time(ctx.trace["op_seconds"],
                                costs.PAGED_ATTENTION)
    if not seconds:
        return None
    nbytes = costs.paged_attention_bytes(
        ctx.engine.model_cfg, ctx.engine.engine_cfg,
        ctx.trace["ticks"])
    peak = costs.peaks(ctx.device["kind"])
    return 100.0 * (nbytes / (peak["hbm_gbps"] * 1e9)) / seconds
