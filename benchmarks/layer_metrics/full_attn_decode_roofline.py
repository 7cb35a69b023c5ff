"""The full layers' decode kernel (``paged_attention``) of a model that also
has window layers, as a share of its roofline: the least time the chip could
take to read every live token's keys and values in the FULL layers alone
(``mixed_attn_costs.decode_bytes`` for the ``engine.attn_full_tokens`` counted
while traced), over the kernel's self time in the trace.
``paged_attn_roofline`` multiplies by every layer and does not hold here.
None where the model has no window layer, the program no such counter or the
trace no such operation."""

from benchmarks.layer_metrics import window_attn_decode_roofline
from benchmarks.trace import mixed_attn_costs

LAYER = "Kernels (ops/)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    return window_attn_decode_roofline.read(
        ctx, mixed_attn_costs.FULL_DECODE, "engine.attn_full_tokens")
