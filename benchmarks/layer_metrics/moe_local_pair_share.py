"""Share of the (position, expert) pairs the expert layers routed whose
expert is held on this chip: ``engine.moe_local_pairs`` (counted on the
device from the router's choices, fetched with the tick's tokens) over
``engine.moe_routed_pairs`` (positions x picks x expert layers, from the
shapes).  With the experts held a quarter of the router's and seeded
weights it reads 25%: far from that, the share or the router is wrong.
None where the program counts no routed pairs."""

LAYER = "Model step (models/llama.py)"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(ctx):
    routed = ctx.counters.get("engine.moe_routed_pairs", 0.0)
    if not routed:
        return None
    return 100.0 * ctx.counters.get("engine.moe_local_pairs", 0.0) / routed
