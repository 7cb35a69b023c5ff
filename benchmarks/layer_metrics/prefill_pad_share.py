"""Share of prefilled token positions that are padding: 1 less
``engine.prefill_tokens`` (real prompt tokens) over
``engine.prefill_padded_tokens`` (rows x bucket of every prefill dispatch,
pad rows included).  None where the program does not count padded tokens."""

LAYER = "Model step (models/llama.py)"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(ctx):
    padded = ctx.counters.get("engine.prefill_padded_tokens", 0.0)
    if not padded:
        return None
    return 100.0 * (1.0 - ctx.counters.get("engine.prefill_tokens", 0.0)
                    / padded)
