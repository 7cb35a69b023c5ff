"""Milliseconds a decoded token stood behind prefills: in every tick that
runs a prefill phase the engine adds the phases' seconds, on its own clock,
times the sequences that hold a first token and so wait for the decode
behind them (``engine.prefill_stall_seq_s``); over ``engine.decode_tokens``.
With ``tick_decode_phase_ms / scan_steps_per_dispatch`` it makes up
``engine_tpot_ms``.  Reads 0 where prefills fall while nobody decodes.  None
where the program counts no such thing (the name is absent), or decoded
nothing."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"


def read(ctx):
    c = ctx.counters
    tokens = c.get("engine.decode_tokens", 0.0)
    if "engine.prefill_stall_seq_s" not in c or not tokens:
        return None
    return 1e3 * c["engine.prefill_stall_seq_s"] / tokens
