"""Milliseconds of a tick spent in its prefill phases: the chunks of
sequences admitted earlier (``engine.tick.prefill_chunk``), admission
(``engine.tick.admission``: staging, the prefill dispatches, activation) and
the first tokens (``engine.tick.first_tokens``: the fetch that waits for the
tick's prefills, then their commit), their ``total_s`` over the ticks.  A
phase that never ran in the window counts 0.  None where the program does not
cut its tick into phases (no ``engine.tick.decode`` timer: ``.admission`` is
older than the cut and says nothing of it)."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"

PHASES = ("engine.tick.prefill_chunk", "engine.tick.admission",
          "engine.tick.first_tokens")


def read(ctx):
    c = ctx.counters
    n = c.get("engine.tick.count", 0.0)
    if not n or not c.get("engine.tick.decode.count", 0.0):
        return None
    return 1e3 * sum(c.get(p + ".total_s", 0.0) for p in PHASES) / n
