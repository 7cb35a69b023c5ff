"""Milliseconds of a tick that none of its phases covers: ``engine.tick``
seconds less the six phases' (``engine.tick.reap``, ``.prefill_chunk``,
``.admission``, ``.first_tokens``, ``.eviction``, ``.decode``), over the
ticks.  The check that the partition is whole: what is left is the branching
between the phases and the spans' own cost.  A phase that never ran counts 0.
None where the program does not cut its tick into phases (no
``engine.tick.decode`` timer); it never reads the whole tick as unnamed."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "ms"
MOVES = "gap_ms_p50"

PHASES = ("engine.tick.reap", "engine.tick.prefill_chunk",
          "engine.tick.admission", "engine.tick.first_tokens",
          "engine.tick.eviction", "engine.tick.decode")


def read(ctx):
    c = ctx.counters
    n = c.get("engine.tick.count", 0.0)
    if not n or not c.get("engine.tick.decode.count", 0.0):
        return None
    named = sum(c.get(p + ".total_s", 0.0) for p in PHASES)
    return 1e3 * (c.get("engine.tick.total_s", 0.0) - named) / n
