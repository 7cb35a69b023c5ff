"""Share of the decode steps' state updates that moved a LIVE sequence's
state on: ``engine.ssm_decode_live_slot_steps`` (slots that hold a sequence
x steps x Mamba-2 layers of every decode dispatch) over
``engine.ssm_decode_slot_steps`` (every slot: the step runs a dead slot's
update too).  100 in a closed loop that keeps every slot taken; below it an
open loop shows what an update over the live slots alone would save.  None
where the program counts no live slot steps (a model without such layers,
or a program before PR 44, which has the second counter alone)."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    c = ctx.counters
    every = c.get("engine.ssm_decode_slot_steps", 0.0)
    if not every or "engine.ssm_decode_live_slot_steps" not in c:
        return None
    return 100.0 * c["engine.ssm_decode_live_slot_steps"] / every
