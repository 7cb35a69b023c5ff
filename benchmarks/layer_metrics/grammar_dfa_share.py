"""Share of constrained runs that got a compiled DFA (which rides the jitted
decode scan) and not the interpreted FSM (which holds the whole batch to one
step per tick): ``serve.grammar.dfa.*`` over dfa + interpreted."""

LAYER = "Grammar (engine/constrain.py)"
UNIT = "%"
MOVES = "gap_ms_p50"


def read(ctx):
    def total(mode):
        return sum(v for k, v in ctx.counters.items()
                   if k.startswith(f"serve.grammar.{mode}."))

    dfa, interpreted = total("dfa"), total("interpreted")
    if dfa + interpreted == 0:
        return None
    return 100.0 * dfa / (dfa + interpreted)
