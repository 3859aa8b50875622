"""Mean number of occupied slots, per tick of the window (the engine's
own per-tick counter)."""
LAYER = "scheduler (runtime/scheduler.py, Engine._admit)"
UNIT = "slots"
BETTER = "higher"
MOVES = "output_tok_s"


def read(ctx):
    xs = ctx.engine_metrics.active_slots[ctx.window.tick_from:]
    return sum(xs) / len(xs) if xs else None
