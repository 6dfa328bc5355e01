"""Share of the refinement program's row-rounds that moved no vertex in a
graph cell, in % (the engine's device-counted ``refine/rounds`` and
``refine/rounds_moved``); padding rows and masked rounds count as
wasted."""
from benchmarks.chip.counters import wasted_rounds


def read(ctx):
    return wasted_rounds(ctx.events, "refine/")
