"""Share of the device clustering rounds that moved no vertex in a
hypergraph cell, in % (the engine's device-counted ``coarsen/lp_rounds``
and ``coarsen/lp_rounds_moved``)."""
from benchmarks.chip.counters import wasted_rounds


def read(ctx):
    return wasted_rounds(ctx.events, "coarsen/lp_")
