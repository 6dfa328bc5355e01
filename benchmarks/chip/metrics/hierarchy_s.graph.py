"""Seconds per solve inside the engine's ``hierarchy`` spans in a graph
cell (host clock): coarsening and contraction down to the coarsest
level."""
from benchmarks.chip.readers import per_solve_span


def read(ctx):
    return per_solve_span(ctx, "hierarchy")
