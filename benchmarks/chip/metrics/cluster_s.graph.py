"""Seconds per solve inside the engine's ``cluster`` spans in a graph
cell (host clock): choosing each level's clusters, inside ``coarsen``."""
from benchmarks.chip.readers import per_solve_span


def read(ctx):
    return per_solve_span(ctx, "cluster")
