"""Seconds per solve inside the engine's ``contract`` spans in a graph
cell (host clock): building each coarse level from its clusters."""
from benchmarks.chip.readers import per_solve_span


def read(ctx):
    return per_solve_span(ctx, "contract")
