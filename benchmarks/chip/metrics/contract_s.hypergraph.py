"""Seconds per solve inside the engine's ``contract`` spans in a hypergraph
cell (host clock): building each coarse level from its clusters."""
from benchmarks.chip.readers import per_solve_span


def read(ctx):
    return per_solve_span(ctx, "contract")
