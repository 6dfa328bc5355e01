"""Seconds per solve inside the engine's ``views`` spans in a hypergraph
cell (host clock): building a level's device views and dispatching their
upload, the first time refinement needs them."""
from benchmarks.chip.readers import per_solve_span


def read(ctx):
    return per_solve_span(ctx, "views")
