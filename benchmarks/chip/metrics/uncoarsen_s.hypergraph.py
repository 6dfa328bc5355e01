"""Seconds per solve inside the engine's ``uncoarsen`` spans in a hypergraph
cell (host clock): projection and refinement from the coarsest level back
to the input."""
from benchmarks.chip.readers import per_solve_span


def read(ctx):
    return per_solve_span(ctx, "uncoarsen")
