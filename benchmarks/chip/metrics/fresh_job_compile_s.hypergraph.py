"""Seconds that job of a hypergraph cell spent getting its new programs
(the program's ``jax/compile_secs`` counter): compiling those the
persistent cache did not hold and loading those it did; see
``fresh_job_compiles.hypergraph``."""
from benchmarks.chip.readers import fresh_job


def read(ctx):
    return fresh_job(ctx, "compile_s")
