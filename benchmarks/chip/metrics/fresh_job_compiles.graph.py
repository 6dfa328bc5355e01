"""XLA programs compiled by one job of a graph cell after the
traced window that lies outside the pool: a new partitioner seed, and a
new instance where the configuration's generator takes a seed.  The
program's ``jax/compiles`` counter less its ``jax/compile_cache_hits``:
programs the persistent cache did not hold.  What a user who brings a new
job pays besides the solve, which the window's fixed pool leaves out."""
from benchmarks.chip.readers import fresh_job


def read(ctx):
    return fresh_job(ctx, "compiles")
