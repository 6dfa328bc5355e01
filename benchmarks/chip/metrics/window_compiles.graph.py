"""XLA programs compiled, or loaded from the persistent cache, inside the
measured window of a graph cell (the program's ``jax/compiles``
counter); set-up warms every shape, so it should read 0."""


def read(ctx):
    return ctx.compiles
