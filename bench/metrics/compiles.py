"""XLA compilations and persistent-cache loads inside the window (a
`jax.monitoring` listener); 0 once the warm-up covers every shape."""
UNIT = "count"


def read(ctx):
    return ctx.compiles
