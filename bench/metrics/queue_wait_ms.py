"""Time a request waits in the engine's queue: the mean `engine.queued`
span, from its admission to the start of the cycle that takes it (the
flush triggers' wait, and the worker's other work meanwhile)."""
from bench import program_trace as pt

UNIT = "ms"


def read(ctx):
    return pt.mean_ms(ctx, "engine.queued")
