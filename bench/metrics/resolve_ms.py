"""Settling a cycle's futures, the router's and the client's callbacks
included (they run on the engine's worker): time in `engine.resolve`
spans per `engine.cycle`."""
from bench import program_trace as pt

UNIT = "ms"


def read(ctx):
    return pt.per_ms(ctx, "engine.resolve", "engine.cycle")
