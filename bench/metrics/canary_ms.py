"""The engine's health gate: time in `engine.canary` spans (one canary
solve and residual per tenant answered) per `engine.cycle`."""
from bench import program_trace as pt

UNIT = "ms"


def read(ctx):
    return pt.per_ms(ctx, "engine.canary", "engine.cycle")
