"""Host time per Monte-Carlo call inside the program: the mean
`blockamc.solve_batched` span, which holds the jitted program's argument
handling and dispatch (the device waits through it), not its device
work."""
from bench import program_trace as pt

UNIT = "ms"


def read(ctx):
    return pt.mean_ms(ctx, "blockamc.solve_batched")
