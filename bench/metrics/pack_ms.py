"""Host time to find or build a bucket's packed arena plan: the mean
`service.pack` span (one per packed flush; a changed membership re-packs
every tenant's operators)."""
from bench import program_trace as pt

UNIT = "ms"


def read(ctx):
    return pt.mean_ms(ctx, "service.pack")
