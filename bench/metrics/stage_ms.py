"""Host assembly, upload and pad of a flush's right-hand sides: time in
`service.stage` spans per `service.flush_all`."""
from bench import program_trace as pt

UNIT = "ms"


def read(ctx):
    return pt.per_ms(ctx, "service.stage", "service.flush_all")
