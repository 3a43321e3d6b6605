"""The device-to-host copy of a flush's answers, and the wait for the
device that it holds: time in `service.fetch` spans per
`service.flush_all`."""
from bench import program_trace as pt

UNIT = "ms"


def read(ctx):
    return pt.per_ms(ctx, "service.fetch", "service.flush_all")
