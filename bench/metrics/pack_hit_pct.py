"""Share of packed flushes whose bucket had the cached membership, so
nothing was re-packed: `service.pack` spans with `hit` 1."""
from bench import program_trace as pt

UNIT = "%"


def read(ctx):
    ss = pt.spans(ctx, "service.pack")
    if not ss:
        return None
    return 100.0 * sum(int(a["hit"]) for _, _, _, a in ss) / len(ss)
