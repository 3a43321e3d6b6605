"""Share of the window in which the engine's worker, the one thread that
drives the device, was not waiting for work: 100 x (1 - its
`engine.idle` time / the window), averaged over the workers (the threads
with an `engine.cycle` or `engine.idle` span; a worker that never
waited reads 100)."""
from bench import program_trace as pt

UNIT = "%"


def read(ctx):
    idle = pt.clipped(ctx, "engine.idle")
    workers = {a["thread"] for *_, a in idle + pt.spans(ctx, "engine.cycle")}
    if not workers:
        return None
    idle_ns = pt.total_ns(idle)
    return 100.0 * (1.0 - idle_ns / len(workers) / pt.window_ns(ctx))
