"""Device time per Monte-Carlo sweep: the `solve_batched` program inside
each `bench.mc_call` span, from the device trace."""
from bench import trace

UNIT = "ms"


def read(ctx):
    if ctx.trace is None:
        return None
    calls = trace.modules_in_spans(ctx.trace, r"solve_batched",
                                   "bench.mc_call")
    if not calls:
        return None
    return sum(c[2] for c in calls) / len(calls) * 1e-6
