"""Device time per call of the served arena executor: the programs whose
name holds `execute_arena` (packed or single-tenant) that ran inside a
`bench.flush_all` span, from the device trace."""
from bench import trace

UNIT = "us"


def read(ctx):
    if ctx.trace is None:
        return None
    calls = trace.modules_in_spans(ctx.trace, r"execute_arena",
                                   "bench.flush_all")
    if not calls:
        return None
    return sum(c[2] for c in calls) / len(calls) * 1e-3
