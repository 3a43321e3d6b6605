"""Share of the traced window in which no operation ran on the device."""
from bench import trace

UNIT = "%"


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    w = trace.window(ctx.trace)
    if w is None:
        return None
    return 100.0 * (1.0 - trace.busy_ns(ctx.trace) / (w[1] - w[0]))
