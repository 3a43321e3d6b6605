"""The busiest device's share of the served executor's device time: the
programs whose name holds `execute_arena` (packed dispatches,
single-tenant flushes and canary solves) that ran wholly inside the
traced window, their durations summed per device.  With one replica per
device, 25% is an even spread of the work over four replicas and 100% is
one replica doing all of it."""
from bench import trace

UNIT = "%"


def read(ctx):
    if ctx.trace is None:
        return None
    w = trace.window(ctx.trace)
    if w is None:
        return None
    work = [sum(d for name, s, d in dev["modules"]
                if "execute_arena" in name and s >= w[0] and s + d <= w[1])
            for dev in ctx.trace["devices"].values()]
    if not sum(work):
        return None
    return 100.0 * max(work) / sum(work)
