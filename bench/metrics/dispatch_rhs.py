"""Right-hand sides answered per engine dispatch over the window
(`EngineStats.answered / dispatches`, deltas across the window)."""
UNIT = "rhs"


def read(ctx):
    c = ctx.counters
    if not c.get("dispatches"):
        return None
    return c["answered"] / c["dispatches"]
