"""95th percentile of request latency over all requests due in the
window, each timed by the client from its scheduled send time to its
answer, as `p50_ms` is (`bench/stats.py`).  A machine stall of a second
or two moves it by several times its run-to-run spread, so it is read
here, in the traced run, and not held to a bound."""
UNIT = "ms"


def read(ctx):
    from bench import stats
    lat = getattr(ctx, "latency_s", None)
    if lat is None or len(lat) == 0:
        return None
    return stats.percentile(lat, 95) * 1e3
