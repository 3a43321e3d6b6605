"""Share of the roofline the served arena executor reaches: the least
time the chip could take for each call's work (bench/work.py, from the
plan's schedule and the call's real tenant and rhs counts) over the
executor's device time in the trace, summed over the window's calls."""
from bench import trace, work

UNIT = "%"


def read(ctx):
    if ctx.trace is None:
        return None
    calls = trace.modules_in_spans(ctx.trace, r"execute_arena",
                                   "bench.flush_all")
    if not calls:
        return None
    c = ctx.cfg
    t_min = t_dev = 0.0
    for _, _, dev_ns, stats in calls:
        flops, nbytes = work.executor_work(c["n"], c["stages"],
                                           c["array_size"],
                                           int(stats["tenants"]),
                                           int(stats["rhs"]))
        t_min += work.roofline_seconds(flops, nbytes, ctx.peaks)[0]
        t_dev += dev_ns * 1e-9
    return 100.0 * t_min / t_dev
