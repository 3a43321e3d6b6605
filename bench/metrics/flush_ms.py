"""Host time per `SolverService.flush_all` call, from the benchmark's
`bench.flush_all` span (packing, upload, executor, download, scatter)."""
UNIT = "ms"


def read(ctx):
    d = [t1 - t0 for name, t0, t1, _ in ctx.spans if name == "bench.flush_all"]
    if not d:
        return None
    return sum(d) / len(d) * 1e-6
