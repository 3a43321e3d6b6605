"""Set-up warms every shape the engine can form, so nothing compiles in
the window: buckets of any tenants and any queue lengths up to the
engine's `max_pending`, through the service and through the fleet."""
import time

import jax
import numpy as np
import pytest

from bench import registry, traffic
from bench.spans import CompileCounter, Recorder
from repro.serve import BackpressureError


def _set_up(root, workload):
    bm = registry.load_benchmark(root)
    cell = registry.find_cell(bm, workload)
    cfg = registry.load_config(bm, cell["config"], root)
    mix = registry.load_traffic(cell["traffic"], root)
    run = registry.load_driver(cfg["system"], root).Run(cfg, mix, 2**33 + 3,
                                                         Recorder())
    run.setup()
    return cfg, run


@pytest.mark.parametrize("workload", ["fig8-fleet.zipf", "fig8-fleet.hot1"])
def test_every_bucket_is_warm(tiny_root, workload):
    cfg, run = _set_up(tiny_root, workload)
    counter = CompileCounter()
    rng = np.random.default_rng(5)
    bucket = run._bucket()
    zero = np.zeros(run.n, np.float32)
    # the tenants the mix sends to
    sent_to = np.flatnonzero(traffic.tenant_weights(run.mix, run.tenants))
    try:
        counter.active = True
        # the service as the engine drives it: any tenants, any queues
        for eng in run.fleet.replica_engines().values():
            with jax.default_device(eng.device):
                for _ in range(12):
                    size = int(rng.integers(1, bucket + 1))
                    for t in rng.choice(sent_to, size):
                        eng.service.submit(run.ids[t], zero)
                    eng.service.flush_all()
        # the fleet, in bursts of up to a bucket
        futs = []
        for _ in range(6):
            for t in rng.choice(sent_to, bucket):
                try:
                    futs.append(run.fleet.submit(run.ids[t], zero))
                except BackpressureError:
                    pass
            time.sleep(0.05)
        for f in futs:
            f.result(timeout=60)
        counter.active = False
    finally:
        run.release()
    assert counter.total == 0
