"""The Monte-Carlo design sweep: back-to-back `blockamc.solve_batched`
calls, each on a fresh Wishart matrix with `draws` fresh noise keys
(paper Fig. 8: 40 random simulations per point), from one caller.

Each call's inputs come from the seed and the call's index, made on the
device by one jitted function, so any call can be made again after the
window for the check.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, traffic


@partial(jax.jit, static_argnames=("n", "aspect", "draws"))
def _problem(key, i, n: int, aspect: int, draws: int):
    k = jax.random.fold_in(key, i)
    ka, kb, kk = jax.random.split(k, 3)
    a = data.wishart_batch(ka, 1, n, aspect)[0]
    b = jax.random.uniform(kb, (n,), jnp.float32, -1.0, 1.0)
    return a, b, jax.random.split(kk, draws)


class Run:
    def __init__(self, cfg: dict, mix: dict, seed: int, recorder):
        from repro.core.analog import AnalogConfig
        from repro.core.nonideal import NonidealConfig
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.rec = recorder
        self.acfg = AnalogConfig(
            g0=cfg["g0"], array_size=cfg["array_size"],
            nonideal=NonidealConfig(sigma=cfg["sigma"], r_wire=cfg["r_wire"],
                                    wire_model=cfg["wire_model"]))
        self.key = jnp.asarray(data.root_key(seed, 3))

    def problem(self, i: int):
        c = self.cfg
        return _problem(self.key, i, n=c["n"], aspect=c["wishart_aspect"],
                        draws=c["draws"])

    def call(self, i: int):
        from repro.core import blockamc
        a, b, keys = self.problem(i)
        return blockamc.solve_batched(a, b, keys, self.acfg,
                                      stages=self.cfg["stages"], mode="fused")

    def setup(self) -> dict:
        t0 = time.perf_counter()
        jax.block_until_ready(self.problem(0))
        t1 = time.perf_counter()
        jax.block_until_ready(self.call(0))
        return {"data_s": t1 - t0, "warm_s": time.perf_counter() - t1}

    def measure(self, seconds: float, window) -> dict:
        draws = self.cfg["draws"]
        self.outs, done = [], []
        window.start()
        t0 = time.perf_counter()
        t_end = t0 + seconds
        i = 0
        while time.perf_counter() < t_end:
            with self.rec.span("bench.mc_call", draws=draws):
                x = jax.block_until_ready(self.call(i))
            self.outs.append(x)
            done.append(time.perf_counter())
            i += 1
        window.end()
        span = done[-1] - t0
        finite = [bool(jnp.all(jnp.isfinite(x))) for x in self.outs]
        failed = draws * finite.count(False)
        return {"attempted": draws * i, "failed": failed, "window_s": span,
                "e2e": {"draws_per_s": draws * (i - finite.count(False))
                        / span},
                "counters": {"calls": i},
                "notes": {"calls": i, "seconds_per_call": span / i}}

    def memory_peak(self) -> int:
        stats = jax.devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def release(self):
        pass

    def compare(self, ref, candidates, sample: int) -> dict:
        """Numbers compared for each candidate over `sample` calls drawn
        from the seed (the window's last call always among them)."""
        from bench.check import rel_gap
        calls = len(self.outs)
        pick = {calls - 1}
        for c in traffic.rng(self.seed, 7).permutation(calls):
            if len(pick) >= sample:
                break
            pick.add(int(c))
        non_finite = sum(int((~np.isfinite(np.asarray(x))).any(axis=-1).sum())
                         for x in self.outs)
        gaps = {c: [] for c in candidates}
        for i in sorted(pick):
            a, b, keys = (np.asarray(v) for v in self.problem(i))
            x_ref = ref.solve(self.cfg, a[None], keys, b[None, :, None],
                              be=ref.REFERENCE)[..., 0]
            for c in candidates:
                if c == "program":
                    x = np.asarray(self.outs[i])
                else:
                    x = np.asarray(ref.solve(self.cfg, a[None], keys,
                                             b[None, :, None],
                                             be=ref.CONTROL)[..., 0])
                gaps[c].extend(rel_gap(x[..., None], x_ref[..., None]).ravel())
        self.outs = None
        return {c: {"max_rel_gap": max(gaps[c], default=0.0),
                    "non_finite": non_finite if c == "program" else 0}
                for c in candidates}
