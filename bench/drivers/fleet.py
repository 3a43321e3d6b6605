"""The served fleet: `ReplicatedSolverFleet` -> `AsyncSolverEngine` ->
`SolverService.flush_all` -> the arena executor, driven by one client.

The fleet takes the configuration's `engine` and `router` settings.
Set-up programs every tenant on every replica through `fleet.program`,
then warms every shape the engine can form under its flush rules, so that
nothing compiles in the window whatever the window's stalls and bursts.
A dispatch takes a whole bucket of M distinct tenants whose longest queue
is k, so M + k - 1 <= the bucket's size: at most the engine's
`max_pending`, and the mix's `outstanding` in a closed loop.
`flush_all` pads k to a power of two with an eager `jnp.pad` that
compiles once per (M, k) and runs the executor at (M, k_pad); a
single-tenant bucket (M = 1) takes `solve_many`, which pads and slices
once per k.  On a TPU v5e each such compile takes 0.5 to 1 s, long enough
in the window to fill the queue.  The warm-up runs one flush per (M, k)
for M = 1 and per (M, k_pad) for M > 1, and the pad alone for every
other (M, k), on a few threads.
"""
from __future__ import annotations

import inspect
import threading
import time
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, stats, traffic
from bench.spans import traced_service_class
from repro.serve import (BackpressureError, EngineStoppedError,
                         NoReplicaAvailableError)

WAIT_AFTER_S = 60.0      # answers due in the window may come this late
BACKOFF_S = (0.005, 1.0)  # least wait after a first refusal, doubling with
                          # each refusal in a row, and the longest wait
INF = float("inf")
WARM_THREADS = 8        # compiles in parallel in set-up
REFUSALS = (BackpressureError, NoReplicaAvailableError, EngineStoppedError)


def _pow2(k: int) -> int:
    return 1 << (k - 1).bit_length()


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
        self.n, self.tenants = cfg["n"], cfg["tenants"]
        self.ids = [f"t{i}" for i in range(self.tenants)]
        self.wait_after = float(mix.get("wait_after_s", WAIT_AFTER_S))

    # -- set-up ---------------------------------------------------------

    def setup(self) -> dict:
        from repro.serve import ReplicatedSolverFleet
        cfg, n = self.cfg, self.n
        t0 = time.perf_counter()
        mats = data.wishart_batch(jnp.asarray(data.root_key(self.seed, 1)),
                                  self.tenants, n, cfg["wishart_aspect"])
        self.mats_host = np.asarray(mats)
        self.keys = data.split_keys(data.root_key(self.seed, 2), self.tenants)
        self.pool = traffic.rhs_pool(self.mix, n, self.seed)
        service = traced_service_class(self.rec)
        acfg, stages = self.acfg, cfg["stages"]
        self.engine_kw = dict(cfg.get("engine", {}))
        self.fleet = ReplicatedSolverFleet(
            lambda: service(acfg, stages), cfg["replicas"],
            devices=jax.devices()[:cfg["replicas"]],
            engine_kw=self.engine_kw, **cfg.get("router", {}))
        t1 = time.perf_counter()
        for i, mid in enumerate(self.ids):
            self.fleet.program(mid, mats[i], key=self.keys[i])
        t2 = time.perf_counter()
        shapes = self._warm()
        t3 = time.perf_counter()
        self.fleet.start()
        return {"data_s": t1 - t0, "program_s": t2 - t1, "warm_s": t3 - t2,
                "warm_shapes": shapes}

    def _bucket(self) -> int:
        """The most requests one dispatch can take."""
        from repro.serve import AsyncSolverEngine
        defaults = {k: p.default for k, p in inspect.signature(
            AsyncSolverEngine.__init__).parameters.items()}
        max_pending = self.engine_kw.get("max_pending",
                                         defaults["max_pending"])
        return min(max_pending, int(self.mix.get("outstanding", max_pending)))

    def _warm_set(self):
        """(flushes, pads): the (M, k) of each warm flush, and the
        (M, k) whose pad alone is warmed."""
        bucket = self._bucket()
        w = traffic.tenant_weights(self.mix, self.tenants)
        most = min(int((w > 0).sum()), bucket)
        flushes, pads = [(1, k) for k in range(1, bucket + 1)], []
        for m in range(2, most + 1):
            ks = range(1, bucket - m + 2)
            flushes += [(m, p) for p in sorted({_pow2(k) for k in ks})]
            pads += [(m, k) for k in ks if _pow2(k) != k]
        return flushes, pads

    def _warm(self) -> int:
        from concurrent.futures import ThreadPoolExecutor

        from repro.core.blockamc import pad_rhs_pow2
        flushes, pads = self._warm_set()
        zero = np.zeros(self.n, np.float32)
        for eng in self.fleet.replica_engines().values():
            svc = eng.service
            with jax.default_device(eng.device):
                for m, k in flushes:
                    for j, mid in enumerate(self.ids[:m]):
                        for _ in range(k if j == 0 else 1):
                            svc.submit(mid, zero)
                    svc.flush_all(self.ids[:m])

            def pad(shape, device=eng.device):
                with jax.default_device(device):
                    pad_rhs_pow2(jnp.asarray(np.zeros(shape, np.float32)))[
                        0].block_until_ready()
            with ThreadPoolExecutor(WARM_THREADS) as pool:
                list(pool.map(pad, [(m, self.n, k) for m, k in pads]))
        return len(flushes) + len(pads)

    # -- the measured window ---------------------------------------------

    _EVENTS = ("rejected", "retries", "straggles", "isolations",
               "quarantines", "reprograms", "degraded")

    def _counters(self) -> dict:
        engines = self.fleet.replica_engines().values()
        c = {k: sum(getattr(e.stats, k) for e in engines)
             for k in ("answered", "dispatches") + self._EVENTS}
        c["drains"] = self.fleet.stats.drains
        c["replacements"] = self.fleet.stats.replacements
        return c

    def measure(self, seconds: float, window) -> dict:
        loop = self.mix["loop"]
        c0 = self._counters()
        if loop == "open":
            out = self._open(seconds, window)
        elif loop == "closed":
            out = self._closed(seconds, window)
        else:
            raise ValueError(f"unknown loop {loop!r}")
        c1 = self._counters()
        out["counters"] = {k: c1[k] - c0[k] for k in c0}
        flushes = [(a["rhs"], t1 - t0) for name, t0, t1, a in self.rec.spans
                   if name == "bench.flush_all"]
        out["notes"]["largest_bucket"] = max((r for r, _ in flushes),
                                             default=0)
        out["notes"]["longest_flush_ms"] = max((d for _, d in flushes),
                                               default=0) * 1e-6
        # the window's unusual events (retries, stragglers, drains, ...)
        out["notes"]["events"] = {k: v for k, v in out["counters"].items()
                                  if k not in ("answered", "dispatches") and v}
        return out

    # -- the client ------------------------------------------------------
    #
    # A refusal (backpressure, no routable replica, a replica stopping)
    # is not an answer.  The client keeps refused requests in one FIFO
    # backlog, in front of any request that falls due meanwhile, and
    # sends nothing until the server's own `retry_after_s` has passed (a
    # doubling backoff where it names none, or names less); then it sends
    # the backlog in order until the next refusal.  So a full server sees
    # one refused send per wait, not one per waiting request, and the
    # backlog drains at the server's own pace.  A request's latency runs
    # from the moment it was due to its answer, so every wait counts.
    # Any other error settles the request as errored.

    def _begin(self, count: int) -> None:
        self.t_done = np.full(count, np.nan)
        self.results, self.errors = [None] * count, {}
        self.cv = threading.Condition()
        self.n_done, self.refusals, self.streak = 0, 0, 0
        self.backlog, self.t_resume = deque(), 0.0

    def _settle(self, i, result=None, error=None) -> None:
        with self.cv:
            self.t_done[i] = time.perf_counter()
            if error is None:
                self.results[i] = result
            else:
                self.errors[i] = error
            self.n_done += 1
            self.cv.notify_all()

    def _refused(self, i, exc) -> None:
        with self.cv:
            wait = max(getattr(exc, "retry_after_s", None) or 0.0,
                       BACKOFF_S[0] * 2.0 ** self.streak)
            self.streak += 1
            self.refusals += 1
            self.backlog.appendleft(i)
            self.t_resume = max(self.t_resume,
                                time.perf_counter() + min(wait, BACKOFF_S[1]))
            self.cv.notify_all()

    def _on_done(self, i, fut) -> None:
        try:
            result = fut.result()
        except REFUSALS as e:
            self._refused(i, e)
        except BaseException as e:                     # noqa: BLE001
            self._settle(i, error=repr(e))
        else:
            self._settle(i, result=result)

    def _send(self, i) -> None:
        try:
            with self.rec.span("bench.submit"):
                fut = self.fleet.submit(self.ids[self.req_tenant[i]],
                                        self.pool[self.req_rhs[i]])
        except REFUSALS as e:
            self._refused(i, e)
        except Exception as e:                         # noqa: BLE001
            self._settle(i, error=repr(e))
        else:
            with self.cv:
                self.streak = 0
            fut.add_done_callback(partial(self._on_done, i))

    def _serve(self, t0, seconds, window, next_new, idle_span) -> int:
        """The client's one thread.  `next_new(now, started, is_open)`
        gives the time the next new request is due (None: no more); each
        due request joins the backlog's tail, and the backlog is sent in
        order whenever no refusal holds it back.  The window closes at
        `t0 + seconds`; the client then sends what is still due and waits
        for every answer, up to the mix's `wait_after_s` (WAIT_AFTER_S)
        past the close.  Returns the requests started."""
        t_close, t_stop = t0 + seconds, t0 + seconds + self.wait_after
        started, is_open = 0, True
        while True:
            i, closing = None, False
            with self.cv:
                now = time.perf_counter()
                if is_open and now >= t_close:
                    is_open, closing = False, True
                t_new = next_new(now, started, is_open)
                if t_new is not None and t_new <= now:
                    self.sent[started] = now
                    self.backlog.append(started)
                    started += 1
                    continue
                if self.backlog and now >= self.t_resume:
                    i = self.backlog.popleft()
                elif not closing:
                    if now >= t_stop or (t_new is None and not self.backlog
                                         and self.n_done >= started):
                        break
                    t_next = min(INF if t_new is None else t_new,
                                 self.t_resume if self.backlog else INF,
                                 t_close if is_open else t_stop)
                    with self.rec.span(idle_span if is_open
                                       else "bench.wait"):
                        self.cv.wait(t_next - now)
            if closing:
                window.end()
            if i is not None:
                self._send(i)
        if is_open:
            window.end()
        return started

    def _open(self, seconds, window) -> dict:
        sched = traffic.open_schedule(self.mix, self.tenants, seconds,
                                      self.seed)
        count = len(sched["t"])
        self.req_tenant, self.req_rhs = sched["tenant"], sched["rhs"]
        self._begin(count)
        self.sent = np.full(count, np.nan)
        window.start()
        t0 = time.perf_counter()
        due = t0 + sched["t"]
        # every request due in the window is sent, however late the
        # client runs
        self._serve(t0, seconds, window,
                    lambda now, j, is_open: due[j] if j < count else None,
                    "bench.client_sleep")
        failed = self._failed(count)
        lat = stats.latencies(due, self.t_done, failed,
                              seconds + self.wait_after)
        late = self.sent - due
        return {"attempted": count, "failed": int(failed.sum()),
                "latency_s": lat, "window_s": seconds,
                "e2e": {"p50_ms": stats.percentile(lat, 50) * 1e3},
                "notes": {"requests": count, "refusals": self.refusals,
                          "send_late_p50_ms": float(np.nanmedian(late) * 1e3),
                          "send_late_max_ms": float(np.nanmax(late) * 1e3)}}

    def _closed(self, seconds, window) -> dict:
        slots = int(self.mix["outstanding"])
        seq = traffic.closed_tenants(self.mix, self.tenants, self.seed)
        cap = int(self.mix.get("max_requests", 2_000_000))
        self.req_tenant = seq[np.arange(cap) % len(seq)]
        self.req_rhs = np.arange(cap) % len(self.pool)
        self._begin(cap)
        self.sent = np.full(cap, np.nan)
        window.start()
        t0 = time.perf_counter()

        def next_new(now, started, is_open):
            # a refused request keeps its slot until it is answered
            if started >= cap or not is_open:
                return None
            return now if started - self.n_done < slots else INF

        count = self._serve(t0, seconds, window, next_new, "bench.wait")
        self.t_done, self.results = self.t_done[:count], self.results[:count]
        self.req_tenant = self.req_tenant[:count]
        self.req_rhs = self.req_rhs[:count]
        failed = self._failed(count)
        solves = stats.rate(self.t_done, ~failed, t0, seconds)
        return {"attempted": count, "failed": int(failed.sum()),
                "window_s": seconds,
                "e2e": {"solves_per_s": solves},
                "notes": {"requests": count, "refusals": self.refusals,
                          "answered_in_window": round(solves * seconds)}}

    def _failed(self, count) -> np.ndarray:
        bad = np.zeros(count, bool)
        for i in range(count):
            r = self.results[i]
            bad[i] = (r is None or r.mode != "analog"
                      or not np.all(np.isfinite(r.x)))
        return bad

    # -- after the window -------------------------------------------------

    def memory_peak(self) -> int:
        peaks = []
        for eng in self.fleet.replica_engines().values():
            mem = eng.device.memory_stats() or {}
            peaks.append(int(mem.get("peak_bytes_in_use", 0)))
        return max(peaks)

    def release(self):
        self.fleet.stop()
        self.fleet = None

    def compare(self, ref, candidates, sample: int) -> dict:
        """Numbers compared for each candidate ("program": the answers
        served in the window; "control": the reference run at the
        control's precision over the same sampled requests)."""
        from bench.check import rel_gap
        count = len(self.results)
        pick = np.sort(traffic.rng(self.seed, 7).permutation(count)[:sample])
        groups = {}
        for i in pick:
            if self.results[i] is not None:
                groups.setdefault(int(self.req_tenant[i]), []).append(i)
        gaps = {c: [] for c in candidates}
        for t, idx in sorted(groups.items()):
            a = self.mats_host[t][None]
            key = self.keys[t][None]
            b = self.pool[self.req_rhs[idx]].T[None]
            x_ref = ref.solve(self.cfg, a, key, b, be=ref.REFERENCE)[0]
            for c in candidates:
                if c == "program":
                    x = np.stack([self.results[i].x for i in idx], axis=1)
                else:
                    x = np.asarray(ref.solve(self.cfg, a, key, b,
                                             be=ref.CONTROL)[0])
                gaps[c].extend(rel_gap(x, x_ref))
        # every request due must be answered: one that ended in an error
        # other than a refusal is `errored`; one with no answer by
        # `wait_after_s` past the close, refused all along or never
        # resolved, is `unanswered`
        errored = len(self.errors)
        unanswered = sum(1 for i in range(count) if self.results[i] is None
                         and i not in self.errors)
        not_analog = sum(1 for r in self.results
                         if r is not None and r.mode != "analog")
        own = {"errored": errored, "unanswered": unanswered,
               "not_analog": not_analog}
        return {c: {"max_rel_gap": max(gaps[c], default=0.0),
                    **{k: v if c == "program" else 0 for k, v in own.items()}}
                for c in candidates}
