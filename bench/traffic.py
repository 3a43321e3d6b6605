"""The one traffic generator: every mix is a data file this module reads.

Mix keys (`bench/traffic/<mix>.json`):

- `loop`: "open" (requests are sent on a schedule whatever the system
  does) or "closed" (each of `outstanding` client slots sends its next
  request when its answer arrives).
- `rate_per_s` (open): mean arrival rate.  Arrivals are Poisson, made
  without a count that varies by seed: the window holds exactly
  round(rate x seconds) requests whose gaps are the exponential quantiles
  (j + 1/2)/N, shuffled by the seed.  Every seed sends the same set of
  gaps and the same tenant counts, in another order.
- `tenant_dist`: "zipf" (with `zipf_s`), "uniform", or "hot" (all
  requests to the first `hot_tenants` tenants).  Tenant counts are
  round(N x weight), so every seed has the same mix.
- `rhs_pool`: right-hand sides are drawn uniform in [-1, 1] from the seed
  into a pool of this many; request i sends pool row `rhs[i]`.
- `outstanding` (closed): client slots kept busy.
- `wait_after_s` (optional, fleet): how long past the window's close the
  client waits for the answers due in it, resending refused requests.
"""
from __future__ import annotations

import numpy as np

# Streams of one seed: each use draws from its own generator.
_ARRIVALS, _TENANTS, _RHS = 1, 2, 3


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def tenant_weights(mix: dict, tenants: int) -> np.ndarray:
    dist = mix.get("tenant_dist", "uniform")
    if dist == "zipf":
        w = 1.0 / np.arange(1, tenants + 1) ** float(mix["zipf_s"])
    elif dist == "uniform":
        w = np.ones(tenants)
    elif dist == "hot":
        w = np.zeros(tenants)
        w[:int(mix["hot_tenants"])] = 1.0
    else:
        raise ValueError(f"unknown tenant_dist {dist!r}")
    return w / w.sum()


def tenant_sequence(mix: dict, tenants: int, count: int,
                    seed: int) -> np.ndarray:
    """`count` tenant indices with counts round(count x weight) (largest
    remainders fill the rest), shuffled by the seed."""
    w = tenant_weights(mix, tenants)
    exact = w * count
    counts = np.floor(exact).astype(np.int64)
    short = count - int(counts.sum())
    if short:
        counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    seq = np.repeat(np.arange(tenants), counts)
    return rng(seed, _TENANTS).permutation(seq)


def open_schedule(mix: dict, tenants: int, seconds: float,
                  seed: int) -> dict:
    """Send times (seconds from the window's start), tenants and rhs rows
    of every request due in an open-loop window."""
    rate = float(mix["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()        # the gaps fill the window
    gaps = rng(seed, _ARRIVALS).permutation(gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    pool = int(mix["rhs_pool"])
    return {"t": t, "tenant": tenant_sequence(mix, tenants, count, seed),
            "rhs": np.arange(count) % pool}


def closed_tenants(mix: dict, tenants: int, seed: int,
                   period: int = 4096) -> np.ndarray:
    """The tenant of closed-loop request i is entry i % period."""
    return tenant_sequence(mix, tenants, period, seed)


def rhs_pool(mix: dict, n: int, seed: int) -> np.ndarray:
    """(rhs_pool, n) float32 right-hand sides, uniform in [-1, 1]."""
    return rng(seed, _RHS).uniform(
        -1.0, 1.0, (int(mix["rhs_pool"]), n)).astype(np.float32)
