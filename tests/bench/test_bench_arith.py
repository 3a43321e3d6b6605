"""End-to-end arithmetic, the work counts and the peaks table."""
import numpy as np
import pytest

from bench import peaks, stats, work


def test_latency_from_schedule_sees_a_planted_stall():
    due = np.arange(1000) * 0.01                   # 100/s for 10 s
    done = due + 0.005                             # 5 ms each
    base = stats.latencies(due, done, np.zeros(1000, bool), 70.0)
    # a 1 s stall at t = 4 s: everything due in it is answered at its end
    stalled = done.copy()
    hit = (due >= 4.0) & (due < 5.0)
    stalled[hit] = 5.0 + 0.005
    lat = stats.latencies(due, stalled, np.zeros(1000, bool), 70.0)
    assert stats.percentile(base, 95) == pytest.approx(0.005)
    assert stats.percentile(lat, 95) > 0.2          # the tail moves
    assert stats.percentile(lat, 50) == pytest.approx(0.005)
    # timed from admission instead (each request admitted when served),
    # the same stall would read 5 ms everywhere: the schedule is the base
    assert stats.percentile(stalled - np.maximum(due, stalled - 0.005),
                            95) == pytest.approx(0.005)


def test_failed_requests_miss_every_limit():
    due = np.zeros(100)
    done = np.full(100, 0.01)
    failed = np.zeros(100, bool)
    failed[:10] = True
    lat = stats.latencies(due, done, failed, 70.0)
    assert stats.percentile(lat, 95) == pytest.approx(70.0)
    done[50] = np.nan                              # never came
    assert stats.latencies(due, done, np.zeros(100, bool), 70.0)[50] == 70.0


def test_rate_over_the_whole_window():
    done = np.linspace(0.0, 10.0, 10001)[1:]       # 1000/s
    ok = np.ones_like(done, bool)
    assert stats.rate(done, ok, 0.0, 10.0) == pytest.approx(1000.0)
    late = done.copy()
    late[(done > 5) & (done < 6)] = 10.5           # a 1 s stall
    assert stats.rate(late, ok, 0.0, 10.0) == pytest.approx(900.0, rel=1e-3)
    ok[:100] = False                               # wrong answers don't count
    assert stats.rate(done, ok, 0.0, 10.0) == pytest.approx(990.0)


def test_work_on_the_paper_plan():
    tiles = work.cascade_tiles(256, 2, 64)
    assert len(tiles) == 23 and set(tiles) == {(64, 64)}
    assert work.tile_elements(256, 2, 64) == 94_208
    assert work.tile_elements(512, 2, 128) == 376_832
    flops, nbytes = work.executor_work(256, 2, 64, instances=8, rhs=8)
    assert flops == 2 * 94_208 * 8
    assert nbytes == 4 * 94_208 * 8 + 8 * 256 * 8
    t, bound = work.roofline_seconds(flops, nbytes, peaks.peaks_for(
        "TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)


def test_work_on_a_ragged_plan():
    # n = 200 on 64^2 arrays: A1 = 100, leaves 50; tiles cut at 64
    assert work.tile_elements(200, 2, 64) == sum(
        r * c for r, c in work.cascade_tiles(200, 2, 64))
    assert (64, 36) in work.cascade_tiles(200, 2, 64)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
