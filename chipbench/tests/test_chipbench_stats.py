"""Latency order statistics over every request of the window."""

import math

from chipbench.tests import tiny  # noqa: F401
from chipbench import drive, stats


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([3.0], 95) == 3.0


def test_failures_count_as_infinite():
    log = drive.Log()
    for uid in range(20):
        log.submitted(uid, due=0.1 * uid, now=0.1 * uid)
        log.req[uid].update(start=0.1 * uid, done=0.1 * uid + 0.010, ok=True)
    log.req[3]["ok"] = False  # failed
    log.req[7]["done"] = None  # never answered
    log.submitted(99, due=5.0, now=5.0)  # due after the window: not counted
    run = {"log": log, "seconds": 2.0}
    lat = stats.latencies_ms(run)
    assert len(lat) == 20 and sum(math.isinf(x) for x in lat) == 2
    assert math.isclose(stats.percentile(lat, 50), 10.0, rel_tol=1e-6)
    assert math.isinf(stats.percentile(lat, 95))
    assert stats.completed_in_window(run) == 18


def test_answer_sample_holds_every_batch_width():
    from chipbench.entries import vig as entry

    def width(n):
        return 1 << (n - 1).bit_length()  # buckets 1, 2, 4, 8

    ticks = [tuple(range(10 * i, 10 * i + n))
             for i, n in enumerate([1] * 30 + [2] * 20 + [3] * 5 + [8] * 2)]
    for seed in (1, 2 ** 40 + 7):
        picked = entry.sample_ticks(ticks, seed, 16, width)
        assert {width(len(t)) for t in picked} == {1, 2, 4, 8}
        assert sum(map(len, picked)) >= 16
        assert len(set(picked)) == len(picked)
        assert picked == entry.sample_ticks(ticks, seed, 16, width)
