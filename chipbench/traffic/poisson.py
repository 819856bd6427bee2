"""Open-loop arrivals at a fixed rate: independent users of an online
API. Requests are due on a schedule and are sent when due, whether or
not earlier ones have finished.

Every seed gets the same work: ``rate_per_s * seconds`` requests due at
the same times, whose gaps are the exponential quantiles (gap i is the
(i + 1/2)/n quantile of Exp(rate_per_s)) in one fixed shuffled order,
scaled to fill the window. The seed draws which pool image each request
carries (and the weights). With a few hundred requests near the knee,
the order of the gaps decides the tail: drawn per seed, on a TPU v5e at
448 px it moved p95 by 25-38 % between seeds, where two runs of one seed
agreed within 15 %.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import drive as common

SERVING = "online"  # requests are due over the window; tails count


# The one order of the gaps, the same for every seed.
GAP_ORDER_SEED = 0


def schedule(mix: dict, seed: int, seconds: float) -> dict:
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    common.rng(GAP_ORDER_SEED, 4).shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())
    return {"due": due,
            "image": common.rng(seed, 2).integers(0, mix["pool_images"], n)}


def warm_lanes(mix: dict, slots: int) -> list[int]:
    """Arrivals leave 1 to ``slots`` requests queued at a tick, and each
    count is its own set of shapes (bucket program, row gathers and
    scatters)."""
    return list(range(1, slots + 1))


def drive(system, mix: dict, pool: np.ndarray, sched: dict, seconds: float,
          spans) -> tuple[common.Log, dict]:
    due, img = sched["due"], sched["image"]
    n = len(due)
    log = common.Log()
    i = 0
    t0 = time.perf_counter()
    with spans.span("window"):
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            if i < n and due[i] <= now:
                with spans.span("submit"):
                    while i < n and due[i] <= now:
                        system.submit(i, pool[img[i]])
                        log.submitted(i, float(due[i]),
                                      time.perf_counter() - t0)
                        i += 1
            if system.queued():
                log.tick(system, spans, t0)
                continue
            nxt = min(float(due[i]) if i < n else seconds, seconds)
            with spans.span("wait_arrival"):
                wait = nxt - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
    window_end = time.perf_counter() - t0
    counters_end = system.counters()
    pending = [(j, pool[img[j]], float(due[j])) for j in range(i, n)]
    drain_s = common.drain(system, log, spans, t0, pending)
    return log, {"t0": t0, "window_s": window_end, "drain_s": drain_s,
                 "scheduled": n, "counters_end": counters_end}
