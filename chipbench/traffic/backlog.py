"""A backlog: every request is due at the window's start, and the queue
is kept at least ``depth_slots`` slot-widths deep for the whole window,
as in offline labelling or feature extraction over a data set. The
system sets the pace; what counts is how many answers it completes in
the window. Each request's image is drawn from the pool.
"""

from __future__ import annotations

import time

from chipbench import drive as common

SERVING = "offline"  # the system sets the pace; answers count


# Pool draws for this many requests, reused in turn past that.
DRAWS = 1 << 20


def schedule(mix: dict, seed: int, seconds: float) -> dict:
    return {"image": common.rng(seed, 2).integers(0, mix["pool_images"],
                                                  DRAWS)}


def warm_lanes(mix: dict, slots: int) -> list[int]:
    """A deep queue fills every slot at every tick."""
    return [slots]


def drive(system, mix: dict, pool, sched: dict, seconds: float,
          spans) -> tuple[common.Log, dict]:
    order = sched["image"]
    depth = int(mix["depth_slots"]) * system.slots
    log = common.Log()
    uid = 0
    t0 = time.perf_counter()
    with spans.span("window"):
        while time.perf_counter() - t0 < seconds:
            with spans.span("submit"):
                while system.queued() < depth:
                    system.submit(uid, pool[order[uid % len(order)]])
                    log.submitted(uid, 0.0, time.perf_counter() - t0)
                    uid += 1
            log.tick(system, spans, t0)
    window_end = time.perf_counter() - t0
    return log, {"t0": t0, "window_s": window_end, "drain_s": 0.0,
                 "scheduled": uid, "counters_end": system.counters()}
