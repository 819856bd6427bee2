"""What every traffic kind shares: the image pool, the request log, and
the drain after the window."""

from __future__ import annotations

import time

import numpy as np

# How long after the window closes requests due inside it may still
# finish; a request not done by then counts as never answered.
DRAIN_S = 60.0


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of the seed."""
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def image_pool(mix: dict, in_chans: int, seed: int) -> np.ndarray:
    """``pool_images`` distinct float32 HWC images drawn from the seed."""
    s = mix["image_size"]
    return rng(seed, 1).standard_normal(
        (mix["pool_images"], s, s, in_chans), dtype=np.float32)


class Log:
    """Per-request times in seconds from the window's start: ``due``,
    ``sub`` (submitted), ``start`` (the serving tick began), ``done``
    (answer on the host), and ``ok``."""

    def __init__(self):
        self.req: dict[int, dict] = {}
        self.ticks: list[tuple[float, float, int]] = []  # start, end, served

    def submitted(self, uid: int, due: float, now: float) -> None:
        self.req[uid] = {"due": due, "sub": now, "start": None, "done": None,
                         "ok": False}

    def tick(self, system, spans, t0: float) -> int:
        ts = time.perf_counter() - t0
        with spans.span("step"):
            done = system.step()
        te = time.perf_counter() - t0
        for uid, ok in done:
            self.req[uid].update(start=ts, done=te, ok=ok)
        self.ticks.append((ts, te, len(done)))
        return len(done)


def drain(system, log: Log, spans, t0: float, pending) -> float:
    """Serve what is queued (after submitting ``pending``, a list of
    ``(uid, image, due)`` already due) until every logged request is
    done or ``DRAIN_S`` has passed; returns the drain's seconds."""
    start = time.perf_counter()
    with spans.span("submit"):
        for uid, image, due in pending:
            system.submit(uid, image)
            log.submitted(uid, due, time.perf_counter() - t0)
    while (system.queued() and time.perf_counter() - start < DRAIN_S):
        log.tick(system, spans, t0)
    return time.perf_counter() - start
