"""Order statistics over requests."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100): the smallest
    value with at least q% of the values at or below it. A failed or
    unanswered request enters as ``inf``."""
    xs = sorted(values)
    if not xs:
        return math.inf
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def latencies_ms(run: dict) -> list[float]:
    """Due-to-answer time of every request due inside the window, in
    ms; ``inf`` for one that failed or was not answered by the end of
    the drain."""
    out = []
    for r in run["log"].req.values():
        if r["due"] >= run["seconds"]:
            continue
        ok = r["ok"] and r["done"] is not None
        out.append((r["done"] - r["due"]) * 1e3 if ok else math.inf)
    return out


def completed_in_window(run: dict) -> int:
    return sum(1 for r in run["log"].req.values()
               if r["ok"] and r["done"] is not None
               and r["done"] <= run["seconds"])


def window_ticks(run: dict) -> list[tuple[float, float, int]]:
    """Ticks that started inside the window and served a request."""
    return [t for t in run["log"].ticks if t[0] < run["seconds"] and t[2]]
