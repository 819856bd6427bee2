"""From a profiler trace to device busy time, idle gaps and top ops.

``events`` reads an ``.xplane.pb`` into plain lists; ``reduce`` works on
those lists only, so the arithmetic is checked on a small recorded trace
without a chip. Device planes are ``/device:TPU:<n>``; on each, the
line of per-operation events (``XLA Ops``) gives the intervals in which
an operation ran. The host's ``TraceAnnotation`` spans (``window``,
``step``, ``submit``, ``wait_arrival``) come from the host plane and say
what the host was doing in each gap.
"""

from __future__ import annotations

import bisect
import glob
import os

OP_LINES = ("XLA Ops",)
HOST_LABELS = ("step", "submit", "wait_arrival")


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def events(path: str) -> dict:
    """``{"device": {plane: [(op, start_ns, end_ns)]}, "host": [(name,
    start_ns, end_ns)], "lines": {plane: [line names]}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"device": {}, "host": [], "lines": {}}
    wanted = set(HOST_LABELS) | {"window"}
    for plane in data.planes:
        lines = list(plane.lines)
        out["lines"][plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for ln in lines:
                if ln.name in OP_LINES:
                    ops.extend((_op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in ln.events)
            out["device"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in lines:
                out["host"].extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in ln.events if e.name in wanted)
    return out


def _op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


class _Labels:
    """What the host was doing at a time: the labelled span (they do
    not nest) that holds it, else ``other``."""

    def __init__(self, host):
        spans = sorted((s, e, nm) for nm, s, e in host if nm != "window")
        self.starts = [s for s, _, _ in spans]
        self.spans = spans

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.spans[i][1] >= t:
            return self.spans[i][2]
        return "other"


def reduce(ev: dict, top: int = 10) -> dict | None:
    """Busy and idle time of the device inside the host's ``window``
    span, averaged over the device planes, with the ops that took most
    device time and the idle time by what the host was doing. None when
    the trace holds no window or no device operation."""
    windows = [(s, e) for nm, s, e in ev["host"] if nm == "window"]
    planes = {p: ops for p, ops in ev["device"].items() if ops}
    if not windows or not planes:
        return None
    w0, w1 = windows[0]
    labels = _Labels(ev["host"])
    busy, per_op, idle = [], {}, {}
    for p, ops in sorted(planes.items()):
        clipped = [(max(a, w0), min(b, w1)) for _, a, b in ops
                   if b > w0 and a < w1]
        merged = _union(clipped)
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in ops:
            lo, hi = max(a, w0), min(b, w1)
            if hi > lo:
                per_op[name] = per_op.get(name, 0.0) + (hi - lo) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                lab = labels.at((a + b) / 2)
                idle[lab] = idle.get(lab, 0.0) + (b - a) * 1e-9 / len(planes)
    busy_s = sum(busy) / len(busy) * 1e-9
    if busy_s <= 0:
        return None
    by = lambda kv: -kv[1]  # noqa: E731
    return {
        "busy_s": busy_s,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": [[k, v] for k, v in sorted(per_op.items(), key=by)[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(idle.items(), key=by)[:top]],
    }
