"""From a profiler trace to device time by the program's named scopes and
idle gaps by the innermost host span.

``trace.reduce`` reads the harness's spans, which do not nest, and names
device ops by XLA's numbering. The program adds its own names: the
engine's tracer annotates each phase of a tick (``engine.step`` and the
``engine.*`` spans inside it, nested), and the ViG forward puts every
device op under a scope path such as ``stage0/block3/digc``. ``events``
reads both; ``reduce`` returns what ``trace.reduce`` returns, computed by
it, with the idle gaps labelled by the innermost span around each, and
the device seconds per scope class and of ``digc`` per stage.
"""

from __future__ import annotations

import bisect
import re

from chipbench import trace

# The scope classes of the ViG forward (``models/vig.py``); a device op
# under none of them is ``unscoped``.
SCOPE_CLASSES = ("digc", "graph_conv", "ffn", "stem", "downsample", "head")
ENGINE_PREFIX = "engine."
MODULE_LINE = "XLA Modules"
_STAGE = re.compile(r"stage(\d+)")
_DOWNSAMPLE = re.compile(r"downsample\d+")


def events(path: str) -> dict:
    """``{"device": {plane: [(op, start_ns, end_ns, scope)]}, "host":
    [(name, start_ns, end_ns)], "lines": {plane: [line names]}}``: the
    harness's spans and the engine's ``engine.*`` spans (their names
    without the ``#`` metadata), and each device op's scope path: the
    ``op_name`` of its instruction in the HLO of the module it ran in
    (empty where the trace holds no such module or instruction)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    hlo = module_op_names(path)
    out = {"device": {}, "host": [], "lines": {}}
    harness = set(trace.HOST_LABELS) | {"window"}
    for plane in data.planes:
        lines = list(plane.lines)
        out["lines"][plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:TPU:"):
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for ln in lines if ln.name == MODULE_LINE
                             for e in ln.events)
            starts = [m[0] for m in modules]
            ops = []
            for ln in lines:
                if ln.name not in trace.OP_LINES:
                    continue
                for e in ln.events:
                    op = trace._op_name(e.name)
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    names = (hlo.get(modules[i][2], {})
                             if i >= 0 and e.start_ns < modules[i][1] else {})
                    ops.append((op, e.start_ns, e.start_ns + e.duration_ns,
                                names.get(op.lstrip("%"), "")))
            out["device"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    name = e.name.split("#", 1)[0]
                    if name in harness or name.startswith(ENGINE_PREFIX):
                        out["host"].append(
                            (name, e.start_ns, e.start_ns + e.duration_ns))
    return out


# -- the HLO the trace carries ------------------------------------------
#
# The profiler writes each module's ``HloProto`` into the trace, as the
# ``Hlo Proto`` stat of an event of the ``/host:metadata`` plane named as
# the module's events on the device's ``XLA Modules`` line. The op events
# carry only their instruction's text, without its ``op_name``, and
# ``ProfileData`` does not read event metadata, so these few fields of the
# ``.xplane.pb`` (``xplane.proto``, ``hlo.proto``) are read here.


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for a varint, a ``memoryview`` for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def module_op_names(path: str) -> dict[str, dict[str, str]]:
    """``{module name: {instruction name: op_name}}`` from the HLO of
    every module the trace at ``path`` holds."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):  # XSpace.planes
        if field != 1:
            continue
        name, metas, stat_names = None, [], {}
        for pf, value in _fields(plane):
            if pf == 2:  # XPlane.name
                name = _text(value)
                if name != "/host:metadata":
                    break
            elif pf == 4:  # XPlane.event_metadata entry
                metas.append(value)
            elif pf == 5:  # XPlane.stat_metadata entry: id -> name
                entry = dict(_fields(value))
                if 2 in entry:
                    stat = dict(_fields(entry[2]))
                    stat_names[stat.get(1, 0)] = _text(stat.get(2, b""))
        if name != "/host:metadata":
            continue
        for entry in metas:
            for ef, meta in _fields(entry):
                if ef != 2:
                    continue
                module, proto = None, None
                for mf, value in _fields(meta):
                    if mf == 2:  # XEventMetadata.name
                        module = _text(value)
                    elif mf == 5:  # XEventMetadata.stats
                        stat = dict(_fields(value))
                        if stat_names.get(stat.get(1)) == "Hlo Proto":
                            proto = stat.get(6)  # XStat.bytes_value
                if module is not None and proto is not None:
                    out[module] = _hlo_op_names(proto)
    return out


def _hlo_op_names(proto) -> dict[str, str]:
    out = {}
    for field, module in _fields(proto):  # HloProto.hlo_module
        if field != 1:
            continue
        for mf, comp in _fields(module):  # HloModuleProto.computations
            if mf != 3:
                continue
            for cf, ins in _fields(comp):  # HloComputationProto.instructions
                if cf != 2:
                    continue
                name = op_name = ""
                for inf, value in _fields(ins):
                    if inf == 1:  # HloInstructionProto.name
                        name = _text(value)
                    elif inf == 7:  # HloInstructionProto.metadata
                        for of, ov in _fields(value):
                            if of == 2:  # OpMetadata.op_name
                                op_name = _text(ov)
                out[name] = op_name
    return out


def scope_class(path: str) -> tuple[str, int | None]:
    """The scope class of a scope path and the stage it lies in:
    ``jit(f)/stage2/block0/digc/while`` -> ``("digc", 2)``."""
    stage = None
    for part in path.split("/"):
        m = _STAGE.fullmatch(part)
        if m:
            stage = int(m.group(1))
        elif part in SCOPE_CLASSES:
            return part, stage
        elif _DOWNSAMPLE.fullmatch(part):
            return "downsample", stage
    return "unscoped", stage


def innermost(spans) -> list[tuple[int, int, str]]:
    """Disjoint ``(start, end, name)`` segments covering ``spans`` (each
    ``(name, start, end)``; spans nest or are disjoint), each named by
    the innermost span that holds it."""
    segs: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []  # (end, name), innermost last
    t = None

    def close_to(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if t < end:
                segs.append((t, end, name))
                t = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        if stack:
            close_to(s)
        if stack and t < s:
            segs.append((t, s, stack[-1][1]))
        stack.append((e, name))
        t = s
    if stack:
        close_to(float("inf"))
    return segs


class _Innermost:
    """What the host was doing over a stretch of time: the innermost
    labelled span at each moment, else ``other``."""

    def __init__(self, host):
        self.segs = innermost([h for h in host if h[0] != "window"])
        self.starts = [s for s, _, _ in self.segs]

    def split(self, a: float, b: float):
        """``(label, ns)`` pieces of ``[a, b]``."""
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        covered = 0.0
        while i < len(self.segs) and self.segs[i][0] < b:
            s, e, name = self.segs[i]
            lo, hi = max(s, a), min(e, b)
            if hi > lo:
                covered += hi - lo
                yield name, hi - lo
            i += 1
        if b - a > covered:
            yield "other", b - a - covered


def reduce(ev: dict, top: int = 10) -> dict | None:
    """``trace.reduce`` of the trace (``busy_s``, ``window_s``,
    ``device_ops`` computed by it), with ``idle_gaps`` split among the
    innermost spans over each gap, and ``scopes``: device seconds per scope class
    (union of the class's op intervals inside the window, averaged over
    the device planes), ``digc_by_stage`` the same for ``digc`` per
    stage. None where ``trace.reduce`` gives None."""
    flat = {"host": [h for h in ev["host"]
                     if not h[0].startswith(ENGINE_PREFIX)],
            "device": {p: [op[:3] for op in ops]
                       for p, ops in ev["device"].items()},
            "lines": ev.get("lines", {})}
    out = trace.reduce(flat, top)
    if out is None:
        return None
    w0 = next(s for nm, s, _ in ev["host"] if nm == "window")
    w1 = w0 + out["window_s"] * 1e9
    planes = {p: ops for p, ops in ev["device"].items() if ops}
    labels = _Innermost(ev["host"])
    idle: dict[str, float] = {}
    by_class: dict[str, list] = {}
    by_stage: dict[int, list] = {}
    for p, ops in sorted(planes.items()):
        clipped = [(max(a, w0), min(b, w1), scope) for _, a, b, scope in ops
                   if b > w0 and a < w1]
        merged = trace._union([(a, b) for a, b, _ in clipped])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            for lab, ns in labels.split(a, b):
                idle[lab] = idle.get(lab, 0.0) + ns * 1e-9 / len(planes)
        cls_iv: dict[str, list] = {}
        stage_iv: dict[int, list] = {}
        for a, b, scope in clipped:
            cls, stage = scope_class(scope)
            cls_iv.setdefault(cls, []).append((a, b))
            if cls == "digc" and stage is not None:
                stage_iv.setdefault(stage, []).append((a, b))
        for into, src in ((by_class, cls_iv), (by_stage, stage_iv)):
            for key, iv in src.items():
                into.setdefault(key, []).append(
                    sum(b - a for a, b in trace._union(iv)))
    n = len(planes)
    by = lambda kv: -kv[1]  # noqa: E731
    out["idle_gaps"] = [[k, v] for k, v in sorted(idle.items(), key=by)[:top]]
    out["scopes"] = {k: sum(v) / n * 1e-9 for k, v in sorted(by_class.items())}
    out["digc_by_stage"] = {k: sum(v) / n * 1e-9
                            for k, v in sorted(by_stage.items())}
    return out
