"""Trace reduction by the program's names: idle gaps by the innermost
host span, device time by named scope and per stage, and the HLO the
trace carries."""

import json
from pathlib import Path

import pytest

from chipbench.tests import tiny  # noqa: F401
from chipbench import trace, trace_scopes

MS = 1_000_000  # ns
DIGC0 = "jit(_lambda)/stage0/block0/digc/while"
DIGC1 = "jit(_lambda)/stage1/block1/digc/fusion"


def nested_trace():
    """A 100 ms window: one tick whose engine spans nest (a guard inside
    admission), then a submit; device ops under named scopes, an eager op
    with no scope, and one op clipped by the window's end."""
    host = [("window", 0, 100 * MS), ("step", 0, 80 * MS),
            ("engine.step", 2 * MS, 78 * MS),
            ("engine.admit", 2 * MS, 40 * MS),
            ("engine.guard", 10 * MS, 20 * MS),
            ("engine.sync", 50 * MS, 70 * MS),
            ("submit", 80 * MS, 90 * MS)]
    dev = {"/device:TPU:0": [
        ("%fusion", 30 * MS, 32 * MS, ""),  # eager reset in admission
        ("%fusion.9", 50 * MS, 52 * MS, "jit(_lambda)/stem/add"),
        ("%while.1", 52 * MS, 60 * MS, DIGC0),
        ("%fusion.3", 55 * MS, 58 * MS, DIGC0 + "/body/fusion"),
        ("%fusion.4", 60 * MS, 62 * MS, "jit(_lambda)/stage0/block0/ffn/dot"),
        ("%fusion.5", 62 * MS, 64 * MS, DIGC1),
        ("%fusion.6", 64 * MS, 66 * MS, "jit(_lambda)/downsample0/conv"),
        ("%fusion.7", 95 * MS, 120 * MS, "jit(_lambda)/head/dot")]}
    return {"host": host, "device": dev, "lines": {}}


def test_gaps_split_among_the_innermost_spans():
    r = trace_scopes.reduce(nested_trace())
    gaps = dict(r["idle_gaps"])
    # [0,2] step; [2,10] admit; [10,20] guard; [20,30] admit; [32,40]
    # admit; [40,50] engine.step; [66,70] sync; [70,78] engine.step;
    # [78,80] step; [80,90] submit; [90,95] other
    assert gaps == pytest.approx({
        "step": 0.004, "engine.admit": 0.026, "engine.guard": 0.010,
        "engine.step": 0.018, "engine.sync": 0.004, "submit": 0.010,
        "other": 0.005})
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_busy_and_top_ops_are_trace_reduce_s():
    ev = nested_trace()
    flat = {"host": [h for h in ev["host"] if not h[0].startswith("engine.")],
            "device": {p: [o[:3] for o in ops]
                       for p, ops in ev["device"].items()}}
    want = trace.reduce(flat)
    got = trace_scopes.reduce(ev)
    assert got["busy_s"] == want["busy_s"]
    assert got["window_s"] == want["window_s"]
    assert got["device_ops"] == want["device_ops"]


def test_device_time_by_scope_and_stage():
    r = trace_scopes.reduce(nested_trace())
    # the while and its body op overlap: their union counts once
    assert r["scopes"] == pytest.approx({
        "digc": 0.010, "ffn": 0.002, "stem": 0.002, "downsample": 0.002,
        "head": 0.005, "unscoped": 0.002})
    assert r["digc_by_stage"] == pytest.approx({0: 0.008, 1: 0.002})
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"])


@pytest.mark.parametrize("path,want", [
    ("jit(_lambda)/stage2/block0/digc/while/body/fusion", ("digc", 2)),
    ("jit(_lambda)/stage0/block1/graph_conv/dot_general", ("graph_conv", 0)),
    ("jit(_lambda)/stage3/block0/ffn/tanh", ("ffn", 3)),
    ("jit(_lambda)/downsample1/conv", ("downsample", None)),
    ("jit(_lambda)/stem/add", ("stem", None)),
    ("jit(_lambda)/head/dot_general", ("head", None)),
    ("jit(_unstack)/squeeze", ("unscoped", None)),
    ("", ("unscoped", None)),
])
def test_scope_class(path, want):
    assert trace_scopes.scope_class(path) == want


def test_events_read_engine_spans_and_the_hlo_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("stage0"), jax.named_scope("digc"):
            y = jnp.sin(x @ x)
        return y.sum()

    g = jax.jit(f)
    x = jnp.ones((64, 64))
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("engine.step#tick=3,lanes=8#"):
            g(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find(str(tmp_path))
    ev = trace_scopes.events(path)
    assert {n for n, _, _ in ev["host"]} == {"window", "engine.step"}
    assert ev["device"] == {} and trace_scopes.reduce(ev) is None
    names = trace_scopes.module_op_names(path)
    ops = [op for module, table in names.items() if module.startswith("jit_f")
           for op in table.values()]
    assert any(op.startswith("jit(f)/stage0/digc/") for op in ops)


def test_recorded_v5e_slice():
    """One tick of a traced ViG-Ti 224 px backlog window recorded on one
    TPU v5e (`TPU v5 lite`) with the engine's tracer annotating and
    every device op's scope path read from the trace's HLO: the
    reduction gives what it gave there."""
    rec = json.loads((Path(__file__).parent / "data" /
                      "trace_v5e_scopes.json").read_text())
    scopes = rec["scopes"]
    ev = {"host": [tuple(h) for h in rec["host"]],
          "device": {p: [(o, a, b, scopes[i]) for o, a, b, i in ops]
                     for p, ops in rec["device"].items()},
          "lines": {}}
    r = trace_scopes.reduce(ev)
    want = rec["reduced"]
    for key in ("busy_s", "window_s"):
        assert r[key] == pytest.approx(want[key])
    assert [k for k, _ in r["device_ops"]] == [k for k, _ in want["device_ops"]]
    assert [v for _, v in r["device_ops"]] == \
        pytest.approx([v for _, v in want["device_ops"]])
    assert dict(r["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]))
    assert r["scopes"] == pytest.approx(want["scopes"])
    assert {str(k): v for k, v in r["digc_by_stage"].items()} == \
        pytest.approx(want["digc_by_stage"])
    # the tick's host time falls under the engine's spans, and its
    # device time under the program's scopes
    gaps = dict(r["idle_gaps"])
    assert gaps.get("step", 0.0) < 0.1 * sum(gaps.values())
    assert r["scopes"]["digc"] > 0 and r["digc_by_stage"]
    assert r["scopes"]["digc"] <= r["busy_s"]
