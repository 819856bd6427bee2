"""Trace reduction: busy union, idle gaps by host span, top ops."""

import pytest

from chipbench.tests import tiny  # noqa: F401
from chipbench import trace

MS = 1_000_000  # ns


def small_trace():
    """A 100 ms window: two overlapping ops inside a step, a gap while
    the host waits for an arrival, a gap in a submit, one op clipped by
    the window's end."""
    host = [("window", 0, 100 * MS), ("step", 0, 40 * MS),
            ("wait_arrival", 40 * MS, 70 * MS), ("submit", 70 * MS, 75 * MS),
            ("step", 75 * MS, 100 * MS)]
    dev = {"/device:TPU:0": [("fusion.1", 5 * MS, 25 * MS),
                             ("convolution.2", 20 * MS, 35 * MS),
                             ("fusion.1", 80 * MS, 110 * MS)]}
    return {"host": host, "device": dev, "lines": {}}


def test_busy_union_and_gaps():
    r = trace.reduce(small_trace())
    assert r["window_s"] == pytest.approx(0.100)
    # union: [5, 35] and [80, 100] -> 50 ms busy
    assert r["busy_s"] == pytest.approx(0.050)
    gaps = dict(r["idle_gaps"])
    # idle: [0,5] step, [35,80] mid 57.5 -> wait_arrival, nothing else
    assert gaps == pytest.approx({"step": 0.005, "wait_arrival": 0.045})
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({"fusion.1": 0.040, "convolution.2": 0.015})
    assert [k for k, _ in r["device_ops"]] == ["fusion.1", "convolution.2"]


def test_nothing_to_read_gives_nothing():
    ev = small_trace()
    assert trace.reduce(dict(ev, device={})) is None
    assert trace.reduce(dict(ev, host=ev["host"][1:])) is None


def test_events_from_a_recorded_host_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.events(trace.find(str(tmp_path)))
    names = [n for n, _, _ in ev["host"]]
    assert "window" in names and "step" in names
    # a CPU run has no device plane: no device metric can be read
    assert ev["device"] == {} and trace.reduce(ev) is None


def test_recorded_v5e_trace():
    """A slice of a traced ViG-Ti 224 px backlog window recorded on one
    TPU v5e (`TPU v5 lite`): the reduction gives what it gave there."""
    import json
    from pathlib import Path

    rec = json.loads((Path(__file__).parent / "data" /
                      "trace_v5e.json").read_text())
    ev = {"host": [tuple(h) for h in rec["host"]],
          "device": {p: [tuple(o) for o in ops]
                     for p, ops in rec["device"].items()},
          "lines": {}}
    r = trace.reduce(ev)
    want = rec["reduced"]
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert [k for k, _ in r["device_ops"]] == [k for k, _ in want["device_ops"]]
    assert all(k.startswith("%") for k, _ in r["device_ops"])
    gaps = dict(r["idle_gaps"])
    assert set(gaps) <= {"step", "submit", "wait_arrival", "other"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
