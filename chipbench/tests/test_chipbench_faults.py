"""A run with the timed path broken underneath comes out not correct:
the harness end to end on a tiny ViG on the CPU, the device check
steered here, with the engine's tick answering wrongly in each of the
ways a served cell can."""

import json

import numpy as np
import pytest

from chipbench.tests import tiny
from chipbench import run


def _break(monkeypatch, fault):
    from repro.serve.engine import VigServeEngine

    step = VigServeEngine.step
    last = {}

    def broken(self):
        queued = list(self.queue)
        n = step(self)
        served = [r for r in queued if r.done and r.logits is not None]
        if fault == "half_batch":
            # the second half of the tick's lanes left out: they carry
            # the first lane's answer
            for r in served[(len(served) + 1) // 2:]:
                r.logits = served[0].logits.copy()
        elif fault == "answer_altered":
            for r in served[:1]:
                r.logits = r.logits.copy()
                r.logits[int(np.argmax(r.logits))] += 1.0
        elif fault == "state_unchanged":
            # the tick hands back what the previous tick produced
            prev = last.get("logits")
            last["logits"] = [r.logits for r in served]
            if prev:
                for r, p in zip(served, prev * len(served)):
                    r.logits = p
        return n

    monkeypatch.setattr(VigServeEngine, "step", broken)


def _run(monkeypatch, conf, mix, trace=False):
    bench = tiny.install(monkeypatch, conf, mix)
    monkeypatch.setattr(run, "jax_setup", lambda: None)
    return run.run_cell("tiny.cell", 2 ** 40 + 9, 1.0, trace, bench=bench)


@pytest.mark.parametrize("conf,mix", [(tiny.ISO, tiny.online_mix()),
                                      (tiny.PYR, tiny.backlog_mix())],
                         ids=["online", "backlog"])
def test_sound_run_is_correct(monkeypatch, conf, mix):
    out = _run(monkeypatch, conf, mix, trace=mix["kind"] == "backlog")
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    json.dumps(out)
    names = set(out["metrics"])
    if mix["kind"] == "poisson":
        assert {"p95_latency_ms", "setup_s"} <= names
    else:
        # traced: per-layer metrics; no device plane on the CPU, so the
        # device time is left out rather than read as 0
        assert {"tick_ms.offline", "mfu.offline"} <= names
        assert "device_ms_per_tick.offline" not in names


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered",
                                   "state_unchanged"])
@pytest.mark.parametrize("conf,mix", [(tiny.ISO, tiny.online_mix()),
                                      (tiny.PYR, tiny.backlog_mix())],
                         ids=["online", "backlog"])
def test_broken_tick_is_not_correct(monkeypatch, conf, mix, fault):
    _break(monkeypatch, fault)
    out = _run(monkeypatch, conf, mix)
    assert not out["correct"]
