"""A configuration whose k ramps over the blocks, served through
``VigServeEngine`` against the plain reference on the CPU at a small
size: each block's lists have that block's own k, and the harness runs
it end to end through ``entries/vig_ramp.py``, refuses a file whose
schedule is not the program's, and comes out not correct when the
program serves one k in every block."""

import dataclasses

import numpy as np
import pytest

from chipbench.tests import tiny
from chipbench import run
from chipbench.entries import vig_ramp as entry
from chipbench.references import vig as ref

# depth 6: blocks 4 and 5 are dilated (d = 2), k ramps 3 to 6
RAMP = dict(tiny.ISO, name="vig_tiny_ramp", entry="vig_ramp", depths=[6],
            num_knn=[3, 3, 4, 5, 5, 6])


def _install(monkeypatch, conf, mix, program_knn=None):
    """``tiny.install``, with the program's variant serving
    ``program_knn`` (default: the file's ``num_knn``)."""
    from repro.models import vig

    bench = tiny.install(monkeypatch, conf, mix)
    knn = conf["num_knn"] if program_knn is None else program_knn
    monkeypatch.setitem(vig.VIG_VARIANTS, conf["variant"],
                        tiny.program_variant(conf).replace(
                            num_knn=tuple(knn)))
    monkeypatch.setattr(run, "jax_setup", lambda: None)
    return bench


def _serve_and_compare(system, conf, size):
    """Serve three images in one tick and check each block's captured
    lists (width, exactness) and the logits against the reference."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    images = rng.standard_normal((3, size, size, 3)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        for uid, image in enumerate(images):
            system.submit(uid, image)
        while system.queued():
            system.step()
        per_lane, width = system.capture((0, 1, 2))
    blocks = ref.plan(conf, size)
    taught = ref.make_forward(conf, size, precision="highest", taught=True)
    for uid, seen in enumerate(per_lane):
        assert [idx.shape for _, _, idx in seen] == [
            (b.grid ** 2, b.k) for b in blocks]
        for blk, (h, y, idx) in zip(blocks, seen):
            assert float(ref.row_gaps(h, y, idx, blk.dilation).max()) < 1e-6
        keep = [np.ones(i.shape[0], bool) for _, _, i in seen]
        want = np.asarray(taught(system.weights, jnp.asarray(images[uid]),
                                 [jnp.asarray(i) for _, _, i in seen], keep,
                                 rows=width))
        np.testing.assert_allclose(system.logits[uid], want, rtol=0,
                                   atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("size", [32, 64], ids=["native", "2x"])
def test_ramped_engine_matches_the_reference(monkeypatch, size):
    _install(monkeypatch, RAMP, tiny.backlog_mix(size))
    ks = [b.k for b in ref.plan(RAMP, size)]
    assert ks == ([3, 3, 4, 5, 5, 6] if size == 32 else [6, 6, 8, 10, 10, 12])
    _serve_and_compare(entry.System(RAMP, size, 2 ** 35 + 1), RAMP, size)


@pytest.mark.parametrize("ramp", [True, False], ids=["ramp", "one_k"])
@pytest.mark.parametrize("size", [32, 64], ids=["native", "2x"])
def test_autotuned_engine_matches_the_reference(monkeypatch, tmp_path,
                                                size, ramp):
    """The engine's default, ``autotune=True``: the tuner measures each
    stage at its widest block (largest k*d), and the tuned schedule
    leaves every block's k to the plan, at the native and a doubled
    grid alike, with a ramp and with one k in every block (no
    ``num_knn``, where a tuned k measured at the doubled grid must not
    be doubled again)."""
    from chipbench.entries import vig as flat_entry
    from repro.serve.engine import VigServeEngine

    if ramp:
        conf, entry_mod = RAMP, entry
        _install(monkeypatch, conf, tiny.backlog_mix(size))
    else:
        conf, entry_mod = dict(RAMP, num_knn=[3] * 6), flat_entry
        tiny.install(monkeypatch, conf, tiny.backlog_mix(size))
    system = entry_mod.System(conf, size, 2 ** 35 + 3)
    system.engine = VigServeEngine(system.cfg, system.weights,
                                   image_sizes=(size,),
                                   tuner_path=tmp_path / "tune.json")
    (row,) = system.engine._stage_rows(size)
    plan = ref.plan(conf, size)
    assert row["k"] * row["dilation"] == max(b.k * b.dilation for b in plan)
    _serve_and_compare(system, conf, size)
    tuned = system.engine._bucket_tuned
    assert tuned and all(r.source == "measured" for rs in tuned.values()
                         for r in rs)


def _run_cell(bench):
    return run.run_cell("tiny.cell", 2 ** 40 + 11, 1.0, False, bench=bench)


def test_ramped_cell_is_correct(monkeypatch):
    out = _run_cell(_install(monkeypatch, RAMP, tiny.backlog_mix()))
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def test_one_k_in_every_block_is_not_correct(monkeypatch):
    """The program plans the file's ramp but every block builds its
    graph at the stage's first k, as a program without per-block k
    does: the lists' widths give it away."""
    from repro.models import vig

    bench = _install(monkeypatch, RAMP, tiny.backlog_mix())
    run_stage = vig.run_stage

    def uniform(stage_params, x, cfg, plan, **kw):
        flat = dataclasses.replace(plan, ks=(plan.ks[0],) * plan.depth)
        return run_stage(stage_params, x, cfg, flat, **kw)

    monkeypatch.setattr(vig, "run_stage", uniform)
    out = _run_cell(bench)
    assert not out["correct"]
    assert out["checks"]["list_gap"]["value"] == float("inf")


@pytest.mark.parametrize("program_knn", [[3] * 6, [3, 3, 4, 5, 6, 6]],
                         ids=["uniform", "other_ramp"])
def test_a_schedule_not_the_programs_is_refused(monkeypatch, program_knn):
    _install(monkeypatch, RAMP, tiny.backlog_mix(), program_knn)
    with pytest.raises(ValueError, match="per-block k"):
        entry.program_config(RAMP)


def test_a_program_without_a_schedule_is_refused_at_once(monkeypatch):
    from repro.models import vig

    _install(monkeypatch, RAMP, tiny.backlog_mix())
    monkeypatch.setitem(vig.VIG_VARIANTS, RAMP["variant"],
                        tiny.program_variant(RAMP))  # num_knn None
    with pytest.raises(ValueError, match="no per-block k schedule"):
        entry.System(RAMP, 32, 1)
