"""Workload autotuner: priors, measurement, persistence, spec filling."""

import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DigcSpec, digc
from repro.core.perfmodel import (
    engine_cost_estimate,
    kernel_cost_estimate,
    kernel_tile_defaults,
)
from repro.core.tuner import (
    DigcTuner,
    TileConfig,
    TuneResult,
    VigSchedule,
    autotune_spec,
    bucket_set_key,
    host_key,
    optimal_bucket_set,
    workload_key,
)


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def test_workload_key_distinguishes_workloads():
    a = workload_key(2, 196, 196, 192, 18)
    b = workload_key(2, 196, 196, 192, 9)
    c = workload_key(2, 196, 196, 192, 18, causal=True)
    assert len({a, b, c}) == 3


def test_host_key_carries_backend_platform_and_jax():
    import platform as _platform

    import jax

    hk = host_key("cpu")
    assert "cpu" in hk
    assert _platform.machine() in hk
    assert jax.__version__ in hk
    assert host_key("tpu") != hk


def test_candidates_exact_only_by_default():
    t = DigcTuner(backend="cpu")
    cands = t.candidates(1024, 1024)
    engine = [c for c in cands if c.impl == "blocked"]
    assert engine and all(c.merge in ("select", "topk") for c in engine)
    approx = t.candidates(1024, 1024, allow_approx=True)
    assert any(c.merge == "packed" for c in approx
               if c.impl == "blocked")


def test_candidates_include_kernel_configs():
    """The fused kernel competes as a first-class exact candidate: both
    LSM/GMM realizations, with the workload VMEM-budgeted tile when the
    feature dims are known."""
    t = DigcTuner(backend="cpu")
    kern = [c for c in t.candidates(3136, 3136, d=96, kd=9)
            if c.impl == "pallas"]
    assert {c.kernel_merge for c in kern} == {"bitonic", "legacy"}
    assert kernel_tile_defaults(3136, 3136, 96, 9) in {
        (c.block_n, c.block_m) for c in kern
    }
    # without d/kd the fallback tiles still field kernel candidates
    assert any(c.impl == "pallas" for c in t.candidates(1024, 1024))


def test_kernel_prior_gates_interpret_off_tpu():
    """Off-TPU the kernel runs in interpret mode: its prior must rank
    below every plausible engine schedule so the measured top-N stays
    engine-only on CPU — while the compiled-TPU prior is competitive."""
    cpu = kernel_cost_estimate(3136, 3136, 96, 9, b=2, backend="cpu")
    assert cpu["interpret"] and cpu["bound"] == "interpret"
    eng = engine_cost_estimate(3136, 3136, 96, 9, b=2, block_m=512,
                               merge="select", backend="cpu")
    assert cpu["total_s"] > eng["total_s"]
    tpu = kernel_cost_estimate(3136, 3136, 96, 9, b=2, backend="tpu",
                               kernel_merge="bitonic")
    assert not tpu["interpret"]
    eng_tpu = engine_cost_estimate(3136, 3136, 96, 9, b=2, block_m=512,
                                   merge="select", backend="tpu")
    assert tpu["total_s"] < 100 * eng_tpu["total_s"]  # same ballpark


def test_kernel_config_ranks_last_on_cpu():
    t = DigcTuner(backend="cpu")
    ranked = t.rank(t.candidates(1024, 1024, d=64, kd=8),
                    b=1, n=1024, m=1024, d=64, kd=8)
    n_kernel = sum(1 for c in ranked if c.impl == "pallas")
    assert n_kernel > 0
    assert all(c.impl == "pallas" for c in ranked[-n_kernel:])


def test_prior_ranks_select_over_topk_at_scale():
    """The cost model must encode the measured finding: top_k-merge
    selection cost dominates at ViG scale."""
    sel = engine_cost_estimate(3136, 3136, 96, 9, b=2, block_m=512,
                               merge="select", backend="cpu")
    tk = engine_cost_estimate(3136, 3136, 96, 9, b=2, block_m=512,
                              merge="topk", backend="cpu")
    assert sel["merge_s"] < tk["merge_s"]


def test_prior_penalizes_oversized_tiles():
    small = engine_cost_estimate(12544, 12544, 96, 9, b=2, block_n=512,
                                 block_m=1024, merge="select", backend="cpu")
    huge = engine_cost_estimate(12544, 12544, 96, 9, b=2, block_n=None,
                                block_m=12544, merge="select", backend="cpu")
    assert huge["spill_s"] > 0.0
    assert small["live_tile_bytes"] < huge["live_tile_bytes"]


def test_tile_config_apply_fills_spec():
    spec = DigcSpec(impl="blocked", k=5)
    cfg = TileConfig(block_n=128, block_m=256, merge="select", fuse_norms=True)
    s = cfg.apply(spec)
    assert (s.block_n, s.block_m, s.merge, s.fuse_norms) == (
        128, 256, "select", True)
    assert s.k == 5 and s.impl == "blocked"


def test_tune_measures_persists_and_caches(tmp_path):
    rng = np.random.default_rng(0)
    x = _rand(rng, 2, 96, 8)
    path = tmp_path / "tune.json"
    spec = DigcSpec(impl="blocked", k=4)
    tuner = DigcTuner(path, measure_iters=1, max_measure=2)
    tuned, res = tuner.tune(x, spec=spec)
    assert res.source == "measured"
    assert res.exact_match  # exact merges only by default
    assert tuned.block_m is not None and tuned.merge in ("select", "topk")
    # tuned spec must produce reference-identical output
    i_r = digc(x, k=4, impl="reference")
    i_t = digc(x, spec=tuned)
    np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_t))
    # persisted under this host's key (schema 3) ...
    data = json.loads(path.read_text())
    assert data["schema"] == 3
    assert list(data["hosts"]) == [host_key()]
    assert len(data["hosts"][host_key()]["schedules"]) == 1
    # ... and served from cache by a fresh tuner (no re-measurement)
    tuner2 = DigcTuner(path)
    tuned2, res2 = tuner2.tune(x, spec=spec)
    assert res2.source == "cached"
    assert (tuned2.block_n, tuned2.block_m, tuned2.merge) == (
        tuned.block_n, tuned.block_m, tuned.merge)


def test_kernel_winner_persists_and_applies(tmp_path):
    """A persisted kernel-tier winner round-trips through the JSON cache
    and fills a spec as impl="pallas" with its LSM/GMM realization."""
    path = tmp_path / "tune.json"
    tuner = DigcTuner(path)
    key = workload_key(2, 3136, 3136, 96, 18)
    cfg = TileConfig(128, 256, "kernel", False, impl="pallas",
                     kernel_merge="bitonic")
    tuner.entries[key] = TuneResult(cfg, 123.0, True, "measured").as_dict()
    tuner.save()
    cached = DigcTuner(path).lookup(key)
    assert cached is not None and cached.source == "cached"
    assert cached.config == cfg
    s = cached.config.apply(DigcSpec(impl="blocked", k=9, dilation=2))
    assert s.impl == "pallas" and s.kernel_merge == "bitonic"
    assert (s.block_n, s.block_m) == (128, 256)
    assert s.merge is None and s.fuse_norms is None  # engine-only knobs
    assert s.k == 9 and s.dilation == 2


def test_tune_cache_not_shared_across_hosts(tmp_path):
    """An entry tuned under one host key must be invisible to another
    host (and to another jax version): schedules are measurements."""
    rng = np.random.default_rng(3)
    x = _rand(rng, 2, 64, 8)
    path = tmp_path / "tune.json"
    spec = DigcSpec(impl="blocked", k=4)
    tuner = DigcTuner(path, measure_iters=1, max_measure=1)
    tuner.tune(x, spec=spec)
    # Same file, different (faked) host: must re-measure, not reuse.
    other = DigcTuner(path, measure_iters=1, max_measure=1)
    other.host = "tpu|linux-v5e|jax-9.9.9"
    slot = other._hosts.setdefault(
        other.host, {"schedules": {}, "bucket_sets": {}})
    other.entries = slot["schedules"]
    other.bucket_sets = slot["bucket_sets"]
    _, res = other.tune(x, spec=spec)
    assert res.source == "measured"
    other.save()
    # Both hosts' entries coexist in the file.
    data = json.loads(path.read_text())
    assert len(data["hosts"]) == 2


def test_schema1_tune_cache_dropped(tmp_path):
    """Flat schema-1 entries carry no platform/jax identity: they are
    dropped on load (re-measured), never silently reused."""
    path = tmp_path / "tune.json"
    path.write_text(json.dumps({
        "schema": 1, "backend": "cpu",
        "entries": {"cpu:b2:n64:m64:d8:kd4": {
            "block_n": None, "block_m": 64, "merge": "select",
            "fuse_norms": False, "us_per_call": 1.0, "exact_match": True,
        }},
    }))
    tuner = DigcTuner(path)
    assert tuner.entries == {}
    assert tuner.lookup(workload_key(2, 64, 64, 8, 4)) is None


def test_tune_schedule_per_stage(tmp_path):
    """tune_schedule: one tuned spec per stage workload, pooled stages
    tune the true (N, M) pair, results persist per stage."""
    path = tmp_path / "tune.json"
    tuner = DigcTuner(path, measure_iters=1, max_measure=1)
    workloads = [
        {"stage": 0, "N": 64, "M": 16, "D": 8, "k": 3, "dilation": 1},
        {"stage": 1, "N": 16, "M": 16, "D": 8, "k": 3, "dilation": 1},
    ]
    sched, results = tuner.tune_schedule(
        workloads, spec=DigcSpec(impl="blocked", k=3), batch=2)
    assert len(sched.stages) == 2
    assert all(r.source == "measured" for r in results)
    assert all(s.merge in ("select", "topk") for s in sched.stages)
    # stage addressing: beyond-last reuses the last entry
    assert sched.spec_for(0) == sched.stages[0]
    assert sched.spec_for(5) == sched.stages[1]
    # both stage workloads cached under distinct keys
    data = json.loads(path.read_text())
    assert len(data["hosts"][host_key()]["schedules"]) == 2
    # a fresh tuner serves the whole schedule from cache
    sched2, results2 = DigcTuner(path).tune_schedule(
        workloads, spec=DigcSpec(impl="blocked", k=3), batch=2)
    assert all(r.source == "cached" for r in results2)
    assert sched2.describe() == sched.describe()


def test_vig_schedule_non_blocked_passthrough():
    tuner = DigcTuner(None)
    workloads = [{"stage": 0, "N": 16, "M": 16, "D": 4, "k": 2,
                  "dilation": 1}]
    sched, results = tuner.tune_schedule(
        workloads, spec=DigcSpec(impl="reference", k=2))
    assert isinstance(sched, VigSchedule)
    assert results[0].source == "prior"
    assert sched.spec_for(0).impl == "reference"


def test_tune_non_blocked_impl_passthrough():
    rng = np.random.default_rng(1)
    x = _rand(rng, 40, 6)
    spec = DigcSpec(impl="reference", k=3)
    tuned, res = autotune_spec(x, spec=spec)
    assert tuned is spec and res.source == "prior"


def test_schema2_tune_cache_migrates_losslessly(tmp_path):
    """A schema-2 file (hosts mapping straight to schedule entries)
    loads with every measurement intact under the schema-3 nesting,
    and the next save writes schema 3 — the committed .digc_tune.json
    upgrade path."""
    path = tmp_path / "tune.json"
    key = workload_key(2, 64, 64, 8, 4)
    entry = {"block_n": None, "block_m": 64, "merge": "select",
             "fuse_norms": False, "impl": "blocked", "kernel_merge": None,
             "us_per_call": 1.0, "exact_match": True, "source": "measured"}
    path.write_text(json.dumps({
        "schema": 2, "hosts": {host_key(): {key: entry}},
    }))
    tuner = DigcTuner(path)
    cached = tuner.lookup(key)
    assert cached is not None and cached.source == "cached"
    assert tuner.bucket_sets == {}
    tuner.save()
    data = json.loads(path.read_text())
    assert data["schema"] == 3
    host = data["hosts"][host_key()]
    assert host["schedules"][key]["block_m"] == 64
    # round-trip: a schema-3 load serves the migrated entry unchanged
    assert DigcTuner(path).lookup(key).config.block_m == 64


def test_optimal_bucket_set_minimizes_padded_work():
    """Tiny closed-form cases: the optimizer picks the boundaries that
    minimize sum(ticks * bucket(live) * cost) under the program cap,
    always covering slots."""
    # singleton-heavy traffic: a 1-bucket saves 7 padded lanes * 10
    # ticks; the rare full tick keeps the mandatory 8.
    assert optimal_bucket_set({1: 10, 8: 1}, slots=8,
                              max_programs=2) == (1, 8)
    # cap 1 leaves no room for boundaries: everything pads to slots
    assert optimal_bucket_set({1: 10, 8: 1}, slots=8,
                              max_programs=1) == (8,)
    # enough cap for every observed count -> zero padded work
    hist = {1: 5, 3: 4, 6: 2}
    full = optimal_bucket_set(hist, slots=8, max_programs=4)
    assert full == (1, 3, 6, 8)
    # empty histogram: nothing observed, serve at the slot width
    assert optimal_bucket_set({}, slots=8) == (8,)
    # per-size costs weight the boundaries toward the expensive cell
    hist2 = {224: {1: 10, 4: 10}, 448: {2: 10}}
    got = optimal_bucket_set(hist2, slots=4, max_programs=2,
                             costs={224: 1, 448: 1000})
    assert 2 in got  # the 448 cell's live count wins the boundary
    with pytest.raises(ValueError, match="outside"):
        optimal_bucket_set({9: 1}, slots=8)


def test_optimal_bucket_set_deterministic():
    """A fixed histogram selects the same set regardless of dict
    insertion order (the fixed-trace determinism the scheduler tests
    rely on); ties break toward fewer, smaller buckets."""
    h1 = {1: 3, 2: 3, 5: 1, 8: 2}
    h2 = dict(reversed(list(h1.items())))
    a = optimal_bucket_set(h1, slots=8, max_programs=3)
    assert a == optimal_bucket_set(h2, slots=8, max_programs=3)
    assert a == optimal_bucket_set(h1, slots=8, max_programs=3)
    # a count observed once with zero benefit is not picked: ties go
    # to the smaller set
    assert optimal_bucket_set({8: 5}, slots=8, max_programs=4) == (8,)


def test_tune_bucket_set_persists_per_shape(tmp_path):
    """tune_bucket_set caches per (slots, sizes, cap) serving shape —
    a fresh tuner (and an engine with buckets="auto") reads the choice
    back without re-deriving; force=True re-derives in place."""
    path = tmp_path / "tune.json"
    tuner = DigcTuner(path)
    hist = {32: {1: 10, 2: 4, 8: 1}}
    got = tuner.tune_bucket_set(hist, slots=8, max_programs=3)
    assert got == optimal_bucket_set(hist, slots=8, max_programs=3)
    fresh = DigcTuner(path)
    assert fresh.lookup_bucket_set(slots=8, sizes=(32,),
                                   max_programs=3) == got
    # a different shape is a different entry
    assert fresh.lookup_bucket_set(slots=4, sizes=(32,),
                                   max_programs=3) is None
    # cached: a different histogram under the same shape returns the
    # cached set unless forced
    other_hist = {32: {7: 100}}
    assert fresh.tune_bucket_set(other_hist, slots=8, max_programs=3,
                                 sizes=(32,)) == got
    forced = fresh.tune_bucket_set(other_hist, slots=8, max_programs=3,
                                   sizes=(32,), force=True)
    assert forced == (7, 8)
    # the recorded histogram makes the cached choice auditable
    data = json.loads(path.read_text())
    entry = data["hosts"][host_key()]["bucket_sets"][
        bucket_set_key(8, (32,), 3)]
    assert entry["hist"] == {"32:7": 100}


def test_kernel_tile_defaults_respect_vmem():
    for (n, m, d, kd) in [(196, 196, 192, 16), (12544, 12544, 96, 9),
                          (4096, 1024, 768, 32)]:
        bn, bm = kernel_tile_defaults(n, m, d, kd)
        work = (bn * d + bm * d + bn * bm + 2 * bn * kd) * 4
        assert work <= 128 * 1024 * 1024 // 8
        assert bn >= 8 and bm >= 128


def test_tune_raises_when_a_kernel_candidate_fails(monkeypatch):
    """A fused-kernel candidate that fails to build raises out of the
    tuner (on a TPU: a kernel Mosaic refuses) instead of being dropped
    from the measured set in silence."""
    from repro.kernels import ops

    def refuse(*args, **kwargs):
        raise RuntimeError("kernel refused by the compiler")

    monkeypatch.setattr(ops, "digc_topk", refuse)
    monkeypatch.setattr(DigcTuner, "candidates", lambda self, *a, **k: [
        TileConfig(128, 128, "kernel", impl="pallas", kernel_merge="bitonic")])
    x = _rand(np.random.default_rng(0), 1, 64, 8)
    with pytest.raises(RuntimeError, match="refused by the compiler"):
        DigcTuner().tune(x, spec=DigcSpec(impl="blocked", k=4))


def test_device_peaks_table():
    """Peaks live in one table keyed by device kind: the target chip's
    feed the model projections (ICI per link), an unlisted chip has
    none."""
    from repro.core import perfmodel as pm
    from repro.launch.roofline import roofline_terms

    v5e = pm.device_peaks("TPU v5 lite")
    assert pm.TPUConfig().peak_flops == v5e["bf16_flops"] == 197e12
    assert pm.TPUConfig().hbm_bw == v5e["hbm_bw"] == 819e9
    terms = roofline_terms(197e12, 819e9, 50e9)
    assert terms == pytest.approx(
        {"compute": 1.0, "memory": 1.0, "collective": 1.0})
    with pytest.raises(KeyError, match="no published peaks"):
        pm.device_peaks("TPU v0 unknown")
