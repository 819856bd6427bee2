"""The serving engine's tracer (``serve/tracer.py``) and the named scopes
of the ViG forward: off by default and free of effect on the answers;
when recording, the tick's spans partition it, and ``host_pulls`` counts
every device-to-host copy the tick makes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DigcSpec
from repro.models import vig
from repro.models.module import init_params
from repro.serve.engine import VigRequest, VigServeEngine
from repro.serve.tracer import NULL_SPAN

CHILDREN = ("engine.admit", "engine.guard", "engine.stage",
            "engine.dispatch", "engine.sync", "engine.writeback")


def _iso():
    return vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=16, patch=4, embed_dims=(16,), depths=(2,),
        num_classes=3, k=3)


def _pyr():
    return vig.VIG_VARIANTS["vig_ti_pyr"].replace(
        image_size=32, embed_dims=(8, 16, 24, 32), depths=(1, 1, 1, 1),
        num_classes=3, k=3)


def _engine(cfg, impl="blocked", **kw):
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    return VigServeEngine(cfg, params, digc_impl=impl, autotune=False, **kw)


def _serve(eng, images, uid0=0):
    """One tick of anonymous requests; their logits in lane order."""
    reqs = [VigRequest(uid=uid0 + i, image=im) for i, im in enumerate(images)]
    for r in reqs:
        eng.submit(r)
    assert eng.step() == len(reqs)
    return [r.logits for r in reqs]


def _images(cfg, n, seed):
    rng = np.random.default_rng(seed)
    s = cfg.image_size
    return [rng.standard_normal((s, s, 3)).astype(np.float32)
            for _ in range(n)]


def test_tracer_off_records_nothing_and_leaves_answers_bit_equal():
    cfg = _iso()
    off, on = _engine(cfg), _engine(cfg)
    on.tracer.recording = True
    assert off.tracer.span("engine.step") is NULL_SPAN
    for t in range(2):
        imgs = _images(cfg, 3, t)
        for a, b in zip(_serve(off, imgs), _serve(on, imgs)):
            np.testing.assert_array_equal(a, b)
    assert off.stats()["tracer"] == {"spans": {}, "counters": {}}
    assert on.stats()["tracer"]["spans"]["engine.step"]["calls"] == 2


@pytest.mark.parametrize("maker", [_iso, _pyr], ids=["iso", "pyr"])
def test_spans_partition_the_tick(maker):
    cfg = maker()
    eng = _engine(cfg)
    _serve(eng, _images(cfg, 4, 0))  # compiles outside the record
    eng.tracer.recording = True
    _serve(eng, _images(cfg, 4, 1), uid0=10)
    spans = eng.tracer.totals()["spans"]
    assert set(spans) == {"engine.step", *CHILDREN}
    root = spans["engine.step"]
    assert root["calls"] == 1
    # guard runs inside admission (one token refresh over the tick's
    # cold resets) and writeback (token refresh) besides its own
    # screening pass
    assert spans["engine.guard"]["calls"] == 1 + 2
    parts = root["self_s"] + sum(spans[c]["self_s"] for c in CHILDREN)
    assert parts == pytest.approx(root["total_s"], rel=1e-9, abs=1e-12)
    assert all(spans[c]["self_s"] > 0 for c in CHILDREN)


def _row_buffers(state) -> int:
    """Device buffers a whole-state pull copies: one per per-row field
    of each entry."""
    return sum(len(e.row_buffers()) for e in state.entries.values())


def _graph_pulls(state) -> int:
    """``graph_age`` and the two ``graph_snap`` reads per entry with a
    cached graph."""
    return sum(3 for e in state.entries.values() if e.graph_age is not None)


# the stale-graph policy keeps a cached graph per entry, which the
# tick's reuse accounting reads back
REUSE = DigcSpec(impl="blocked", k=3, reuse="tick", drift_tau=0.05,
                 max_stale=8)


@pytest.mark.parametrize("guards", [True, False], ids=["guards", "bare"])
@pytest.mark.parametrize("maker,impl", [(_iso, "blocked"), (_pyr, "blocked"),
                                        (_iso, REUSE)],
                         ids=["iso", "pyr", "iso_reuse"])
def test_host_pulls_count_every_copy_to_the_host(maker, impl, guards):
    cfg = maker()
    eng = _engine(cfg, impl, guards=guards)
    _serve(eng, _images(cfg, 4, 0))
    eng.tracer.recording = True
    n = 3
    _serve(eng, _images(cfg, n, 1), uid0=10)
    state = eng.slot_state()
    buffers = _row_buffers(state)
    assert buffers > 0 and (_graph_pulls(state) > 0) == (impl is REUSE)
    # guarded: the n anonymous lanes are cold-reset on admission in one
    # call and re-fingerprinted in one refresh; then one finiteness and
    # one fingerprint pull screen the picked lanes, and one refresh
    # follows the scatter
    guard_pulls = (1 + 3) * buffers if guards else 0
    want = guard_pulls + _graph_pulls(state) + 1  # + the logits
    assert eng.tracer.totals()["counters"] == {
        "host_pulls": want, "reset_calls": 1, "reset_rows": n,
        **_digc_work(cfg, (n,))}


def _lowered(cfg, impl):
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    st = vig.init_vig_state(cfg, 2, impl, per_slot=True)
    s = cfg.image_size
    f = jax.jit(lambda p, im, st: vig.vig_forward(p, im, cfg, digc_impl=impl,
                                                   state=st))
    return f.lower(params, jnp.zeros((2, s, s, 3)), st)


@pytest.mark.parametrize("maker", [_iso, _pyr], ids=["iso", "pyr"])
def test_named_scopes_land_in_the_program(maker):
    cfg = maker()
    lowered = _lowered(cfg, "blocked")
    text = lowered.as_text(debug_info=True)
    for scope in ("stem/", "stage0/block0/digc/", "stage0/block0/graph_conv/",
                  "stage0/block0/ffn/", "head/"):
        assert scope in text, scope
    if len(cfg.depths) > 1:
        assert "downsample0/" in text and "stage1/block0/digc/" in text
    # the blocked tier's top-k selection (a TopK call or a sort) and any
    # loop left around its tiles sit under the blocks' digc scopes
    lines = lowered.compile().as_text().splitlines()
    digc = r"/stage\d+/block\d+/digc/"

    def op_names(pattern):
        return [re.search(r'op_name="([^"]*)"', ln)
                for ln in lines if re.search(pattern, ln)]

    selects = op_names(r'^\s*%\S+ = .* (sort\(|custom-call\(.*'
                       r'custom_call_target="TopK")')
    assert selects
    assert all(m and re.search(digc, m.group(1)) for m in selects)
    assert all(m and re.search(digc, m.group(1))
               for m in op_names(r"^\s*%while\S* = .* while\("))


def test_pallas_kernels_carry_stable_names():
    cfg = _iso()
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    st = vig.init_vig_state(cfg, 2, "pallas", per_slot=True)
    jaxpr = jax.make_jaxpr(lambda p, im, st: vig.vig_forward(
        p, im, cfg, digc_impl="pallas", state=st))(
            params, jnp.zeros((2, 16, 16, 3)), st)

    def names(jx):
        for e in jx.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["name"]
            for v in e.params.values():
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    yield from names(sub)

    assert set(names(jaxpr.jaxpr)) == {"digc_topk", "mrconv"}


def _digc_work(cfg, lanes):
    """The DIGC counters of ticks of ``lanes`` live lanes each, from the
    plans: per block, live lanes x N x k (``digc_lists``) and x k*d
    (``digc_candidates``); and per tick one co-node tile per block
    (``digc_tiles``: these small graphs fit one tile)."""
    plans = vig.vig_stage_plans(cfg)
    return {"digc_lists": sum(lanes) * sum(p.n * k for p in plans
                                           for k in p.k_effs),
            "digc_candidates": sum(lanes) * sum(
                p.n * k * d for p in plans
                for k, d in zip(p.k_effs, p.dilations)),
            "digc_tiles": len(lanes) * sum(p.depth for p in plans)}


def _ramp():
    return _iso().replace(depths=(4,), num_knn=(3, 3, 4, 5))


@pytest.mark.parametrize("maker", [_iso, _pyr, _ramp],
                         ids=["iso", "pyr", "ramp"])
def test_digc_work_counters_follow_the_plans(maker):
    cfg = maker()
    eng = _engine(cfg)
    _serve(eng, _images(cfg, 4, 0))
    assert not {"digc_lists", "digc_tiles"} & set(
        eng.tracer.totals()["counters"])
    eng.tracer.recording = True
    lanes = (3, 2)
    for t, n in enumerate(lanes):
        _serve(eng, _images(cfg, n, t + 1), uid0=10 * (t + 1))
    if cfg.num_knn is not None:
        assert [k for p in vig.vig_stage_plans(cfg)
                for k in p.k_effs] == [3, 3, 4, 5]
    want = _digc_work(cfg, lanes)
    counters = eng.tracer.totals()["counters"]
    assert {k: counters[k] for k in want} == want
    eng.tracer.recording = False
    _serve(eng, _images(cfg, 2, 9), uid0=90)
    counters = eng.tracer.totals()["counters"]
    assert {k: counters[k] for k in want} == want


@pytest.mark.parametrize("variant,size,block_m,tiles", [
    ("vig_ti_iso", 224, None, 12),  # M = 196: one tile a block
    ("vig_ti_iso", 448, None, 12),  # M = 784: one tile, not four
    ("vig_ti_iso", 448, 256, 48),  # an explicit width streams four
    ("vig_s_pyr", 224, None, 12),  # stage 0: N = 3136 over M = 196
    ("vig_ti_iso", 896, None, 60),  # M = 3136: five tiles of 640
])
def test_digc_tiles_counts_conode_tiles_per_tick(variant, size, block_m,
                                                 tiles):
    """``digc_tiles`` counts the co-node tiles a bucket-8 tick's blocks
    stream at published widths, once per tick whatever its live lanes,
    and nothing while the tracer is off."""
    cfg = vig.VIG_VARIANTS[variant]
    params = jax.eval_shape(lambda: init_params(
        vig.vig_param_spec(cfg), jax.random.PRNGKey(0)))
    eng = VigServeEngine(cfg, params, image_sizes=(size,), autotune=False,
                         digc_impl=DigcSpec(impl="blocked", block_m=block_m))
    eng._count_digc(8, size, 3)
    assert "digc_tiles" not in eng.tracer.totals()["counters"]
    eng.tracer.recording = True
    for lanes in (3, 8):
        eng._count_digc(8, size, lanes)
    assert eng.tracer.totals()["counters"]["digc_tiles"] == 2 * tiles
