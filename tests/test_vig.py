"""ViG model tests: variants, impl-swapping, DIGC workload accounting,
short training convergence."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models import vig
from repro.models.module import init_params


def _tiny_iso(k=4):
    return vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=64, embed_dims=(32,), depths=(2,), num_classes=7, k=k
    )


def _tiny_pyr():
    return vig.VIG_VARIANTS["vig_ti_pyr"].replace(
        image_size=32, embed_dims=(16, 24, 32, 48), depths=(1, 1, 1, 1),
        num_classes=7, k=3,
    )


def test_all_variants_registered():
    assert set(vig.VIG_VARIANTS) == {
        "vig_ti_iso", "vig_s_iso", "vig_b_iso",
        "vig_ti_pyr", "vig_s_pyr", "vig_m_pyr", "vig_b_pyr",
    }
    # paper dims
    assert vig.VIG_VARIANTS["vig_ti_iso"].embed_dims == (192,)
    assert vig.VIG_VARIANTS["vig_b_iso"].embed_dims == (640,)
    assert vig.VIG_VARIANTS["vig_ti_pyr"].embed_dims == (48, 96, 240, 384)


@pytest.mark.parametrize("maker", [_tiny_iso, _tiny_pyr])
def test_forward_shape_finite(maker):
    cfg = maker()
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1),
                             (2, cfg.image_size, cfg.image_size, 3))
    logits = vig.vig_forward(params, imgs, cfg)
    assert logits.shape == (2, cfg.num_classes)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_digc_impl_swap_is_exact():
    """The paper's modularity claim: swapping the DIGC implementation
    (reference / blocked / pallas) must not change model output."""
    cfg = _tiny_iso()
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, 3))
    base = vig.vig_forward(params, imgs, cfg, digc_impl="blocked")
    for impl in ("reference", "pallas"):
        out = vig.vig_forward(params, imgs, cfg, digc_impl=impl)
        np.testing.assert_allclose(np.asarray(base), np.asarray(out),
                                   rtol=1e-5, atol=1e-5)


def test_count_digc_work_vig_ti_224():
    cfg = vig.VIG_VARIANTS["vig_ti_iso"]
    work = vig.count_digc_work(cfg)
    assert len(work) == 12
    assert all(w["N"] == 196 and w["M"] == 196 and w["D"] == 192 for w in work)
    # dilation grows with depth
    assert work[0]["dilation"] == 1 and work[-1]["dilation"] > 1


def test_count_digc_work_pyramid_reduction():
    work = vig.count_digc_work(vig.VIG_VARIANTS["vig_ti_pyr"])
    # stage 0: grid 56 -> N=3136, co-nodes pooled by r=4 -> 196
    assert work[0] == {"stage": 0, "N": 3136, "M": 196, "D": 48, "k": 9,
                       "dilation": 1}
    assert work[-1]["stage"] == 3
    # last stage: 7x7, no reduction
    assert work[-1]["N"] == 49 and work[-1]["M"] == 49


def test_patchify_inverse_shape():
    imgs = jnp.arange(2 * 32 * 32 * 3, dtype=jnp.float32).reshape(2, 32, 32, 3)
    p = vig.patchify(imgs, 8)
    assert p.shape == (2, 16, 8 * 8 * 3)


def test_resolution_dilation_parity_at_native():
    """Per-cell dilation schedules (DESIGN.md §13/§14): at or below
    the native grid the scaled schedule IS the model's schedule — the
    explicit grid= plans must match the default plans exactly, block
    for block, so native serving cells stay byte-identical to the
    pre-scaling programs."""
    for name in ("vig_ti_iso", "vig_ti_pyr"):
        cfg = vig.VIG_VARIANTS[name]
        base = vig.vig_stage_plans(cfg)
        at_native = vig.vig_stage_plans(cfg, grid=cfg.base_grid)
        for p0, p1 in zip(base, at_native):
            assert p0.dilations == p1.dilations, name
            assert p0.k_effs == p1.k_effs, name
            assert p0.spec.k == p1.spec.k, name
    # below native: the ramp never shrinks a stride either
    half = vig.vig_stage_plans(vig.VIG_VARIANTS["vig_ti_iso"], grid=7)
    assert all(d >= 1 for d in half[0].dilations)
    assert vig._resolution_dilation(3, 7, 14) == 3


def test_resolution_dilation_scales_above_native():
    """Above the native grid the dilation stride rides the same linear
    ramp as k — d at native, 2d at twice native, clamped — and the
    scaled schedule still honors the m-feasibility clamp
    (k_eff * dilation <= m) on every block."""
    assert vig._resolution_dilation(2, 28, 14) == 4
    assert vig._resolution_dilation(2, 21, 14) == 3
    assert vig._resolution_dilation(2, 56, 14) == 4  # clamped at 2d
    cfg = vig.VIG_VARIANTS["vig_ti_iso"]
    native = vig.vig_stage_plans(cfg)[0]
    doubled = vig.vig_stage_plans(cfg, grid=cfg.base_grid * 2)[0]
    # every block's stride doubled with the grid, under the scaled cap
    # (max_dilation rides the ramp too: the 2x cell may exceed the
    # native cap, up to 2x it)
    assert doubled.dilations == tuple(
        min(2 * d, 2 * cfg.max_dilation) for d in native.dilations)
    assert max(doubled.dilations) > cfg.max_dilation
    for dil, k_eff in zip(doubled.dilations, doubled.k_effs):
        assert k_eff * dil <= doubled.m
    # use_dilation=False stays inert at every resolution
    flat = vig.vig_stage_plans(cfg.replace(use_dilation=False),
                               grid=cfg.base_grid * 2)[0]
    assert set(flat.dilations) == {1}


# The official code's ViG-B schedule: [int(x) for x in
# torch.linspace(9, 18, 16)], block i at dilation min(i // 4 + 1, 10).
B_KNN = (9, 9, 10, 10, 11, 12, 12, 13, 13, 14, 15, 15, 16, 16, 17, 18)
B_DIL = (1,) * 4 + (2,) * 4 + (3,) * 4 + (4,) * 4


def test_vig_b_iso_plans_the_published_schedule():
    cfg = vig.VIG_VARIANTS["vig_b_iso"]
    assert cfg.embed_dims == (640,) and cfg.depths == (16,)
    assert cfg.num_knn == B_KNN and cfg.max_dilation == 10
    (plan,) = vig.vig_stage_plans(cfg)
    assert plan.ks == plan.k_effs == B_KNN and plan.dilations == B_DIL
    assert plan.spec.k == 9 and plan.n == plan.m == 196
    assert sum(k * d for k, d in zip(plan.k_effs, plan.dilations)) == 573
    assert vig.VIG_VARIANTS["vig_s_iso"].num_knn == B_KNN
    with pytest.raises(ValueError, match="num_knn"):
        cfg.replace(depths=(12,))


def test_resolution_k_ramps_each_blocks_own_k():
    cfg = vig.VIG_VARIANTS["vig_b_iso"]
    (doubled,) = vig.vig_stage_plans(cfg, grid=28)
    assert doubled.ks == tuple(2 * k for k in B_KNN)
    assert doubled.dilations == tuple(2 * d for d in B_DIL)
    (half_up,) = vig.vig_stage_plans(cfg, grid=21)
    assert half_up.ks == tuple(vig._resolution_k(k, 21, 14) for k in B_KNN)
    assert half_up.ks == tuple(int(round(1.5 * k)) for k in B_KNN)
    for plan in (doubled, half_up):
        assert all(k * d <= plan.m
                   for k, d in zip(plan.k_effs, plan.dilations))


def _lowered_text(cfg, size):
    from repro.models.module import abstract_params

    params = abstract_params(vig.vig_param_spec(cfg))
    images = jax.ShapeDtypeStruct((2, size, size, 3), jnp.float32)
    st = vig.init_vig_state(cfg, 2, None, per_slot=True,
                            grid=size // cfg.patch)
    f = jax.jit(lambda p, im, st: vig.vig_forward(p, im, cfg, state=st))
    return f.lower(params, images, st).as_text()


@pytest.mark.parametrize("name,size", [("vig_ti_iso", 64), ("vig_ti_iso", 128),
                                       ("vig_s_pyr", 64)])
def test_uniform_schedule_is_the_scheduleless_program(name, size):
    """``num_knn`` of one k in every block plans and lowers exactly as
    ``num_knn=None``: the configurations without a ramp serve the
    program they served before the schedule existed."""
    cfg = vig.VIG_VARIANTS[name].replace(image_size=size)
    assert cfg.num_knn is None
    flat = cfg.replace(num_knn=(cfg.k,) * sum(cfg.depths))
    for grid in (None, 2 * cfg.base_grid):
        assert vig.vig_stage_plans(cfg, grid=grid) == vig.vig_stage_plans(
            flat, grid=grid)
    assert vig.count_digc_work(cfg) == vig.count_digc_work(flat)
    assert _lowered_text(cfg, size) == _lowered_text(flat, size)


def _ramp_iso(knn=(3, 3, 4, 5)):
    return vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=32, patch=8, embed_dims=(16,), depths=(len(knn),),
        num_classes=5, k=knn[0], num_knn=knn)


def test_per_block_k_reaches_the_accounting_and_the_reuse_replay():
    """``count_digc_work`` reports each block's own k, and the
    stale-graph replay builds each call's lists at the width its block
    served: only the blocks of the cached graph's k can reuse it."""
    from repro.core import DigcSpec
    from repro.core.tuner import tune_reuse

    cfg = _ramp_iso()
    assert [w["k"] for w in vig.count_digc_work(cfg)] == [3, 3, 4, 5]
    assert [w["k"] for w in vig.count_digc_work(cfg, grid=8)] == [6, 6, 8, 10]
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1), (3, 1, 32, 32, 3))
    spec = DigcSpec(impl="blocked", k=3)

    def frac(c):
        ticks = []
        for im in imgs:
            cap = []
            vig.vig_forward(params, im, c, digc_impl=spec, digc_capture=cap)
            assert [i.shape[-1] for *_, i in cap] == list(
                c.num_knn or (c.k,) * 4)
            ticks.append(cap)
        _, results = tune_reuse(ticks, spec=spec, policy="tick", taus=(1e9,),
                                max_stale=100)
        return results[0].reuse_frac

    # "tick" reuses every call after a stage's first: blocks 1-3 of each
    # tick and, after the first tick, block 0 too; under the ramp only
    # block 1 shares block 0's k
    assert frac(cfg.replace(num_knn=None)) == pytest.approx(11 / 12)
    assert frac(cfg) == pytest.approx(5 / 12)


def test_reuse_state_under_a_ramp_caches_the_first_blocks_k():
    """The stale-graph buffers are sized by a stage's first block; under
    a ramp a block of another k builds its own lists and leaves them."""
    from repro.core import DigcSpec

    cfg = _ramp_iso()
    spec = DigcSpec(impl="blocked", k=3, reuse="tick", drift_tau=1e9,
                    max_stale=100)
    st = vig.init_vig_state(cfg, 2, spec, per_slot=True)
    assert st.entries["stage0"].graph_idx.shape == (2, 16, 3)
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    answers = []
    for _ in range(2):
        cap = []
        logits, st = vig.vig_forward(params, imgs, cfg, digc_impl=spec,
                                     state=st, digc_capture=cap)
        assert [i.shape for *_, i in cap] == [(2, 16, k) for k in (3, 3, 4, 5)]
        assert st.entries["stage0"].graph_idx.shape == (2, 16, 3)
        answers.append(np.asarray(logits))
    # block 1 serves block 0's graph (the "tick" policy), so the answer
    # departs from the plain forward's; blocks 2 and 3 build their own
    assert all(np.isfinite(a).all() for a in answers)
    assert not np.array_equal(answers[0], np.asarray(
        vig.vig_forward(params, imgs, cfg)))


@pytest.mark.slow
def test_vig_training_reduces_loss():
    from repro.data.pipeline import DataConfig, synth_image_batch
    from repro.train.optimizer import OptConfig
    from repro.train.trainer import init_train_state, make_train_step

    cfg = _tiny_iso()
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    oc = OptConfig(lr=1e-3, warmup_steps=5, total_steps=40, weight_decay=0.0)
    step_fn = jax.jit(make_train_step(cfg, oc, loss_fn=vig.vig_loss_fn,
                                      param_dtype=jnp.float32))
    opt = init_train_state(params)
    dc = DataConfig(seq_len=1, global_batch=8, vocab_size=1, seed=0)
    losses = []
    for s in range(40):
        b = synth_image_batch(dc, s, image_size=64, num_classes=cfg.num_classes)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt, m = step_fn(params, opt, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-8:]) < np.mean(losses[:8]) - 0.2, losses[::8]
