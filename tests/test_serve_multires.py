"""Multi-resolution serving: ragged N as a bucket dimension (§13).

The acceptance contract for the (B, N) lattice:

* **Parity**: a mixed-size ragged trace through one ``VigServeEngine``
  (``image_sizes=``) must match, per request, the same-resolution B=1
  replay of its own (tenant, size) stream — warm state follows the
  tenant per N-bucket, across bucket changes AND across
  eviction/parking (the parked copy carries every N-bucket's rows).
* **Bit-identity**: with B=1 cells, every served row is bit-identical
  (CPU) to the jitted B=1 same-resolution replay; a padded (masked)
  request is bit-identical to the B=1 replay of the same padded
  forward, and pad nodes provably never enter a live row's top-k
  (DIGC-level bitwise check).
* **Program bound**: at most |buckets| x |image_sizes| compiled
  programs for a whole mixed trace (``on_compile`` sees (size, bucket)
  cells).
* **Typed config/submit errors**: odd-grid pyramids fail at engine
  construction naming the stage and grid; off-lattice submissions fail
  at the submitter naming the field.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import DigcSpec, digc
from repro.models import vig
from repro.models.module import init_params
from repro.models.vig import VigGridError
from repro.serve.engine import VigRequest, VigServeEngine
from _subproc import run_snippet


def _tiny_vig(impl):
    """16x16 / patch 4 -> native N=16 grid; single stage, r=1."""
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=16, patch=4, embed_dims=(16,), depths=(2,),
        num_classes=3, k=3, digc_impl=impl,
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _image(rng, s=16):
    return rng.standard_normal((s, s, 3)).astype(np.float32)


def _replay_stream(cfg, params, impl, reqs, size):
    """Jitted B=1 stateful replay of one (tenant, size) stream — the
    same program shape a B=1 cell serves, so comparisons against B=1
    cells are bitwise and against padded buckets are allclose."""
    state = vig.init_vig_state(cfg, 1, impl, per_slot=True,
                               grid=size // cfg.patch)
    fwd = jax.jit(
        lambda p, im, s: vig.vig_forward(p, im, cfg, digc_impl=impl,
                                         state=s)
    )
    outs = []
    for r in reqs:
        logits, state = fwd(params, jnp.asarray(r.image)[None], state)
        outs.append(np.asarray(logits)[0])
    return outs


# ---------------------------------------------------------------------------
# Parity: one engine, mixed 16/24/32 trace == per-(tenant, size) replay


def test_mixed_trace_matches_same_resolution_replay():
    """Tenants x sizes interleave on a 2-slot engine (so eviction +
    multi-bucket parking fire): every request matches its own
    (tenant, size) B=1 replay — the cluster tier's centroid carry makes
    any cold-vs-warm or cross-bucket state leak visible — and the
    program count stays <= |buckets| x |image_sizes|."""
    cfg, params = _tiny_vig("cluster")
    compiled = []
    eng = VigServeEngine(cfg, params, digc_impl="cluster", autotune=False,
                         buckets=(1, 2), image_sizes=(16, 24, 32),
                         on_compile=compiled.append)
    rng = np.random.default_rng(11)
    waves = [
        [("A", 16)], [("B", 24), ("C", 16)], [("A", 16), ("B", 24)],
        [("C", 32)], [("A", 24)], [("A", 16), ("C", 16)], [("B", 24)],
    ]
    streams: dict[tuple, list[VigRequest]] = {}
    uid = 0
    for wave in waves:
        for t, s in wave:
            req = VigRequest(uid=uid, image=_image(rng, s), tenant=t)
            streams.setdefault((t, s), []).append(req)
            eng.submit(req)
            uid += 1
        # a wave may span several cells -> several ticks
        while eng.queue:
            eng.step()
            assert eng.last_cell is not None
            size, bucket = eng.last_cell
            assert bucket == eng.bucket_for(len(eng.last_lanes))
    for (t, s), reqs in streams.items():
        refs = _replay_stream(cfg, params, "cluster", reqs, s)
        for req, ref in zip(reqs, refs):
            assert req.done and req.fault is None
            np.testing.assert_allclose(req.logits, ref, rtol=1e-5,
                                       atol=1e-5)
    assert eng.compile_count <= len(eng.buckets) * len(eng.image_sizes)
    assert eng.compile_count == len(set(compiled))
    assert all(s in eng.image_sizes and b in eng.buckets
               for s, b in compiled)
    # the trace crossed slots: at least one eviction parked rows for
    # MULTIPLE N-buckets (the {size: rows} layout)
    assert eng.park_hits + len(eng._parked) >= 1


def test_eviction_parks_and_restores_every_n_bucket():
    """A tenant warm at two resolutions, LRU-evicted, must come back
    warm at BOTH: the parked copy is keyed by N-bucket."""
    cfg, params = _tiny_vig("cluster")
    eng = VigServeEngine(cfg, params, digc_impl="cluster", autotune=False,
                         buckets=(1,), image_sizes=(16, 24))
    rng = np.random.default_rng(3)
    for uid, (t, s) in enumerate([("A", 16), ("A", 24)]):
        eng.submit(VigRequest(uid=uid, image=_image(rng, s), tenant=t))
        eng.run()
    a_slot = eng._tenant_slot["A"]
    assert eng.slot_row_steps(16)["stage0"][a_slot] == 2
    assert eng.slot_row_steps(24)["stage0"][a_slot] == 2
    # evict A by filling the slot ring with fresh tenants
    for uid, t in enumerate(["B", "C"], start=10):
        eng.submit(VigRequest(uid=uid, image=_image(rng), tenant=t))
        eng.run()
    assert "A" in eng._parked
    assert set(eng._parked["A"]) == {16, 24}  # every N-bucket parked
    # re-admit: A's row counters continue from the parked copy at both
    # sizes (a cold admit would restart the count from zero)
    eng.submit(VigRequest(uid=20, image=_image(rng, 16), tenant="A"))
    eng.run()
    assert eng.park_hits == 1
    a_slot = eng._tenant_slot["A"]
    assert eng.slot_row_steps(16)["stage0"][a_slot] == 4
    assert eng.slot_row_steps(24)["stage0"][a_slot] == 2


# ---------------------------------------------------------------------------
# Bit-identity (CPU): B=1 cells vs the jitted B=1 replay


def test_b1_cells_bitwise_identical_to_replay():
    cfg, params = _tiny_vig("blocked")
    eng = VigServeEngine(cfg, params, digc_impl="blocked", autotune=False,
                         buckets=(1,), image_sizes=(16, 24))
    rng = np.random.default_rng(5)
    streams: dict[tuple, list[VigRequest]] = {}
    for uid, (t, s) in enumerate(
        [("A", 16), ("B", 24), ("A", 16), ("B", 24), ("A", 24)]
    ):
        req = VigRequest(uid=uid, image=_image(rng, s), tenant=t)
        streams.setdefault((t, s), []).append(req)
        eng.submit(req)
    eng.run()
    for (t, s), reqs in streams.items():
        refs = _replay_stream(cfg, params, "blocked", reqs, s)
        for req, ref in zip(reqs, refs):
            np.testing.assert_array_equal(req.logits, ref)


def test_padded_request_bitwise_vs_masked_replay():
    """A ragged 20px request served through the 24px cell's masked
    program is bit-identical to the B=1 replay of the same padded
    forward (same canvas, same mask) — the pad-isolation contract at
    the engine boundary."""
    cfg, params = _tiny_vig("blocked")
    eng = VigServeEngine(cfg, params, digc_impl="blocked", autotune=False,
                         buckets=(1,), image_sizes=(24,))
    rng = np.random.default_rng(9)
    img = _image(rng, 20)
    req = VigRequest(uid=0, image=img, tenant="P")
    eng.submit(req)
    assert req._serve_size == 24
    mask = np.asarray(req._serve_mask)
    assert mask.sum() == (20 // 4) ** 2 and mask.size == (24 // 4) ** 2
    eng.run()
    assert req.done and req.fault is None
    canvas = np.zeros((24, 24, 3), np.float32)
    canvas[:20, :20] = img
    state = vig.init_vig_state(cfg, 1, "blocked", per_slot=True, grid=6)
    fwd = jax.jit(
        lambda p, im, s, mv: vig.vig_forward(
            p, im, cfg, digc_impl="blocked", state=s, valid_mask=mv)
    )
    ref, _ = fwd(params, jnp.asarray(canvas)[None], state,
                 jnp.asarray(mask)[None])
    np.testing.assert_array_equal(req.logits, np.asarray(ref)[0])


@pytest.mark.parametrize("impl", ["reference", "blocked"])
def test_pad_nodes_never_enter_live_topk(impl):
    """DIGC-level bitwise pad isolation: appending garbage pad nodes
    under an m_valid mask leaves every live row's top-k — indices AND
    the selection itself — exactly the live-only build's."""
    rng = np.random.default_rng(1)
    n0, n_pad, d = 20, 12, 8
    x_live = jnp.asarray(rng.standard_normal((2, n0, d)), jnp.float32)
    pads = jnp.asarray(100.0 * rng.standard_normal((2, n_pad, d)),
                       jnp.float32)
    x_pad = jnp.concatenate([x_live, pads], axis=1)
    mask = np.zeros(n0 + n_pad, bool)
    mask[:n0] = True
    spec = DigcSpec(impl=impl, k=4)
    idx_live = np.asarray(digc(x_live, spec=spec))
    idx_pad = np.asarray(digc(x_pad, spec=spec,
                              m_valid=jnp.asarray(mask)))
    np.testing.assert_array_equal(idx_pad[:, :n0], idx_live)
    assert (idx_pad[:, :n0] < n0).all()  # no pad index ever selected


def test_pad_mask_rejected_by_incapable_tier():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1, 16, 8)), jnp.float32)
    with pytest.raises(ValueError, match="pad-node masking"):
        digc(x, spec=DigcSpec(impl="cluster", k=3),
             m_valid=jnp.ones(16, bool))


# ---------------------------------------------------------------------------
# Typed errors: odd grids at construction, off-lattice submits


def test_odd_grid_pyramid_raises_at_engine_construction():
    """A size whose grid goes odd before a downsample (or indivisible
    by a pooling ratio) must fail when the engine is built — a typed
    VigGridError naming the stage and grid, not a mid-tick reshape
    crash."""
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=16, patch=4, embed_dims=(16, 16), depths=(1, 1),
        num_classes=3, k=3, digc_impl="blocked",
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    with pytest.raises(VigGridError, match=r"stage0: grid 5.*downsample"):
        VigServeEngine(cfg, params, autotune=False,
                       image_sizes=(16, 20))
    pooled = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=16, patch=4, embed_dims=(16,), depths=(2,),
        reduce_ratios=(4,), num_classes=3, k=3, digc_impl="blocked",
    )
    pooled_params = init_params(vig.vig_param_spec(pooled),
                                jax.random.PRNGKey(0))
    with pytest.raises(VigGridError, match=r"stage0: grid 6.*reduce"):
        VigServeEngine(pooled, pooled_params, autotune=False,
                       image_sizes=(24,))


def test_submit_typed_errors_on_the_lattice():
    cfg, params = _tiny_vig("blocked")
    eng = VigServeEngine(cfg, params, digc_impl="blocked", autotune=False,
                         image_sizes=(16, 24))
    with pytest.raises(ValueError, match="non-square"):
        eng.submit(VigRequest(uid=0,
                              image=np.zeros((16, 24, 3), np.float32)))
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(VigRequest(uid=1,
                              image=np.zeros((32, 32, 3), np.float32)))
    with pytest.raises(ValueError, match="divisible"):
        eng.submit(VigRequest(uid=2,
                              image=np.zeros((18, 18, 3), np.float32)))
    # a pooled pyramid cannot take pad nodes: typed refusal at submit
    pooled = cfg.replace(reduce_ratios=(2,))
    pooled_params = init_params(vig.vig_param_spec(pooled),
                                jax.random.PRNGKey(0))
    eng2 = VigServeEngine(pooled, pooled_params, autotune=False,
                          image_sizes=(16, 32))
    with pytest.raises(ValueError, match="pad nodes"):
        eng2.submit(VigRequest(uid=3,
                               image=np.zeros((24, 24, 3), np.float32)))
    # without image_sizes= the legacy exact-shape contract holds
    legacy = VigServeEngine(cfg, params, digc_impl="blocked",
                            autotune=False)
    with pytest.raises(ValueError, match="shape"):
        legacy.submit(VigRequest(uid=4,
                                 image=np.zeros((8, 8, 3), np.float32)))


# ---------------------------------------------------------------------------
# Mesh divisibility: ticks pad to the batch axis instead of refusing


def test_mesh_tick_padding_serves_nondividing_bucket():
    """buckets=(3,) on a 2-device batch axis used to be refused at
    construction; now the tick pads to width 4 (replicating lane 0)
    and every row still matches its B=1 replay. Buckets smaller than
    the axis stay a typed construction error."""
    out = run_snippet(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import DigcSpec
        from repro.models import vig
        from repro.models.module import init_params
        from repro.serve.engine import VigRequest, VigServeEngine

        assert jax.device_count() == 4
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("ring", "data"))
        cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
            image_size=16, patch=4, embed_dims=(16,), depths=(2,),
            num_classes=3, k=3, digc_impl="ring")
        params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))

        try:
            VigServeEngine(cfg, params, digc_impl="ring", autotune=False,
                           mesh=mesh, mesh_axis="ring",
                           mesh_batch_axis="data", buckets=(1, 3))
            raise SystemExit("small bucket accepted")
        except ValueError as e:
            assert "smaller than" in str(e), e

        eng = VigServeEngine(cfg, params, digc_impl="ring", autotune=False,
                             mesh=mesh, mesh_axis="ring",
                             mesh_batch_axis="data", buckets=(3,))
        assert eng._tick_width(3) == 4
        rng = np.random.default_rng(7)
        reqs = [VigRequest(uid=i,
                           image=rng.standard_normal((16, 16, 3))
                           .astype(np.float32), tenant=t)
                for i, t in enumerate("ABC")]
        for r in reqs:
            eng.submit(r)
        assert eng.step() == 3
        assert eng.last_bucket == 3

        spec = DigcSpec(impl="ring", mesh=mesh, axis_name="ring")
        fwd = jax.jit(lambda p, im, s: vig.vig_forward(
            p, im, cfg, digc_impl=spec, state=s))
        for r in reqs:
            st = vig.init_vig_state(cfg, 1, spec, per_slot=True,
                                    mesh=mesh, mesh_axis="ring")
            ref, _ = fwd(params, jnp.asarray(r.image)[None], st)
            np.testing.assert_allclose(r.logits, np.asarray(ref)[0],
                                       rtol=1e-5, atol=1e-5)
        print("MESH-PAD-OK")
        """,
        devices=4,
    ).stdout
    assert "MESH-PAD-OK" in out


# ---------------------------------------------------------------------------
# tune_reuse across N-buckets: per-N grouping + tau scaling


def test_tune_reuse_mixed_n_groups_and_scales_tau():
    from repro.core.tuner import scale_tau, tune_reuse

    assert scale_tau(0.0, 400, 100) == 0.0  # tau=0 stays exact
    assert scale_tau(0.1, 400, 100) == pytest.approx(0.2)
    assert scale_tau(0.1, 400, 400) == pytest.approx(0.1)

    rng = np.random.default_rng(4)
    h16 = rng.standard_normal((1, 16, 8)).astype(np.float32)
    h36 = rng.standard_normal((1, 36, 8)).astype(np.float32)
    # static streams at two N under ONE layer key: per-(key, N) grouping
    # must give each its own cache stream (interleaved N would otherwise
    # cross-compare snapshots and never reuse)
    ticks = [[("stage0", h16, None), ("stage0", h36, None)]
             for _ in range(4)]
    spec = DigcSpec(impl="blocked", k=3)
    tuned, results = tune_reuse(ticks, spec=spec, policy="layer",
                                taus=(0.05,), max_stale=8)
    assert tuned.reuse == "layer"
    static = [r for r in results if r.drift_tau == 0.05][0]
    assert static.reuse_frac > 0.5  # both streams reuse after warmup
    assert static.n is None  # mixed-N trace: no single node count
    # tau=0 bit-identity per bucket: nothing reuses, spec unchanged
    tuned0, results0 = tune_reuse(ticks, spec=spec, policy="layer",
                                  taus=(0.0,))
    assert results0[0].reuse_frac == 0.0
    assert tuned0.reuse is None or results0[0].admitted
    # single-N trace records its node count
    _, r16 = tune_reuse([[("stage0", h16, None)]] * 3, spec=spec,
                        policy="layer", taus=(0.05,))
    assert r16[0].n == 16
