"""Functional DIGC state (core/state.py): pytree round-trips through
jitted forwards, runtime-gated warm starts, donation, and parity of the
jitted forward with the un-jitted one."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import DigcSpec, digc
from repro.core.state import DigcState, DigcStateEntry, state_entry
from repro.launch.mesh import make_mesh


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


# ---------------------------------------------------------------------------
# DigcState as a pytree


def test_state_is_a_pytree_and_functional():
    st = DigcState.init({
        "a": state_entry(centroids_shape=(1, 4, 8)),
        "b": state_entry(),
    })
    leaves = jax.tree_util.tree_leaves(st)
    assert len(leaves) == 3  # a.step, a.centroids, b.step
    st2 = st.set("b", st.entries["b"].bump())
    assert st.steps() == {"a": 0, "b": 0}  # original untouched
    assert st2.steps() == {"a": 0, "b": 1}
    assert st.get("missing") is None and st.get(None) is None


def test_state_entry_warm_flag():
    e = state_entry(centroids_shape=(1, 2, 3))
    assert not bool(e.warm)
    assert bool(e.bump().warm)


def test_state_entry_row_counters_and_bump():
    """Per-row counters (multi-tenant serving): allocated via rows=,
    advanced by bump alongside the scalar step, row_warm per row."""
    e = state_entry(centroids_shape=(3, 2, 4), rows=3)
    assert e.row_step.shape == (3,) and e.row_warm is not None
    assert not bool(jnp.any(e.row_warm))
    e2 = e.bump()
    assert int(e2.step) == 1
    np.testing.assert_array_equal(np.asarray(e2.row_step), [1, 1, 1])
    assert bool(jnp.all(e2.row_warm))
    # legacy entries carry no row counters: pytree structure unchanged
    assert state_entry().row_step is None
    assert state_entry().row_warm is None
    assert len(jax.tree_util.tree_leaves(state_entry())) == 1


def test_state_row_lifecycle_take_put_reset():
    """The serving slot lifecycle: gather slot rows into a bucket batch
    (repeats = padding lanes), scatter live lanes back (padding lanes
    dropped), cold-reset a reassigned slot."""
    st = DigcState.init({
        "s": state_entry(centroids_shape=(4, 2, 3), sq_y_shape=(4, 5),
                         rows=4),
    })
    # make rows distinguishable: row r's centroids are all r+1
    marked = DigcStateEntry(
        step=jnp.int32(7),
        centroids=jnp.arange(1, 5, dtype=jnp.float32)[:, None, None]
        * jnp.ones((4, 2, 3)),
        sq_y=jnp.arange(1, 5, dtype=jnp.float32)[:, None] * jnp.ones((4, 5)),
        row_step=jnp.asarray([3, 0, 2, 1], jnp.int32),
    )
    st = st.set("s", marked)
    # bucket of 4 over lanes [2, 0] + padding replicating lane 0 (slot 2)
    bucket = st.take_rows([2, 0, 2, 2])
    b = bucket.entries["s"]
    np.testing.assert_array_equal(np.asarray(b.row_step), [2, 3, 2, 2])
    np.testing.assert_array_equal(np.asarray(b.centroids[1]),
                                  np.asarray(marked.centroids[0]))
    assert int(b.step) == 7
    # the forward bumps; pretend it also rewrote centroids
    served = bucket.set("s", b.bump(centroids=b.centroids + 100.0))
    back = st.put_rows(served, [2, 0])
    a = back.entries["s"]
    # live lanes landed at their slots
    np.testing.assert_array_equal(np.asarray(a.row_step), [4, 0, 3, 1])
    np.testing.assert_allclose(np.asarray(a.centroids[2]),
                               np.asarray(marked.centroids[2]) + 100.0)
    np.testing.assert_allclose(np.asarray(a.centroids[0]),
                               np.asarray(marked.centroids[0]) + 100.0)
    # padding lanes (src rows 2, 3) dropped: untouched slots identical
    np.testing.assert_array_equal(np.asarray(a.centroids[1]),
                                  np.asarray(marked.centroids[1]))
    np.testing.assert_array_equal(np.asarray(a.centroids[3]),
                                  np.asarray(marked.centroids[3]))
    np.testing.assert_array_equal(np.asarray(a.sq_y[3]),
                                  np.asarray(marked.sq_y[3]))
    assert int(a.step) == 8  # scalar counter taken from the served entry
    # reset: slot 0 reassigned to a new tenant -> cold zero rows
    reset = back.reset_rows([0])
    r = reset.entries["s"]
    np.testing.assert_array_equal(np.asarray(r.row_step), [0, 0, 3, 1])
    np.testing.assert_array_equal(np.asarray(r.centroids[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(r.sq_y[0]), 0.0)
    np.testing.assert_allclose(np.asarray(r.centroids[2]),
                               np.asarray(a.centroids[2]))
    assert back.row_steps() == {"s": [4, 0, 3, 1]}


def test_state_take_put_rows_are_one_compiled_call_each():
    """``DigcState.take_rows`` / ``put_rows`` over a state of several
    entries gather and scatter every entry's rows (padding lanes
    dropped on the way back) in one compiled call each, compiled once
    per number of rows: a new set of rows of the same count reuses the
    program."""
    from repro.core import state as state_mod

    rng = np.random.default_rng(3)
    entries = {
        "a": state_entry(centroids_shape=(6, 2, 3), sq_y_shape=(6, 5),
                         rows=6),
        "b": state_entry(sq_y_shape=(6, 4), rows=6),
    }
    entries = {k: dataclasses.replace(
        e, step=jnp.int32(5),
        **{f: _rand(rng, *v.shape).astype(v.dtype)
           for f, v in e.row_buffers().items()})
        for k, e in entries.items()}
    st = DigcState.init(entries)
    takes0 = state_mod._take_rows._cache_size()
    puts0 = state_mod._put_rows._cache_size()
    for rows, lanes in (([4, 1, 4, 4], [4, 1]), ([0, 5, 0, 0], [0, 5])):
        bucket = st.take_rows(rows)
        served = DigcState.init({
            k: e.bump(**{f: v + 1 for f, v in e.row_buffers().items()
                         if f != "row_step"})
            for k, e in bucket.entries.items()})
        back = st.put_rows(served, lanes)
        for k, e in st.entries.items():
            got_take = bucket.entries[k].row_buffers()
            got_put = back.entries[k].row_buffers()
            assert int(bucket.entries[k].step) == 5
            assert int(back.entries[k].step) == 6
            for f, v in e.row_buffers().items():
                v = np.asarray(v)
                np.testing.assert_array_equal(np.asarray(got_take[f]),
                                              v[rows])
                want = v.copy()
                want[lanes] = np.asarray(
                    served.entries[k].row_buffers()[f])[:len(lanes)]
                np.testing.assert_array_equal(np.asarray(got_put[f]), want)
    assert state_mod._take_rows._cache_size() == takes0 + 1
    assert state_mod._put_rows._cache_size() == puts0 + 1


# ---------------------------------------------------------------------------
# digc(..., state=) — the functional form


def test_digc_state_passthrough_for_stateless_builders():
    """A builder without state support (reference) must return the
    state unchanged — same object structure, same steps."""
    rng = np.random.default_rng(0)
    x = _rand(rng, 2, 20, 6)
    st = DigcState.init({"k0": state_entry()})
    idx, new_st = digc(x, k=3, impl="reference", state=st, state_key="k0")
    assert new_st.steps() == {"k0": 0}
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(digc(x, k=3, impl="reference"))
    )


def test_digc_state_missing_entry_passthrough():
    """state without an entry for the key: stateless compute, state
    passes through (entries are init-time only — structure is the
    compiled program's contract)."""
    rng = np.random.default_rng(1)
    x = _rand(rng, 2, 20, 6)
    st = DigcState.init({})
    idx, new_st = digc(x, k=3, impl="blocked", state=st, state_key="k0")
    assert len(new_st) == 0
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(digc(x, k=3, impl="blocked"))
    )


def test_blocked_gallery_norms_jit_exact_and_counted():
    """Frozen-gallery norms through a jitted digc: exact indices on
    every call, sq_y filled on the cold call, step counts requests."""
    rng = np.random.default_rng(3)
    x, y = _rand(rng, 2, 40, 8), _rand(rng, 2, 64, 8)
    i_ref = digc(x, y, k=5, impl="reference")
    st = DigcState.init({"gal": state_entry(sq_y_shape=(2, 64))})
    fn = jax.jit(
        lambda a, by, s: digc(a, by, k=5, impl="blocked",
                              state=s, state_key="gal")
    )
    i1, st = fn(x, y, st)
    i2, st = fn(x, y, st)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(i_ref))
    assert st.steps() == {"gal": 2}
    np.testing.assert_allclose(
        np.asarray(st.entries["gal"].sq_y),
        np.asarray(jnp.sum(y * y, -1)), rtol=1e-6,
    )


def test_blocked_gallery_norms_warm_branch_engages():
    """Proof the warm branch actually reads the carried norms: a warm
    entry seeded with deliberately wrong sq_y must change the result
    (the cold path would recompute and hide the reuse)."""
    rng = np.random.default_rng(4)
    x, y = _rand(rng, 1, 24, 4), _rand(rng, 1, 32, 4)
    wrong = jnp.linspace(100.0, 1000.0, 32)[None, :]
    warm_entry = DigcStateEntry(
        step=jnp.ones((), jnp.int32), sq_y=wrong
    )
    _, d_warm, _ = digc(
        x, y, k=3, impl="blocked", return_dists=True,
        state=DigcState.init({"g": warm_entry}), state_key="g",
    )
    _, d_true = digc(x, y, k=3, impl="blocked", return_dists=True)
    assert not np.allclose(np.asarray(d_warm), np.asarray(d_true))


def test_cluster_state_jit_warm_start_recall_and_drift():
    """Cluster tier through jit: full probe + ample capacity stays
    exact cold AND warm; centroids drift when the features drift."""
    from repro.core.strategies import recall_vs_exact

    rng = np.random.default_rng(5)
    x1 = _rand(rng, 2, 128, 16)
    x2 = x1 + 0.05 * _rand(rng, 2, 128, 16)
    spec = DigcSpec(impl="cluster", k=4, n_clusters=8, n_probe=8,
                    capacity_factor=8.0)
    st = DigcState.init({"s0": state_entry(centroids_shape=(2, 8, 16))})
    fn = jax.jit(lambda a, s: digc(a, spec=spec, state=s, state_key="s0"))
    i_cold, st1 = fn(x1, st)
    c1 = np.asarray(st1.entries["s0"].centroids)
    assert st1.steps() == {"s0": 1}
    assert not np.allclose(c1, 0.0)  # cold call wrote real centroids
    i_warm, st2 = fn(x2, st1)
    c2 = np.asarray(st2.entries["s0"].centroids)
    assert st2.steps() == {"s0": 2}
    assert not np.array_equal(c1, c2)  # warm start tracked the drift
    assert recall_vs_exact(x1, x1, i_cold, 4) == 1.0
    assert recall_vs_exact(x2, x2, i_warm, 4) == 1.0


def test_cluster_rowwise_warm_gate_matches_b1_replay():
    """Per-row warm gating (multi-tenant batches): a batch mixing a
    warm row with a freshly reset (cold) row must give each row exactly
    what a B=1 call with that row's own state history gives — warm rows
    the 2-Lloyd refinement, cold rows the full cold build."""
    rng = np.random.default_rng(40)
    x1 = _rand(rng, 3, 64, 8)
    x2 = x1 + 0.05 * _rand(rng, 3, 64, 8)
    spec = DigcSpec(impl="cluster", k=4, n_clusters=4, n_probe=4,
                    capacity_factor=8.0)
    st = DigcState.init({
        "s": state_entry(centroids_shape=(3, 4, 8), rows=3)
    })
    fn = jax.jit(lambda a, s: digc(a, spec=spec, state=s, state_key="s"))
    _, st1 = fn(x1, st)
    assert st1.row_steps() == {"s": [1, 1, 1]}
    # row 2's tenant evicted: cold reset; rows 0/1 stay warm
    i_mixed, st2 = fn(x2, st1.reset_rows([2]))
    assert st2.row_steps() == {"s": [2, 2, 1]}

    def replay(row, warm):
        s = DigcState.init({
            "s": state_entry(centroids_shape=(1, 4, 8), rows=1)
        })
        f1 = jax.jit(lambda a, sv: digc(a, spec=spec, state=sv,
                                        state_key="s"))
        if warm:
            _, s = f1(x1[row:row + 1], s)
        idx, _ = f1(x2[row:row + 1], s)
        return np.asarray(idx)[0]

    np.testing.assert_array_equal(np.asarray(i_mixed[0]), replay(0, True))
    np.testing.assert_array_equal(np.asarray(i_mixed[1]), replay(1, True))
    np.testing.assert_array_equal(np.asarray(i_mixed[2]), replay(2, False))


def test_blocked_rowwise_gallery_norms_exact_after_reset():
    """Blocked frozen-gallery norms with per-row counters stay exact
    through resets (warm rows read carried norms, reset rows
    recompute)."""
    rng = np.random.default_rng(41)
    x, y = _rand(rng, 2, 20, 6), _rand(rng, 2, 32, 6)
    i_ref = digc(x, y, k=3, impl="reference")
    st = DigcState.init({"g": state_entry(sq_y_shape=(2, 32), rows=2)})
    fn = jax.jit(lambda a, by, s: digc(a, by, k=3, impl="blocked",
                                       state=s, state_key="g"))
    i1, st = fn(x, y, st)
    i2, st = fn(x, y, st.reset_rows([0]))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(i_ref))
    assert st.row_steps() == {"g": [1, 2]}
    np.testing.assert_allclose(np.asarray(st.entries["g"].sq_y),
                               np.asarray(jnp.sum(y * y, -1)), rtol=1e-6)


def test_init_vig_state_per_slot_rows():
    """per_slot=True allocates (B,) row counters on every stage entry
    (the multi-tenant serving layout)."""
    from repro.models import vig

    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=32, embed_dims=(16,), depths=(2,), num_classes=3, k=3,
    )
    st = vig.init_vig_state(cfg, 4, "cluster", per_slot=True)
    e = st.entries["stage0"]
    assert e.row_step.shape == (4,) and e.centroids is not None
    assert st.row_steps() == {"stage0": [0, 0, 0, 0]}
    # default stays the single-tenant layout (no row counters)
    st_flat = vig.init_vig_state(cfg, 4, "cluster")
    assert st_flat.entries["stage0"].row_step is None


def test_cluster_state_shape_mismatch_is_cold_and_safe():
    """A stale-shaped centroid buffer (workload changed) must not be
    read or written — cold build, counter still advances."""
    rng = np.random.default_rng(6)
    x = _rand(rng, 2, 128, 16)
    spec = DigcSpec(impl="cluster", k=4, n_clusters=8, n_probe=8,
                    capacity_factor=8.0)
    stale = state_entry(centroids_shape=(2, 5, 16))  # wrong C
    st = DigcState.init({"s0": stale})
    idx, st1 = digc(x, spec=spec, state=st, state_key="s0")
    assert st1.steps() == {"s0": 1}
    assert st1.entries["s0"].centroids.shape == (2, 5, 16)  # untouched
    np.testing.assert_array_equal(
        np.asarray(st1.entries["s0"].centroids), np.zeros((2, 5, 16))
    )


# ---------------------------------------------------------------------------
# vig_forward round-trip


def _tiny_vig(impl):
    from repro.models import vig
    from repro.models.module import init_params

    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=32, embed_dims=(16,), depths=(2,), num_classes=3, k=3,
        digc_impl=impl,
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    return vig, cfg, params, imgs


def test_vig_forward_state_roundtrip_jitted_cluster():
    """DigcState through a jitted vig_forward: warm start engages on
    call 2 (centroids move under feature drift), steps count blocks x
    requests, logits stay finite."""
    vig, cfg, params, imgs = _tiny_vig("cluster")
    st = vig.init_vig_state(cfg, 2, "cluster")
    assert st.entries["stage0"].centroids is not None
    fwd = jax.jit(
        lambda p, im, s: vig.vig_forward(p, im, cfg, digc_impl="cluster",
                                         state=s)
    )
    l1, st1 = fwd(params, imgs, st)
    c1 = np.asarray(st1.entries["stage0"].centroids)
    imgs2 = imgs + 0.1 * jax.random.normal(jax.random.PRNGKey(2), imgs.shape)
    l2, st2 = fwd(params, imgs2, st1)
    c2 = np.asarray(st2.entries["stage0"].centroids)
    assert st1.steps() == {"stage0": 2}  # 2 blocks
    assert st2.steps() == {"stage0": 4}
    assert not np.allclose(c1, 0.0) and not np.array_equal(c1, c2)
    assert bool(jnp.all(jnp.isfinite(l1))) and bool(jnp.all(jnp.isfinite(l2)))


def test_vig_forward_state_exact_tier_indices_unchanged():
    """For the exact blocked tier the state must be observationally
    inert: jitted state-threaded logits == stateless logits."""
    vig, cfg, params, imgs = _tiny_vig("blocked")
    st = vig.init_vig_state(cfg, 2, "blocked")
    fwd = jax.jit(
        lambda p, im, s: vig.vig_forward(p, im, cfg, digc_impl="blocked",
                                         state=s)
    )
    l1, st1 = fwd(params, imgs, st)
    l2, st2 = fwd(params, imgs, st1)
    base = jax.jit(
        lambda p, im: vig.vig_forward(p, im, cfg, digc_impl="blocked")
    )(params, imgs)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(base),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l2), np.asarray(base),
                               rtol=1e-5, atol=1e-5)
    # self-graph stages carry no norm buffers, only counters
    assert st2.steps() == {"stage0": 4}


def test_vig_forward_state_donation():
    """The serving pattern: state donated into the jitted forward. The
    donated input must be consumed (non-CPU backends) and the carried
    state must keep working either way."""
    vig, cfg, params, imgs = _tiny_vig("cluster")
    st = vig.init_vig_state(cfg, 2, "cluster")
    fwd = jax.jit(
        lambda p, im, s: vig.vig_forward(p, im, cfg, digc_impl="cluster",
                                         state=s),
        donate_argnums=(2,),
    )
    import warnings

    with warnings.catch_warnings():
        # CPU ignores donation with a warning; that is fine here.
        warnings.simplefilter("ignore")
        l1, st1 = fwd(params, imgs, st)
        l2, st2 = fwd(params, imgs, st1)
    assert st2.steps() == {"stage0": 4}
    assert bool(jnp.all(jnp.isfinite(l2)))
    if jax.default_backend() != "cpu":
        assert st.entries["stage0"].centroids.is_deleted()


def test_vig_forward_state_matches_eager_cache_shim():
    """Jitted vs un-jitted functional forward on the cluster tier: same
    Lloyd schedule (cold 5 iters, warm 2), so the logits and the carried
    state agree request over request."""
    vig, cfg, params, imgs = _tiny_vig("cluster")
    fwd = jax.jit(lambda p, im, s: vig.vig_forward(
        p, im, cfg, digc_impl="cluster", state=s))
    st_j = st_e = vig.init_vig_state(cfg, 2, "cluster")
    for _ in range(2):
        l_jit, st_j = fwd(params, imgs, st_j)
        l_eager, st_e = vig.vig_forward(params, imgs, cfg,
                                        digc_impl="cluster", state=st_e)
        np.testing.assert_allclose(
            np.asarray(l_jit), np.asarray(l_eager), rtol=1e-4, atol=1e-4
        )
    assert st_j.steps() == st_e.steps() == {"stage0": 4}
    np.testing.assert_allclose(
        np.asarray(st_j.entries["stage0"].centroids),
        np.asarray(st_e.entries["stage0"].centroids), rtol=1e-4, atol=1e-4,
    )


def test_jitted_state_forward_tracks_new_images():
    """No stale constant across requests: one compiled forward with
    state, fed two different image batches, equals the un-jitted
    forward given the same state each time — nothing from the first
    call is baked into the program."""
    vig, cfg, params, imgs1 = _tiny_vig("cluster")
    imgs2 = jax.random.normal(jax.random.PRNGKey(2), imgs1.shape)
    traces = []

    def forward(p, im, s):
        traces.append(1)
        return vig.vig_forward(p, im, cfg, digc_impl="cluster", state=s)

    fwd = jax.jit(forward)
    st = vig.init_vig_state(cfg, 2, "cluster")
    outs = []
    for imgs in (imgs1, imgs2):
        l_eager, _ = vig.vig_forward(params, imgs, cfg,
                                     digc_impl="cluster", state=st)
        l_jit, st = fwd(params, imgs, st)
        np.testing.assert_allclose(
            np.asarray(l_jit), np.asarray(l_eager), rtol=1e-4, atol=1e-4
        )
        outs.append(np.asarray(l_jit))
    assert not np.allclose(outs[0], outs[1])
    assert len(traces) == 1  # one program served both requests


def test_init_vig_state_pyramid_shapes():
    """Pyramid models get one entry per stage; cluster stages size
    their centroid buffers off the stage's pooled co-node count."""
    from repro.core.strategies import default_cluster_params
    from repro.models import vig

    cfg = vig.VIG_VARIANTS["vig_ti_pyr"].replace(
        image_size=32, embed_dims=(8, 12, 16, 24), depths=(1, 1, 1, 1),
        num_classes=3, k=3,
    )
    st = vig.init_vig_state(cfg, 4, "cluster")
    assert sorted(st.entries) == ["stage0", "stage1", "stage2", "stage3"]
    grid = cfg.base_grid
    for si in range(4):
        r = cfg.reduce_ratios[si]
        m = (grid // max(r, 1)) ** 2
        nc, _ = default_cluster_params(m, None, None)
        e = st.entries[f"stage{si}"]
        assert e.centroids.shape == (4, nc, cfg.embed_dims[si])
        if si < 3:
            grid //= 2
    # non-cluster impls: counters only
    st_b = vig.init_vig_state(cfg, 4, "blocked")
    assert all(e.centroids is None for e in st_b.entries.values())


# ---------------------------------------------------------------------------
# Sharding-aware allocation + row ops (DESIGN.md §10)


def test_state_entry_mesh_placement():
    """``state_entry(mesh=)`` places the buffers with PartitionSpecs:
    ``sq_y`` partitioned along the ring axis on its co-node dim, the
    counters and centroids replicated — and a co-node count that does
    not divide the axis falls back to replication (placement is a
    performance choice, never a semantic one)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh((1,), ("data",))
    e = state_entry(sq_y_shape=(2, 8), centroids_shape=(2, 3, 4), rows=2,
                    mesh=mesh)
    assert isinstance(e.sq_y.sharding, NamedSharding)
    assert e.sq_y.sharding.spec == P(None, "data")
    assert e.centroids.sharding.spec == P()
    assert e.row_step.sharding.spec == P()
    # step counter semantics unchanged
    assert int(e.step) == 0 and int(e.bump().step) == 1
    # a placement axis the mesh does not have is a named error, not a
    # KeyError deep in the divisibility check
    with pytest.raises(ValueError, match="not an axis"):
        state_entry(sq_y_shape=(1, 8), mesh=mesh, axis_name="ring")
    # (the ragged-M replicated fallback needs a >1-device axis to be
    # observable; asserted in test_ring's 4-device subprocess)


def test_state_row_ops_preserve_named_sharding():
    """take_rows / put_rows / reset_rows keep sharded entries on their
    mesh — an eager slot-lifecycle pass must not collapse a
    device-resident buffer onto the default device — and accept
    host-side (numpy) source rows, the parking round trip."""
    mesh = make_mesh((1,), ("data",))
    st = DigcState.init({
        "s": state_entry(sq_y_shape=(4, 8), centroids_shape=(4, 2, 3),
                         rows=4, mesh=mesh),
    })
    want = st.entries["s"].sq_y.sharding
    bucket = st.take_rows([2, 0, 2, 2])
    assert bucket.entries["s"].sq_y.sharding == want
    back = st.put_rows(bucket, [1, 3])
    assert back.entries["s"].sq_y.sharding == want
    assert back.entries["s"].centroids.sharding == st.entries["s"].centroids.sharding
    reset = back.reset_rows([0])
    assert reset.entries["s"].sq_y.sharding == want
    # parking round trip: host copies scatter back onto the mesh
    parked = jax.tree_util.tree_map(np.asarray, st.take_rows([1]))
    restored = st.put_rows(parked, [2])
    assert restored.entries["s"].sq_y.sharding == want


def test_init_vig_state_mesh_placement_and_spec_mesh_wins():
    """``init_vig_state(mesh=)`` places every stage entry; a stage spec
    that names its own mesh/axis wins over the argument."""
    from repro.core.builder import DigcSpec
    from repro.models import vig

    mesh = make_mesh((1,), ("data",))
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=16, patch=4, embed_dims=(16,), depths=(2,),
        num_classes=3, k=3,
    )
    st = vig.init_vig_state(cfg, 2, "cluster", per_slot=True, mesh=mesh)
    e = st.entries["stage0"]
    assert e.row_step.sharding.mesh.shape == {"data": 1}
    spec = DigcSpec(impl="ring", mesh=mesh, axis_name="data")
    st2 = vig.init_vig_state(cfg, 2, spec, per_slot=True)
    assert st2.entries["stage0"].row_step.sharding.mesh.shape == {"data": 1}
