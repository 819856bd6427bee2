"""Ring-DIGC (distributed GMM): exactness vs single-device reference,
and the functional-state contract (DESIGN.md §10).

The 8-device tests run in a subprocess so the XLA host-platform flag
never leaks into the main test process (which must see 1 device); the
4-device parity tests below do the same but at tiny shapes, so they
run fast enough for the tier-1 job. The fast tests ride a degenerate
1-device mesh in the main process.
"""

import numpy as np

from _subproc import run_snippet
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# Fast (1-device mesh, main process): batched parity + state contract


def test_ring_batched_parity_and_state_contract():
    """Batched ring == reference on a 1-device mesh, and the ring
    builder is a ``supports_state`` tier: a frozen-gallery entry
    (explicit co-nodes, matching sq_y shape) advances its counters and
    carries the co-node norms — the sharded analogue of the blocked
    tier's gallery hook."""
    import jax
    import jax.numpy as jnp

    from repro.core import DigcSpec, digc
    from repro.core.builder import get_builder
    from repro.core.state import DigcState, state_entry

    assert get_builder("ring").supports_state
    mesh = make_mesh((1,), ("data",))
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 48, 12), jnp.float32)
    y = jnp.asarray(rng.randn(2, 40, 12), jnp.float32)
    i_ref = digc(x, y, k=4, impl="reference")
    spec = DigcSpec(impl="ring", k=4, mesh=mesh)
    with mesh:
        i_ring = digc(x, y, spec=spec)
        st = DigcState.init({"ring0": state_entry(sq_y_shape=(2, 40),
                                                  rows=2)})
        i_cold, st1 = digc(x, y, spec=spec, state=st, state_key="ring0")
        i_warm, st2 = digc(x, y, spec=spec, state=st1, state_key="ring0")
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_ring))
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_cold))
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_warm))
    assert st1.steps() == {"ring0": 1} and st2.steps() == {"ring0": 2}
    assert st1.row_steps() == {"ring0": [1, 1]}
    # shared 2D gallery next to batched nodes (the frozen-gallery
    # spelling) broadcasts, as it did before the batched rewrite
    from repro.core.ring import ring_digc

    with mesh:
        i_shared = ring_digc(x, y[0], k=4, mesh=mesh)
    i_shared_ref = digc(x, jnp.broadcast_to(y[0][None], y.shape),
                        k=4, impl="reference")
    np.testing.assert_array_equal(np.asarray(i_shared),
                                  np.asarray(i_shared_ref))
    # the cold pass wrote the true gallery norms into the entry
    np.testing.assert_allclose(
        np.asarray(st1.entries["ring0"].sq_y),
        np.asarray(jnp.sum(y.astype(jnp.float32) ** 2, -1)),
        rtol=1e-6,
    )


def test_ring_state_entry_planned():
    """PR-4 pinned this as a strict xfail ("core/ring.py is outside the
    functional-state path"); the ROADMAP sharded-serving item landed, so
    it is now the live contract: ``digc(impl="ring", state=...)``
    advances a DigcState entry the same way the blocked tier carries
    its frozen-gallery norms (self-graph calls advance counters only —
    their co-nodes drift every call, so norms are never carried)."""
    import jax
    import jax.numpy as jnp

    from repro.core import DigcSpec, digc
    from repro.core.builder import get_builder
    from repro.core.state import DigcState, state_entry

    assert get_builder("ring").supports_state
    mesh = make_mesh((1,), ("data",))
    x = jnp.asarray(np.random.RandomState(6).randn(32, 8), jnp.float32)
    st = DigcState.init({"r": state_entry(sq_y_shape=(1, 32))})
    with mesh:
        _, new_st = digc(x, spec=DigcSpec(impl="ring", k=3, mesh=mesh),
                         state=st, state_key="r")
    assert new_st.steps() == {"r": 1}
    # self-graph: norms not carried (the gallery is this call's x)
    np.testing.assert_array_equal(
        np.asarray(new_st.entries["r"].sq_y), 0.0)


def test_ring_warm_gate_engages_stale_norms():
    """Proof the warm path actually *reads* the carried norms (not a
    silent recompute): poisoning one co-node's carried norm on a warm
    entry pushes that co-node out of every neighbor list."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core import DigcSpec, digc
    from repro.core.state import DigcState, state_entry

    mesh = make_mesh((1,), ("data",))
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(1, 24, 8), jnp.float32)
    y = jnp.asarray(rng.randn(1, 16, 8), jnp.float32)
    spec = DigcSpec(impl="ring", k=4, mesh=mesh)
    st = DigcState.init({"r": state_entry(sq_y_shape=(1, 16), rows=1)})
    i_ref, st1 = digc(x, y, spec=spec, state=st, state_key="r")
    victim = int(np.asarray(i_ref)[0, 0, 0])
    poisoned = dataclasses.replace(
        st1.entries["r"],
        sq_y=st1.entries["r"].sq_y.at[:, victim].add(1e9),
    )
    i_pois, _ = digc(x, y, spec=spec, state=st1.set("r", poisoned),
                     state_key="r")
    assert victim not in np.asarray(i_pois)
    # and a *cold* row ignores the poison entirely (per-row gate)
    cold = dataclasses.replace(
        poisoned, row_step=jnp.zeros((1,), jnp.int32))
    i_cold, _ = digc(x, y, spec=spec, state=st1.set("r", cold),
                     state_key="r")
    np.testing.assert_array_equal(np.asarray(i_cold), np.asarray(i_ref))


def test_ring_mesh_shape_in_workload_key():
    """Sharded workloads key separately in the tune cache: the mesh
    shape rides ``DigcSpec.mesh_shape()`` into ``workload_key`` and
    unsharded keys are unchanged (the committed cache stays valid)."""
    import jax

    from repro.core import DigcSpec, workload_key

    mesh = make_mesh((1,), ("data",))
    spec = DigcSpec(impl="ring", k=4, mesh=mesh)
    assert spec.mesh_shape() == (1,)
    assert DigcSpec(impl="blocked", k=4).mesh_shape() is None
    base = workload_key(1, 64, 64, 16, 4)
    assert workload_key(1, 64, 64, 16, 4, mesh_shape=(4,)) == base + ":mesh4"
    assert workload_key(1, 64, 64, 16, 4, mesh_shape=None) == base


# ---------------------------------------------------------------------------
# Multi-device subprocess tests


def _run(snippet: str, *, devices: int = 8, timeout: int = 600) -> str:
    return run_snippet(snippet, devices=devices, timeout=timeout).stdout


# -- fast 4-device parity (tiny shapes, tier-1) -----------------------------


def test_ring_4dev_parity_warm_cold_and_sharded_state():
    """One subprocess, 4 forced host devices, tiny shapes (fast on
    CPU): ring-sharded construction == single-device blocked bitwise;
    a frozen-gallery entry placed with a PartitionSpec stays 4-way
    sharded through a warm round-trip; warm == cold bitwise; a 2D
    (rows x ring) mesh shards the batch rows data-parallel."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import DigcSpec, digc
        from repro.core.state import DigcState, state_entry
        assert jax.device_count() == 4
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(2, 48, 12), jnp.float32)
        y = jnp.asarray(rng.randn(2, 40, 12), jnp.float32)
        i_blk = digc(x, y, k=4, impl="blocked")
        spec = DigcSpec(impl="ring", k=4, mesh=mesh)
        # stateless sharded == single-device blocked, bitwise
        assert bool(jnp.all(digc(x, y, spec=spec) == i_blk))
        # sharded frozen-gallery entry: cold -> warm, bitwise stable
        e = state_entry(sq_y_shape=(2, 40), rows=2, mesh=mesh)
        assert len(e.sq_y.addressable_shards) == 4
        # ragged co-node count: replicated fallback (placement is a
        # performance choice, never a semantic one)
        ragged = state_entry(sq_y_shape=(1, 7), mesh=mesh)
        assert ragged.sq_y.sharding.spec == P()
        st = DigcState.init({"r": e})
        i_cold, st1 = digc(x, y, spec=spec, state=st, state_key="r")
        assert len(st1.entries["r"].sq_y.addressable_shards) == 4
        i_warm, st2 = digc(x, y, spec=spec, state=st1, state_key="r")
        assert bool(jnp.all(i_cold == i_blk))
        assert bool(jnp.all(i_warm == i_blk))
        assert st2.steps() == {"r": 2}
        # mixed warm/cold rows (multi-tenant batch) still exact
        import dataclasses
        mixed = dataclasses.replace(
            st1.entries["r"], row_step=jnp.asarray([1, 0], jnp.int32))
        i_mix, _ = digc(x, y, spec=spec, state=st1.set("r", mixed),
                        state_key="r")
        assert bool(jnp.all(i_mix == i_blk))
        # 2D mesh: data-parallel batch rows x ring-sharded co-nodes
        mesh2 = make_mesh((2, 2), ("rows", "ring"))
        spec2 = DigcSpec(impl="ring", k=4, mesh=mesh2, axis_name="ring",
                         batch_axis="rows")
        assert bool(jnp.all(digc(x, y, spec=spec2) == i_blk))
        print("RING_4DEV_OK")
        """,
        devices=4,
    )
    assert "RING_4DEV_OK" in out


# -- 8-device exhaustive (fast tier since the CPU-platform pin) -------------


def test_ring_digc_exact():
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import digc
        from repro.core.ring import ring_digc
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        rng = np.random.RandomState(2)
        for (N, M, D, k, dil) in [(64, 64, 16, 4, 1), (120, 100, 32, 4, 2), (16, 24, 8, 2, 1)]:
            x = jnp.asarray(rng.randn(N, D), jnp.float32)
            y = jnp.asarray(rng.randn(M, D), jnp.float32)
            ir, dr = digc(x, y, k=k, dilation=dil, impl="reference", return_dists=True)
            with mesh:
                ig, dg = ring_digc(x, y, k=k, dilation=dil, mesh=mesh, return_dists=True)
            assert bool(jnp.all(ir == ig)), (N, M)
            assert bool(jnp.allclose(dr, dg, rtol=1e-5, atol=1e-4)), (N, M)
        print("RING_OK")
        """
    )
    assert "RING_OK" in out


def test_ring_digc_self_graph():
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import digc
        from repro.core.ring import ring_digc
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(80, 24), jnp.float32)
        ir = digc(x, k=5, impl="reference")
        with mesh:
            ig = ring_digc(x, k=5, mesh=mesh)
        assert bool(jnp.all(ir == ig))
        print("RING_SELF_OK")
        """
    )
    assert "RING_SELF_OK" in out


def test_ring_digc_batched_registry():
    """(B, N, D) through the registry == stacked per-image reference —
    one shard_map program for the whole batch (the per-image unroll is
    gone), state passing through jit with donation."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import DigcSpec, digc
        from repro.core.state import DigcState, state_entry
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(2, 64, 16), jnp.float32)
        ir = digc(x, k=4, impl="reference")
        spec = DigcSpec(impl="ring", k=4, mesh=mesh)
        with mesh:
            ig = digc(x, spec=spec)
        assert ig.shape == (2, 64, 4), ig.shape
        assert bool(jnp.all(ir == ig))
        # donated jit round-trip of a sharded frozen-gallery entry
        y = jnp.asarray(rng.randn(2, 64, 16), jnp.float32)
        iry = digc(x, y, k=4, impl="reference")
        st = DigcState.init({"r": state_entry(sq_y_shape=(2, 64), rows=2,
                                              mesh=mesh)})
        f = jax.jit(lambda a, b, s: digc(a, b, spec=spec, state=s,
                                         state_key="r"),
                    donate_argnums=(2,))
        i1, st1 = f(x, y, st)
        i2, st2 = f(x, y, st1)
        assert bool(jnp.all(i1 == iry)) and bool(jnp.all(i2 == iry))
        assert st2.steps() == {"r": 2}
        print("RING_BATCHED_OK")
        """
    )
    assert "RING_BATCHED_OK" in out
