"""Mesh-native multi-tenant serving (DESIGN.md §10).

The acceptance contract for sharded serving: on a 4-forced-host-device
mesh, a bucketed ragged multi-tenant trace through ``VigServeEngine``
(ring-sharded co-node construction, per-slot ``DigcState`` rows placed
with ``PartitionSpec``s) is **bit-identical** (CPU) to the per-tenant
B=1 replay of each tenant's own history, while compiling at most
|bucket set| programs (asserted through the ``compile_count`` /
``on_compile`` hook) — and the construction indices match the
single-device blocked tier bitwise.

Runs in a subprocess so the forced-device-count flag never leaks into
the main test process; tiny shapes keep it inside the tier-1 budget.
"""

from _subproc import run_snippet


def _run(snippet: str, *, devices: int = 4, timeout: int = 600) -> str:
    return run_snippet(snippet, devices=devices, timeout=timeout).stdout


def test_mesh_native_engine_bucketed_trace_matches_b1_replay():
    """Ragged trace, 3 tenants, buckets {1,2} on a 4-device ring:
    every request bit-matches its tenant's B=1 replay, <= 2 programs
    compile, the slot state lives on the mesh, and the construction is
    bitwise the single-device blocked result."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import DigcSpec, digc
        from repro.models import vig
        from repro.models.module import init_params
        from repro.serve.engine import VigRequest, VigServeEngine

        assert jax.device_count() == 4
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("ring",))
        cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
            image_size=16, patch=4, embed_dims=(16,), depths=(2,),
            num_classes=3, k=3, digc_impl="ring")
        params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        img = lambda: rng.standard_normal((16, 16, 3)).astype(np.float32)

        compiled = []
        eng = VigServeEngine(cfg, params, digc_impl="ring", autotune=False,
                             buckets=(1, 2), mesh=mesh, mesh_axis="ring",
                             on_compile=compiled.append)
        waves = [["A"], ["B", "C"], ["A", "B"], ["C"], ["B", "A"]]
        per_t = {}
        uid = 0
        for w in waves:
            for t in w:
                r = VigRequest(uid=uid, image=img(), tenant=t)
                uid += 1
                per_t.setdefault(t, []).append(r)
                eng.submit(r)
            assert eng.step() == len(w)
            assert eng.last_bucket == eng.bucket_for(len(w))
        # <= |bucket set| compiled programs on the whole ragged trace
        assert eng.compile_count <= 2, eng.compile_count
        assert sorted(set(compiled)) == sorted(eng._programs)
        # the canonical slot state lives on the mesh
        ent = eng._slot_state.entries["stage0"]
        assert ent.row_step.sharding.mesh.shape == {"ring": 4}

        # per-tenant B=1 replay (same mesh-native spec): bit-identical
        spec = DigcSpec(impl="ring", mesh=mesh, axis_name="ring")
        def replay(reqs):
            state = vig.init_vig_state(cfg, 1, spec, per_slot=True,
                                       mesh=mesh, mesh_axis="ring")
            fwd = jax.jit(lambda p, im, s: vig.vig_forward(
                p, im, cfg, digc_impl=spec, state=s))
            outs = []
            for r in reqs:
                lg, state = fwd(params, jnp.asarray(r.image)[None], state)
                outs.append(np.asarray(lg)[0])
            return outs
        for t, reqs in per_t.items():
            for r, ref in zip(reqs, replay(reqs)):
                assert r.done
                assert np.array_equal(r.logits, ref), t
        # single-device exact-tier cross-check (fp-tolerant: a jitted
        # B>1 batch reassociates matmul sums vs the B=1 program)
        base = jax.jit(lambda p, im: vig.vig_forward(p, im, cfg,
                                                     digc_impl="blocked"))
        for t, reqs in per_t.items():
            for r in reqs:
                ref = np.asarray(base(params, jnp.asarray(r.image)[None]))[0]
                np.testing.assert_allclose(r.logits, ref, rtol=1e-5,
                                           atol=1e-5)
        # and the construction itself is bitwise the blocked result
        x = jnp.asarray(rng.standard_normal((2, 16, 16)), jnp.float32)
        assert bool(jnp.all(
            digc(x, k=3, impl="blocked")
            == digc(x, spec=DigcSpec(impl="ring", k=3, mesh=mesh,
                                     axis_name="ring"))))
        print("SHARDED_ENGINE_OK")
        """
    )
    assert "SHARDED_ENGINE_OK" in out


def test_mesh_native_engine_parking_survives_slot_churn():
    """LRU state parking on the sharded path: a tenant evicted from a
    2-slot mesh-native engine re-admits WARM (bit-matches its full-
    history B=1 replay) because its sharded state rows round-tripped
    through the host-side parking tier; with park_capacity=0 the same
    churn re-admits cold."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import DigcSpec
        from repro.models import vig
        from repro.models.module import init_params
        from repro.serve.engine import VigRequest, VigServeEngine

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("ring",))
        cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
            image_size=16, patch=4, embed_dims=(16,), depths=(2,),
            num_classes=3, k=3, digc_impl="ring")
        params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
        rng = np.random.default_rng(13)
        mk = lambda t: VigRequest(uid=int(rng.integers(1 << 30)),
                                  image=rng.standard_normal(
                                      (16, 16, 3)).astype(np.float32),
                                  tenant=t)
        spec = DigcSpec(impl="ring", mesh=mesh, axis_name="ring")
        def replay(reqs):
            state = vig.init_vig_state(cfg, 1, spec, per_slot=True,
                                       mesh=mesh, mesh_axis="ring")
            fwd = jax.jit(lambda p, im, s: vig.vig_forward(
                p, im, cfg, digc_impl=spec, state=s))
            outs = []
            for r in reqs:
                lg, state = fwd(params, jnp.asarray(r.image)[None], state)
                outs.append(np.asarray(lg)[0])
            return outs

        eng = VigServeEngine(cfg, params, digc_impl="ring", autotune=False,
                             buckets=(1, 2), mesh=mesh, mesh_axis="ring")
        a1, b1 = mk("A"), mk("B")
        eng.submit(a1), eng.submit(b1); eng.step()
        c1 = mk("C"); eng.submit(c1); eng.step()   # evicts + parks LRU
        evicted = "A" if "A" not in eng.slot_tenant else "B"
        assert evicted in eng._parked
        e2 = mk(evicted); eng.submit(e2); eng.step()  # restores warm
        assert eng.park_hits == 1 and eng.last_restores
        hist = {"A": [a1], "B": [b1]}[evicted] + [e2]
        refs = replay(hist)
        assert np.array_equal(e2.logits, refs[-1])
        # row counters continued from the parked copy (2 blocks/request)
        slot = eng._tenant_slot[evicted]
        assert eng.slot_row_steps()["stage0"][slot] == 2 * sum(cfg.depths)
        print("SHARDED_PARKING_OK")
        """
    )
    assert "SHARDED_PARKING_OK" in out


def test_mesh_native_engine_batched_reset_keeps_placement():
    """A tick of anonymous lanes on a 4-device ring engine cold-resets
    them in one compiled call: each per-row buffer keeps its
    NamedSharding, the reset rows are zero and the others untouched,
    and every answer bit-matches a fresh-state B=1 replay (a row left
    warm by its previous occupant would not)."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import DigcSpec
        from repro.models import vig
        from repro.models.module import init_params
        from repro.serve.engine import VigRequest, VigServeEngine
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4,), ("ring",))
        cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
            image_size=16, patch=4, embed_dims=(16,), depths=(2,),
            num_classes=3, k=3, digc_impl="ring")
        params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
        rng = np.random.default_rng(17)
        eng = VigServeEngine(cfg, params, digc_impl="ring", autotune=False,
                             buckets=(1, 2), mesh=mesh,
                             mesh_axis="ring")
        spec = DigcSpec(impl="ring", mesh=mesh, axis_name="ring")
        fwd = jax.jit(lambda p, im, s: vig.vig_forward(
            p, im, cfg, digc_impl=spec, state=s))
        uid = 0
        for t, n in enumerate((2, 2)):
            eng.tracer.recording = t == 1
            reqs = [VigRequest(uid=uid + i, image=rng.standard_normal(
                        (16, 16, 3)).astype(np.float32)) for i in range(n)]
            uid += n
            for r in reqs:
                eng.submit(r)
            assert eng.step() == n
            for r in reqs:
                cold = vig.init_vig_state(cfg, 1, spec, per_slot=True,
                                          mesh=mesh, mesh_axis="ring")
                ref, _ = fwd(params, jnp.asarray(r.image)[None], cold)
                assert np.array_equal(r.logits, np.asarray(ref)[0])
        counters = eng.tracer.totals()["counters"]
        assert (counters["reset_calls"], counters["reset_rows"]) == (1, 2)
        # each slot's counters count one request's blocks: both were cold
        assert eng.slot_row_steps()["stage0"] == [sum(cfg.depths)] * 2

        st = eng.slot_state()
        reset = st.reset_rows([0])
        for key, e in st.entries.items():
            for f, v in e.row_buffers().items():
                w = getattr(reset.entries[key], f)
                assert isinstance(v.sharding, jax.sharding.NamedSharding)
                assert w.sharding == v.sharding, (key, f)
                host, got = np.asarray(v), np.asarray(w)
                assert not got[0].any() and host[1].any()
                assert np.array_equal(got[1], host[1])
        print("SHARDED_RESET_OK")
        """
    )
    assert "SHARDED_RESET_OK" in out
