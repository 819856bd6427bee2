"""Multi-tenant bucketed serving (DESIGN.md §9).

Three layers of proof that the slot/bucket/state lifecycle is sound:

* **Parity**: a ragged request trace through the bucketed
  ``VigServeEngine`` must match, per request, an unbatched B=1
  ``vig_forward`` replay of the same tenant's requests — for every
  tier, including after slot eviction + refill. Any cross-tenant state
  leak, padding-lane clobber, or per-row warm-gate bug breaks this.
* **Properties** (hypothesis, stubbed programs so no compiles): for
  arbitrary arrival sequences, (a) the chosen bucket is the smallest
  that fits the active slots, (b) padding lanes never mutate live
  ``DigcState`` rows, (c) compiled-program count stays ≤ the bucket-set
  size (asserted through the compile-counter hook).
* **LM engine regression**: ``ServeEngine``'s decode/prefill cache
  writes carry an explicit per-slot commit mask — mixed-length slots
  must decode exactly as if each were served alone, in ONE jitted call
  per tick (``decode_step`` takes the per-slot position vector; the
  call count is pinned).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.state import DigcState
from repro.launch.mesh import make_mesh
from repro.models import vig
from repro.models.module import init_params
from repro.serve.engine import VigRequest, VigServeEngine

TIERS = ("reference", "blocked", "pallas", "cluster", "axial")


def _tiny_vig(impl):
    """16x16 / patch 4 -> N=16 grid; cluster runs full-probe (exact)."""
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=16, patch=4, embed_dims=(16,), depths=(2,),
        num_classes=3, k=3, digc_impl=impl,
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _image(rng):
    return rng.standard_normal((16, 16, 3)).astype(np.float32)


def _replay_tenant(cfg, params, impl, reqs, *, state=None):
    """Unbatched B=1 stateful replay of one tenant's request stream.

    Returns (per-request logits, final state). ``state=None`` starts
    cold, matching a freshly admitted slot."""
    if state is None:
        state = vig.init_vig_state(cfg, 1, impl, per_slot=True)
    fwd = jax.jit(
        lambda p, im, s: vig.vig_forward(p, im, cfg, digc_impl=impl, state=s)
    )
    outs = []
    for r in reqs:
        logits, state = fwd(params, jnp.asarray(r.image)[None], state)
        outs.append(np.asarray(logits)[0])
    return outs, state


def test_cell_graphs_reproduce_a_served_tick():
    """``cell_graphs`` re-runs a tick as it was served (bucket 4, one
    lane-0 padding lane): its logits are the served ones, and every
    captured neighbour list is the tier's graph of the captured nodes
    at that block's k and dilation."""
    from repro.core import digc

    cfg, params = _tiny_vig("blocked")
    eng = VigServeEngine(cfg, params, digc_impl="blocked", autotune=False,
                         buckets=(1, 2, 4))
    rng = np.random.default_rng(0)
    imgs = [_image(rng) for _ in range(3)]
    reqs = [VigRequest(uid=i, image=im, tenant=t)
            for i, (t, im) in enumerate(zip("ABC", imgs))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    logits, graphs = eng.cell_graphs(imgs)
    np.testing.assert_allclose(logits, np.stack([r.logits for r in reqs]),
                               rtol=1e-6, atol=1e-6)
    rows = vig.count_digc_work(cfg)
    assert len(graphs) == len(rows)
    for (key, nodes, co_nodes, idx), row in zip(graphs, rows):
        assert key == "stage0" and co_nodes is None
        assert nodes.shape == (3, 16, 16) and idx.shape == (3, 16, row["k"])
        want = digc(jnp.asarray(nodes), impl="blocked", k=row["k"],
                    dilation=row["dilation"])
        np.testing.assert_array_equal(idx, np.asarray(want))


# ---------------------------------------------------------------------------
# Parity: bucketed multi-tenant trace == per-tenant unbatched replay


@pytest.mark.parametrize("impl", TIERS)
def test_bucketed_ragged_trace_matches_unbatched_replay(impl):
    """Tenants A/B/C interleave raggedly (tick sizes 1-3, buckets
    {1,2,4}); every request's logits must match the tenant's own B=1
    replay — warm state follows the tenant across bucket changes and
    never crosses tenants or padding lanes."""
    cfg, params = _tiny_vig(impl)
    eng = VigServeEngine(cfg, params, digc_impl=impl, autotune=False,
                         buckets=(1, 2, 4))
    rng = np.random.default_rng(7)
    waves = [["A"], ["B", "C"], ["A", "B"], ["C"], ["A", "B", "C"]]
    per_tenant: dict[str, list[VigRequest]] = {}
    uid = 0
    for wave in waves:
        for t in wave:
            req = VigRequest(uid=uid, image=_image(rng), tenant=t)
            per_tenant.setdefault(t, []).append(req)
            eng.submit(req)
            uid += 1
        served = eng.step()
        assert served == len(wave)
        # bucket policy: smallest bucket that fits the wave
        assert eng.last_bucket == eng.bucket_for(len(wave))
    for t, reqs in per_tenant.items():
        refs, _ = _replay_tenant(cfg, params, impl, reqs)
        for req, ref in zip(reqs, refs):
            assert req.done
            np.testing.assert_allclose(req.logits, ref, rtol=1e-5, atol=1e-5)
    # at most |bucket set| compiled programs for the whole ragged trace
    assert eng.compile_count <= 3
    assert set(eng.stats()["bucket_ticks"]) <= {1, 2, 4}


def test_bucketed_full_width_trace_1_to_8():
    """The acceptance trace shape: tick sizes 1-8 interleaved on the
    default bucket set {1,2,4,8}. Every request matches the stateless
    unbatched forward (exact tier), with at most 4 compiled programs."""
    impl = "blocked"
    cfg, params = _tiny_vig(impl)
    eng = VigServeEngine(cfg, params, digc_impl=impl, autotune=False)
    assert eng.buckets == (1, 2, 4, 8) and eng.slots == 8
    rng = np.random.default_rng(23)
    uid = 0
    all_reqs = []
    for w, size in enumerate((1, 3, 8, 2, 5, 4, 7, 6)):
        wave = [VigRequest(uid=uid + i, image=_image(rng),
                           tenant=(w + i) % 8) for i in range(size)]
        uid += size
        all_reqs.extend(wave)
        for r in wave:
            eng.submit(r)
        assert eng.step() == size
        assert eng.last_bucket == eng.bucket_for(size)
    base = jax.jit(lambda p, im: vig.vig_forward(p, im, cfg,
                                                 digc_impl=impl))
    for r in all_reqs:
        ref = np.asarray(base(params, jnp.asarray(r.image)[None]))[0]
        np.testing.assert_allclose(r.logits, ref, rtol=1e-5, atol=1e-5)
    assert eng.compile_count <= 4
    assert set(eng.stats()["bucket_ticks"]) <= {1, 2, 4, 8}


def test_bucketed_eviction_refill_no_state_bleed():
    """Slot churn on the stateful tier: 3 tenants on 2 slots. The
    evicted slot's new tenant must serve **cold** (no warm start from
    the previous occupant's centroids), the surviving tenant must stay
    warm, and the returning tenant re-admits **warm** — its rows were
    parked host-side on eviction (LRU state parking, DESIGN.md §10)
    and restored on re-admit."""
    impl = "cluster"
    cfg, params = _tiny_vig(impl)
    eng = VigServeEngine(cfg, params, digc_impl=impl, autotune=False,
                         buckets=(1, 2))
    rng = np.random.default_rng(11)
    mk = lambda t: VigRequest(uid=rng.integers(1 << 30), image=_image(rng),
                              tenant=t)

    # warm A and B over two ticks
    a1, b1 = mk("A"), mk("B")
    eng.submit(a1), eng.submit(b1)
    eng.step()
    a2, b2 = mk("A"), mk("B")
    eng.submit(a2), eng.submit(b2)
    eng.step()
    refs_a, _ = _replay_tenant(cfg, params, impl, [a1, a2])
    np.testing.assert_allclose(a2.logits, refs_a[1], rtol=1e-5, atol=1e-5)
    assert set(eng.slot_tenant) == {"A", "B"}

    # C arrives alone: evicts (and parks) the LRU slot, must serve cold
    c1 = mk("C")
    eng.submit(c1)
    eng.step()
    assert eng.last_resets  # a slot was reassigned (cold reset)
    ref_c, _ = _replay_tenant(cfg, params, impl, [c1])
    np.testing.assert_allclose(c1.logits, ref_c[0], rtol=1e-5, atol=1e-5)
    evicted = "A" if "A" not in eng.slot_tenant else "B"
    survivor = "B" if evicted == "A" else "A"
    assert evicted in eng._parked  # the evictee's rows were parked

    # the survivor's warm row must be untouched by C's admission tick
    s3 = mk(survivor)
    eng.submit(s3)
    eng.step()
    history = {"A": [a1, a2], "B": [b1, b2]}[survivor] + [s3]
    refs_s, _ = _replay_tenant(cfg, params, impl, history)
    np.testing.assert_allclose(s3.logits, refs_s[-1], rtol=1e-5, atol=1e-5)

    # the evicted tenant returns: restored WARM from its parked rows —
    # it must match the replay of its FULL history, not a cold start
    e4 = mk(evicted)
    eng.submit(e4)
    eng.step()
    assert eng.park_hits == 1 and eng.last_restores
    full = {"A": [a1, a2], "B": [b1, b2]}[evicted] + [e4]
    refs_e, _ = _replay_tenant(cfg, params, impl, full)
    np.testing.assert_allclose(e4.logits, refs_e[-1], rtol=1e-5, atol=1e-5)


def test_eviction_readmit_cold_when_parking_disabled():
    """park_capacity=0 restores the PR-4 contract: an evicted tenant's
    state is gone and it re-admits cold."""
    impl = "cluster"
    cfg, params = _tiny_vig(impl)
    eng = VigServeEngine(cfg, params, digc_impl=impl, autotune=False,
                         buckets=(1, 2), park_capacity=0)
    rng = np.random.default_rng(12)
    mk = lambda t: VigRequest(uid=rng.integers(1 << 30), image=_image(rng),
                              tenant=t)
    a1, b1 = mk("A"), mk("B")
    eng.submit(a1), eng.submit(b1)
    eng.step()
    c1 = mk("C")
    eng.submit(c1)
    eng.step()
    evicted = "A" if "A" not in eng.slot_tenant else "B"
    assert not eng._parked
    e2 = mk(evicted)
    eng.submit(e2)
    eng.step()
    assert eng.park_hits == 0 and not eng.last_restores
    ref_cold, _ = _replay_tenant(cfg, params, impl, [e2])
    np.testing.assert_allclose(e2.logits, ref_cold[0], rtol=1e-5, atol=1e-5)


def test_parking_lru_capacity_and_release():
    """The parking tier is bounded LRU (oldest parked copy dropped at
    capacity) and an explicit release() drops the parked copy too."""
    eng = _stub_engine((1, 2), park=2)
    img = np.zeros((16, 16, 3), np.float32)
    uid = 0
    # churn 5 tenants through 2 slots: evictions park in LRU order
    for t in ("A", "B", "C", "D", "E"):
        eng.submit(VigRequest(uid=uid, image=img, tenant=t))
        uid += 1
        eng.step()
    # A..C were evicted in order; capacity 2 keeps only the last two
    assert list(eng._parked) == ["B", "C"]
    assert eng.park_evictions == 1  # A dropped at capacity
    # release drops both the slot binding and the parked copy
    eng.release("C")
    assert "C" not in eng._parked
    # a re-admitted parked tenant consumes its copy (restore-once)
    eng.submit(VigRequest(uid=uid, image=img, tenant="B"))
    eng.step()
    assert eng.park_hits == 1 and "B" not in eng._parked
    assert eng.last_restores and not eng.last_resets


def test_bucketed_padding_lanes_keep_warm_gate_and_idle_rows():
    """A single tenant on a bucket-4 engine: three lanes are padding
    every tick. The tenant must still engage its warm start on tick 2
    (padding lanes replicate a live row, so the all-warm fast path
    holds), idle slots' rows must stay exactly zero, and tenant
    release() must cold-reset the slot."""
    impl = "cluster"
    cfg, params = _tiny_vig(impl)
    eng = VigServeEngine(cfg, params, digc_impl=impl, autotune=False,
                         buckets=(4,))
    rng = np.random.default_rng(13)
    reqs = [VigRequest(uid=i, image=_image(rng), tenant="A")
            for i in range(3)]
    for r in reqs[:2]:
        eng.submit(r)
        eng.step()
        assert eng.last_bucket == 4 and len(eng.last_lanes) == 1
    refs, _ = _replay_tenant(cfg, params, impl, reqs[:2])
    for r, ref in zip(reqs[:2], refs):
        np.testing.assert_allclose(r.logits, ref, rtol=1e-5, atol=1e-5)
    # the warm gate engaged: slot row counted once per block per request
    slot = eng._tenant_slot["A"]
    row_steps = eng.slot_row_steps()["stage0"]
    assert row_steps[slot] == 2 * sum(cfg.depths)
    # idle slots: never served, rows exactly zero
    ent = eng._slot_state.entries["stage0"]
    for s in range(eng.slots):
        if s != slot:
            assert row_steps[s] == 0
            np.testing.assert_array_equal(
                np.asarray(ent.centroids[s]), 0.0)
    warm_cents = np.asarray(ent.centroids[slot])
    assert not np.allclose(warm_cents, 0.0)
    # release: the tenant's rows are cold-reset, its next request is cold
    eng.release("A")
    assert eng.slot_tenant[slot] is None
    np.testing.assert_array_equal(
        np.asarray(eng._slot_state.entries["stage0"].centroids[slot]), 0.0)
    eng.submit(reqs[2])
    eng.step()
    ref_cold, _ = _replay_tenant(cfg, params, impl, [reqs[2]])
    np.testing.assert_allclose(reqs[2].logits, ref_cold[0],
                               rtol=1e-5, atol=1e-5)


def test_bucketed_compile_count_real_jit():
    """Real compiled programs: a trace touching every bucket compiles
    exactly |buckets| programs, and the on_compile hook sees each."""
    cfg, params = _tiny_vig("blocked")
    seen = []
    eng = VigServeEngine(cfg, params, digc_impl="blocked", autotune=False,
                         buckets=(1, 2), on_compile=seen.append)
    rng = np.random.default_rng(17)
    for wave in ([0], [1, 2], [3], [4, 5], [6]):
        for t in wave:
            eng.submit(VigRequest(uid=t, image=_image(rng), tenant=t))
        eng.step()
    assert eng.compile_count == 2
    assert sorted(seen) == [1, 2]
    assert all(r in (1, 2) for r in eng.stats()["bucket_ticks"])


def test_bucketed_requires_valid_buckets():
    cfg, params = _tiny_vig("blocked")
    with pytest.raises(ValueError, match="buckets"):
        VigServeEngine(cfg, params, autotune=False, buckets=(0, 2))
    with pytest.raises(ValueError, match="active"):
        VigServeEngine(cfg, params, autotune=False,
                       buckets=(1, 2)).bucket_for(3)


def test_mesh_mode_rejects_invalid_configurations():
    """Sharded-mode validation: non-distributed impls have no mesh
    knobs; a sharded batch axis needs a bucket set (the exact-size
    policy serves counts that cannot all divide the axis — refusing at
    init beats crashing mid-tick after admission mutated slot state)."""
    cfg, params = _tiny_vig("ring")
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="mesh-native"):
        VigServeEngine(cfg, params, digc_impl="blocked", autotune=False,
                       mesh=mesh)
    with pytest.raises(ValueError, match="bucket set"):
        VigServeEngine(cfg, params, digc_impl="ring", autotune=False,
                       mesh=mesh, mesh_batch_axis="data", buckets=None)


def test_anonymous_requests_free_their_slot():
    """tenant=None requests are one-shot: their slot is freed the tick
    they complete, so a stream of anonymous requests can never pin
    slots and LRU-evict live warm tenants."""
    eng = _stub_engine((1, 2))
    eng.submit(VigRequest(uid=0, image=np.zeros((16, 16, 3), np.float32),
                          tenant="A"))
    eng.step()
    for uid in range(1, 5):  # anonymous churn on the other slot
        eng.submit(VigRequest(uid=uid,
                              image=np.zeros((16, 16, 3), np.float32)))
        eng.step()
        assert eng.last_resets  # each one-shot admitted cold
    # A's binding (and warm row) survived four anonymous one-shots
    assert "A" in eng.slot_tenant
    assert eng.slot_tenant.count(None) == eng.slots - 1
    a_slot = eng._tenant_slot["A"]
    assert eng.slot_row_steps()["stage0"][a_slot] == 1


def test_admission_reserves_active_tenants_before_evicting():
    """Queue order must not decide whose warm state survives: with
    warm tenants A/B on a full 2-slot engine and one tick's queue
    [C, A], A (active this tick) keeps its slot and warm row; C may
    only evict the idle tenant B."""
    eng = _stub_engine((1, 2))
    img = np.zeros((16, 16, 3), np.float32)
    for uid, t in ((0, "A"), (1, "B")):
        eng.submit(VigRequest(uid=uid, image=img, tenant=t))
    eng.step()
    a_slot = eng._tenant_slot["A"]
    # C arrives ahead of A in the same tick
    eng.submit(VigRequest(uid=2, image=img, tenant="C"))
    eng.submit(VigRequest(uid=3, image=img, tenant="A"))
    assert eng.step() == 2
    assert eng._tenant_slot["A"] == a_slot  # A kept its slot...
    assert eng.slot_row_steps()["stage0"][a_slot] == 2  # ...and warmth
    assert "B" not in eng._tenant_slot  # the idle tenant was evicted
    assert eng._tenant_slot["C"] not in (None, a_slot)


def test_warmup_schedule_never_leaks_into_other_buckets(tmp_path):
    """A warmup()-tuned schedule is a measurement at self.batch; the
    request path must tune per bucket instead of baking the B=batch
    tile into every bucket's program (only a user-provided VigSchedule
    applies everywhere)."""
    from repro.core.tuner import VigSchedule
    from repro.core.builder import DigcSpec

    cfg, params = _tiny_vig("blocked")
    eng = VigServeEngine(cfg, params, batch=4, buckets=(1, 2),
                         tuner_path=tmp_path / "tune.json")
    eng.warmup()
    assert eng.schedule is not None and not eng._user_schedule
    choice = eng._bucket_choice(1)
    assert choice is not eng.schedule  # tuned at b=1, not reused from b=4
    assert 1 in eng._bucket_schedules
    # a user-provided schedule does apply to every bucket
    sched = VigSchedule(stages=(
        DigcSpec(impl="blocked", k=3, block_m=16, merge="topk"),
    ))
    eng2 = VigServeEngine(cfg, params, digc_impl=sched, buckets=(1, 2))
    assert eng2._bucket_choice(1) is sched
    assert eng2._bucket_choice(2) is sched


def test_fixed_policy_is_one_program_per_batch_size():
    """buckets=None: the PR-3 baseline — exact-size ticks, one program
    per distinct batch size (the bench's comparison anchor)."""
    cfg, params = _tiny_vig("blocked")
    eng = VigServeEngine(cfg, params, digc_impl="blocked", autotune=False,
                         buckets=None, batch=4)
    rng = np.random.default_rng(19)
    uid = 0
    for wave_size in (1, 3, 2, 3, 1):
        for _ in range(wave_size):
            eng.submit(VigRequest(uid=uid, image=_image(rng), tenant=uid))
            uid += 1
        eng.step()
        assert eng.last_bucket == wave_size  # no padding
    assert eng.compile_count == 3  # sizes {1, 2, 3}


# ---------------------------------------------------------------------------
# Property tests: scheduler/state-lifecycle invariants under arbitrary
# arrival sequences. Programs are stubbed (no compiles), so hypothesis
# can drive hundreds of ticks; the stub bumps every state entry exactly
# like a depth-1 forward would.


class _StubProgramEngine(VigServeEngine):
    def _build_program(self, bucket):
        def fake_fwd(params, imgs, state):
            b = imgs.shape[0]
            new = DigcState(entries={
                k: e.bump() for k, e in state.entries.items()
            })
            return jnp.zeros((b, self.cfg.num_classes), jnp.float32), new

        return fake_fwd


def _stub_engine(buckets, on_compile=None, park=8):
    cfg, params = _tiny_vig("cluster")
    return _StubProgramEngine(cfg, params, digc_impl="cluster",
                              autotune=False, buckets=buckets,
                              on_compile=on_compile, park_capacity=park)


@settings(max_examples=60)
@given(active=st.integers(1, 8),
       buckets=st.sampled_from([(1, 2, 4, 8), (2, 8), (8,), (1, 3, 5, 8)]))
def test_property_bucket_is_smallest_that_fits(active, buckets):
    eng = _stub_engine(buckets)
    b = eng.bucket_for(active)
    assert b in buckets and b >= active
    assert all(c < active for c in buckets if c < b)  # none smaller fits


@settings(max_examples=25)
@given(arrivals=st.lists(st.integers(0, 5), min_size=1, max_size=14))
def test_property_padding_never_mutates_live_rows(arrivals):
    """Arbitrary arrival sequences (tenant ids 0-5 on 4 slots, so both
    padding, eviction and park/restore occur): after every tick, rows
    of slots that neither served nor were reset/restored this tick are
    bit-identical, the served slots' counters advanced exactly once
    (from 0 on a cold reset, from the parked value on a restore), and
    the bucket was the smallest that fits."""
    eng = _stub_engine((1, 2, 4))
    for i, t in enumerate(arrivals):
        eng.submit(VigRequest(
            uid=i, image=np.zeros((16, 16, 3), np.float32), tenant=t))
    served_total = 0
    while eng.queue:
        state = eng._ensure_slot_state()
        before = {
            k: jax.tree_util.tree_map(np.asarray, e)
            for k, e in state.entries.items()
        }
        parked_before = {
            t: {k: int(e.row_step[0]) for k, e in st.entries.items()}
            for t, st in eng._parked.items()
        }
        served = eng.step()
        served_total += served
        assert served == len(eng.last_lanes) >= 1
        assert eng.last_bucket == eng.bucket_for(served)
        touched = (set(eng.last_lanes) | set(eng.last_resets)
                   | set(eng.last_restores))
        after = eng._slot_state
        for key, ent in after.entries.items():
            for s in range(eng.slots):
                old_step = before[key].row_step[s]
                new_step = int(ent.row_step[s])
                if s not in touched:
                    # padding lanes replicate live rows but are dropped
                    # on scatter: untouched slots are bit-identical
                    assert new_step == old_step
                    np.testing.assert_array_equal(
                        np.asarray(ent.centroids[s]),
                        before[key].centroids[s])
                elif s in eng.last_lanes:
                    if s in eng.last_resets:
                        base = 0  # cold admit
                    elif s in eng.last_restores:
                        # warm re-admit: continue from the parked copy
                        base = parked_before[eng.slot_tenant[s]][key]
                    else:
                        base = old_step
                    assert new_step == base + 1
    assert served_total == len(arrivals)


@settings(max_examples=25)
@given(arrivals=st.lists(st.integers(0, 9), min_size=1, max_size=20),
       buckets=st.sampled_from([(1, 2, 4), (4,), (1, 4), (2, 3, 4)]))
def test_property_program_count_bounded_by_bucket_set(arrivals, buckets):
    compiled = []
    eng = _stub_engine(buckets, on_compile=compiled.append)
    for i, t in enumerate(arrivals):
        eng.submit(VigRequest(
            uid=i, image=np.zeros((16, 16, 3), np.float32), tenant=t))
    eng.run()
    assert eng.compile_count <= len(buckets)
    assert eng.compile_count == len(set(compiled))  # hook saw each once
    assert set(compiled) <= set(buckets)
    assert set(eng.bucket_ticks) == set(compiled)


# ---------------------------------------------------------------------------
# LM ServeEngine: per-slot commit mask across mixed-length slots


def _lm_setup():
    from repro.configs import get_smoke
    from repro.launch.api import get_api

    cfg = get_smoke("olmo-1b").replace(dtype="float32")
    api = get_api(cfg)
    params = init_params(api.param_spec(), jax.random.PRNGKey(0))
    return cfg, params


def test_serve_engine_mixed_length_slots_match_solo():
    """Regression (PR-4): without the per-slot commit mask a slot
    prefilling clobbered its neighbors' cache rows — mixed-length
    batches silently decoded garbage. Now with per-slot position
    vectors (one decode call per tick) each request must still match a
    solo (slots=1) run exactly."""
    from repro.serve.engine import Request, ServeEngine

    cfg, params = _lm_setup()
    prompts = {0: np.asarray([5, 9, 2], np.int32),
               1: np.asarray([7, 1, 4, 3, 8], np.int32)}
    eng = ServeEngine(cfg, params, slots=2, max_len=32)
    for uid, p in prompts.items():
        eng.submit(Request(uid=uid, prompt=p, max_new_tokens=4))
    got = {r.uid: r.out_tokens for r in eng.run()}
    for uid, p in prompts.items():
        solo = ServeEngine(cfg, params, slots=1, max_len=32)
        solo.submit(Request(uid=uid, prompt=p, max_new_tokens=4))
        assert got[uid] == solo.run()[0].out_tokens, uid


def test_serve_engine_rejects_empty_prompt():
    from repro.serve.engine import Request, ServeEngine

    cfg, params = _lm_setup()
    eng = ServeEngine(cfg, params, slots=1, max_len=16)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(uid=0, prompt=np.asarray([], np.int32)))


def test_serve_engine_respects_one_token_budget():
    """max_new_tokens=1 is satisfied by the prefill token itself: no
    extra decode step, exactly one output token."""
    from repro.serve.engine import Request, ServeEngine

    cfg, params = _lm_setup()
    eng = ServeEngine(cfg, params, slots=1, max_len=16)
    eng.submit(Request(uid=0, prompt=np.asarray([5, 9], np.int32),
                       max_new_tokens=1))
    out = eng.run()
    assert len(out) == 1 and len(out[0].out_tokens) == 1
    assert eng.decode_calls == 2  # prefill only, no decode tick


def test_user_schedule_sizes_slot_state():
    """_ensure_slot_state must allocate from the same impl choice the
    bucket programs run: a user VigSchedule with a cluster stage spec
    gets matching per-slot centroid buffers (warm starts engage)."""
    from repro.core.builder import DigcSpec
    from repro.core.strategies import default_cluster_params
    from repro.core.tuner import VigSchedule

    cfg, params = _tiny_vig("cluster")
    sched = VigSchedule(stages=(
        DigcSpec(impl="cluster", k=3, n_clusters=3, n_probe=3,
                 capacity_factor=8.0),
    ))
    eng = VigServeEngine(cfg, params, digc_impl=sched, autotune=False,
                         buckets=(1, 2))
    ent = eng._ensure_slot_state().entries["stage0"]
    nc, _ = default_cluster_params(16, 3, 3)
    assert ent.centroids.shape == (2, nc, 16)
    # and the warm start actually engages through the program
    rng = np.random.default_rng(29)
    for uid in range(2):
        eng.submit(VigRequest(uid=uid, image=_image(rng), tenant="A"))
        eng.step()
    slot = eng._tenant_slot["A"]
    assert eng.slot_row_steps()["stage0"][slot] == 2 * sum(cfg.depths)
    assert not np.allclose(
        np.asarray(eng._slot_state.entries["stage0"].centroids[slot]), 0.0)


def test_serve_engine_one_decode_call_per_tick_pinned():
    """Pin the collapsed scheduling (ROADMAP PR-4 follow-up, landed):
    ``decode_step`` takes the per-slot position *vector*, so a tick
    over slots at distinct positions is ONE jitted call — the
    per-position-group loop (one call per distinct length) is gone,
    and the per-slot commit masks still protect inactive slots."""
    from repro.serve.engine import Request, ServeEngine

    cfg, params = _lm_setup()
    eng = ServeEngine(cfg, params, slots=2, max_len=32)
    # same length: 1 decode call per tick
    eng.submit(Request(uid=0, prompt=np.asarray([5, 9], np.int32),
                       max_new_tokens=3))
    eng.submit(Request(uid=1, prompt=np.asarray([7, 1], np.int32),
                       max_new_tokens=3))
    eng.step()  # prefill (2 tokens per slot) + first batched decode
    before = eng.decode_calls
    eng.step()
    assert eng.decode_calls == before + 1  # one call
    # mixed length: STILL one decode call per tick (the collapse)
    eng2 = ServeEngine(cfg, params, slots=2, max_len=32)
    eng2.submit(Request(uid=0, prompt=np.asarray([5], np.int32),
                        max_new_tokens=4))
    eng2.submit(Request(uid=1, prompt=np.asarray([7, 1, 4], np.int32),
                        max_new_tokens=4))
    eng2.step()
    before = eng2.decode_calls
    eng2.step()
    assert eng2.decode_calls == before + 1  # mixed lengths, one call
