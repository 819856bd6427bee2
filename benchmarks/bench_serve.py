"""Serving-path benchmark: the jitted functional-state ``VigServeEngine``
vs the legacy eager ``DigcCache`` shim per request, plus the
multi-tenant ragged-arrival trace (bucketed vs the PR-3 fixed-batch
policy).

The acceptance workload is the ViG N=3136 regime (224^2 / patch 4 —
the grid where PR-2 measured the eager cache-aware cluster tier): the
jitted path must serve the cluster tier with **no eager fallback** at
per-request latency <= the eager shim's. Rows record both modes plus
the speedup, per tier, so the jit-vs-eager gap is part of the perf
trajectory.

The multi-tenant rows serve one ragged trace (arrival waves of 1-8
interleaved tenants) through the request path twice: ``buckets=
(1,2,4,8)`` (pad to the smallest fitting bucket, <= 4 compiled
programs) and ``buckets=None`` (the PR-3 baseline: exact-size ticks,
one program per distinct batch size). The cold rows include program
compilation — exactly what the one-program-per-batch-size engine pays
on a ragged stream — and the warm rows re-serve the same trace through
the already-compiled programs (steady state).

The ``serve/guarded_*`` rows price the fault-tolerance guards
(DESIGN.md §11) on the fault-free path: the same ragged trace with
the admission/state screening armed vs ``guards=False``, with the
warm overhead ratio pinned by the acceptance bar (<= 1.05x).

The ``serve/stale_*`` rows price stale-graph serving (DESIGN.md §12):
the same steady multi-tenant trace under every reuse policy vs
``reuse`` off, a drift-gated high-res (N=12544) per-tick row where the
acceptance bar demands >= 1.3x warm speedup, and the recall-vs-
drift_tau sweep that records what graph quality each gate width buys.
``serve/clustertick_*`` profiles the cluster tier's index-build vs
dispatch split across batch sizes (the superlinear-B question,
ROADMAP).

The ``serve/sched_*`` rows price the SLO-bounded admission scheduler
(DESIGN.md §14): a seeded Poisson+burst arrival trace replayed under a
``VirtualClock`` through the auto-tuned bucketed scheduler vs a
fixed-cadence exact-size server, cold (compile-count capped vs
one-program-per-size) and warm (coalesced full ticks vs sub-width
windows), in the dispatch-bound N=256 regime where per-tick fixed
cost is what batching amortizes.
"""

import dataclasses
import os
import tempfile
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit

TUNE_CACHE = ".digc_tune.json"


def _engine(cfg, params, impl, mode, batch, smoke):
    from repro.serve.engine import VigServeEngine

    return VigServeEngine(
        cfg, params, digc_impl=impl, batch=batch, mode=mode,
        # blocked autotunes through the committed host-keyed cache;
        # smoke keeps its toy workloads out of it (in-memory tuner).
        autotune=(impl == "blocked"),
        tuner_path=None if smoke else TUNE_CACHE,
    )


def run(smoke: bool = False, res: int = 224, batch: int = 2, iters: int = 3):
    from repro.models import vig
    from repro.models.module import init_params

    if smoke:
        res, iters = 32, 1
    # res=224 / patch 4 -> grid 56 -> N=3136 (the PR-2 cluster-tier
    # measurement workload), one isotropic stage of two blocks.
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=res, patch=4, embed_dims=(96,), depths=(2,),
        num_classes=10, k=9,
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(
        rng.standard_normal((batch, res, res, 3)), jnp.float32
    )
    n = cfg.base_grid ** 2
    for impl in ("cluster", "blocked"):
        per_mode = {}
        for mode in ("jit", "eager"):
            eng = _engine(cfg, params, impl, mode, batch, smoke)
            # Two warmup calls: compile + engage the warm start, so the
            # measured steady state is what a serving replica sees.
            t = timeit(lambda: eng.infer(imgs), warmup=2, iters=iters)
            per_mode[mode] = t
            emit(
                f"serve/{impl}_{mode}_us", t * 1e6,
                f"B={batch};N={n};per-request forward;mode={mode};"
                f"requests_served={eng.requests_served}",
            )
        emit(
            f"serve/{impl}_jit_speedup", per_mode["eager"] / per_mode["jit"],
            f"B={batch};N={n};eager_us={per_mode['eager'] * 1e6:.0f};"
            f"jit_us={per_mode['jit'] * 1e6:.0f};x_eager_over_jit "
            "(>=1 means the jitted functional-state path wins)",
        )
    _run_multitenant(cfg, params, n, res, smoke)
    _run_guarded(cfg, params, n, res, smoke)
    _run_stale(cfg, params, n, res, smoke)
    _run_stale_highres(smoke)
    _run_stale_recall(smoke)
    _run_clustertick_profile(smoke)
    _run_multires(smoke)
    _run_sharded(smoke)
    _run_sched(smoke)
    return True


def _serve_trace(engine, waves, images):
    """Submit the ragged trace wave by wave and drain; returns wall
    seconds for the full trace (one engine tick per wave)."""
    from repro.serve.engine import VigRequest

    uid = 0
    t0 = time.perf_counter()
    for wave in waves:
        for tenant in wave:
            engine.submit(VigRequest(uid=uid, image=images[tenant],
                                     tenant=tenant))
            uid += 1
        engine.step()
    assert not engine.queue
    return time.perf_counter() - t0


def _run_multitenant(cfg, params, n, res, smoke):
    """Ragged multi-tenant trace: bucket policies vs the PR-3
    fixed-batch (one program per batch size) baseline.

    The bucket set is a compile-count vs padding-waste dial: the
    coarse ``{8}`` policy compiles one program and pads everything
    (best cold-trace throughput — ragged streams are compile-
    dominated), ``{1,2,4,8}`` compiles four and pads by at most 2x
    (best steady-state latency among the bucketed policies), and the
    PR-3 baseline compiles one program per distinct tick size. Rows
    record cold (incl. compiles) and warm (steady) per policy.
    """
    from repro.serve.engine import VigServeEngine

    impl = "cluster"  # the stateful showcase tier (per-slot warm starts)
    if smoke:
        wave_sizes = (1, 3, 2, 4)
        policies = (("b1_2_4", (1, 2, 4)), ("b4", (4,)), ("fixed", None))
        slots = 4
    else:
        wave_sizes = (1, 3, 8, 2, 5, 4, 7, 6)
        policies = (("b1_2_4_8", (1, 2, 4, 8)), ("b8", (8,)),
                    ("fixed", None))
        slots = 8
    # tenants cycle through the slots; wave w serves tenants
    # w, w+1, ... (mod slots) so arrivals interleave raggedly
    waves = [
        [(w + i) % slots for i in range(size)]
        for w, size in enumerate(wave_sizes)
    ]
    total = sum(wave_sizes)
    rng = np.random.default_rng(0)
    images = [rng.standard_normal((res, res, 3)).astype(np.float32)
              for _ in range(slots)]

    results = {}
    for policy, bconf in policies:
        eng = VigServeEngine(cfg, params, digc_impl=impl, autotune=False,
                             buckets=bconf, batch=slots)
        cold = _serve_trace(eng, waves, images)  # includes compiles
        cold_ticks = sorted(eng.bucket_ticks.items())  # before warm pass
        warm = _serve_trace(eng, waves, images)  # steady state
        results[policy] = (cold, warm, eng)
        emit(
            f"serve/multitenant_{policy}_cold_us", cold / total * 1e6,
            f"N={n};requests={total};waves={list(wave_sizes)};"
            f"programs={eng.compile_count};"
            f"bucket_ticks={cold_ticks};"
            "per-request incl. compiles (ragged trace, cluster tier)",
        )
        emit(
            f"serve/multitenant_{policy}_warm_us", warm / total * 1e6,
            f"N={n};requests={total};steady state, programs compiled",
        )
    for policy, _ in policies[:-1]:  # each bucketed policy vs PR-3
        for phase, idx in (("cold", 0), ("warm", 1)):
            emit(
                f"serve/multitenant_{policy}_speedup_{phase}",
                results["fixed"][idx] / results[policy][idx],
                f"N={n};requests={total};x_fixed_over_{policy};"
                f"{policy}_programs={results[policy][2].compile_count};"
                f"fixed_programs={results['fixed'][2].compile_count}",
            )


def _run_guarded(cfg, params, n, res, smoke):
    """Guard overhead on the fault-free path (DESIGN.md §11).

    The same ragged trace as the multitenant rows, served with the
    fault-tolerance guards armed (admission finiteness screen, per-row
    integrity fingerprints, state finiteness checks — the engine
    default) vs ``guards=False`` (the unguarded PR-6 path). The
    guarded warm row is the number the acceptance bar pins: steady-
    state overhead must stay within a few percent, since every healthy
    tick pays the screening whether or not a fault ever occurs. No
    fault plan is attached — injection costs nothing when absent; this
    measures detection, not injection.
    """
    from repro.serve.engine import VigServeEngine

    impl = "cluster"
    if smoke:
        wave_sizes, bconf, slots = (1, 3, 2, 4), (1, 2, 4), 4
    else:
        wave_sizes, bconf, slots = (1, 3, 8, 2, 5, 4, 7, 6), (1, 2, 4, 8), 8
    waves = [
        [(w + i) % slots for i in range(size)]
        for w, size in enumerate(wave_sizes)
    ]
    total = sum(wave_sizes)
    rng = np.random.default_rng(0)
    images = [rng.standard_normal((res, res, 3)).astype(np.float32)
              for _ in range(slots)]
    engines, cold_s, warm_s = {}, {}, {}
    for label, guards in (("unguarded", False), ("guarded", True)):
        eng = VigServeEngine(cfg, params, digc_impl=impl, autotune=False,
                             buckets=bconf, batch=slots, guards=guards)
        engines[label] = eng
        cold_s[label] = _serve_trace(eng, waves, images)  # incl. compiles
        warm_s[label] = float("inf")
    # Interleaved best-of-5 warm passes: the overhead row divides two
    # small numbers, so back-to-back measurement (all passes of one
    # engine, then the other) would bake clock/cache drift into the
    # ratio; alternating engines cancels it.
    for _ in range(5):
        for label, eng in engines.items():
            warm_s[label] = min(warm_s[label],
                                _serve_trace(eng, waves, images))
    for eng in engines.values():
        assert eng.stats()["quarantines"] == 0  # fault-free by design
    results = {label: (cold_s[label], warm_s[label]) for label in engines}
    for phase, idx in (("cold", 0), ("warm", 1)):
        emit(
            f"serve/guarded_{phase}_us", results["guarded"][idx] / total * 1e6,
            f"N={n};requests={total};guards on, no fault plan;"
            f"unguarded_us={results['unguarded'][idx] / total * 1e6:.0f};"
            + ("per-request incl. compiles" if phase == "cold"
               else "steady state"),
        )
        emit(
            f"serve/guarded_overhead_{phase}",
            results["guarded"][idx] / results["unguarded"][idx],
            f"N={n};requests={total};x_guarded_over_unguarded "
            "(1.0 = free; acceptance bar: warm <= 1.05)",
        )


def _stale_spec(policy, *, impl="cluster", k=9, max_stale=8):
    from repro.core.builder import DEFAULT_DRIFT_TAU, DigcSpec

    extra = {}
    if policy is not None:
        extra = dict(reuse=policy, drift_tau=DEFAULT_DRIFT_TAU,
                     max_stale=max_stale)
    return DigcSpec(impl=impl, k=k, **extra)


def _run_stale(cfg, params, n, res, smoke):
    """Stale-graph serving policies on a steady multi-tenant stream
    (DESIGN.md §12).

    Each tenant re-submits the *same* image every tick — the
    steady-stream limit where per-row drift is ~0, so the reuse gate's
    headroom is maximal: ``tick``/``layer`` serve the cached graph
    (with a rebuild every ``max_stale`` ticks), ``overlap`` serves the
    cached graph while refreshing it unconditionally (paying the build
    off the serving path's critical answer, not skipping it), and
    ``off`` rebuilds per call — today's baseline. Cold rows include
    compiles; warm rows are best-of-3 steady state. The per-policy
    reuse/rebuild split from ``stats()`` lands in the derived column,
    so the row is auditable against the gate's actual behavior."""
    from repro.serve.engine import VigServeEngine

    slots, ticks = (2, 2) if smoke else (4, 4)
    waves = [list(range(slots))] * ticks
    total = slots * ticks
    rng = np.random.default_rng(0)
    images = [rng.standard_normal((res, res, 3)).astype(np.float32)
              for _ in range(slots)]
    policies = (("off", None), ("reuse_layer", "layer"),
                ("reuse_tick", "tick"), ("overlap", "overlap"))
    results = {}
    for label, policy in policies:
        spec = _stale_spec(policy)
        eng = VigServeEngine(cfg, params, digc_impl=spec, autotune=False,
                             buckets=(slots,), batch=slots)
        cold = _serve_trace(eng, waves, images)  # includes compiles
        warm = float("inf")
        for _ in range(3):
            warm = min(warm, _serve_trace(eng, waves, images))
        st = eng.stats()
        results[label] = (cold, warm)
        info = (f"N={n};requests={total};policy={policy or 'off'};"
                f"graph_reuses={st['graph_reuses']};"
                f"graph_rebuilds={st['graph_rebuilds']}")
        emit(f"serve/stale_{label}_cold_us", cold / total * 1e6,
             info + ";per-request incl. compiles")
        emit(f"serve/stale_{label}_warm_us", warm / total * 1e6,
             info + ";steady state")
    for label, _ in policies[1:]:
        emit(
            f"serve/stale_{label}_speedup_warm",
            results["off"][1] / results[label][1],
            f"N={n};requests={total};x_off_over_{label} "
            "(steady stream, drift ~0)",
        )


def _run_stale_highres(smoke):
    """The acceptance workload: N=12544 (448^2 / patch 4), where DIGC
    is ~95% of the tick (PAPER.md). One jitted stateful ``vig_forward``
    per tick on a steady stream; the ``tick`` policy must clear >= 1.3x
    warm per-tick speedup over ``reuse`` off. Uses the cluster tier —
    the N=12544 serving tier of record — with a long staleness bound so
    the steady window prices the gate, not the periodic refresh."""
    from repro.models import vig
    from repro.models.module import init_params

    res = 32 if smoke else 448
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=res, patch=4, embed_dims=(48,), depths=(2,),
        num_classes=10, k=9,
    )
    n = cfg.base_grid ** 2
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.standard_normal((1, res, res, 3)), jnp.float32)

    per_policy = {}
    for label, policy in (("off", None), ("tick", "tick")):
        spec = _stale_spec(policy, max_stale=64)
        state = vig.init_vig_state(cfg, 1, spec)
        fwd = jax.jit(lambda p, im, s, _spec=spec: vig.vig_forward(
            p, im, cfg, digc_impl=_spec, state=s))
        for _ in range(2):  # compile + engage the warm/reuse branch
            _, state = fwd(params, img, state)
        jax.block_until_ready(state.entries)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out, state = fwd(params, img, state)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        per_policy[label] = best
        emit(
            f"serve/stale_highres_{label}_warm_us", best * 1e6,
            f"N={n};B=1;cluster tier;per-tick steady state;"
            f"policy={policy or 'off'}",
        )
    emit(
        "serve/stale_highres_speedup_warm",
        per_policy["off"] / per_policy["tick"],
        f"N={n};x_off_over_tick;acceptance bar: >= 1.3 at N=12544",
    )


def _run_stale_recall(smoke):
    """Recall vs drift_tau: what graph quality each gate width buys.

    The stream mirrors what the drift statistic sees on real embeddings
    (DESIGN.md §12): tiny frame-to-frame jitter (relative drift ~1e-4,
    the graph barely moves) punctuated by scene cuts every third tick —
    fresh content at a shifted energy level. The cut energies are
    normalized so the gate sees a *pinned* ~0.077 relative drift (the
    0.06-0.14 content band) at every N, instead of riding the
    statistic's O(1/sqrt(N*D)) sampling noise. Replayed through
    the reuse gate at every tau, scoring the *served* graph against a
    per-call exact rebuild — the same replay ``core.tuner.tune_reuse``
    uses for its recall floor, so the recorded curve is exactly what
    the tuner would decide from. Taus below the cut band rebuild on
    cuts and reuse through jitter (high recall); taus above it serve a
    dead graph across cuts and recall collapses. One row per (N, tau);
    the default-tau row carries the acceptance bar (recall >= 0.95)."""
    from repro.core.builder import DEFAULT_DRIFT_TAU, DigcSpec
    from repro.core.tuner import tune_reuse

    sizes = (64,) if smoke else (3136, 12544)
    taus = (0.01, 0.02, DEFAULT_DRIFT_TAU, 0.1, 0.2)
    ticks_n = 4 if smoke else 8
    rng = np.random.default_rng(0)
    for n in sizes:
        h = rng.standard_normal((1, n, 32)).astype(np.float32)
        h /= np.sqrt((h * h).mean())
        energy, cuts = 1.0, 0
        ticks = []
        for t in range(ticks_n):
            if t > 0 and t % 3 == 0:
                # scene cut: fresh content, energy stepped by 1.08x so
                # the gate sees ~0.077 relative drift deterministically
                energy = energy / 1.08 if cuts % 2 == 0 else energy * 1.08
                cuts += 1
                f = rng.standard_normal(h.shape).astype(np.float32)
                h = f / np.sqrt((f * f).mean()) * np.sqrt(energy)
            else:
                # frame jitter: drift ~1e-4, graph nearly static
                h = h + 0.01 * rng.standard_normal(h.shape).astype(
                    np.float32)
            ticks.append([("s", jnp.asarray(h), None)])
        _, results = tune_reuse(
            ticks, spec=DigcSpec(impl="blocked", k=9), policy="layer",
            taus=taus, max_stale=8, recall_floor=0.95,
        )
        for r in results:
            bar = (";acceptance bar: recall >= 0.95"
                   if r.drift_tau == DEFAULT_DRIFT_TAU else "")
            emit(
                f"serve/stale_recall_n{n}_tau{r.drift_tau:g}",
                r.recall,
                f"N={n};reuse_frac={r.reuse_frac:.2f};"
                f"admitted={r.admitted};recall of served graph vs "
                f"exact rebuild (synthetic drift stream){bar}",
            )


def _run_clustertick_profile(smoke):
    """Cluster-tick cost split across batch size: index build (k-means
    + member scatter) vs search/dispatch (probe + top-k). The open
    ROADMAP question is why the cluster tick scales *superlinearly* in
    B — these rows pin which half grows faster than linear, per B, so
    the answer is a table lookup instead of a rerun. Self-graph
    workload (no shared co-nodes): the index is vmapped per row,
    matching what serving pays."""
    from repro.core.strategies import (
        cluster_digc,
        default_cluster_params,
        _cluster_index,
    )

    n, bs = (64, (1, 2)) if smoke else (3136, (1, 2, 4, 8))
    d, k = 32, 9
    n_clusters, _ = default_cluster_params(n, None, None)
    cap = max(int(n / n_clusters * 2.0), k)
    rng = np.random.default_rng(0)
    base = None
    for b in bs:
        x = jnp.asarray(rng.standard_normal((b, n, d)), jnp.float32)
        index_fn = jax.jit(jax.vmap(
            lambda yb: _cluster_index(yb, n_clusters=n_clusters, cap=cap,
                                      seed=0)
        ))
        total_fn = jax.jit(lambda a: cluster_digc(a, k=k))
        t_index = timeit(lambda: index_fn(x), warmup=1,
                         iters=1 if smoke else 3)
        t_total = timeit(lambda: total_fn(x), warmup=1,
                         iters=1 if smoke else 3)
        t_dispatch = max(t_total - t_index, 0.0)
        if base is None:
            base = (t_index, t_dispatch)
        emit(
            f"serve/clustertick_b{b}_index_us", t_index * 1e6,
            f"N={n};B={b};k-means + member scatter;"
            f"x_vs_b1={t_index / base[0]:.2f} (linear would be {b}.00)",
        )
        emit(
            f"serve/clustertick_b{b}_dispatch_us", t_dispatch * 1e6,
            f"N={n};B={b};probe + top-k (total - index);"
            f"x_vs_b1={t_dispatch / max(base[1], 1e-12):.2f} "
            f"(linear would be {b}.00)",
        )


def _run_multires(smoke):
    """Multi-resolution lattice rows (DESIGN.md §13): one
    ``image_sizes=`` engine serving a mixed ragged-resolution trace vs
    the one-engine-per-size baseline (each size gets its own dedicated
    engine; the sum of their trace times is what a deployment without
    the lattice pays). The acceptance cells are N=3136 (224^2/4) and
    N=12544 (448^2/4) — the grid where DIGC is ~95% of the tick
    (PAPER.md) — on the cluster tier, reuse off, so the rows price the
    lattice's admission/program surface, not the §12 gate. Per-N warm
    per-tick rows compare each lattice cell against its dedicated
    engine at steady state (the lattice's overhead is dict lookups and
    per-size state scatter; the bar is parity)."""
    from repro.models import vig
    from repro.models.module import init_params
    from repro.serve.engine import VigRequest, VigServeEngine

    sizes = (16, 32) if smoke else (224, 448)
    s0, s1 = sizes
    impl = "cluster"
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=s0, patch=4, embed_dims=(48,), depths=(2,),
        num_classes=10, k=9,
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    ns = {s: (s // cfg.patch) ** 2 for s in sizes}
    rng = np.random.default_rng(0)
    # mixed ragged trace: A/B ride the small cell (buckets 1-2), C
    # holds the large one — the arrival shape a detection deployment
    # sees (many small crops, few full frames)
    waves = [[("A", s0)], [("B", s0), ("C", s1)],
             [("A", s0), ("B", s0)], [("C", s1)], [("A", s0)]]
    total = sum(len(w) for w in waves)
    images = {}
    for wave in waves:
        for t, s in wave:
            if (t, s) not in images:
                images[t, s] = rng.standard_normal((s, s, 3)) \
                    .astype(np.float32)
    uid_box = [0]

    def serve(pools):
        t0 = time.perf_counter()
        for wave in waves:
            for t, s in wave:
                pools[s].submit(VigRequest(uid=uid_box[0],
                                           image=images[t, s], tenant=t))
                uid_box[0] += 1
            for eng in {id(e): e for e in pools.values()}.values():
                while eng.queue:
                    eng.step()
        return time.perf_counter() - t0

    lat = VigServeEngine(cfg, params, digc_impl=impl, autotune=False,
                         buckets=(1, 2), image_sizes=sizes, batch=4)
    lattice = {s: lat for s in sizes}
    dedicated = {}
    for s in sizes:
        c = cfg.replace(image_size=s)
        p = init_params(vig.vig_param_spec(c), jax.random.PRNGKey(0))
        dedicated[s] = VigServeEngine(c, p, digc_impl=impl,
                                      autotune=False, buckets=(1, 2),
                                      batch=4)

    results = {}
    for label, pools in (("", lattice), ("persize_", dedicated)):
        cold = serve(pools)  # includes compiles
        warm = serve(pools)  # steady state
        results[label] = (cold, warm)
        programs = sum({id(e): e.compile_count
                        for e in pools.values()}.values())
        emit(
            f"serve/multires_{label}cold_us", cold / total * 1e6,
            f"N={ns[s1]};sizes={list(sizes)};requests={total};"
            f"programs={programs};per-request incl. compiles "
            "(mixed-resolution ragged trace, cluster tier)",
        )
        emit(
            f"serve/multires_{label}warm_us", warm / total * 1e6,
            f"N={ns[s1]};sizes={list(sizes)};requests={total};"
            "steady state, programs compiled",
        )
    assert lat.compile_count <= len(lat.buckets) * len(sizes)
    for phase, idx in (("cold", 0), ("warm", 1)):
        emit(
            f"serve/multires_speedup_{phase}",
            results["persize_"][idx] / results[""][idx],
            f"N={ns[s1]};sizes={list(sizes)};x_persize_over_lattice;"
            f"lattice_programs={lat.compile_count}",
        )

    # per-N steady-state per-tick: each lattice cell vs its dedicated
    # engine (both warm from the traces above)
    def tick_us(eng, t, s):
        best = float("inf")
        for _ in range(3):
            req = VigRequest(uid=uid_box[0], image=images[t, s], tenant=t)
            uid_box[0] += 1
            t0 = time.perf_counter()
            eng.submit(req)
            eng.step()
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    from repro.models.vig import _resolution_k

    for s in sizes:
        t = "A" if s == s0 else "C"
        k_lat = _resolution_k(cfg.k, s // cfg.patch, cfg.base_grid)
        lat_us = tick_us(lat, t, s)
        ded_us = tick_us(dedicated[s], t, s)
        emit(
            f"serve/multires_n{ns[s]}_warm_us", lat_us,
            f"N={ns[s]};B=1;cluster tier;lattice cell ({s}, 1), "
            f"per-tick steady state, k={k_lat}",
        )
        emit(
            f"serve/multires_n{ns[s]}_speedup_warm", ded_us / lat_us,
            f"N={ns[s]};x_dedicated_over_lattice;dedicated {s}px "
            f"engine (k={cfg.k}) vs the (B, N) lattice cell "
            f"(k={k_lat}: above native the ramp buys recall, so the "
            "bar is ~1.0 only at native size)",
        )


def _run_sharded(smoke):
    """Sharded-trace rows: the same ragged multi-tenant trace served by
    the mesh-native ring engine on a 1-device mesh and on a mesh over
    every local device (up to 4), in this process and on the devices
    JAX was given (DESIGN.md §10). On a TPU host that is the chips and
    their ICI; on a one-device host only the 1-device row exists."""
    from repro.launch.mesh import make_mesh
    from repro.models import vig
    from repro.models.module import init_params
    from repro.serve.engine import VigRequest, VigServeEngine

    res, waves = (32, (1, 3, 2, 4)) if smoke else (64, (1, 3, 4, 2, 4, 1))
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=res, patch=4, embed_dims=(32,), depths=(2,),
        num_classes=10, k=9, digc_impl="ring")
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    slots = 4
    images = [rng.standard_normal((res, res, 3)).astype(np.float32)
              for _ in range(slots)]
    wave_t = [[(w + i) % slots for i in range(size)]
              for w, size in enumerate(waves)]

    def trace(eng):
        uid = 0
        t0 = time.perf_counter()
        for wave in wave_t:
            for tenant in wave:
                eng.submit(VigRequest(uid=uid, image=images[tenant],
                                      tenant=tenant))
                uid += 1
            eng.step()
        return time.perf_counter() - t0

    total = sum(waves)
    platform = jax.devices()[0].platform
    for ndev in sorted({1, min(4, jax.device_count())}):
        mesh = make_mesh((ndev,), ("ring",))
        eng = VigServeEngine(cfg, params, digc_impl="ring", autotune=False,
                             buckets=(1, 2, 4), mesh=mesh, mesh_axis="ring")
        for phase, secs in (("cold", trace(eng)), ("warm", trace(eng))):
            emit(
                f"serve/sharded_mesh{ndev}_{phase}_us", secs / total * 1e6,
                f"N={cfg.base_grid ** 2};requests={total};"
                f"programs={eng.compile_count};ring mesh={ndev} "
                f"{platform} dev;per-request"
                + (";incl. compiles" if phase == "cold" else ";steady"),
            )


def _run_sched(smoke):
    """SLO-bounded admission scheduling rows (DESIGN.md §14): the
    auto-selected bucketed policy vs exact-size programs on a replayed
    ragged arrival trace (the shared seeded Poisson+burst generator,
    ``serve.sched.arrival_trace``).

    The baseline is a fixed-cadence exact-size server: one tick per
    ``window_ms`` of arrivals, ``buckets=None`` — each distinct tick
    size compiles its own program and sub-width windows dispatch as-is.
    The scheduled engine replays the same trace per-arrival under a
    ``VirtualClock`` with ``buckets="auto"``: singletons wait up to the
    SLO and coalesce into fuller bucketed ticks, with the bucket set
    picked by the arrival-histogram optimizer from a (stub-program)
    profiling pass over this very trace — the tick structure under a
    virtual clock is scheduler-only, so the profiling replay costs no
    compiles and its live-lane histogram is exactly the real engine's.
    Cold rows include compiles (cap'd program count vs one per distinct
    size); warm rows re-replay through compiled programs, where the
    win is per-tick fixed cost amortized over coalesced lanes.
    """
    from repro.core.state import DigcState
    from repro.models import vig
    from repro.models.module import init_params
    from repro.serve.engine import VigRequest, VigServeEngine
    from repro.serve.sched import VirtualClock, arrival_trace, replay

    # The scheduler's win regime is dispatch-bound serving: at N=256
    # eight warm singleton ticks cost ~1.5x one coalesced 8-tick
    # (per-tick fixed cost dominates), while at N=3136 the blocked
    # tier's per-lane cost grows with B on CPU (the superlinear-B
    # question, ROADMAP) and coalescing pays — so the rows measure the
    # regime the policy targets.
    if smoke:
        res, tenants, slots = 32, 4, 4
        trace_kw = dict(seed=0, tenants=4, poisson_ms=25.0, poisson_n=8,
                        burst_every_ms=120.0, burst_n=1, burst_size=3)
    else:
        res, tenants, slots = 64, 8, 8
        trace_kw = dict(seed=0, tenants=8, poisson_ms=25.0, poisson_n=48,
                        burst_every_ms=400.0, burst_n=3, burst_size=6)
    # slo ~ slots * poisson_ms: budget for a full slot width of
    # arrivals to coalesce, so steady-state ticks run full and the
    # live-lane histogram concentrates on few buckets (fewer compiles)
    window_ms, slo_ms, cap = 50.0, 300.0, 4
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=res, patch=4, embed_dims=(96,), depths=(2,),
        num_classes=10, k=9, digc_impl="blocked",
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    n = (res // 4) ** 2
    rng = np.random.default_rng(0)
    images = {
        f"t{i}": rng.standard_normal((res, res, 3)).astype(np.float32)
        for i in range(tenants)
    }
    arrivals = arrival_trace(**trace_kw)
    total = len(arrivals)

    # -- exact-size fixed-cadence baseline ------------------------------
    win: dict[int, list] = {}
    for a in arrivals:
        win.setdefault(int(a.t_ms // window_ms), []).append(a.tenant)
    waves = [win[k] for k in sorted(win)]

    def serve_windows(eng):
        uid = 0
        t0 = time.perf_counter()
        for wave in waves:
            for tenant in wave:
                eng.submit(VigRequest(uid=uid, image=images[tenant],
                                      tenant=tenant))
                uid += 1
            while eng.queue:  # a repeated tenant takes an extra tick
                eng.step()
        return time.perf_counter() - t0

    exact = VigServeEngine(cfg, params, digc_impl="blocked",
                           autotune=False, buckets=None, batch=slots)
    exact_cold = serve_windows(exact)
    exact_ticks = sum(exact.bucket_ticks.values())
    # warm: min of 3 steady-state passes (per-request times are ms-
    # scale here, so scheduler noise would otherwise dominate the row)
    exact_warm = min(serve_windows(exact) for _ in range(3))

    # -- profiling pass (stub programs) -> tuned bucket set -------------
    class _StubSched(VigServeEngine):
        def _build_program(self, bucket):
            def fake_fwd(params, imgs, state):
                new = DigcState(entries={
                    k: e.bump() for k, e in state.entries.items()
                })
                return (jnp.zeros((imgs.shape[0], self.cfg.num_classes),
                                  jnp.float32), new)

            return fake_fwd

    tuner_path = TUNE_CACHE if not smoke else os.path.join(
        tempfile.mkdtemp(prefix="digc_sched_smoke"), "tune.json")
    clock = VirtualClock()
    # buckets=None: slots == batch (the auto engine's serving shape)
    # and the live-lane histogram is bucket-independent regardless
    prof = _StubSched(cfg, params, digc_impl="blocked", autotune=False,
                      buckets=None, batch=slots, slo_ms=slo_ms,
                      clock=clock, bucket_cap=cap, tuner_path=tuner_path)
    replay(prof, arrivals, images, clock=clock)
    tuned = prof.retune_buckets()

    # -- scheduled engine on the tuned (auto) bucket set ----------------
    def sched_pass(eng, clk):
        # re-anchor the trace at the clock's current time so the warm
        # pass replays the same *relative* timing (the clock is
        # monotonic; absolute times from the cold pass are in its past)
        shift = clk.now() * 1e3
        shifted = [dataclasses.replace(a, t_ms=a.t_ms + shift)
                   for a in arrivals]
        t0 = time.perf_counter()
        ticks = replay(eng, shifted, images, clock=clk)
        return time.perf_counter() - t0, ticks

    clock = VirtualClock()
    auto = VigServeEngine(cfg, params, digc_impl="blocked",
                          autotune=False, buckets="auto", batch=slots,
                          bucket_cap=cap, slo_ms=slo_ms, clock=clock,
                          tuner_path=tuner_path)
    assert auto.buckets == tuned, (auto.buckets, tuned)
    auto_cold, cold_ticks = sched_pass(auto, clock)
    auto_warm = min(sched_pass(auto, clock)[0] for _ in range(3))
    util = auto.stats()["util"]

    emit(
        "serve/sched_exact_cold_us", exact_cold / total * 1e6,
        f"N={n};requests={total};programs={exact.compile_count};"
        f"ticks={exact_ticks};window_ms={window_ms:g};exact-size "
        "fixed-cadence baseline, per-request incl. compiles",
    )
    emit(
        "serve/sched_exact_warm_us", exact_warm / total * 1e6,
        f"N={n};requests={total};steady state, programs compiled",
    )
    emit(
        "serve/sched_auto_cold_us", auto_cold / total * 1e6,
        f"N={n};requests={total};programs={auto.compile_count};"
        f"ticks={len(cold_ticks)};buckets={tuned};slo_ms={slo_ms:g};"
        f"deferrals={auto.deferrals};auto-tuned bucketed scheduler, "
        "per-request incl. compiles",
    )
    emit(
        "serve/sched_auto_warm_us", auto_warm / total * 1e6,
        f"N={n};requests={total};util={util:.3f};steady state",
    )
    for phase, ex, au in (("cold", exact_cold, auto_cold),
                          ("warm", exact_warm, auto_warm)):
        emit(
            f"serve/sched_speedup_{phase}", ex / au,
            f"N={n};requests={total};x_exact_over_auto;"
            f"auto_programs={auto.compile_count};"
            f"exact_programs={exact.compile_count} "
            "(>=1 means the SLO-scheduled auto-bucketed policy wins)",
        )


if __name__ == "__main__":
    run()
