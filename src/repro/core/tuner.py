"""Workload autotuner for the streaming DIGC engine.

GraphLeap's lesson (PAPERS.md, arXiv 2604.21290) is that a decoupled
construction dataflow leaves most of its headroom on the table until
the tile/merge configuration is *tuned per workload*. This module
picks ``(block_n, block_m, merge, fuse_norms)`` — or a fused-kernel
config ``(impl="pallas", block_n, block_m, kernel_merge)`` — per
``(backend, B, N, M, D, kd, causal, pos_bias)`` workload, so
kernel-vs-engine is a measured per-workload choice, not a code path:

  1. rank the candidate grid with the analytical cost models
     (``perfmodel.engine_cost_estimate`` for engine schedules,
     ``perfmodel.kernel_cost_estimate`` for kernel configs — the
     latter's interpret-mode penalty keeps emulated kernels out of the
     measured top-N off-TPU while compiled TPU configs compete on
     roofline terms) — priors;
  2. measure the top-ranked candidates on the live workload arrays
     (median wall time over a few jitted calls) — refinement;
  3. verify each measured candidate's indices against an
     exact-by-construction oracle config on the same probe input, so a
     tie-tolerant variant (``fuse_norms``) is only ever chosen when it
     matched exactly on the workload it will serve;
  4. persist the winner to a JSON cache keyed by the workload so later
     runs (and serving engines) skip the measurement entirely.

The tuner never changes *what* is computed — only the engine schedule.
Approximate merges (``packed``) are excluded unless ``allow_approx``.

The JSON cache is **host-keyed** (schema 3): entries nest under
``host_key()`` = backend + platform + jax version, so a schedule tuned
on one machine is never silently reused on another — a laptop's
block_n=512 is not a v5e's. Each host slot holds two stores:
``"schedules"`` (the tile measurements above, keyed by
``workload_key``) and ``"bucket_sets"`` (the serving engine's
arrival-histogram bucket-set choices, keyed by ``bucket_set_key`` —
see ``optimal_bucket_set``/``tune_bucket_set``, DESIGN.md §14).
Schema-2 files (hosts mapping straight to schedule entries) migrate
losslessly on load — the measurements stay valid, only the nesting
moved. Schema-1 files (flat, backend-only keys) are not migrated:
their entries cannot be attributed to a host, so they are dropped on
load and re-measured.

``VigSchedule`` maps pyramid stages to tuned specs:
``DigcTuner.tune_schedule`` tunes each stage's (N, M, D, kd) workload
separately — the PR-2 engine applied the stage-0 schedule everywhere,
but a pooled stage (M = N/r²) or a downsampled one (N/4) wants
different tiles.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import platform
import time
from pathlib import Path
from typing import Any, Optional, Sequence

import numpy as np

from repro.core.builder import DigcSpec
from repro.core.perfmodel import (
    engine_cost_estimate,
    kernel_cost_estimate,
    kernel_tile_defaults,
)

# Knobs the tuner owns on a DigcSpec.
TUNED_KNOBS = ("block_n", "block_m", "merge", "fuse_norms", "kernel_merge")

_BLOCK_N_CANDIDATES = (None, 256, 512, 1024)
_BLOCK_M_CANDIDATES = (256, 512, 1024, 2048, 4096)
_EXACT_MERGES = ("select", "topk")
# Fused-kernel candidates compete as first-class configs: the LSM/GMM
# realization is a measured per-workload choice (ISSUE 6 tentpole).
_KERNEL_MERGES = ("bitonic", "legacy")
_KERNEL_TILE_FALLBACKS = ((128, 256), (256, 512))


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One schedule — engine tiles *or* a fused-kernel config: the
    tuner's unit of search. ``impl`` picks the tier ("blocked" engine
    schedules keep their historical field meanings; "pallas" configs
    carry kernel tile dims + the ``kernel_merge`` variant and use
    ``merge="kernel"`` as a display placeholder)."""

    block_n: Optional[int]
    block_m: int
    merge: str
    fuse_norms: bool = False
    impl: str = "blocked"
    kernel_merge: Optional[str] = None

    def apply(self, spec: DigcSpec) -> DigcSpec:
        if self.impl == "pallas":
            return spec.replace(
                impl="pallas",
                block_n=self.block_n,
                block_m=self.block_m,
                kernel_merge=self.kernel_merge,
                # engine-only knobs must be unset for the kernel builder
                merge=None,
                fuse_norms=None,
                group_w=None,
            )
        return spec.replace(
            block_n=self.block_n,
            block_m=self.block_m,
            merge=self.merge,
            fuse_norms=self.fuse_norms or None,
        )

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class TuneResult:
    config: TileConfig
    us_per_call: float
    exact_match: bool
    source: str  # "measured" | "cached" | "prior"

    def as_dict(self) -> dict:
        return {
            **self.config.as_dict(),
            "us_per_call": self.us_per_call,
            "exact_match": self.exact_match,
            "source": self.source,
        }


def host_key(backend: Optional[str] = None) -> str:
    """Identity of the measuring host: backend + platform + jax version.

    A tuned schedule is a *measurement* of this machine; entries under a
    different host key are never read (and a jax upgrade re-measures —
    compiler changes move the optimum).
    """
    import jax

    backend = backend if backend is not None else jax.default_backend()
    return (
        f"{backend}|{platform.system().lower()}-{platform.machine()}"
        f"|jax-{jax.__version__}"
    )


def workload_key(
    b: int, n: int, m: int, d: int, kd: int,
    causal: bool = False, has_pos: bool = False,
    mesh_shape: Optional[tuple[int, ...]] = None,
) -> str:
    """Workload identity within one host (see ``host_key``).

    ``mesh_shape`` (device counts per mesh axis, ``DigcSpec.
    mesh_shape()``) keys sharded workloads separately: a schedule
    measured with co-nodes rotating a 4-device ring is a different
    measurement from the single-device tile sweep, even at identical
    (B, N, M) — the per-hop tile is M/n_dev wide and the ICI transfer
    is part of the measured step. Unsharded workloads (the common
    case) keep their historical keys. Today this is a *forward guard*:
    ``tune()`` only measures the blocked tier, which carries no mesh
    knob — the suffix exists so the committed single-device entries
    can never be clobbered (or mis-served) the day a sharded tier
    becomes measurable (ROADMAP: ring on real ICI).
    """
    key = f"b{b}:n{n}:m{m}:d{d}:kd{kd}"
    if causal:
        key += ":causal"
    if has_pos:
        key += ":pos"
    if mesh_shape:
        key += ":mesh" + "x".join(str(s) for s in mesh_shape)
    return key


def bucket_set_key(slots: int, sizes, max_programs: int) -> str:
    """Identity of one serving shape for bucket-set persistence: the
    slot count, the configured N-bucket image sizes, and the
    compile-count cap. Unlike schedules (measurements of a machine), a
    bucket set is a property of the *arrival trace* — but it is stored
    under the host key anyway, because the trace that produced it was
    served on this host and another machine's replica should re-profile
    its own traffic."""
    return (
        f"slots{int(slots)}:cap{int(max_programs)}:sizes"
        + "-".join(str(int(s)) for s in sorted(sizes))
    )


def optimal_bucket_set(
    hist, *, slots: int, max_programs: int = 4, costs=None,
) -> tuple[int, ...]:
    """The (B, N) bucket set minimizing expected padded-lane work under
    a compile-count cap (DESIGN.md §14).

    ``hist`` is a serving engine's live-lane histogram — ``{size:
    {live: ticks}}``, or a flat ``{live: ticks}`` for single-size
    traffic: how many ticks served exactly ``live`` lanes at each
    N-bucket. Under bucket set S, a tick at ``live`` lanes pays
    ``min(b in S : b >= live)`` lanes of compute (padding lanes run the
    full forward), weighted by ``costs[size]`` (per-lane work, e.g. the
    patch count N; default 1). The optimizer minimizes

        sum_{size, live} hist[size][live] * bucket_S(live) * cost[size]

    by brute force over subsets of the *observed* live counts — an
    optimal bucket boundary always sits on an observed count, so the
    candidate pool is tiny (at most ``slots`` values) — of at most
    ``max_programs`` buckets, always including ``slots`` so every
    admissible tick fits. Ties break deterministically: least work,
    then fewest buckets, then lexicographically smallest — a fixed
    trace always selects the same set. An empty histogram returns
    ``(slots,)``."""
    slots = int(slots)
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    if int(max_programs) < 1:
        raise ValueError(f"max_programs must be >= 1, got {max_programs}")
    if hist and not isinstance(next(iter(hist.values())), dict):
        hist = {None: hist}
    weights: dict[tuple, float] = {}
    for size, per in (hist or {}).items():
        cost = 1.0 if costs is None else float(costs.get(size, 1.0))
        for live, ticks in per.items():
            live = int(live)
            if not 1 <= live <= slots:
                raise ValueError(
                    f"histogram live-lane count {live} outside "
                    f"1..slots={slots}"
                )
            weights[(size, live)] = (
                weights.get((size, live), 0.0) + float(ticks) * cost
            )
    if not weights:
        return (slots,)
    pool = sorted({live for _, live in weights if live < slots})
    best = None
    for r in range(min(int(max_programs) - 1, len(pool)) + 1):
        for extra in itertools.combinations(pool, r):
            cand = tuple(sorted(set(extra) | {slots}))
            work = sum(
                w * min(b for b in cand if b >= live)
                for (_, live), w in weights.items()
            )
            key = (work, len(cand), cand)
            if best is None or key < best:
                best = key
    return best[2]


class DigcTuner:
    """Prior-ranked, measurement-refined, JSON-persisted tile tuner."""

    def __init__(
        self,
        path: Optional[str | Path] = None,
        *,
        backend: Optional[str] = None,
        measure_iters: int = 2,
        max_measure: int = 6,
    ):
        import jax

        self.path = Path(path) if path is not None else None
        self.backend = backend if backend is not None else jax.default_backend()
        self.host = host_key(self.backend)
        self.measure_iters = measure_iters
        self.max_measure = max_measure
        # Full file contents (all hosts) are preserved on save; only
        # this host's entries are ever *read*. Schema 3 nests each
        # host's stores by kind: {"schedules": {workload key: tile},
        # "bucket_sets": {serving-shape key: bucket set}}.
        self._hosts: dict[str, dict] = {}
        if self.path is not None and self.path.exists():
            data = json.loads(self.path.read_text())
            if data.get("schema") == 3:
                self._hosts = {
                    h: {"schedules": dict(v.get("schedules", {})),
                        "bucket_sets": dict(v.get("bucket_sets", {}))}
                    for h, v in data.get("hosts", {}).items()
                }
            elif data.get("schema") == 2:
                # schema-2 migration: hosts mapped straight to their
                # schedule entries. The measurements stay valid — only
                # the nesting moved — so lift them under "schedules"
                # and start each host with an empty bucket-set store.
                self._hosts = {
                    h: {"schedules": dict(e), "bucket_sets": {}}
                    for h, e in data.get("hosts", {}).items()
                }
            # schema 1: flat backend-keyed entries with no platform/jax
            # identity — unattributable, so dropped (re-measured here).
        _slot = self._hosts.setdefault(
            self.host, {"schedules": {}, "bucket_sets": {}}
        )
        self.entries: dict[str, dict] = _slot["schedules"]
        self.bucket_sets: dict[str, dict] = _slot["bucket_sets"]

    # -- candidate generation -------------------------------------------

    def candidates(
        self, n: int, m: int, *, d: Optional[int] = None,
        kd: Optional[int] = None, allow_approx: bool = False
    ) -> list[TileConfig]:
        block_ns = {bn if (bn is None or bn < n) else None
                    for bn in _BLOCK_N_CANDIDATES}
        block_ms = {min(bm, m) for bm in _BLOCK_M_CANDIDATES}
        block_ms.add(m)
        merges = list(_EXACT_MERGES) + (["packed"] if allow_approx else [])
        out = []
        for bn in sorted(block_ns, key=lambda v: -1 if v is None else v):
            for bm in sorted(block_ms):
                for merge in merges:
                    for fuse in (False, True):
                        out.append(TileConfig(bn, bm, merge, fuse))
        # Fused-kernel configs: the VMEM-budgeted workload default tile
        # plus fixed fallbacks, each with both LSM/GMM realizations.
        # All exact (unpacked), so they verify against the same oracle.
        kernel_tiles = set(_KERNEL_TILE_FALLBACKS)
        if d is not None and kd is not None:
            kernel_tiles.add(kernel_tile_defaults(n, m, d, kd))
        for bn, bm in sorted(kernel_tiles):
            for km in _KERNEL_MERGES:
                out.append(TileConfig(bn, bm, "kernel", False,
                                      impl="pallas", kernel_merge=km))
        return out

    def rank(
        self, cands: list[TileConfig], *, b, n, m, d, kd
    ) -> list[TileConfig]:
        def prior(cfg: TileConfig) -> float:
            if cfg.impl == "pallas":
                return kernel_cost_estimate(
                    n, m, d, kd, b=b, block_n=cfg.block_n or 128,
                    block_m=cfg.block_m,
                    kernel_merge=cfg.kernel_merge or "bitonic",
                    backend=self.backend,
                )["total_s"]
            return engine_cost_estimate(
                n, m, d, kd, b=b, block_n=cfg.block_n, block_m=cfg.block_m,
                merge=cfg.merge, fuse_norms=cfg.fuse_norms,
                backend=self.backend,
            )["total_s"]

        return sorted(cands, key=prior)

    # -- persistence ----------------------------------------------------

    def lookup(self, key: str) -> Optional[TuneResult]:
        e = self.entries.get(key)
        if e is None:
            return None
        return TuneResult(
            TileConfig(e["block_n"], e["block_m"], e["merge"],
                       e.get("fuse_norms", False),
                       # pre-PR-6 entries are engine schedules
                       e.get("impl", "blocked"),
                       e.get("kernel_merge")),
            e.get("us_per_call", float("nan")),
            e.get("exact_match", True),
            "cached",
        )

    def save(self) -> None:
        if self.path is None:
            return
        self.path.write_text(json.dumps(
            {"schema": 3, "hosts": self._hosts},
            indent=2, sort_keys=True,
        ) + "\n")

    def lookup_bucket_set(
        self, *, slots: int, sizes, max_programs: int = 4,
    ) -> Optional[tuple[int, ...]]:
        """The persisted bucket set for one serving shape, or None."""
        e = self.bucket_sets.get(bucket_set_key(slots, sizes, max_programs))
        if e is None:
            return None
        return tuple(int(b) for b in e["buckets"])

    def tune_bucket_set(
        self, hist, *, slots: int, max_programs: int = 4, costs=None,
        sizes=None, force: bool = False,
    ) -> tuple[int, ...]:
        """Persisted ``optimal_bucket_set``: derive the bucket set from
        an arrival histogram and cache it per host + serving shape,
        exactly like tuned schedules — a later engine constructed with
        ``buckets="auto"`` and the same tuner path starts on it without
        re-profiling. ``sizes`` pins the shape key (default: the
        histogram's own size keys); the histogram itself is recorded in
        the entry so a cached choice stays auditable."""
        if hist and not isinstance(next(iter(hist.values())), dict):
            hist = {None: hist}
        if sizes is None:
            sizes = sorted(s for s in (hist or {}) if s is not None)
        key = bucket_set_key(slots, sizes, max_programs)
        if not force:
            e = self.bucket_sets.get(key)
            if e is not None:
                return tuple(int(b) for b in e["buckets"])
        buckets = optimal_bucket_set(
            hist, slots=slots, max_programs=max_programs, costs=costs
        )
        self.bucket_sets[key] = {
            "buckets": list(buckets),
            "hist": {
                f"{'any' if s is None else s}:{live}": int(t)
                for s, per in (hist or {}).items()
                for live, t in sorted(per.items())
            },
        }
        self.save()
        return buckets

    # -- tuning ---------------------------------------------------------

    def tune(
        self,
        x,
        y=None,
        *,
        spec: DigcSpec,
        pos_bias=None,
        force: bool = False,
        allow_approx: bool = False,
    ) -> tuple[DigcSpec, TuneResult]:
        """Fill the engine-schedule knobs of ``spec`` for this workload.

        Measures on the live arrays (so the cache records what this
        host actually does), verifies candidates against an exact
        oracle config on the same probe input, persists the winner.
        Returns (tuned spec, result). Only the ``blocked`` engine tier
        is tunable; other impls pass through unchanged.
        """
        import jax

        from repro.core.digc import digc

        if spec.impl != "blocked":
            return spec, TuneResult(
                TileConfig(spec.block_n, spec.block_m or 0,
                           spec.merge or "n/a"),
                float("nan"), True, "prior",
            )
        x3 = x if x.ndim == 3 else x[None]
        b, n, d = x3.shape
        m = n if y is None else (y.shape[-2])
        kd = spec.k * spec.dilation
        key = workload_key(b, n, m, d, kd, spec.causal,
                           pos_bias is not None,
                           mesh_shape=spec.mesh_shape())
        if not force:
            cached = self.lookup(key)
            if cached is not None:
                return cached.config.apply(spec), cached

        cands = self.rank(
            self.candidates(n, m, d=d, kd=kd, allow_approx=allow_approx),
            b=b, n=n, m=m, d=d, kd=kd,
        )[: self.max_measure]

        def run(cfg: TileConfig):
            s = cfg.apply(spec)
            fn = jax.jit(lambda a, by: digc(
                a, by, spec=s, pos_bias=pos_bias, return_dists=True,
            ))
            out = jax.block_until_ready(fn(x, y))
            times = []
            for _ in range(self.measure_iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, y))
                times.append(time.perf_counter() - t0)
            return out, float(np.median(times))

        oracle_cfg = TileConfig(None, m, "select", False)
        oracle_out, oracle_t = run(oracle_cfg)
        oracle_idx = np.asarray(oracle_out[0])
        results = [TuneResult(oracle_cfg, oracle_t * 1e6, True, "measured")]
        for cfg in cands:
            if cfg == oracle_cfg:
                continue
            out, t = run(cfg)
            match = bool(np.array_equal(np.asarray(out[0]), oracle_idx))
            results.append(TuneResult(cfg, t * 1e6, match, "measured"))

        eligible = [
            r for r in results
            if r.exact_match or (allow_approx and r.config.merge == "packed")
        ]
        best = min(eligible, key=lambda r: r.us_per_call)
        self.entries[key] = best.as_dict()
        self.save()
        return best.config.apply(spec), best

    # -- per-stage schedules --------------------------------------------

    def tune_schedule(
        self,
        workloads: Sequence[dict],
        *,
        spec: DigcSpec,
        batch: int = 1,
        rng_seed: int = 0,
        force: bool = False,
    ) -> tuple["VigSchedule", list[TuneResult]]:
        """Tune one engine schedule per model stage.

        ``workloads`` is one dict per stage — ``{"N", "M", "D", "k",
        "dilation"}``, e.g. the first row of each stage from
        ``models.vig.count_digc_work`` — measured on synthetic probe
        arrays of the stage's true shape (pooled stages tune the real
        (N, M) workload, not a self-graph stand-in). Returns the
        ``VigSchedule`` plus the per-stage results; cached entries are
        served without re-measurement. The measured k and dilation only
        choose the tiles: each stage's spec keeps ``spec``'s own k and
        dilation, which stay model-owned (``models.vig.vig_stage_plans``
        derives every block's k from them, its ``num_knn`` schedule and
        the serving grid; a workload row's k is already resolved to
        one block at one grid).
        """
        import jax.numpy as jnp

        rng = np.random.default_rng(rng_seed)
        stages: list[DigcSpec] = []
        results: list[TuneResult] = []
        for work in workloads:
            probe = jnp.asarray(
                rng.standard_normal((batch, work["N"], work["D"])),
                jnp.float32,
            )
            y_probe = None
            if work["M"] != work["N"]:
                y_probe = jnp.asarray(
                    rng.standard_normal((batch, work["M"], work["D"])),
                    jnp.float32,
                )
            stage_spec = spec.replace(
                k=work["k"], dilation=work["dilation"],
                block_n=None, block_m=None, merge=None, fuse_norms=None,
                kernel_merge=None,
            )
            tuned, result = self.tune(probe, y_probe, spec=stage_spec,
                                      force=force)
            stages.append(tuned.replace(k=spec.k, dilation=spec.dilation))
            results.append(result)
        return VigSchedule(stages=tuple(stages)), results

    def tune_bucket_schedules(
        self,
        workloads: Sequence[dict],
        *,
        spec: DigcSpec,
        buckets: Sequence[int],
        rng_seed: int = 0,
        force: bool = False,
    ) -> tuple[dict[int, "VigSchedule"], dict[int, list[TuneResult]]]:
        """One ``VigSchedule`` per serving bucket (bucketed multi-tenant
        serving, DESIGN.md §9).

        The workload key includes the batch size, and a bucketed engine
        serves each request batch padded to a bucket — so the schedule
        must be resolved **per bucket**, not per request batch: a
        B=8-tuned tile is not a B=1-tuned tile. Returns ``{bucket:
        schedule}`` plus the per-bucket results; previously-measured
        (host-keyed) entries are served from the JSON cache.
        """
        schedules: dict[int, VigSchedule] = {}
        results: dict[int, list[TuneResult]] = {}
        for b in sorted(set(int(v) for v in buckets)):
            schedules[b], results[b] = self.tune_schedule(
                workloads, spec=spec, batch=b, rng_seed=rng_seed,
                force=force,
            )
        return schedules, results


@dataclasses.dataclass
class ReuseTuneResult:
    """One measured point of the reuse-policy search (DESIGN.md §12)."""

    policy: str
    drift_tau: float
    max_stale: int
    reuse_frac: float  # fraction of calls served from the cached graph
    recall: float      # neighbor recall of served vs per-call exact
    admitted: bool     # recall >= floor
    n: Optional[int] = None  # node count, when the trace is single-N

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def scale_tau(tau: float, n_ref: int, n: int) -> float:
    """Normalize a drift gate across N-buckets (DESIGN.md §13).

    ``drift_stat`` is a per-row mean of |x|^2 over the N nodes, so its
    tick-to-tick relative fluctuation shrinks ~1/sqrt(N): a tau
    admitted at the reference bucket ``n_ref`` under-gates (spurious
    rebuilds) at a smaller N and over-gates at a larger one. Widening
    by sqrt(n_ref / n) keeps the false-rebuild rate comparable across
    buckets; tau=0 stays exactly 0 (the bit-identity contract), and
    the statistic itself is untouched — the serving gate's formula is
    pinned by the stale-graph tests."""
    if tau == 0.0:
        return 0.0
    return float(tau) * float(np.sqrt(n_ref / max(n, 1)))


def _served_recall(served: np.ndarray, exact: np.ndarray) -> float:
    k = exact.shape[-1]
    s = served.reshape(-1, k)
    e = exact.reshape(-1, k)
    hits = 0
    for i in range(e.shape[0]):
        hits += len(set(e[i]) & set(s[i]))
    return hits / e.size


def tune_reuse(
    ticks: Sequence[Sequence[tuple]],
    *,
    spec: DigcSpec,
    policy: str = "tick",
    taus: Sequence[float] = (0.02, 0.05, 0.1, 0.2),
    max_stale: int = 4,
    recall_floor: float = 0.95,
) -> tuple[DigcSpec, list[ReuseTuneResult]]:
    """Pick the widest drift gate that keeps served-graph recall above
    ``recall_floor``, by replaying a captured feature trace through the
    stale-graph gate (DESIGN.md §12).

    ``ticks`` is a sequence of ``digc_capture`` lists — one per
    consecutive ``models.vig.vig_forward`` call on the live request
    stream, each holding ``(layer_key, h, cond[, idx])`` per DIGC call
    (the served ``idx`` is read only for its width, the block's own k,
    which a ``num_knn`` ramp varies inside a stage; without it the
    spec's k applies, and the replay builds its own lists). The
    replay mirrors ``core.digc._reuse_build`` exactly (same drift
    statistic, same strict ``<`` gate, same staleness bound) but runs
    host-side against per-call exact graphs, so every candidate tau's
    *served* recall — cached rows scored against what a rebuild would
    have returned — is measured, not estimated. Among candidates whose
    mean recall clears the floor, the one skipping the most builds
    wins; if none clears it, reuse stays off (the returned spec is
    unchanged). A wider tau never lowers reuse, so this is the
    recall-constrained maximum of the swept grid.

    **Mixed resolutions** (DESIGN.md §13): ``drift_stat`` is a mean
    |x|^2 over the N nodes, so a trace that interleaves N-buckets
    under one layer key would (a) compare snapshots across unrelated
    resolutions and (b) mis-gate a tau admitted at one N when applied
    at another. The replay therefore groups per (layer_key, N) — its
    own cache stream per N-bucket, exactly how the lattice engine
    keys per-size state — and evaluates each group at the per-N
    effective gate ``scale_tau(tau, n_ref, n)`` (n_ref = the largest
    N in the trace, whose gate is the nominal tau). tau=0 scales to
    exactly 0 in every bucket — the bit-identity contract holds
    per-bucket.
    """
    from repro.core.digc import digc, drift_stat

    if policy not in ("layer", "tick", "overlap"):
        raise ValueError(f"tune_reuse: unknown policy {policy!r}")
    base = spec.replace(reuse=None, drift_tau=None, max_stale=None)

    # Group the trace per (graph-cache entry, N-bucket), preserving
    # tick structure, and compute each call's exact graph + drift
    # statistic once.
    per_key: dict[tuple, list[list[dict]]] = {}
    for tick in ticks:
        seen_this_tick: dict[tuple, int] = {}
        for layer_key, h, cond, *idx in tick:
            x3 = h if h.ndim == 3 else h[None]
            m = cond.shape[-2] if cond is not None else x3.shape[-2]
            dil = max(base.dilation, 1)
            k = idx[0].shape[-1] if idx else base.k
            k_eff = min(k, m // dil) or 1
            if k_eff * dil > m:
                dil = 1
            call_spec = base.replace(k=k_eff, dilation=dil)
            gkey = (layer_key, int(x3.shape[-2]))
            first = gkey not in seen_this_tick
            seen_this_tick[gkey] = 1
            rows = per_key.setdefault(gkey, [])
            if first:
                rows.append([])
            rows[-1].append({
                "exact": np.asarray(digc(x3, cond, spec=call_spec)),
                "stat": np.asarray(drift_stat(x3)),
            })

    ns = sorted({n for _, n in per_key})
    n_ref = ns[-1] if ns else 1
    single_n = ns[0] if len(ns) == 1 else None
    results: list[ReuseTuneResult] = []
    for tau in sorted(set(float(t) for t in taus)):
        recalls: list[float] = []
        reused = 0
        total = 0
        for (_, n), calls_by_tick in per_key.items():
            tau_n = scale_tau(tau, n_ref, n)
            cached = snap = age = None
            for calls in calls_by_tick:
                for ci, call in enumerate(calls):
                    stat, exact = call["stat"], call["exact"]
                    total += stat.shape[0]
                    if cached is not None and cached.shape != exact.shape:
                        # a block of another k than the cached graph's
                        # (a num_knn ramp): the gate cannot engage, and
                        # the build leaves the cache as it was
                        recalls.append(1.0)
                        continue
                    if cached is None:
                        reuse_row = np.zeros(stat.shape, bool)
                    elif policy == "overlap":
                        reuse_row = np.ones(stat.shape, bool)
                    elif policy == "tick" and ci > 0:
                        reuse_row = np.ones(stat.shape, bool)
                    else:
                        drift = (np.abs(stat - snap)
                                 / np.maximum(np.abs(snap), 1e-9))
                        reuse_row = (age < max_stale) & (drift < tau_n)
                    reused += int(reuse_row.sum())
                    if reuse_row.all() and policy != "overlap":
                        served = cached
                        age = age + (0 if policy == "tick" and ci > 0
                                     else 1)
                    else:
                        sel = reuse_row.reshape(
                            reuse_row.shape + (1,) * (exact.ndim - 1))
                        served = (np.where(sel, cached, exact)
                                  if cached is not None else exact)
                        cached, snap = exact, stat
                        age = np.where(reuse_row,
                                       (age if age is not None else 0) + 1,
                                       0)
                    recalls.append(_served_recall(served, exact))
        recall = float(np.mean(recalls)) if recalls else 1.0
        frac = reused / total if total else 0.0
        results.append(ReuseTuneResult(
            policy, tau, max_stale, frac, recall,
            bool(recall >= recall_floor), n=single_n,
        ))
        if policy == "overlap":
            break  # tau does not enter the overlap gate

    admitted = [r for r in results if r.admitted]
    if not admitted:
        return spec, results
    best = max(admitted, key=lambda r: (r.reuse_frac, r.drift_tau))
    return spec.replace(reuse=policy, drift_tau=best.drift_tau,
                        max_stale=max_stale), results


@dataclasses.dataclass(frozen=True)
class VigSchedule:
    """Stage -> tuned ``DigcSpec`` map for a pyramid/isotropic model.

    The PR-2 engine tuned the stage-0 workload and applied those knobs
    to every stage; a schedule gives each stage its own measured entry
    (later pyramid stages run at N/4, N/16, ... and pooled co-nodes —
    different optimal tiles). Stages beyond the tuple reuse the last
    entry, so an isotropic model's schedule is one spec.
    """

    stages: tuple[DigcSpec, ...]

    def spec_for(self, si: int) -> DigcSpec:
        if not self.stages:
            raise ValueError("empty VigSchedule")
        return self.stages[min(si, len(self.stages) - 1)]

    def with_reuse(
        self,
        policy: Optional[str],
        drift_tau: Optional[float] = None,
        max_stale: Optional[int] = None,
    ) -> "VigSchedule":
        """Overlay a stale-graph reuse policy (DESIGN.md §12) on every
        stage whose tier carries construction state. Stateless tiers
        (e.g. the fused Pallas kernel) keep their tuned spec unchanged
        — their builders have no cache to serve from, and the knobs
        would be rejected by ``validate``. ``policy=None`` strips the
        reuse knobs everywhere."""
        from repro.core.builder import get_builder

        stages = []
        for s in self.stages:
            if policy is None:
                stages.append(s.replace(reuse=None, drift_tau=None,
                                        max_stale=None))
            elif get_builder(s.impl).supports_state:
                stages.append(s.replace(reuse=policy, drift_tau=drift_tau,
                                        max_stale=max_stale))
            else:
                stages.append(s)
        return VigSchedule(stages=tuple(stages))

    def describe(self) -> list[dict]:
        return [
            {
                "stage": si,
                "impl": s.impl,
                "block_n": s.block_n,
                "block_m": s.block_m,
                "merge": s.merge,
                "fuse_norms": bool(s.fuse_norms),
                "kernel_merge": s.kernel_merge,
                "reuse": s.reuse,
            }
            for si, s in enumerate(self.stages)
        ]


def autotune_spec(
    x,
    y=None,
    *,
    spec: DigcSpec,
    pos_bias=None,
    path: Optional[str | Path] = None,
    tuner: Optional[DigcTuner] = None,
    **kw,
) -> tuple[DigcSpec, TuneResult]:
    """One-shot convenience: tune ``spec``'s engine schedule for x/y."""
    tuner = tuner if tuner is not None else DigcTuner(path)
    return tuner.tune(x, y, spec=spec, pos_bias=pos_bias, **kw)
