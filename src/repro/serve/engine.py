"""Batched serving engines.

* ``ServeEngine`` — LM prefill + decode with a slot-based batch
  (continuous-batching-lite). Requests occupy fixed batch slots;
  finished slots are refilled from the queue without stalling in-flight
  decodes. Per-slot lengths are tracked host-side; a decode tick is a
  **single** jit'd call over the full slot batch even when slot
  lengths differ (static shapes — production TPU serving style):
  ``decode_step`` takes the per-slot position *vector*, each row
  writing its cache at its own position. Every cache write still
  carries an explicit per-slot commit mask, so prefilling one slot can
  never clobber an in-flight neighbor's cache rows.
* ``VigServeEngine`` — multi-tenant ViG image serving with
  cross-request DIGC state (DESIGN.md §9): a host-side request queue
  feeds fixed slots, each engine tick pads the active slots to a small
  static **bucket** (default {1, 2, 4, 8}) and serves it through one
  donated jit program per bucket — at most |bucket set| compiled
  programs no matter how ragged the arrival stream. Per-slot
  ``DigcState`` rows (cluster centroids, gallery norms, per-row step
  counters) are gathered into the bucket batch and scattered back for
  live lanes only, so a tenant's warm start follows it across buckets
  and padding lanes never touch live state.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.faults import FaultError, FaultInfo
from repro.models.config import ModelConfig
from repro.models import transformer as tr
from repro.serve.tracer import EngineTracer


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 16
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


def _merge_cache_rows(new, old, keep, cfg: ModelConfig):
    """Commit ``new`` cache rows only where ``keep`` (B,) is True.

    ``decode_step`` writes its k/v (or recurrent state) for **every**
    batch row — each at its own per-slot position now, but idle and
    draining slots still decode garbage tokens — so a per-slot engine
    must mask the commit, or inactive slots get garbage written into
    their caches. Leaves carry the batch axis at 1 when layer-stacked
    (the scan layout, (L, B, ...)) and at 0 for the unstacked hybrid
    remainder entries ((B, ...)).
    """

    def merge(axis):
        def f(n, o):
            shape = [1] * n.ndim
            shape[axis] = keep.shape[0]
            return jnp.where(keep.reshape(shape), n, o)

        return f

    if cfg.family == "hybrid":
        return {
            "groups": jax.tree_util.tree_map(
                merge(1), new["groups"], old["groups"]
            ),
            "rem": jax.tree_util.tree_map(merge(0), new["rem"], old["rem"]),
        }
    return jax.tree_util.tree_map(merge(1), new, old)


class ServeEngine:
    """Greedy-decoding engine over the functional model API."""

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 512):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.cache = tr.init_cache(cfg, slots, max_len)
        self.slot_req: list[Optional[Request]] = [None] * slots
        self.slot_pos = np.zeros(slots, np.int32)
        self.queue: list[Request] = []
        self.decode_calls = 0  # observability: jitted steps issued

        def _decode(p, c, t, pos, keep):
            logits, new_c = tr.decode_step(p, c, t, pos, cfg)
            return logits, _merge_cache_rows(new_c, c, keep, cfg)

        # The cache is donated: the commit-mask merge rewrites every
        # leaf, and the caller always replaces self.cache with the
        # result, so XLA may update the old buffers in place instead of
        # doubling the KV cache's memory traffic each step.
        self._decode = jax.jit(_decode, donate_argnums=(1,))

    def submit(self, req: Request):
        if len(req.prompt) == 0:
            raise ValueError(
                f"request {req.uid}: empty prompt (prefill needs at "
                "least one token to produce a next-token distribution)"
            )
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.uid}: max_new_tokens must be >= 1 "
                "(prefill always emits the first token)"
            )
        self.queue.append(req)

    def _step_decode(self, tokens, pos, members: list[int]):
        """One jitted decode committing only ``members``' cache rows.
        ``pos`` is the (slots,) per-slot position vector — a single
        call serves arbitrarily mixed-length slots (DESIGN.md §9)."""
        keep = np.zeros(self.slots, bool)
        keep[members] = True
        self.decode_calls += 1
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(pos, dtype=jnp.int32), jnp.asarray(keep),
        )
        return logits

    def _prefill_one(self, slot: int, req: Request):
        """Feed the prompt through decode steps (token-by-token prefill;
        simple and cache-layout-identical to decode). Only this slot's
        cache rows are committed — other slots may be mid-decode at
        overlapping positions."""
        for t, tok in enumerate(req.prompt):
            tokens = np.zeros((self.slots, 1), np.int32)
            tokens[slot, 0] = tok
            logits = self._step_decode(
                tokens, np.full(self.slots, t, np.int32), [slot]
            )
        self.slot_pos[slot] = len(req.prompt)
        nxt = int(jnp.argmax(logits[slot, -1]))
        req.out_tokens.append(nxt)
        if len(req.out_tokens) >= req.max_new_tokens:
            req.done = True  # budget met by the prefill token itself

    def step(self) -> int:
        """One engine tick: refill slots, one decode step for the whole
        batch. Returns number of active requests."""
        for s in range(self.slots):
            if self.slot_req[s] is None or self.slot_req[s].done:
                if self.queue:
                    req = self.queue.pop(0)
                    self.slot_req[s] = req
                    self._prefill_one(s, req)
        active = [s for s in range(self.slots)
                  if self.slot_req[s] is not None and not self.slot_req[s].done]
        if not active:
            return 0
        # batch decode: every active slot advances one token
        tokens = np.zeros((self.slots, 1), np.int32)
        for s in active:
            tokens[s, 0] = self.slot_req[s].out_tokens[-1]
        # decode_step takes the per-slot position vector, so a tick over
        # arbitrarily mixed-length slots is ONE jitted call — each row
        # writes its cache at (and attends up to) its own position, and
        # the commit mask still restricts the write to the active slots
        # (call count pinned in the serve tests; the per-position-group
        # loop this replaced issued one call per distinct length).
        logits = self._step_decode(tokens, self.slot_pos.copy(), active)
        for s in active:
            req = self.slot_req[s]
            nxt = int(jnp.argmax(logits[s, -1]))
            req.out_tokens.append(nxt)
            self.slot_pos[s] += 1
            if len(req.out_tokens) >= req.max_new_tokens:
                req.done = True
        return len(active)

    def run(self) -> list[Request]:
        finished: list[Request] = []
        while self.queue or any(
            r is not None and not r.done for r in self.slot_req
        ):
            self.step()
            for s, r in enumerate(self.slot_req):
                if r is not None and r.done:
                    finished.append(r)
                    self.slot_req[s] = None
        return finished


# ---------------------------------------------------------------------------
# ViG image serving


@dataclasses.dataclass
class VigRequest:
    """One image inference request.

    ``tenant`` names the state stream the request belongs to:
    consecutive requests of one tenant share a serving slot, so the
    cluster tier warm-starts request N+1's k-means from request N's
    centroids — but only within the tenant. ``tenant=None`` marks a
    one-shot anonymous request (always a cold slot).

    A quarantined request completes with ``done=True``,
    ``logits=None`` and the detected fault in ``fault`` (DESIGN.md
    §11) — failure is a typed per-request outcome, never an engine
    crash.

    ``tclass`` names the request's tenant *class* — the key into the
    engine's per-class ``slo_ms`` dict when the SLO-bounded admission
    queue is armed (DESIGN.md §14). With a scalar ``slo_ms`` (or the
    default synchronous engine) the class is inert.
    """

    uid: int
    image: np.ndarray  # (H, W, C) float
    tenant: Optional[Any] = None
    logits: Optional[np.ndarray] = None
    done: bool = False
    fault: Optional[FaultInfo] = None
    tclass: str = "default"


DEFAULT_BUCKETS = (1, 2, 4, 8)


class VigServeEngine:
    """Multi-tenant bucketed ViG inference with cross-request DIGC
    state, served through a single donated ``jax.jit`` per bucket.

    **The request path** (``submit``/``step``/``run``) is the
    multi-tenant engine (DESIGN.md §9): requests occupy fixed slots
    (``slots = max(buckets)``), each tick gathers the active slots,
    pads them to the smallest bucket that fits, and runs that bucket's
    compiled program. State is per **slot**, not per bucket: the
    canonical ``DigcState`` keeps one row per slot (with per-row step
    counters, ``init_vig_state(per_slot=True)``); each tick slices the
    active rows into the bucket batch and scatters the live lanes back,
    so

    * a tenant's warm start follows it even when the serving bucket
      changes tick to tick,
    * padding lanes (which replicate a live lane so their compute is
      well-conditioned) are never scattered back — they cannot clobber
      live state,
    * a slot reassigned to a new tenant is row-reset first — warm state
      never leaks between tenants.

    ``buckets=None`` disables padding: every tick compiles/serves at
    the exact active-batch size (the PR-3 one-program-per-batch-size
    behavior, kept as the benchmark baseline).

    **The multi-resolution lattice** (``image_sizes=``, DESIGN.md
    §13): the bucket grid gains an N dimension — each configured image
    size is an N-bucket whose patch count sizes its own per-slot state
    (``_slot_states[size]``) and programs. Admission resolves every
    request to the smallest cell that fits: an exact configured size
    serves unmasked (its program trace is byte-identical to a
    single-size engine's), a ragged size is zero-padded up to its cell
    with the pad nodes BIG-norm-masked out of every DIGC top-k and the
    mean pooling (single-stage r=1 models only — typed submit error
    otherwise). A tick serves ONE (size, pad-variant) cell — the
    head-of-queue's — so a mixed 224/448/800 trace compiles at most
    |buckets| x |image_sizes| programs and every served row still
    matches its own same-resolution B=1 replay bit-for-bit on CPU.
    Without an explicit ``image_sizes`` the engine is single-size and
    keeps the strict exact-shape submit contract.

    **Sharded mode** (``mesh=``, DESIGN.md §10): the engine goes
    mesh-native — the construction spec is threaded with the mesh
    (``mesh_axis`` names the co-node ring axis, ``mesh_batch_axis``
    optionally shards bucket rows data-parallel), the canonical slot
    state is allocated with matching ``PartitionSpec``s
    (``init_vig_state(mesh=)``), and every bucket program runs the
    distributed builder's ``shard_map`` inside the same donated jit.
    The slot/bucket/warm-gating lifecycle is unchanged: a ragged
    multi-tenant trace on an N-device mesh still compiles at most
    |bucket set| programs and each row still matches its own B=1
    replay bit-for-bit on CPU. Build the mesh with
    ``repro.launch.mesh.make_mesh`` (``Auto`` axes): the default
    ``Explicit`` axes of ``jax.make_mesh`` reject the state's row
    gathers and scatters.

    **LRU state parking** (``park_capacity``, DESIGN.md §10): when a
    tenant is LRU-evicted from its slot, its state rows are copied to
    host memory (bounded by ``park_capacity`` tenants, oldest parked
    copy dropped first) and restored on re-admit — hot tenants survive
    slot churn warm instead of re-admitting cold. ``release()`` (an
    explicit disconnect) still drops state entirely, and
    ``park_capacity=0`` restores the PR-4 evict-means-cold behavior.

    **SLO-bounded admission scheduling** (``slo_ms``/``clock``/
    ``prefetch``/``bucket_cap``, DESIGN.md §14): a positive ``slo_ms``
    (scalar, or per tenant class via ``{class: ms}`` keyed by
    ``VigRequest.tclass``) arms the async admission queue — a tick
    dispatches a (size, masked) cell only when its earliest member
    deadline arrives or it holds a full slot width of tenants, so
    singleton arrivals coalesce into well-filled ticks instead of each
    padding up to a bucket. ``clock`` injects a deterministic time
    source (``serve.sched.VirtualClock``); ``buckets="auto"`` resolves
    the bucket set from the host tuner cache (the arrival-histogram
    optimizer — ``retune_buckets()`` re-derives and persists it from
    the live-lane histogram a served trace accumulated, capped at
    ``bucket_cap`` programs); ``prefetch`` lets the queue issue parked
    tenants' host->device row uploads ahead of their admitting tick.
    ``slo_ms=0`` (the default) is the legacy synchronous engine,
    byte-for-byte.

    **Fault tolerance** (``guards``/``fault_plan``/``deadline_ms``,
    DESIGN.md §11): every picked lane passes an admission finiteness
    screen and per-row state checks (integrity fingerprints + state
    finiteness) before reaching a compiled program; a failing lane is
    quarantined (request fails with a typed ``FaultInfo``, its slot
    cold-resets) or recovered (silent corruption → cold re-serve)
    without perturbing co-batched tenants. Program builds and parking
    restores retry with backoff; persistent build failures and
    repeated deadline misses walk the degradation ladder
    (``core.builder.fallback_chain``). ``fault_plan`` injects
    failures at the named sites for testing; ``guards=False`` keeps
    the unguarded PR-6 fast path.

    **The direct path** (``infer``) runs one batched forward per call
    with one compiled program + state per exact batch size — the PR-3
    API, still the right call for offline fixed-batch workloads.

    Two pieces of graph-construction state persist across requests:

    * a functional ``DigcState`` (``core/state.py``) — threaded
      in-and-out of the jitted forward, so stateful builders work
      *inside* the compiled program: the cluster tier warm-starts its
      per-stage k-means from the previous request's centroids (2 Lloyd
      iterations instead of 5, gated by a runtime step counter — per
      slot row on the request path). The state argument is donated:
      XLA writes the new centroids into the old buffers, so
      steady-state serving allocates nothing for DIGC state.
    * a ``VigSchedule`` — ``warmup()`` tunes the blocked tier's engine
      knobs (block_n, block_m, merge, fuse_norms) **per pyramid
      stage** via ``core.tuner.DigcTuner.tune_schedule``; the request
      path resolves the schedule **per bucket** (the workload key
      includes the batch size — a B=8 tile is not a B=1 tile). Later
      engine instances with the same tuner path skip the measurement
      (host-keyed JSON cache).
    """

    def __init__(self, cfg, params, *, digc_impl=None, batch: int = 8,
                 autotune: bool = True, tuner_path=None,
                 buckets: Optional[tuple] = DEFAULT_BUCKETS,
                 image_sizes: Optional[tuple] = None,
                 on_compile: Optional[Callable[[int], None]] = None,
                 mesh=None, mesh_axis: str = "data",
                 mesh_batch_axis: Optional[str] = None,
                 park_capacity: int = 8,
                 fault_plan=None, guards: bool = True,
                 deadline_ms: Optional[float] = None,
                 deadline_strikes: int = 2,
                 retry_attempts: int = 3, retry_backoff: float = 0.02,
                 slo_ms=0.0, clock: Optional[Callable[[], float]] = None,
                 prefetch: bool = True, bucket_cap: int = 4):
        from repro.core.builder import get_builder
        from repro.models.vig import resolve_digc_spec, vig_stage_plans

        from repro.core.tuner import VigSchedule

        # buckets="auto" defers the choice to the host tuner cache (the
        # arrival-histogram bucket-set optimizer, DESIGN.md §14); it is
        # materialized below, after image_sizes resolve, so the lookup
        # can key on the full serving shape.
        self._auto_buckets = isinstance(buckets, str)
        if self._auto_buckets and buckets != "auto":
            raise ValueError(
                f"buckets must be a tuple, None, or 'auto': {buckets!r}")
        if buckets is not None and not self._auto_buckets:
            buckets = tuple(sorted(set(int(b) for b in buckets)))
            if not buckets or buckets[0] < 1:
                raise ValueError(f"buckets must be positive ints: {buckets!r}")
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.spec = resolve_digc_spec(cfg, digc_impl)
        # -- multi-resolution lattice (DESIGN.md §13): the bucket grid
        # gains an N dimension. Each configured image size is an
        # N-bucket (N = (size/patch)^2 patch nodes); admission resolves
        # every request to the smallest size that fits and the engine
        # serves at most |buckets| x |image_sizes| compiled programs.
        # Each size's pyramid is screened here, at construction — an
        # odd-grid config must fail with the typed VigGridError naming
        # the stage and grid, not three ticks later inside a jit trace.
        # Lattice admission (ragged sizes padded up to a cell) is
        # opt-in via an explicit image_sizes; the default engine keeps
        # the strict exact-shape submit contract.
        self._lattice = image_sizes is not None
        if image_sizes is None:
            image_sizes = (cfg.image_size,)
        sizes = tuple(sorted(set(int(s) for s in image_sizes)))
        if not sizes or sizes[0] < cfg.patch:
            raise ValueError(
                f"image_sizes must be >= patch={cfg.patch}: {image_sizes!r}"
            )
        for s in sizes:
            if s % cfg.patch:
                raise ValueError(
                    f"image_sizes: {s} is not divisible by the model "
                    f"patch size {cfg.patch}"
                )
            vig_stage_plans(cfg, grid=s // cfg.patch)  # VigGridError here
        self.image_sizes = sizes
        self.bucket_cap = int(bucket_cap)
        if self._auto_buckets:
            buckets = self._auto_bucket_set(batch, tuner_path)
        # -- sharded mode (DESIGN.md §10): thread the mesh into the
        # construction spec, so every bucket program and the slot state
        # allocation see the same placement. mesh_axis names the
        # co-node ring axis; mesh_batch_axis optionally shards the
        # bucket rows data-parallel (every bucket must divide by it).
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.mesh_batch_axis = mesh_batch_axis
        if mesh is not None:
            if isinstance(digc_impl, VigSchedule):
                raise ValueError(
                    "mesh= applies one placement to every stage; a "
                    "pre-tuned VigSchedule carries per-stage specs — "
                    "set mesh/axis_name on its stage specs instead"
                )
            builder = get_builder(self.spec.impl)
            if not {"mesh", "axis_name"} <= builder.knobs:
                raise ValueError(
                    f"DIGC impl {self.spec.impl!r} is not mesh-native "
                    "(no mesh/axis_name knobs); sharded serving needs "
                    "a distributed builder (ring)"
                )
            if mesh_batch_axis is not None:
                if buckets is None:
                    # The exact-size policy serves every active count
                    # 1..slots; most of those cannot divide a >1-device
                    # batch axis, and failing mid-tick (after admission
                    # mutated slot state) is worse than refusing here.
                    raise ValueError(
                        "mesh_batch_axis requires a bucket set: the "
                        "exact-size policy (buckets=None) serves "
                        "arbitrary batch sizes, which cannot all "
                        "divide a sharded batch axis"
                    )
                dsz = int(mesh.shape[mesh_batch_axis])
                bad = [v for v in buckets if v < dsz]
                if bad:
                    # A bucket below the axis size cannot give every
                    # device a live row even after padding — that is a
                    # config error. Buckets that merely fail to *divide*
                    # the axis are fine: step() pads the tick to the
                    # next axis multiple (padding lanes replicate lane
                    # 0, exactly like bucket padding) instead of
                    # refusing at construction.
                    raise ValueError(
                        f"bucket sizes {bad} are smaller than the "
                        f"{mesh_batch_axis!r} mesh axis ({dsz} devices); "
                        "configure buckets >= the axis size (non-"
                        "dividing buckets are padded per tick)"
                    )
            self.spec = self.spec.replace(
                mesh=mesh, axis_name=mesh_axis, batch_axis=mesh_batch_axis
            )
        self.autotune = autotune
        self.tuner_path = tuner_path
        # A pre-tuned VigSchedule may be passed directly as digc_impl
        # (e.g. tuned offline); warmup() then has nothing to do. Only a
        # *user-provided* schedule applies to every bucket — a
        # warmup()-tuned one is a measurement at self.batch and must
        # not leak into other buckets' programs (_bucket_choice).
        self._user_schedule = isinstance(digc_impl, VigSchedule)
        self.schedule = digc_impl if self._user_schedule else None
        self.tuned = None  # per-stage TuneResults once warmed up
        self.requests_served = 0
        # direct path: batch size -> [compiled forward, DigcState]
        self._compiled: dict[int, list] = {}

        # -- multi-tenant request path ----------------------------------
        self.buckets = buckets
        self.slots = max(buckets) if buckets is not None else batch
        self.on_compile = on_compile  # compile-counter hook (tests/ops)
        self.compile_count = 0  # programs built on the request path
        self.queue: list[VigRequest] = []
        self.slot_tenant: list[Optional[Any]] = [None] * self.slots
        self._tenant_slot: dict[Any, int] = {}
        self._slot_last_tick = [0] * self.slots
        self._tick = 0
        # canonical per-slot DigcState, one per N-bucket (lazy): row
        # buffers are sized by the size's stage plans, and the §9-§12
        # row lifecycle (gather/scatter, parking, quarantine, cached
        # graphs) is keyed (slot, N-bucket). ``_slot_state`` (below)
        # aliases the primary size — single-size engines see the
        # pre-multires attribute unchanged.
        self._slot_states: dict[int, Any] = {}  # size -> DigcState
        # programs/schedules key by ``_program_key``: the bare bucket
        # for a single-size engine (the pre-multires contract the
        # on_compile tests pin), (size, bucket) on the lattice, plus a
        # "pad" tag for the mask-threading variant.
        self._programs: dict[Any, Callable] = {}  # cell key -> compiled fwd
        self._bucket_schedules: dict[Any, Any] = {}
        self._bucket_tuned: dict[Any, list] = {}
        self.bucket_ticks: dict[int, int] = {}
        self.cell_ticks: dict[tuple, int] = {}  # (size, bucket) -> ticks
        # -- LRU state parking (DESIGN.md §10): host-side copies of
        # evicted tenants' state rows, restored on re-admit so hot
        # tenants survive slot churn warm. Bounded; park_capacity=0
        # disables (evictees re-admit cold, the PR-4 behavior).
        self.park_capacity = int(park_capacity)
        self._parked: "dict[Any, Any]" = {}  # tenant -> host DigcState rows
        self.park_hits = 0
        self.park_evictions = 0
        # -- SLO-bounded async admission (DESIGN.md §14) ----------------
        # A positive slo (scalar ms, or {tenant class: ms}) arms the
        # scheduler: submit() only enqueues, and a tick dispatches a
        # (size, masked) cell when its earliest member deadline arrives
        # or it can fill the full slot width — coalescing singleton
        # arrivals into well-filled ticks instead of padding them up.
        # slo_ms=0 (the default) keeps the legacy bind-on-next-tick
        # admission byte-for-byte: _select_cell short-circuits to the
        # head-of-queue cell and nothing else in the tick changes.
        self._slo_ms = (dict(slo_ms) if isinstance(slo_ms, dict)
                        else float(slo_ms))
        _slo_vals = (self._slo_ms.values()
                     if isinstance(self._slo_ms, dict) else [self._slo_ms])
        if any(float(v) < 0 for v in _slo_vals):
            raise ValueError(f"slo_ms must be >= 0: {slo_ms!r}")
        self._sched_active = any(float(v) > 0 for v in _slo_vals)
        self._clock = clock  # None = wall time; a VirtualClock in tests
        self._enq_seq = 0  # submit-order stamp (per-tenant FIFO anchor)
        self._next_deadline: Optional[float] = None
        self.deferrals = 0  # ticks that waited instead of dispatching
        # padding-waste accounting (stats(); feeds the bucket-set
        # optimizer): padded_lanes == sum over ticks of (width - live).
        self.live_lanes = 0
        self.padded_lanes = 0
        self.lane_hist: dict[tuple, int] = {}  # (size, live) -> ticks
        # -- prefetched parking restore (DESIGN.md §14): the queue
        # names who the next tick admits, so parked tenants' host rows
        # start their host->device upload ahead of the admitting tick.
        self._prefetch = bool(prefetch)
        self._park_prefetch: dict[Any, tuple] = {}  # tenant -> (host, dev)
        self.prefetch_issued = 0
        self.prefetch_hits = 0
        # last-tick observability (asserted by the property tests)
        self.last_lanes: list[int] = []
        self.last_resets: list[int] = []
        self.last_restores: list[int] = []
        self.last_bucket: Optional[int] = None
        self.last_cell: Optional[tuple] = None  # (size, bucket) last tick
        # -- fault tolerance (DESIGN.md §11) ----------------------------
        # fault_plan injects failures at named sites (tests/chaos);
        # guards=True arms the detection/recovery machinery — per-lane
        # finiteness screening, state-integrity fingerprints, the
        # deadline budget. guards=False keeps the PR-6 fast path (the
        # serve/guarded_* bench rows measure the difference).
        self.fault_plan = fault_plan
        self.guards = bool(guards)
        self.deadline_ms = deadline_ms
        self.deadline_strikes = int(deadline_strikes)
        self.retry_attempts = int(retry_attempts)
        self.retry_backoff = float(retry_backoff)
        self.quarantines = 0
        self.state_resets = 0
        self.deadline_misses = 0
        self.park_losses = 0
        self.retries = 0
        self.requests_failed = 0
        self.fallback_level = 0  # rungs descended on the ladder
        self.fault_log: list[FaultInfo] = []  # detected (not injected)
        self.last_quarantined: list[int] = []  # slots, last tick
        self._row_tokens: dict[str, dict[int, int]] = {}
        self._consecutive_misses = 0
        self._program_ticks: dict[Any, int] = {}  # cell key -> ticks served
        # -- stale-graph serving (DESIGN.md §12) ------------------------
        # Lane-granular reuse accounting, reconstructed host-side from
        # graph_age deltas after each tick (age resets to 0 on rebuild,
        # grows monotonically under reuse) — no extra device sync, the
        # logits pull already closed the tick.
        self.graph_reuses = 0
        self.graph_rebuilds = 0
        self._drift_sum = 0.0
        self._drift_n = 0
        self.last_drift: dict[str, float] = {}  # entry key -> mean drift
        # Spans and counters of the tick (serve/tracer.py): off until a
        # caller that measures the engine switches it on.
        self.tracer = EngineTracer()
        # (size, bucket) -> per-lane (lists, candidates) and per-tick
        # co-node tiles, for the tracer
        self._digc_work: dict[tuple, tuple[int, int, int]] = {}

    # -- multi-resolution lattice plumbing (DESIGN.md §13) --------------

    @property
    def _slot_state(self):
        """The primary size's canonical slot state — the pre-multires
        attribute, kept as an alias so single-size callers (and the
        serve tests) keep reading/assigning one state object."""
        return self._slot_states.get(self.image_sizes[0])

    @_slot_state.setter
    def _slot_state(self, value):
        if value is None:
            self._slot_states.pop(self.image_sizes[0], None)
        else:
            self._slot_states[self.image_sizes[0]] = value

    def _multi_size(self) -> bool:
        return len(self.image_sizes) > 1

    def _req_size(self, req) -> int:
        return getattr(req, "_serve_size", self.image_sizes[0])

    def _req_mask(self, req):
        return getattr(req, "_serve_mask", None)

    def _program_key(self, bucket: int, size: Optional[int] = None,
                     masked: bool = False):
        """Cell key for programs/ticks/on_compile: the bare bucket on a
        single-size engine (the pre-multires contract), (size, bucket)
        on the lattice, with a "pad" tag for the mask variant."""
        size = self.image_sizes[0] if size is None else size
        if masked:
            return (size, bucket, "pad")
        if not self._multi_size():
            return bucket
        return (size, bucket)

    def _tick_width(self, bucket: int) -> int:
        """Static batch width of one tick's program: the bucket, padded
        up to the next ``mesh_batch_axis`` multiple when the rows are
        sharded data-parallel — a non-dividing bucket pads its tick
        (replicating lane 0) instead of failing at construction."""
        if self.mesh is None or self.mesh_batch_axis is None:
            return bucket
        dsz = int(self.mesh.shape[self.mesh_batch_axis])
        return -(-bucket // dsz) * dsz

    def _reset_rows_all(self, slots) -> None:
        """Cold-reset ``slots``' rows in every allocated N-bucket state
        (quarantine/release/admission: a slot's occupancy changes for
        all resolutions at once, so stale warm rows at *any* size must
        not survive into the next tenant): one compiled reset per
        N-bucket, then one token refresh over all of ``slots``."""
        for size, st in self._slot_states.items():
            self._slot_states[size] = self._reset(st, slots)
        self._refresh_tokens(slots)

    def _reset(self, state, slots):
        """``state.reset_rows(slots)``, counted on the tracer: one
        compiled reset (``reset_calls``) over ``len(slots)`` slots
        (``reset_rows``)."""
        self.tracer.count("reset_calls")
        self.tracer.count("reset_rows", len(slots))
        return state.reset_rows(slots)

    def _count_digc(self, bucket: int, size: int, lanes: int) -> None:
        """Count the tick's DIGC work on the tracer, from the cell's
        stage plans (no pull): ``digc_lists``, the neighbour entries its
        graphs hold (live lanes x N x k per block), ``digc_candidates``,
        what the top-(k*d) merge keeps before the dilation stride (live
        lanes x N x k*d per block), and ``digc_tiles``, the co-node tiles
        the ``blocked`` tier's blocks stream (ceil(M / block_m) per
        block, ``block_m`` as ``engine.conode_tile`` sizes it for the
        tick's width; per tick, whatever the live lanes)."""
        if not self.tracer.recording:
            return
        key = (size, bucket)
        work = self._digc_work.get(key)
        if work is None:
            from repro.core.engine import conode_tile
            from repro.models.vig import vig_stage_plans

            plans = vig_stage_plans(self.cfg, self._choice_for(bucket, size),
                                    grid=size // self.cfg.patch)
            width = self._tick_width(bucket)
            work = self._digc_work[key] = (
                sum(p.n * k for p in plans for k in p.k_effs),
                sum(p.n * k * d for p in plans
                    for k, d in zip(p.k_effs, p.dilations)),
                sum(p.depth * -(-p.m // conode_tile(
                    width, p.n, p.m, p.spec.block_m, p.spec.block_n))
                    for p in plans if p.spec.impl == "blocked"))
        self.tracer.count("digc_lists", lanes * work[0])
        self.tracer.count("digc_candidates", lanes * work[1])
        self.tracer.count("digc_tiles", work[2])

    # -- SLO-bounded admission scheduling (DESIGN.md §14) ---------------

    def _now(self) -> float:
        """Scheduler time: the injected clock (a ``VirtualClock`` or
        any zero-arg callable) or wall ``time.monotonic``."""
        if self._clock is None:
            return time.monotonic()
        now = getattr(self._clock, "now", None)
        return now() if now is not None else self._clock()

    def _slo_s(self, req) -> float:
        """The request's admission budget in seconds: its tenant
        class's entry in the slo_ms dict (falling back to "default",
        then 0 = dispatch-now), or the scalar slo."""
        if isinstance(self._slo_ms, dict):
            ms = self._slo_ms.get(req.tclass, self._slo_ms.get("default", 0.0))
        else:
            ms = self._slo_ms
        return float(ms) / 1e3

    def _tkey(self, req):
        """Slot-identity of a request: its tenant, or a unique one-shot
        key for anonymous requests."""
        return req.tenant if req.tenant is not None else ("req", req.uid)

    def _cell_of(self, req) -> tuple:
        """The (size, masked) lattice cell a request resolved to."""
        return (self._req_size(req), self._req_mask(req) is not None)

    def _enqueue(self, req: VigRequest) -> None:
        """Admit a validated request to the queue, stamped with its
        arrival time and submit order (the deadline and FIFO anchors),
        and give the parking prefetcher a look at the new queue."""
        req._enq_t = self._now()
        req._enq_seq = self._enq_seq
        self._enq_seq += 1
        self.queue.append(req)
        self._prefetch_parked()

    def _select_cell(self, peek: bool = False):
        """Choose the (size, masked) cell the next tick serves and its
        eligible requests, or defer.

        Legacy (``slo_ms=0``): the head-of-queue's cell and every
        queued request that resolved to it — the bind-on-next-tick
        admission, unchanged byte-for-byte.

        Scheduler (any positive slo): each request carries a deadline
        (arrival + its class budget); a tenant's *effective* deadline
        is the min over all its queued requests, attributed to its
        head request (a tight-slo request queued behind a lax one
        pulls the head forward — FIFO never starves a deadline). A
        cell is **ripe** when its earliest member deadline has arrived
        or it holds a full slot width of distinct tenants; the ripe
        cell with the earliest (deadline, arrival) dispatches, and
        only tenant *head* requests are eligible, so per-tenant FIFO
        holds even across cells. With no ripe cell the tick defers:
        ``_next_deadline`` records when the earliest cell ripens
        (``run()`` advances the clock to it — a VirtualClock jumps,
        the wall clock sleeps).

        ``peek=True`` never defers — it returns the cell that WILL
        dispatch at the next deadline, which is what the parking
        prefetcher keys its uploads on."""
        if not self.queue:
            return None, None
        if not self._sched_active:
            cell = self._cell_of(self.queue[0])
            return cell, [r for r in self.queue if self._cell_of(r) == cell]
        heads: dict[Any, VigRequest] = {}
        eff: dict[Any, float] = {}
        for r in self.queue:
            tk = self._tkey(r)
            heads.setdefault(tk, r)
            dl = r._enq_t + self._slo_s(r)
            eff[tk] = min(eff.get(tk, dl), dl)
        cells: dict[tuple, list] = {}  # cell -> [deadline, tenants, seq]
        for tk, head in heads.items():
            info = cells.setdefault(self._cell_of(head),
                                    [float("inf"), 0, head._enq_seq])
            info[0] = min(info[0], eff[tk])
            info[1] += 1
            info[2] = min(info[2], head._enq_seq)
        now = self._now()
        ripe = [c for c, (dl, nt, _) in cells.items()
                if now >= dl - 1e-9 or nt >= self.slots]
        if not ripe:
            if not peek:
                self._next_deadline = min(i[0] for i in cells.values())
                return None, None
            ripe = list(cells)
        cell = min(ripe, key=lambda c: (cells[c][0], cells[c][2]))
        head_ids = {id(r) for r in heads.values()}
        eligible = [r for r in self.queue
                    if id(r) in head_ids and self._cell_of(r) == cell]
        if not peek:
            self._next_deadline = None
        return cell, eligible

    def next_deadline(self) -> Optional[float]:
        """The earliest admission deadline among queued requests, or
        None (empty queue, or scheduler not armed). A serving loop
        wakes at this time even with no new arrivals — replaying a
        trace, ``serve.sched.replay`` advances the clock here between
        arrivals so no queued cell overshoots its SLO."""
        if not self._sched_active or not self.queue:
            return None
        return min(r._enq_t + self._slo_s(r) for r in self.queue)

    def _advance_to_deadline(self) -> None:
        """Move time to the next admission deadline after a deferred
        tick: a clock with ``advance_to`` (VirtualClock) jumps —
        deterministic tests/benches; the wall clock sleeps the
        remainder."""
        target = self._next_deadline
        if target is None:
            return
        adv = getattr(self._clock, "advance_to", None)
        if adv is not None:
            adv(target)
            return
        delta = target - self._now()
        if delta > 0:
            time.sleep(min(delta, 60.0))

    def _prefetch_parked(self) -> None:
        """Issue the next tick's parking restores ahead of time: the
        admission queue names who the next tick admits, so a parked,
        unslotted tenant among the predicted admits starts its
        host->device row upload (``prefetch_park_rows``) now, off the
        admitting tick's critical path. Purely a placement hint —
        ``_unpark`` still passes the ``park.restore`` fault site and
        the §11 bind-time integrity screens, and consumes the device
        copy only when the restored host object is the very one the
        upload was issued from."""
        if not self._prefetch or not self._parked or not self.queue:
            return
        from repro.core.state import prefetch_park_rows

        _, eligible = self._select_cell(peek=True)
        for req in (eligible or [])[: self.slots]:
            tk = self._tkey(req)
            if (tk in self._parked and tk not in self._tenant_slot
                    and tk not in self._park_prefetch):
                host = self._parked[tk]
                self._park_prefetch[tk] = (host, prefetch_park_rows(host))
                self.prefetch_issued += 1

    def retune_buckets(self, max_programs: Optional[int] = None,
                       force: bool = True) -> tuple:
        """Re-derive the bucket set from the live-lane histogram this
        engine's served trace accumulated (``lane_hist``), via the
        arrival-histogram optimizer in ``core.tuner`` — persisted per
        host in the tuner cache exactly like ``VigSchedule``s, so the
        next engine constructed with ``buckets="auto"`` and the same
        tuner path starts on the optimized set. Takes effect live:
        programs for dropped buckets stay compiled but ``bucket_for``
        never picks them again; new buckets compile lazily on first
        use."""
        from repro.core.tuner import DigcTuner, optimal_bucket_set

        hist: dict[int, dict[int, int]] = {}
        for (sz, live), ticks in self.lane_hist.items():
            per = hist.setdefault(sz, {})
            per[live] = per.get(live, 0) + ticks
        cap = self.bucket_cap if max_programs is None else int(max_programs)
        costs = {s: (s // self.cfg.patch) ** 2 for s in self.image_sizes}
        if self.tuner_path is not None:
            new = DigcTuner(self.tuner_path).tune_bucket_set(
                hist, slots=self.slots, max_programs=cap, costs=costs,
                sizes=self.image_sizes, force=force)
        else:
            new = optimal_bucket_set(hist, slots=self.slots,
                                     max_programs=cap, costs=costs)
        self.buckets = new
        return new

    def _auto_bucket_set(self, slots: int, tuner_path) -> tuple:
        """Materialize ``buckets="auto"``: the host-persisted bucket
        set for this (slots, sizes, cap) serving shape when the tuner
        cache holds one (a previous trace's ``retune_buckets``), else
        the default ladder capped at ``slots``."""
        if tuner_path is not None:
            from repro.core.tuner import DigcTuner

            found = DigcTuner(tuner_path).lookup_bucket_set(
                slots=slots, sizes=self.image_sizes,
                max_programs=self.bucket_cap)
            if found is not None:
                return found
        return tuple(b for b in DEFAULT_BUCKETS if b < slots) + (slots,)

    # -- tuning ---------------------------------------------------------

    def _stage_rows(self, size: Optional[int] = None) -> list[dict]:
        """One workload row per stage: pooled stages tune the real
        (N, M) pair, later pyramid stages get their own entries.
        ``size`` selects the N-bucket (default: the native pyramid) —
        the rows carry that bucket's (N, M, k), so the tuner's workload
        key covers both lattice dimensions. A stage whose blocks differ
        in k (a ``num_knn`` ramp) or dilation tunes at its widest
        block, the largest k*d: the tile chosen there holds the widest
        merge, and every block of the stage runs it."""
        from repro.models.vig import count_digc_work

        grid = None if size is None else size // self.cfg.patch
        stages: dict[int, list[dict]] = {}
        for row in count_digc_work(self.cfg, grid=grid):
            stages.setdefault(row["stage"], []).append(row)
        return [max(stages[si], key=lambda r: r["k"] * r["dilation"])
                for si in sorted(stages)]

    def warmup(self, rng_seed: int = 0):
        """Autotune a per-stage engine schedule (blocked tier only).

        Tunes the direct-path batch size; the request path additionally
        tunes per bucket, lazily, on each bucket's first tick. A no-op
        when a pre-tuned ``VigSchedule`` was passed at construction —
        warmup never clobbers a user-provided schedule.
        """
        if (not self.autotune or self.spec.impl != "blocked"
                or self.schedule is not None):
            return None
        from repro.core.tuner import DigcTuner

        tuner = DigcTuner(self.tuner_path)
        self.schedule, self.tuned = tuner.tune_schedule(
            self._stage_rows(),
            spec=self.spec, batch=self.batch, rng_seed=rng_seed,
        )
        # Forwards compiled before the schedule existed bake the old
        # spec: drop them so the next request recompiles with it.
        self._compiled.clear()
        return self.tuned

    def _impl_choice(self):
        return self.schedule if self.schedule is not None else self.spec

    def _bucket_choice(self, bucket: int, size: Optional[int] = None):
        """Resolve the DIGC impl/schedule for one (B, N) cell's program.

        The tuner's workload key includes the batch size AND the node
        counts (``_stage_rows(size)`` feeds the cell's own N/M), so
        lattice serving tunes **per cell** (``tune_bucket_schedules``),
        never reusing a schedule measured at a different batch or
        resolution — including the one ``warmup()`` measured at
        ``self.batch`` for the direct path (a warmup-tuned B=8 tile
        must not bake into the B=1 program; only a user-provided
        schedule applies everywhere).
        """
        if self._user_schedule:
            return self.schedule
        if self.spec.impl != "blocked" or not self.autotune:
            return self.spec
        size = self.image_sizes[0] if size is None else size

        def _skey(b):
            return b if not self._multi_size() else (size, b)

        if _skey(bucket) not in self._bucket_schedules:
            from repro.core.tuner import DigcTuner

            # First miss tunes every configured bucket at once (for
            # this size): a serving replica will compile them all
            # anyway, and the tuner's JSON cache makes later engines
            # free.
            targets = self.buckets if self.buckets is not None else (bucket,)
            tuner = DigcTuner(self.tuner_path)
            schedules, tuned = tuner.tune_bucket_schedules(
                self._stage_rows(size), spec=self.spec, buckets=targets,
            )
            self._bucket_schedules.update(
                {_skey(b): s for b, s in schedules.items()}
            )
            self._bucket_tuned.update(
                {_skey(b): t for b, t in tuned.items()}
            )
        return self._bucket_schedules[_skey(bucket)]

    # -- direct fixed-batch path (PR-3 API) -----------------------------

    def _infer_jit(self, images) -> jax.Array:
        from repro.models.vig import init_vig_state, vig_forward

        b = int(images.shape[0])
        if b not in self._compiled:
            choice = self._impl_choice()
            fwd = jax.jit(
                lambda p, im, st: vig_forward(
                    p, im, self.cfg, digc_impl=choice, state=st
                ),
                donate_argnums=(2,),
            )
            self._compiled[b] = [fwd, init_vig_state(self.cfg, b, choice)]
        fwd, state = self._compiled[b]
        logits, new_state = fwd(self.params, images, state)
        self._compiled[b][1] = new_state
        return logits

    def infer(self, images) -> jax.Array:
        """images (B, H, W, C) -> logits (B, num_classes).

        Direct fixed-batch path: one compiled program + state per exact
        batch size. Ragged multi-tenant traffic belongs on the request
        path (``submit``/``run``) instead.
        """
        if (self.autotune and self.tuned is None and self.schedule is None
                and self.spec.impl == "blocked"):
            self.warmup()
        logits = self._infer_jit(images)
        self.requests_served += int(images.shape[0])
        return logits

    # -- multi-tenant request path --------------------------------------

    def submit(self, req: VigRequest) -> None:
        """Enqueue a request for the next engine tick.

        Validates the image against the engine's model config up
        front: a malformed request must fail here, at the submitter,
        with a typed error naming the field — not as a shape error
        deep inside a jitted program three ticks later (where it would
        take co-batched tenants down with it).
        """
        img = np.asarray(req.image)
        if img.ndim != 3:
            raise ValueError(
                f"VigRequest.image (uid={req.uid}): expected a 3-d "
                f"(H, W, C) array, got ndim={img.ndim} shape={img.shape}"
            )
        h, w, c = img.shape
        if c != self.cfg.in_chans:
            raise ValueError(
                f"VigRequest.image (uid={req.uid}): {c} channels does "
                f"not match the engine config in_chans={self.cfg.in_chans}"
            )
        if h != w:
            raise ValueError(
                f"VigRequest.image (uid={req.uid}): non-square image "
                f"{img.shape}; the patch lattice needs H == W"
            )
        if not np.issubdtype(img.dtype, np.floating):
            raise ValueError(
                f"VigRequest.image (uid={req.uid}): dtype {img.dtype} is "
                "not a float dtype; pass float32 pixel features"
            )
        # -- N-bucket resolution (DESIGN.md §13): an exact configured
        # size serves its own cell unmasked; a ragged size pads up to
        # the smallest cell that fits, carrying a per-node live mask so
        # DIGC BIG-norm-masks the pad nodes out of every top-k.
        if h in self.image_sizes:
            req._serve_size, req._serve_mask = h, None
            self._enqueue(req)
            return
        if not self._lattice:
            want = (self.cfg.image_size, self.cfg.image_size,
                    self.cfg.in_chans)
            raise ValueError(
                f"VigRequest.image (uid={req.uid}): shape {img.shape} "
                f"does not match the engine config {want} "
                "(image_size, image_size, in_chans); construct the "
                "engine with image_sizes= to serve ragged resolutions"
            )
        if h % self.cfg.patch:
            raise ValueError(
                f"VigRequest.image (uid={req.uid}): size {h} is not "
                f"divisible by the model patch size {self.cfg.patch}"
            )
        fits = [s for s in self.image_sizes if s >= h]
        if not fits:
            raise ValueError(
                f"VigRequest.image (uid={req.uid}): size {h} exceeds "
                f"the largest configured image size "
                f"{self.image_sizes[-1]} (image_sizes={self.image_sizes})"
            )
        size = fits[0]
        self._check_pad_capable(req, h)
        g, g0 = size // self.cfg.patch, h // self.cfg.patch
        mask2d = np.zeros((g, g), bool)
        mask2d[:g0, :g0] = True
        req._serve_size, req._serve_mask = size, mask2d.reshape(-1)
        self._enqueue(req)

    def _check_pad_capable(self, req, h: int) -> None:
        """Typed submit-time screen for the padded (masked) path: pad
        nodes require a single-stage r=1 model (pooling/downsampling
        would mix pad and live rows) and a pad-capable DIGC tier
        (``GraphBuilder.supports_pad`` — the BIG-norm masking)."""
        from repro.core.builder import get_builder

        cfg = self.cfg
        if len(cfg.depths) > 1 or any(
            r > 1 for r in cfg.reduce_ratios[:len(cfg.depths)]
        ):
            raise ValueError(
                f"VigRequest.image (uid={req.uid}): size {h} needs "
                f"pad nodes to reach the {self.image_sizes} cell set, "
                f"but model {cfg.name!r} has a multi-stage/pooled "
                f"pyramid (depths={cfg.depths}, "
                f"reduce_ratios={cfg.reduce_ratios}) that would mix pad "
                "and live rows — submit an exact configured size, or "
                "add this size to image_sizes"
            )
        impl = (self.schedule.spec_for(0).impl if self._user_schedule
                else self.spec.impl)
        if not get_builder(impl).supports_pad:
            raise ValueError(
                f"VigRequest.image (uid={req.uid}): size {h} needs pad "
                f"nodes, but DIGC impl {impl!r} does not support "
                "pad-node masking (m_valid); submit an exact configured "
                "size, or serve a pad-capable tier"
            )

    # -- fault tolerance (DESIGN.md §11) --------------------------------

    def _fire(self, site: str, value=None, **ctx):
        """Fault-injection hook: a no-op (returning ``value``
        unchanged) unless a ``FaultPlan`` was supplied."""
        if self.fault_plan is None:
            return value
        return self.fault_plan.fire(site, value=value, tick=self._tick, **ctx)

    def _retry(self, fn, what: str):
        """Bounded retry with exponential backoff for host-side
        transients (parking restore, program build). Re-raises the
        last error once the budget is spent."""
        last = None
        for attempt in range(self.retry_attempts):
            try:
                return fn()
            except Exception as e:  # noqa: BLE001 — transient boundary
                last = e
                self.retries += 1
                if attempt + 1 < self.retry_attempts:
                    time.sleep(self.retry_backoff * (2 ** attempt))
        raise last

    def _token_key(self, size: int, key: str) -> str:
        """Integrity-token namespace: per (N-bucket, entry) on the
        lattice; the bare entry key on a single-size engine."""
        return key if not self._multi_size() else f"{size}:{key}"

    def _refresh_tokens(self, slots, size: Optional[int] = None) -> None:
        """Re-fingerprint ``slots``' state rows after a *sanctioned*
        write (admission reset, unpark restore, end-of-tick scatter).
        Any later mismatch is an unsanctioned mutation. ``size``
        restricts the refresh to one N-bucket's state (the per-tick
        scatter); ``None`` re-fingerprints every allocated bucket
        (slot-lifecycle writes touch them all)."""
        if not self.guards or not self._slot_states:
            return
        targets = (self._slot_states.items() if size is None
                   else [(size, self._slot_states[size])]
                   if size in self._slot_states else [])
        with self.tracer.span("engine.guard"):
            for sz, st in targets:
                fps = st.row_fingerprints(list(slots), self.tracer.to_host)
                for key, rows in fps.items():
                    self._row_tokens.setdefault(
                        self._token_key(sz, key), {}
                    ).update(rows)

    def _graph_stats_update(self, old_state, new_state, lanes) -> None:
        """Reconcile per-lane graph reuse/rebuild counters from one
        tick's state delta (stale-graph serving, DESIGN.md §12).

        ``graph_age`` is authoritative: the reuse gate in core/digc
        resets a row's age to 0 whenever its graph was rebuilt this
        call chain and grows it otherwise, so ``new_age == 0`` after a
        served tick means the lane paid a DIGC build and anything else
        means it rode the cached graph. Drift is recovered from the
        snapshot statistic the gate itself uses: on a rebuild the entry
        adopts the fresh ``graph_snap``, so the relative delta vs the
        previous snapshot is (approximately) the drift that tripped
        the gate. Both states are read at slot granularity — the
        bucket-shaped tick arrays are donated into the jit program and
        gone by the time this runs."""
        rows = np.asarray(lanes, dtype=np.int64)
        to_host = self.tracer.to_host
        for key, new_e in new_state.entries.items():
            if new_e.graph_age is None:
                continue
            old_e = old_state.entries.get(key)
            if old_e is None or old_e.graph_age is None:
                continue
            new_age = to_host(new_e.graph_age)[rows]
            rebuilt = new_age == 0
            self.graph_rebuilds += int(rebuilt.sum())
            self.graph_reuses += int((~rebuilt).sum())
            old_snap = to_host(old_e.graph_snap)[rows]
            new_snap = to_host(new_e.graph_snap)[rows]
            # cold lanes carry the zero-initialized snapshot — their
            # first build is an admission, not drift
            warm = np.abs(old_snap) > 0
            drift = np.where(
                warm,
                np.abs(new_snap - old_snap) / np.maximum(np.abs(old_snap),
                                                         1e-9),
                0.0,
            )[warm]
            if drift.size:
                self.last_drift[key] = float(drift.mean())
                self._drift_sum += float(drift.sum())
                self._drift_n += int(drift.size)

    def _row_intact(self, slot: int, fps=None,
                    size: Optional[int] = None) -> bool:
        """Check ``slot``'s rows against their integrity tokens (for
        the ``size`` N-bucket being served). Rows never fingerprinted
        (no sanctioned write yet) are trusted. ``fps`` passes
        precomputed fingerprints so one tick's lanes share a single
        device->host pull."""
        size = self.image_sizes[0] if size is None else size
        st = self._slot_states.get(size)
        if st is None:
            return True
        if fps is None:
            fps = st.row_fingerprints([slot], self.tracer.to_host)
        for key, rows in fps.items():
            want = self._row_tokens.get(
                self._token_key(size, key), {}
            ).get(slot)
            if want is not None and rows[slot] != want:
                return False
        return True

    def _row_finite(self, slot: int, finite=None,
                    size: Optional[int] = None) -> bool:
        size = self.image_sizes[0] if size is None else size
        st = self._slot_states.get(size)
        if st is None:
            return True
        if finite is None:
            finite = st.rows_finite([slot], self.tracer.to_host)
        return finite[slot]

    def _quarantine(self, slot: int, req: VigRequest,
                    info: FaultInfo) -> None:
        """Fail one request with a typed ``FaultInfo`` and cold-reset
        its slot, leaving every co-batched tenant untouched: the faulty
        lane simply never reaches the compiled program."""
        req.fault = info
        req.logits = None
        req.done = True
        self.quarantines += 1
        self.requests_failed += 1
        self.fault_log.append(info)
        self.last_quarantined.append(slot)
        if self._slot_states:
            # A poisoned carry is suspect at every resolution the slot
            # holds rows for — reset them all (one counted reset).
            self._reset_rows_all([slot])
            self.state_resets += 1
        self._slot_last_tick[slot] = self._tick
        if req.tenant is None:
            self.slot_tenant[slot] = None
            self._tenant_slot.pop(("req", req.uid), None)

    def _degrade(self, info: FaultInfo) -> bool:
        """Descend one rung of the degradation ladder
        (``core.builder.fallback_chain``): drop every compiled program
        and rebuild at the next-simpler tier. Returns False when the
        ladder is exhausted."""
        from repro.core.builder import fallback_chain

        chain = fallback_chain(self._ladder_base_impl())
        if self.fallback_level >= len(chain):
            return False
        self.fallback_level += 1
        self._programs.clear()
        self._program_ticks.clear()
        self._consecutive_misses = 0
        self.fault_log.append(info)
        return True

    def _ladder_base_impl(self) -> str:
        choice = self._impl_choice()
        return (choice.spec_for(0).impl if hasattr(choice, "spec_for")
                else choice.impl)

    def release(self, tenant: Any) -> None:
        """Tenant disconnect: free its slot and cold-reset the rows, so
        the next occupant cannot warm-start from its state. A released
        tenant's parked copy (if any) is dropped too — disconnect means
        gone, unlike an LRU eviction (which parks)."""
        self._parked.pop(tenant, None)
        self._park_prefetch.pop(tenant, None)
        slot = self._tenant_slot.pop(tenant, None)
        if slot is None:
            return
        self.slot_tenant[slot] = None
        if self._slot_states:
            self._reset_rows_all([slot])

    # -- LRU state parking (DESIGN.md §10) ------------------------------

    def _park(self, tenant: Any, slot: int) -> None:
        """Copy an evicted tenant's state rows to host memory (bounded,
        LRU-dropped) so a later re-admit restores them warm. On the
        multi-resolution lattice the parked copy holds the slot's rows
        for **every** allocated N-bucket (``{size: rows}``) — a tenant
        re-admitted after serving at two resolutions gets both carries
        back; single-size engines park the bare rows (the pre-multires
        layout the parking tests read)."""
        if self.park_capacity <= 0 or not self._slot_states:
            return
        host = {
            size: jax.tree_util.tree_map(
                self.tracer.to_host, st.take_rows([slot])
            )
            for size, st in self._slot_states.items()
        }
        self._parked.pop(tenant, None)  # re-insert = most recent
        # a fresh park supersedes any in-flight prefetch of older rows
        self._park_prefetch.pop(tenant, None)
        self._parked[tenant] = (host if self._multi_size()
                                else host[self.image_sizes[0]])
        while len(self._parked) > self.park_capacity:
            oldest = next(iter(self._parked))
            del self._parked[oldest]
            self._park_prefetch.pop(oldest, None)
            self.park_evictions += 1

    def _unpark(self, tenant: Any, slot: int) -> bool:
        """Restore a parked tenant's rows into its freshly bound slot.
        Returns False (caller cold-resets) when nothing is parked. Only
        the *row* fields are restored — the scalar ``step`` stays the
        canonical entry's (it is the engine-global call counter, not a
        per-tenant value; per-row validity lives in ``row_step``).

        The restore passes the ``park.restore`` fault site: transient
        errors are retried with backoff; a ``None`` coming back after a
        parked copy existed is a parking-store **loss** — counted, and
        the tenant re-admits cold (the caller resets the slot)."""
        had_copy = tenant in self._parked
        host = self._parked.pop(tenant, None)
        prefetched = self._park_prefetch.pop(tenant, None)
        orig = host
        if host is not None:
            try:
                host = self._retry(
                    lambda: self._fire("park.restore", value=host,
                                       tenant=tenant),
                    "park restore",
                )
            except FaultError:
                host = None
        if host is None:
            if had_copy:
                # The parked rows existed but could not be restored —
                # account the loss; the cold reset that follows is the
                # recovery, not a silent fallback.
                self.park_losses += 1
                self.state_resets += 1  # the caller's cold reset is recovery
                self.fault_log.append(FaultInfo(
                    kind="parking_loss", site="park.restore",
                    tenant=tenant, tick=self._tick,
                    detail="parked rows unrecoverable; re-admitting cold",
                ))
            return False
        from repro.core.state import DigcState

        if prefetched is not None and host is orig:
            # The queue-driven prefetch already uploaded exactly these
            # host rows (identity-checked: a fault-site replacement
            # must re-upload) — bind the in-flight device copy instead,
            # taking the host->device transfer off the tick. The §11
            # integrity screens below (_refresh_tokens now, the batched
            # fingerprint/finiteness pull next tick) run against the
            # bound rows either way.
            host = prefetched[1]
            self.prefetch_hits += 1
        per_size = (host if self._multi_size()
                    else {self.image_sizes[0]: host})
        # N-buckets allocated since the park (no rows in the copy) must
        # not keep the *previous* occupant's rows: reset first, then
        # lay the parked copy over its own sizes.
        for size, st in self._slot_states.items():
            if size not in per_size:
                self._slot_states[size] = self._reset(st, [slot])
        for size, rows in per_size.items():
            state = self._ensure_slot_state(size)
            self._slot_states[size] = DigcState(entries={
                k: dataclasses.replace(
                    e.put_rows(rows.entries[k], [slot]), step=e.step
                )
                for k, e in state.entries.items()
            })
        self.park_hits += 1
        self._refresh_tokens([slot])
        return True

    def bucket_for(self, active: int) -> int:
        """Smallest bucket that fits ``active`` slots (the bucket
        policy); the exact count when bucketing is disabled."""
        if not 1 <= active <= self.slots:
            raise ValueError(f"active={active} outside 1..{self.slots}")
        if self.buckets is None:
            return active
        return next(b for b in self.buckets if b >= active)

    def _ensure_slot_state(self, size: Optional[int] = None):
        from repro.models.vig import init_vig_state

        size = self.image_sizes[0] if size is None else size
        if size not in self._slot_states:
            # Allocate from the same impl choice the bucket programs
            # resolve: a user-provided VigSchedule may carry per-stage
            # specs (e.g. cluster with stage-specific n_clusters) whose
            # entry shapes differ from a stage-0-only resolution. The
            # autotuned (blocked-only) schedules never change entry
            # shapes, so the canonical state stays bucket-independent.
            # Row buffers are sized by this N-bucket's stage plans
            # (grid=) — a 448 cell's cached-graph rows are N=12544.
            choice = self.schedule if self._user_schedule else self.spec
            self._slot_states[size] = init_vig_state(
                self.cfg, self.slots, choice, per_slot=True,
                mesh=self.mesh, mesh_axis=self.mesh_axis,
                grid=size // self.cfg.patch,
            )
        return self._slot_states[size]

    def _choice_for(self, bucket: int, size: Optional[int] = None):
        """Resolve the cell's DIGC impl through the degradation
        ladder: at fallback level 0 this is the tuned per-cell
        choice; each descended rung swaps in the next tier of
        ``core.builder.fallback_chain`` (simpler machinery, never less
        exact)."""
        if self.fallback_level == 0:
            return self._bucket_choice(bucket, size)
        from repro.core.builder import degraded_spec, fallback_chain

        chain = fallback_chain(self._ladder_base_impl())
        return degraded_spec(self.spec, chain[self.fallback_level - 1])

    def _build_program(self, bucket: int, size: Optional[int] = None,
                       masked: bool = False) -> Callable:
        """Compile one (B, N) cell's donated forward. Split out so
        tests can stub program construction and count compiles. Passes
        the ``program.build`` fault site (injected compile failures).
        ``masked=True`` builds the pad-node variant: a fourth (B, N)
        bool argument marks live nodes, BIG-norm-masked through DIGC
        (exact-size cells keep the 3-argument program, so their trace
        is byte-identical to the single-size engine's)."""
        from repro.models.vig import vig_forward

        size = self.image_sizes[0] if size is None else size
        choice = self._choice_for(bucket, size)
        impl = (choice.spec_for(0).impl if hasattr(choice, "spec_for")
                else choice.impl)
        self._fire("program.build", bucket=bucket, impl=impl)
        if masked:
            return jax.jit(
                lambda p, im, st, mv: vig_forward(
                    p, im, self.cfg, digc_impl=choice, state=st,
                    valid_mask=mv,
                ),
                donate_argnums=(2,),
            )
        return jax.jit(
            lambda p, im, st: vig_forward(
                p, im, self.cfg, digc_impl=choice, state=st
            ),
            donate_argnums=(2,),
        )

    def _program_for(self, bucket: int, size: Optional[int] = None,
                     masked: bool = False) -> Callable:
        """Cell program lookup with recovery: a failing build is
        retried (transient compile-service hiccups), and a
        persistently failing tier walks the degradation ladder until a
        rung builds — only an exhausted ladder re-raises."""
        key = self._program_key(bucket, size, masked)
        legacy = key == bucket  # single-size, unmasked: the 1-arg
        # _build_program call the stubbing tests override
        while key not in self._programs:
            try:
                if legacy:
                    prog = self._retry(
                        lambda: self._build_program(bucket),
                        f"bucket {bucket} program build",
                    )
                else:
                    prog = self._retry(
                        lambda: self._build_program(
                            bucket, size=size, masked=masked
                        ),
                        f"cell {key} program build",
                    )
            except Exception as e:  # noqa: BLE001 — ladder boundary
                info = (e.info if isinstance(e, FaultError) else FaultInfo(
                    kind="compile_failure", site="program.build",
                    tick=self._tick, detail=repr(e),
                ))
                if not self._degrade(dataclasses.replace(
                    info, kind="compile_degrade",
                    detail=f"{info.detail}; descending ladder",
                )):
                    raise
                continue
            self._programs[key] = prog
            self.compile_count += 1
            if self.on_compile is not None:
                self.on_compile(key)
        return self._programs[key]

    def _admit(self, tenant_key, used: set) -> Optional[int]:
        """Bind a new tenant to a slot: a free one, else LRU-evict an
        idle one (never a slot already serving this tick; the evictee's
        rows are parked host-side first). The bound slot's state rows
        are restored from the tenant's parked copy when one exists;
        else the slot joins ``last_resets``, which ``_serve`` cold-resets
        in one call once admission is done. Returns None when every
        slot is busy this tick."""
        free = [s for s in range(self.slots) if self.slot_tenant[s] is None
                and s not in used]
        if free:
            slot = free[0]
        else:
            idle = [s for s in range(self.slots) if s not in used]
            if not idle:
                return None
            slot = min(idle, key=lambda s: self._slot_last_tick[s])
            evicted = self.slot_tenant[slot]
            if evicted is not None:
                del self._tenant_slot[evicted]
                self._park(evicted, slot)
        self.slot_tenant[slot] = tenant_key
        self._tenant_slot[tenant_key] = slot
        if self._unpark(tenant_key, slot):
            self.last_restores.append(slot)
        else:
            self.last_resets.append(slot)
        return slot

    def step(self) -> int:
        """One engine tick: admit queued requests into slots, serve the
        active slots padded to a bucket, scatter state back. Returns
        the number of requests served.

        On the multi-resolution lattice a tick serves exactly ONE
        (size, pad-variant) cell — the head-of-queue's. Requests
        resolved to other cells stay queued (in order) for a later
        tick: a compiled program has one static (B, N) shape, and
        mixing cells in a tick would need a second program anyway."""
        if not self.queue:
            return 0
        cell, eligible = self._select_cell()
        if cell is None:
            # Scheduler deferral (slo_ms > 0): no cell is ripe — wait
            # for arrivals to fill a cell or for the recorded
            # ``_next_deadline`` (run() advances the clock to it). Not
            # a tick: _tick/last_* stay untouched.
            self.deferrals += 1
            self._prefetch_parked()
            return 0
        self._tick += 1
        with self.tracer.span("engine.step", tick=self._tick) as root:
            return self._serve(cell, eligible, root)

    def _serve(self, cell, eligible, root) -> int:
        """The tick ``step()`` dispatched, in the tracer's phases:
        ``engine.admit`` binds slots, ``engine.stage`` reads the lane
        images, ``engine.guard`` screens lanes and state rows (and every
        integrity-token refresh, wherever it runs), ``engine.stage``
        builds the batch and copies it to the device, ``engine.dispatch``
        calls the program, ``engine.sync`` pulls the logits, and
        ``engine.writeback`` scatters state and completes requests."""
        span = self.tracer.span
        size, masked_cell = cell
        self.last_resets = []
        self.last_restores = []
        self.last_quarantined = []
        used: set[int] = set()
        assigned: dict[int, int] = {}  # id(request) -> slot
        _tkey = self._tkey

        with span("engine.admit"):
            # Admission pass 1 — tenants that already own a slot reserve
            # it first, so a new tenant admitted later in the same tick
            # can only LRU-evict *idle* slots, never a warm tenant that is
            # itself active this tick (queue order must not decide whose
            # warm state survives). One lane per tenant per tick: state is
            # a serial carry, a tenant's second request waits for the next
            # tick so it warm-starts from the first's output.
            for req in eligible:
                if len(assigned) >= self.slots:
                    break
                slot = self._tenant_slot.get(_tkey(req))
                if slot is not None and slot not in used:
                    used.add(slot)
                    assigned[id(req)] = slot
            # Admission pass 2 — new tenants, in arrival order, into free
            # slots first, else LRU-evicting an idle slot.
            for req in eligible:
                if len(assigned) >= self.slots:
                    break
                if id(req) in assigned:
                    continue
                tkey = _tkey(req)
                if self._tenant_slot.get(tkey) is not None:
                    continue  # bound tenant already serving this tick
                slot = self._admit(tkey, used)
                if slot is None:
                    continue
                used.add(slot)
                assigned[id(req)] = slot
            # Every slot bound cold this tick, reset at once: nothing
            # reads a newly bound slot's rows before this, and a slot in
            # ``used`` is never evicted (parked) within the pass.
            if self.last_resets:
                self._reset_rows_all(self.last_resets)
            picked = [(assigned[id(r)], r) for r in eligible
                      if id(r) in assigned]
            self.queue = [r for r in self.queue if id(r) not in assigned]
            picked.sort(key=lambda sr: sr[0])

            state = self._ensure_slot_state(size)
            # Fault site: unsanctioned state mutation (bit corruption
            # that bypassed put_rows/reset_rows). The replaced state is
            # adopted WITHOUT refreshing the integrity tokens — detecting
            # exactly this is what the tokens are for.
            mutated = self._fire("state.rows", value=state)
            if mutated is not state:
                self._slot_states[size] = state = mutated

        with span("engine.stage"):
            images = []
            for _, req in picked:
                img = np.asarray(req.image, np.float32)
                fired = self._fire("admit.image", value=img,
                                   tenant=req.tenant)
                images.append(img if fired is img
                              else np.asarray(fired, np.float32))

        # Guarded screening (DESIGN.md §11): each picked lane passes
        # the admission finiteness screen and the state-row checks
        # before it may reach a compiled program. A failing lane is
        # handled per the fault taxonomy — co-batched healthy tenants
        # are served exactly as if the faulty lane never existed.
        healthy: list[tuple[int, VigRequest, np.ndarray]] = []
        with span("engine.guard"):
            # One batched device->host pull for all picked lanes' state
            # checks — the sync, not the crc/isfinite, is the guard cost
            # (the serve/guarded_* bench rows price exactly this).
            finite = fps = None
            if self.guards and picked:
                slots_picked = [slot for slot, _ in picked]
                finite = state.rows_finite(slots_picked, self.tracer.to_host)
                fps = state.row_fingerprints(slots_picked,
                                             self.tracer.to_host)
            for (slot, req), img in zip(picked, images):
                if self.guards and not np.isfinite(img).all():
                    self._quarantine(slot, req, FaultInfo(
                        kind="nonfinite_input", site="admit.image",
                        tenant=req.tenant, tick=self._tick,
                        detail="non-finite values in submitted image",
                    ))
                    continue
                if self.guards:
                    if not self._row_finite(slot, finite, size):
                        # Non-finite state rows: the tenant's warm carry
                        # is poisoned — fail this request, cold-reset the
                        # slot.
                        self._quarantine(slot, req, FaultInfo(
                            kind="nonfinite_state", site="state.rows",
                            tenant=req.tenant, tick=self._tick,
                            detail=f"non-finite state rows on slot {slot}",
                        ))
                        continue
                    if not self._row_intact(slot, fps, size):
                        # Finite but token-mismatched rows (silent
                        # corruption): recover by serving this request
                        # COLD — reset, re-fingerprint, keep the lane.
                        state = self._reset(state, [slot])
                        self._slot_states[size] = state
                        self.state_resets += 1
                        self.fault_log.append(FaultInfo(
                            kind="state_corruption", site="state.rows",
                            tenant=req.tenant, tick=self._tick,
                            detail=(f"integrity token mismatch on slot "
                                    f"{slot}; cold reset"),
                        ))
                        self.last_resets.append(slot)
                        self._refresh_tokens([slot], size)
                healthy.append((slot, req, img))

        if not healthy:
            self.last_lanes = []
            self.last_bucket = None
            self.last_cell = None
            self._prefetch_parked()
            return 0

        lanes = [slot for slot, _, _ in healthy]
        a = len(lanes)
        root.set(lanes=a)
        bucket = self.bucket_for(a)
        self.last_lanes = list(lanes)
        self.last_bucket = bucket
        self.last_cell = (size, bucket)
        # Padding lanes replicate lane 0 (image AND state row): their
        # compute mirrors a live lane — well-conditioned, and warm
        # whenever lane 0 is, so they never force the mixed warm/cold
        # path — and their outputs/state are simply dropped. The tick
        # width additionally rounds the bucket up to the next
        # mesh_batch_axis multiple (same replication) when the rows are
        # sharded — non-dividing buckets pad instead of failing.
        width = self._tick_width(bucket)
        rows = lanes + [lanes[0]] * (width - a)
        with span("engine.stage"):
            imgs_list: list[np.ndarray] = []
            masks_list: list[np.ndarray] = []
            for _, req, img in healthy:
                if masked_cell and img.shape[0] < size:
                    # Zero-pad the ragged image up to its cell: the patch
                    # embed is stride-patch (node-local), so live patches
                    # see exactly their own pixels and pad patches are
                    # BIG-norm-masked out of every top-k downstream.
                    canvas = np.zeros((size, size, img.shape[-1]),
                                      np.float32)
                    canvas[:img.shape[0], :img.shape[1]] = img
                    img = canvas
                imgs_list.append(img)
                if masked_cell:
                    mask = self._req_mask(req)
                    n = (size // self.cfg.patch) ** 2
                    masks_list.append(np.ones(n, bool) if mask is None
                                      else np.asarray(mask, bool))
            imgs = jnp.asarray(np.stack(
                imgs_list + [imgs_list[0]] * (width - a)))
            masks = (jnp.asarray(np.stack(
                masks_list + [masks_list[0]] * (width - a))),
            ) if masked_cell else ()
            state = self._slot_states[size]
            bucket_state = state.take_rows(rows)
        # The timed serve section: dispatch + device compute + the
        # host sync that materializes the logits. A per-engine
        # deadline budget (deadline_ms) turns stragglers into counted
        # misses; deadline_strikes consecutive misses descend the
        # degradation ladder.
        with span("engine.dispatch"):
            fwd = self._program_for(bucket, size, masked_cell)
            pkey = self._program_key(bucket, size, masked_cell)
            t0 = time.perf_counter()
            self._fire("tick.serve", bucket=bucket)
            logits, new_bucket_state = fwd(self.params, imgs, bucket_state,
                                           *masks)
        with span("engine.writeback"):
            # Scatter live lanes only: src rows >= a (padding) are dropped.
            self._slot_states[size] = state.put_rows(new_bucket_state, lanes)
        with span("engine.sync"):
            logits_np = self.tracer.to_host(logits)  # closes the region
        with span("engine.writeback"):
            self._graph_stats_update(state, self._slot_states[size], lanes)
            elapsed_ms = (time.perf_counter() - t0) * 1e3
            first_tick = pkey not in self._program_ticks
            self._program_ticks[pkey] = self._program_ticks.get(pkey, 0) + 1
            if self.deadline_ms is not None and not first_tick:
                # A bucket program's first served tick includes its jit
                # compile — never a deadline signal.
                if elapsed_ms > self.deadline_ms:
                    self.deadline_misses += 1
                    self._consecutive_misses += 1
                    info = FaultInfo(
                        kind="deadline_miss", site="tick.serve",
                        tick=self._tick,
                        detail=(f"bucket {bucket} tick {elapsed_ms:.2f}ms "
                                f"> budget {self.deadline_ms}ms"),
                    )
                    self.fault_log.append(info)
                    if self._consecutive_misses >= self.deadline_strikes:
                        self._degrade(dataclasses.replace(
                            info, kind="deadline_degrade",
                            detail=(f"{self._consecutive_misses} "
                                    "consecutive misses; descending "
                                    "ladder"),
                        ))
                else:
                    self._consecutive_misses = 0
            self._refresh_tokens(lanes, size)
            for i, (slot, req, _) in enumerate(healthy):
                req.logits = logits_np[i]
                req.done = True
                self._slot_last_tick[slot] = self._tick
                if req.tenant is None:
                    # anonymous one-shot: free the slot immediately so it
                    # never pins out live warm tenants under LRU eviction
                    # (the next occupant is cold-reset on admission)
                    self.slot_tenant[slot] = None
                    self._tenant_slot.pop(("req", req.uid), None)
            self.requests_served += a
            self.bucket_ticks[bucket] = self.bucket_ticks.get(bucket, 0) + 1
            cell = (size, bucket)
            self.cell_ticks[cell] = self.cell_ticks.get(cell, 0) + 1
            # padding-waste accounting (stats()/retune_buckets): the
            # invariant the property tests pin is padded_lanes ==
            # sum over ticks of (width - live), exactly.
            self.live_lanes += a
            self.padded_lanes += width - a
            self._count_digc(bucket, size, a)
            self.lane_hist[(size, a)] = self.lane_hist.get((size, a), 0) + 1
            self._prefetch_parked()
        return a

    def run(self) -> list[VigRequest]:
        """Drain the queue; returns the completed requests in
        submission order. (The engine keeps no completion log of its
        own — a step()-driven server owns its request objects, so
        nothing accumulates across ticks.)

        Under the admission scheduler (slo_ms > 0) a deferred tick
        advances time to the next recorded deadline — a ``VirtualClock``
        jumps (deterministic drains in tests/benches), the wall clock
        sleeps the remainder — so draining always terminates."""
        pending = list(self.queue)
        while self.queue:
            served = self.step()
            if not served and self.queue and self._next_deadline is not None:
                self._advance_to_deadline()
        return [r for r in pending if r.done]

    # -- observability --------------------------------------------------

    def state_steps(self) -> dict:
        """Per-batch-size view of the functional state's step counters
        (the direct fixed-batch path)."""
        return {b: c[1].steps() for b, c in self._compiled.items()}

    def slot_row_steps(self, size: Optional[int] = None) -> dict:
        """Per-slot request counters of the canonical multi-tenant
        state (empty before the first tick). ``size`` selects an
        N-bucket on the lattice; default is the primary size."""
        st = self.slot_state(size)
        return {} if st is None else st.row_steps()

    def slot_state(self, size: Optional[int] = None):
        """The canonical per-slot ``DigcState`` of an N-bucket (default:
        the primary size), or None before its first tick — where its
        buffers live (``.sharding``) is what sharded serving places."""
        return self._slot_states.get(
            self.image_sizes[0] if size is None else size)

    def program_text(self, bucket: int, size: Optional[int] = None) -> str:
        """Optimized HLO of an already-served exact-size (B, N) cell's
        program: which kernels its ticks run (a fused Pallas kernel
        shows as ``tpu_custom_call``) and which collectives."""
        size = self.image_sizes[0] if size is None else size
        fwd = self._programs[self._program_key(bucket, size)]
        width = self._tick_width(bucket)
        images = jax.ShapeDtypeStruct(
            (width, size, size, self.cfg.in_chans), jnp.float32)
        state = self._slot_states[size].take_rows([0] * width)
        return fwd.lower(self.params, images, state).compile().as_text()

    def cell_graphs(self, images, size: Optional[int] = None):
        """Run one exact-size tick of ``images`` (lane i on slot i's
        state rows) through the cell's forward as a served tick builds
        it — the tier the ladder serves, the tick width with lane-0
        padding, the mesh, the slot-state rows — with ``digc_capture``
        on, and return host ``(logits, graphs)`` for the live lanes:
        one ``(layer_key, nodes, co_nodes, idx)`` per DIGC call
        (``co_nodes`` None on a self-graph). A separate program: it
        leaves the served programs and the slot state untouched."""
        from repro.models.vig import vig_forward

        size = self.image_sizes[0] if size is None else size
        a = len(images)
        bucket = self.bucket_for(a)
        rows = list(range(a)) + [0] * (self._tick_width(bucket) - a)
        imgs = np.stack([np.asarray(images[r], np.float32) for r in rows])
        state = self._ensure_slot_state(size).take_rows(rows)
        choice = self._choice_for(bucket, size)
        cap: list = []

        def fwd(p, im, st):
            cap.clear()
            logits, _ = vig_forward(p, im, self.cfg, digc_impl=choice,
                                    state=st, digc_capture=cap)
            return logits, [c[1:] for c in cap]

        logits, arrays = jax.jit(fwd)(self.params, jnp.asarray(imgs), state)
        live = lambda v: None if v is None else np.asarray(v)[:a]  # noqa: E731
        return np.asarray(logits)[:a], [
            (c[0], *(live(v) for v in arr)) for c, arr in zip(cap, arrays)]

    def stats(self) -> dict:
        out = {"requests_served": self.requests_served,
               "digc_state": self.state_steps(),
               "buckets": self.buckets,
               "image_sizes": self.image_sizes,
               "bucket_ticks": dict(self.bucket_ticks),
               "cell_ticks": {f"{s}x{b}": n
                              for (s, b), n in self.cell_ticks.items()},
               "compiled_programs": self.compile_count,
               "slot_tenants": list(self.slot_tenant),
               "slot_row_steps": self.slot_row_steps(),
               "mesh": (None if self.mesh is None
                        else {k: int(v) for k, v in self.mesh.shape.items()}),
               "parked_tenants": list(self._parked),
               "park_hits": self.park_hits,
               "park_evictions": self.park_evictions,
               # admission scheduling + padding-waste accounting
               # (DESIGN.md §14) — live on the legacy slo_ms=0 path too
               "queue_depth": len(self.queue),
               "live_lanes": self.live_lanes,
               "padded_lanes": self.padded_lanes,
               "util": (self.live_lanes
                        / (self.live_lanes + self.padded_lanes)
                        if (self.live_lanes + self.padded_lanes) else 1.0),
               "lane_hist": {f"{s}x{live}": n
                             for (s, live), n in sorted(self.lane_hist.items())},
               "deferrals": self.deferrals,
               "slo_ms": (dict(self._slo_ms)
                          if isinstance(self._slo_ms, dict) else self._slo_ms),
               "prefetch_issued": self.prefetch_issued,
               "prefetch_hits": self.prefetch_hits,
               # fault tolerance (DESIGN.md §11)
               "guards": self.guards,
               "quarantines": self.quarantines,
               "state_resets": self.state_resets,
               "deadline_misses": self.deadline_misses,
               "fallback_level": self.fallback_level,
               "park_losses": self.park_losses,
               "retries": self.retries,
               "requests_failed": self.requests_failed,
               # stale-graph serving (DESIGN.md §12)
               "graph_reuses": self.graph_reuses,
               "graph_rebuilds": self.graph_rebuilds,
               "drift": {
                   "mean": (self._drift_sum / self._drift_n
                            if self._drift_n else 0.0),
                   "last": dict(self.last_drift),
               },
               "faults": [f.as_dict() for f in self.fault_log[-16:]],
               # spans and counters of the tick (serve/tracer.py)
               "tracer": self.tracer.totals()}
        if self.fallback_level > 0:
            from repro.core.builder import fallback_chain

            chain = fallback_chain(self._ladder_base_impl())
            out["fallback_impl"] = chain[self.fallback_level - 1]
        if self.schedule is not None:
            out["schedule"] = self.schedule.describe()
        if self.tuned is not None:
            out["tuned"] = [r.as_dict() for r in self.tuned]
        if self._bucket_schedules:
            out["bucket_schedules"] = {
                b: s.describe() for b, s in self._bucket_schedules.items()
            }
        return out
