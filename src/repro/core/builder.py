"""DigcSpec + GraphBuilder registry (DESIGN.md §4).

The paper's modularity claim — "the graph construction approach can be
generalized by adjusting the mechanism used to compute similarity" — is
realized here as first-class objects instead of stringly-typed if/elif
chains:

  * ``DigcSpec``     — a frozen dataclass naming the implementation plus
    every tunable knob (k, dilation, block shapes, strategy-specific
    parameters). Unknown knobs for a given builder *raise* instead of
    being silently dropped.
  * ``GraphBuilder`` — one registered entry per implementation tier or
    strategy: a batched build function, the set of knobs it accepts,
    capability flags (pos_bias / causal / exact / distributed) and an
    optional fused aggregation kernel.
  * the registry    — ``register`` / ``get_builder`` / ``list_builders``.
    Builders self-register at import time; ``_LAZY`` maps names to the
    module that registers them so ``get_builder("pallas")`` works without
    eagerly importing the kernel package.

Every build function is **batched-first**: it receives x (B, N, D),
y (B, M, D) and optional pos_bias (B, N, M) and returns (idx, dist),
each (B, N, k). ``promote_batch`` lifts single-image (N, D) inputs to
B=1 so the public ``digc`` entry point accepts both ranks.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Optional


@dataclasses.dataclass(frozen=True)
class DigcSpec:
    """Complete specification of one DIGC invocation.

    ``impl``, ``k``, ``dilation`` and ``causal`` are common to every
    builder; the remaining fields are strategy-specific knobs that
    default to None (= builder default). Setting a knob the selected
    builder does not accept is a ``ValueError`` at dispatch time.
    ``k`` has no default on purpose (None = unset): consumers that own
    a k (e.g. the ViG config) fill it in, so a spec passed only to pick
    an impl can never silently override the model's neighbor count.
    """

    impl: str = "blocked"
    k: Optional[int] = None
    dilation: int = 1
    causal: bool = False
    # --- blocked / pallas tiling
    block_n: Optional[int] = None
    block_m: Optional[int] = None
    # --- streaming-engine merge strategy (core/engine.py)
    merge: Optional[str] = None
    fuse_norms: Optional[bool] = None
    # selection group width for merge="select": 32 (one int32 lane
    # mask word, the default) or up to 64 (two mask words)
    group_w: Optional[int] = None
    # --- pallas kernel variants (§Perf iterations)
    interpret: Optional[bool] = None
    packed: Optional[bool] = None
    mxu_bf16: Optional[bool] = None
    bucket_rounds: Optional[int] = None
    # LSM/GMM realization inside the fused kernel: "bitonic" (default,
    # sorted two-level merge) or "legacy" (kd-pass extraction)
    kernel_merge: Optional[str] = None
    # --- cluster (ClusterViG family)
    n_clusters: Optional[int] = None
    n_probe: Optional[int] = None
    capacity_factor: Optional[float] = None
    seed: Optional[int] = None
    # --- axial (GreedyViG family)
    grid_h: Optional[int] = None
    grid_w: Optional[int] = None
    # --- stale-graph serving (DESIGN.md §12): drift-gated reuse of the
    # cached graph carried in a DigcStateEntry. Policies:
    #   "off"     — rebuild every call (the default; None means off)
    #   "layer"   — every call may serve the cached graph when the
    #               per-row feature drift is below drift_tau and the
    #               graph is younger than max_stale gated calls
    #   "tick"    — only the first call per forward (per stage) gates;
    #               later layers of the same tick reuse unconditionally
    #   "overlap" — always serve the cached (one-call-stale) graph and
    #               issue the refresh build data-independently of the
    #               convolution (pipelined double-buffer)
    reuse: Optional[str] = None
    drift_tau: Optional[float] = None
    max_stale: Optional[int] = None
    # --- ring (distributed): mesh + co-node ring axis, plus an
    # optional second mesh axis sharding the batch rows data-parallel
    # (serving slot rows x ring-sharded co-nodes, DESIGN.md §10)
    mesh: Optional[Any] = None
    axis_name: Optional[str] = None
    batch_axis: Optional[str] = None

    def mesh_shape(self) -> Optional[tuple[int, ...]]:
        """Device counts of the spec's mesh (None when unsharded) —
        part of the tuner's workload identity: a schedule measured on
        an N-way ring is not a single-device schedule."""
        if self.mesh is None:
            return None
        return tuple(int(s) for s in self.mesh.shape.values())

    def replace(self, **kw) -> "DigcSpec":
        return dataclasses.replace(self, **kw)

    def with_grid(self, grid_h: int, grid_w: int) -> "DigcSpec":
        """Fill grid-geometry knobs if this spec's builder accepts them.

        Models re-derive geometry per stage (pyramid stages shrink the
        grid), so any user-supplied grid knobs are replaced by the
        actual stage grid; a no-op for builders without grid knobs.
        """
        builder = get_builder(self.impl)
        updates = {
            f: v
            for f, v in (("grid_h", grid_h), ("grid_w", grid_w))
            if f in builder.knobs
        }
        return self.replace(**updates) if updates else self

    def knobs(self) -> dict[str, Any]:
        """The non-None strategy-specific knobs of this spec."""
        return {
            f: getattr(self, f)
            for f in KNOB_FIELDS
            if getattr(self, f) is not None
        }


_COMMON_FIELDS = ("impl", "k", "dilation", "causal")
KNOB_FIELDS: tuple[str, ...] = tuple(
    f.name for f in dataclasses.fields(DigcSpec) if f.name not in _COMMON_FIELDS
)

# -- stale-graph reuse policy (DESIGN.md §12) ------------------------------

REUSE_POLICIES: tuple[str, ...] = ("off", "layer", "tick", "overlap")
REUSE_KNOBS: frozenset = frozenset({"reuse", "drift_tau", "max_stale"})
DEFAULT_DRIFT_TAU = 0.05
DEFAULT_MAX_STALE = 4


def reuse_params(spec: DigcSpec) -> tuple[Optional[str], float, int]:
    """The spec's effective (policy, drift_tau, max_stale) triple.

    Policy is None when reuse is off ("off" and unset collapse — both
    mean every call rebuilds). Unset knobs take the serving defaults;
    the values themselves are validated by ``GraphBuilder.validate``.
    """
    policy = spec.reuse if spec.reuse not in (None, "off") else None
    tau = (
        float(spec.drift_tau) if spec.drift_tau is not None
        else DEFAULT_DRIFT_TAU
    )
    stale = (
        int(spec.max_stale) if spec.max_stale is not None
        else DEFAULT_MAX_STALE
    )
    return policy, tau, stale


@dataclasses.dataclass(frozen=True)
class GraphBuilder:
    """One registered graph-construction implementation.

    ``build(x, y, pos_bias, spec) -> (idx, dist)`` with batched inputs
    x (B, N, D), y (B, M, D), pos_bias (B, N, M) | None; outputs are
    (B, N, k) each, distances ascending, BIG-sentinel for invalid lanes.

    ``y`` is None for a self-graph call (the caller passed no co-nodes)
    — the explicit marker object identity cannot provide under jit.
    Builders that differentiate the self-graph case (axial) key on it;
    everyone else treats None as "co-nodes = x".
    """

    name: str
    build: Callable
    knobs: frozenset
    exact: bool = True
    supports_pos_bias: bool = False
    supports_causal: bool = False
    distributed: bool = False
    # Builders that thread functional DigcState (core/state.py) accept
    # build(..., state_entry=) and return (idx, dist, new_entry); for
    # everyone else digc() passes the state through unchanged.
    supports_state: bool = False
    # Builders that accept build(..., m_valid=) — a (M,) or (B, M) bool
    # mask marking live co-nodes. Masked co-nodes take the ring tier's
    # BIG-norm treatment (distance >= BIG/2, can never enter a top-k),
    # which is what lets serving pad ragged patch counts up to a static
    # N-bucket with inert pad nodes (DESIGN.md §13).
    supports_pad: bool = False
    # Optional fused neighbor aggregation (x, y, idx) -> (B, N, D);
    # None means the consumer uses the generic mr_aggregate.
    aggregate: Optional[Callable] = None
    doc: str = ""

    def validate(self, spec: DigcSpec, *, has_pos_bias: bool = False) -> None:
        """Reject knobs this builder does not accept (no silent drops)."""
        bad = [
            f
            for f in KNOB_FIELDS
            if getattr(spec, f) is not None and f not in self.knobs
        ]
        if bad:
            raise ValueError(
                f"DIGC impl {self.name!r} does not accept knob(s) {bad}; "
                f"accepted: {sorted(self.knobs) or '(none)'}"
            )
        if spec.causal and not self.supports_causal:
            raise ValueError(f"DIGC impl {self.name!r} does not support causal")
        if has_pos_bias and not self.supports_pos_bias:
            raise ValueError(f"DIGC impl {self.name!r} does not support pos_bias")
        # Reuse-policy values (the knob *names* were screened above):
        # malformed policies must fail at dispatch, not three ticks into
        # a serving loop as a silent always-rebuild.
        if spec.reuse is not None and spec.reuse not in REUSE_POLICIES:
            raise ValueError(
                f"DigcSpec.reuse={spec.reuse!r} is not a reuse policy; "
                f"valid: {REUSE_POLICIES}"
            )
        if spec.drift_tau is not None:
            if spec.drift_tau < 0:
                raise ValueError(
                    f"DigcSpec.drift_tau must be >= 0, got {spec.drift_tau}"
                )
            if spec.reuse in (None, "off"):
                raise ValueError(
                    "DigcSpec.drift_tau is set but reuse is off; pass "
                    "reuse='layer'|'tick'|'overlap' (a gate threshold "
                    "without a gate is a config error)"
                )
        if spec.max_stale is not None:
            if spec.max_stale < 1:
                raise ValueError(
                    f"DigcSpec.max_stale must be >= 1, got {spec.max_stale}"
                )
            if spec.reuse in (None, "off"):
                raise ValueError(
                    "DigcSpec.max_stale is set but reuse is off; pass "
                    "reuse='layer'|'tick'|'overlap'"
                )


_REGISTRY: dict[str, GraphBuilder] = {}

# name -> module whose import registers it (keeps the import graph light:
# asking for "pallas" is what pulls in the kernel package).
_LAZY: dict[str, str] = {
    "reference": "repro.core.digc",
    "blocked": "repro.core.digc",
    "pallas": "repro.kernels.ops",
    "ring": "repro.core.ring",
    "cluster": "repro.core.strategies",
    "axial": "repro.core.strategies",
}


def register(builder: GraphBuilder, *, overwrite: bool = False) -> GraphBuilder:
    if builder.name in _REGISTRY and not overwrite:
        raise ValueError(f"GraphBuilder {builder.name!r} already registered")
    _REGISTRY[builder.name] = builder
    return builder


def available_impls() -> tuple[str, ...]:
    """Names of every registered (or lazily registrable) builder."""
    return tuple(sorted(set(_REGISTRY) | set(_LAZY)))


def get_builder(name: str) -> GraphBuilder:
    if name not in _REGISTRY and name in _LAZY:
        importlib.import_module(_LAZY[name])
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown DIGC impl: {name!r}; available: {available_impls()}"
        )
    return _REGISTRY[name]


def list_builders() -> tuple[GraphBuilder, ...]:
    """All builders, lazily importing their defining modules."""
    return tuple(get_builder(n) for n in available_impls())


def resolve_spec(
    spec: Optional[DigcSpec] = None,
    *,
    impl: Optional[str] = None,
    k: Optional[int] = None,
    dilation: Optional[int] = None,
    causal: Optional[bool] = None,
    **knobs,
) -> DigcSpec:
    """Build (or refine) a DigcSpec from keyword-style arguments.

    With ``spec=None`` this is the legacy ``digc(x, k=.., impl=..)``
    path; with a spec, any explicitly passed common field or knob
    overrides the spec's value. Unknown knob *names* raise immediately.
    """
    unknown = set(knobs) - set(KNOB_FIELDS)
    if unknown:
        raise ValueError(
            f"unknown DIGC knob(s) {sorted(unknown)}; valid knobs: "
            f"{list(KNOB_FIELDS)}"
        )
    if spec is None:
        if k is None:
            raise TypeError("digc() requires k= (or a full spec=)")
        return DigcSpec(
            impl=impl or "blocked",
            k=k,
            dilation=1 if dilation is None else dilation,
            causal=bool(causal),
            **knobs,
        )
    overrides: dict[str, Any] = dict(knobs)
    if impl is not None:
        overrides["impl"] = impl
    if k is not None:
        overrides["k"] = k
    if dilation is not None:
        overrides["dilation"] = dilation
    if causal is not None:
        overrides["causal"] = causal
    spec = spec.replace(**overrides) if overrides else spec
    if spec.k is None:
        raise TypeError("DigcSpec.k is unset: pass k= or spec.replace(k=...)")
    return spec


# -- degradation ladder (fault-tolerant serving, DESIGN.md §11) ------------
#
# The paper's tiers are interchangeable by construction (same contract,
# "scales seamlessly across image resolutions, ViG layer types, and
# model sizes") — which gives serving a principled degraded mode: when
# a tier's program fails to build (a Pallas compile failure on an
# untested shape) or blows its tick deadline, serve the *same* request
# through the next-less-specialized tier instead of dying.
#
# Ordering rules: each rung must (1) accept the common spec fields
# (k / dilation / causal) with no tier-specific knobs, (2) depend on
# strictly less machinery than the rung above (pallas needs a working
# Mosaic lowering; blocked needs only XLA; reference needs only
# jnp.top_k and O(N*M) memory), and (3) never be *less* exact than the
# rung above — degrading must trade speed, not correctness. Approximate
# tiers (cluster, axial) and the distributed ring therefore degrade
# *into* the exact chain (blocked -> reference), never out of it.

DEGRADATION_LADDER: tuple[str, ...] = ("pallas", "blocked", "reference")


def fallback_chain(impl: str) -> tuple[str, ...]:
    """Ordered degraded impls to serve through when ``impl`` is
    unhealthy; empty for the last-resort tier (reference)."""
    if impl in DEGRADATION_LADDER:
        return DEGRADATION_LADDER[DEGRADATION_LADDER.index(impl) + 1:]
    # tiers outside the ladder (cluster / axial / ring) degrade into
    # the exact single-device chain
    return DEGRADATION_LADDER[1:]


def degraded_spec(spec: DigcSpec, impl: str) -> DigcSpec:
    """A clean spec serving ``spec``'s common fields through a
    degraded impl: strategy knobs are dropped — they belong to the
    tier that just failed, and the fallback must not inherit, say, a
    Pallas tile shape as a blocked block size. The stale-graph reuse
    knobs drop too: a degraded engine rebuilds every graph — trading
    speed is the ladder's contract, trading graph freshness is not."""
    return DigcSpec(
        impl=impl, k=spec.k, dilation=spec.dilation, causal=spec.causal
    )


def promote_batch(x, y=None, pos_bias=None):
    """Lift (N, D) [+ (N, M) pos_bias] to B=1; pass (B, N, D) through.

    Returns (x3, y3, pos3, squeeze) where squeeze records whether the
    caller should drop the batch axis from the outputs.
    """
    import jax.numpy as jnp

    if x.ndim not in (2, 3):
        raise ValueError(f"DIGC nodes must be (N, D) or (B, N, D); got {x.shape}")
    squeeze = x.ndim == 2
    x3 = x[None] if squeeze else x
    if y is None:
        y3 = x3
    else:
        if y.ndim not in (2, 3):
            raise ValueError(
                f"DIGC co-nodes must be (M, D) or (B, M, D); got {y.shape}"
            )
        y3 = y[None] if y.ndim == 2 else y
    if y3.shape[0] != x3.shape[0]:
        raise ValueError(
            f"batch mismatch: nodes {x3.shape[0]} vs co-nodes {y3.shape[0]}"
        )
    p3 = None
    if pos_bias is not None:
        if pos_bias.ndim not in (2, 3):
            raise ValueError(
                f"pos_bias must be (N, M) or (B, N, M); got {pos_bias.shape}"
            )
        p3 = pos_bias[None] if pos_bias.ndim == 2 else pos_bias
        n, m = x3.shape[1], y3.shape[1]
        if p3.shape[1:] != (n, m):
            raise ValueError(
                f"pos_bias shape {pos_bias.shape} does not match "
                f"N={n} nodes x M={m} co-nodes"
            )
        if p3.shape[0] not in (1, x3.shape[0]):
            raise ValueError(
                f"pos_bias batch {p3.shape[0]} does not match nodes batch "
                f"{x3.shape[0]} (or 1 for shared)"
            )
        if p3.shape[0] != x3.shape[0]:
            p3 = jnp.broadcast_to(p3, (x3.shape[0],) + p3.shape[1:])
    return x3, y3, p3, squeeze
