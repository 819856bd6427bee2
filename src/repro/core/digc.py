"""Dynamic Image Graph Construction (DIGC).

The paper's Algorithm 1: given node features X (N, D), co-node features
Y (M, D), optional relative positional bias P (N, M), a neighbor count k
and dilation d, return for every node the indices of its dilated
k-nearest co-nodes under squared euclidean distance:

    D_XY = ||x||^2 - 2 X Y^T + ||y||^2  (+ P)
    I'   = argsort(D_XY)[:, :k*d]
    I    = I'[:, ::d]

Every implementation is **batched-first**: inputs may be (B, N, D) /
(B, M, D) (a batch of images, the serving case) or (N, D) / (M, D)
(promoted to B=1, outputs squeezed back).

Implementation tiers (see DESIGN.md §3):

  * ``digc_reference``   -- Algorithm 1 verbatim. Materializes the full
    B x N x M distance matrix (this is the paper's CPU/GPU baseline and
    the oracle for every test).
  * ``digc_blocked``     -- the paper's streaming insight at the XLA
    level, routed through the unified engine (``repro.core.engine``,
    DESIGN.md §5): a two-level (block_n x block_m) tile grid with a
    pluggable LSM/GMM merge (exact grouped selection by default). Live
    memory is O(B * block_n * block_m), never O(B * N * M).
  * ``digc_pallas``      -- the fused Pallas TPU kernel
    (``repro.kernels.digc_topk``): distance + selection in one pass with
    the running candidate buffer resident in VMEM and batch as the
    leading grid dimension.

A fourth, distributed tier (``repro.core.ring``, DESIGN.md §10) runs
the same contract mesh-sharded: co-node shards rotate a device ring,
the whole batch rides one shard_map program, and a ``DigcState`` entry
carries the sharded co-node norms across requests.

``digc`` is the public entry point: a thin lookup into the GraphBuilder
registry (``repro.core.builder``, DESIGN.md §4). Select a tier with a
``DigcSpec`` (``digc(x, y, spec=...)``) or the legacy ``impl=`` keyword.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

import dataclasses

from repro.core.builder import (
    REUSE_KNOBS,
    DigcSpec,
    GraphBuilder,
    get_builder,
    promote_batch,
    register,
    resolve_spec,
    reuse_params,
)

# Large-but-finite sentinel: inf would produce nan under (inf - inf) when a
# positional bias is added to a padded lane.
BIG = float(1e30)

Array = jax.Array


def pairwise_sq_dists(x: Array, y: Array, pos_bias: Optional[Array] = None) -> Array:
    """Squared-euclidean distance matrix (Algorithm 1 lines 3-7).

    x (..., N, D), y (..., M, D) -> (..., N, M); leading batch dims
    broadcast through the einsum.
    """
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    inner = -2.0 * jnp.einsum("...nd,...md->...nm", x, y)
    sq_x = jnp.sum(x * x, axis=-1)[..., :, None]
    sq_y = jnp.sum(y * y, axis=-1)[..., None, :]
    d = inner + sq_x + sq_y
    if pos_bias is not None:
        d = d + pos_bias
    return d


def dilate(idx_sorted: Array, dilation: int) -> Array:
    """Neighbor Selection Module: every d-th entry of the top k*d list."""
    if dilation == 1:
        return idx_sorted
    return idx_sorted[..., ::dilation]


def digc_reference(
    x: Array,
    y: Optional[Array] = None,
    *,
    k: int,
    dilation: int = 1,
    pos_bias: Optional[Array] = None,
    return_dists: bool = False,
    causal: bool = False,
    m_valid: Optional[Array] = None,
):
    """Algorithm 1, verbatim (materializes the full distance matrix).

    Accepts (N, D) or (B, N, D). Entries reported with distance >=
    BIG/2 are invalid placeholders (causally excluded / padding); their
    indices are unspecified and consumers must mask on the distance.
    This matches the blocked and Pallas tiers. ``m_valid`` ((M,) or
    (B, M) bool) BIG-masks pad co-node columns — the ring tier's pad
    idiom, so live rows' top-k is exactly the top-k over live co-nodes.
    """
    x3, y3, p3, squeeze = promote_batch(x, y, pos_bias)
    kd = k * dilation
    _, n, _ = x3.shape
    m = y3.shape[1]
    if kd > m:
        raise ValueError(f"k*dilation={kd} exceeds number of co-nodes M={m}")
    d_xy = pairwise_sq_dists(x3, y3, p3)
    if m_valid is not None:
        mask = jnp.asarray(m_valid, bool)
        mask = mask[None, None, :] if mask.ndim == 1 else mask[:, None, :]
        d_xy = jnp.where(mask, d_xy, BIG)
    if causal:
        keep = jnp.arange(m)[None, :] <= jnp.arange(n)[:, None]
        d_xy = jnp.where(keep[None], d_xy, BIG)
    neg_top, idx = lax.top_k(-d_xy, kd)  # sorted ascending by distance
    idx = dilate(idx.astype(jnp.int32), dilation)
    dist = dilate(-neg_top, dilation)
    if squeeze:
        idx, dist = idx[0], dist[0]
    if return_dists:
        return idx, dist
    return idx


def merge_topk(
    run_d: Array, run_i: Array, blk_d: Array, blk_i: Array, kd: int
) -> tuple[Array, Array]:
    """Merge a running sorted top-kd list with a new candidate block.

    This is the TPU analogue of the paper's GMM k-way heap merge: the
    running list plays the role of the heap contents, the block plays the
    role of a freshly-sorted local stream. Output is sorted ascending.

    run_d/run_i: (..., N, kd); blk_d/blk_i: (..., N, B). Returns the new
    (..., N, kd) pair; leading batch dims pass through.
    """
    cand_d = jnp.concatenate([run_d, blk_d], axis=-1)
    cand_i = jnp.concatenate([run_i, blk_i], axis=-1)
    neg_top, sel = lax.top_k(-cand_d, kd)
    new_i = jnp.take_along_axis(cand_i, sel, axis=-1)
    return -neg_top, new_i


def digc_blocked(
    x: Array,
    y: Optional[Array] = None,
    *,
    k: int,
    dilation: int = 1,
    pos_bias: Optional[Array] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    merge: Optional[str] = None,
    fuse_norms: bool = False,
    mxu_bf16: bool = False,
    sq_y: Optional[Array] = None,
    return_dists: bool = False,
    causal: bool = False,
    group_w: Optional[int] = None,
    m_valid: Optional[Array] = None,
):
    """Streaming DIGC through the unified engine (``core/engine.py``).

    Paper-faithful dataflow (DCM tile -> local selection -> global
    merge -> dilated selection) expressed in pure XLA so it runs on any
    backend; the Pallas kernel implements the same dataflow fused.
    Two-level tiling: the whole batch advances through each
    (block_n x block_m) tile together, so live memory is
    O(B * block_n * block_m). ``block_m=None`` sizes the co-node tile
    from the shape (``engine.conode_tile``: the whole co-node set when
    its distance block fits the engine's budget, else the fewest equal
    tiles that do); an explicit ``block_m`` is kept. ``merge`` selects
    the per-tile LSM: "topk" (the default) one exact ``lax.top_k`` per
    tile, "select" exact grouped extraction in kd rounds, "packed"
    tie-tolerant packed keys;
    ``fuse_norms`` folds the norm terms into the distance matmul
    (tie-tolerant), ``mxu_bf16`` runs the contraction in bf16.
    """
    from repro.core.engine import conode_tile, stream_topk

    x3, y3, p3, squeeze = promote_batch(x, y, pos_bias)
    kd = k * dilation
    b, n, _ = x3.shape
    block_m = conode_tile(b, n, y3.shape[1], block_m, block_n)
    dist, idx = stream_topk(
        x3,
        None if y is None else y3,
        p3,
        kd=kd,
        block_m=block_m,
        block_n=block_n,
        merge=merge,
        fuse_norms=fuse_norms,
        mxu_bf16=mxu_bf16,
        causal=causal,
        sq_y=sq_y,
        group_w=group_w,
        m_valid=m_valid,
    )
    idx = dilate(idx, dilation)
    dist = dilate(dist, dilation)
    if squeeze:
        idx, dist = idx[0], dist[0]
    if return_dists:
        return idx, dist
    return idx


# --------------------------------------------------------------------------
# Drift-gated stale-graph reuse (DESIGN.md §12).
#
# The graph index is a cached, versioned artifact living in the
# DigcStateEntry (graph_idx/graph_dist + the graph_snap drift snapshot
# and graph_age staleness counter). The gate below is impl-agnostic: it
# wraps any supports_state builder's build, so every stateful tier
# (blocked, cluster, ring) serves through the same policy machinery.
# Everything is a runtime lax.cond inside the one donated jit program —
# warm serving stays a single dispatch — and per batch row, so
# co-batched tenants gate independently.


def drift_stat(x: Array) -> Array:
    """The cheap per-row feature statistic the reuse gate compares:
    mean |x|^2 over nodes and channels, (B, N, D) -> (B,) float32.
    Vision GNN's observation that patch features evolve smoothly across
    layers is what makes this scalar a usable drift proxy; the
    recall-vs-drift_tau bench rows measure how far it can be trusted."""
    return jnp.mean(jnp.square(x.astype(jnp.float32)), axis=(1, 2))


def _mix_rows(sel_row, kept, built):
    """Per-row select between two pytree-aligned buffers: ``sel_row``
    (B,) True keeps ``kept``'s row. None passes ``built`` through."""
    if kept is None or built is None:
        return built
    sel = sel_row.reshape(sel_row.shape + (1,) * (built.ndim - 1))
    return jnp.where(sel, kept, built)


def _stateful_build(builder, x3, y_arg, p3, spec, entry, m_valid=None):
    kw = {} if m_valid is None else {"m_valid": m_valid}
    idx, dist, new_entry = builder.build(x3, y_arg, p3, spec,
                                         state_entry=entry, **kw)
    return idx, dist, new_entry


def _reuse_build(builder, x3, y_arg, p3, spec, entry, *, reuse_first,
                 m_valid=None):
    """The drift-gated reuse path around a stateful builder's build.

    Returns (idx, dist, new_entry). Falls back to the plain stateful
    build (bit-identical to ``reuse="off"``) whenever the policy cannot
    engage *statically*: no cached-graph buffers in the entry, a cached
    shape from another workload, or ``drift_tau == 0`` (the documented
    "reuse disabled" verification setting — a zero threshold admits no
    drift, including none at all).
    """
    policy, tau, max_stale = reuse_params(spec)
    b, n, _ = x3.shape
    if (
        policy is None
        or entry.graph_idx is None
        or entry.graph_idx.shape != (b, n, spec.k)
    ):
        return _stateful_build(builder, x3, y_arg, p3, spec, entry, m_valid)
    if policy in ("layer", "tick") and tau == 0.0:
        return _stateful_build(builder, x3, y_arg, p3, spec, entry, m_valid)

    valid = (
        entry.row_warm if entry.row_step is not None
        else jnp.broadcast_to(entry.warm, (b,))
    )
    stat = drift_stat(x3)

    if policy == "overlap":
        return _overlap_build(
            builder, x3, y_arg, p3, spec, entry, valid=valid, stat=stat,
            m_valid=m_valid,
        )

    drift = jnp.abs(stat - entry.graph_snap) / jnp.maximum(
        jnp.abs(entry.graph_snap), 1e-9
    )
    if policy == "tick" and not reuse_first:
        # Within a tick, layers after the stage's gated first call reuse
        # whatever that call left (fresh or reused) unconditionally and
        # without aging — the graph is per-tick in this policy, so
        # staleness is counted in ticks, not layers.
        reuse_row = valid
        age_inc = 0
    else:
        reuse_row = valid & (entry.graph_age < max_stale) & (drift < tau)
        age_inc = 1

    def serve_cached():
        return (
            entry.graph_idx,
            entry.graph_dist,
            entry.bump(graph_age=entry.graph_age + age_inc),
        )

    def rebuild_mixed():
        f_idx, f_dist, built = _stateful_build(
            builder, x3, y_arg, p3, spec, entry, m_valid
        )
        idx = _mix_rows(reuse_row, entry.graph_idx, f_idx)
        dist = _mix_rows(reuse_row, entry.graph_dist, f_dist)
        # Per-row independence: a reused row must carry exactly the
        # builder state its solo replay (which never built) would —
        # keep its centroids/norms, not the mixed batch's rebuild.
        return idx, dist, dataclasses.replace(
            built,
            centroids=_mix_rows(reuse_row, entry.centroids, built.centroids),
            sq_y=_mix_rows(reuse_row, entry.sq_y, built.sq_y),
            graph_idx=idx,
            graph_dist=dist,
            graph_snap=jnp.where(reuse_row, entry.graph_snap, stat),
            graph_age=jnp.where(
                reuse_row, entry.graph_age + age_inc, jnp.int32(0)
            ),
        )

    # All-reuse is the serving steady state: the cond's true branch
    # touches no distance compute at all — the whole build is skipped,
    # which is where the warm per-tick speedup comes from.
    return lax.cond(jnp.all(reuse_row), serve_cached, rebuild_mixed)


def _overlap_build(builder, x3, y_arg, p3, spec, entry, *, valid, stat,
                   m_valid=None):
    """Double-buffered overlap (DESIGN.md §12): serve the cached
    (one-call-stale) graph unconditionally for warm rows, and issue the
    refresh build so that the *served* outputs never depend on it — the
    fresh graph flows only into the returned entry (next call's cache),
    so XLA's scheduler is free to run it concurrently with the MRConv/
    FFN compute consuming the cached graph. Cold rows take a build
    inside the mixed branch (a second build that tick — cold only)."""

    def serve_cached():
        return entry.graph_idx, entry.graph_dist

    def serve_mixed():
        f_idx, f_dist, _ = _stateful_build(
            builder, x3, y_arg, p3, spec, entry, m_valid
        )
        return (
            _mix_rows(valid, entry.graph_idx, f_idx),
            _mix_rows(valid, entry.graph_dist, f_dist),
        )

    idx, dist = lax.cond(jnp.all(valid), serve_cached, serve_mixed)
    # The refresh build: data-independent of (idx, dist) by
    # construction — it is captured only by the state update.
    f_idx, f_dist, built = _stateful_build(
        builder, x3, y_arg, p3, spec, entry, m_valid
    )
    new_entry = dataclasses.replace(
        built,
        graph_idx=f_idx,
        graph_dist=f_dist,
        graph_snap=stat,
        graph_age=jnp.zeros_like(entry.graph_age),
    )
    return idx, dist, new_entry


def digc(
    x: Array,
    y: Optional[Array] = None,
    *,
    spec: Optional[DigcSpec] = None,
    k: Optional[int] = None,
    dilation: Optional[int] = None,
    impl: Optional[str] = None,
    pos_bias: Optional[Array] = None,
    return_dists: bool = False,
    causal: Optional[bool] = None,
    state=None,
    state_key=None,
    reuse_first: bool = True,
    fault_plan=None,
    m_valid: Optional[Array] = None,
    **knobs,
):
    """Public DIGC API: a thin GraphBuilder-registry lookup.

    Either pass a full ``spec=DigcSpec(...)`` or the legacy keywords
    (``k``, ``dilation``, ``impl``, plus builder knobs). Unknown knobs
    for the selected builder raise instead of being silently dropped.
    Accepts (N, D) or (B, N, D) nodes; outputs match the input rank.
    ``y=None`` is the self-graph spelling — builders that distinguish it
    (axial) see None; passing x explicitly as y counts as external
    co-nodes (so eager and jitted calls agree).

    ``state``/``state_key`` (a functional ``repro.core.state.DigcState``
    pytree plus the key naming this call's entry) select the
    **functional form**: the call returns ``(idx[, dist], new_state)``
    and works *under jit* — stateful builders (cluster centroids,
    frozen-gallery norms) read their entry's buffers gated on its step
    counter and return an updated entry; builders without state (or a
    state with no entry for the key) pass the state through unchanged.
    When the spec carries a ``reuse`` policy and the entry carries
    cached-graph buffers, the call serves through the drift gate
    (DESIGN.md §12); ``reuse_first=False`` marks a non-first call of
    the same forward pass (the ``"tick"`` policy reuses those
    unconditionally instead of re-gating).

    ``fault_plan`` (a ``repro.core.faults.FaultPlan``) is the
    fault-injection hook (DESIGN.md §11): when set, the node features
    pass through the plan's ``digc.x`` site before construction —
    zero-overhead and a no-op when ``None``, and host-side only
    (bypassed under tracing).

    ``m_valid`` ((M,) or (B, M) bool) marks live co-nodes; pad columns
    are BIG-norm-masked so they can never enter a live row's top-k
    (the multi-resolution pad-node contract, DESIGN.md §13). Raises for
    builders without the ``supports_pad`` capability.
    """
    spec = resolve_spec(
        spec, impl=impl, k=k, dilation=dilation, causal=causal, **knobs
    )
    builder = get_builder(spec.impl)
    builder.validate(spec, has_pos_bias=pos_bias is not None)
    if m_valid is not None and not builder.supports_pad:
        raise ValueError(
            f"DIGC impl {spec.impl!r} does not support pad-node masking "
            f"(m_valid); pad-capable impls: "
            f"{[b.name for b in _pad_capable()]}"
        )
    if fault_plan is not None and not isinstance(x, jax.core.Tracer):
        x = jnp.asarray(fault_plan.fire("digc.x", value=x, impl=spec.impl))
    x3, y3, p3, squeeze = promote_batch(x, y, pos_bias)
    y_arg = None if y is None else y3
    if state is not None:
        entry = state.get(state_key)
        if builder.supports_state and entry is not None:
            # The stale-graph reuse gate (DESIGN.md §12) wraps every
            # stateful builder uniformly; with reuse off it *is* the
            # plain build. ``reuse_first`` marks the first call of a
            # forward pass for this entry (the tick-policy gate point).
            idx, dist, new_entry = _reuse_build(
                builder, x3, y_arg, p3, spec, entry,
                reuse_first=reuse_first, m_valid=m_valid,
            )
            state = state.set(state_key, new_entry)
        else:
            idx, dist = builder.build(x3, y_arg, p3, spec, **_pad_kw(m_valid))
        if squeeze:
            idx, dist = idx[0], dist[0]
        if return_dists:
            return idx, dist, state
        return idx, state
    idx, dist = builder.build(x3, y_arg, p3, spec, **_pad_kw(m_valid))
    if squeeze:
        idx, dist = idx[0], dist[0]
    if return_dists:
        return idx, dist
    return idx


def _pad_kw(m_valid):
    """Keyword dict for a build call: empty when unmasked so builders
    without the ``m_valid`` keyword keep their signatures."""
    return {} if m_valid is None else {"m_valid": m_valid}


def _pad_capable():
    from repro.core.builder import available_impls

    out = []
    for name in available_impls():
        try:
            b = get_builder(name)
        except Exception:
            continue
        if b.supports_pad:
            out.append(b)
    return out


@functools.partial(jax.jit, static_argnames=("k", "dilation"))
def digc_blocked_jit(x, y, k: int, dilation: int = 1):
    return digc_blocked(x, y, k=k, dilation=dilation)


# --------------------------------------------------------------------------
# Registry entries (DESIGN.md §4). Build fns take batched (B, N, D) /
# (B, M, D) / (B, N, M) and return ((B, N, k) idx, (B, N, k) dist).


def _build_reference(x, y, pos_bias, spec: DigcSpec, m_valid=None):
    return digc_reference(
        x, y, k=spec.k, dilation=spec.dilation, pos_bias=pos_bias,
        causal=spec.causal, return_dists=True, m_valid=m_valid,
    )


def _build_blocked(x, y, pos_bias, spec: DigcSpec, state_entry=None,
                   m_valid=None):
    # Exact tier: no implicit cache reads. Per-call norm reuse
    # (self-graph ||x||^2 == ||y||^2) happens inside the engine; a
    # caller serving a *fixed* co-node gallery passes precomputed norms
    # explicitly via digc_blocked(sq_y=...) or through a functional
    # state entry carrying sq_y — an implicit cache keyed by call-site
    # would silently serve stale norms once the co-node contents change
    # (e.g. per-layer pooled features), corrupting an exact tier.
    sq_y = None
    new_entry = None
    if state_entry is not None:
        new_entry = state_entry.bump()
        if (
            y is not None
            and state_entry.sq_y is not None
            and state_entry.sq_y.shape == y.shape[:-1]
        ):
            # The entry asserts this gallery is frozen (state.py
            # invalidation rules): compute the norms on the cold call
            # only, then carry them — jit-compatible because the cold
            # branch is a lax.cond on the runtime step counter. With
            # per-row counters (multi-tenant serving) the gate is per
            # batch row: warm rows read their carried norms, rows just
            # reset for a new tenant recompute theirs — norms are cheap
            # enough that the mixed batch computes them unconditionally
            # and selects.
            if state_entry.row_step is not None:
                sq_y = jnp.where(
                    state_entry.row_warm[:, None],
                    state_entry.sq_y,
                    jnp.sum(y.astype(jnp.float32) ** 2, axis=-1),
                )
            else:
                sq_y = lax.cond(
                    state_entry.warm,
                    lambda: state_entry.sq_y,
                    lambda: jnp.sum(y.astype(jnp.float32) ** 2, axis=-1),
                )
            new_entry = state_entry.bump(sq_y=sq_y)
    out = digc_blocked(
        x, y, k=spec.k, dilation=spec.dilation, pos_bias=pos_bias,
        causal=spec.causal, return_dists=True,
        block_m=spec.block_m,
        block_n=spec.block_n,
        merge=spec.merge,
        fuse_norms=bool(spec.fuse_norms),
        mxu_bf16=bool(spec.mxu_bf16),
        sq_y=sq_y,
        group_w=spec.group_w,
        m_valid=m_valid,
    )
    if state_entry is not None:
        return (*out, new_entry)
    return out


register(GraphBuilder(
    name="reference",
    build=_build_reference,
    knobs=frozenset(),
    exact=True,
    supports_pos_bias=True,
    supports_causal=True,
    supports_pad=True,  # BIG-masked pad co-node columns (m_valid)
    doc="Algorithm 1 verbatim; full distance matrix (oracle tier)",
))

register(GraphBuilder(
    name="blocked",
    build=_build_blocked,
    knobs=frozenset({
        "block_n", "block_m", "merge", "fuse_norms", "mxu_bf16", "group_w",
    }) | REUSE_KNOBS,
    exact=True,  # merge="packed" / fuse_norms / mxu_bf16 opt into tie-tolerance
    supports_pos_bias=True,
    supports_causal=True,
    supports_state=True,  # frozen-gallery norms via DigcState entries
    supports_pad=True,  # BIG-norm pad masking folded into sq_y
    doc="streaming XLA engine: two-level (block_n x block_m) tiling + "
        "pluggable LSM/GMM merge (select | topk | packed)",
))
