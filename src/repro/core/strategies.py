"""Alternative graph-construction strategies (paper §VI conclusion:
"the graph construction approach can be generalized by adjusting the
mechanism used to compute similarity ... clustering-based approaches
exemplified by ClusterViG and greedy edge-selection techniques used in
GreedyViG").

Both reuse the DIGC substrate (blocked distance + top-k merge), keep
static shapes (TPU-compilable), and are batched-first — (B, N, D) in,
(B, N, k) out, with (N, D) promoted to B=1:

  * ``cluster_digc`` — IVF-style two-stage search (ClusterViG family):
    k-means centroids over co-nodes, queries probe only the n_probe
    nearest clusters. O(N·(C + probe·cap)·D) vs O(N·M·D).
  * ``axial_digc``   — GreedyViG-family axial construction: candidates
    restricted to the query's grid row + column. O(N·(H+W)·D).

Approximate by design; recall measured in the tests. Both are
registered GraphBuilders (DESIGN.md §4), peers of the exact tiers.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.builder import (
    REUSE_KNOBS, DigcSpec, GraphBuilder, promote_batch, register,
)
from repro.core.digc import BIG, digc_blocked, dilate, pairwise_sq_dists
from repro.core.engine import select_topkd


def kmeans(y: jax.Array, n_clusters: int, iters: int = 5,
           seed: int = 0, init: Optional[jax.Array] = None) -> jax.Array:
    """Lightweight Lloyd's iterations. y (M, D) -> centroids (C, D).

    ``init`` warm-starts from previous centroids (a DigcState carry:
    consecutive ViG layers / serving requests drift slowly, so a warm
    start converges in 1-2 iterations instead of 5 from random init).
    """
    m = y.shape[0]
    if init is None:
        idx = jax.random.permutation(jax.random.PRNGKey(seed), m)[:n_clusters]
        cents = y[idx]
    else:
        cents = init.astype(y.dtype)

    def step(cents, _):
        d = pairwise_sq_dists(y, cents)  # (M, C)
        assign = jnp.argmin(d, axis=1)
        onehot = jax.nn.one_hot(assign, n_clusters, dtype=y.dtype)  # (M, C)
        sums = onehot.T @ y  # (C, D)
        counts = jnp.sum(onehot, axis=0)[:, None]
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), cents)
        return new, None

    cents, _ = lax.scan(step, cents, None, length=iters)
    return cents


def default_cluster_params(m: int, n_clusters: Optional[int],
                           n_probe: Optional[int]) -> tuple[int, int]:
    """Workload-adaptive defaults (previously hard-coded in the model):
    ~28 co-nodes per cluster, probe up to 8 clusters."""
    if n_clusters is None:
        n_clusters = max(m // 28, 4)
    n_clusters = min(n_clusters, m)
    if n_probe is None:
        n_probe = 8
    return n_clusters, min(n_probe, n_clusters)


def _segment_ranks(labels: jax.Array) -> jax.Array:
    """Rank of each element within its label group, in original order.

    Sort-based: a stable argsort groups equal labels, the rank within a
    group is the position minus the group start (a running max over
    change points), scattered back through the sort order. O(L log L)
    on L elements — replaces the (L, C) one-hot + column cumsum whose
    materialized L*C intermediate dominated the dispatch cost.
    """
    L = labels.shape[0]
    order = jnp.argsort(labels)  # lax.sort: stable
    sorted_l = labels[order]
    pos = jnp.arange(L, dtype=jnp.int32)
    change = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_l[1:] != sorted_l[:-1]]
    )
    seg_start = lax.cummax(jnp.where(change, pos, 0))
    rank_sorted = pos - seg_start
    return jnp.zeros((L,), jnp.int32).at[order].set(rank_sorted)


def _cluster_index(y, *, n_clusters, cap, seed, iters=5, init_centroids=None):
    """Build the IVF index for one co-node set: y (M, D) ->
    (centroids (C, D), members (C, cap) with pad id M).

    Hoisted out of the per-image search so it runs once when co-nodes
    are shared across the batch, and so DigcState can warm-start the
    k-means from a previous layer's / request's centroids.
    """
    m = y.shape[0]
    cents = kmeans(y, n_clusters, iters=iters, seed=seed, init=init_centroids)
    d_yc = pairwise_sq_dists(y, cents)  # (M, C)
    assign = jnp.argmin(d_yc, axis=1)  # (M,)
    # fixed-capacity member lists via rank-in-cluster scatter
    pos = _segment_ranks(assign)  # (M,)
    keep = pos < cap
    slot = jnp.where(keep, assign * cap + pos, n_clusters * cap)
    members = jnp.full((n_clusters * cap + 1,), m, jnp.int32)  # m = pad id
    members = members.at[slot].set(jnp.arange(m, dtype=jnp.int32))
    members = members[:-1].reshape(n_clusters, cap)
    return cents, members


def _cluster_search(x, y, cents, members, *, kd, n_probe, block_t=128):
    """Dispatch-form two-stage search for one image.

    Stage 2 is organized cluster-major (the MoE group-GEMM pattern, as
    in ClusterViG's balanced partitions): each (query, probe-slot) pair
    is assigned a dispatch slot in its target cluster's *block-aligned*
    segment — every cluster's pair list is padded only up to the next
    ``block_t`` boundary, so the static dispatch size is
    N*n_probe + C*block_t and **no query is ever dropped**. Each
    block_t-row block belongs to exactly one cluster and runs one dense
    (block_t x D) @ (D x cap) contraction against that cluster's member
    features; per-query candidate rows are combined back by slot.

    This replaces the per-query candidate-feature gather of the old
    path — (N, P, D) rows pulled through XLA's scalar row-gather, ~60x
    the traffic of the cluster-major form — with matmul-form distances
    (``pairwise_sq_dists`` algebra: ||y||^2 - 2xy; the query norm is
    added back at the end, rank-invariant since it is constant per
    row).
    """
    n, d = x.shape
    m = y.shape[0]
    n_clusters, cap = members.shape
    y_pad = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)], axis=0)
    sq_y = jnp.concatenate(
        [jnp.sum(y.astype(jnp.float32) ** 2, axis=-1), jnp.full((1,), BIG)], 0
    )
    cluster_feats = y_pad[members]  # (C, cap, D) — cluster-major gather
    sq_members = sq_y[members]  # (C, cap); BIG on member pads

    # stage 1: nearest centroids per query
    d_xc = pairwise_sq_dists(x, cents)  # (N, C)
    _, probe = lax.top_k(-d_xc, n_probe)  # (N, n_probe)

    # dispatch: each (query, probe-slot) pair gets a slot in its target
    # cluster's block-aligned segment
    flat_c = probe.reshape(-1)  # (N * n_probe,)
    q_of = jnp.repeat(jnp.arange(n, dtype=jnp.int32), n_probe)
    rank = _segment_ranks(flat_c)  # (N * n_probe,)
    counts = jnp.zeros((n_clusters,), jnp.int32).at[flat_c].add(1)
    seg_len = ((counts + block_t - 1) // block_t) * block_t
    ends = jnp.cumsum(seg_len)
    starts = ends - seg_len
    slot = starts[flat_c] + rank  # (N * n_probe,) — never dropped
    # static bound on sum(seg_len), rounded to whole blocks
    nblocks = -(-(n * n_probe) // block_t) + n_clusters
    total = nblocks * block_t
    qmap = jnp.full((total,), n, jnp.int32).at[slot].set(q_of)
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    x_disp = x_pad[qmap].reshape(nblocks, block_t, d)
    # block -> owning cluster (blocks past the used prefix hit the BIG
    # pad cluster)
    block_c = jnp.searchsorted(
        ends, jnp.arange(nblocks, dtype=jnp.int32) * block_t, side="right"
    )
    feats_pad = jnp.concatenate(
        [cluster_feats, jnp.zeros((1, cap, d), y.dtype)], axis=0)
    sqm_pad = jnp.concatenate(
        [sq_members, jnp.full((1, cap), BIG, jnp.float32)], axis=0)
    feats_blk = feats_pad[jnp.minimum(block_c, n_clusters)]  # (nb, cap, D)
    sqm_blk = sqm_pad[jnp.minimum(block_c, n_clusters)]  # (nb, cap)

    # per-block dense contraction: -2 X_blk Y_c^T + ||y||^2
    xy = lax.dot_general(
        x_disp, feats_blk, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # (nb, block_t, cap)
    d_c = sqm_blk[:, None, :] - 2.0 * xy

    # combine: each (query, slot) reads its cap-row back
    d_flat = d_c.reshape(total, cap)
    cand_d = d_flat[slot].reshape(n, n_probe * cap)  # (N, P)
    cand_i = members[probe].reshape(n, n_probe * cap)

    kd_eff = min(kd, cand_d.shape[1])
    vals, cols = select_topkd(cand_d, kd_eff)
    idx = jnp.take_along_axis(cand_i, cols, axis=-1)
    # add the per-query norm back (rank-invariant; BIG lanes stay BIG)
    dist = vals + jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)[:, None]
    dist = jnp.where(vals >= BIG / 2, vals, dist)
    if kd_eff < kd:  # pad to kd for API uniformity
        idx = jnp.pad(idx, ((0, 0), (0, kd - kd_eff)))
        dist = jnp.pad(dist, ((0, 0), (0, kd - kd_eff)), constant_values=BIG)
    return idx, dist


def cluster_digc(
    x: jax.Array,
    y: Optional[jax.Array] = None,
    *,
    k: int,
    dilation: int = 1,
    n_clusters: Optional[int] = None,
    n_probe: Optional[int] = None,
    capacity_factor: float = 2.0,
    seed: int = 0,
    kmeans_iters: int = 5,
    init_centroids: Optional[jax.Array] = None,
    init_valid: Optional[jax.Array] = None,
    warm_iters: int = 2,
    return_dists: bool = False,
    return_state: bool = False,
):
    """Two-stage ANN graph construction (ClusterViG family).

    1. cluster co-nodes (k-means, static iters; ``init_centroids``
       warm-starts from a previous layer/request via a functional
       ``DigcState`` entry);
    2. bucket members into fixed-capacity cluster lists (overflow
       drops, like the MoE dispatch);
    3. per query: top-n_probe centroids, then top-k·d over the probed
       clusters' members in dispatch form (one dense contraction per
       cluster; see ``_cluster_search``).

    Accepts (N, D) or (B, N, D); the whole batch shares static cluster
    shapes. Index construction is hoisted out of the per-image search:
    a shared co-node set — explicit (M, D) co-nodes next to batched
    (B, N, D) queries — is indexed **once** and broadcast, instead of
    being re-clustered per image. ``n_clusters`` / ``n_probe`` default
    to a workload-adaptive heuristic (``default_cluster_params``).

    ``init_valid`` selects the **functional warm start**: a traced ()
    bool (a ``DigcStateEntry`` step counter test). Both branches are
    staged — ``lax.cond`` runs the warm index build (``warm_iters``
    Lloyd iterations from ``init_centroids``) when true and the cold
    build (``kmeans_iters`` from random init) when false — so the same
    compiled program serves the first and every later request. A (B,)
    bool vector makes validity **per batch row** (multi-tenant
    serving, DESIGN.md §9): each row gets the build its own validity
    selects — all-warm batches pay one build, mixed batches stage both
    and select per row. With ``init_valid=None`` warm/cold is a
    trace-time choice: ``init_centroids`` present means warm.

    ``return_state=True`` additionally returns {"centroids": (B, C, D)}
    for warm-starting the next call.
    """
    # Shared external co-nodes: index once, before batch promotion.
    shared_y = y is not None and y.ndim == 2 and x.ndim == 3
    if shared_y:
        b = x.shape[0]
        y = jnp.broadcast_to(y[None], (b,) + y.shape)
    x3, y3, _, squeeze = promote_batch(x, y)
    b = x3.shape[0]
    m = y3.shape[1]
    kd = k * dilation
    n_clusters, n_probe = default_cluster_params(m, n_clusters, n_probe)
    cap = max(int(m / n_clusters * capacity_factor), kd)

    init3 = init_centroids
    if init3 is not None and init3.ndim == 2:
        init3 = jnp.broadcast_to(init3[None], (b,) + init3.shape)
    if init3 is not None and init3.shape[1] != n_clusters:
        init3 = None  # stale centroid shape (workload changed): cold start

    def build_index(iters: int, init_b3, shared: bool = shared_y):
        def index_one(yb, init_b=None):
            return _cluster_index(
                yb, n_clusters=n_clusters, cap=cap, seed=seed,
                iters=iters, init_centroids=init_b,
            )

        if shared:
            cents1, members1 = index_one(
                y3[0], None if init_b3 is None else init_b3[0]
            )
            return (
                jnp.broadcast_to(cents1[None], (b,) + cents1.shape),
                jnp.broadcast_to(members1[None], (b,) + members1.shape),
            )
        if init_b3 is None:
            return jax.vmap(lambda yb: index_one(yb))(y3)
        return jax.vmap(index_one)(y3, init_b3)

    if init3 is None:
        cents, members = build_index(kmeans_iters, None)
    elif init_valid is None:
        cents, members = build_index(kmeans_iters, init3)
    elif jnp.ndim(init_valid) == 0:
        cents, members = lax.cond(
            init_valid,
            lambda: build_index(warm_iters, init3),
            lambda: build_index(kmeans_iters, None),
        )
    else:
        # (B,) per-row validity (multi-tenant serving): a batch mixing
        # warm tenants with cold ones must give each *row* exactly the
        # build a B=1 call with that row's validity would — warm rows a
        # warm_iters Lloyd refinement of their carried centroids, cold
        # rows the full cold build. Steady state (every row warm) pays
        # one build; a mixed batch stages both and selects per row.
        # Shared-co-node indexing is per-row here by construction: rows
        # carry independent init centroids.
        valid = init_valid

        def mixed_index():
            cw, mw = build_index(warm_iters, init3, shared=False)
            cc, mc = build_index(kmeans_iters, None, shared=False)
            sel = valid[:, None, None]
            return jnp.where(sel, cw, cc), jnp.where(sel, mw, mc)

        cents, members = lax.cond(
            jnp.all(valid),
            lambda: build_index(warm_iters, init3, shared=False),
            mixed_index,
        )

    idx, dist = jax.vmap(
        lambda xb, yb, cb, mb: _cluster_search(
            xb, yb, cb, mb, kd=kd, n_probe=n_probe,
        )
    )(x3, y3, cents, members)
    idx = dilate(idx, dilation)
    dist = dilate(dist, dilation)
    if squeeze:
        idx, dist = idx[0], dist[0]
    out = (idx, dist) if return_dists else idx
    if return_state:
        state = {"centroids": cents}
        return (*out, state) if return_dists else (out, state)
    return out


def axial_digc(
    x: jax.Array,
    *,
    grid_h: int,
    grid_w: int,
    k: int,
    dilation: int = 1,
    return_dists: bool = False,
):
    """Axial construction (GreedyViG family): each patch considers only
    its grid row and column — O(N·(H+W)·D), no full distance matrix.

    x (N, D) or (B, N, D) with N == grid_h * grid_w, row-major patch
    order. The candidate structure is shared across the batch, so the
    whole batch runs as one gather + one top-k.
    """
    x3, _, _, squeeze = promote_batch(x)
    b, n, d = x3.shape
    assert n == grid_h * grid_w, (n, grid_h, grid_w)
    kd = k * dilation

    rows = jnp.arange(grid_h)
    cols = jnp.arange(grid_w)
    # row candidates for patch (r, c): ids r*W + c' for all c'
    row_ids = rows[:, None, None] * grid_w + cols[None, None, :]  # (H,1,W)
    row_ids = jnp.broadcast_to(row_ids, (grid_h, grid_w, grid_w))
    # column candidates for patch (r, c): ids r'*W + c for all r'
    col_ids = rows[None, None, :] * grid_w + cols[None, :, None]  # (1,W,H)
    col_ids = jnp.broadcast_to(col_ids, (grid_h, grid_w, grid_h))
    cand = jnp.concatenate([row_ids, col_ids], axis=-1).reshape(n, grid_w + grid_h)

    feats = x3[:, cand]  # (B, N, H+W, D)
    dists = jnp.sum((feats - x3[:, :, None, :]) ** 2, axis=-1)  # (B, N, H+W)
    # the row and column lists intersect exactly at the query itself:
    # mask the column-side duplicate so it can't displace a neighbor
    qid = jnp.arange(n, dtype=cand.dtype)
    dup = cand[:, grid_w:] == qid[:, None]  # (N, H)
    dists = dists.at[:, :, grid_w:].set(
        jnp.where(dup[None], BIG, dists[:, :, grid_w:])
    )
    kd_eff = min(kd, cand.shape[1])
    neg, sel = lax.top_k(-dists, kd_eff)
    cand_b = jnp.broadcast_to(cand[None], (b,) + cand.shape)
    idx = jnp.take_along_axis(cand_b, sel, axis=-1)
    dist = -neg
    if kd_eff < kd:
        idx = jnp.pad(idx, ((0, 0), (0, 0), (0, kd - kd_eff)))
        dist = jnp.pad(dist, ((0, 0), (0, 0), (0, kd - kd_eff)),
                       constant_values=BIG)
    idx = dilate(idx, dilation)
    dist = dilate(dist, dilation)
    if squeeze:
        idx, dist = idx[0], dist[0]
    if return_dists:
        return idx, dist
    return idx


def recall_vs_exact(x, y, idx_approx, k: int) -> float:
    """Neighbor-set recall of an approximate construction vs Algorithm 1."""
    import numpy as np

    from repro.core.digc import digc_reference

    exact = np.asarray(digc_reference(x, y, k=k))
    approx = np.asarray(idx_approx)[..., :k]
    exact = exact.reshape(-1, k)
    approx = approx.reshape(-1, k)
    hits = 0
    for i in range(exact.shape[0]):
        hits += len(set(exact[i]) & set(approx[i]))
    return hits / exact.size


# --------------------------------------------------------------------------
# Registry entries (DESIGN.md §4).


def _build_cluster(x, y, pos_bias, spec: DigcSpec, state_entry=None):
    del pos_bias  # validated unsupported upstream
    if state_entry is not None:
        return _build_cluster_stateful(x, y, spec, state_entry)
    return cluster_digc(
        x, y, k=spec.k, dilation=spec.dilation,
        n_clusters=spec.n_clusters, n_probe=spec.n_probe,
        capacity_factor=(
            spec.capacity_factor if spec.capacity_factor is not None else 2.0
        ),
        seed=spec.seed if spec.seed is not None else 0,
        return_dists=True,
    )


def _build_cluster_stateful(x, y, spec: DigcSpec, entry):
    """Functional form: (x, y, spec, DigcStateEntry) ->
    (idx, dist, new entry). Jit-native — warm/cold is a runtime
    ``lax.cond`` on the entry's step counter (per batch row when the
    entry carries ``row_step``: multi-tenant batches mix warm and cold
    tenants), and the new centroids are returned in the entry
    (donation-stable shapes/dtypes)."""
    m = y.shape[1] if y is not None else x.shape[1]
    n_clusters, _ = default_cluster_params(m, spec.n_clusters, spec.n_probe)
    expected = (x.shape[0], n_clusters, x.shape[-1])
    init = entry.centroids
    common = dict(
        k=spec.k, dilation=spec.dilation,
        n_clusters=spec.n_clusters, n_probe=spec.n_probe,
        capacity_factor=(
            spec.capacity_factor if spec.capacity_factor is not None else 2.0
        ),
        seed=spec.seed if spec.seed is not None else 0,
        return_dists=True, return_state=True,
    )
    if init is None or init.shape != expected:
        # No centroid buffer for this workload (shape is static): cold
        # build, advance the counter only — never write mismatched
        # shapes into the state (the pytree structure is the compiled
        # program's contract).
        idx, dist, st = cluster_digc(x, y, **common)
        return idx, dist, entry.bump()
    valid = entry.row_warm if entry.row_step is not None else entry.warm
    idx, dist, st = cluster_digc(
        x, y, init_centroids=init, init_valid=valid, **common
    )
    return idx, dist, entry.bump(
        centroids=st["centroids"].astype(init.dtype)
    )


def _build_axial(x, y, pos_bias, spec: DigcSpec):
    del pos_bias
    n = x.shape[1]
    if y is not None:
        # Axial candidates are x's own grid row/column — it is a
        # self-graph construction (the y=None spelling) and cannot
        # target explicit co-nodes: pooled model stages and any
        # caller-supplied y fall back to the exact streaming tier, as
        # the model used to special-case by hand.
        return digc_blocked(
            x, y, k=spec.k, dilation=spec.dilation, return_dists=True
        )
    gh, gw = spec.grid_h, spec.grid_w
    if gh is None and gw is None:
        side = int(round(n ** 0.5))
        if side * side != n:
            raise ValueError(
                f"axial DIGC needs grid_h/grid_w for non-square N={n}"
            )
        gh = gw = side
    elif gh is None:
        gh = n // gw
    elif gw is None:
        gw = n // gh
    if gh * gw != n:
        raise ValueError(
            f"axial grid {gh}x{gw} does not match N={n} nodes"
        )
    return axial_digc(
        x, grid_h=gh, grid_w=gw, k=spec.k, dilation=spec.dilation,
        return_dists=True,
    )


register(GraphBuilder(
    name="cluster",
    build=_build_cluster,
    knobs=frozenset({"n_clusters", "n_probe", "capacity_factor", "seed"})
    | REUSE_KNOBS,
    exact=False,
    supports_state=True,  # jit-native centroid warm starts via DigcState
    doc="ClusterViG-family IVF search: k-means index (shared co-nodes "
        "indexed once, DigcState warm starts) + dispatch-form "
        "probe",
))

register(GraphBuilder(
    name="axial",
    build=_build_axial,
    knobs=frozenset({"grid_h", "grid_w"}),
    exact=False,
    doc="GreedyViG-family axial (row+column) construction; falls back "
        "to blocked when co-nodes are pooled (M != N)",
))
