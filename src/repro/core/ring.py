"""Ring-DIGC: the paper's GMM lifted to the pod level (beyond-paper).

Co-node features are sharded across devices along a mesh axis. Each hop,
every device (a) kicks off the ``collective_permute`` that rotates the
co-node shard to its ring neighbor and (b) merges the shard it currently
holds into its running top-(k*d) list. XLA's latency-hiding scheduler
overlaps (a) with (b) — the ICI link plays the role of the FPGA heap's
input streams, the running list plays the heap.

After ``num_devices`` hops every device has seen every co-node shard and
holds the exact global top-(k*d) for its local nodes: no device ever
materializes the full co-node set, so graphs whose co-node features
exceed per-device HBM still construct exactly.

The tier is **batched-first and mesh-native** (DESIGN.md §10): the whole
(B, N, D) batch rides one ``shard_map`` program — the node and co-node
axes shard along ``axis_name`` and an optional ``batch_axis`` shards the
batch rows data-parallel (serving slot rows × ring-sharded co-nodes).

It is also a **stateful builder** (``GraphBuilder.supports_state``): a
``DigcStateEntry`` carrying the co-node squared norms (``sq_y``) rides
the same contract as the blocked tier's frozen-gallery hook, but the
norms live *sharded* — each device selects, inside the shard_map body,
between its carried norm shard (warm) and a fresh shard-local norm pass
(cold), gated per batch row by the entry's ``row_step`` counters. A warm
hop therefore never touches the co-node features for norms at all: only
the (m_loc,) norm shard rotates the ring alongside its feature shard.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.builder import (
    REUSE_KNOBS, DigcSpec, GraphBuilder, promote_batch, register,
)
from repro.core.digc import BIG, dilate, merge_topk


def _ring_hops(x_loc, y_loc, sq_loc, *, kd, axis_name, n_dev):
    """The hop loop run on each device inside shard_map.

    x_loc (b, n_loc, D) local node shard; y_loc (b, m_loc, D) local
    co-node shard; sq_loc (b, m_loc) the shard's co-node squared norms
    (already selected warm/cold and BIG-masked on padding — the hop
    loop never recomputes them: norms rotate the ring with their
    feature shard). Returns (dist, idx) of the *global* top-kd, idx in
    global co-node coordinates.
    """
    my = lax.axis_index(axis_name)
    m_loc = y_loc.shape[-2]
    n_loc = x_loc.shape[-2]
    b = x_loc.shape[0]

    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    # Hoisted out of the hop loop: the query norms never rotate.
    sq_x = jnp.sum(x_loc * x_loc, -1, keepdims=True)  # (b, n_loc, 1)

    def hop(h, state):
        y_cur, sq_cur, run_d, run_i = state
        # Kick off the rotation first so the permute DMA overlaps the
        # local distance+merge compute below (double buffering). The
        # norm shard rides the same rotation as its feature shard.
        y_next = lax.ppermute(y_cur, axis_name, perm)
        sq_next = lax.ppermute(sq_cur, axis_name, perm)
        # Shard currently held originated at device (my - h) mod n_dev.
        owner = (my.astype(jnp.int32) - h) % n_dev
        off = owner.astype(jnp.int32) * m_loc
        inner = jnp.einsum("bnd,bmd->bnm", x_loc, y_cur)
        d_blk = sq_x - 2.0 * inner + sq_cur[:, None, :]
        blk_i = off + lax.broadcasted_iota(jnp.int32, (b, n_loc, m_loc), 2)
        new_d, new_i = merge_topk(run_d, run_i, d_blk, blk_i, kd)
        return (y_next, sq_next, new_d, new_i)

    init = (
        y_loc,
        sq_loc,
        jnp.full((b, n_loc, kd), BIG, jnp.float32),
        jnp.zeros((b, n_loc, kd), jnp.int32),
    )
    _, _, run_d, run_i = lax.fori_loop(0, n_dev, hop, init)
    return run_d, run_i


def _local_norms(y_loc, sq_loc, valid_loc, *, m, axis_name, live_loc=None):
    """Select this device's co-node norm shard: carried (warm rows) or
    a fresh shard-local pass (cold rows), then BIG-mask padded co-nodes
    so they can never be selected. Runs inside shard_map — the global
    (B, M) norm array is only ever touched one shard at a time, which
    is what lets a ``DigcStateEntry.sq_y`` placed with a
    ``PartitionSpec`` stay resident on its device across requests.
    ``live_loc`` (b, m_loc) extends the same BIG-norm treatment to
    caller-declared pad co-nodes (``m_valid``) — serving's N-bucket pad
    nodes ride the exact masking the ring's own device padding uses."""
    m_loc = y_loc.shape[-2]
    my = lax.axis_index(axis_name)
    gid = my.astype(jnp.int32) * m_loc + jnp.arange(m_loc, dtype=jnp.int32)
    pad = gid >= m  # (m_loc,)
    fresh = jnp.sum(y_loc * y_loc, -1)  # (b, m_loc)
    if sq_loc is None:
        sq = fresh
    else:
        sq = jnp.where(valid_loc[:, None], sq_loc, fresh)
    if live_loc is not None:
        sq = jnp.where(live_loc, sq, jnp.float32(BIG))
    return jnp.where(pad[None, :], jnp.float32(BIG), sq)


def ring_digc(
    x: jax.Array,
    y: Optional[jax.Array] = None,
    *,
    k: int,
    dilation: int = 1,
    mesh: Optional[Mesh] = None,
    axis_name: str = "data",
    batch_axis: Optional[str] = None,
    sq_y: Optional[jax.Array] = None,
    sq_valid: Optional[jax.Array] = None,
    return_dists: bool = False,
    return_norms: bool = False,
    m_valid: Optional[jax.Array] = None,
):
    """Distributed DIGC over a device ring.

    Nodes AND co-nodes are sharded along ``axis_name``; the result
    (B, N, k) arrives sharded over nodes. Exact — bit-identical
    neighbor sets to the single-device reference. Accepts (N, D) or
    (B, N, D): the whole batch rides **one** shard_map program (the
    old per-image unroll is gone), and ``batch_axis`` optionally
    shards the batch rows along a second mesh axis (data-parallel
    rows × ring-sharded co-nodes; B must divide by that axis).

    ``sq_y`` (B, M) carries precomputed co-node squared norms — the
    frozen-gallery hook, same contract as ``digc_blocked(sq_y=)`` but
    sharded: each device reads only its norm shard. ``sq_valid`` is a
    traced () or (B,) bool selecting carried vs freshly-computed norms
    (per batch row with a vector — multi-tenant serving mixes warm and
    cold rows). ``return_norms`` appends the selected (B, M) norms so
    a stateful caller can carry them into its ``DigcStateEntry``.
    ``m_valid`` ((M,) or (B, M) bool) marks live co-nodes: pad lanes
    take the same BIG-norm masking as the ring's internal device
    padding, so serving's N-bucket pad nodes can never enter a top-k
    (carried norms at masked lanes come back BIG — self-consistent for
    a frozen gallery, whose pad set never changes).
    """
    if mesh is None:
        raise ValueError("ring_digc requires an explicit mesh")
    if y is not None and y.ndim == 2 and x.ndim == 3:
        # Shared co-node gallery next to batched nodes (the frozen-
        # gallery spelling): broadcast across the batch, as before the
        # batched-shard_map rewrite.
        y = jnp.broadcast_to(y[None], (x.shape[0],) + y.shape)
    x3, y3, _, squeeze = promote_batch(x, y)
    n_dev = mesh.shape[axis_name]
    b, n, feat = x3.shape
    m = y3.shape[1]
    kd = k * dilation
    if kd > m:
        raise ValueError(f"k*dilation={kd} exceeds number of co-nodes M={m}")
    if batch_axis is not None and b % mesh.shape[batch_axis] != 0:
        raise ValueError(
            f"batch {b} does not divide the {batch_axis!r} mesh axis "
            f"({mesh.shape[batch_axis]} devices)"
        )

    n_pad = _ceil_to(n, n_dev)
    m_pad = _ceil_to(m, n_dev)
    x_p = jnp.pad(x3.astype(jnp.float32), ((0, 0), (0, n_pad - n), (0, 0)))
    # Padded co-nodes are zero rows masked through their *norm* (BIG):
    # distance = |x|^2 - 0 + BIG >= BIG/2, so a pad lane can never
    # displace a real neighbor and the feature rows stay cheap zeros.
    y_p = jnp.pad(y3.astype(jnp.float32), ((0, 0), (0, m_pad - m), (0, 0)))

    stateful = sq_y is not None
    if stateful:
        sq_p = jnp.pad(
            sq_y.astype(jnp.float32), ((0, 0), (0, m_pad - m))
        )
        valid = sq_valid if sq_valid is not None else jnp.bool_(True)
        valid = jnp.broadcast_to(jnp.asarray(valid, bool), (b,))

    live_p = None
    if m_valid is not None:
        live = jnp.asarray(m_valid, bool)
        live = live[None, :] if live.ndim == 1 else live
        live = jnp.broadcast_to(live, (b, m))
        # Pad lanes beyond M are already gid-masked inside the body;
        # padding the caller mask with False keeps the two consistent.
        live_p = jnp.pad(live, ((0, 0), (0, m_pad - m)))

    bspec = batch_axis  # None = batch rows replicated along the ring

    def body_stateless(x_loc, y_loc, live_loc=None):
        sq = _local_norms(
            y_loc, None, None, m=m, axis_name=axis_name, live_loc=live_loc
        )
        return _ring_hops(
            x_loc, y_loc, sq, kd=kd, axis_name=axis_name, n_dev=n_dev
        )

    def body_stateful(x_loc, y_loc, sq_loc, valid_loc, live_loc=None):
        sq = _local_norms(
            y_loc, sq_loc, valid_loc, m=m, axis_name=axis_name,
            live_loc=live_loc,
        )
        run_d, run_i = _ring_hops(
            x_loc, y_loc, sq, kd=kd, axis_name=axis_name, n_dev=n_dev
        )
        return run_d, run_i, sq

    mask_specs = () if live_p is None else (P(bspec, axis_name),)
    mask_args = () if live_p is None else (live_p,)
    if stateful:
        mapped = jax.shard_map(
            body_stateful,
            mesh=mesh,
            in_specs=(
                P(bspec, axis_name, None),
                P(bspec, axis_name, None),
                P(bspec, axis_name),
                P(bspec),
            ) + mask_specs,
            out_specs=(
                P(bspec, axis_name, None),
                P(bspec, axis_name, None),
                P(bspec, axis_name),
            ),
            check_vma=False,
        )
        run_d, run_i, sq_out = mapped(x_p, y_p, sq_p, valid, *mask_args)
    else:
        mapped = jax.shard_map(
            body_stateless,
            mesh=mesh,
            in_specs=(
                P(bspec, axis_name, None),
                P(bspec, axis_name, None),
            ) + mask_specs,
            out_specs=(P(bspec, axis_name, None), P(bspec, axis_name, None)),
            check_vma=False,
        )
        run_d, run_i = mapped(x_p, y_p, *mask_args)
        sq_out = None

    run_d = run_d[:, :n]
    run_i = run_i[:, :n]
    idx = dilate(run_i, dilation)
    dist = dilate(run_d, dilation)
    if squeeze:
        idx, dist = idx[0], dist[0]
    out = (idx, dist) if return_dists else (idx,)
    if return_norms:
        # The selected norms, pad lanes sliced off: exactly what the
        # next warm call's entry should carry. BIG pad masking lives
        # only beyond [:m], so the carried values are the true norms.
        norms = None if sq_out is None else sq_out[:, :m]
        out = out + (norms,)
    return out if len(out) > 1 else out[0]


def _ceil_to(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


# --------------------------------------------------------------------------
# Registry entry (DESIGN.md §4, §10).


def _build_ring(x, y, pos_bias, spec: DigcSpec, state_entry=None,
                m_valid=None):
    del pos_bias  # validated unsupported upstream
    common = dict(
        k=spec.k, dilation=spec.dilation, mesh=spec.mesh,
        axis_name=spec.axis_name if spec.axis_name is not None else "data",
        batch_axis=spec.batch_axis,
        return_dists=True,
        m_valid=m_valid,
    )
    if state_entry is None:
        return ring_digc(x, y, **common)
    # Functional form: same frozen-gallery contract as the blocked tier
    # (state.py invalidation rules) — the entry's sq_y asserts the
    # co-node set identified by its key is frozen, so it only engages
    # for explicit co-nodes of the matching shape. Self-graph calls
    # (y=None: co-nodes are this call's features, drifting every call)
    # advance the counters but never carry norms. Warm/cold is a
    # runtime value, per batch row when the entry carries row_step.
    if (
        y is not None
        and state_entry.sq_y is not None
        and state_entry.sq_y.shape == y.shape[:-1]
    ):
        valid = (
            state_entry.row_warm
            if state_entry.row_step is not None
            else state_entry.warm
        )
        idx, dist, norms = ring_digc(
            x, y, sq_y=state_entry.sq_y, sq_valid=valid,
            return_norms=True, **common,
        )
        return idx, dist, state_entry.bump(sq_y=norms)
    idx, dist = ring_digc(x, y, **common)
    return idx, dist, state_entry.bump()


register(GraphBuilder(
    name="ring",
    build=_build_ring,
    knobs=frozenset({"mesh", "axis_name", "batch_axis"}) | REUSE_KNOBS,
    exact=True,
    distributed=True,
    supports_state=True,  # sharded co-node norms via DigcState entries
    supports_pad=True,  # m_valid rides the same BIG-norm mask as device pads
    doc="pod-level GMM: co-node shards rotate a device ring "
        "(requires mesh= knob; batch_axis= shards rows data-parallel; "
        "stateful — carries sharded frozen-gallery norms)",
))
