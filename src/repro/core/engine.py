"""Unified streaming DIGC engine: two-level tiling + pluggable merges.

Every exact XLA tier routes through ``stream_topk``, the engine's one
entry point. It reproduces the paper's module split at the XLA level —
DCM (a distance tile per grid step), LSM (each tile's own top-kd), GMM
(a global merge of per-tile survivors) — with two structural upgrades
over the PR-1 ``digc_blocked``:

* **Two-level tiling.** The query dimension N tiles as well as the
  co-node dimension M (``block_n`` x ``block_m`` grid, outer scan over
  query blocks, inner scan over co-node blocks), so live memory is
  O(B * block_n * block_m) instead of O(B * N * block_m). High
  resolution ViG stages (N = 12544+) stream through a cache-sized
  working set instead of materializing 100+ MB of distance rows.
* **Merge strategies.** The LSM/GMM realization is a knob
  (``DigcSpec.merge``). ``"topk"`` and ``"select"`` share one skeleton —
  each co-node tile keeps its own top-kd (LSM), then one ``lax.top_k``
  over the ``nb_m * kd`` survivors (GMM) — and differ only in the LSM:

    - ``"topk"`` (default, ``merge=None``) — one exact ``lax.top_k``
      over the tile's W columns: a data-independent op over full rows
      whose cost barely moves with kd. Ties go to the lowest column.
      On a TPU v5e it beat ``"select"`` at every shape the ViG cells
      serve (PERF.md §6).
    - ``"select"`` — grouped two-level extraction (``select_topkd``):
      each tile is reshaped to (groups, width<=32) lanes, a per-group
      running min is kept, and each of kd dependent rounds touches only
      the winning group. Exact, bit-identical to ``"topk"``; its cost is
      linear in kd. Opt-in, and a tuner candidate.
    - ``"packed"`` — single-int32 packed-key min/mask merge
      (``core/packedkey.py``), the XLA mirror of the Pallas kernel's
      packed path. Tie-tolerant (truncated distances), halves merge
      operand traffic.

* **Norm reuse.** ``||y||^2`` is computed once per call, shared with
  the self-graph ``||x||^2`` when y is None, accepted precomputed via
  ``sq_y=`` (a frozen gallery's norms, carried by a ``DigcState``
  entry), and optionally folded
  into the distance matmul itself (``fuse_norms``: operands augmented
  to [-2x, 1, ||x||^2] / [y, ||y||^2, 1] so the whole distance tile is
  one contraction — no separate broadcast-add passes over the tile).
  ``fuse_norms`` changes fp32 summation order, so it is tie-tolerant
  rather than bit-exact; it is off unless the tuner measures it a win.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.packedkey import (
    INT_BIG,
    gmm_merge,
    idx_bits_for,
    key_less,
    lsm_topk,
    next_pow2,
    pack_keys,
    unpack_keys,
)

BIG = float(1e30)

MERGE_STRATEGIES = ("select", "topk", "packed")

# Default group width for the two-level selection: 32 keeps the
# per-group extracted-lane set in one int32 bitmask word. Widths up to
# 64 are supported with a two-word mask (``DigcSpec.group_w``): fewer
# groups to reduce over per round, at the price of a second mask word
# and a wider per-round gather — whether that wins is workload- and
# backend-dependent.
_SELECT_GROUP_W = 32
_SELECT_GROUP_W_MAX = 64

# Co-node tile sizing (``conode_tile``). Each tile's float32 distance
# block is B x rows x block_m x 4 bytes. 64 MiB holds a whole 448 px
# ViG graph (B 8, N = M = 784: 19.7 MB) and the pyramid's 224 px stage 0
# (N 3136, M 196: 19.7 MB) in one tile, so those skip the second
# top-k over per-tile survivors, while a 896 px self-graph (B 8, 3136^2:
# 315 MB) still streams in a few tiles, a small share of a v5e's 16 GB.
# Split tiles are 128-lane aligned and never narrower than 256 columns,
# so no shape streams more tiles than a fixed 256-wide grid would.
CONODE_TILE_BUDGET = 64 << 20
CONODE_TILE_FLOOR = 256
CONODE_TILE_ALIGN = 128


def _ceil_to(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def conode_tile(b: int, n: int, m: int, block_m: Optional[int] = None,
                block_n: Optional[int] = None) -> int:
    """Co-node tile width of the streaming engine for B x N queries
    against M co-nodes, the queries tiled by ``block_n`` when it is
    below N. An explicit ``block_m`` is kept, clamped to [1, M].
    Otherwise the whole co-node set is one tile when the float32
    distance block of one query tile fits ``CONODE_TILE_BUDGET``; above
    it, the fewest equal tiles that fit, 128-aligned, between
    ``CONODE_TILE_FLOOR`` and M columns wide."""
    if block_m is not None:
        return max(1, min(block_m, m))
    rows = block_n if block_n is not None and block_n < n else n
    tile_bytes = b * rows * m * 4
    if tile_bytes <= CONODE_TILE_BUDGET:
        return m
    t = -(-tile_bytes // CONODE_TILE_BUDGET)
    width = _ceil_to(-(-m // t), CONODE_TILE_ALIGN)
    return min(m, max(CONODE_TILE_FLOOR, width))


# ---------------------------------------------------------------------------
# LSM: grouped two-level selection


def select_topkd(d_blk: jax.Array, kd: int, group_w: int = _SELECT_GROUP_W):
    """Exact top-kd of each row of ``d_blk`` (..., N, W), ascending.

    Two-level extraction: columns fold into G = ceil(W / w) groups of
    w <= 64 lanes; a per-group running min (and a bitmask of
    already-extracted lanes, one int32 word per 32 lanes) is
    maintained, so each of the kd rounds reduces over G group-mins plus
    the single winning group — O(G + w) lane ops — instead of sweeping
    all W candidates. Total cost is one full pass (the group-min build)
    plus kd tiny rounds, vs the kd-passes-over-W of ``lax.top_k``-style
    selection.

    Ties resolve to the lowest column (group-major order), matching
    ``lax.top_k``. Returns (dist (..., N, kd), col (..., N, kd)) where
    ``col`` indexes into W; rows with fewer than kd finite candidates
    pad with BIG-distance lanes (indices unspecified, mask on dist).
    """
    *lead, n, W = d_blk.shape
    w = max(1, min(group_w, _SELECT_GROUP_W_MAX, W))
    G = -(-W // w)
    pad = G * w - W
    if pad:
        d_blk = jnp.pad(
            d_blk,
            [(0, 0)] * len(lead) + [(0, 0), (0, pad)],
            constant_values=BIG,
        )
    resh = d_blk.reshape(*lead, n, G, w)
    gmin = jnp.min(resh, axis=-1)  # (..., N, G)
    nw = -(-w // 32)  # mask words per group (1 for w<=32, 2 for w<=64)
    bits = jnp.zeros((*gmin.shape, nw), jnp.int32)
    gcol = lax.broadcasted_iota(jnp.int32, gmin.shape, gmin.ndim - 1)
    wcol = jnp.arange(w, dtype=jnp.int32)
    wword = wcol // 32  # static lane -> mask-word map
    wbit = wcol % 32
    word_iota = jnp.arange(nw, dtype=jnp.int32)
    out_shape = (*lead, n, kd)
    out_col = lax.broadcasted_iota(jnp.int32, out_shape, len(out_shape) - 1)

    def body(t, state):
        gmin, bits, od, oi = state
        gstar = jnp.argmin(gmin, axis=-1)  # (..., N)
        grp = jnp.take_along_axis(resh, gstar[..., None, None], axis=-2)
        grp = jnp.squeeze(grp, -2)  # (..., N, w)
        mask = jnp.take_along_axis(bits, gstar[..., None, None], axis=-2)
        mask = jnp.squeeze(mask, -2)  # (..., N, nw)
        live = jnp.bitwise_and(
            jnp.right_shift(mask[..., wword], wbit), 1
        ) == 0  # (..., N, w)
        grp_m = jnp.where(live, grp, BIG)
        pos = jnp.argmin(grp_m, axis=-1)  # (..., N)
        val = jnp.min(grp_m, axis=-1)
        col = gstar.astype(jnp.int32) * w + pos.astype(jnp.int32)
        od = jnp.where(out_col == t, val[..., None], od)
        oi = jnp.where(out_col == t, col[..., None], oi)
        setbit = jnp.where(
            word_iota == (pos[..., None] // 32),
            jnp.left_shift(jnp.int32(1), pos[..., None] % 32),
            0,
        )  # (..., N, nw)
        newbits = mask | setbit
        hitg = gcol == gstar[..., None]
        bits = jnp.where(hitg[..., None], newbits[..., None, :], bits)
        newmin = jnp.min(jnp.where(wcol == pos[..., None], BIG, grp_m), -1)
        gmin = jnp.where(hitg, newmin[..., None], gmin)
        return gmin, bits, od, oi

    init = (
        gmin,
        bits,
        jnp.full(out_shape, BIG, jnp.float32),
        jnp.zeros(out_shape, jnp.int32),
    )
    _, _, od, oi = lax.fori_loop(0, kd, body, init)
    return od, oi


# ---------------------------------------------------------------------------
# GMM merge bodies


def topk_lsm(d: jax.Array, kd: int):
    """Exact top-kd of each row of ``d`` (..., N, W), ascending: one
    ``lax.top_k`` over the row, ties to the lowest column. Returns
    (dist (..., N, kd), col (..., N, kd)), ``col`` indexing into W."""
    neg, col = lax.top_k(-d, kd)
    return -neg, col


def merge_packed_xla(run_k, blk_k, kd: int):
    """Packed-key sorted two-level merge — the XLA mirror of the Pallas
    kernel's bitonic LSM+GMM, built from the same ``core/packedkey``
    networks: reduce the tile to its top-kd_pad sorted descending
    (``lsm_topk``), then one O(log kd_pad) ``gmm_merge`` into the
    running buffer. ``run_k`` must be sorted ascending (the scan
    invariant: the INT_BIG init is sorted, and this returns sorted).
    Keys are unique (index bits), so the result is exactly the kd
    lexicographically-smallest (dist, idx) pairs of the union."""
    kd_pad = next_pow2(kd)
    if run_k.shape[-1] < kd_pad:
        run_k = jnp.concatenate(
            [run_k, jnp.full(run_k.shape[:-1] + (kd_pad - run_k.shape[-1],),
                             INT_BIG, jnp.int32)],
            axis=-1,
        )
    top = lsm_topk((blk_k,), kd_pad, key_less, (INT_BIG,), descending=True)
    (merged,) = gmm_merge((run_k[..., :kd_pad],), (top[0][..., :kd_pad],),
                          kd_pad, key_less)
    return merged[..., :kd]


# ---------------------------------------------------------------------------
# The engine


def stream_topk(
    x3: jax.Array,
    y3: Optional[jax.Array] = None,
    pos_bias: Optional[jax.Array] = None,
    *,
    kd: int,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    merge: Optional[str] = None,
    fuse_norms: bool = False,
    mxu_bf16: bool = False,
    causal: bool = False,
    sq_y: Optional[jax.Array] = None,
    group_w: Optional[int] = None,
    m_valid: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Streaming top-kd over a (block_n x block_m) tile grid.

    x3 (B, N, D); y3 (B, M, D) or None for a self-graph (co-nodes = x,
    norms shared); pos_bias (B, N, M) or None. Returns (dist, idx),
    each (B, N, kd), distances ascending, BIG-sentinel invalid lanes.

    ``block_m=None`` streams the whole co-node set in one tile;
    ``block_n=None`` disables query tiling (PR-1 behavior). ``sq_y``
    accepts precomputed co-node squared norms (B, M) — the hook a
    ``DigcState`` entry uses to serve a fixed co-node gallery.

    ``m_valid`` is an (M,) or (B, M) bool mask of *live* co-nodes: pad
    co-nodes take the same BIG-norm masking the internal tile padding
    already uses (the ring tier's pad idiom lifted engine-wide), so a
    pad node's distance is >= BIG/2 from every query and can never
    displace a live neighbor — serving pads ragged patch counts to a
    static N-bucket with exact results on the live rows (DESIGN.md §13).
    """
    if merge is None:
        merge = "topk"
    if merge not in MERGE_STRATEGIES:
        raise ValueError(
            f"unknown merge strategy {merge!r}; one of {MERGE_STRATEGIES}"
        )
    if group_w is None:
        group_w = _SELECT_GROUP_W
    if not 1 <= group_w <= _SELECT_GROUP_W_MAX:
        raise ValueError(
            f"group_w={group_w} out of range [1, {_SELECT_GROUP_W_MAX}]"
        )
    self_graph = y3 is None
    y3 = x3 if self_graph else y3
    b, n, feat = x3.shape
    m = y3.shape[1]
    if kd > m:
        raise ValueError(f"k*dilation={kd} exceeds number of co-nodes M={m}")

    x3 = x3.astype(jnp.float32)
    y3 = x3 if self_graph else y3.astype(jnp.float32)
    sq_x = jnp.sum(x3 * x3, axis=-1)  # (B, N)
    if sq_y is None:
        sq_y = sq_x if self_graph else jnp.sum(y3 * y3, axis=-1)
    else:
        sq_y = sq_y.astype(jnp.float32)
    if m_valid is not None:
        # Live-node mask rides the norm term: every merge strategy and
        # the fuse_norms operand packing consume sq_y, so one mask site
        # covers them all. The query-side sq_x stays unmasked — pad
        # *rows* still compute (garbage) neighbors; only pad *columns*
        # are unselectable.
        mask = jnp.asarray(m_valid, bool)
        mask = mask[None, :] if mask.ndim == 1 else mask
        if mask.shape[-1] != m:
            raise ValueError(
                f"m_valid has {mask.shape[-1]} co-node lanes, expected M={m}"
            )
        sq_y = jnp.where(mask, sq_y, BIG)

    block_m = m if block_m is None else max(1, min(block_m, m))
    m_pad = _ceil_to(m, block_m)
    nb_m = m_pad // block_m
    y_p = jnp.pad(y3, ((0, 0), (0, m_pad - m), (0, 0)))
    # Padded co-nodes are masked through their norm term.
    sq_y_p = jnp.pad(sq_y, ((0, 0), (0, m_pad - m)))
    sq_y_p = jnp.where(jnp.arange(m_pad)[None, :] < m, sq_y_p, BIG)

    if mxu_bf16:
        fuse_norms = False  # norm terms must stay fp32
    if fuse_norms:
        ones_x = jnp.ones((b, n, 1), jnp.float32)
        ones_y = jnp.ones((b, m_pad, 1), jnp.float32)
        x_op = jnp.concatenate([-2.0 * x3, ones_x, sq_x[..., None]], axis=-1)
        y_op = jnp.concatenate([y_p, sq_y_p[..., None], ones_y], axis=-1)
    elif mxu_bf16:
        x_op = x3.astype(jnp.bfloat16)
        y_op = y_p.astype(jnp.bfloat16)
    else:
        x_op = x3
        y_op = y_p

    y_blocks = y_op.reshape(b, nb_m, block_m, y_op.shape[-1]).transpose(1, 0, 2, 3)
    sqy_blocks = sq_y_p.reshape(b, nb_m, block_m).transpose(1, 0, 2)
    offsets = jnp.arange(nb_m, dtype=jnp.int32) * block_m

    idx_bits = idx_bits_for(m_pad) if merge == "packed" else 0

    if pos_bias is not None:
        pos_bias = jnp.pad(
            pos_bias.astype(jnp.float32), ((0, 0), (0, 0), (0, m_pad - m))
        )

    def run_queries(xq_op, sqx_q, p_q, row_off):
        """Top-kd for one query block (B, bn, ...) at global row offset."""
        bn = xq_op.shape[1]

        def tile_dists(y_blk, sqy_blk, off, p_blk):
            d_blk = lax.dot_general(
                xq_op, y_blk, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            if not fuse_norms:
                d_blk = sqx_q[..., None] - 2.0 * d_blk + sqy_blk[:, None, :]
            if p_blk is not None:
                d_blk = d_blk + p_blk
            cols = off + lax.broadcasted_iota(jnp.int32, d_blk.shape, 2)
            if causal:
                rows = row_off + lax.broadcasted_iota(
                    jnp.int32, d_blk.shape, 1
                )
                d_blk = jnp.where(cols <= rows, d_blk, BIG)
            return d_blk, cols

        def p_blk_for(step):
            if p_q is None:
                return None
            return lax.dynamic_slice_in_dim(p_q, step * block_m, block_m, 2)

        if merge == "packed":
            def step(run_k, sm):
                y_blk, sqy_blk, off, step_i = sm
                d_blk, cols = tile_dists(y_blk, sqy_blk, off, p_blk_for(step_i))
                blk_k = pack_keys(d_blk, cols, idx_bits)
                return merge_packed_xla(run_k, blk_k, kd), None

            init = jnp.full((b, bn, kd), INT_BIG, jnp.int32)
            run_k, _ = lax.scan(
                step, init,
                (y_blocks, sqy_blocks, offsets,
                 jnp.arange(nb_m, dtype=jnp.int32)),
            )
            return unpack_keys(run_k, idx_bits)

        # "topk" / "select": each tile keeps its own top-kw (LSM), then
        # one lax.top_k over the nb_m * kw survivors (GMM). A tile
        # narrower than kd keeps all of its columns: nb_m * W >= M >= kd.
        kw = min(kd, block_m)
        if merge == "select":
            lsm = functools.partial(select_topkd, group_w=group_w)
        else:
            lsm = topk_lsm

        def step(carry, sm):
            y_blk, sqy_blk, off, step_i = sm
            d_blk, _ = tile_dists(y_blk, sqy_blk, off, p_blk_for(step_i))
            vals, col = lsm(d_blk, kw)
            return carry, (vals, off + col)

        _, (vals, idxs) = lax.scan(
            step, None,
            (y_blocks, sqy_blocks, offsets, jnp.arange(nb_m, dtype=jnp.int32)),
        )
        if nb_m == 1:
            return vals[0], idxs[0]
        cd = vals.transpose(1, 2, 0, 3).reshape(b, bn, nb_m * kw)
        ci = idxs.transpose(1, 2, 0, 3).reshape(b, bn, nb_m * kw)
        dist, sel = topk_lsm(cd, kd)
        return dist, jnp.take_along_axis(ci, sel, axis=-1)

    if block_n is None or block_n >= n:
        return run_queries(x_op, sq_x, pos_bias, jnp.int32(0))

    block_n = max(1, block_n)
    n_pad = _ceil_to(n, block_n)
    nb_n = n_pad // block_n
    x_op_p = jnp.pad(x_op, ((0, 0), (0, n_pad - n), (0, 0)))
    sq_x_p = jnp.pad(sq_x, ((0, 0), (0, n_pad - n)))
    p_p = None
    if pos_bias is not None:
        p_p = jnp.pad(pos_bias, ((0, 0), (0, n_pad - n), (0, 0)))

    def q_step(carry, qi):
        row_off = qi * block_n
        xq = lax.dynamic_slice_in_dim(x_op_p, row_off, block_n, 1)
        sqx_q = lax.dynamic_slice_in_dim(sq_x_p, row_off, block_n, 1)
        p_q = (
            None if p_p is None
            else lax.dynamic_slice_in_dim(p_p, row_off, block_n, 1)
        )
        return carry, run_queries(xq, sqx_q, p_q, row_off)

    _, (dist_q, idx_q) = lax.scan(
        q_step, None, jnp.arange(nb_n, dtype=jnp.int32)
    )
    dist = dist_q.transpose(1, 0, 2, 3).reshape(b, n_pad, kd)[:, :n]
    idx = idx_q.transpose(1, 0, 2, 3).reshape(b, n_pad, kd)[:, :n]
    return dist, idx
