"""Fused streaming DIGC kernel: pairwise distance + running top-(k*d).

TPU-native port of the paper's DCM + LSM + GMM pipeline (DESIGN.md §2):

  * grid = (B, N/block_n, M/block_m); batch is the leading grid
    dimension (no model-level vmap over interpret-mode calls), node
    blocks are independent ("parallel"), the co-node dimension streams
    ("arbitrary"). The Pallas grid pipeline overlaps the HBM->VMEM DMA
    of tile j+1 with the compute of tile j — the TPU analogue of the
    FPGA's deep pipelining.
  * DCM: one MXU contraction per tile, `x_blk @ y_blk^T`, plus the
    rank-1 norm terms. fp32 accumulation.
  * LSM (default ``kernel_merge="bitonic"``): each (bn, bm) tile is
    reduced to its sorted top-kd_pad by a partial bitonic sort — sort
    width-kd_pad groups in O(log^2 kd_pad) data-independent VPU
    passes, then tournament-merge group pairs (core/packedkey.py, the
    networks shared with the engine's packed merge).
  * GMM: the tile's sorted list folds into a running sorted buffer
    with ONE O(log kd_pad) bitonic merge of two sorted sequences — the
    paper's heap insertion as a sorting network. The buffer lives in a
    VMEM **scratch accumulator** (``scratch_shapes``), not in
    revisited output blocks: outputs are written once per (b, i)
    row-block, on the last streaming step.
  * ``kernel_merge="legacy"`` keeps the previous kd-sequential
    (min, argmin, mask) extraction merge (and its ``bucket_rounds``
    approximate pre-reduction) as a measured alternative — the tuner
    treats old-vs-new as a per-workload choice.
  * NSM (stride-d selection) happens in the wrapper (`ops.digc_topk`);
    the kernel returns the full sorted top-(k*d) list, matching the
    paper's modular split.

The full N x M distance matrix never exists in HBM (or VMEM): per-tile
working set = block_n*D + block_m*D + block_n*block_m + 2*block_n*kd
floats (+ 2*block_n*kd_pad scratch), chosen to fit VMEM with
MXU-aligned tile shapes.

Validated in interpret mode on CPU against ``ref.digc_reference``, and
compiled for TPU v5e by ``tests/test_chip_compile.py``. ``interpret=None``
resolves to compiled on a TPU backend and interpret everywhere else
(``repro.kernels.resolve_interpret``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

# Packed (dist|idx) int32 keys and the bitonic sort/merge networks are
# shared with the XLA engine's packed merge (core/engine.py) — one
# format and one network family across tiers (DESIGN.md §5).
from repro.core.packedkey import (
    IDX_FILL,
    INT_BIG,
    dist_idx_less,
    gmm_merge,
    idx_bits_for,
    key_less,
    lane_width,
    lsm_topk,
    next_pow2,
)
from repro.core.packedkey import pack_keys as _pack_keys
from repro.core.packedkey import unpack_keys as _unpack_keys

BIG = float(1e30)  # plain float: jnp scalars would be captured as consts

KERNEL_MERGES = ("bitonic", "legacy")

# Rows the bitonic merge handles per loop step: one f32/int32 sublane
# tile, so each lane chunk of a slab is a single vector register.
_SLAB = 8


def _bucket_reduce(blk_k, kd: int, rounds: int):
    """Pre-reduce a packed tile (bn, bm) to its per-bucket top-`rounds`
    candidates: bm columns fold into kd buckets, one min-pass per round.
    O(rounds) passes instead of O(kd) — the LSM local-sort stage taken
    to its cheapest useful form. Per-tile approximate, but the global
    top-kd is spread across tiles, so end-to-end recall stays high
    (measured in the tests; rounds trades recall vs speed)."""
    bn, bm = blk_k.shape
    g = kd
    w = bm // g
    resh = blk_k.reshape(bn, g, w)
    outs = []
    for r in range(rounds):
        m = jnp.min(resh, axis=2)  # (bn, g)
        outs.append(m)
        if r + 1 < rounds:
            resh = jnp.where(resh == m[:, :, None], INT_BIG, resh)
    return jnp.concatenate(outs, axis=1)  # (bn, g*rounds)


def _merge_body_packed(kd: int, run_k, blk_k):
    """Legacy packed-key merge: kd passes of (min, compare-mask) over
    one int32 candidate array. ~2 VPU ops/element/pass vs ~4 for the
    two-array form, half the VMEM operand traffic. Keys are unique
    (index bits), so the masked update hits exactly one lane per pass."""
    cand = jnp.concatenate([run_k, blk_k], axis=1)  # (bn, kd+bm) int32
    bn = cand.shape[0]
    out_col = lax.broadcasted_iota(jnp.int32, (bn, kd), 1)

    def body(t, state):
        cand, out = state
        m = jnp.min(cand, axis=1)  # (bn,) packed min == (dist, idx) min
        out = jnp.where(out_col == t, m[:, None], out)
        cand = jnp.where(cand == m[:, None], INT_BIG, cand)
        return cand, out

    _, out = lax.fori_loop(
        0, kd, body, (cand, jnp.full((bn, kd), INT_BIG, jnp.int32))
    )
    return out


def _merge_body(kd: int, run_d, run_i, blk_d, blk_i):
    """Legacy merge: k*d rounds of (min, argmin, mask) over
    [running | tile] candidates.

    Returns the new sorted running (dist, idx) pair. All ops are
    elementwise/reduction VPU ops — no sort networks, no data-dependent
    control flow, but kd *sequential* extraction passes per tile (the
    cost the bitonic path removes).
    """
    cand_d = jnp.concatenate([run_d, blk_d], axis=1)  # (bn, kd+bm)
    cand_i = jnp.concatenate([run_i, blk_i], axis=1)
    bn = cand_d.shape[0]
    width = cand_d.shape[1]
    col = lax.broadcasted_iota(jnp.int32, (bn, width), 1)
    out_col = lax.broadcasted_iota(jnp.int32, (bn, kd), 1)

    def body(t, state):
        cd, od, oi = state
        amin = jnp.argmin(cd, axis=1)  # (bn,)
        vmin = jnp.min(cd, axis=1)
        hit = col == amin[:, None]
        gidx = jnp.max(jnp.where(hit, cand_i, jnp.int32(-1)), axis=1)
        od = jnp.where(out_col == t, vmin[:, None], od)
        oi = jnp.where(out_col == t, gidx[:, None], oi)
        cd = jnp.where(hit, BIG, cd)
        return cd, od, oi

    init = (
        cand_d,
        jnp.full((bn, kd), BIG, jnp.float32),
        jnp.zeros((bn, kd), jnp.int32),
    )
    _, out_d, out_i = lax.fori_loop(0, kd, body, init)
    return out_d, out_i


def _digc_kernel(x_ref, y_ref, *rest, kd: int, kd_pad: int, m_total: int,
                 block_m: int, block_n: int, has_pos: bool, causal: bool,
                 packed: bool, mxu_bf16: bool, kernel_merge: str,
                 idx_bits: int = 16, bucket_rounds: int = 0):
    refs = list(rest)
    p_ref = refs.pop(0) if has_pos else None
    if packed:
        ok_ref = refs.pop(0)  # int32 packed (dist|idx) output
    else:
        od_ref = refs.pop(0)
        oi_ref = refs.pop(0)
    bitonic = kernel_merge == "bitonic"
    if bitonic:
        # VMEM scratch accumulator: the running sorted buffer, written
        # to the outputs once, on the last streaming step; and the
        # tile's distances (or packed keys), which the merge reads one
        # row slab at a time.
        if packed:
            ak_ref, tile_ref = refs
        else:
            ad_ref, ai_ref, tile_ref = refs
    # grid = (B, N/bn, M/bm): program_id(0) is the batch index (its
    # blocks are squeezed out of the refs by the None BlockSpec dims).
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        if bitonic:
            if packed:
                ak_ref[...] = jnp.full(ak_ref.shape, INT_BIG, jnp.int32)
            else:
                ad_ref[...] = jnp.full(ad_ref.shape, BIG, jnp.float32)
                ai_ref[...] = jnp.full(ai_ref.shape, IDX_FILL, jnp.int32)
        elif packed:
            ok_ref[...] = jnp.full(ok_ref.shape, INT_BIG, jnp.int32)
        else:
            od_ref[...] = jnp.full(od_ref.shape, BIG, jnp.float32)
            oi_ref[...] = jnp.zeros(oi_ref.shape, jnp.int32)

    def _merge_slab(r, carry):
        # LSM: a slab's top-kd_pad per row, sorted descending; GMM: one
        # sorted merge into the ascending running buffer. Slabs keep the
        # unrolled networks to a few vector registers per array.
        rows = pl.ds(pl.multiple_of(r * _SLAB, _SLAB), _SLAB)
        if packed:
            top = lsm_topk((tile_ref[rows, :],), kd_pad, key_less,
                           (INT_BIG,), descending=True)
            (ak_ref[rows, :],) = gmm_merge((ak_ref[rows, :],), top, kd_pad,
                                           key_less)
        else:
            cols = j * block_m + lax.broadcasted_iota(
                jnp.int32, (_SLAB, block_m), 1)
            top = lsm_topk((tile_ref[rows, :], cols), kd_pad, dist_idx_less,
                           (BIG, IDX_FILL), descending=True)
            run_d, run_i = gmm_merge((ad_ref[rows, :], ai_ref[rows, :]), top,
                                     kd_pad, dist_idx_less)
            ad_ref[rows, :] = run_d
            ai_ref[rows, :] = run_i
        return carry

    def _do_tile():
        if mxu_bf16:
            # MXU-native: bf16 x bf16 -> fp32 accumulation (4x the fp32
            # matmul rate on v5e). Norm terms stay fp32.
            x = x_ref[...].astype(jnp.bfloat16)
            y = y_ref[...].astype(jnp.bfloat16)
        else:
            x = x_ref[...].astype(jnp.float32)
            y = y_ref[...].astype(jnp.float32)
        x32 = x.astype(jnp.float32)
        y32 = y.astype(jnp.float32)
        sq_x = jnp.sum(x32 * x32, axis=1, keepdims=True)  # (bn, 1)
        sq_y = jnp.sum(y32 * y32, axis=1)  # (bm,)
        # DCM: MXU contraction, fp32 accumulate. bf16 operands take one
        # pass whatever the caller's matmul-precision scope: Mosaic
        # refuses an fp32 contract precision on bf16 inputs.
        xy = lax.dot_general(
            x, y, (((1,), (1,)), ((), ())),
            precision=lax.Precision.DEFAULT if mxu_bf16 else None,
            preferred_element_type=jnp.float32,
        )  # (bn, bm)
        d_blk = sq_x - 2.0 * xy + sq_y[None, :]
        if p_ref is not None:
            d_blk = d_blk + p_ref[...].astype(jnp.float32)
        bn, bm = d_blk.shape
        cols = j * block_m + lax.broadcasted_iota(jnp.int32, (bn, bm), 1)
        d_blk = jnp.where(cols < m_total, d_blk, BIG)
        if causal:
            rows = i * block_n + lax.broadcasted_iota(jnp.int32, (bn, bm), 0)
            d_blk = jnp.where(cols <= rows, d_blk, BIG)

        if bitonic:
            tile_ref[...] = (_pack_keys(d_blk, cols, idx_bits) if packed
                             else d_blk)
            lax.fori_loop(0, bn // _SLAB, _merge_slab, 0)
        elif packed:
            blk_k = _pack_keys(d_blk, cols, idx_bits)
            if bucket_rounds > 0:
                blk_k = _bucket_reduce(blk_k, kd, bucket_rounds)
            ok_ref[...] = _merge_body_packed(kd, ok_ref[...], blk_k)
        else:
            run_d, run_i = _merge_body(kd, od_ref[...], oi_ref[...], d_blk, cols)
            od_ref[...] = run_d
            oi_ref[...] = run_i

    if causal:
        # Tiles strictly above the block diagonal contribute nothing:
        # skip the matmul + merge entirely (the FPGA has no such early
        # exit; this is a free TPU-side win from static grid predication).
        @pl.when(j * block_m <= i * block_n + (block_n - 1))
        def _live():
            _do_tile()
    else:
        _do_tile()

    if bitonic:
        # Single unpack/write per (b, i) row-block — the scratch
        # accumulator replaces the revisited-output-block pattern.
        @pl.when(j == pl.num_programs(2) - 1)
        def _final():
            if packed:
                ok_ref[...] = ak_ref[..., :kd]
            else:
                od_ref[...] = ad_ref[..., :kd]
                oi_ref[...] = ai_ref[..., :kd]


@functools.partial(
    jax.jit,
    static_argnames=("kd", "block_n", "block_m", "interpret", "m_valid",
                     "causal", "packed", "mxu_bf16", "bucket_rounds",
                     "kernel_merge"),
)
def digc_topk_pallas(
    x: jax.Array,
    y: jax.Array,
    pos_bias: Optional[jax.Array] = None,
    *,
    kd: int,
    block_n: int = 128,
    block_m: int = 256,
    interpret: Optional[bool] = None,
    m_valid: Optional[int] = None,
    causal: bool = False,
    packed: bool = False,
    mxu_bf16: bool = False,
    bucket_rounds: int = 0,
    kernel_merge: Optional[str] = None,
):
    """Run the fused kernel with batch as the leading grid dimension.

    x (B, N, D) or (N, D) (promoted to B=1 and squeezed back), y
    likewise, pos_bias (B, N, M) / (N, M). Inputs must be pre-padded:
    N % block_n == 0, M % block_m == 0 (use ``ops.digc_topk`` for the
    padding wrapper). Returns (dist, idx), each (B, N, kd) — (N, kd)
    for unbatched input — sorted ascending by distance. ``m_valid`` is
    the true (unpadded) co-node count; columns >= m_valid are masked to
    BIG inside the kernel.

    ``kernel_merge``: "bitonic" (default; partial bitonic LSM + sorted
    GMM, exact when unpacked) or "legacy" (kd-pass extraction merge).
    ``bucket_rounds`` implies/requires the legacy packed path.
    ``interpret=None`` resolves to compiled on TPU, interpret elsewhere.
    """
    if kernel_merge is None:
        kernel_merge = "legacy" if bucket_rounds > 0 else "bitonic"
    if kernel_merge not in KERNEL_MERGES:
        raise ValueError(
            f"unknown kernel_merge {kernel_merge!r}; expected one of "
            f"{KERNEL_MERGES}"
        )
    if bucket_rounds > 0:
        # The preconditions the kernel used to check (and silently skip
        # on) are wrapper-level contract violations now.
        if kernel_merge != "legacy":
            raise ValueError(
                "bucket_rounds pre-reduction belongs to the legacy merge; "
                f"got kernel_merge={kernel_merge!r} with "
                f"bucket_rounds={bucket_rounds}"
            )
        if not packed:
            raise ValueError("bucket_rounds requires packed=True keys")
        if block_m % kd != 0 or block_m // kd < 2:
            raise ValueError(
                "bucket_rounds requires block_m % kd == 0 and "
                f"block_m // kd >= 2; got block_m={block_m}, kd={kd}"
            )
    interpret = resolve_interpret(interpret)
    squeeze = x.ndim == 2
    if squeeze:
        x = x[None]
        y = y[None]
        if pos_bias is not None:
            pos_bias = pos_bias[None]
    b, n, feat = x.shape
    m = y.shape[1]
    assert y.shape[0] == b, (x.shape, y.shape)
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    if packed and m > 65536:
        raise ValueError("packed keys hold u16 indices: require M <= 65536")
    m_real = m_valid if m_valid is not None else m
    idx_bits = idx_bits_for(m_real) if packed else 16
    kd_pad = next_pow2(kd)
    grid = (b, n // block_n, m // block_m)

    kernel = functools.partial(
        _digc_kernel,
        kd=kd,
        kd_pad=kd_pad,
        m_total=m_valid if m_valid is not None else m,
        block_m=block_m,
        block_n=block_n,
        has_pos=pos_bias is not None,
        causal=causal,
        packed=packed,
        mxu_bf16=mxu_bf16,
        kernel_merge=kernel_merge,
        idx_bits=idx_bits,
        bucket_rounds=bucket_rounds,
    )
    # Leading None squeezes the batch dim out of the refs: each program
    # instance sees the same 2D tile shapes as the single-image kernel.
    in_specs = [
        pl.BlockSpec((None, block_n, feat), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((None, block_m, feat), lambda b, i, j: (b, j, 0)),
    ]
    args = [x, y]
    if pos_bias is not None:
        in_specs.append(
            pl.BlockSpec((None, block_n, block_m), lambda b, i, j: (b, i, j))
        )
        args.append(pos_bias)

    run_spec = pl.BlockSpec((None, block_n, kd), lambda b, i, j: (b, i, 0))
    if packed:
        out_shape = [jax.ShapeDtypeStruct((b, n, kd), jnp.int32)]
        out_specs = [run_spec]
    else:
        out_shape = [
            jax.ShapeDtypeStruct((b, n, kd), jnp.float32),
            jax.ShapeDtypeStruct((b, n, kd), jnp.int32),
        ]
        out_specs = [run_spec, run_spec]
    scratch_shapes = []
    if kernel_merge == "bitonic":
        # The running list lives in lanes [0, kd_pad) of a buffer as wide
        # as the LSM's lane chunks, so the GMM merge never changes width.
        if block_n % _SLAB:
            raise ValueError(
                f"the bitonic merge needs block_n % {_SLAB} == 0; got "
                f"block_n={block_n}"
            )
        lw = lane_width(block_m, kd_pad)
        if packed:
            scratch_shapes = [pltpu.VMEM((block_n, lw), jnp.int32),
                              pltpu.VMEM((block_n, block_m), jnp.int32)]
        else:
            scratch_shapes = [
                pltpu.VMEM((block_n, lw), jnp.float32),
                pltpu.VMEM((block_n, lw), jnp.int32),
                pltpu.VMEM((block_n, block_m), jnp.float32),
            ]
    outs = pl.pallas_call(
        kernel,
        name="digc_topk",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(*args)
    if packed:
        dist, idx = _unpack_keys(outs[0], idx_bits)
    else:
        dist, idx = outs[0], outs[1]
    if squeeze:
        dist, idx = dist[0], idx[0]
    return dist, idx
