"""jit'd public wrappers for the Pallas kernels (padding + NSM).

Both wrappers are batched-first: (B, N, D) inputs map straight onto the
kernels' leading batch grid dimension; (N, D) inputs are promoted to
B=1 and squeezed back. This module also registers the ``pallas``
GraphBuilder (DESIGN.md §4), including its fused MRConv aggregation.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.builder import DigcSpec, GraphBuilder, promote_batch, register
from repro.kernels.digc_topk import digc_topk_pallas
from repro.kernels.mrconv import mrconv_pallas


def _ceil_to(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def mrconv(x: jax.Array, y: jax.Array, idx: jax.Array, *,
           block_n: int = 128, block_m: int = 512,
           interpret: Optional[bool] = None) -> jax.Array:
    """Fused max-relative aggregation with automatic padding.
    x: (B, N, D) | (N, D), y: (B, M, D) | (M, D), idx: (B, N, k) | (N, k)
    -> aggregate of x's rank."""
    if not (x.ndim == y.ndim == idx.ndim) or x.ndim not in (2, 3):
        raise ValueError(
            "mrconv expects (N, D)/(M, D)/(N, k) or uniformly batched "
            f"(B, ...) inputs; got {x.shape}, {y.shape}, {idx.shape}"
        )
    squeeze = x.ndim == 2
    if squeeze:
        x, y, idx = x[None], y[None], idx[None]
    b, n, d = x.shape
    m = y.shape[1]
    block_n = min(block_n, _ceil_to(n, 8))
    block_m = min(block_m, _ceil_to(m, 128))
    n_pad = _ceil_to(n, block_n)
    m_pad = _ceil_to(m, block_m)
    x_p = jnp.pad(x, ((0, 0), (0, n_pad - n), (0, 0)))
    y_p = jnp.pad(y, ((0, 0), (0, m_pad - m), (0, 0)))
    idx_p = jnp.pad(idx, ((0, 0), (0, n_pad - n), (0, 0)))
    out = mrconv_pallas(x_p, y_p, idx_p, block_n=block_n, block_m=block_m,
                        interpret=interpret)
    out = out[:, :n].astype(x.dtype)
    return out[0] if squeeze else out


def digc_topk(
    x: jax.Array,
    y: jax.Array,
    *,
    k: int,
    dilation: int = 1,
    pos_bias: Optional[jax.Array] = None,
    block_n: Optional[int] = None,
    block_m: Optional[int] = None,
    interpret: Optional[bool] = None,
    return_dists: bool = False,
    causal: bool = False,
    packed: bool = False,
    mxu_bf16: bool = False,
    bucket_rounds: int = 0,
    kernel_merge: Optional[str] = None,
):
    """Fused-kernel DIGC with automatic padding and dilated selection.

    x: (B, N, D) | (N, D) nodes, y co-nodes, optional pos_bias
    (B, N, M) | (N, M). Returns idx (B, N, k) [, dist] matching x's rank.
    Tile sizes default to the workload-adaptive VMEM-budgeted choice
    (``perfmodel.kernel_tile_defaults``) instead of one fixed shape.
    ``kernel_merge`` selects the LSM/GMM realization ("bitonic" default,
    "legacy" kd-pass); ``interpret=None`` is compiled-on-TPU auto.
    """
    x3, y3, p3, squeeze = promote_batch(x, y, pos_bias)
    _, n, feat = x3.shape
    m = y3.shape[1]
    kd = k * dilation
    if kd > m:
        raise ValueError(f"k*dilation={kd} exceeds number of co-nodes M={m}")
    if block_n is None or block_m is None:
        from repro.core.perfmodel import kernel_tile_defaults

        bn_auto, bm_auto = kernel_tile_defaults(n, m, feat, kd)
        block_n = bn_auto if block_n is None else block_n
        block_m = bm_auto if block_m is None else block_m
    block_n = min(block_n, _ceil_to(n, 8))
    block_m = min(block_m, _ceil_to(m, 128))
    n_pad = _ceil_to(n, block_n)
    m_pad = _ceil_to(m, block_m)
    x_p = jnp.pad(x3, ((0, 0), (0, n_pad - n), (0, 0)))
    y_p = jnp.pad(y3, ((0, 0), (0, m_pad - m), (0, 0)))
    p_p = None
    if p3 is not None:
        p_p = jnp.pad(p3, ((0, 0), (0, n_pad - n), (0, m_pad - m)))
    dist, idx = digc_topk_pallas(
        x_p,
        y_p,
        p_p,
        kd=kd,
        block_n=block_n,
        block_m=block_m,
        interpret=interpret,
        m_valid=m,
        causal=causal,
        packed=packed,
        mxu_bf16=mxu_bf16,
        bucket_rounds=bucket_rounds,
        kernel_merge=kernel_merge,
    )
    dist = dist[:, :n, ::dilation]
    idx = idx[:, :n, ::dilation]
    if squeeze:
        dist, idx = dist[0], idx[0]
    if return_dists:
        return idx, dist
    return idx


# --------------------------------------------------------------------------
# Registry entry (DESIGN.md §4).


def _build_pallas(x, y, pos_bias, spec: DigcSpec):
    return digc_topk(
        x, y, k=spec.k, dilation=spec.dilation, pos_bias=pos_bias,
        causal=spec.causal, return_dists=True,
        block_n=spec.block_n,  # None = workload-adaptive VMEM-budgeted tiles
        block_m=spec.block_m,
        interpret=spec.interpret,  # None = compiled on TPU, interpret off-TPU
        packed=bool(spec.packed),
        mxu_bf16=bool(spec.mxu_bf16),
        bucket_rounds=spec.bucket_rounds if spec.bucket_rounds is not None else 0,
        kernel_merge=spec.kernel_merge,
    )


register(GraphBuilder(
    name="pallas",
    build=_build_pallas,
    knobs=frozenset({
        "block_n", "block_m", "interpret", "packed", "mxu_bf16",
        "bucket_rounds", "kernel_merge",
    }),
    exact=True,  # packed / bucket_rounds knobs opt into approximation
    supports_pos_bias=True,
    supports_causal=True,
    aggregate=mrconv,  # fused gather-aggregate kernel
    doc="fused Pallas kernel: distance + streaming top-kd in VMEM, "
        "batch as leading grid dim",
))
