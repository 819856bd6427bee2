"""Fused max-relative graph convolution (MRConv) kernel.

The consumer of DIGC's neighbor lists inside every ViG Grapher block:

    agg[i] = max_{j in N(i)} (y[idx[i, j]] - x[i])

TPU adaptation: arbitrary row gathers are the classic weak spot of the
vector unit, so the gather is expressed as a one-hot contraction on the
MXU (`onehot(idx) @ Y`) — the standard TPU embedding-gather idiom. The
co-node table streams through VMEM in blocks; each (node-block,
co-block) tile contributes its rows via a masked one-hot matmul and a
running elementwise max, so neither the full one-hot matrix nor an
(N, k, D) gathered tensor ever materializes.

grid = (B, N/bn, M/bm) with batch as the leading ("parallel") grid
dimension; per-tile work: k one-hot (bn, bm) @ (bm, D) MXU
contractions, one per neighbour column, each at HIGHEST precision (an
exact f32 gather; about six bf16 MXU passes). Validated in interpret mode vs
ref.mr_aggregate and compiled for TPU v5e by tests/test_chip_compile.py.
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

NEG = -1e30


def _mrconv_kernel(x_ref, idx_ref, y_ref, o_ref, *, block_m: int, k: int):
    # grid = (B, N/bn, M/bm); batch blocks are squeezed out of the refs.
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, NEG, jnp.float32)

    x = x_ref[...].astype(jnp.float32)  # (bn, D)
    y = y_ref[...].astype(jnp.float32)  # (bm, D)
    bn = x.shape[0]
    bm = y.shape[0]
    cols = lax.broadcasted_iota(jnp.int32, (bn, bm), 1)
    acc = o_ref[...]
    # One neighbour column at a time: a (bn, bm) one-hot of the
    # neighbours that live in THIS co-block (all-zero rows otherwise)
    # gathers their rows on the MXU, and a masked running max folds
    # them in, without flattening the (bn, k) index tile across the
    # lane axis. Nominally the FLOPs of one (bn*k, bm) contraction; at
    # HIGHEST precision the MXU runs each as about six bf16 passes.
    for t in range(k):
        local = idx_ref[:, t:t + 1] - j * block_m  # (bn, 1)
        onehot = (cols == local).astype(jnp.float32)
        gathered = lax.dot_general(
            onehot, y, (((1,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST,  # an exact row gather
            preferred_element_type=jnp.float32,
        )  # (bn, D)
        in_block = (local >= 0) & (local < bm)
        acc = jnp.maximum(acc, jnp.where(in_block, gathered - x, NEG))
    o_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret")
)
def mrconv_pallas(x: jax.Array, y: jax.Array, idx: jax.Array, *,
                  block_n: int = 128, block_m: int = 512,
                  interpret: Optional[bool] = None) -> jax.Array:
    """x: (B, N, D) nodes, y: (B, M, D) co-nodes, idx: (B, N, k)
    neighbor ids -> (B, N, D) max-relative aggregate; (N, D)-rank inputs
    are promoted to B=1 and squeezed back. Requires N % block_n == 0 and
    M % block_m == 0 (see ops.mrconv for the padding wrapper)."""
    squeeze = x.ndim == 2
    if squeeze:
        x, y, idx = x[None], y[None], idx[None]
    b, n, d = x.shape
    m = y.shape[1]
    k = idx.shape[2]
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    grid = (b, n // block_n, m // block_m)
    kernel = functools.partial(_mrconv_kernel, block_m=block_m, k=k)
    out = pl.pallas_call(
        kernel,
        name="mrconv",
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_n, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_n, k), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_m, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_n, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, d), jnp.float32),
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(x, idx.astype(jnp.int32), y)
    return out[0] if squeeze else out
