"""Gradient compression: int8 ring all-reduce with error feedback.

For DCN-bound multi-pod training the cross-pod gradient all-reduce is
the dominant collective. This module quantizes chunks to int8 with a
per-chunk fp32 scale (~4x traffic cut), runs a ring reduce-scatter +
all-gather over `collective_permute` (bandwidth-optimal), and keeps the
quantization residual in an error-feedback buffer so compression noise
does not bias the optimizer (1-bit-Adam-family argument).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12  # scalar per chunk
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def _ring_allreduce_int8(x: jax.Array, axis_name: str, n_dev: int) -> jax.Array:
    """Bandwidth-optimal ring all-reduce; each hop's payload is int8 +
    one fp32 scale per chunk. x: (n_dev * chunk,) fp32 -> summed."""
    chunk = x.shape[0] // n_dev
    xs = x.reshape(n_dev, chunk)
    me = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    # reduce-scatter: after n-1 hops, device d owns the full sum of
    # chunk (d+1) mod n
    def rs_hop(h, acc):
        send_idx = (me - h) % n_dev
        payload = jnp.take(acc, send_idx, axis=0)
        q, s = quantize_int8(payload)
        q_r = lax.ppermute(q, axis_name, fwd)
        s_r = lax.ppermute(s, axis_name, fwd)
        recv = dequantize_int8(q_r, s_r)
        recv_idx = (me - h - 1) % n_dev
        return acc.at[recv_idx].add(recv)

    acc = lax.fori_loop(0, n_dev - 1, rs_hop, xs)

    # all-gather the owned chunks (int8 again)
    def ag_hop(h, acc):
        send_idx = (me + 1 - h) % n_dev
        payload = jnp.take(acc, send_idx, axis=0)
        q, s = quantize_int8(payload)
        q_r = lax.ppermute(q, axis_name, fwd)
        s_r = lax.ppermute(s, axis_name, fwd)
        recv = dequantize_int8(q_r, s_r)
        recv_idx = (me - h) % n_dev
        return acc.at[recv_idx].set(recv)

    acc = lax.fori_loop(0, n_dev - 1, ag_hop, acc)
    return acc.reshape(-1)


def compressed_psum(x: jax.Array, axis_name: str, n_dev: int) -> jax.Array:
    """Drop-in psum replacement (int8 ring). x flat fp32, padded to
    n_dev multiple by the caller."""
    return _ring_allreduce_int8(x, axis_name, n_dev)


def compressed_allreduce_tree(grads, mesh: Mesh, axis_name: str = "pod"):
    """All-reduce a gradient pytree across `axis_name` with int8 ring
    compression. Grads must be identical-shaped on every member (DP).
    Returns the SUM (caller divides)."""
    n_dev = mesh.shape[axis_name]
    if n_dev == 1:
        return grads
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    pad = (-flat.size) % n_dev
    if pad:
        flat = jnp.pad(flat, (0, pad))

    body = functools.partial(compressed_psum, axis_name=axis_name, n_dev=n_dev)
    other = tuple(a for a in mesh.axis_names if a != axis_name)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P(),
        out_specs=P(),
        check_vma=False,
    )
    summed = mapped(flat)
    if pad:
        summed = summed[: flat.size - pad]
    out = []
    off = 0
    for l, n in zip(leaves, sizes):
        out.append(summed[off : off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


class ErrorFeedback:
    """Residual accumulator: g_compressed = Q(g + e); e' = (g + e) -
    dequant(Q(...)). Keeps long-run compression error unbiased."""

    @staticmethod
    def init(grads):
        return jax.tree_util.tree_map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads
        )

    @staticmethod
    def apply(grads, residual):
        corrected = jax.tree_util.tree_map(
            lambda g, e: g.astype(jnp.float32) + e, grads, residual
        )
        q = jax.tree_util.tree_map(lambda c: dequantize_int8(*quantize_int8(c)), corrected)
        new_residual = jax.tree_util.tree_map(lambda c, d: c - d, corrected, q)
        return q, new_residual
