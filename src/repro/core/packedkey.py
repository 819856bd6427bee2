"""Order-preserving packed (distance, index) keys — shared by the XLA
engine and the Pallas kernel.

The paper's GMM stage moves (distance, index) pairs through the merge
network as one word (u16 index + truncated distance). The TPU/XLA
analogue packs both into a single int32 whose *integer* order equals
the lexicographic (distance, index) order:

  * the fp32 distance is made order-monotonic with the standard IEEE
    total-order flip (non-negative floats keep their bit pattern;
    negative floats are inverted), then truncated to the top
    ``32 - idx_bits`` bits;
  * the low ``idx_bits = ceil(log2 M)`` bits hold the co-node index.

One array instead of two halves merge traffic, ``min()`` extracts the
(dist, idx) winner in a single op, and ties created by the truncation
resolve to the *lowest index* — the same tie rule as ``lax.top_k``.
Precision is adaptive: M=196 keeps 16 mantissa bits (near-exact);
M=16384 (ViG @ 2048^2) keeps 9. Packed selection is therefore
tie-tolerant rather than bit-exact: indices may differ from the fp32
path only where two distances agree in their truncated high bits
(within ~2^-(23-idx_bits) relative). Exact consumers use the unpacked
paths; ``kernels/digc_topk.py`` and ``core/engine.py`` expose packing
as an opt-in knob (``DigcSpec.packed`` / ``merge="packed"``).

This module also hosts the **bitonic networks** shared by the Pallas
kernel's LSM+GMM stages and the engine's packed merge (``lsm_topk`` /
``gmm_merge``, comparator-generic so the kernel's exact path moves
(dist, idx) pairs and the packed paths one int32 key array). Every
network is built from data-independent compare-exchange passes over
lane rotations — no gathers, no data-dependent control flow, no
reshape of the lane axis, static shapes throughout — so the same code
lowers with Mosaic and runs under XLA (DESIGN.md §2). Because the
packed-key integer order *is* the lexicographic (dist, idx) order,
the networks preserve the lowest-index tie rule exactly.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

# Packed-key sentinel (a very large distance with index bits zeroed).
# A python int so it inlines as a weak-typed literal in kernels instead
# of being captured as a constant.
INT_BIG = 0x7F7F0000

# Beyond 20 index bits fewer than 3 mantissa bits survive — selection
# degenerates to exponent-only comparison. Refuse rather than degrade.
MAX_IDX_BITS = 20


def idx_bits_for(m: int) -> int:
    """Index bits needed to address co-nodes [0, m); at least 1."""
    if m > (1 << MAX_IDX_BITS):
        raise ValueError(
            f"packed keys support at most {1 << MAX_IDX_BITS} co-nodes "
            f"({MAX_IDX_BITS} index bits); got M={m}. Use an unpacked "
            "merge for larger co-node sets."
        )
    return max(int(m - 1).bit_length(), 1)


def pack_keys(d: jax.Array, idx: jax.Array, idx_bits: int) -> jax.Array:
    """Order-preserving (distance, index) -> single int32 key."""
    INT_MIN = jnp.int32(-(2**31))
    bits = jax.lax.bitcast_convert_type(d.astype(jnp.float32), jnp.int32)
    key = jnp.where(bits >= 0, bits, jnp.invert(bits) ^ INT_MIN)
    hi = jnp.right_shift(key, idx_bits)  # arithmetic shift: order-preserving
    mask = jnp.int32((1 << idx_bits) - 1)
    return jnp.left_shift(hi, idx_bits) | (idx & mask)


def unpack_keys(keys: jax.Array, idx_bits: int) -> tuple[jax.Array, jax.Array]:
    """Inverse of ``pack_keys``: int32 keys -> (fp32 distance, int32 idx).

    The recovered distance carries the truncation (low ``idx_bits``
    mantissa bits zeroed) — within 2^-(23-idx_bits) relative of the
    original, and still far above ``BIG/2`` for sentinel lanes.
    """
    INT_MIN = jnp.int32(-(2**31))
    idx = keys & jnp.int32((1 << idx_bits) - 1)
    bits = jnp.left_shift(jnp.right_shift(keys, idx_bits), idx_bits)
    bits = jnp.where(bits >= 0, bits, jnp.invert(bits ^ INT_MIN))
    return jax.lax.bitcast_convert_type(bits, jnp.float32), idx


# ---------------------------------------------------------------------------
# Bitonic compare-exchange networks (LSM local sort + GMM sorted merge)
#
# The networks move a *tuple* of arrays in lockstep so the kernel's
# exact path can sort (dist, idx) pairs under the lexicographic order
# (``dist_idx_less``); packed callers pass a single int32 key array,
# whose integer order already encodes it (``key_less``).
#
# Lowering-safe form (DESIGN.md §2): every pass keeps the last (lane)
# axis whole. Lane p meets its partner p ^ dist through two lane
# rotations (``pltpu.roll``, which lowers to a TPU lane rotate inside a
# kernel and to ``jnp.roll`` under XLA) selected by a lane-iota bit;
# directions come from lane-iota bits too. Wider inputs are cut into
# lane chunks by static slices, and chunk pairs meet elementwise. Groups
# are sorted in alternating directions, so two of them always meet as
# (ascending, descending) and no reversal is ever needed.

# Index fill for padded lanes in the exact two-array path: larger than
# any real co-node index, so a padding lane loses every distance tie.
IDX_FILL = 0x7FFFFFFF

# Chunk width of the lane networks: one TPU vector register row.
LANES = 128


def next_pow2(v: int) -> int:
    """Smallest power of two >= v (1 for v <= 1)."""
    return 1 if v <= 1 else 1 << (v - 1).bit_length()


def key_less(a: Sequence[jax.Array], b: Sequence[jax.Array]) -> jax.Array:
    """Packed-key comparator: integer order == (dist, idx) order."""
    return a[0] < b[0]


def dist_idx_less(a: Sequence[jax.Array], b: Sequence[jax.Array]) -> jax.Array:
    """Lexicographic (distance, index) comparator — ``lax.top_k``'s tie
    rule (lowest index wins among equal distances), made explicit."""
    return (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))


def _lane(v: jax.Array) -> jax.Array:
    # broadcasted_iota keeps this a traced op (TPU rejects 1D iota and
    # captured constants).
    return lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)


# ``pltpu.roll`` (jnp.roll semantics) has lowering rules for Mosaic
# and XLA but no eager rule; the jit wrapper covers eager callers and
# inlines under any trace, a kernel's included.
_roll = jax.jit(pltpu.roll, static_argnums=(1, 2))


def _partner(v: jax.Array, dist: int, up: jax.Array) -> jax.Array:
    """Lane p's value at lane p ^ dist: lanes with the ``dist`` bit set
    read dist lanes back, the others dist lanes ahead."""
    width = v.shape[-1]
    axis = v.ndim - 1
    return jnp.where(up, _roll(v, dist, axis), _roll(v, width - dist, axis))


def _ce_pass(vals: tuple, dist: int, asc, less: Callable) -> tuple:
    """One compare-exchange pass at partner distance ``dist`` (< width)
    along the last axis. ``asc`` (a python bool or a lane mask) is the
    direction of the pair each lane belongs to: an ascending pair keeps
    the lesser element in its lower lane."""
    up = (_lane(vals[0]) & dist) != 0
    partner = tuple(_partner(v, dist, up) for v in vals)
    if less is key_less:
        (v,), (p,) = vals, partner
        take_min = up != asc
        return (jnp.where(take_min, jnp.minimum(v, p), jnp.maximum(v, p)),)
    # Boolean algebra only: Mosaic does not select between or compare
    # boolean vectors.
    first = (up & less(partner, vals)) | (~up & less(vals, partner))
    if asc is True:
        keep = first
    elif asc is False:
        keep = ~first
    else:
        keep = ~(first ^ asc)
    return tuple(jnp.where(keep, v, p) for v, p in zip(vals, partner))


def _lane_dir(lane: jax.Array, bit: int):
    """Ascending where the lane's ``bit`` is clear."""
    return (lane & bit) == 0


def _group_sort(vals: tuple, group: int, target, less: Callable) -> tuple:
    """Sort every run of ``group`` lanes; the final direction of each
    run is ``target`` (a python bool or a lane mask)."""
    lane = _lane(vals[0])
    run = 2
    while run <= group:
        asc = target if run == group else _lane_dir(lane, run)
        dist = run // 2
        while dist >= 1:
            vals = _ce_pass(vals, dist, asc, less)
            dist //= 2
        run *= 2
    return vals


def _clean(vals: tuple, group: int, asc, less: Callable) -> tuple:
    """Sort every bitonic run of ``group`` lanes in direction ``asc``:
    log2(group) passes."""
    dist = group // 2
    while dist >= 1:
        vals = _ce_pass(vals, dist, asc, less)
        dist //= 2
    return vals


def _pairwise_less(a: tuple, b: tuple, less: Callable) -> tuple:
    take_a = less(a, b)
    return tuple(jnp.where(take_a, x, y) for x, y in zip(a, b))


def lane_width(width: int, k_pad: int) -> int:
    """Chunk width ``lsm_topk`` works in for a last axis of ``width``
    lanes: one vector row (or less for narrow inputs), never narrower
    than one group of ``k_pad``."""
    return max(k_pad, min(LANES, next_pow2(width)))


def lsm_topk(vals: tuple, k_pad: int, less: Callable, fill: tuple, *,
             descending: bool = False) -> tuple:
    """LSM: the lowest ``k_pad`` of the last axis (any width), sorted,
    in lanes [0, k_pad) of a ``lane_width(width, k_pad)``-wide result
    (the other lanes hold discarded candidates).

    The input is cut into lane chunks (static slices, ``fill``-padded
    to a power-of-two count); every k_pad-lane group is sorted, then
    group pairs are tournament-merged until one group remains: first
    chunk against chunk (elementwise, the work halving every round),
    then within the last chunk by lane rotations. Each group is sorted
    in the direction its next merge needs, so every merge is one
    elementwise min of an ascending and a descending group plus
    log2(k_pad) clean passes. ``descending`` orders the result
    high-to-low (what ``gmm_merge`` takes)."""
    if k_pad & (k_pad - 1):
        raise ValueError(f"lsm_topk needs a power-of-two k_pad; got {k_pad}")
    lead = vals[0].shape[:-1]
    width = vals[0].shape[-1]
    lw = lane_width(width, k_pad)
    chunks = []
    for c in range(-(-width // lw)):
        part = tuple(v[..., c * lw:(c + 1) * lw] for v in vals)
        short = lw - part[0].shape[-1]
        if short:
            part = tuple(
                jnp.concatenate([p, jnp.full(lead + (short,), f, p.dtype)],
                                axis=-1)
                for p, f in zip(part, fill)
            )
        chunks.append(part)
    n_chunks = next_pow2(len(chunks))
    while len(chunks) < n_chunks:
        chunks.append(tuple(jnp.full(lead + (lw,), f, v.dtype)
                            for v, f in zip(vals, fill)))
    lane = _lane(chunks[0][0])

    def direction(bit, c):
        # Direction of chunk c's groups before the merge at virtual lane
        # distance ``bit`` (virtual lane = c * lw + lane).
        if bit is None:
            return not descending
        if bit >= lw:
            return (c * lw) & bit == 0
        return _lane_dir(lane, bit)

    dists = []
    d = n_chunks * lw // 2
    while d >= k_pad:
        dists.append(d)
        d //= 2
    first = dists[0] if dists else None
    chunks = [_group_sort(ch, k_pad, direction(first, c), less)
              for c, ch in enumerate(chunks)]
    for r, dist in enumerate(dists):
        nxt = dists[r + 1] if r + 1 < len(dists) else None
        if dist >= lw:
            half = dist // lw
            chunks = [
                _clean(_pairwise_less(chunks[c], chunks[c + half], less),
                       k_pad, direction(nxt, c), less)
                for c in range(half)
            ]
        else:
            (ch,) = chunks
            ch = _ce_pass(ch, dist, True, less)
            chunks = [_clean(ch, k_pad, direction(nxt, 0), less)]
    return chunks[0]


def gmm_merge(run: tuple, top: tuple, k_pad: int, less: Callable) -> tuple:
    """GMM: fold a descending list ``top`` into the ascending running
    list ``run`` (both in lanes [0, k_pad) of equal-width arrays) in
    1 + log2(k_pad) passes. The elementwise winners of an ascending and
    a descending list are exactly the k_pad smallest of their union and
    form a bitonic sequence; the clean passes sort it ascending — the
    paper's heap insertion as a sorting network."""
    return _clean(_pairwise_less(run, top, less), k_pad, True, less)
