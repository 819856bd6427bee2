"""Plain ViG forward: the yardstick that decides ``correct``.

Straight ``jax.numpy`` from the configuration file's own numbers, one
image at a time, with no kernels, no state and no batching. It imports
nothing of the program under test. It follows Han et al., *Vision GNN*
(NeurIPS 2022, arXiv:2206.00272) as the configuration files state it,
with these departures, which the program makes too:

- LayerNorm (no bias) in place of BatchNorm before the Grapher and FFN.
- Linear layers without bias; the stem is one linear map of each
  ``patch x patch`` patch (the paper's stem is a stack of convolutions).
- No relative positional bias in the DIGC distance.
- The Grapher is ``x + fc_out(gelu(fc_graph([h, max_j (y_j - h_i)])))``
  with ``h = fc_in(LN(x))`` and ``y`` the co-nodes (``h`` pooled r x r):
  MRConv over the dilated k nearest co-nodes. The FFN is
  ``x + fc2(gelu(fc1(LN(x))))``.
- Each block's k is the configuration's ``num_knn``: for isotropic
  ViG-Ti, 9 in every block where the official code ramps it from 9 to
  18 over the blocks (the configuration's ``k_schedule``).
- Above the native grid, k and the dilation ramp to twice their native
  values at twice the native grid (the configuration's
  ``resolution_ramp``), and the position embedding is resized
  bilinearly.

The weights are drawn here from the seed, in one jitted call, and handed
to the program and the reference alike.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Block:
    stage: int
    index: int  # block index inside its stage
    grid: int  # node grid side
    r: int  # co-node pooling ratio
    k: int
    dilation: int

    @property
    def m(self) -> int:
        return (self.grid // self.r) ** 2


def _ramp(v: int, grid: int, native: int) -> int:
    if grid <= native:
        return v
    return int(round(v * (1.0 + min(1.0, (grid - native) / native))))


def plan(conf: dict, image_size: int) -> list[Block]:
    """Every Grapher block's grid, pooling, k and dilation at
    ``image_size``, worked out from the configuration file alone."""
    grid = image_size // conf["patch"]
    native = conf["image_size"] // conf["patch"]
    out, gb = [], 0
    for si, depth in enumerate(conf["depths"]):
        r = conf["reduce_ratios"][si]
        m = (grid // r) ** 2
        for bi in range(depth):
            k = _ramp(conf["num_knn"][gb], grid, native)
            d = gb // 4 + 1 if conf["use_dilation"] else 1
            cap = _ramp(conf["max_dilation"], grid, native)
            d = min(_ramp(d, grid, native), cap)
            while k * d > m and d > 1:
                d -= 1
            k_eff = min(k, m // d) or 1
            if k_eff * d > m:
                d = 1
            out.append(Block(si, bi, grid, r, k_eff, d))
            gb += 1
        grid //= 2
        native //= 2
    return out


def weight_shapes(conf: dict) -> dict:
    """The weight tree: (shape, init) per leaf."""
    dims, p, c = conf["embed_dims"], conf["patch"], conf["in_chans"]
    n0 = (conf["image_size"] // p) ** 2
    tree = {"stem": ((p * p * c, dims[0]), "fanin"),
            "pos": ((n0, dims[0]), "normal"),
            "head": ((dims[-1], conf["num_classes"]), "fanin")}
    f = conf["ffn_ratio"]
    for si, (d, depth) in enumerate(zip(dims, conf["depths"])):
        tree[f"stage{si}"] = {f"block{bi}": {
            "ln_g": {"scale": ((d,), "ones")},
            "fc_in": ((d, d), "fanin"),
            "fc_graph": ((2 * d, d), "fanin"),
            "fc_out": ((d, d), "fanin"),
            "ln_f": {"scale": ((d,), "ones")},
            "fc1": ((d, f * d), "fanin"),
            "fc2": ((f * d, d), "fanin"),
        } for bi in range(depth)}
        if si + 1 < len(dims):
            tree[f"down{si}"] = ((4 * d, dims[si + 1]), "fanin")
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_weights(conf: dict, key) -> dict:
    """float32 weights from ``key`` in one jitted call on the device:
    N(0, 1/fan_in) for matrices, N(0, 0.02) for the position embedding,
    ones for the norm scales."""
    shapes = weight_shapes(conf)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_leaf)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for (shape, init), k in zip(leaves, keys):
            if init == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            elif init == "normal":
                out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
            else:
                out.append(jax.random.normal(k, shape, jnp.float32)
                           / math.sqrt(shape[0]))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key)


def _ln(x, scale):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale


def _pool(h, grid: int, r: int):
    if r == 1:
        return h
    d = h.shape[-1]
    g = grid // r
    return h.reshape(g, r, g, r, d).mean(axis=(1, 3)).reshape(g * g, d)


def sq_dists(h, y):
    """(N, M) squared euclidean distances, the contraction at
    ``highest`` precision (the products of the operands' own dtype)."""
    inner = jnp.matmul(h, y.T, precision=jax.lax.Precision.HIGHEST)
    return (jnp.sum(h * h, -1)[:, None] - 2.0 * inner
            + jnp.sum(y * y, -1)[None, :])


def neighbours(h, y, k: int, dilation: int):
    """Every ``dilation``-th of the ``k * dilation`` co-nodes of ``y``
    nearest each row of ``h``."""
    _, idx = jax.lax.top_k(-sq_dists(h, y), k * dilation)
    return idx[:, ::dilation]


def _pos(pos, native: int, grid: int):
    if grid == native:
        return pos
    d = pos.shape[-1]
    out = jax.image.resize(pos.reshape(native, native, d), (grid, grid, d),
                           method="bilinear")
    return out.reshape(grid * grid, d)


def forward_one(w, image, conf: dict, blocks: list[Block],
                graphs: Optional[list] = None, keep: Optional[list] = None,
                rows: int = 1):
    """One (H, W, C) image -> (logits, [(h, y, idx) per block]).

    ``graphs`` (one (N, k) index array per block) replaces the forward's
    own neighbour search with given lists, row by row where ``keep``
    (one (N,) bool array per block) is true; the other rows take the
    forward's own exact lists. The comparison hands it the served lists
    and keeps the rows that passed the list check: the logits are then
    compared on one graph, and not on two that differ only where
    candidates tie, while a row that failed changes the graph.

    ``rows`` shapes the head's contraction as the served tick's: the
    pooled vector repeated over the tick's width. On a TPU, XLA takes
    one bf16 MXU pass for a matrix but another path for a single row,
    both at the default precision; the answer is row 0."""
    p = conf["patch"]
    g = image.shape[0] // p
    c = image.shape[-1]
    x = image.reshape(g, p, g, p, c).transpose(0, 2, 1, 3, 4)
    x = x.reshape(g * g, p * p * c) @ w["stem"]
    x = x + _pos(w["pos"], conf["image_size"] // p, g)
    seen = []
    for i, blk in enumerate(blocks):
        bp = w[f"stage{blk.stage}"][f"block{blk.index}"]
        h = _ln(x, bp["ln_g"]["scale"]) @ bp["fc_in"]
        y = _pool(h, blk.grid, blk.r)
        if graphs is None:
            idx = neighbours(h, y, blk.k, blk.dilation)
        else:
            idx = jnp.where(keep[i][:, None], graphs[i],
                            neighbours(h, y, blk.k, blk.dilation))
        seen.append((h, y, idx))
        agg = jnp.max(y[idx] - h[:, None, :], axis=1)
        h = jnp.concatenate([h, agg], -1) @ bp["fc_graph"]
        x = x + jax.nn.gelu(h) @ bp["fc_out"]
        x = x + jax.nn.gelu(_ln(x, bp["ln_f"]["scale"]) @ bp["fc1"]) @ bp["fc2"]
        if (blk.index + 1 == conf["depths"][blk.stage]
                and blk.stage + 1 < len(conf["depths"])):
            gs, d = blk.grid // 2, x.shape[-1]
            x = x.reshape(gs, 2, gs, 2, d).transpose(0, 2, 1, 3, 4)
            x = x.reshape(gs * gs, 4 * d) @ w[f"down{blk.stage}"]
    pooled = jnp.broadcast_to(jnp.mean(x, axis=0), (rows, x.shape[-1]))
    return (pooled @ w["head"])[0], seen


def row_gaps(h, y, idx, dilation: int):
    """How far each row's neighbour list is from the exact one, for the
    block's own features: the largest gap between the sorted distances
    of the row's k listed co-nodes and the row's exact order statistics
    0, d, ..., (k-1)d, relative to |h_i|^2 + max |y|^2. Tie order cannot
    move an order statistic, so an exact list reads ~0 and a wrong
    member the spacing of its row's order statistics. (N,) float32."""
    h = jnp.asarray(h, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    idx = jnp.asarray(idx, jnp.int32)
    want_idx = _neighbours(h, y, idx.shape[-1], dilation)
    return _gap_rows(h, y, idx, want_idx)


@jax.jit
def _gap_rows(h, y, idx, want_idx):
    dist = sq_dists(h, y)
    got = jnp.sort(jnp.take_along_axis(dist, idx, axis=1), axis=1)
    want = jnp.take_along_axis(dist, want_idx, axis=1)
    scale = jnp.sum(h * h, -1) + jnp.max(jnp.sum(y * y, -1))
    return jnp.abs(got - want).max(1) / scale


_neighbours = jax.jit(neighbours, static_argnums=(2, 3))


def make_forward(conf: dict, image_size: int, *, dtype=jnp.float32,
                 precision: str = "default", taught: bool = False):
    """A jitted single-image forward at ``image_size``.

    ``taught`` False: ``(w, image) -> (logits, [(h, y, idx)])``.
    ``taught`` True: ``(w, image, graphs, keep, rows) -> logits`` on
    given lists (see ``forward_one``).
    ``dtype`` casts the weights and the image and computes in it
    throughout; ``precision`` is the matmul precision."""
    blocks = plan(conf, image_size)

    def cast(w, image):
        return (jax.tree_util.tree_map(lambda a: a.astype(dtype), w),
                image.astype(dtype))

    if taught:
        def fwd(w, image, graphs, keep, rows):
            w, image = cast(w, image)
            logits, _ = forward_one(w, image, conf, blocks, graphs, keep,
                                    rows)
            return logits.astype(jnp.float32)
    else:
        def fwd(w, image):
            w, image = cast(w, image)
            logits, seen = forward_one(w, image, conf, blocks)
            return logits.astype(jnp.float32), seen

    jitted = jax.jit(fwd, static_argnames="rows") if taught else jax.jit(fwd)

    def run(*args, **kw):
        with jax.default_matmul_precision(precision):
            return jitted(*args, **kw)

    run.blocks = blocks
    return run
