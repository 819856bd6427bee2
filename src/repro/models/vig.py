"""Vision GNN (ViG) backbones — isotropic and pyramid variants.

Each Grapher block re-runs DIGC on the current features (the *dynamic*
in DIGC) and aggregates neighbors with max-relative graph convolution,
exactly the pipeline the paper accelerates. The DIGC implementation is
a constructor choice resolved through the GraphBuilder registry
(`digc_impl` names any registered builder — reference | blocked |
pallas | cluster | axial | ... — or pass a full DigcSpec), mirroring
the paper's "modular similarity mechanism" claim. The model contains no
strategy-specific code: DIGC runs batched over (B, N, D) directly and
each builder brings its own fused aggregation if it has one.

Pyramid variants pool co-nodes by the stage reduction ratio r before
graph construction (paper §III-C: Y from spatial pooling, M = N / r^2).

Deviation from the torch reference: BatchNorm -> LayerNorm (stateless,
jit-friendly); this changes training dynamics, not DIGC structure.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core.builder import DigcSpec, get_builder
from repro.core.digc import digc
from repro.core.graph import mr_aggregate
from repro.core.state import DigcState, state_entry
from repro.core.tuner import VigSchedule
from repro.models.module import spec


class VigGridError(ValueError):
    """Typed config-time error for grid geometry a model cannot run:
    non-square / non-patch-aligned inputs, or a pyramid stage whose
    grid is not divisible by its reduce ratio or by the 2x downsample
    (the old failure mode was a bare reshape TypeError mid-forward)."""


@dataclasses.dataclass(frozen=True)
class VigConfig:
    name: str
    variant: str  # isotropic | pyramid
    image_size: int = 224
    patch: int = 16
    in_chans: int = 3
    embed_dims: tuple[int, ...] = (192,)
    depths: tuple[int, ...] = (12,)
    reduce_ratios: tuple[int, ...] = (1,)
    k: int = 9
    max_dilation: int = 4
    use_dilation: bool = True
    num_classes: int = 1000
    digc_impl: str = "blocked"
    ffn_ratio: int = 4
    # Per-block neighbour counts over the whole network (the official
    # isotropic ViGs ramp k, ``linspace(k, 2k, depth)``); None serves
    # ``k`` in every block.
    num_knn: Optional[tuple[int, ...]] = None

    def __post_init__(self):
        if self.num_knn is not None and len(self.num_knn) != sum(self.depths):
            raise ValueError(
                f"{self.name}: num_knn has {len(self.num_knn)} entries for "
                f"{sum(self.depths)} blocks"
            )

    @property
    def base_grid(self) -> int:
        return self.image_size // self.patch

    def grid_at_stage(self, si: int) -> int:
        return max(self.base_grid // (2**si), 1)

    def replace(self, **kw) -> "VigConfig":
        return dataclasses.replace(self, **kw)


# The official code's k schedule of ViG-S and ViG-B (vig_pytorch/vig.py):
# ``[int(x) for x in torch.linspace(9, 18, 16)]``, with max_dilation
# 196 // 18 = 10.
_KNN_RAMP_16 = (9, 9, 10, 10, 11, 12, 12, 13, 13, 14, 15, 15, 16, 16, 17, 18)

# ViG paper variants.
VIG_VARIANTS = {
    "vig_ti_iso": VigConfig("vig_ti_iso", "isotropic", embed_dims=(192,), depths=(12,)),
    "vig_s_iso": VigConfig("vig_s_iso", "isotropic", embed_dims=(320,),
                           depths=(16,), num_knn=_KNN_RAMP_16,
                           max_dilation=10),
    "vig_b_iso": VigConfig("vig_b_iso", "isotropic", embed_dims=(640,),
                           depths=(16,), num_knn=_KNN_RAMP_16,
                           max_dilation=10),
    "vig_ti_pyr": VigConfig(
        "vig_ti_pyr", "pyramid", patch=4, embed_dims=(48, 96, 240, 384),
        depths=(2, 2, 6, 2), reduce_ratios=(4, 2, 1, 1),
    ),
    "vig_s_pyr": VigConfig(
        "vig_s_pyr", "pyramid", patch=4, embed_dims=(80, 160, 400, 640),
        depths=(2, 2, 6, 2), reduce_ratios=(4, 2, 1, 1),
    ),
    "vig_m_pyr": VigConfig(
        "vig_m_pyr", "pyramid", patch=4, embed_dims=(96, 192, 384, 768),
        depths=(2, 2, 16, 2), reduce_ratios=(4, 2, 1, 1),
    ),
    "vig_b_pyr": VigConfig(
        "vig_b_pyr", "pyramid", patch=4, embed_dims=(128, 256, 512, 1024),
        depths=(2, 2, 18, 2), reduce_ratios=(4, 2, 1, 1),
    ),
}


# ---------------------------------------------------------------------------
# Param spec


def _block_spec(d: int, ffn: int):
    return {
        "ln_g": {"scale": spec((d,), ("embed",), init="ones")},
        "fc_in": spec((d, d), ("embed", "mlp")),
        "fc_graph": spec((2 * d, d), ("mlp", "embed")),
        "fc_out": spec((d, d), ("embed", "mlp")),
        "ln_f": {"scale": spec((d,), ("embed",), init="ones")},
        "fc1": spec((d, ffn * d), ("embed", "mlp")),
        "fc2": spec((ffn * d, d), ("mlp", "embed")),
    }


def vig_param_spec(cfg: VigConfig):
    g0 = cfg.base_grid
    n0 = g0 * g0
    p: dict[str, Any] = {
        "stem": spec(
            (cfg.patch * cfg.patch * cfg.in_chans, cfg.embed_dims[0]),
            ("embed", "mlp"),
        ),
        "pos": spec((n0, cfg.embed_dims[0]), ("seq", "embed"), init="normal"),
        "head": spec((cfg.embed_dims[-1], cfg.num_classes), ("embed", "vocab")),
    }
    for si, (d, depth) in enumerate(zip(cfg.embed_dims, cfg.depths)):
        p[f"stage{si}"] = {
            f"block{bi}": _block_spec(d, cfg.ffn_ratio) for bi in range(depth)
        }
        if si + 1 < len(cfg.embed_dims):
            p[f"down{si}"] = spec(
                (4 * d, cfg.embed_dims[si + 1]), ("embed", "mlp")
            )
    return p


# ---------------------------------------------------------------------------
# Forward


def _ln(x, scale):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6) * scale).astype(x.dtype)


def patchify(images: jax.Array, patch: int) -> jax.Array:
    """(B, H, W, C) -> (B, N, patch*patch*C)."""
    b, h, w, c = images.shape
    gh, gw = h // patch, w // patch
    x = images.reshape(b, gh, patch, gw, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, gh * gw, patch * patch * c)


def _pool_conodes(x: jax.Array, grid: int, r: int) -> Optional[jax.Array]:
    """(B, N, D) on a grid -> average-pooled co-nodes (B, N/r^2, D).

    Returns None for r <= 1: co-nodes are the nodes themselves, and
    None is the registry's explicit self-graph marker (DESIGN.md §4).
    """
    if r <= 1:
        return None
    if grid % r:
        raise VigGridError(
            f"co-node pooling needs grid divisible by r={r}; got "
            f"grid={grid} (vig_stage_plans screens this at config time)"
        )
    b, n, d = x.shape
    g2 = grid // r
    xg = x.reshape(b, g2, r, g2, r, d)
    return xg.mean(axis=(2, 4)).reshape(b, g2 * g2, d)


def _downsample(x: jax.Array, grid: int, w: jax.Array) -> jax.Array:
    """2x2 patch-merge + linear projection."""
    if grid % 2:
        raise VigGridError(
            f"2x2 downsample needs an even grid; got grid={grid} "
            f"(vig_stage_plans screens this at config time)"
        )
    b, n, d = x.shape
    g2 = grid // 2
    xg = x.reshape(b, g2, 2, g2, 2, d).transpose(0, 1, 3, 2, 4, 5)
    xg = xg.reshape(b, g2 * g2, 4 * d)
    return xg @ w


def _dilation_for(cfg: VigConfig, global_block: int, m: int,
                  k: Optional[int] = None, *,
                  grid: Optional[int] = None,
                  base_grid: Optional[int] = None) -> int:
    if not cfg.use_dilation:
        return 1
    k = cfg.k if k is None else k
    d = global_block // 4 + 1
    cap = cfg.max_dilation
    if grid is not None and base_grid is not None:
        # Per-cell dilation schedule (DESIGN.md §13/§14): the stride
        # AND its cap ride the same resolution ramp as k, so a
        # high-resolution cell's dilated blocks keep the same
        # *relative* reach across the denser grid; at or below the
        # native grid both scalers return their inputs, so native
        # plans are untouched.
        d = _resolution_dilation(d, grid, base_grid)
        cap = _resolution_dilation(cap, grid, base_grid)
    d = min(d, cap)
    while k * d > m and d > 1:
        d -= 1
    return d


def _resolution_k(k: int, grid: int, base_grid: int) -> int:
    """The resolution-scaled neighbor count: ``n_knn = linspace(k, 2k)``
    in the resolution dimension (the ViG / PVG-DET idiom — more pixels
    per object means each node needs proportionally more neighbors to
    cover the same receptive field). k at the model's native grid,
    ramping linearly to 2k at twice the native grid, clamped to
    [k, 2k]; grids at or below native keep the model's k, so native
    forwards are byte-identical to the pre-multires behavior."""
    if grid <= base_grid:
        return k
    frac = min(1.0, (grid - base_grid) / base_grid)
    return int(round(k * (1.0 + frac)))


def _resolution_dilation(d: int, grid: int, base_grid: int) -> int:
    """The resolution-scaled dilation stride, mirroring
    ``_resolution_k``: d at the model's native grid, ramping linearly
    to 2d at twice the native grid, clamped to [d, 2d]. A dilated
    block's receptive reach is ~k*d node strides — on a denser grid the
    same stride covers a smaller fraction of the image, so the stride
    widens with resolution exactly as the neighbor count does (the
    PVG-DET ramp applied to the dilation schedule). Grids at or below
    native return ``d`` unchanged — native plans stay byte-identical."""
    if grid <= base_grid:
        return d
    frac = min(1.0, (grid - base_grid) / base_grid)
    return int(round(d * (1.0 + frac)))


def _pos_for_grid(pos: jax.Array, base_grid: int, grid: int) -> jax.Array:
    """Resample the learned (base_grid^2, D) positional embedding to a
    serving grid: reshape to 2D, bilinear-resize, flatten — the
    standard ViT/ViG practice for off-native resolutions. Deterministic
    (no RNG, no data dependence), so an engine forward and its B=1
    replay see bit-identical embeddings; a no-op at the native grid."""
    if grid == base_grid:
        return pos
    d = pos.shape[-1]
    pos2d = pos.reshape(base_grid, base_grid, d)
    out = jax.image.resize(pos2d, (grid, grid, d), method="bilinear")
    return out.reshape(grid * grid, d).astype(pos.dtype)


# ---------------------------------------------------------------------------
# Stage pipeline (DESIGN.md §12)
#
# The forward pass is an explicit pipeline of per-stage plans instead of
# an implicit layer loop: every piece of stage geometry a DIGC call
# depends on (grid, co-node pooling, per-block dilation and effective k)
# is derived ONCE here, so the model forward, the functional state
# allocator and the workload accounting all read the same plan — the
# cached-graph buffers in ``DigcState`` are sized by exactly the
# derivation that later writes them.


@dataclasses.dataclass(frozen=True)
class StagePlan:
    """One stage of the ViG pipeline: static geometry + resolved spec."""

    index: int
    depth: int
    grid: int
    r: int
    m: int  # co-nodes per image (grid/r)^2
    spec: DigcSpec  # stage spec, k/dilation still model-owned (k = ks[0])
    dilations: tuple[int, ...]  # per block, after the m-feasibility clamp
    k_effs: tuple[int, ...]  # per block effective neighbor count
    ks: tuple[int, ...]  # per block resolution-scaled k, before the clamps

    @property
    def key(self) -> str:
        """The state key every block of this stage shares."""
        return f"stage{self.index}"

    @property
    def n(self) -> int:
        return self.grid * self.grid


def _block_geometry(cfg: VigConfig, gb: int, m: int,
                    k: Optional[int] = None, *,
                    grid: Optional[int] = None,
                    base_grid: Optional[int] = None) -> tuple[int, int]:
    """(dilation, k_eff) for global block ``gb`` against ``m`` co-nodes
    — the single source of the k/dilation clamps the old layer loop
    applied inline. ``k`` overrides cfg.k (the resolution-scaled
    schedule feeds the stage's scaled k here); ``grid``/``base_grid``
    additionally scale the dilation schedule for off-native cells
    (``_resolution_dilation``), with the same m-feasibility clamps
    applied *after* scaling."""
    k = cfg.k if k is None else k
    dil = _dilation_for(cfg, gb, m, k, grid=grid, base_grid=base_grid)
    k_eff = min(k, m // max(dil, 1)) or 1
    if k_eff * dil > m:
        dil = 1
    return dil, k_eff


def vig_stage_plans(cfg: VigConfig,
                    digc_impl: Union[str, DigcSpec, "VigSchedule", None] = None,
                    *, grid: Optional[int] = None,
                    ) -> tuple[StagePlan, ...]:
    """Materialize the stage pipeline for a model + DIGC choice.

    Each block's k is the config's ``num_knn`` entry for it (a per-block
    schedule wins over the spec's k), else the spec's k; the resolution
    ramp and the clamps then apply to that block's own k (``ks``,
    ``k_effs``).

    ``grid`` is the serving patch grid (default: the config's native
    ``base_grid``) — the resolution-parametric hook: stage grids, m,
    the per-block (dilation, k_eff) clamps and the resolution-scaled
    k and dilation schedules (``_resolution_k`` /
    ``_resolution_dilation``) all derive from it, so one config serves
    any square input whose grid passes the divisibility screen.

    Raises ``VigGridError`` at config time (here, not mid-forward) when
    a stage's grid is not divisible by its reduce ratio or, for any
    stage but the last, by the 2x downsample — naming the stage and
    grid (e.g. 800^2 / patch 16 -> grid 50 -> 25 breaks the second
    downsample of a 4-stage pyramid).
    """
    plans = []
    grid = cfg.base_grid if grid is None else int(grid)
    if grid < 1:
        raise VigGridError(f"serving grid must be >= 1; got {grid}")
    gb = 0
    for si, depth in enumerate(cfg.depths):
        spec = resolve_digc_spec(cfg, digc_impl, stage=si)
        r = cfg.reduce_ratios[si] if si < len(cfg.reduce_ratios) else 1
        if r > 1 and grid % r:
            raise VigGridError(
                f"stage{si}: grid {grid} is not divisible by its "
                f"reduce ratio r={r} (model {cfg.name!r}); serve a "
                f"resolution whose stage grids divide, or drop the "
                f"pooling ratio"
            )
        if si + 1 < len(cfg.depths) and grid % 2:
            raise VigGridError(
                f"stage{si}: grid {grid} is odd but stage{si + 1} "
                f"needs the 2x2 downsample (model {cfg.name!r}); "
                f"serve a resolution divisible through every stage"
            )
        native = cfg.grid_at_stage(si)
        ks = tuple(
            _resolution_k(spec.k if cfg.num_knn is None
                          else cfg.num_knn[gb + bi], grid, native)
            for bi in range(depth)
        )
        spec = spec.replace(k=ks[0])
        m = (grid // max(r, 1)) ** 2
        geo = tuple(
            _block_geometry(cfg, gb + bi, m, ks[bi], grid=grid,
                            base_grid=native)
            for bi in range(depth)
        )
        plans.append(StagePlan(
            index=si, depth=depth, grid=grid, r=r, m=m, spec=spec,
            dilations=tuple(g[0] for g in geo),
            k_effs=tuple(g[1] for g in geo), ks=ks,
        ))
        gb += depth
        if si + 1 < len(cfg.depths):
            grid //= 2
    return tuple(plans)


def resolve_digc_spec(cfg: VigConfig,
                      digc_impl: Union[str, DigcSpec, None],
                      stage: int = 0) -> DigcSpec:
    """Normalize the model's DIGC choice to a DigcSpec.

    A spec that leaves ``k`` unset (the default) inherits cfg.k, so
    passing ``DigcSpec(impl="pallas")`` only picks the implementation;
    an explicit ``k`` in the spec wins over the config. A
    ``VigSchedule`` resolves to its entry for ``stage`` (per-stage
    tuned engine schedules, ``core.tuner.tune_schedule``).
    """
    choice = digc_impl if digc_impl is not None else cfg.digc_impl
    if isinstance(choice, VigSchedule):
        choice = choice.spec_for(stage)
    if isinstance(choice, DigcSpec):
        return choice if choice.k is not None else choice.replace(k=cfg.k)
    return DigcSpec(impl=choice, k=cfg.k)


def grapher_block(bp, x, cfg: VigConfig, grid: int, r: int, dilation: int,
                  digc_spec: Optional[DigcSpec] = None,
                  layer_key: Optional[str] = None,
                  state: Optional[DigcState] = None,
                  reuse_first: bool = True,
                  digc_capture: Optional[list] = None,
                  m_valid: Optional[jax.Array] = None):
    """x (B, N, D) -> ((B, N, D), state); one Grapher + FFN residual
    pair. The second return is the (possibly updated) ``DigcState`` —
    ``None`` when no state was passed.

    Graph construction runs batched through the registry — no per-sample
    closure, no strategy branching; the builder supplies its fused
    aggregation (e.g. the MRConv Pallas kernel) when it has one.
    Construction state across layers and requests is ``state`` (a
    functional ``DigcState`` pytree, keyed by ``layer_key``): stateful
    builders read and return their entry *through* the trace, so warm
    starts work in compiled serving. ``reuse_first`` marks the first
    block of a stage within a forward pass — the gate point of the
    ``"tick"`` stale-graph policy (DESIGN.md §12).

    ``digc_capture`` (a list) collects ``(layer_key, h, cond, idx)``
    per DIGC call — the probe hook the tuner's recall-floor
    verification and the recall-vs-drift bench replay against, and the
    neighbour lists the call built; works under jit when the caller
    returns the captured arrays as outputs.

    ``m_valid`` ((N,) or (B, N) bool) marks live nodes when the batch
    carries N-bucket pad nodes (DESIGN.md §13): pad co-node columns are
    BIG-norm-masked inside DIGC so they never enter a live row's top-k.
    Only meaningful for self-graph stages (r == 1 — pooling would mix
    pad and live nodes); the caller (``vig_forward``) screens that.
    """
    dspec = digc_spec if digc_spec is not None else resolve_digc_spec(cfg, None)
    with jax.named_scope("graph_conv"):
        h = _ln(x, bp["ln_g"]["scale"])
        h = h @ bp["fc_in"]
        cond = _pool_conodes(h, grid, r)  # None = self-graph
    m = cond.shape[1] if cond is not None else h.shape[1]
    k_eff = min(dspec.k, m // max(dilation, 1)) or 1
    if k_eff * dilation > m:
        dilation = 1
    # k/dilation/grid geometry are stage-derived: override whatever the
    # incoming spec carries (pyramid stages shrink the grid every
    # downsample, so a fixed user grid would go stale).
    dspec = dspec.replace(k=k_eff, dilation=dilation).with_grid(grid, grid)
    builder = get_builder(dspec.impl)
    # Centroid warm starts are shared per stage (same co-node geometry):
    # layer l+1 starts from layer l's centroids, the next request from
    # this one's — features drift slowly, so 2 Lloyd iterations suffice.
    # Named scopes (``digc``, ``graph_conv``, ``ffn``, under the caller's
    # ``stage{si}/block{bi}``) only add metadata: the profiler's device
    # ops carry them, and the program's ops and numbers do not change.
    with jax.named_scope("digc"):
        if state is not None:
            idx, state = digc(h, cond, spec=dspec, state=state,
                              state_key=layer_key,
                              reuse_first=reuse_first,
                              m_valid=m_valid)  # (B, N, k)
        else:
            idx = digc(h, cond, spec=dspec, m_valid=m_valid)  # (B, N, k)
    if digc_capture is not None:
        digc_capture.append((layer_key, h, cond, idx))
    aggregate = builder.aggregate if builder.aggregate is not None else mr_aggregate
    with jax.named_scope("graph_conv"):
        agg = aggregate(h, cond if cond is not None else h, idx)
        h = jnp.concatenate([h, agg], axis=-1) @ bp["fc_graph"]
        h = jax.nn.gelu(h) @ bp["fc_out"]
        x = x + h
    with jax.named_scope("ffn"):
        f = _ln(x, bp["ln_f"]["scale"])
        f = jax.nn.gelu(f @ bp["fc1"]) @ bp["fc2"]
        return x + f, state


def run_stage(stage_params, x, cfg: VigConfig, plan: StagePlan, *,
              state: Optional[DigcState] = None,
              digc_capture: Optional[list] = None,
              m_valid: Optional[jax.Array] = None):
    """Run one pipeline stage: ``plan.depth`` Grapher+FFN blocks over a
    fixed grid, each with its own k (``plan.ks``), sharing the stage's
    state key (layer l+1 warm-starts — or, under a reuse policy, serves
    — layer l's graph artifact)."""
    for bi in range(plan.depth):
        with jax.named_scope(f"block{bi}"):
            x, state = grapher_block(
                stage_params[f"block{bi}"], x, cfg, plan.grid, plan.r,
                plan.dilations[bi],
                digc_spec=plan.spec.replace(k=plan.ks[bi]),
                layer_key=plan.key, state=state, reuse_first=(bi == 0),
                digc_capture=digc_capture, m_valid=m_valid,
            )
    return x, state


def vig_forward(params, images, cfg: VigConfig, *,
                digc_impl: Union[str, DigcSpec, "VigSchedule", None] = None,
                state: Optional[DigcState] = None,
                digc_capture: Optional[list] = None,
                valid_mask: Optional[jax.Array] = None):
    """images (B, H, W, C) -> class logits (B, num_classes).

    ``digc_impl`` may be a registered builder name, a full DigcSpec, or
    a ``VigSchedule`` (per-stage tuned specs). The forward is an
    explicit stage pipeline (``vig_stage_plans`` / ``run_stage``,
    DESIGN.md §12): patchify → stem → per-stage Grapher blocks (with
    the graph index treated as a cached, versioned state artifact when
    the spec carries a ``reuse`` policy) → downsample → head.
    Construction state across blocks and requests is ``state``, a
    functional ``DigcState`` (see ``init_vig_state``): the call then
    returns ``(logits, new_state)`` and is fully jit-compatible; blocks
    in a stage share a state key, so layer l+1 warm-starts from layer
    l, and feeding the returned state into the next call warm-starts
    request-to-request *inside* the compiled program. Without it the
    call returns logits only.

    ``digc_capture`` (a list) collects every DIGC call's
    ``(layer_key, nodes, co_nodes, idx)`` — the recall-verification
    probe hook (see ``grapher_block``).

    **Resolution-parametric** (DESIGN.md §13): the serving grid is
    inferred from the image shape — H == W, divisible by ``cfg.patch``
    (``VigGridError`` otherwise) — so one config + param set serves any
    square resolution whose grid passes ``vig_stage_plans``'s screen.
    Off-native grids bilinear-resample the positional embedding
    (``_pos_for_grid``) and scale k per stage (``_resolution_k``); the
    native grid runs byte-identical to the pre-multires forward.

    ``valid_mask`` ((N,) or (B, N) bool) marks live nodes when images
    were zero-padded up to an N-bucket: pad nodes are BIG-norm-masked
    out of every DIGC top-k and excluded from the mean pooling (all
    other compute is node-local). Supported only for single-stage
    models with r == 1 — pooling/downsampling would mix pad and live
    rows — enforced here with a ``VigGridError``.
    """
    b, hh, ww, _ = images.shape
    if hh != ww:
        raise VigGridError(
            f"vig_forward needs square inputs; got H={hh}, W={ww} "
            f"(pad to a square N-bucket upstream)"
        )
    if hh % cfg.patch:
        raise VigGridError(
            f"image size {hh} is not divisible by patch={cfg.patch}"
        )
    grid0 = hh // cfg.patch
    plans = vig_stage_plans(cfg, digc_impl, grid=grid0)
    if valid_mask is not None and (
        len(cfg.depths) > 1 or any(p.r > 1 for p in plans)
    ):
        raise VigGridError(
            f"valid_mask (N-bucket pad nodes) requires a single-stage "
            f"model with r=1 — pooling/downsampling mixes pad and live "
            f"rows; model {cfg.name!r} has depths={cfg.depths}, "
            f"reduce_ratios={cfg.reduce_ratios}"
        )
    with jax.named_scope("stem"):
        x = patchify(images, cfg.patch) @ params["stem"]
        x = x + _pos_for_grid(params["pos"], cfg.base_grid, grid0)
    for plan in plans:
        with jax.named_scope(f"stage{plan.index}"):
            x, state = run_stage(
                params[plan.key], x, cfg, plan, state=state,
                digc_capture=digc_capture, m_valid=valid_mask,
            )
        if plan.index + 1 < len(cfg.depths):
            with jax.named_scope(f"downsample{plan.index}"):
                x = _downsample(x, plan.grid, params[f"down{plan.index}"])
    with jax.named_scope("head"):
        if valid_mask is None:
            pooled = jnp.mean(x, axis=1)
        else:
            mask = jnp.asarray(valid_mask, bool)
            mask = mask[None, :] if mask.ndim == 1 else mask
            w = mask.astype(x.dtype)[..., None]
            pooled = jnp.sum(x * w, axis=1) / jnp.sum(
                w, axis=1
            ).clip(1.0)
        logits = pooled @ params["head"]
    if state is not None:
        return logits, state
    return logits


def init_vig_state(cfg: VigConfig, batch: int,
                   digc_impl: Union[str, DigcSpec, "VigSchedule", None] = None,
                   *, per_slot: bool = False, mesh=None,
                   mesh_axis: str = "data",
                   grid: Optional[int] = None) -> DigcState:
    """Allocate the functional DIGC state for a model + batch size.

    One entry per stage (the key ``grapher_block`` passes): a cold
    step counter always; a (B, C, D) centroid buffer when the stage's
    builder is the cluster tier (C from ``default_cluster_params`` on
    the stage's co-node count — the same derivation the builder uses,
    so shapes line up). The pytree structure this fixes is the compiled
    program's contract: changing batch size or impl means re-init.

    ``per_slot=True`` additionally allocates (batch,) per-row step
    counters on every entry — the multi-tenant serving layout
    (DESIGN.md §9): each batch row is a serving slot whose warm/cold
    validity is tracked independently, so the slot lifecycle
    (``DigcState.take_rows`` / ``put_rows`` / ``reset_rows``) can admit
    and evict tenants without cross-contaminating warm starts.

    ``mesh``/``mesh_axis`` place every entry for sharded construction
    (DESIGN.md §10): a stage whose spec carries a mesh (the ring tier)
    must see its state buffers resident where its ``shard_map`` body
    reads them. A spec that names its own mesh (``spec.mesh``) wins
    over the argument, so a mixed schedule (ring stage next to a
    single-device stage) places each stage where it runs. In a ViG
    forward the co-nodes are this call's own features (never a frozen
    gallery), so ring/blocked stages carry counters only — placement
    matters the moment a caller allocates gallery norms or centroids.

    ``grid`` sizes the state for an off-native serving resolution
    (DESIGN.md §13): the multi-resolution engine allocates one state
    per N-bucket, each sized by the plans that bucket's forward runs.
    """
    from repro.core.builder import reuse_params
    from repro.core.strategies import default_cluster_params

    rows = batch if per_slot else None
    entries = {}
    for plan in vig_stage_plans(cfg, digc_impl, grid=grid):
        spec = plan.spec
        stage_mesh = spec.mesh if spec.mesh is not None else mesh
        stage_axis = (
            spec.axis_name if spec.axis_name is not None else mesh_axis
        )
        alloc = dict(mesh=stage_mesh, axis_name=stage_axis, rows=rows)
        if spec.impl == "cluster":
            n_clusters, _ = default_cluster_params(
                plan.m, spec.n_clusters, spec.n_probe
            )
            alloc["centroids_shape"] = (
                batch, n_clusters, cfg.embed_dims[plan.index]
            )
        policy, _, _ = reuse_params(spec)
        if policy is not None:
            # Cached-graph buffers (DESIGN.md §12), sized by the
            # stage's FIRST block — the same derivation grapher_block
            # applies, so the shapes line up; a later block whose
            # k_eff differs (a num_knn ramp, or a clamp at tiny co-node
            # counts) simply never engages the cache (static shape
            # check in the gate): under a ramp only the blocks of the
            # first block's k share the cached graph.
            alloc["graph_shape"] = (batch, plan.n, plan.k_effs[0])
        entries[plan.key] = state_entry(**alloc)
    return DigcState.init(entries)


def vig_loss_fn(params, batch, cfg: VigConfig):
    logits = vig_forward(params, batch["images"], cfg).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold), {}


def count_digc_work(cfg: VigConfig, *, grid: Optional[int] = None):
    """Per-image DIGC workload (N, M, D, k, dilation) per block — feeds
    the tuner's per-stage rows (``VigServeEngine._stage_rows``). Reads
    the same ``vig_stage_plans`` the forward executes (including, with
    ``grid=``, an off-native serving resolution and its scaled k, and a
    per-block ``num_knn`` schedule: ``k`` is the block's own, before the
    co-node clamps), so the accounting can never drift from the model."""
    out = []
    for plan in vig_stage_plans(cfg, grid=grid):
        d = cfg.embed_dims[plan.index]
        for bi in range(plan.depth):
            out.append({
                "stage": plan.index, "N": plan.n, "M": plan.m, "D": d,
                "k": plan.ks[bi], "dilation": plan.dilations[bi],
            })
    return out
