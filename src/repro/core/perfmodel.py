"""The paper's analytical performance model (Table I) + our TPU analogue.

Paper cycle model (per ViG layer DIGC):
    DCM: ceil(N/P_row) * ceil(M/P_col) * ceil(D/P_vec)
    LSM: ceil(N/P_sort) * (m * ceil(log2 m))
    GMM: N * k * ceil(log2 Q)
    NSM: ceil(N/Q) * k
Reference config (ViG-Tiny): N=M=196, D=192, k=8, d=2, m=28,
P_row=P_col=14, P_vec=8, P_sort=7, Q=7 -> Table I reports
DCM=4704, LSM=3920, GMM=4704, NSM=224.

The TPU model estimates the same quantities for the Pallas kernel:
MXU cycles for the -2XY^T tile matmuls, VPU cycles for the running
top-kd merge, HBM bytes moved (the paper's DDR-traffic claim).
"""

from __future__ import annotations

import math
from dataclasses import dataclass


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def clog2(v: int) -> int:
    return max(1, math.ceil(math.log2(max(v, 2))))


@dataclass(frozen=True)
class FPGAConfig:
    """Static parallelism of the paper's accelerator."""

    p_row: int = 14
    p_col: int = 14
    p_vec: int = 8
    p_sort: int = 7
    q: int = 7
    m_part: int = 28  # partition size m


def fpga_cycles(n: int, m: int, d: int, k: int, cfg: FPGAConfig = FPGAConfig()):
    """Paper Table I formulas, verbatim."""
    dcm = ceil_div(n, cfg.p_row) * ceil_div(m, cfg.p_col) * ceil_div(d, cfg.p_vec)
    lsm = ceil_div(n, cfg.p_sort) * (cfg.m_part * clog2(cfg.m_part))
    gmm = n * k * clog2(cfg.q)
    nsm = ceil_div(n, cfg.q) * k
    return {"DCM": dcm, "LSM": lsm, "GMM": gmm, "NSM": nsm}


def fpga_latency_ms(n: int, m: int, d: int, k: int, clock_hz: float = 600e6,
                    cfg: FPGAConfig = FPGAConfig()) -> float:
    """Pipeline latency estimate: modules are deeply pipelined, so total
    time ~ max stage (streaming) + fill; we report the sum as the
    conservative serial bound (matches the paper's per-module table)."""
    cyc = fpga_cycles(n, m, d, k, cfg)
    return sum(cyc.values()) / clock_hz * 1e3


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s of interchip interconnect per chip over its
# four ICI links (50 GB/s per link, the bandwidth one collective hop
# sees). A device that is not listed has no peaks: ``device_peaks``
# raises rather than guess.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9,
                    "ici_link_bw": 1600e9 / 8 / 4},
}
TARGET_DEVICE_KIND = "TPU v5 lite"  # the chip the kernels are tiled for
TARGET_PEAKS = DEVICE_PEAKS[TARGET_DEVICE_KIND]  # model projections


def device_peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (bytes/s, FLOP/s),
    for a number reported against a run on that chip."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add "
            "them to perfmodel.DEVICE_PEAKS with their source"
        ) from None


@dataclass(frozen=True)
class TPUConfig:
    """Single-chip constants of the target TPU (``TARGET_PEAKS``)."""

    peak_flops: float = TARGET_PEAKS["bf16_flops"]  # bf16 FLOP/s per chip
    hbm_bw: float = TARGET_PEAKS["hbm_bw"]  # bytes/s
    vpu_lanes: int = 8 * 128  # f32 lanes per cycle (one VPU op = 1024 elems)
    clock_hz: float = 940e6
    vmem_bytes: int = 128 * 1024 * 1024


def digc_flops(n: int, m: int, d: int) -> int:
    """FLOPs for the distance computation (the MXU term dominates)."""
    return 2 * n * m * d  # -2XY^T matmul; norm terms are O(ND + MD)


def digc_hbm_bytes(n: int, m: int, d: int, kd: int, *, block_n: int,
                   streaming: bool, with_pos_bias: bool = False,
                   dtype_bytes: int = 4) -> int:
    """External-memory traffic. The paper's central claim: streaming keeps
    traffic at O(ND + MD + N*kd) while the naive path writes + re-reads
    the N*M distance matrix."""
    x_bytes = n * d * dtype_bytes
    # Y is re-read once per node-block sweep (same as a blocked matmul).
    y_sweeps = ceil_div(n, block_n) if streaming else 1
    y_bytes = m * d * dtype_bytes * y_sweeps
    out_bytes = n * kd * (4 + 4)
    p_bytes = n * m * dtype_bytes if with_pos_bias else 0
    traffic = x_bytes + y_bytes + out_bytes + p_bytes
    if not streaming:
        traffic += 2 * n * m * dtype_bytes  # write + read back D_XY for sort
        traffic += 2 * n * m * (4 + 4)  # sort (dist, idx) pairs through memory
    return traffic


def tpu_digc_estimate(n: int, m: int, d: int, k: int, dilation: int,
                      block_n: int = 128, block_m: int = 256,
                      cfg: TPUConfig = TPUConfig(), *,
                      mxu_bf16: bool = False, packed: bool = False,
                      input_bytes: int = 4, bucket_rounds: int = 0,
                      kernel_merge: str = "legacy"):
    """Roofline-style estimate for the fused Pallas DIGC kernel.

    Variant knobs (the §Perf hillclimb levers, all implemented in
    kernels/digc_topk.py and validated in interpret mode):
      * mxu_bf16: bf16 x bf16 -> fp32 MXU contraction: full 197 TF/s;
        the fp32 path runs the MXU at ~1/4 rate.
      * packed:   single int32 (dist|idx) merge keys: compare-exchange
        is a min/max pair (~1.5 ops/elem/pass) vs the two-array
        predicate+4-select form (~3.5); the legacy extraction passes
        cost ~3 vs ~6 ops/elem/pass for the same reason.
      * input_bytes: 2 when X/Y are stored bf16 in HBM.
      * bucket_rounds r>0 (legacy only): per-tile bucketed pre-reduction
        — r min-pass sweeps fold bm columns into kd buckets, then the
        running merge touches only r*kd survivors. O(r) passes instead
        of O(kd); recall@kd measured >= 0.99 at r=2 on ViG workloads.
      * kernel_merge: "legacy" = kd sequential extraction sweeps over
        (kd + block_m) candidates per tile; "bitonic" = the sorted
        two-level merge — per tile, a local group sort costs
        log2(kd_pad)*(log2(kd_pad)+1)/2 passes over bm elements, the
        tournament reduce a further (log2(kd_pad)+1) amortized passes
        (geometric over rounds), and the GMM fold one (log2(kd_pad)+1)-
        pass merge over kd_pad — so per-element passes drop from
        O(kd) to O(log^2 kd_pad), independent of bm, and stay exact.
    """
    kd = k * dilation
    flops = digc_flops(n, m, d)
    peak = cfg.peak_flops if mxu_bf16 else cfg.peak_flops / 4
    compute_s = flops / peak
    bytes_moved = digc_hbm_bytes(n, m, d, kd, block_n=block_n,
                                 streaming=True, dtype_bytes=input_bytes)
    memory_s = bytes_moved / cfg.hbm_bw
    tiles = ceil_div(n, block_n) * ceil_div(m, block_m)
    if kernel_merge == "bitonic":
        kd_pad = 1 if kd <= 1 else 1 << (kd - 1).bit_length()
        lg = clog2(kd_pad)
        ce_ops = 1.5 if packed else 3.5  # ops per element per CE pass
        local_sort = block_m * (lg * (lg + 1) // 2)  # LSM group sort
        tournament = block_m * (lg + 1)  # geometric sum over rounds
        gmm = kd_pad * (lg + 1)  # one sorted merge per tile
        vpu_ops = tiles * block_n * (local_sort + tournament + gmm) * ce_ops
    elif bucket_rounds > 0:
        sweep = tiles * block_n * block_m * (3 * bucket_rounds - 1)
        fine = tiles * kd * block_n * (kd + bucket_rounds * kd) * 3
        vpu_ops = sweep + fine
    else:
        ops_per_elem = 3 if packed else 6
        vpu_ops = tiles * kd * block_n * (kd + block_m) * ops_per_elem
    merge_s = vpu_ops / (cfg.vpu_lanes * cfg.clock_hz)
    naive_bytes = digc_hbm_bytes(n, m, d, kd, block_n=block_n,
                                 streaming=False, dtype_bytes=input_bytes)
    return {
        "flops": flops,
        "compute_s": compute_s,
        "hbm_bytes": bytes_moved,
        "memory_s": memory_s,
        "merge_s": merge_s,
        "bound": max(
            [("compute", compute_s), ("memory", memory_s), ("merge", merge_s)],
            key=lambda t: t[1],
        )[0],
        "latency_s": max(compute_s, memory_s, merge_s),
        "naive_hbm_bytes": naive_bytes,
        "traffic_saving": naive_bytes / bytes_moved,
    }


def vig_resolution_to_nodes(resolution: int, patch: int = 16, reduction: int = 1) -> int:
    side = resolution // patch
    n = side * side
    return n // (reduction * reduction)


def kernel_tile_defaults(
    n: int, m: int, d: int, kd: int,
    vmem_bytes: int = TPUConfig().vmem_bytes,
) -> tuple[int, int]:
    """Workload-adaptive default (block_n, block_m) for the Pallas kernel.

    Replaces the old hard-coded 128x256: pick the largest MXU-aligned
    tile whose per-instance working set (block_n*D + block_m*D +
    block_n*block_m + 2*block_n*kd floats) fits a double-buffered VMEM
    budget, preferring wider co-node tiles (fewer streaming steps, the
    merge runs once per tile) then taller query tiles.
    """
    budget = vmem_bytes // 8  # double-buffered pipeline, headroom
    best = (128, 256)
    best_score = -1.0
    for bn in (128, 256, 512):
        if bn > max(ceil_div(n, 8) * 8, 8):
            continue
        for bm in (256, 512, 1024, 2048):
            if bm > ceil_div(m, 128) * 128:
                continue
            work = (bn * d + bm * d + bn * bm + 2 * bn * kd) * 4
            if work > budget:
                continue
            score = bm * 2 + bn  # wider co-node tiles first
            if score > best_score:
                best, best_score = (bn, bm), score
    return best


# ---------------------------------------------------------------------------
# XLA streaming-engine cost model (tuner priors)

# Per-backend throughput constants (seconds per unit). These are only
# used to *rank* tile configurations before measurement refines them
# (core/tuner.py), so rough magnitudes suffice. CPU: fitted to the
# measured CPU decomposition (gemm ~40 GFLOP/s, lax.top_k ~9 ns per
# candidate row-element, fused elementwise lane ~1 ns, tile
# materialization ~0.15 ns/byte). TPU: fitted to jitted ``stream_topk``
# calls on a v5e (PERF.md §6): a ``select_topkd`` round costs 19-23 ns
# per row over G + 2w = 71-72 lanes; the one-``lax.top_k``-per-tile
# path 0.3 ns per candidate row-element at W = 196 and 1.2-1.9 ns at
# M = 784 in four tiles of 256, final merge included.
_ENGINE_CONSTANTS = {
    "cpu": dict(gemm=1 / 40e9, topk=9e-9, lane=1e-9, byte=1.5e-10),
    "tpu": dict(gemm=1 / 49e12, topk=6e-10, lane=2.7e-10, byte=1.2e-12),
}


def engine_cost_estimate(
    n: int,
    m: int,
    d: int,
    kd: int,
    *,
    b: int = 1,
    block_n: int | None = None,
    block_m: int | None = None,
    merge: str = "topk",
    fuse_norms: bool = False,
    mxu_bf16: bool = False,
    backend: str = "cpu",
    select_group_w: int = 32,
) -> dict:
    """Analytical cost of one ``stream_topk`` call (seconds, by term).

    Mirrors the engine's actual dataflow: a (block_n x block_m) tile
    grid, a DCM contraction + tile assembly per tile, and the selected
    LSM/GMM merge. ``topk`` (the engine's default) costs one
    ``lax.top_k`` over every tile's columns; ``select`` one build pass
    over each tile plus kd O(G + w) rounds; both then one ``lax.top_k``
    over the tiles' survivors when there is more than one co-node tile.
    ``packed`` costs a pack pass plus kd min/mask passes.
    """
    c = _ENGINE_CONSTANTS.get(backend, _ENGINE_CONSTANTS["cpu"])
    bn = n if block_n is None else min(block_n, n)
    bm = m if block_m is None else min(block_m, m)
    nb_n = ceil_div(n, bn)
    nb_m = ceil_div(m, bm)
    rows = b * nb_n * bn  # padded query rows
    tile_elems = rows * nb_m * bm

    d_eff = d + 2 if fuse_norms else d
    gemm_rate = c["gemm"] / 2 if (mxu_bf16 and backend == "tpu") else c["gemm"]
    gemm_s = 2.0 * tile_elems * d_eff * gemm_rate
    # Tile assembly (norm adds + masks) reads/writes the tile unless the
    # norms were folded into the contraction.
    assembly_s = tile_elems * 4 * c["byte"] * (1 if fuse_norms else 3)

    kw = min(kd, bm)  # survivors per tile
    final = 0.0 if nb_m == 1 else rows * nb_m * kw * c["topk"]
    if merge == "select":
        w = min(select_group_w, bm)
        groups = ceil_div(bm, w)
        build = tile_elems * c["lane"]
        rounds = rows * nb_m * kw * (groups + 2 * w) * c["lane"]
        merge_s = build + rounds + final
    elif merge == "packed":
        # Bitonic two-level merge (core/packedkey networks): group sort
        # + tournament + sorted fold, O(log^2 kd_pad) passes per elem.
        kd_pad = 1 if kd <= 1 else 1 << (kd - 1).bit_length()
        lg = clog2(kd_pad)
        pack = tile_elems * 2 * c["lane"]
        passes = rows * nb_m * (
            bm * (lg * (lg + 1) // 2 + lg + 1) + kd_pad * (lg + 1)
        ) * 1.5 * c["lane"]
        merge_s = pack + passes
    else:  # "topk"
        merge_s = tile_elems * c["topk"] + final

    # Per-tile dispatch overhead (scan step launch, slices, transposes).
    overhead_s = nb_n * nb_m * 50e-6 if backend == "cpu" else 0.0
    # Live-tile footprint: tiles that overflow the cache budget (CPU
    # LLC / TPU VMEM headroom) pay re-read traffic on every merge pass.
    live_tile_bytes = b * bn * bm * 4
    budget = 24e6 if backend == "cpu" else 64e6
    spill_s = max(0.0, live_tile_bytes - budget) * nb_n * nb_m * 4 * c["byte"]
    total = gemm_s + assembly_s + merge_s + overhead_s + spill_s
    return {
        "gemm_s": gemm_s,
        "assembly_s": assembly_s,
        "merge_s": merge_s,
        "overhead_s": overhead_s,
        "spill_s": spill_s,
        "total_s": total,
        "live_tile_bytes": live_tile_bytes,
    }


# Interpret-mode emulation constants (fitted to CPU wall-clock): each
# grid program pays a python/XLA dispatch, plus per-element emulated
# vector work. Huge relative to the engine on purpose — the prior must
# keep interpret-mode kernel configs out of the measured top-N on CPU
# while letting compiled TPU configs compete on roofline terms.
_INTERPRET_PROGRAM_S = 2e-3
_INTERPRET_ELEM_S = 2e-8


def kernel_cost_estimate(
    n: int,
    m: int,
    d: int,
    kd: int,
    *,
    b: int = 1,
    block_n: int = 128,
    block_m: int = 256,
    kernel_merge: str = "bitonic",
    packed: bool = False,
    mxu_bf16: bool = False,
    backend: str = "cpu",
    interpret: bool | None = None,
) -> dict:
    """Analytical cost of one fused-kernel DIGC call (tuner priors).

    The engine/kernel choice is a *measured* decision (core/tuner.py);
    this prior only has to rank sensibly: on a TPU backend the cost is
    the roofline ``tpu_digc_estimate`` scaled by batch, everywhere else
    the interpret-mode emulation penalty dominates by construction.
    """
    if interpret is None:
        interpret = backend != "tpu"
    n_pad = ceil_div(n, block_n) * block_n
    m_pad = ceil_div(m, block_m) * block_m
    if interpret:
        programs = b * ceil_div(n, block_n) * ceil_div(m, block_m)
        total = (programs * _INTERPRET_PROGRAM_S
                 + b * n_pad * m_pad * _INTERPRET_ELEM_S)
        return {"total_s": total, "interpret": True, "bound": "interpret"}
    est = tpu_digc_estimate(
        n_pad, m_pad, d, kd, 1, block_n=block_n, block_m=block_m,
        mxu_bf16=mxu_bf16, packed=packed, kernel_merge=kernel_merge,
    )
    return {
        "total_s": est["latency_s"] * b,
        "interpret": False,
        "bound": est["bound"],
    }
