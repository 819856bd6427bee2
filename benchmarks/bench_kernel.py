"""DIGC kernel microbenchmarks (supplement): blocked-impl block-size
sweep + the §Perf hillclimb progression (modeled TPU terms + measured
recall for the approximate variants). Wall-clock on XLA:CPU; the Pallas
kernel itself is validated in interpret mode (tests)."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import DigcSpec, digc
from repro.core.perfmodel import TPUConfig, device_peaks, tpu_digc_estimate
from benchmarks.common import emit, timeit


def _hillclimb():
    """EXPERIMENTS.md §Perf Cell 1, regenerated: modeled terms at the
    paper's largest workload (ViG @ 2048^2)."""
    w = dict(n=16384, m=16384, d=192, k=8, dilation=2)
    iters = [
        ("K0_baseline", {}),
        ("K1_packed", dict(packed=True)),
        ("K2_bf16_mxu", dict(packed=True, mxu_bf16=True)),
        ("K3_bf16_hbm", dict(packed=True, mxu_bf16=True, input_bytes=2)),
        ("K4_big_blocks", dict(packed=True, mxu_bf16=True, input_bytes=2,
                               block_n=512, block_m=1024)),
        ("K5_bucketed_r2", dict(packed=True, mxu_bf16=True, input_bytes=2,
                                block_n=512, block_m=1024, bucket_rounds=2)),
        # PR 6: sorted two-level merge (bitonic LSM + single GMM pass).
        # K6 is the *exact* fp32 form at default tiles; K7 stacks it on
        # the packed/bf16/big-block pipeline it was designed for.
        ("K6_bitonic_exact", dict(kernel_merge="bitonic")),
        ("K7_bitonic_packed", dict(kernel_merge="bitonic", packed=True,
                                   mxu_bf16=True, input_bytes=2,
                                   block_n=512, block_m=1024)),
    ]
    base = None
    for name, kw in iters:
        e = tpu_digc_estimate(**w, **kw)
        base = base or e["latency_s"]
        mxu = e["flops"] / TPUConfig().peak_flops / e["latency_s"]
        emit(f"kernel/{name}_us", e["latency_s"] * 1e6,
             f"bound={e['bound']};speedup={base/e['latency_s']:.2f}x;mxu_frac={mxu:.3f}")


def _bucketed_recall(n=2048):
    from repro.kernels import ref as kref

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, 192)), jnp.float32)
    _, i_ref = kref.digc_reference(x, x, kd=16)
    a = np.asarray(i_ref)
    for rounds in (1, 2, 3):
        spec = DigcSpec(impl="pallas", k=16, block_n=128, block_m=256,
                        packed=True, bucket_rounds=rounds)
        i_b = digc(x, spec=spec)
        b = np.asarray(i_b)
        rec = np.mean([len(set(a[i]) & set(b[i])) / 16 for i in range(n)])
        emit(f"kernel/bucketed_r{rounds}_recall", rec * 100,
             f"recall@16 percent, N={n} self-graph (registry pallas spec)")


def _merge_ablation(x, k, iters=2):
    """Engine merge-strategy sweep at a fixed tile config: the LSM/GMM
    realization is the lever the block_m sweep above cannot move."""
    n, d = x.shape[-2], x.shape[-1]
    for merge in ("topk", "select", "packed"):
        spec = DigcSpec(impl="blocked", k=k, block_m=1024, merge=merge)
        fn = jax.jit(lambda a, s=spec: digc(a, spec=s))
        t = timeit(fn, x, iters=iters)
        emit(f"kernel/engine_merge_{merge}_us", t * 1e6,
             f"N={n};D={d};block_m=1024")


def _group_w_ablation(x, k, iters=2):
    """select-merge group width at large block_m (ROADMAP: does a
    two-word 64-lane mask beat the one-word 32-lane default when each
    tile holds thousands of candidates?). Wider groups halve the
    per-round group-min reduction but double the winning-group gather
    and pay a second mask word."""
    n, d = x.shape[-2], x.shape[-1]
    bm = min(4096, n)
    base = None
    for w in (32, 64):
        spec = DigcSpec(impl="blocked", k=k, block_m=bm, merge="select",
                        group_w=w)
        fn = jax.jit(lambda a, s=spec: digc(a, spec=s))
        # The w32/w64 gap is ~25% on CPU: needs more samples than the
        # block-size sweep to stay out of the noise floor.
        t = timeit(fn, x, warmup=2, iters=max(3, iters))
        base = base or t
        emit(f"kernel/select_groupw{w}_us", t * 1e6,
             f"N={n};D={d};block_m={bm};speedup_vs_w32={base/t:.2f}x")


def _merge_sweep(smoke: bool = False, iters=2):
    """Kernel merge-strategy sweep: measured wall-clock (interpret mode
    off-TPU: the CPU floor) plus the modeled TPU bound/mxu_frac for the
    same config. On a TPU the row also carries the measured MXU share
    against that chip's published peak (an unlisted chip raises)."""
    dev = jax.devices()[0]
    peaks = device_peaks(dev.device_kind) if dev.platform == "tpu" else None
    n = 256 if smoke else 1024
    kd, bn, bm = 16, 128, 256  # bm % kd == 0, bm // kd >= 2
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((n, 192)), jnp.float32)
    variants = [
        ("legacy", dict(kernel_merge="legacy")),
        ("bucket_r2", dict(kernel_merge="legacy", packed=True,
                           bucket_rounds=2)),
        ("bitonic", dict(kernel_merge="bitonic")),
    ]
    for name, kw in variants:
        spec = DigcSpec(impl="pallas", k=kd, block_n=bn, block_m=bm, **kw)
        fn = jax.jit(lambda a, s=spec: digc(a, spec=s))
        t = timeit(fn, x, iters=iters)
        e = tpu_digc_estimate(
            n=n, m=n, d=192, k=kd, dilation=1, block_n=bn, block_m=bm,
            packed=kw.get("packed", False),
            bucket_rounds=kw.get("bucket_rounds", 0),
            kernel_merge=kw["kernel_merge"],
        )
        mxu = e["flops"] / TPUConfig().peak_flops / e["latency_s"]
        mode = ("interpret" if peaks is None else
                f"compiled;measured_mxu_frac="
                f"{e['flops'] / peaks['bf16_flops'] / t:.3f}")
        emit(f"kernel/merge_{name}_us", t * 1e6,
             f"{mode};N={n};kd={kd};bn={bn};bm={bm};"
             f"bound={e['bound']};tpu_model_us={e['latency_s'] * 1e6:.1f};"
             f"mxu_frac={mxu:.3f}")


def run(smoke: bool = False):
    rng = np.random.default_rng(0)
    n, d, k = (512, 192, 9) if smoke else (4096, 192, 9)
    iters = 1 if smoke else 2
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    for bm in (256, 512, 1024):
        spec = DigcSpec(impl="blocked", k=k, block_m=bm)
        fn = jax.jit(lambda a, s=spec: digc(a, spec=s))
        t = timeit(fn, x, iters=iters)
        emit(f"kernel/blocked_bm{bm}_us", t * 1e6, f"N={n};D={d}")
    _merge_ablation(x, k, iters=iters)
    _group_w_ablation(x, k, iters=iters)
    _merge_sweep(smoke, iters=iters)
    _hillclimb()
    _bucketed_recall(n=256 if smoke else 2048)
    return True


if __name__ == "__main__":
    run()
