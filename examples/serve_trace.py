"""Trace-replay driver for the SLO-bounded admission scheduler
(DESIGN.md §14).

Replays the seeded Poisson+burst arrival trace (``serve.sched``'s
``arrival_trace``) through a ``VigServeEngine`` under a
``VirtualClock``, twice:

* the exact-size baseline (``buckets=None``, ``slo_ms=0``): every
  arrival wave dispatches immediately at its own batch size;
* the scheduled engine (bucketed, ``slo_ms``): sub-width arrivals
  wait up to their SLO and coalesce into fuller ticks, then the
  served trace re-tunes the bucket set via the arrival-histogram
  optimizer.

Prints per-engine tick/utilization/compile stats and the tuned bucket
set — a deterministic smoke of the whole §14 path (no wall-clock
sleeps: the virtual clock jumps straight to deadlines).

    PYTHONPATH=src python examples/serve_trace.py
    PYTHONPATH=src python examples/serve_trace.py --slo-ms 80 --seed 3
"""

import argparse

import numpy as np
import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.models import vig
from repro.models.module import init_params
from repro.serve.engine import VigServeEngine
from repro.serve.sched import VirtualClock, arrival_trace, replay


def _model(image_size, patch):
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=image_size, patch=patch, embed_dims=(32,), depths=(2,),
        num_classes=10, k=4, digc_impl="blocked",
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _report(tag, eng, ticks):
    s = eng.stats()
    served = sum(t[0] for t in ticks)
    print(f"{tag}:")
    print(f"  requests {served}  ticks {len(ticks)}  "
          f"deferrals {s['deferrals']}")
    print(f"  live lanes {s['live_lanes']}  padded {s['padded_lanes']}  "
          f"util {s['util']:.3f}")
    print(f"  compiled programs {s['compiled_programs']}  "
          f"buckets {s['buckets']}")
    print(f"  prefetch issued/hits {s['prefetch_issued']}"
          f"/{s['prefetch_hits']}  park hits {s['park_hits']}")


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--patch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--arrivals", type=int, default=48)
    ap.add_argument("--slo-ms", type=float, default=120.0)
    ap.add_argument("--bucket-cap", type=int, default=4)
    args = ap.parse_args(argv)

    cfg, params = _model(args.image_size, args.patch)
    rng = np.random.default_rng(args.seed)
    images = {f"t{i}": rng.standard_normal(
        (args.image_size, args.image_size, 3)).astype(np.float32)
        for i in range(args.tenants)}
    arrivals = arrival_trace(seed=args.seed, tenants=args.tenants,
                             poisson_n=args.arrivals)
    print(f"trace: {len(arrivals)} arrivals over "
          f"{arrivals[-1].t_ms:.0f} ms, {args.tenants} tenants")

    clock = VirtualClock()
    exact = VigServeEngine(cfg, params, digc_impl="blocked",
                           autotune=False, buckets=None, clock=clock)
    _report("exact-size baseline (slo_ms=0)",
            exact, replay(exact, arrivals, images, clock=clock))

    clock = VirtualClock()
    sched = VigServeEngine(cfg, params, digc_impl="blocked",
                           autotune=False, slo_ms=args.slo_ms,
                           clock=clock, bucket_cap=args.bucket_cap)
    _report(f"scheduled (slo_ms={args.slo_ms:g}, buckets={sched.buckets})",
            sched, replay(sched, arrivals, images, clock=clock))
    tuned = sched.retune_buckets()
    print(f"  retuned bucket set for this trace: {tuned}")


if __name__ == "__main__":
    main()
