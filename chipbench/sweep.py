"""Find the knee of an open-loop cell: the highest rate it sustains.

    python3 chipbench/sweep.py --workload <cell> --rates 40,80,120 --seconds 10

One system, warmed once, is driven at each rate in turn with the cell's
own traffic at that rate. A rate is sustained when every request due in
the window is answered and the queue does not grow over the window: the
mean latency of the window's last quarter of requests is under twice
that of its first quarter. The cell's rate is then set, by hand, to
about 0.8 x the highest sustained rate, and the sweep recorded in the
traffic file.
"""

from __future__ import annotations

import argparse
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import device, registry, run as bench_run, stats  # noqa: E402
from chipbench import drive as common  # noqa: E402
from chipbench import spans as spans_mod  # noqa: E402

GROWTH = 2.0  # last/first quarter latency that marks a growing queue


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench_run.SRC))
    cell = registry.cell(registry.benchmark(), args.workload)
    try:
        device.require(cell["chips"])
    except device.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    bench_run.jax_setup()
    conf = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    entry = registry.module("entries", conf["entry"])
    kind = registry.module("traffic", mix["kind"])
    clock = spans_mod.CompileClock()
    system = entry.System(conf, mix["image_size"], args.seed)
    pool = common.image_pool(mix, conf["in_chans"], args.seed)
    bench_run.warm(system, pool, kind.warm_lanes(mix, system.slots), clock)
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        at = dict(mix, rate_per_s=rate)
        log, info = kind.drive(system, at, pool,
                               kind.schedule(at, args.seed, args.seconds),
                               args.seconds, spans_mod.Spans())
        while system.queued():  # nothing of this rate reaches the next
            system.step()
        system.forget()
        run = {"log": log, "seconds": args.seconds}
        lat = stats.latencies_ms(run)
        q = max(1, len(lat) // 4)
        head, tail = lat[:q], lat[-q:]
        served = sum(math.isfinite(x) for x in lat)
        growth = (sum(tail) / len(tail)) / max(sum(head) / len(head), 1e-9)
        ticks = stats.window_ticks(run)
        sustained = served == len(lat) and growth < GROWTH
        print(f"rate {rate:g}/s: answered {served}/{len(lat)}, p50 "
              f"{stats.percentile(lat, 50):.2f} ms, p95 "
              f"{stats.percentile(lat, 95):.2f} ms, last/first quarter "
              f"latency {growth:.2f}, ticks {len(ticks)}, mean lanes "
              f"{served / max(len(ticks), 1):.2f}, drain {info['drain_s']:.2f} s, "
              f"{'sustained' if sustained else 'not sustained'}", flush=True)
        if not sustained:
            break
        knee = rate
    print(f"knee {knee} requests/s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
