"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, in one process: the cell's system is built, warmed up and
driven through a short window of its own traffic, and the numbers of a
run are read on a sample of its answers drawn from the seed (the
program's readings). The same sampled requests are then answered by the
control, the plain reference in bfloat16 (one precision step below the
configuration's), and read by the same numbers. Each reading is one
line; the limits go between the program's largest and the control's
smallest. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import device, registry, run as bench_run  # noqa: E402
from chipbench import drive as common  # noqa: E402
from chipbench import spans as spans_mod  # noqa: E402


def readings(name: str, seed: int, seconds: float,
             bench: dict | None = None) -> dict:
    """The program's and the control's numbers for one seed:
    ``{who: (numbers, answers read)}``."""
    bench = registry.benchmark() if bench is None else bench
    cell = registry.cell(bench, name)
    conf = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    entry = registry.module("entries", conf["entry"])
    kind = registry.module("traffic", mix["kind"])
    clock = spans_mod.CompileClock()
    system = entry.System(conf, mix["image_size"], seed)
    pool = common.image_pool(mix, conf["in_chans"], seed)
    bench_run.warm(system, pool, kind.warm_lanes(mix, system.slots), clock)
    kind.drive(system, mix, pool, kind.schedule(mix, seed, seconds), seconds,
               spans_mod.Spans())
    picks = entry.sample_ticks(system.ticks, seed, bench_run.SAMPLE_REQUESTS,
                               system.engine.bucket_for)
    images = [(system.images[u], system.engine.bucket_for(len(lanes)))
              for lanes in picks for u in lanes]
    w = system.weights
    limits = registry.limits(name)
    out = {}
    nums, n = entry.check(system, seed, bench_run.SAMPLE_REQUESTS, limits)
    out["program"] = (nums, n)
    ctl = entry.Control(conf, mix["image_size"], w)
    answers = [(img, *ctl.answer(img), width) for img, width in images]
    out["control"] = (entry.numbers(conf, mix["image_size"], w, answers,
                                    limits), len(answers))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench_run.SRC))
    cell = registry.cell(registry.benchmark(), args.workload)
    try:
        device.require(cell["chips"])
    except device.NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    bench_run.jax_setup()
    for seed in (int(s) for s in args.seeds.split(",")):
        for who, (nums, n) in readings(args.workload, seed,
                                       args.seconds).items():
            print(f"reading {args.workload} seed {seed} {who} answers {n} "
                  + " ".join(f"{k} {v!r}" for k, v in nums.items()),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
