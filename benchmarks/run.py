# One function per paper table. Print ``name,us_per_call,derived`` CSV
# and dump the rows to BENCH_digc.json (perf trajectory record).
import argparse
import json
import re
import sys
from pathlib import Path

from benchmarks.common import ROWS, dump_json, header
from benchmarks import (
    bench_table1_cycles,
    bench_table2_resources,
    bench_table3_digc_runtime,
    bench_table4_e2e,
    bench_fig1_fraction,
    bench_kernel,
    bench_serve,
    bench_strategies,
)
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "table1": bench_table1_cycles.run,
    "table2": bench_table2_resources.run,
    "table3": bench_table3_digc_runtime.run,
    "table4": bench_table4_e2e.run,
    "fig1": bench_fig1_fraction.run,
    "kernel": bench_kernel.run,
    "serve": bench_serve.run,
    "strategies": bench_strategies.run,
}


# Per-suite kwargs for the CI smoke mode: exercise every harness code
# path (timing loops, tuner, JSON dump) at toy workloads in ~a minute.
SMOKE_ARGS = {
    "table3": dict(resolutions=(256,), iters=1),
    "table4": dict(res=128, depth=1),
    "fig1": dict(resolutions=(256,), depth=1),
    "kernel": dict(smoke=True),
    "serve": dict(smoke=True),
    "strategies": dict(smoke=True),
}


# Rows the regression gate watches: the guard-overhead ratio and every
# stale-graph, multi-resolution and admission-scheduler warm row
# (absolute us and speedup ratios alike).
_REGRESS_RE = re.compile(
    r"^serve/(guarded_overhead_warm$"
    r"|(stale|multires|sched)(_.*)?(_warm_us|_warm)$)"
)
_REGRESS_RATIO = 1.15


def _workload_n(derived: str):
    m = re.search(r"\bN=(\d+)", derived or "")
    return m.group(1) if m else None


def check_regress(baseline_path: str) -> list[str]:
    """Compare this run's watched rows against the committed record.

    A ``*_us`` row regresses when it got slower by more than
    ``_REGRESS_RATIO``; a speedup/overhead ratio row regresses when the
    speedup shrank (or overhead grew) past the same ratio. Rows only
    compare against a baseline row at the *same workload* (the ``N=``
    tag in the derived column) — smoke runs use toy shapes, so their
    rows exercise the gate's mechanics without false alarms against
    the committed full-resolution record."""
    path = Path(baseline_path)
    if not path.exists():
        print(f"# check-regress: no baseline at {path}, skipped",
              flush=True)
        return []
    base = {
        r["name"]: r for r in
        json.loads(path.read_text()).get("rows", [])
    }
    failures = []
    for name, value, derived in ROWS:
        if not _REGRESS_RE.match(name) or name not in base:
            continue
        ref = base[name]
        if _workload_n(derived) != _workload_n(ref.get("derived", "")):
            continue
        want = float(ref["us_per_call"])
        if name.endswith("_us"):
            bad = value > want * _REGRESS_RATIO
            direction = "slower"
        elif "overhead" in name:
            bad = value > want * _REGRESS_RATIO
            direction = "more overhead"
        else:  # speedup rows: smaller is worse
            bad = value < want / _REGRESS_RATIO
            direction = "less speedup"
        if bad:
            failures.append(
                f"{name}: {value:.3f} vs baseline {want:.3f} "
                f"({direction} than the {_REGRESS_RATIO}x gate)"
            )
    return failures


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=list(SUITES))
    ap.add_argument("--fast", action="store_true",
                    help="smaller resolutions for quick runs")
    ap.add_argument("--smoke", action="store_true",
                    help="toy workloads, 1 iter: CI harness exercise "
                         "(not a perf record)")
    ap.add_argument("--json", default="BENCH_digc.json",
                    help="output JSON path ('' disables)")
    ap.add_argument("--check-regress", action="store_true",
                    help="fail if serve/guarded_overhead_warm or any "
                         "serve/{stale,multires,sched}_* warm row "
                         "regresses >"
                         f"{_REGRESS_RATIO}x vs the committed "
                         "BENCH_digc.json (same-workload rows only)")
    args = ap.parse_args()
    if args.smoke and args.json == "BENCH_digc.json":
        args.json = ""  # never overwrite the perf record with smoke rows
    header()
    for name in args.only:
        fn = SUITES[name]
        if args.smoke:
            fn(**SMOKE_ARGS.get(name, {}))
        elif args.fast and name == "table3":
            fn(resolutions=(256, 512), iters=1)
        elif args.fast and name == "fig1":
            fn(resolutions=(256,))
        else:
            fn()
    if args.check_regress:
        failures = check_regress("BENCH_digc.json")
        if failures:
            for f in failures:
                print(f"# REGRESSION {f}", flush=True)
            sys.exit(1)
        print("# check-regress: ok", flush=True)
    if args.json:
        path = dump_json(args.json, suites=args.only)
        print(f"# wrote {path}", flush=True)


if __name__ == '__main__':
    main()
