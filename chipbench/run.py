"""Run one cell of ``BENCHMARK.json`` once, on the chip this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's system from its configuration and seed, warms up the
shapes its traffic uses, drives the traffic for ``--seconds`` on one
thread, then checks a sample of the answers against the plain reference.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics: those of the host clock and the
engine's counters read in the measured window, those of the device in a
profiled window of the same traffic served before it) and ``device``,
and last ``checks``: each number compared beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 1.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

from chipbench import device, registry, spans as spans_mod, stats  # noqa: E402
from chipbench import drive as common  # noqa: E402

SRC = CHECKOUT / "src"
TRACE_DIR = CHECKOUT / ".chipbench_trace"
TRACE_SECONDS = 10.0  # the longest profiled window of a --trace 1 run
SAMPLE_REQUESTS = 16  # answers checked per run, in whole served ticks


def say(*parts) -> None:
    print(*parts, flush=True)


def jax_setup():
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR`` where set, else the fixed
    ``<checkout>/.jax_cache``), with every program kept, so that only a
    cell's first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def profiler_options():
    """Device ops and the host's annotations only: the Python tracer (on
    by default) records every Python call of the engine's host path and
    stretches a tick several times over."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # TraceAnnotation spans
    return opts


def warm(system, pool, lanes, clock) -> None:
    """Serve ``n`` pool images at once for each ``n`` in ``lanes``, after
    one first tick that allocates the engine's state: every shape the
    traffic uses compiles (or loads) here."""
    uid = 10 ** 9
    for n in [lanes[0]] + list(lanes):
        t = time.perf_counter()
        c0 = clock.snapshot()
        for _ in range(n):
            system.submit(uid, pool[uid % len(pool)])
            uid += 1
        while system.queued():
            system.step()
        c1 = clock.snapshot()
        say(f"warm lanes {n}: {time.perf_counter() - t:.3f} s, backend "
            f"compile {c1[0] - c0[0]:.3f} s in {c1[1] - c0[1]} compiles, "
            f"cache hits {c1[2] - c0[2]}")
    system.forget()


def tick_quarters(log, seconds: float) -> list[float]:
    """Mean tick time, in ms, of the serving ticks that started in each
    quarter of the window: a window that warms up or slows down shows."""
    out = []
    for q in range(4):
        lo, hi = q * seconds / 4, (q + 1) * seconds / 4
        ms = [(e - s) * 1e3 for s, e, n in log.ticks if n and lo <= s < hi]
        if ms:
            out.append(sum(ms) / len(ms))
    return out


def traced_window(system, kind, mix: dict, pool, seed: int,
                  seconds: float) -> dict | None:
    """A profiled window of ``seconds`` of the cell's traffic, served
    before the measured window and forgotten after it: the device's busy
    time, top ops and idle gaps, and the ticks served (``ticks``). Even
    with only device ops and annotations recorded, the profiler slows the
    engine's host path, so every host-clock and counter metric is read
    from the untraced window instead."""
    import jax

    from chipbench import trace as trace_mod

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR),
                             profiler_options=profiler_options())
    log, _ = kind.drive(system, mix, pool, kind.schedule(mix, seed, seconds),
                        seconds, spans_mod.Spans(trace=True))
    jax.profiler.stop_trace()
    while system.queued():
        system.step()
    system.forget()
    ev = trace_mod.events(trace_mod.find(str(TRACE_DIR)))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    say("trace planes:", json.dumps(ev["lines"]))
    reduced = trace_mod.reduce(ev)
    ticks = len(stats.window_ticks({"log": log, "seconds": seconds}))
    say(f"traced window: {seconds} s, {ticks} ticks, mean "
        + ", ".join(f"{q:.3f}" for q in tick_quarters(log, seconds))
        + " ms by quarter")
    if reduced is not None:
        reduced["ticks"] = ticks
    return reduced


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             bench: dict | None = None) -> dict:
    """One run of cell ``name``; returns the result object."""
    bench = registry.benchmark() if bench is None else bench
    cell = registry.cell(bench, name)
    conf = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    limits = registry.limits(name)
    entry = registry.module("entries", conf["entry"])
    kind = registry.module("traffic", mix["kind"])
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))

    dev = device.require(cell["chips"])
    t_dev = time.perf_counter()
    jax_setup()
    import jax

    clock = spans_mod.CompileClock()
    system = entry.System(conf, mix["image_size"], seed)
    t_sys = time.perf_counter()
    pool = common.image_pool(mix, conf["in_chans"], seed)
    sched = kind.schedule(mix, seed, seconds)
    t_pool = time.perf_counter()
    warm(system, pool, kind.warm_lanes(mix, system.slots), clock)
    t_warm = time.perf_counter()
    reduced = None
    if trace:
        reduced = traced_window(system, kind, mix, pool, seed,
                                min(seconds, TRACE_SECONDS))
    counters_start = system.counters()
    gc.collect()
    gc.freeze()
    c0 = clock.snapshot()
    log, info = kind.drive(system, mix, pool, sched, seconds,
                           spans_mod.Spans())
    c1 = clock.snapshot()
    counters_end = info["counters_end"]
    gc.unfreeze()
    setup_s = info["t0"] - T0
    say(f"setup_s {setup_s:.3f}: device {t_dev - T0:.3f} s, weights and "
        f"engine {t_sys - t_dev:.3f} s, images and schedule "
        f"{t_pool - t_sys:.3f} s, warm-up {t_warm - t_pool:.3f} s, "
        f"backend compile {c0[0]:.3f} s in {c0[1]} compiles, "
        f"cache hits {c0[2]}")
    say(f"window: {info['window_s']:.3f} s, drain {info['drain_s']:.3f} s, "
        f"{len(log.ticks)} ticks, compiles inside {c1[1] - c0[1]} "
        f"({c1[0] - c0[0]:.3f} s)")
    quarters = tick_quarters(log, seconds)
    if quarters:
        say("tick ms by quarter of the window: "
            + ", ".join(f"{q:.3f}" for q in quarters))
    late = sorted(r["sub"] - r["due"] for r in log.req.values())
    if late and kind.SERVING == "online":
        say(f"generator lateness: median {late[len(late) // 2] * 1e3:.3f} ms, "
            f"max {late[-1] * 1e3:.3f} ms over {len(late)} requests")
    say("counters at window start", json.dumps(counters_start))
    say("counters at window end", json.dumps(counters_end))
    devices = jax.devices()[:cell["chips"]]
    dev["memory_peak_bytes"] = device.memory_peak_bytes(devices)

    from chipbench import flops

    run = {"log": log, "seconds": float(seconds), "setup_s": setup_s,
           "counters_start": counters_start, "counters_end": counters_end,
           "trace": reduced,
           "flops_per_image": flops.per_image(conf, mix["image_size"]),
           "peak_flops": device.peaks(dev["kind"])["bf16_flops"]}
    if kind.SERVING == "online":
        # every request due inside the window
        attempted = [r for r in log.req.values() if r["due"] < seconds]
    else:
        # every request a tick finished inside the window
        attempted = [r for r in log.req.values()
                     if r["done"] is not None and r["done"] <= seconds]
    failed = sum(1 for r in attempted if not r["ok"])

    metrics = {}
    for m in registry.metrics_for(bench, name, trace):
        value = registry.module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_check = time.perf_counter()
    nums, checked = entry.check(system, seed, SAMPLE_REQUESTS, limits)
    say(f"checked {checked} answers in {time.perf_counter() - t_check:.3f} s")
    checks = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    correct = (checked > 0 and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    out = {"correct": correct, "attempted": len(attempted), "failed": failed,
           "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chipbench: no program under {SRC}", file=sys.stderr)
        return 2
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except device.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
