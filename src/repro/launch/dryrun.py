import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on
the production meshes and record memory / cost / collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun \
        [--arch ID ...] [--shape ID ...] [--multi-pod | --single-pod | --both]
        [--out results/dryrun] [--force]

The 512 placeholder CPU devices exist ONLY in this process (the env var
above is set before any jax import). Results are cached per cell as
JSON so reruns resume where they stopped.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, cell_supported, get_config  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import make_cell  # noqa: E402
from repro.models.module import use_mesh  # noqa: E402


def _compile_cell(arch, shape_id, mesh, cfg):
    cell = make_cell(arch, shape_id, mesh, cfg=cfg)
    with use_mesh(mesh, cell["rules"]):
        lowered = jax.jit(
            cell["fn"], in_shardings=cell["in_shardings"]
        ).lower(*cell["args"])
        compiled = lowered.compile()
    return compiled


def _measure(compiled):
    hlo = compiled.as_text()
    cost = compiled.cost_analysis()
    coll = rl.parse_collectives(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "hbm_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(coll["total_bytes"]),
        "coll_by_kind": coll["bytes_by_kind"],
        "coll_counts": coll["counts"],
    }


def _depth_unit(cfg):
    """(unit size in layers, depths for the two probe compiles)."""
    if cfg.family == "hybrid":
        u = len(cfg.hybrid.pattern)
        return u, (u, 2 * u)
    return 1, (2, 4)


def _with_depth(cfg, n_layers):
    kw = {"num_layers": n_layers, "scan_layers": False}
    return cfg.replace(**kw)


def _extrapolate(base: dict, probe_hi: dict, d_lo: int, d_hi: int,
                 full_layers: int, unit: int) -> dict:
    """Linear-in-depth extrapolation of per-device roofline terms.

    XLA's HloCostAnalysis counts while-loop bodies once, so the
    full-depth scanned compile under-reports flops. The two *unrolled*
    probe compiles at depths d_lo < d_hi give the exact per-layer cost;
    totals at the real depth follow linearly (layer costs are
    depth-independent by construction)."""
    out = {}
    units_lo = d_lo / unit
    units_hi = d_hi / unit
    units_full = full_layers / unit
    for key in ("flops", "hbm_bytes", "collective_bytes"):
        per_unit = (probe_hi[key] - base[key]) / (units_hi - units_lo)
        out[key] = base[key] + per_unit * (units_full - units_lo)
    return out


def run_cell(arch: str, shape_id: str, *, multi_pod: bool, out_dir: Path,
             force: bool = False, cfg=None, tag: str = "",
             probes: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    name = f"{arch}__{shape_id}__{mesh_name}{tag}"
    out_path = out_dir / f"{name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = cfg or get_config(arch)
    ok, why = cell_supported(cfg, shape_id)
    rec = {
        "arch": arch, "shape": shape_id, "mesh": mesh_name,
        "kind": SHAPES[shape_id][2], "seq_len": SHAPES[shape_id][0],
        "global_batch": SHAPES[shape_id][1],
    }
    if not ok:
        rec.update({"status": "skipped", "reason": why})
        _write(out_path, rec)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = mesh.devices.size
        # 1) full-depth scanned compile: proves the production config
        #    lowers+compiles and yields the true memory footprint.
        compiled = _compile_cell(arch, shape_id, mesh, cfg)
        mem = compiled.memory_analysis()
        full_meas = _measure(compiled)
        del compiled

        # 2) two unrolled probe compiles -> exact per-layer terms.
        if probes:
            unit, (d_lo, d_hi) = _depth_unit(cfg)
            lo = _measure(_compile_cell(arch, shape_id, mesh, _with_depth(cfg, d_lo)))
            hi = _measure(_compile_cell(arch, shape_id, mesh, _with_depth(cfg, d_hi)))
            terms = _extrapolate(lo, hi, d_lo, d_hi, cfg.num_layers, unit)
        else:
            terms = {k: full_meas[k] for k in
                     ("flops", "hbm_bytes", "collective_bytes")}

        roof_terms = {
            f"{k}_s": v for k, v in rl.roofline_terms(
                terms["flops"], terms["hbm_bytes"],
                terms["collective_bytes"]).items()
        }
        bound = max(
            ("compute", "memory", "collective"),
            key=lambda k: roof_terms[f"{k}_s"],
        )
        mf = rl.model_flops(cfg, rec["kind"], rec["seq_len"],
                            rec["global_batch"], n_chips)
        rec.update({
            "status": "ok",
            "compile_s": round(time.time() - t0, 1),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            },
            "per_device": terms,
            "per_device_scanned_raw": {
                k: full_meas[k] for k in
                ("flops", "hbm_bytes", "collective_bytes")
            },
            "coll_by_kind": full_meas["coll_by_kind"],
            "roofline": {**roof_terms, "bound": bound},
            "model_flops_per_chip": mf,
            "useful_flop_frac": (mf / terms["flops"]) if terms["flops"] else None,
        })
    except Exception as e:  # record failures — they are bugs to fix
        rec.update({
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
            "compile_s": round(time.time() - t0, 1),
        })
    _write(out_path, rec)
    return rec


def _write(path: Path, rec: dict):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=1, default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=list(ARCH_IDS))
    ap.add_argument("--shape", nargs="*", default=list(SHAPES))
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    pods = []
    if args.single_pod or not args.multi_pod:
        pods.append(False)
    if args.multi_pod or not args.single_pod:
        pods.append(True)

    out_dir = Path(args.out)
    failures = 0
    for arch in args.arch:
        for shape_id in args.shape:
            for multi_pod in pods:
                t0 = time.time()
                rec = run_cell(arch, shape_id, multi_pod=multi_pod,
                               out_dir=out_dir, force=args.force)
                jax.clear_caches()
                status = rec["status"]
                if status == "error":
                    failures += 1
                    print(f"[FAIL] {arch} {shape_id} mp={multi_pod}: "
                          f"{rec['error']}", flush=True)
                else:
                    extra = ""
                    if status == "ok":
                        r = rec["roofline"]
                        extra = (f" bound={r['bound']}"
                                 f" c={r['compute_s']:.2e}s"
                                 f" m={r['memory_s']:.2e}s"
                                 f" x={r['collective_s']:.2e}s"
                                 f" compile={rec['compile_s']}s")
                    print(f"[{status.upper()}] {arch} {shape_id} "
                          f"mp={multi_pod}{extra}", flush=True)
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
