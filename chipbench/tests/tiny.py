"""Tiny cells for the CPU tests: the harness end to end at a size a test
run holds, with the device check steered by the test."""

from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

ISO = dict(name="vig_tiny", entry="vig", variant="vig_tiny",
           architecture="isotropic", image_size=32, patch=8, in_chans=3,
           embed_dims=[16], depths=[4], reduce_ratios=[1], k=3,
           num_knn=[3] * 4, max_dilation=4, use_dilation=True, ffn_ratio=4, num_classes=10)
PYR = dict(ISO, architecture="pyramid", patch=4, embed_dims=[8, 16],
           depths=[1, 1], reduce_ratios=[2, 1], num_knn=[3] * 2)
LIMITS = {"list_gap": 1e-4, "logit_gap": 1e-4}


def program_variant(conf: dict):
    from repro.models.vig import VigConfig

    return VigConfig(conf["variant"], conf["architecture"],
                     image_size=conf["image_size"], patch=conf["patch"],
                     embed_dims=tuple(conf["embed_dims"]),
                     depths=tuple(conf["depths"]),
                     reduce_ratios=tuple(conf["reduce_ratios"]),
                     k=conf["k"], num_classes=conf["num_classes"])


def install(monkeypatch, conf: dict, mix: dict, limits=LIMITS) -> dict:
    """Register ``conf`` as the program's ``vig_tiny`` variant, make the
    harness find it, ``mix`` and ``limits`` under the cell
    ``tiny.cell``, and let the run go ahead on the CPU. Returns the
    benchmark object to pass to ``run_cell``."""
    import jax

    from chipbench import device, registry
    from repro.models import vig

    monkeypatch.setitem(vig.VIG_VARIANTS, conf["variant"],
                        program_variant(conf))
    monkeypatch.setattr(registry, "config", lambda name: conf)
    monkeypatch.setattr(registry, "traffic", lambda name: mix)
    monkeypatch.setattr(registry, "limits", lambda name: limits)
    monkeypatch.setattr(device, "require", lambda chips: device.describe(
        jax.devices()[:chips]))
    monkeypatch.setattr(device, "peaks", lambda kind: {"bf16_flops": 1e12})
    bench = copy.deepcopy(registry.benchmark())
    bench["workloads"].append(dict(name="tiny.cell", config="vig_tiny",
                                   traffic="tiny", chips=1, why="test"))
    online = mix["kind"] == "poisson"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and (m["name"] == "p95_latency_ms"
                                 or m["name"].endswith(".online")) == online:
            m["workloads"].append("tiny.cell")
    return bench


def online_mix(size: int = 64) -> dict:
    # fast enough that ticks hold several requests on the CPU
    return dict(kind="poisson", image_size=size, rate_per_s=1000,
                pool_images=4)


def backlog_mix(size: int = 64) -> dict:
    return dict(kind="backlog", image_size=size, depth_slots=2,
                pool_images=4)
