"""Quickstart: build dynamic image graphs with DIGC through the
GraphBuilder registry (every implementation tier), batched, then run a
tiny ViG forward pass.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    DigcSpec,
    available_impls,
    digc,
    degree_histogram,
    edge_list,
    fpga_cycles,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import vig
from repro.models.module import init_params


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)

    # --- 1. DIGC on the paper's ViG-Tiny workload: N=M=196, D=192 -----
    # Batched-first: a (B, N, D) batch of images goes through every
    # registered builder in one call — no per-sample vmap.
    b, n, d, k, dil = 2, 196, 192, 8, 2
    feats = jnp.asarray(rng.standard_normal((b, n, d)), jnp.float32)

    print(f"registered DIGC builders: {available_impls()}")
    idx_ref = digc(feats, spec=DigcSpec(impl="reference", k=k, dilation=dil))
    idx_blk = digc(feats, spec=DigcSpec(impl="blocked", k=k, dilation=dil))
    idx_pl = digc(feats, spec=DigcSpec(impl="pallas", k=k, dilation=dil))
    assert bool(jnp.all(idx_ref == idx_blk)) and bool(jnp.all(idx_ref == idx_pl))
    print(f"DIGC: batch={b}, {n} nodes, k={k}, dilation={dil}")
    print(f"  neighbor lists agree across reference/blocked/pallas: True")
    edges = edge_list(idx_blk[0])
    deg = degree_histogram(idx_blk[0], n)
    print(f"  edges={edges.shape[1]}, in-degree mean={float(deg.mean()):.1f} "
          f"max={int(deg.max())}")
    print(f"  paper Table I cycle model @ this workload: {fpga_cycles(n, n, d, k)}")

    # single-image (N, D) still works — promoted to B=1 internally
    idx_one = digc(feats[0], k=k, dilation=dil, impl="blocked")
    assert bool(jnp.all(idx_one == idx_blk[0]))

    # --- 2. tiny ViG classifier forward --------------------------------
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=64, embed_dims=(48,), depths=(2,), num_classes=10, k=5
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    images = jnp.asarray(rng.standard_normal((2, 64, 64, 3)), jnp.float32)
    logits = jax.jit(lambda p, im: vig.vig_forward(p, im, cfg))(params, images)
    print(f"ViG forward: images {images.shape} -> logits {logits.shape}")
    print(f"  predictions: {jnp.argmax(logits, -1).tolist()}")


if __name__ == "__main__":
    main()
