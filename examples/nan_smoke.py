"""NaN-debug smoke of the reference tier (DESIGN.md §11, CI fast job).

Runs the reference DIGC builder and a tiny ViG forward (cold and warm
ticks through the functional state) with well-conditioned inputs.
Executed under ``JAX_DEBUG_NANS=1`` in CI, it proves the fault-free
reference path manufactures no NaN/Inf anywhere in its compute — the
baseline the serving guards' finiteness screens are calibrated
against: any non-finite value they catch came from the *input or
corruption*, never from healthy reference-tier arithmetic.

    JAX_DEBUG_NANS=1 PYTHONPATH=src python examples/nan_smoke.py
"""

import os

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import DigcSpec, digc
from repro.launch.compile_cache import enable_compile_cache
from repro.models import vig
from repro.models.module import init_params


def main():
    enable_compile_cache()
    debug_nans = jax.config.jax_debug_nans
    print(f"jax_debug_nans={debug_nans} "
          f"(JAX_DEBUG_NANS={os.environ.get('JAX_DEBUG_NANS', '<unset>')})")
    rng = np.random.default_rng(0)

    # --- reference DIGC, eager and jitted -----------------------------
    feats = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)
    spec = DigcSpec(impl="reference", k=4, dilation=2)
    idx = digc(feats, spec=spec)
    idx_jit = jax.jit(lambda f: digc(f, spec=spec))(feats)
    assert bool(jnp.all(idx == idx_jit))
    print(f"reference DIGC: idx {idx.shape}, eager == jit")

    # --- tiny ViG forward, cold then warm state tick ------------------
    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=16, patch=4, embed_dims=(16,), depths=(2,),
        num_classes=3, k=3, digc_impl="reference",
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    state = vig.init_vig_state(cfg, 2, "reference")
    images = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), jnp.float32)
    fwd = jax.jit(lambda p, im, s: vig.vig_forward(
        p, im, cfg, digc_impl="reference", state=s))
    for tick in (1, 2):
        logits, state = fwd(params, images, state)
        assert bool(jnp.isfinite(logits).all())
        print(f"ViG tick {tick}: logits {logits.shape} all finite")
    print("NAN_SMOKE_OK")


if __name__ == "__main__":
    main()
