"""The plain reference against the program's forward, on the CPU at a
small size, for both configurations; and the reference's own derivation
of k and dilation against the program's at the served sizes."""

import numpy as np
import pytest

from chipbench.tests import tiny  # noqa: F401
from chipbench import registry
from chipbench.entries import vig as entry
from chipbench.references import vig as ref


@pytest.mark.parametrize("name,size", [("vig_ti_iso", 224), ("vig_ti_iso", 896),
                                       ("vig_s_pyr", 224), ("vig_s_pyr", 896),
                                       ("vig_ti_iso", 448), ("vig_s_pyr", 256)])
def test_plan_matches_the_program(name, size):
    from repro.models.vig import count_digc_work

    conf = registry.config(name)
    cfg = entry.program_config(conf)
    mine = [(b.grid ** 2, b.m, b.k, b.dilation) for b in ref.plan(conf, size)]
    theirs = [(r["N"], r["M"], min(r["k"], r["M"] // r["dilation"]),
               r["dilation"]) for r in count_digc_work(cfg, grid=size // conf["patch"])]
    assert mine == theirs


@pytest.mark.parametrize("name,size", [("vig_ti_iso", 64), ("vig_ti_iso", 256),
                                       ("vig_s_pyr", 64), ("vig_s_pyr", 256)])
def test_reference_matches_vig_forward(name, size):
    import jax
    import jax.numpy as jnp

    from repro.models.vig import vig_forward

    conf = registry.config(name)
    cfg = entry.program_config(conf)
    w = entry.weights(conf, 2 ** 33 + 5)
    image = np.random.default_rng(0).standard_normal(
        (size, size, 3)).astype(np.float32)
    cap = []
    with jax.default_matmul_precision("highest"):
        served = np.asarray(vig_forward(w, jnp.asarray(image)[None], cfg,
                                        digc_capture=cap))[0]
    blocks = ref.plan(conf, size)
    assert len(cap) == len(blocks)
    seen = []
    for blk, (_, h, co, idx) in zip(blocks, cap):
        h, idx = np.asarray(h)[0], np.asarray(idx)[0]
        y = h if co is None else np.asarray(co)[0]
        assert float(ref.row_gaps(h, y, idx, blk.dilation).max()) < 1e-6
        seen.append((h, y, idx))
    taught = ref.make_forward(conf, size, precision="highest", taught=True)
    keep = [np.ones(i.shape[0], bool) for _, _, i in seen]
    want = np.asarray(taught(w, jnp.asarray(image),
                             [jnp.asarray(i) for _, _, i in seen], keep,
                             rows=1))
    np.testing.assert_allclose(served, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())
    if size <= conf["image_size"]:
        # at or below the native grid, the reference's own lists are the
        # program's here (above it, one tie taken the other way moves
        # every later block: hence the lists handed over)
        own = ref.make_forward(conf, size, precision="highest")
        logits, _ = own(w, jnp.asarray(image))
        np.testing.assert_allclose(served, np.asarray(logits), rtol=0,
                                   atol=1e-5 * np.abs(want).max())


def test_list_gap_sees_a_wrong_neighbour():
    rng = np.random.default_rng(1)
    h = rng.standard_normal((64, 16)).astype(np.float32)
    import jax.numpy as jnp

    idx = np.asarray(ref.neighbours(jnp.asarray(h), jnp.asarray(h), 4, 2))
    assert float(ref.row_gaps(h, h, idx, 2).max()) < 1e-6
    bad = idx.copy()
    bad[5, 1] = (bad[5, 1] + 17) % 64
    gaps = np.asarray(ref.row_gaps(h, h, bad, 2))
    assert gaps[5] > 1e-2 and np.delete(gaps, 5).max() < 1e-6
