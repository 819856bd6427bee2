"""Pallas DIGC kernel: shape/dtype sweeps vs the pure-jnp oracle (ref.py)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import BIG
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.digc_topk import digc_topk_pallas


def _rand(rng, *shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def assert_same_valid(i_a, d_a, i_b, d_b):
    va = np.asarray(d_a) < BIG / 2
    vb = np.asarray(d_b) < BIG / 2
    np.testing.assert_array_equal(va, vb)
    np.testing.assert_array_equal(
        np.where(va, np.asarray(i_a), -1), np.where(vb, np.asarray(i_b), -1)
    )
    np.testing.assert_allclose(
        np.where(va, np.asarray(d_a), 0.0),
        np.where(vb, np.asarray(d_b), 0.0),
        rtol=1e-5,
        atol=1e-4,
    )


@pytest.mark.parametrize(
    "n,m,d",
    [
        (8, 128, 8),
        (16, 128, 32),
        (32, 256, 64),
        (64, 384, 128),
        (100, 130, 48),  # padding on both axes
        (33, 257, 17),  # awkward everything
        (128, 128, 192),  # ViG-Ti feature dim
    ],
)
@pytest.mark.parametrize("kd", [1, 4, 9])
def test_kernel_shape_sweep(n, m, d, kd):
    rng = np.random.default_rng(n * 7 + m)
    x, y = _rand(rng, n, d), _rand(rng, m, d)
    d_ref, i_ref = kref.digc_reference(x, y, kd=kd)
    i_k, d_k = ops.digc_topk(
        x, y, k=kd, block_n=32, block_m=128, return_dists=True
    )
    assert_same_valid(i_ref, d_ref, i_k, d_k)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_kernel_dtype_sweep(dtype):
    rng = np.random.default_rng(11)
    x, y = _rand(rng, 32, 24, dtype=dtype), _rand(rng, 160, 24, dtype=dtype)
    d_ref, i_ref = kref.digc_reference(x, y, kd=5)
    i_k, d_k = ops.digc_topk(x, y, k=5, block_n=16, block_m=128, return_dists=True)
    # kernel computes in fp32 after upcast — identical selection
    assert_same_valid(i_ref, d_ref, i_k, d_k)


@pytest.mark.parametrize("block_n,block_m", [(8, 128), (16, 256), (64, 128), (128, 512)])
def test_kernel_block_shape_invariance(block_n, block_m):
    rng = np.random.default_rng(12)
    x, y = _rand(rng, 96, 32), _rand(rng, 300, 32)
    d_ref, i_ref = kref.digc_reference(x, y, kd=7)
    i_k, d_k = ops.digc_topk(
        x, y, k=7, block_n=block_n, block_m=block_m, return_dists=True
    )
    assert_same_valid(i_ref, d_ref, i_k, d_k)


def test_kernel_pos_bias():
    rng = np.random.default_rng(13)
    x, y = _rand(rng, 48, 16), _rand(rng, 200, 16)
    p = _rand(rng, 48, 200) * 0.5
    d_ref, i_ref = kref.digc_reference(x, y, p, kd=6)
    i_k, d_k = ops.digc_topk(
        x, y, k=6, pos_bias=p, block_n=16, block_m=128, return_dists=True
    )
    assert_same_valid(i_ref, d_ref, i_k, d_k)


def test_kernel_causal():
    rng = np.random.default_rng(14)
    x = _rand(rng, 64, 16)
    i_k, d_k = ops.digc_topk(
        x, x, k=4, causal=True, block_n=16, block_m=128, return_dists=True
    )
    valid = np.asarray(d_k) < BIG / 2
    rows = np.arange(64)[:, None]
    assert np.all(np.where(valid, np.asarray(i_k) <= rows, True))
    assert np.array_equal(valid.sum(1), np.minimum(np.arange(64) + 1, 4))


def test_kernel_dilation():
    rng = np.random.default_rng(15)
    x, y = _rand(rng, 40, 16), _rand(rng, 256, 16)
    d_full, i_full = kref.digc_reference(x, y, kd=8)
    i_k = ops.digc_topk(x, y, k=4, dilation=2, block_n=8, block_m=128)
    np.testing.assert_array_equal(np.asarray(i_full[:, ::2][:, :4]), np.asarray(i_k))


def test_kernel_vig_tiny_shape():
    """The paper's reference config: N=M=196, D=192, k=8, d=2."""
    rng = np.random.default_rng(16)
    x = _rand(rng, 196, 192)
    d_ref, i_ref = kref.digc_reference(x, x, kd=16)
    i_k, d_k = ops.digc_topk(
        x, x, k=8, dilation=2, block_n=32, block_m=128, return_dists=True
    )
    np.testing.assert_array_equal(np.asarray(i_ref[:, ::2]), np.asarray(i_k))


def test_pallas_call_unpadded_direct():
    """digc_topk_pallas direct path (no wrapper) on aligned shapes."""
    rng = np.random.default_rng(17)
    x, y = _rand(rng, 64, 32), _rand(rng, 256, 32)
    d_ref, i_ref = kref.digc_reference(x, y, kd=4)
    d_k, i_k = digc_topk_pallas(x, y, kd=4, block_n=32, block_m=128)
    assert_same_valid(i_ref, d_ref, i_k, d_k)


def test_kernels_resolve_to_compiled_on_tpu(monkeypatch):
    """With the backend reporting a TPU, ``interpret=None`` (every
    caller's default) reaches ``pallas_call`` as compiled mode for both
    kernels, and interpret mode is refused there."""
    import jax
    from jax.experimental import pallas as pl

    from repro.kernels import resolve_interpret

    assert resolve_interpret(None) is True  # this CPU backend
    seen = []

    def fake_pallas_call(kernel, *, out_shape, interpret, **kw):
        seen.append(interpret)

        def run(*args):
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                out_shape)
        return run

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pl, "pallas_call", fake_pallas_call)
    assert resolve_interpret(None) is False
    with pytest.raises(ValueError, match="only off-TPU"):
        resolve_interpret(True)
    # Shapes no other test uses: the jitted kernels retrace here.
    x = jnp.zeros((1, 24, 20), jnp.float32)
    ops.digc_topk(x, x, k=3)
    ops.mrconv(x, x, jnp.zeros((1, 24, 3), jnp.int32))
    assert seen == [False, False]
