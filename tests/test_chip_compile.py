"""Compile rehearsal for the chip: the main path's Pallas kernels at real
widths, compiled by the TPU compiler for one chip of a described v5e.

Nothing runs — a compile proves that Mosaic accepts the kernel (no
unsupported primitive or shape cast, VMEM within budget), not that it
is correct or fast; interpret-mode tests and the chip smoke cover that.
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker given this
file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    print(compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n,d,kd", [(3136, 192, 9), (12544, 320, 18)])
@pytest.mark.parametrize("merge,packed", [
    ("bitonic", False), ("bitonic", True), ("legacy", False)])
def test_digc_topk_compiles_for_v5e(one_chip, n, d, kd, merge, packed):
    """The fused top-k kernel with its default (VMEM-budgeted) tiles at
    ViG-Ti/S widths: N = 3136 (896 px) and N = 12544."""
    x = _shape(one_chip, (1, n, d))
    _compile(lambda a: ops.digc_topk(a, a, k=kd, kernel_merge=merge,
                                     packed=packed, interpret=False), x)


def test_digc_topk_bf16_compiles_under_highest(one_chip):
    """The mxu_bf16 kernel inside a `highest` matmul-precision scope:
    its bf16 contraction keeps one MXU pass (Mosaic refuses an fp32
    contract precision on bf16 operands)."""
    x = _shape(one_chip, (1, 3136, 192))
    with jax.default_matmul_precision("highest"):
        _compile(lambda a: ops.digc_topk(a, a, k=9, mxu_bf16=True,
                                         interpret=False), x)


@pytest.mark.parametrize("d", [192, 640])
def test_mrconv_compiles_for_v5e(one_chip, d):
    """The fused MRConv kernel at N = 3136, ViG-Ti and ViG-B widths."""
    n, k = 3136, 9
    x = _shape(one_chip, (1, n, d))
    idx = _shape(one_chip, (1, n, k), jnp.int32)
    _compile(lambda a, i: ops.mrconv(a, a, i, interpret=False), x, idx)
