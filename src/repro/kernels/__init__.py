"""Pallas kernels for the paper's hot spots (fused DIGC top-k, fused
MRConv), their padding wrappers (``ops``) and jnp references (``ref``)."""

import jax


def resolve_interpret(interpret):
    """Whether a kernel call runs in Pallas interpret mode. ``None``
    means compiled on a TPU backend and interpreted everywhere else;
    interpret mode exists only off-TPU, so asking for it on a TPU is an
    error rather than a silent slow path."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "Pallas interpret mode runs only off-TPU; this backend is a TPU")
    return bool(interpret)
