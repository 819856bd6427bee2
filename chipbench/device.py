"""The accelerator the run holds, and its published peaks."""

from __future__ import annotations

# Published peaks of one chip, keyed by ``device_kind``. TPU v5e: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s). A device not in the table is an error.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require(chips: int) -> dict:
    """The device description of a run on ``chips`` TPU chips; raises
    ``NoAccelerator`` when JAX finds no TPU or too few of them."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoAccelerator(
            f"no TPU: JAX found {dev.platform} ({dev.device_kind})")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chips; JAX found {len(devices)}")
    return describe(devices[:chips])


def describe(devices) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "device_kind": dev.device_kind, "count": len(devices)}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them to chipbench/device.py with their source"
                       ) from None


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
