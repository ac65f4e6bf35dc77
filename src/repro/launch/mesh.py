"""Production mesh construction (single-pod 16x16 and multi-pod 2x16x16).

Defined as functions so importing this module never touches jax device
state (jax locks the device count on first backend init).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s inter-chip interconnect.
# The ICI figure below is the roofline's per-link uni-directional model.
V5E = "TPU v5 lite"
PEAKS = {
    V5E: {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


# the roofline analysis models the v5e production pod
PEAK_FLOPS = peaks(V5E)["flops_bf16"]
HBM_BW = peaks(V5E)["hbm_bw"]
ICI_BW = peaks(V5E)["ici_bw"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_single_pod_with_pod_axis() -> Mesh:
    """(1, 16, 16) so the same ("pod","data","model") specs work 1-pod."""
    return jax.make_mesh((1, 16, 16), ("pod", "data", "model"))


def make_host_mesh(n: int = 8, axes=("data", "model")) -> Mesh:
    """Small mesh over forced host devices for tests."""
    devs = np.array(jax.devices()[:n])
    if len(axes) == 2:
        return Mesh(devs.reshape(2, n // 2), axes)
    return Mesh(devs.reshape((1, 2, n // 2)), axes)
