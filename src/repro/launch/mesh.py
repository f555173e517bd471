"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax
import; everything else sees the real single-device CPU).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU tests (requires >= n_data*n_model fake devices)."""
    return jax.make_mesh(
        (n_data, n_model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_worker_mesh(n_shards: int, axis_name: str = "worker"):
    """1-D federation mesh for the sharded trajectory engine
    (`repro.core.engine.run_scanned(mesh=...)`): `n_shards` devices, one
    axis.  Uses the classic Mesh API so fake-device CPU runs (set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
    initializes) work on every jax the repo supports.  `axis_name`
    defaults to the engine's "worker"; `launch.train --mesh-workers`
    passes "data" to reuse the LLM zoo's worker-axis partitioning rules.
    """
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < n_shards:
        raise ValueError(
            f"worker mesh needs {n_shards} devices but only "
            f"{len(devices)} are visible; launch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} (before "
            "jax initializes) for a fake-device CPU mesh")
    return Mesh(np.asarray(devices[:n_shards]), (axis_name,))


# Published per-chip peaks, keyed by `jax.Device.device_kind` (Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s; the ICI figure is per link).  A kind missing here is an
# error, never a default.
CHIP_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# the chip the HLO dry-run estimates (`launch.roofline`) are sized for
TARGET_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> dict:
    """Peak rates of one chip of `device_kind`; raises on an unknown kind."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r}; known kinds: "
            f"{sorted(CHIP_PEAKS)}") from None
