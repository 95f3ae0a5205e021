"""Mesh construction and the per-chip peaks of the devices the repo targets.

Functions (not module-level state), so importing never touches jax device
state.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip
# interconnect (``ici_bw`` is that over the chip's 4 links, per link).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The per-chip peaks of ``device_kind``.  A device missing from
    :data:`PEAKS` is an error: no number is assumed for it."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (the partitioner places
    whatever the sharding rules leave open)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_demo_mesh(data: int = 2, model: int = 4):
    """Small mesh for sharding tests (requires forced host devices)."""
    return make_mesh((data, model), ("data", "model"))


def force_host_devices(n: int) -> None:
    """Present the host CPU as n XLA devices.  Must run before the jax
    backend initializes (i.e. before the first jax.devices() call)."""
    if n:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={n}").strip()


def host_mesh(mesh_shape: str = "", force_devices: int = 0):
    """(data, model) mesh over whatever devices exist.

    ``mesh_shape``: "DxM" (e.g. "2x4"); empty = all devices on the data
    axis.  ``force_devices``: force N host devices first (CPU containers;
    call before anything else touches jax devices).  The shared entry point
    for launch/train.py and launch/clients_sweep.py.
    """
    force_host_devices(force_devices)
    devs = jax.devices()
    if mesh_shape:
        d, m = (int(x) for x in mesh_shape.split("x"))
    else:
        d, m = len(devs), 1
    assert d * m == len(devs), f"mesh {d}x{m} != {len(devs)} devices"
    return make_mesh((d, m), ("data", "model"))


def batch_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
