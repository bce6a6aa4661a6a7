"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run forces a
512-device host platform while tests/benches must see a single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_types(n: int) -> dict:
    """make_mesh kwargs pinning explicit Auto axis types."""
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips for the multi-pod run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_types(len(axes)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices exist (tests/examples)."""
    return jax.make_mesh((data, model), ("data", "model"), **_axis_types(2))


def make_shard_mesh(n_devices: int | None = None):
    """1-D mesh for device-partitioned SpGEMM execution.

    ``core.partition.partition_plan`` (and ``ocean_spgemm(devices=...)``)
    accept this mesh directly; the bin ladder is split across its devices.
    Defaults to every local device.
    """
    n = len(jax.devices()) if n_devices is None else n_devices
    return jax.make_mesh((n,), ("shard",), **_axis_types(1))
