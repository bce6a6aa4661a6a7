"""Async device-launch substrate shared by every sharded stage.

``core.executor`` introduced a dispatch -> collect pipeline for SpGEMM
execution: enqueue device work without blocking, start async
device-to-host copies, then pull results back in *completion order*
(per-array readiness, never a global barrier). That machinery is not
execution-specific — any stage whose per-shard outputs merge exactly on
the host can use it. This module is the repo-wide home for it; the
numeric executor (``core.executor``) and the sharded analysis pipeline
(``core.analysis.AnalysisPipeline``) both dispatch through these helpers.

The byte counts of host<->device copies (:func:`to_host`,
:func:`to_device`, feeding ``OceanReport.copy_bytes``) live here too, as
does the device-set plumbing (``resolve_devices``/``topology_key``), so
stages below the partitioner (e.g. analysis) can normalize device specs
without importing ``core.partition`` (which depends on the plan
containers); ``core.partition`` re-exports them unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Sequence, Tuple, Union

import jax
import numpy as np

from repro.obs import trace

DeviceSpec = Union[None, int, Sequence, "jax.sharding.Mesh"]


def resolve_devices(devices: DeviceSpec = None) -> Tuple:
    """Normalize a device spec to a tuple of jax devices.

    Accepts ``None`` (all local devices), an int (first N local devices), a
    1-D mesh (e.g. ``launch.mesh.make_shard_mesh()``; any mesh is flattened
    in row-major order), or an explicit device sequence.
    """
    if devices is None:
        return tuple(jax.devices())
    if isinstance(devices, int):
        local = jax.devices()
        if devices < 1 or devices > len(local):
            raise ValueError(
                f"requested {devices} devices, have {len(local)}")
        return tuple(local[:devices])
    if isinstance(devices, jax.sharding.Mesh):
        return tuple(np.asarray(devices.devices).flatten().tolist())
    devices = tuple(devices)
    if not devices:
        raise ValueError("empty device set")
    return devices


def topology_key(devices: Sequence) -> str:
    """Stable string identity of an ordered device set — the extra
    component plan caches key sharded plans by."""
    return ",".join(f"{d.platform}:{d.id}" for d in devices)


def new_copy_bytes() -> Dict[str, int]:
    """An empty count of one call's copies, by direction."""
    return {"d2h": 0, "h2d": 0}


def to_host(x, copies: Dict[str, int]) -> np.ndarray:
    """``np.asarray(x)``, adding to ``copies["d2h"]`` the bytes that cross.

    A device array crosses on its first read only: ``jax.Array`` keeps the
    host copy (``_npy_value``, where the backend had to copy), so a later
    read of the same array costs nothing and counts nothing. A host array
    counts nothing."""
    if isinstance(x, jax.Array) and getattr(x, "_npy_value", None) is None:
        copies["d2h"] += x.nbytes
    return np.asarray(x)


def to_device(x, copies: Dict[str, int], device=None) -> jax.Array:
    """Commit ``x`` to ``device`` (the default device when None), adding
    to ``copies["h2d"]`` the bytes of a host array in the dtype it lands
    in (64-bit types narrow unless x64 is on); a device array is moved, if
    at all, between devices and counts nothing."""
    if not isinstance(x, jax.Array):
        x = np.asarray(x)
        copies["h2d"] += x.size * jax.dtypes.canonicalize_dtype(
            x.dtype).itemsize
    return jax.device_put(x, device)


@dataclasses.dataclass
class Launch:
    """One in-flight device computation awaiting collection.

    ``tag`` is caller-owned identity (which shard/bin/stage produced it);
    ``order`` is the dispatch order — the stable anchor merges sort by
    when completion order must not leak into results.
    """
    tag: object
    order: int
    arrays: Tuple


def device_context(device):
    """Context manager placing jax computations on ``device`` (no-op when
    ``device`` is None — the unsharded single-device path)."""
    return (jax.default_device(device) if device is not None
            else contextlib.nullcontext())


def start_async_host_copies(launches: Sequence[Launch]) -> None:
    """Begin async D2H copies for every launch so collection overlaps
    transfers with still-outstanding compute."""
    for it in launches:
        for arr in it.arrays:
            start = getattr(arr, "copy_to_host_async", None)
            if start is not None:
                start()


def launch_ready(it: Launch) -> bool:
    """True when every array of the launch is resident (non-blocking)."""
    for arr in it.arrays:
        ready = getattr(arr, "is_ready", None)
        if ready is not None and not ready():
            return False
    return True


def overlap_host_work(launches: Sequence[Launch],
                      work: Callable[[], object]
                      ) -> Tuple[object, float, bool]:
    """Run independent host-side ``work`` while ``launches`` are in flight.

    The canonical slot for this is right after
    :func:`start_async_host_copies`, before the collect loop: on async
    backends the devices keep computing / copying while ``work`` executes
    on the host, so its cost is hidden behind the outstanding launches.
    Returns ``(result, seconds, overlapped)`` where ``overlapped`` is True
    iff at least one launch was still pending when the work started —
    i.e. the seconds were genuinely concurrent with device work rather
    than running after everything already finished (the synchronous-CPU
    degenerate case).
    """
    pending = any(not launch_ready(it) for it in launches)
    with trace.span("dispatch.overlap_host_work") as sp:
        t0 = time.perf_counter()
        result = work()
        dt = time.perf_counter() - t0
        sp.measured(t0, dt).set(overlapped=pending)
    return result, dt, pending


def collect_in_completion_order(launches: Sequence[Launch]
                                ) -> Iterator[Launch]:
    """Yield launches as they complete (ready-first, no global barrier).

    When nothing is ready yet the oldest outstanding launch is yielded —
    the caller's materialization blocks only on that one item.
    """
    remaining: List[Launch] = list(launches)
    while remaining:
        idx = next((i for i, it in enumerate(remaining)
                    if launch_ready(it)), 0)
        yield remaining.pop(idx)
