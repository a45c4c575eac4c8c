"""Mesh construction (functions; nothing touches jax device state at import
time).  The meshes are small: one node axis over the chips of one host (1 or
4 TPU v5e chips), or forced host devices in CPU tests and examples, which set
``XLA_FLAGS=--xla_force_host_platform_device_count=<count>`` before any jax
import.

Multi-process correctness (DESIGN.md §12): under ``jax.distributed`` every
process sees the GLOBAL device list, but a mesh that shards host-fed data
must be laid out PROCESS-MAJOR — each process's addressable devices occupy a
contiguous block of the node axis, so the per-host rows a process feeds
(``jax.make_array_from_callback``) land on its own devices.  ``jax.devices()``
already interleaves by process on some backends; :func:`_device_grid` builds
the grid explicitly from each process's local device list instead of trusting
that order.
"""
from __future__ import annotations

import math

import numpy as np


def _device_grid(n: int, what: str):
    """The first ``n`` global devices in PROCESS-MAJOR order, as a flat
    numpy object array — the canonical device layout the mesh builder
    reshapes.  Raises actionable errors when the process/device arithmetic
    cannot work."""
    import jax

    procs = jax.process_count()
    devices = jax.devices()
    if len(devices) < n:
        hint = (
            "the entry point must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=<count> "
            "before any jax import" if procs == 1 else
            "each process must be started with jax.distributed.initialize("
            "coordinator_address=, num_processes=, process_id=) and enough "
            "local devices (XLA_FLAGS=--xla_force_host_platform_device_"
            "count=<count> per host) that the processes together cover the "
            "mesh")
        raise RuntimeError(
            f"need {n} devices for {what}, have {len(devices)} "
            f"across {procs} process(es) — {hint}")
    if procs == 1:
        return np.array(devices[:n])
    if n % procs:
        raise RuntimeError(
            f"{what} needs {n} devices split over {procs} processes, but "
            f"{n} % {procs} != 0 — launch a process count that divides the "
            "mesh size (jax.distributed.initialize(num_processes=...))")
    per = n // procs
    grid = []
    for p in range(procs):
        local = [d for d in devices if d.process_index == p]
        if len(local) < per:
            raise RuntimeError(
                f"{what} needs {per} devices from process {p}, which has "
                f"{len(local)} — every process must expose the same local "
                "device count (set XLA_FLAGS=--xla_force_host_platform_"
                f"device_count={per} on each host before jax.distributed."
                "initialize)")
        grid.extend(local[:per])
    return np.array(grid)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for tests (run in a subprocess with forced host devices).
    Works under ``jax.distributed`` too: the grid is process-major, so a
    2-process launch puts the first half of the node axis on process 0."""
    import jax

    n = math.prod(shape)
    grid = _device_grid(n, f"mesh {shape}")
    return jax.sharding.Mesh(grid.reshape(shape), axes)
