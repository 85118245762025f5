"""Mesh construction for the two veneur axes: hosts (fan-in) × series (shard).

ICI-friendly layout: the ``hosts`` reduction axis is placed innermost so the
psum/pmax collectives ride neighbouring chips; the ``series`` axis never
communicates after ingest (each device owns its rows outright, like a
reference worker owns its ``map[MetricKey]*sampler``, ``worker.go:54-91``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HOSTS_AXIS = "hosts"
SERIES_AXIS = "series"


def _largest_pow2_divisor(n: int, cap: int) -> int:
    d = 1
    while d * 2 <= cap and n % (d * 2) == 0:
        d *= 2
    return d


def fleet_mesh(devices: Optional[Sequence[jax.Device]] = None,
               hosts: Optional[int] = None) -> Mesh:
    """Build a 2-D ``(series, hosts)`` mesh over the available devices.

    ``hosts`` defaults to the largest power-of-two divisor of the device
    count ≤ device_count (so an 8-chip slice becomes 1×8 pure fan-in by
    default when hosts=None is resolved to all devices); pass ``hosts=1``
    for a pure series-sharded layout.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if hosts is None:
        hosts = _largest_pow2_divisor(n, n)
    if n % hosts != 0:
        raise ValueError(f"{n} devices not divisible by hosts={hosts}")
    arr = np.asarray(devices).reshape(n // hosts, hosts)
    return Mesh(arr, (SERIES_AXIS, HOSTS_AXIS))


def series_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard dim 0 (the series axis) across the mesh's series devices;
    replicate over hosts."""
    spec = P(SERIES_AXIS, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def host_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Shard dim 0 (per-host contributions) across the hosts axis;
    replicate over series devices (each series shard filters its rows)."""
    spec = P(HOSTS_AXIS, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
