"""Bytes one device of a mesh has to move for its part of the work,
from shapes alone. ``lib/roofline.py`` counts a whole group's rows; a
trace's program time is one device's (``readers/trace_program_time.py``
takes the device that spent most), so a share of a roofline on a mesh
needs the device's own count. As there, the functions describe the
work, not the program: whatever implements the dispatch is held to the
same count."""

from __future__ import annotations

F32 = 4
I32 = 4


def mesh_sample_ingest_bytes(samples: int, series_axis: int,
                             hosts_axis: int) -> dict:
    """One sample dispatch of ``samples`` staged samples on a
    ``series_axis`` x ``hosts_axis`` mesh, for one device. The chunk is
    split over the hosts axis, so the device reads its slice (a row, a
    value and a weight a sample). A row lives on the devices of its
    series shard, every one of the hosts axis, and each ends the
    dispatch with the whole chunk's contribution to its block: of the
    chunk's samples ``1 / series_axis`` fall there, and each touches at
    most one bin entry (weight, weighted mean), one anchor entry (the
    same two) and the row's five scalars (count, sum, min, max,
    reciprocal sum), read and written. No plane is counted whole: the
    rows a chunk does not touch need no byte moved."""
    sliced = -(-samples // hosts_axis)
    touched = -(-samples // series_axis)
    reads = sliced * (I32 + 2 * F32) + touched * (2 + 2 + 5) * F32
    writes = touched * (2 + 2 + 5) * F32
    return {"reads": reads, "writes": writes, "total": reads + writes}
