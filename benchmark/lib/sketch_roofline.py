"""Bytes one dispatch of a sketch's ingest has to move, from shapes
alone: the heavy-hitter group's count-min update and the dense digest
group's row-drained sample ingest. As in ``lib/roofline.py`` the
functions describe the work, not the program: whatever implements the
dispatch is held to the same count, and no plane is counted whole (the
rows and columns a chunk does not touch need no byte moved)."""

from __future__ import annotations

F32 = 4
U32 = 4
I32 = 4


def topk_update_bytes(lines: float, streams: int, depth: int,
                      k: int) -> dict:
    """One count-min update of ``lines`` staged lines over ``streams``
    series: a line's row, stable series id, two key halves and weight
    are read, and its ``depth`` table columns read and written (the
    estimate reads what the add wrote: the same entries). Every touched
    stream's standing list of ``k`` (two key halves, a count) is read
    with its ``depth`` table columns a key, and written."""
    staged = lines * (I32 + 3 * U32 + F32)
    columns = lines * depth * F32
    lists = streams * k * (2 * U32 + F32)
    refresh = streams * k * depth * F32
    reads = staged + columns + lists + refresh
    writes = columns + lists
    return {"reads": reads, "writes": writes, "total": reads + writes}


def sample_rowdrain_bytes(samples: int, rows_drained: float,
                          centroids: int, anchors: int) -> dict:
    """One sample dispatch of ``samples`` staged samples that drains
    ``rows_drained`` held rows first: a sample's row, value and weight
    are read; a drained row's digest (mean, weight: ``centroids``
    each), its bins (weight, weighted mean) and its anchor summary
    (the same two, ``anchors`` each) are read and written; then every
    sample touches at most one bin entry, one anchor entry (two floats
    each) and the row's five scalars, read and written."""
    staged = samples * (I32 + 2 * F32)
    drained = rows_drained * (4 * centroids + 2 * anchors) * F32
    binned = samples * (2 + 2 + 5) * F32
    reads = staged + drained + binned
    writes = drained + binned
    return {"reads": reads, "writes": writes, "total": reads + writes}
