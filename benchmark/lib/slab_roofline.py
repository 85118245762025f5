"""Bytes one flush of a slab digest group has to move, from shapes
alone. As in ``lib/roofline.py`` the function describes the work, not
the program: rows nothing was written to need no byte moved, and no
slab is counted whole."""

from __future__ import annotations

F32 = 4
STORAGE_BYTES = {"float32": 4, "packed16": 2}


def slab_flush_bytes(rows: float, centroids: int, anchors: int,
                     percentiles: list, digest_dtype: str) -> dict:
    """One interval's flush of ``rows`` live rows of a slab group whose
    digest planes are held in ``digest_dtype``: every such row's digest
    (mean, weight: ``centroids`` each, 16 bits a value where it is
    ``packed16``: bfloat16 weights, means coded against the row's frame) is
    read and written with its two float32 bounds, and its two imported
    extrema are read; its bins (weight, weighted mean: ``centroids``
    float32 each), its anchor summary (the same two, ``anchors`` each)
    and its five scalar stats are read; a value for each of
    ``percentiles`` and the median is written."""
    storage = STORAGE_BYTES[digest_dtype]
    digest = rows * (2 * centroids * storage + 2 * F32)
    temp = rows * (2 * centroids + 2 * anchors + 5 + 2) * F32
    quantiles = rows * (len(percentiles) + 1) * F32
    reads = digest + temp
    writes = digest + quantiles
    return {"reads": reads, "writes": writes, "total": reads + writes}
