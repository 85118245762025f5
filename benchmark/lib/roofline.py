"""Bytes and operations the work needs, from its shapes alone. The
functions here describe the work, not the program: a later PR that
changes how the flush is implemented is held to the same count."""

from __future__ import annotations

F32 = 4


def digest_flush_bytes(rows: int, centroids: int, anchors: int,
                       quantiles: int) -> dict:
    """One interval's flush of a dense t-digest group with ``rows`` live
    series (rows nothing was written to need no byte moved): it has to
    read every such row's digest (mean, weight: ``[rows, K]``; min,
    max) and the interval's binned samples (weight, weighted mean:
    ``[rows, K]``; the anchor summary ``[rows, A]`` twice; count, sum,
    min, max, reciprocal sum) with the imported extrema, and write the
    drained digest, ``quantiles`` values a row and the five scalars."""
    digest = rows * (2 * centroids + 2) * F32
    temp = rows * (2 * centroids + 2 * anchors + 5) * F32
    imported = rows * 2 * F32
    reads = digest + temp + imported + quantiles * F32
    writes = digest + rows * (quantiles + 5) * F32
    return {"reads": reads, "writes": writes, "total": reads + writes}


def least_seconds(work: dict, peak: dict) -> dict:
    """The least time the chip could take and which peak bounds it."""
    by_bytes = work["total"] / peak["hbm_bytes_per_s"]
    by_flops = work.get("flops", 0.0) / peak["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute"}
