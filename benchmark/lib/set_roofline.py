"""Bytes the sparse set store's programs have to move, from shapes
alone. As in ``lib/roofline.py`` the functions describe the work, not
the program: a set's registers are what it filled, four bytes an entry
while it has at most ``width`` of them and ``registers`` bytes (one a
register) once it has more; an empty register needs no byte moved."""

from __future__ import annotations

HASH_AND_ROW = 12   # a 64-bit member hash and its 32-bit row
ENTRY = 4           # a sparse entry: register << 8 | rho
ESTIMATE = 4        # one float32 estimate a set


def set_flush_bytes(rows_live: float, entries_live: float,
                    rows_dense: float, registers: int) -> dict:
    """One interval's estimate of ``rows_live`` live sets, of which
    ``rows_dense`` hold dense registers and the rest ``entries_live``
    entries in all: every filled register read once, an estimate a set
    written."""
    reads = entries_live * ENTRY + rows_dense * registers
    writes = rows_live * ESTIMATE
    return {"reads": reads, "writes": writes, "total": reads + writes}


def set_ingest_bytes(hashes: float, entries_touched: float,
                     dense_writes: float, promotions: float, width: int,
                     registers: int) -> dict:
    """One insert dispatch: its ``hashes`` read with their rows; the
    touched sparse sets' ``entries_touched`` entries read and written;
    ``dense_writes`` registers of dense sets read and written (a byte
    each); each of the ``promotions`` sets' entries (at most ``width``)
    read and its dense row written."""
    reads = (hashes * HASH_AND_ROW + entries_touched * ENTRY
             + dense_writes + promotions * width * ENTRY)
    writes = (entries_touched * ENTRY + dense_writes
              + promotions * registers)
    return {"reads": reads, "writes": writes, "total": reads + writes}
