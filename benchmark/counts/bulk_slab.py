"""The least bytes the bulk materialize programs
(`ops/crdt_kernels.py materialize_full[_lean]_device`) must move for a
list of slabs [docs, padded rows]: every input lane read once, every
output of the summary wire written once. Nothing re-read, no
temporaries, no pred edges (their padded length is the program's
choice): a floor, so a roofline share built on it cannot pass 100%.

Per cell (one padded op row of one doc):
  in   11 int32 lanes (ops/columnar.py COLUMNS: action, actor, ctr, seq,
       obj, key, ref, insert, vkind, value, dt)            = 44 bytes
  out  the fused summary wire: 2 mask bits (map winner, element live)
       + ceil(log2 rows) bits of element order              = (2+log2 N)/8
The kernel is bandwidth-bound (sorts, scatters and gathers over int32
lanes; no matrix unit work), so the bound is bytes over HBM bytes/s.
"""

from __future__ import annotations

from typing import List

IN_BYTES_PER_CELL = 44


def wire_bits_per_cell(rows: int) -> int:
    return 2 + max(1, (rows - 1).bit_length())


def bytes_moved(slabs: List[List[int]]) -> float:
    total = 0.0
    for docs, rows in slabs:
        cells = docs * rows
        total += cells * IN_BYTES_PER_CELL
        total += cells * wire_bits_per_cell(rows) / 8
    return total
