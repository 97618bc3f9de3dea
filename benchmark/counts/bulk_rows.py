"""The least bytes the bulk materialize programs must move for a store
given as every doc's REAL op rows: each real row's input lanes read
once, each real row's share of the summary wire written once. No
padding of any kind is counted (not the pow2 of a doc's rows, not the
slab's longest doc, not the padded doc axis): how the loader forms its
slabs can only add bytes to this, so a roofline share built on it falls
with padding and cannot pass 100%.

Per real row (counts/bulk_slab.py has the lanes by name):
  in   11 int32 lanes                                   = 44 bytes
  out  2 mask bits + ceil(log2 rows of its doc) bits of
       element order                                    = (2+log2 n)/8
"""

from __future__ import annotations

from typing import List

from benchmark.counts.bulk_slab import IN_BYTES_PER_CELL, wire_bits_per_cell


def bytes_moved(doc_rows: List[int]) -> float:
    total = 0.0
    for rows in doc_rows:
        total += rows * IN_BYTES_PER_CELL
        total += rows * wire_bits_per_cell(rows) / 8
    return total
