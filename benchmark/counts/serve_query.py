"""The least bytes one dispatch of a read tier query program
(`hypermerge_tpu/serve/kernels.py`, XLA programs `serve_<kind>_b<B>_n<N>`)
must move: the lanes of the resident [6, N] int32 arrays the query reads,
once, for each of its B rows, and its answer written once. Nothing
re-read, no sort passes, no temporaries: a floor, so a roofline share
built on it cannot pass 100%. B is the dispatch's padded batch (the
program reads every row it is given; pad rows repeat the first doc's).

  seq_order   reads live, rank, obj, insert (4 lanes); writes the [B, N]
              int32 order and B counts
  map_lookup  reads map winner, key, obj (3 lanes); writes B rows + B flags
  counts      reads live, obj, insert, map winner (4 lanes); writes 2 x B
"""

from __future__ import annotations

LANES_READ = {"seq_order": 4, "map_lookup": 3, "counts": 4}


def bytes_moved(kind: str, batch: int, rows: int) -> float:
    read = batch * LANES_READ[kind] * rows * 4
    if kind == "seq_order":
        return float(read + batch * rows * 4 + batch * 4)
    if kind == "map_lookup":
        return float(read + batch * 4 + batch)
    return float(read + 2 * batch * 4)
