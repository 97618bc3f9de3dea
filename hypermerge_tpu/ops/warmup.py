"""Speculative XLA compile warmup for the bulk cold-start path.

Every distinct slab executable costs tens of seconds of XLA compile the
first time a process dispatches it (PERF.md has the chip's figures). A
deployment that knows it is about to bulk-open a
corpus (a server starting up, the benchmark writing its corpus) can
overlap that compile with its own host-side IO by starting warmup in a
daemon thread first; the compile holds one host core while it runs.

The warmup compiles the *exact* executables `RepoBackend.open_many`
will dispatch: it packs the same synthetic single-writer template
histories the benchmark corpus is built from (ops/corpus.py `distinct`
templates via ops/synth.py), padded to the same slab shapes (`bulk_shapes`:
the loader's own former, so a store of ragged lengths warms one program
a rung of its ladder), through the same `run_batch_full` entry — so dtypes, A_loc/K buckets, and pred
widths all land on the jit cache key the real load produces. If a real
load's shapes differ, the warmup was merely an extra cached executable;
correctness is untouched (jit keys on shapes).

Parity note: the reference has no equivalent — Node JITs nothing ahead
of time. This is TPU-native infrastructure in the same spirit as the
persistent compilation cache (ops/compile_cache.py), which handles the
second process; warmup handles the first.
"""

from __future__ import annotations

import math
import os
import threading
from typing import List, Optional, Sequence, Tuple, Union

INF = float("inf")


def bulk_shapes(
    doc_ops: Sequence[int], slab: Optional[int] = None
) -> List[Tuple[int, int]]:
    """The distinct [docs, rows] of the slabs the bulk loader forms for
    docs of these op counts met in this order, in dispatch order: the
    loader's own former (backend/pipeline.py SlabFormer) under its cell
    budget and device gate, the doc axis at its pow2 and the rows at
    the pow2 over the slab's longest doc."""
    from ..backend.bulk_loader import SLAB_CELLS, device_min_cells
    from ..backend.pipeline import SlabFormer
    from .columnar import round_up_pow2

    if slab is None:
        slab = int(os.environ.get("HM_BULK_SLAB", "4096"))
    former = SlabFormer(slab, SLAB_CELLS, device_min_cells())
    slabs = [
        full for full in (former.add(n, n) for n in doc_ops)
        if full is not None
    ] + former.flush()
    return list(dict.fromkeys(
        (round_up_pow2(len(s)), round_up_pow2(max(1, max(s))))
        for s in slabs
    ))


def bulk_buckets(
    n_docs_total: int, slab: Optional[int] = None, n_ops: int = 1
) -> List[int]:
    """The doc-axis jit buckets of a load of `n_docs_total` docs of
    `n_ops` ops each: full slabs share one bucket, the tail rounds up
    to its own pow2."""
    return [d for d, _n in bulk_shapes([n_ops] * n_docs_total, slab)]


def template_specs(
    n_ops: int, ops_per_change: int = 16, distinct: int = 8, seed: int = 0
) -> list:
    """Pack specs of the corpus' own template histories (ops/corpus.py
    make_corpus defaults) -> identical value ranges, pred widths, and
    key tables, with no repo on disk."""
    from ..storage.colcache import FeedColumnCache, MemoryColumnStorage
    from .synth import synth_changes

    specs = []
    for t in range(max(1, distinct)):
        # "actor00" is synth_changes' single-writer actor name — the
        # cache writer must match or refs look foreign and packing falls
        # off the no-sort fast path (ops/corpus.py _TEMPLATE_ACTOR)
        cc = FeedColumnCache(MemoryColumnStorage(), writer="actor00")
        for c in synth_changes(
            n_ops, n_actors=1, ops_per_change=ops_per_change, seed=seed + t
        ):
            cc.append_change(c)
        specs.append([(cc.columns(), 0, INF)])
    return specs


def _warm(
    doc_ops: Sequence[int],
    slab: Optional[int],
    ops_per_change: int,
    distinct: int,
    seed: int,
) -> None:
    import numpy as np

    from ..backend.bulk_loader import device_min_cells, pack_slab
    from .crdt_kernels import batch_is_lean, run_batch_full

    min_cells = device_min_cells()
    for bucket, n_rows in bulk_shapes(doc_ops, slab):
        if bucket * n_rows < min_cells:
            continue  # host-kernel path: nothing to compile
        # histories as long as the shape's longest doc: its rows are
        # the pow2 over them, and a store that supersedes more than the
        # pred floor gets the pow2 over them there too
        n_ops = max(n for n in doc_ops if n <= n_rows)
        specs = template_specs(n_ops, ops_per_change, distinct, seed)
        batch = pack_slab(specs[: min(len(specs), bucket)], n_docs=bucket)
        out, summary = run_batch_full(batch, lean=batch_is_lean(batch))
        # force compile completion (dispatch alone returns early)
        np.asarray(summary.ravel()[:1])


def warmup_bulk(
    n_docs_total: int,
    n_ops: Union[int, Sequence[int]],
    slab: Optional[int] = None,
    ops_per_change: int = 16,
    distinct: int = 8,
    seed: int = 0,
    background: bool = True,
) -> Optional[threading.Thread]:
    """Compile the bulk-load executables for a `n_docs_total` x `n_ops`
    corpus ahead of the load; `n_ops` may be every doc's op count in
    store order instead (a store of ragged lengths: one program a slab
    shape of `bulk_shapes`, the ladder of rungs the loader forms). `background=True` returns a started daemon
    thread (callers need not join: a real load issued meanwhile simply
    blocks inside jit until the shared executable is ready);
    `background=False` compiles inline and returns None."""
    doc_ops = (
        [int(n_ops)] * n_docs_total if isinstance(n_ops, int)
        else list(n_ops)
    )
    if background:
        th = threading.Thread(
            target=_warm,
            args=(doc_ops, slab, ops_per_change, distinct, seed),
            daemon=True,
            name="hm-warmup",
        )
        th.start()
        return th
    _warm(doc_ops, slab, ops_per_change, distinct, seed)
    return None
