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
templates via ops/synth.py), padded to the same slab buckets, through
the same `run_batch_full` entry — so dtypes, A_loc/K buckets, and pred
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
from typing import List, Optional

INF = float("inf")


def bulk_buckets(n_docs_total: int, slab: Optional[int] = None) -> List[int]:
    """The doc-axis jit buckets the bulk loader will use for a load of
    `n_docs_total` docs: full slabs share one bucket, the tail rounds up
    to its own pow2 (backend/bulk_loader.py BulkLoader._load_slabs)."""
    from .columnar import round_up_pow2

    if slab is None:
        slab = int(os.environ.get("HM_BULK_SLAB", "4096"))
    buckets = []
    for base in range(0, n_docs_total, slab):
        chunk = min(slab, n_docs_total - base)
        b = round_up_pow2(chunk)
        if b not in buckets:
            buckets.append(b)
    return buckets


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
    n_docs_total: int,
    n_ops: int,
    slab: Optional[int],
    ops_per_change: int,
    distinct: int,
    seed: int,
) -> None:
    import numpy as np

    from ..crdt.change import Action
    from .columnar import pack_docs_columns, round_up_pow2
    from .crdt_kernels import run_batch_full

    min_cells = int(os.environ.get("HM_DEVICE_MIN_CELLS", "131072"))
    n_rows = round_up_pow2(max(1, n_ops))
    specs = template_specs(n_ops, ops_per_change, distinct, seed)

    for bucket in bulk_buckets(n_docs_total, slab):
        if bucket * n_rows < min_cells:
            continue  # host-kernel path: nothing to compile
        batch = pack_docs_columns(
            specs[: min(len(specs), bucket)], n_docs=bucket, n_rows=n_rows
        )
        lean = not bool(np.any(batch.cols["action"] == int(Action.INC)))
        out, summary = run_batch_full(batch, lean=lean)
        # force compile completion (dispatch alone returns early)
        np.asarray(summary.ravel()[:1])


def warmup_bulk(
    n_docs_total: int,
    n_ops: int,
    slab: Optional[int] = None,
    ops_per_change: int = 16,
    distinct: int = 8,
    seed: int = 0,
    background: bool = True,
) -> Optional[threading.Thread]:
    """Compile the bulk-load executables for a `n_docs_total` x `n_ops`
    corpus ahead of the load. `background=True` returns a started daemon
    thread (callers need not join: a real load issued meanwhile simply
    blocks inside jit until the shared executable is ready);
    `background=False` compiles inline and returns None."""
    if background:
        th = threading.Thread(
            target=_warm,
            args=(n_docs_total, n_ops, slab, ops_per_change, distinct, seed),
            daemon=True,
            name="hm-warmup",
        )
        th.start()
        return th
    _warm(n_docs_total, n_ops, slab, ops_per_change, distinct, seed)
    return None
