"""The bulk loader: a cold open of many docs as one streamed program.

Each doc's feed windows come from the columnar sidecars
(storage/colcache.py), pack vectorized (ops/columnar.py) and
materialize in slab-sized device dispatches: io -> spec -> form -> pack
-> dispatch -> fetch over slabs, overlapped by backend/pipeline.py,
where the reference replays doc by doc (src/RepoBackend.ts:238-257).
Slabs are formed by length (pipeline.SlabFormer): docs of one row rung,
in store order, within SLAB_CELLS cells. One
schedule on every host: without the native pack the numpy pack runs on
one pack worker, and with several devices whole slabs go round-robin.

Imports point one way: repo_backend -> bulk_loader -> pipeline, ops/*,
parallel/sharded, storage/*; what imports jax loads where it is used.
"""

from __future__ import annotations

import functools
import os
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

from .. import msgs, telemetry
from ..analysis.lockdep import make_lock
from ..ops import columnar
from ..storage.colcache import load_slab_images
from ..utils.debug import log
from ..utils.ids import root_actor_id
from .doc_backend import DocBackend
from .pipeline import (
    FetchContext, SlabFormer, SlabPipeline, Stage, pack_worker_count,
)

# device->host summary-wire transfer bytes (same series sharded.py's
# collective gather feeds; handle cached — one per-slab bump)
_M_D2H = telemetry.counter("mesh.d2h_bytes")
# column sidecars by loader: slab-granular (one pass over cols.slab) or
# feed by feed (_prefetch_columns)
_M_COLS_BULK = telemetry.counter("loader.cols_bulk_feeds")
_M_COLS_SINGLE = telemetry.counter("loader.cols_single_feeds")
# of the former, those somebody read a plane of through numpy
# (colcache._ImagePlanes adds)
_M_PLANES_BUILT = telemetry.counter("loader.cols_planes_built")
# feed heads of a bulk open by who answered: the store's head snapshot
# (storage/feed.py HeadSnapshot) or a probe of the feed's files
_M_HEADS_SNAP = telemetry.counter("loader.heads_snapshot_feeds")
_M_HEADS_PROBED = telemetry.counter("loader.heads_probed_feeds")
# every dispatched slab's padded cells (docs x rows of its batch) and
# the real op rows in them: their ratio is what forming slabs costs
_M_CELLS_PADDED = telemetry.counter("loader.slab_cells_padded")
_M_ROWS_REAL = telemetry.counter("loader.slab_rows_real")
# the padded cells again, by where their slab's program runs rga_order's
# rounds; keyed by the stats key each feeds
_M_RGA_CELLS = {
    "rga_vmem_cells": telemetry.counter("kernel.rga_vmem_cells"),
    "rga_xla_cells": telemetry.counter("kernel.rga_xla_cells"),
}

# the cell budget of one slab (docs x rows): the largest slab of the
# 1,024-op yardstick store, HM_BULK_SLAB's 4,096 docs x 1,024 rows. The
# former lets no slab of longer docs count more, so the host's pack
# buffers and the device memory a slab takes are the same whatever the
# lengths of the store's docs.
SLAB_CELLS = 4096 * 1024


# a slab's pred axis is at least its row axis over this. The pack sizes
# it to the pow2 over the slab's widest doc, which follows the data: of
# two stores of one law, one would ask for a [4, 262144] program of
# 32,768 pred columns and the other for one of 65,536. With the floor a
# store whose docs supersede up to a quarter of their ops (the yardstick
# stores: 15%) asks for one program a slab shape, whatever its seed; one
# that supersedes more keeps the pow2 over its widest doc.
PRED_ROWS = 4


def pack_slab(
    specs, n_docs: Optional[int] = None, n_rows: Optional[int] = None
) -> columnar.ColumnarBatch:
    """The batch of one slab of doc feed specs, as the loader dispatches
    it (and ops/warmup.py compiles it ahead, `n_docs` given): the doc
    axis at its pow2, so every slab of a rung, and every later bulk
    load, reuses one compiled executable; the rows at the pow2 over the
    longest doc, or at `n_rows` (the read tier's installs: their docs'
    length rung, so that a rung has one program however long the docs
    of a page happen to be); the pred axis at least rows / PRED_ROWS."""
    if n_docs is None:
        n_docs = columnar.round_up_pow2(len(specs))
    batch = columnar.pack_docs_columns(specs, n_rows=n_rows, n_docs=n_docs)
    n_pred = batch.n_rows // PRED_ROWS
    with telemetry.span("pipeline.pack.widen", "pipeline", P=n_pred):
        return columnar.widen_preds(batch, n_pred)


def device_min_cells() -> int:
    """The device gate: a slab of fewer [D, N] cells is answered by the
    numpy kernel twin (small loads aren't worth a device dispatch, let
    alone a fresh compile). Read by the dispatch, and by the former,
    which merges a thin remainder upward where a slab has room."""
    return int(os.environ.get("HM_DEVICE_MIN_CELLS", "131072"))

# summary-fetch workers: one per device up to this many (each worker
# is a host-side parse plus one transfer at a time)
FETCH_WORKERS = 4

# the seconds of a dispatch's child spans (recorded where the work
# happens: ops/crdt_kernels.py) -> stats keys
_DISPATCH_KIDS = (
    ("pipeline.narrow", "t_narrow"),
    ("pipeline.upload", "t_upload"),
    ("pipeline.enqueue", "t_dispatch"),
)
_STAGE_KEYS = (
    "t_io", "t_spec", "t_pack", "t_narrow", "t_upload", "t_dispatch",
)
# last_bulk_stats as a load begins (docs and t_sql are the load's own)
_STATS0: Dict[str, Any] = {
    "fast": 0, "memo": 0, "fallback": 0,
    # which kernel ran each slab: the device program or the numpy twin
    # below HM_DEVICE_MIN_CELLS — and on which platform the device
    # slabs ran (None: none did)
    "device_slabs": 0, "host_slabs": 0, "platform": None,
    "pack_workers": 0,
    # column sidecars loaded slab-granular / feed by feed
    # (_prefetch_columns); cold feeds whose head the store's snapshot
    # answered / whose files were probed; the shares land after the load
    "cols_bulk_feeds": 0, "cols_single_feeds": 0, "cols_bulk_pct": 0.0,
    # slab-granular feeds somebody read a plane of through numpy, from
    # the load's start to its barrier (colcache._ImagePlanes; the
    # process's count, so another load's feeds read meanwhile are in
    # it): 0 where every pack is native
    "cols_planes_built": 0, "cols_planes_built_pct": 0.0,
    "heads_snapshot_feeds": 0, "heads_probed_feeds": 0,
    "heads_snapshot_pct": 0.0,
    # feeds the open read; docs whose slab took the general
    # (multi-writer) pack and their share; that pack's feeds by who
    # gathered their rows (the native entry / its numpy twin); the
    # feeds the prefix pack's gate judged, by who judged them (a feed
    # that carries its latch already counts as neither); the widest
    # actor and pred buckets among the slabs' programs
    "feeds": 0, "pack_general_docs": 0, "pack_general_pct": 0.0,
    "pack_gather_native_feeds": 0, "pack_gather_twin_feeds": 0,
    "pack_gather_native_pct": 0.0,
    "pack_gate_native_feeds": 0, "pack_gate_twin_feeds": 0,
    "pack_gate_native_pct": 0.0,
    "a_loc_max": 0, "pred_max": 0,
    # what forming the slabs cost: the dispatched slabs, their [D, N]
    # shapes in dispatch order, their padded cells over the real op
    # rows in them (1.0: no padding), and the distinct device programs
    # (D, N, A, K, P, lean) among them; the shares land after the load
    "slabs": 0, "slab_shapes": (), "rows_real": 0, "cells_padded": 0,
    "slab_waste_x": 0.0, "slab_programs": 0,
    "t_form": 0.0,
    # padded cells of the device slabs by where their program ran
    # rga_order's rounds (crdt_kernels.rga_rounds_in_vmem): out of VMEM
    # / through XLA's gather; the share lands after the load
    "rga_vmem_cells": 0, "rga_xla_cells": 0, "rga_vmem_cells_pct": 0.0,
    **dict.fromkeys(_STAGE_KEYS, 0.0),
}


def _memo_entry_bytes(m: Dict) -> int:
    return (
        m["mw_bits"].nbytes
        + m["el_bits"].nbytes
        + m["order"].nbytes
        + m["clock_row"].nbytes
        + 512  # dict/key overhead estimate
    )


def _pct(part: int, whole: int) -> float:
    return round(100.0 * part / whole, 3) if whole else 0.0


class BulkLoader:
    """Everything only a bulk load reads or writes: the load and its
    stages, the round-robin scheduler, the materialization barrier and
    the per-doc summary memo, under `repo.bulk` (one load or barrier at
    a time) and `repo.stats` (stage threads add timings concurrently).
    Of the backend it serves it uses only what a load needs:

      calls   _doc_feed_spec, _get_or_create_actor, _init_bulk_doc,
              _doc_snapshot_fn, _gate_unknown_empty, _load_document,
              _settle_store_rows, _note_unheld,
              _begin_bulk_actors / _end_bulk_actors
              (the deferred feed rows and actor syncs live on the
              backend, whose actor plumbing fills them), _doc_notify
      stores  id, db, cursors, clocks, feeds, _col_slab, live, and
              docs under the backend's `repo` lock
      queue   to_frontend
    """

    def __init__(self, backend: Any) -> None:
        self._back = backend
        self._mutex = make_lock("repo.bulk")
        self._stats_lock = make_lock("repo.stats")
        # device summary refs of the latest load, which the barrier
        # fetches, and its memo-served docs
        self._pending_summaries: List = []
        self._pending_memo: List = []
        # the latest load's async fetch workers, joined by the barrier
        self._fetch_ctx: Optional[FetchContext] = None
        self._bulk_t0: Optional[float] = None
        self._bulk_planes0 = 0.0  # _M_PLANES_BUILT as the latest load began
        self._bulk_open = 0  # request id of the latest load's spans
        # per-doc summary memo: doc_id -> last fetched summary row + the
        # clock it was fetched at. A later bulk load of a doc whose
        # clock has not moved (the same clock rows the device-resident
        # ClockStore mirror tracks) is CLEAN: it skips pack, dispatch,
        # and the summary transfer entirely — only dirty docs ride the
        # wire. Bounded LRU by BYTES (HM_SUMMARY_MEMO_MB, 0 disables) —
        # entries scale with the doc's row bucket, so an entry-count cap
        # would let large buckets pin gigabytes.
        self._summary_memo: "OrderedDict[str, Dict]" = OrderedDict()
        self._summary_memo_bytes = 0
        self.last_bulk_stats: Dict[str, Any] = {}

    def summary_memo_row(self, doc_id: str) -> Optional[Dict]:
        """The memo's row for `doc_id` (None when it holds none): the
        serve tier's installs reuse its lanes at an equal clock."""
        return self._summary_memo.get(doc_id)

    # ------------------------------------------------------------------
    # the load

    def load(self, doc_ids: List[str], slab: Optional[int] = None) -> None:
        """Cold-start many docs with zero per-op host work (BASELINE
        config 4). Docs come up ready with host-verified clocks and
        lazily-decoded snapshot patches; the host OpSet reconstructs
        only when a doc takes its first incremental change.

        Host-side work is batched, not per-doc: one cursor upsert + one
        SELECT for all docs, one feed-registry executemany, one clock
        executemany, sidecar loads a slab at a time, and per-actor syncs
        deferred to a single pass at the end. Device dispatches are
        async — the materialization barrier is `fetch_summaries`."""
        # the open's request id: the facade's (Repo.open_many is the
        # root span) or, called directly, the next of the sequence
        open_id = telemetry.open_id()
        if slab is None:
            slab = int(os.environ.get("HM_BULK_SLAB", "4096"))
        with telemetry.span(
            "pipeline.bulk_load", "pipeline", open=open_id,
            docs=len(doc_ids),
        ):
            with self._mutex:  # concurrent open_many calls serialize
                self._bulk_open = open_id
                self._load_locked(doc_ids, slab)

    def _load_locked(self, doc_ids, slab) -> None:
        back = self._back
        # summaries are for the latest load: drop refs nobody fetched so
        # repeated open_many calls can't pin old slabs' host+device memory
        self._pending_summaries = []
        self._pending_memo = []
        # nobody ran the barrier for the previous load: settle its
        # fetch worker before dispatching a new pipeline
        self._settle_fetch("unfetched bulk load's fetch")

        self._bulk_t0 = time.perf_counter()
        self._bulk_planes0 = _M_PLANES_BUILT.value()

        # -- phase 1: register docs + one bulk cursor upsert/select -----
        new_docs: List[DocBackend] = []
        already_ready: List[str] = []  # open docs: frontend may re-read
        with Stage("pipeline.register", docs=len(doc_ids)) as register:
            with back._lock:
                for doc_id in doc_ids:
                    existing = back.docs.get(doc_id)
                    if existing is not None:
                        if existing._announced:
                            already_ready.append(doc_id)
                        continue
                    doc = DocBackend(
                        doc_id, back._doc_notify, None, live=back.live
                    )
                    back.docs[doc_id] = doc
                    new_docs.append(doc)
            # docs closed with store rows still in the debouncer must
            # not bulk-reload from the stale rows (same guard as
            # open/destroy)
            back._settle_store_rows({d.id for d in new_docs})
            back._note_unheld([d.id for d in new_docs])
            with back.db.bulk():
                back.cursors.add_actors(
                    back.id,
                    [(d.id, root_actor_id(d.id)) for d in new_docs],
                )
            cursor_map = back.cursors.get_multiple(
                back.id, [d.id for d in new_docs]
            )
        # stage breakdown (seconds): each stage's BUSY time — the
        # stages overlap, so the wall clock is `wall_critical_path`,
        # ~max(stage) rather than sum(stages). t_fetch lands when the
        # materialization barrier runs.
        # rebinding the stats dict holds repo.stats (guard manifest,
        # analysis/guards.py): stage threads _stat_add concurrently
        # once the load streams, and tools read the dict after
        with self._stats_lock:
            self.last_bulk_stats = dict(
                _STATS0, docs=len(new_docs), t_sql=round(register.dur, 3)
            )

        ready_ids: List[str] = []
        clock_rows: Dict[str, Dict[str, int]] = {}
        back._begin_bulk_actors()
        try:
            # -- phases 2-4: io -> spec -> pack -> dispatch, streamed
            # per slab ------------------------------------------------
            memo_hits, fallback_docs = self._load_slabs(
                new_docs, cursor_map, slab, ready_ids, clock_rows
            )
            stats = self.last_bulk_stats
            for pct, part, rest in (
                ("cols_bulk_pct", "cols_bulk_feeds", "cols_single_feeds"),
                ("heads_snapshot_pct", "heads_snapshot_feeds",
                 "heads_probed_feeds"),
                ("pack_gather_native_pct", "pack_gather_native_feeds",
                 "pack_gather_twin_feeds"),
                ("pack_gate_native_pct", "pack_gate_native_feeds",
                 "pack_gate_twin_feeds"),
                ("rga_vmem_cells_pct", "rga_vmem_cells", "rga_xla_cells"),
            ):
                stats[pct] = _pct(stats[part], stats[part] + stats[rest])
            stats["pack_general_pct"] = _pct(
                stats["pack_general_docs"], len(new_docs)
            )
            stats["slab_waste_x"] = round(
                stats["cells_padded"] / max(1, stats["rows_real"]), 3
            )
            stats["memo"] = len(memo_hits)
            stats["fallback"] = len(fallback_docs)
            stats["fast"] = len(new_docs) - len(fallback_docs)
            for (doc, spec, clock, n_changes, actor_ids), m in memo_hits:
                back._init_bulk_doc(
                    doc, clock, n_changes, actor_ids,
                    back._doc_snapshot_fn(spec, clock),
                    ready_ids, clock_rows,
                )
                self._pending_memo.append((doc.id, m))
            with Stage(
                "pipeline.clock_rows", self._stat_add, "t_sql",
                docs=len(clock_rows),
            ):
                with back.db.bulk():
                    back.clocks.update_many(back.id, clock_rows)
            for doc in fallback_docs:
                back._load_document(doc)
            if fallback_docs:
                log(
                    "repo:backend",
                    f"bulk load: {len(fallback_docs)}/{len(new_docs)} "
                    "docs fell back to per-op host replay "
                    "(non-contiguous feed seqs)",
                )
        except Exception:
            # a failed load must not pin device refs, leave the fetch
            # workers running unjoined (a failure AFTER pipe.run —
            # clock write, fallback replay — still has them: no hm-pipe
            # thread may outlive the load), or hand the barrier a
            # half-fetched pending list
            self._pending_summaries = []
            self._pending_memo = []
            self._bulk_t0 = None  # a later barrier must not stamp
            # wall_critical_path with this dead load's idle time
            self._settle_fetch("failed bulk load's fetch")  # logged:
            # the load's own error is the one to raise
            raise
        finally:
            with telemetry.span("pipeline.actors_flush", "pipeline"):
                back._end_bulk_actors()
        with self._stats_lock:
            # provisional: the barrier extends this through the fetch
            self.last_bulk_stats["wall_critical_path"] = round(
                time.perf_counter() - self._bulk_t0, 3
            )
        ready_ids.extend(already_ready)
        if ready_ids:
            with telemetry.span(
                "pipeline.notify", "pipeline", docs=len(ready_ids)
            ):
                back.to_frontend.push(msgs.bulk_ready_msg(ready_ids))

    def _stat_add(self, key: str, dt: float, stats=None) -> None:
        """Accumulate a stage's seconds into last_bulk_stats (pipeline
        stage threads add concurrently), or into the `stats` dict a
        stage captured when its load began. Microsecond precision:
        rounding each addition to ms would floor a short stage to 0."""
        with self._stats_lock:
            s = self.last_bulk_stats if stats is None else stats
            s[key] = round(s.get(key, 0.0) + dt, 6)

    # ------------------------------------------------------------------
    # stages

    def _load_slabs(
        self, new_docs, cursor_map, slab, ready_ids, clock_rows
    ):
        """Streamed phases 2-4: slab N+1's sidecar IO and pack proceed
        while slab N is on-device and slab N-1's summary is in flight
        to host (backend/pipeline.py). Slabs are formed by length from
        the post-memo-filter entry stream (pipeline.SlabFormer), docs
        in store order inside each. Returns (memo_hits,
        fallback_docs)."""
        back = self._back
        contiguous: Dict[str, bool] = {}
        open_id = self._bulk_open

        # the stages time themselves (pipeline.Stage: one clock pair
        # per stage feeds span, stat and counter); these closures only
        # do the work

        def classify(doc):
            spec, clock, n_changes, actor_ids, ok = back._doc_feed_spec(
                doc.id, contiguous, cursor_map[doc.id]
            )
            if not ok:
                return ("fallback", doc)
            if n_changes == 0:
                back._gate_unknown_empty(doc)
            e = (doc, spec, clock, n_changes, actor_ids)
            m = self._summary_memo.get(doc.id)
            if m is not None and m["clock"] == clock:
                return ("memo", (e, m))
            return ("entry", e)

        def rows(e):
            # the op rows of the doc's feed windows: what its slab's
            # row axis has to hold
            return sum(
                hi - lo for lo, hi in (fc.window(s, t) for fc, s, t in e[1])
            )

        def pack(chunk):
            # on a pack-pool worker (HM_PACK_WORKERS)
            return pack_slab([e[1] for e in chunk])

        stats = self.last_bulk_stats  # captured: the fetch worker can
        # outlive this load; its timings belong to THIS load's stats
        programs: set = set()

        # mesh-aware accounting: the scheduler (built here, before any
        # dispatch, so the fetch stage can size itself) accumulates
        # per-chip dispatch busy time across loads — snapshot now, diff
        # after the run, so the stats carry THIS load's per-chip times
        rr = self._rr
        disp0 = list(rr.t_dispatch_chip) if rr is not None else None
        slabs0 = list(rr.slabs_per_chip) if rr is not None else None

        def fetch(seq, entry):
            wire = entry[3]
            with Stage(
                "pipeline.fetch", busy="fetch", open=open_id, slab=seq,
                parent="pipeline.dispatch",
            ) as sp:
                self._fetch_slab(entry)
            dt = sp.dur
            chip = None
            if rr is not None and hasattr(wire, "devices"):
                try:
                    chip = rr.device_index(next(iter(wire.devices())))
                except Exception:  # non-jax wire / foreign device
                    chip = None
            with self._stats_lock:
                stats["t_fetch_busy"] = round(
                    stats.get("t_fetch_busy", 0.0) + dt, 6
                )
                if chip is not None:
                    per = stats.setdefault(
                        "t_fetch_chips", [0.0] * len(rr.devices)
                    )
                    per[chip] = round(per[chip] + dt, 6)

        pipe = SlabPipeline(
            new_docs,
            prefetch=lambda chunk: self._open_feeds(chunk, cursor_map),
            classify=classify,
            rows=rows,
            former=SlabFormer(slab, SLAB_CELLS, device_min_cells()),
            pack=pack,
            dispatch=lambda seq, chunk, batch: self._dispatch(
                seq, chunk, batch, ready_ids, clock_rows, programs
            ),
            fetch=fetch,
            stat=lambda key, dt: self._stat_add(key, dt, stats),
            slab=slab,
            # fetch overlaps across chips: one worker per device
            fetch_workers=(
                1 if rr is None else min(len(rr.devices), FETCH_WORKERS)
            ),
            pack_workers=pack_worker_count(),
            open_id=open_id,
        )
        ctx = FetchContext()
        try:
            memo_hits, fallbacks = pipe.run(ctx)
        finally:
            if rr is not None:
                rr.release()  # dispatching done: drop backpressure refs
        with self._stats_lock:
            # pool shape + per-worker busy lanes: sum(busy) can exceed
            # the wall once packs overlap — a trace draws one lane per
            # worker (speedup = sum(busy)/wall)
            stats["pack_workers"] = pipe.pack_workers
            stats["t_pack_busy_per_worker"] = [
                round(b, 6) for b in pipe.pack_busy
            ]
            stats["t_pack_wall"] = round(pipe.pack_wall(), 6)
            if rr is not None:
                stats["t_dispatch_chips"] = [
                    round(b - a, 6)
                    for a, b in zip(disp0, rr.t_dispatch_chip)
                ]
                stats["slabs_per_chip"] = [
                    b - a for a, b in zip(slabs0, rr.slabs_per_chip)
                ]
        self._fetch_ctx = ctx
        return memo_hits, fallbacks

    def _open_feeds(self, docs, cursor_map) -> None:
        """The io stage of `docs`: open every cursor actor's feed, then
        load the actors' column sidecars."""
        needed = self._collect_cursor_actors(docs, cursor_map)
        with telemetry.span(
            "storage.feeds.open", "storage", feeds=len(needed)
        ):
            actors = [self._back._get_or_create_actor(a) for a in needed]
        with telemetry.span(
            "storage.columns.load", "storage", feeds=len(actors)
        ) as sp:
            bulk, single = self._prefetch_columns(actors)
            sp.note(bulk=bulk)
        _M_COLS_BULK.add(bulk)
        _M_COLS_SINGLE.add(single)
        with self._stats_lock:
            stats = self.last_bulk_stats
            stats["feeds"] += len(needed)
            stats["cols_bulk_feeds"] += bulk
            stats["cols_single_feeds"] += single

    def _collect_cursor_actors(self, docs, cursor_map) -> List[str]:
        """The chunk's cursor actors, each once, in doc order."""
        return list(
            dict.fromkeys(a for d in docs for a in cursor_map[d.id])
        )

    def _prefetch_columns(self, actors: List[Any]) -> Tuple[int, int]:
        """Load the column sidecars of a chunk's actors. First the head
        of every cold actor's feed, in one batch (FeedStore.
        resolve_heads): answered by the store's head snapshot where a
        clean close sealed one, probed feed by feed (`.len` + `stat`)
        where not; after it `Actor.seq_head` is a list length. Feeds
        whose sidecar is one complete v3 image in the corpus slab,
        level with the feed head, load slab-granular: one pass over the
        chunk's extents (colcache.load_slab_images; views of the
        mapping, no copy, no per-feed parse). Every other feed (a v2
        tail, a legacy or memory sidecar, HM_SLAB=0, a sidecar ahead of
        or behind its feed) loads through Actor.columns(), feed by
        feed, on this thread: both are mmap slices and Python, which no
        thread pool speeds up (it only took the GIL from the pack
        worker). Returns (feeds loaded slab-granular, feeds loaded one
        by one); feeds whose cache was loaded already count in neither.
        Who answered the heads goes to last_bulk_stats
        (heads_snapshot_feeds / heads_probed_feeds) and the counters of
        the same names."""
        cold = [a for a in actors if not a.colcache.loaded]
        bulk: set = set()
        slab = self._back._col_slab
        cands: List[Any] = []
        if slab is not None:
            # hint the chunk's extents into the page cache first (the
            # NEXT chunk's hint overlaps this chunk's pack)
            slab.prefetch([a.id for a in actors])
            cands = [a for a in cold if a.colcache.slab is slab]
        with telemetry.span(
            "storage.columns.heads", "storage", feeds=len(cold)
        ) as sp:
            snap, probed = self._back.feeds.resolve_heads(
                [a.feed for a in cold]
            )
            sp.note(probed=probed)
            # the staleness rule, batched: a sidecar installs only if
            # it holds exactly its feed head's count of changes
            heads = [a.seq_head for a in cands]
        _M_HEADS_SNAP.add(snap)
        _M_HEADS_PROBED.add(probed)
        with self._stats_lock:
            stats = self.last_bulk_stats
            for key, n in (
                ("heads_snapshot_feeds", snap),
                ("heads_probed_feeds", probed),
            ):
                stats[key] = stats.get(key, 0) + n
        if cands:
            with telemetry.span(
                "storage.columns.bulk", "storage", feeds=len(cands)
            ):
                done = load_slab_images(
                    slab, [a.colcache for a in cands], heads
                )
            bulk = {a.id for a, d in zip(cands, done) if d}
        rest = [a for a in actors if a.id not in bulk]
        if rest:
            with telemetry.span(
                "storage.columns.single", "storage", feeds=len(rest)
            ):
                for a in rest:
                    a.columns()  # loads, or catches a loaded one up
        return len(bulk), len(cold) - len(bulk)

    def _dispatch(
        self, seq, chunk, batch, ready_ids, clock_rows, programs
    ):
        """One packed slab -> async device dispatch + deferred doc init.
        Returns the pending-summary entry (a mutable list: the fetch
        worker replaces its wire slot with parsed host arrays).
        `programs` gathers the load's distinct device programs,
        (D, N, A, K, P, lean).

        The whole of it is the `pipeline.dispatch` stage (it runs on
        the loading thread, inside `pipeline.bulk_load`, whose open id
        comes down to it, and holds back the next slab): host-arg
        narrowing, upload and the jitted call are its child spans, and
        their ends feed t_narrow / t_upload / t_dispatch."""
        from ..ops.crdt_kernels import (
            batch_is_lean, bucket_doc_actors, rga_rounds_in_vmem,
            run_batch_full,
        )
        from ..ops.host_kernel import run_batch_host
        from ..ops.materialize import DecodedBatch, decode_patch

        stats = self.last_bulk_stats
        with Stage("pipeline.dispatch", busy="dispatch", slab=seq) as sp:
            min_cells = device_min_cells()
            # host clocks (authoritative, from sidecar metadata) for
            # every doc in the slab, padded docs empty — lets the device
            # path skip the seq wire entirely
            slab_clocks = [e[2] for e in chunk] + [{}] * (
                batch.n_docs - len(chunk)
            )
            _da, a_loc, k_loc = bucket_doc_actors(batch)
            shape = (batch.n_docs, batch.n_rows)
            cells, real = shape[0] * shape[1], int(batch.n_ops.sum())
            _M_CELLS_PADDED.add(cells)
            _M_ROWS_REAL.add(real)
            with self._stats_lock:
                stats["slabs"] += 1
                stats["slab_shapes"] += (shape,)
                stats["cells_padded"] += cells
                stats["rows_real"] += real
                if batch.packed_by == "general":
                    stats["pack_general_docs"] += len(chunk)
                    native, twin = batch.gather_feeds
                    stats["pack_gather_native_feeds"] += native
                    stats["pack_gather_twin_feeds"] += twin
                native, twin = batch.gate_feeds
                stats["pack_gate_native_feeds"] += native
                stats["pack_gate_twin_feeds"] += twin
                stats["a_loc_max"] = max(stats["a_loc_max"], a_loc)
                stats["pred_max"] = max(
                    stats["pred_max"], batch.psrc.shape[1]
                )
            lean = False
            if cells < min_cells:
                with telemetry.timed(
                    "pipeline.enqueue", "pipeline", host=1,
                    D=shape[0], N=shape[1],
                ):
                    out = run_batch_host(batch)
                summary = None
                with self._stats_lock:
                    stats["host_slabs"] += 1
            else:
                from ..ops import compile_cache

                platform = compile_cache.ensure()  # may init the backend
                with self._stats_lock:
                    stats["device_slabs"] += 1
                    stats["platform"] = platform
                # no INC ops + host clocks in hand -> skip the seq and
                # value wires (~4 of 14 bytes/op uploaded) AND the
                # summary wire's clock section
                lean = batch_is_lean(batch)
                programs.add(
                    shape + (a_loc, k_loc, batch.psrc.shape[1], lean)
                )
                rga = (
                    "rga_vmem_cells" if rga_rounds_in_vmem(shape[1])
                    else "rga_xla_cells"
                )
                _M_RGA_CELLS[rga].add(cells)
                with self._stats_lock:
                    stats["slab_programs"] = len(programs)
                    stats[rga] += cells
                rr = self._rr
                if rr is not None:
                    # multi-chip: successive WHOLE slabs land on
                    # successive devices (bounded in-flight queues per
                    # device) — chips run independent programs
                    out, summary = rr.dispatch(batch, lean=lean)
                    with self._stats_lock:
                        stats["rr_slabs"] = stats.get("rr_slabs", 0) + 1
                        stats.setdefault("rr_devices", len(rr.devices))
                else:
                    out, summary = run_batch_full(batch, lean=lean)
                # start the device->host copy of the ONE fused wire
                # buffer now, so that the fetch overlaps the transfer
                # with later slabs' pack + compute
                try:
                    summary.copy_to_host_async()
                except AttributeError:  # non-device backend
                    pass
            dec = DecodedBatch(batch, out, host_clocks=slab_clocks)
            entry = [[e[0].id for e in chunk], batch, dec, summary, lean]
            self._pending_summaries.append(entry)
            with telemetry.span(
                "pipeline.init_docs", "pipeline", docs=len(chunk)
            ):
                for j, (doc, _spec, clock, n_changes, actor_ids) in (
                    enumerate(chunk)
                ):
                    self._back._init_bulk_doc(
                        doc, clock, n_changes, actor_ids,
                        lambda dec=dec, j=j: decode_patch(
                            dec.doc_view(j), 0
                        ),
                        ready_ids, clock_rows,
                    )
        for kid, key in _DISPATCH_KIDS:
            self._stat_add(key, sp.kids.get(kid, 0.0))
        return entry

    def _fetch_slab(self, entry) -> None:
        """Transfer + parse one slab's summary wire (the fetch stage:
        runs on the pipeline's fetch worker so the barrier finds host
        arrays already decoded; idempotent for host-kernel slabs).

        This runs even for loads whose caller never hits the barrier
        (the frontend OpenBulk path) — deliberately: the parse swaps
        the pinned DEVICE wire buffer for a compact host dict, so a
        barrier-less cold open releases its device memory as the
        worker drains instead of pinning every slab's wire until the
        next load, and a late barrier is nearly free."""
        from ..ops.materialize import fetch_summary

        _ids, batch, _dec, wire, lean = entry
        if wire is None or isinstance(wire, dict):
            return
        nbytes = getattr(wire, "nbytes", 0)
        entry[3] = fetch_summary(wire, batch, lean)
        if nbytes:
            _M_D2H.add(nbytes)

    @functools.cached_property
    def _rr(self):
        """The round-robin slab scheduler when more than one device is
        visible (HM_MESH=0 holds the loader to one), else None. Built
        on the first load, on the loading thread."""
        if os.environ.get("HM_MESH", "1") == "0":
            return None
        import jax

        # a JAX error here propagates: a backend that cannot come up is
        # not "one device"
        if len(jax.devices()) < 2:
            return None
        from ..parallel.sharded import SlabRoundRobin

        # whole slabs per chip, same kernels
        return SlabRoundRobin(jax.devices())

    # ------------------------------------------------------------------
    # the barrier and the summary memo

    def fetch_summaries(self):
        """The materialization barrier for the preceding bulk load:
        every slab's fused summary wire buffer (winner/liveness masks
        bit-packed, element order at ceil(log2 N) bits/entry, narrow
        counts; clock section only on non-lean runs) on the host — ONE
        device buffer per slab — as decoded BulkSummaries. Docs the
        summary memo served (clock unchanged since their last fetch)
        transfer nothing. After this, any doc in the load renders
        host-side with no further device work. Clears the pending refs
        and refreshes the memo with the freshly fetched rows.

        The fetch workers already transferred + parsed each slab's wire
        while later slabs were packing/dispatching; this barrier joins
        them (re-raising any fetch failure) and assembles host-side
        only — `t_fetch` records the residual (non-overlapped) wait,
        while `t_fetch_busy` holds the workers' busy time.

        Runs under `repo.bulk` (the guard of the pending accumulators,
        analysis/guards.py): a barrier racing a new load would
        otherwise swap the pending lists out from under each other —
        the load's stale-join path still covers barrier-less loads."""
        from ..ops.materialize import BulkSummaries

        with self._mutex:
            pending = self._pending_summaries
            memo_pending = self._pending_memo
            fetch_ctx = self._fetch_ctx
            wall_t0 = self._bulk_t0
            planes0 = self._bulk_planes0
            self._pending_summaries = []
            self._pending_memo = []
            self._fetch_ctx = None
            # one barrier per load — cleared up front so neither a
            # fetch failure below nor a later (empty) barrier call can
            # restamp the critical path with idle wall time
            self._bulk_t0 = None
            with Stage(
                "pipeline.barrier", open=self._bulk_open,
                parent="repo.open_many", slabs=len(pending),
            ) as barrier:
                if fetch_ctx is not None:
                    fetch_ctx.join()  # PipelineError on fetch failure
                out = BulkSummaries(
                    pending, memo_slabs=self._memo_slabs(memo_pending)
                )
                self._memoize_summaries(out, pending, memo_pending)
        with self._stats_lock:
            self.last_bulk_stats["t_fetch"] = round(barrier.dur, 3)
            if wall_t0 is not None:
                stats = self.last_bulk_stats
                stats["wall_critical_path"] = round(
                    time.perf_counter() - wall_t0, 3
                )
                built = int(_M_PLANES_BUILT.value() - planes0)
                stats["cols_planes_built"] = built
                stats["cols_planes_built_pct"] = _pct(
                    built, stats["cols_bulk_feeds"]
                )
        return out

    def close(self) -> None:
        """A barrier-less bulk load (frontend OpenBulk) may still have
        a fetch worker draining device buffers: settle it."""
        with self._mutex:
            self._settle_fetch("bulk fetch at close")

    def _settle_fetch(self, what: str) -> None:
        """Join the fetch workers nobody barriered for, under
        `repo.bulk`; their error, if any, does not vanish with the
        discarded context but goes to the log."""
        ctx, self._fetch_ctx = self._fetch_ctx, None
        if ctx is not None:
            try:
                ctx.join()
            except Exception as e:
                log("repo:backend", f"{what}: {e}")

    def _memo_slabs(self, memo_pending):
        """Memo-served docs as BulkSummaries memo groups (grouped by N
        so rows stack into one arrays dict per bucket)."""
        if not memo_pending:
            return []
        import numpy as np

        from ..ops.crdt_kernels import unpack_bits_le

        groups: Dict[tuple, List] = {}
        for doc_id, m in memo_pending:
            key = (m["N"], len(m["clock_row"]))
            groups.setdefault(key, []).append((doc_id, m))
        out = []
        for (N, _A), items in groups.items():
            def bits(key):
                return unpack_bits_le(
                    np.stack([m[key] for _d, m in items]), N
                )

            arrays = {
                "map_winner": bits("mw_bits"),
                "elem_live": bits("el_bits"),
                "elem_order": np.stack(
                    [m["order"] for _d, m in items]
                ).astype(np.int64),
                "n_live_elems": np.asarray(
                    [m["n_live"] for _d, m in items], np.int64
                ),
                "n_map_entries": np.asarray(
                    [m["n_map"] for _d, m in items], np.int64
                ),
                # the real [A_loc] local-slot clock rows, same columnar
                # contract as fetched slabs (arrays()['clock'])
                "clock": np.stack([m["clock_row"] for _d, m in items]),
            }
            out.append((
                [d for d, _m in items],
                arrays,
                [m["clock"] for _d, m in items],
            ))
        return out

    def _memoize_summaries(self, summaries, pending, memo_pending) -> None:
        """Refresh the per-doc summary memo from freshly fetched slab
        rows (byte-bounded LRU)."""
        cap = int(os.environ.get("HM_SUMMARY_MEMO_MB", "256")) << 20
        if cap <= 0:
            return
        import numpy as np

        memo = self._summary_memo
        for doc_id, m in memo_pending:  # served rows stay warm
            if doc_id in memo:
                memo.move_to_end(doc_id)
        for i, (doc_ids, batch, dec, _wire, _lean) in enumerate(pending):
            if dec.host_clocks is None:
                continue  # no authoritative clock: not memoizable
            arrays = summaries.slabs[i][2]
            N = batch.n_rows
            mwb = np.packbits(
                arrays["map_winner"], axis=1, bitorder="little"
            )
            elb = np.packbits(
                arrays["elem_live"], axis=1, bitorder="little"
            )
            odt = np.int16 if N < 2**15 else np.int32
            order = arrays["elem_order"].astype(odt)
            clock_arr = np.asarray(arrays["clock"], np.int32)
            for j, doc_id in enumerate(doc_ids):
                old = memo.pop(doc_id, None)
                if old is not None:
                    self._summary_memo_bytes -= _memo_entry_bytes(old)
                entry = {
                    "clock": dict(dec.host_clocks[j]),
                    "N": N,
                    "n_live": int(arrays["n_live_elems"][j]),
                    "n_map": int(arrays["n_map_entries"][j]),
                    "mw_bits": mwb[j].copy(),
                    "el_bits": elb[j].copy(),
                    "order": order[j].copy(),
                    "clock_row": clock_arr[j].copy(),
                }
                memo[doc_id] = entry
                self._summary_memo_bytes += _memo_entry_bytes(entry)
        while memo and self._summary_memo_bytes > cap:
            _d, old = memo.popitem(last=False)
            self._summary_memo_bytes -= _memo_entry_bytes(old)
