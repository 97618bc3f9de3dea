"""Streaming slab pipeline — overlap IO → pack → dispatch → fetch.

Run one stage after another and a cold open costs the SUM of its
per-slab stage costs: sidecar IO, spec, pack, upload/dispatch, and the
summary fetch (BENCH_r05: 9.45s = 0.37 sql + 1.99 io + 0.21 spec +
2.96 pack + ~0.1 wire + 2.68 fetch + 1.14 other). But the stages are
independent per slab: slab N+1's sidecar reads and pack need nothing
from slab N beyond host buffers, and slab N's device work needs nothing
from the host at all. This module is the classic software-pipelining /
double-buffering move from accelerator input pipelines: four stages
connected by small BOUNDED queues so the cold open costs ~max(stage)
instead of sum(stages), with at most `QUEUE_DEPTH` slabs of host
staging alive per seam — double buffering, not an unbounded backlog.

    io/spec thread:   slab read-ahead: feed opens, then the chunk's
                      column sidecars in one pass over the corpus
                      slab's mapping (colcache.load_slab_images: views,
                      no copy; feeds it cannot take load one by one).
                      All of it is Python under the GIL but the feed
                      head lookups, so it runs on this one thread: a
                      pool only took the GIL from the pack worker.
                      Then per-doc feed specs, binned into slabs by
                      length (SlabFormer): a slab goes to the pack
                      pool when its rung fills, the rest when the
                      stream ends; docs keep store order in a slab.
    pack pool:        pack_docs_columns on HM_PACK_WORKERS threads —
                      the native hm_pack_prefix call is bound through
                      ctypes.CDLL and therefore RELEASES the GIL
                      (native/__init__.py pack_parallel_ok), so N
                      workers pack N slabs on N cores concurrently.
                      Sharding is slab-granular and the emit into the
                      dispatch queue is SEQUENCED (a turn counter under
                      the pipeline.pack_pool condition), so slab order
                      and bytes stay identical to a single worker's
                      no matter which worker finishes first. Per-worker
                      busy seconds are kept apart
                      (pack_busy[w]) so busy-vs-wall accounting stays
                      honest — the SUM of pack busy can exceed the
                      load's wall once packs genuinely overlap.
    caller thread:    async device upload + dispatch (round-robin
                      across visible devices via parallel/sharded.py
                      SlabRoundRobin, or the one device) plus deferred
                      doc init; never blocks on results.
    fetch workers:    summary wire transfer + host parse for slab N
                      overlapped with slab N+1's pack; with >1 device
                      one worker per chip (bounded, bulk_loader.
                      FETCH_WORKERS) so fetches overlap ACROSS chips
                      too. The
                      materialization barrier (fetch_bulk_summaries)
                      joins them and finds host arrays.

Failure contract: any stage raising aborts the whole pipeline — every
queue drains, every worker joins (bounded), device refs drop, and the
caller sees one PipelineError carrying the original exception. A fetch
failure after the load returned surfaces at the barrier via
FetchContext.join.
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Any, Callable, List, Optional, Tuple

from ..analysis.lockdep import make_condition, make_lock
from .. import telemetry
from ..ops.columnar import round_up_pow2

# process-wide pipeline series (telemetry registry): cumulative stage
# busy seconds + slab counts across every bulk load, and live queue
# depth gauges — the "what is the cold open doing RIGHT NOW" view
# tools/top.py renders. last_bulk_stats stays the per-load truth
# (the benchmark's bulk_stats reader); these are the daemon-lifetime
# aggregate.
_M_SLABS = telemetry.counter("pipeline.slabs")
_M_BUSY = {
    stage: telemetry.counter(f"pipeline.{stage}_busy_s")
    for stage in ("io", "pack", "dispatch", "fetch")
}


class Stage:
    """One timed stage of a bulk load, as a context manager yielding
    its span. The clock is read once at each end (telemetry.timed) and
    that one pair feeds all three books: the span (ring / profiler,
    when live), the load's `last_bulk_stats` key (`stat(key, s)`) and
    the process-wide `pipeline.<busy>_busy_s` counter."""

    __slots__ = ("sp", "stat", "key", "busy")

    def __init__(
        self, name: str, stat: Optional[Callable[[str, float], None]] = None,
        key: Optional[str] = None, busy: Optional[str] = None, **ids: Any,
    ) -> None:
        self.sp = telemetry.timed(name, "pipeline", **ids)
        self.stat = stat
        self.key = key
        self.busy = busy

    def __enter__(self) -> telemetry.SpanHandle:
        return self.sp.__enter__()

    def __exit__(self, *exc) -> None:
        self.sp.__exit__(*exc)
        if self.key is not None:
            self.stat(self.key, self.sp.dur)
        if self.busy is not None:
            _M_BUSY[self.busy].add(self.sp.dur)


class PipelineError(RuntimeError):
    """A pipeline stage failed; the original exception is __cause__."""


class _Abort(Exception):
    """Internal: another stage failed; unwind quietly."""


_DONE = object()
_POLL_S = 0.05
_JOIN_S = 120.0
# bounded depth of each stage queue (the fetch seam holds twice it)
QUEUE_DEPTH = 2

# the ladder of row rungs a load's docs are binned by: these tops, and
# above the last every power of two (131,072, 262,144 ...). A slab's
# rows are its rung's top or, under a step of 4, half of it, so a store
# that fills its rungs asks for one program a rung. The 1,024-op
# yardstick store sits on a rung of its own. The step is 4 where cells
# are cheap and 2 where they are dear: a cell of a 262,144-row slab
# costs the chip two to three times a cell of a 1,024-row one (18
# rounds of 32-bit gathers for 10 of 16-bit), and such a slab holds so
# few docs that the pow2 doc axis pads most. Measured on the chip
# against a step of 4 all the way, on a store whose lengths halve in
# count as they double (PERF.md, PR 30): 7 programs for 6, 2.1 padded
# cells a real one for 2.5, 5.3 s of kernel an open for 7.9. A step of
# 2 all the way (12 programs there, reckoned at 2.2 cells) was not run.
ROW_RUNGS = (256, 1024, 4096, 16384, 65536)


class SlabFormer:
    """Forms a load's slabs by length, as the specced entries stream
    in store order.

    An entry of `rows` op rows belongs to the lowest rung of the
    ladder above whose top is >= rows. A slab holds entries of
    one rung, in store order, at most `capacity(top)` of them: `slab`
    docs (HM_BULK_SLAB), or as many as keep docs x top within `cells`
    where that is fewer. The pack pads it to the pow2 over its longest
    doc, as ever, so a slab never counts more than `cells` cells and a
    store of one length forms the slabs that chunks of `slab` docs in
    store order would: `add` hands a rung's slab back the moment it is
    full.

    `flush` ends the stream with the rungs' remainders. One that would
    fall under the device gate (`min_cells`: its pow2 docs x
    pow2 rows) joins the remainder of the next occupied rung where the
    two fit one slab of that rung, so a thin bucket is not answered by
    the host twin while a slab above has room for it, and no merge
    ever costs more than the one slab it saves. The top rung's
    remainder has nowhere to go and is dispatched as it is. The
    remainders go out highest rung first: the slab of the longest docs
    has the longest kernel and the largest wire, and its fetch then
    runs beside the others' kernels instead of after the last of
    them."""

    def __init__(self, slab: int, cells: int, min_cells: int = 0) -> None:
        self.slab = max(1, int(slab))
        self.cells = int(cells)
        self.min_cells = int(min_cells)
        self._n = 0  # store-order index of the next entry
        # rung top -> [(index, rows, entry)], in store order
        self._bins: dict = {}

    def capacity(self, top: int) -> int:
        return max(1, min(self.slab, self.cells // top))

    @staticmethod
    def rung(rows: int) -> int:
        for top in ROW_RUNGS:
            if rows <= top:
                return top
        return round_up_pow2(rows)

    def add(self, rows: int, entry: Any) -> Optional[List[Any]]:
        """Bin one entry; the slab it completes, or None."""
        top = self.rung(rows)
        held = self._bins.setdefault(top, [])
        held.append((self._n, rows, entry))
        self._n += 1
        if len(held) < self.capacity(top):
            return None
        del self._bins[top]
        return [e for _i, _r, e in held]

    def _thin(self, held: List[tuple]) -> bool:
        rows = max(r for _i, r, _e in held)
        return round_up_pow2(len(held)) * round_up_pow2(rows) < self.min_cells

    def flush(self) -> List[List[Any]]:
        """The slabs of what `add` still holds, highest rung first."""
        out: List[List[Any]] = []
        tops = sorted(self._bins)
        for at, top in enumerate(tops):
            held = self._bins.pop(top)
            above = tops[at + 1] if at + 1 < len(tops) else None
            if (
                above is not None
                and self._thin(held)
                and len(held) + len(self._bins[above])
                <= self.capacity(above)
            ):
                self._bins[above] = sorted(held + self._bins[above])
                continue
            out.append([e for _i, _r, e in held])
        return out[::-1]


def pack_worker_count() -> int:
    """Size of the pack pool. HM_PACK_WORKERS=N pins N workers; 0 (the
    default) resolves automatically: min(4, cores) when the native pack
    entry points both drop the GIL and are safe to call concurrently
    (native.pack_parallel_ok — stateless C loops into caller-owned
    buffers), else 1 — the numpy scatter twin holds the GIL for long
    stretches, so extra pack threads would only contend."""
    v = int(os.environ.get("HM_PACK_WORKERS", "0") or 0)
    if v > 0:
        return v
    from .. import native

    if not native.pack_parallel_ok():
        return 1
    return max(1, min(4, os.cpu_count() or 1))


class FetchContext:
    """Handle on the async fetch stage (one or more workers — with >1
    device the fetch overlaps ACROSS chips: each worker can be pulling
    a different chip's wire concurrently). The barrier
    (BulkLoader.fetch_summaries) joins it before decoding; a
    fetch error recorded during the overlap window re-raises there."""

    def __init__(self) -> None:
        self.threads: List[threading.Thread] = []
        self.error: Optional[BaseException] = None

    def join(self, timeout: float = _JOIN_S) -> None:
        for t in self.threads:
            t.join(timeout)
            if t.is_alive():  # pragma: no cover - defensive
                raise PipelineError("pipeline fetch stage did not drain")
        if self.error is not None:
            raise PipelineError(
                "bulk summary fetch failed"
            ) from self.error


class SlabPipeline:
    """One bulk load's stage executor. All callables are supplied by
    the BulkLoader (which owns locks, stats, and device handles):

      prefetch(doc_chunk)      read-ahead actors + sidecar columns
      classify(doc)            -> ("entry", e) | ("memo", (e, m))
                                  | ("fallback", doc)
      rows(e)                  an entry's op rows (what SlabFormer
                                  bins it by)
      pack(entries)            -> ColumnarBatch
      dispatch(seq, entries, batch) -> pending summary entry (runs on
                                  the CALLER thread — device dispatch
                                  and doc init stay single-threaded)
      fetch(seq, entry)        transfer + parse one slab's summary
                                  (mutates the entry in place)
      stat(key, seconds)       adds a stage's seconds to the load's
                                  stats (t_io, t_spec, t_pack)

    `slab` is the io stage's chunk (docs read ahead at once) and
    `former` the SlabFormer that cuts the specced entries into slabs.
    io, spec, form and pack are timed here (`Stage`); dispatch and
    fetch time themselves in the loader, which keeps per-chip books from the
    same readings. Every span carries `open=open_id` and its `slab`
    (io, spec and form their `chunk`: `_io_loop`);
    each blocking queue / turn wait is a `pipeline.wait` span.
    """

    def __init__(
        self,
        docs: List[Any],
        *,
        prefetch: Callable[[List[Any]], None],
        classify: Callable[[Any], Tuple[str, Any]],
        rows: Callable[[Any], int],
        former: SlabFormer,
        pack: Callable[[List[Any]], Any],
        dispatch: Callable[[int, List[Any], Any], Any],
        fetch: Callable[[int, Any], None],
        stat: Callable[[str, float], None],
        slab: int,
        fetch_workers: int = 1,
        pack_workers: int = 1,
        open_id: int = 0,
    ) -> None:
        self.docs = docs
        self.prefetch = prefetch
        self.classify = classify
        self.rows = rows
        self.former = former
        self.pack = pack
        self.dispatch = dispatch
        self.fetch = fetch
        self.stat = stat
        self.open_id = open_id
        self.slab = max(1, int(slab))
        self.fetch_workers = max(1, int(fetch_workers))
        self.pack_workers = max(1, int(pack_workers))
        depth = QUEUE_DEPTH
        self.pack_q: "queue.Queue" = queue.Queue(maxsize=depth)
        self.disp_q: "queue.Queue" = queue.Queue(maxsize=depth)
        self.fetch_q: "queue.Queue" = queue.Queue(maxsize=2 * depth)
        # live queue-depth gauges (one table per seam, process-wide:
        # concurrent loads share the gauges — last writer wins, which
        # is the right answer for a "now" view)
        self._q_gauges = {
            id(self.pack_q): telemetry.gauge("pipeline.q_pack"),
            id(self.disp_q): telemetry.gauge("pipeline.q_dispatch"),
            id(self.fetch_q): telemetry.gauge("pipeline.q_fetch"),
        }
        self._q_names = {
            id(self.pack_q): "pack",
            id(self.disp_q): "dispatch",
            id(self.fetch_q): "fetch",
        }
        self.abort = threading.Event()
        self.error: Optional[BaseException] = None
        self.error_stage: Optional[str] = None
        self._err_lock = make_lock("pipeline.err")
        self.memo_hits: List[Any] = []
        self.fallbacks: List[Any] = []
        # -- pack pool sequencing + per-worker busy accounting ---------
        # slabs are packed CONCURRENTLY but emitted into disp_q in slab
        # order: a worker holding packed slab `seq` waits its turn on
        # the pack_pool condition, so downstream (dispatch, fetch, doc
        # init) sees the slab stream one pack thread would produce.
        self._pack_cv = make_condition("pipeline.pack_pool")
        self._pack_turn = 0         # next slab seq allowed to emit
        self._pack_eof_claimed = False  # one worker forwards _DONE
        self.total_slabs: Optional[int] = None  # set by io before EOF
        # per-worker slots, single-writer by construction (worker w is
        # the only writer of index w) — read after the workers join
        self.pack_busy = [0.0] * self.pack_workers
        self.pack_t0 = [None] * self.pack_workers  # first pack start
        self.pack_t1 = [None] * self.pack_workers  # last pack end

    # -- queue plumbing (abort-aware: a failed stage must never leave a
    # sibling blocked forever on a full/empty bounded queue) ----------

    # Each blocking wait is a `pipeline.wait` span naming its queue and
    # side. A put carries the slab that waited (work held back by a
    # full queue or by its turn); a get is a worker starved of work and
    # carries none, so a slab's waits never overlap its own busy spans.

    def _wait(self, q_name: str, side: str):
        return telemetry.begin(
            "pipeline.wait", "pipeline", open=self.open_id, q=q_name,
            side=side,
        )

    def _put(self, q: "queue.Queue", item: Any) -> None:
        """Queue items are (slab seq, ...) tuples, or _DONE."""
        sp = self._wait(self._q_names[id(q)], "put")
        try:
            while True:
                if self.abort.is_set():
                    raise _Abort()
                try:
                    q.put(item, timeout=_POLL_S)
                    self._q_gauges[id(q)].set(q.qsize())
                    return
                except queue.Full:
                    continue
        finally:
            sp.end(**({} if item is _DONE else {"slab": item[0]}))

    def _get(self, q: "queue.Queue") -> Any:
        sp = self._wait(self._q_names[id(q)], "get")
        try:
            while True:
                if self.abort.is_set():
                    raise _Abort()
                try:
                    item = q.get(timeout=_POLL_S)
                    self._q_gauges[id(q)].set(q.qsize())
                    return item
                except queue.Empty:
                    continue
        finally:
            sp.end()

    def _fail(self, stage: str, exc: BaseException) -> None:
        with self._err_lock:
            if self.error is None:
                self.error = exc
                self.error_stage = stage
        self.abort.set()

    # -- stages ---------------------------------------------------------

    def _io_loop(self) -> None:
        """Read-ahead + spec + form: emits slabs as the former fills
        them, and its remainders when the docs end. The io, spec and
        form spans carry the doc chunk's index as `chunk`: a chunk's
        docs land in the slabs of several rungs and a slab holds docs
        of several chunks, so `slab` is the tag of formed slabs alone
        (pack, dispatch, fetch: the dispatch order). The io and spec
        spans carry the chunk's index as `slab` too, as they did when
        a chunk was a slab: the benchmark's accepted readers pair a
        slab's spans by it (`loader.queue_wait_s`), which holds in a
        store of one length. A form span's `slabs` counts what it
        emitted, and the last one, `flush=1`, is the end of the
        stream."""
        try:
            seq = 0
            for base in range(0, len(self.docs), self.slab):
                if self.abort.is_set():
                    raise _Abort()
                chunk = self.docs[base : base + self.slab]
                at = base // self.slab
                ids = {"open": self.open_id, "chunk": at, "slab": at}
                entries: List[Any] = []
                with Stage(
                    "pipeline.io", self.stat, "t_io", "io",
                    parent="pipeline.bulk_load", **ids,
                ):
                    self.prefetch(chunk)
                with Stage(
                    "pipeline.spec", self.stat, "t_spec", "io",
                    parent="pipeline.bulk_load", docs=len(chunk), **ids,
                ):
                    for doc in chunk:
                        kind, payload = self.classify(doc)
                        if kind == "entry":
                            entries.append(payload)
                        elif kind == "memo":
                            self.memo_hits.append(payload)
                        else:
                            self.fallbacks.append(payload)
                seq = self._emit(seq, self._form(entries, False, at))
            chunks = -(-len(self.docs) // self.slab)
            seq = self._emit(seq, self._form([], True, chunks))
            # publish the slab count BEFORE the EOF token: the worker
            # that claims EOF forwarding reads it after taking the
            # token off the queue (queue put/get is the happens-before)
            self.total_slabs = seq
            self._put(self.pack_q, _DONE)
        except _Abort:
            pass
        except BaseException as e:
            self._fail("io", e)

    def _form(
        self, entries: List[Any], flush: bool, chunk: int
    ) -> List[Any]:
        """Bin the specced entries of chunk `chunk` (or, `flush`, end
        the stream); the slabs that are ready for the pack pool."""
        with Stage(
            "pipeline.form", self.stat, "t_form", "io",
            parent="pipeline.bulk_load", docs=len(entries),
            flush=int(flush), open=self.open_id, chunk=chunk,
        ) as sp:
            longest = 0
            ready: List[Any] = []
            for e in entries:
                n = self.rows(e)
                longest = max(longest, n)
                full = self.former.add(n, e)
                if full is not None:
                    ready.append(full)
            if flush:
                ready.extend(self.former.flush())
            sp.note(N=round_up_pow2(longest) if entries else 0, slabs=len(ready))
        return ready

    def _emit(self, seq: int, slabs: List[Any]) -> int:
        # the put blocks on a full queue: that's backpressure WAIT,
        # not io busy, so it stays outside the busy windows
        for entries in slabs:
            self._put(self.pack_q, (seq, entries))
            seq += 1
        return seq

    def _await_pack_turn(self, seq: int) -> None:
        """Block until slab `seq` may emit into disp_q (ordered merge
        of the pack pool's out-of-order completions). Abort-aware."""
        sp = self._wait("turn", "put")
        try:
            with self._pack_cv:
                while self._pack_turn != seq:
                    if self.abort.is_set():
                        raise _Abort()
                    self._pack_cv.wait(_POLL_S)
        finally:
            sp.end(slab=seq)

    def _bump_pack_turn(self) -> None:
        with self._pack_cv:
            self._pack_turn += 1
            self._pack_cv.notify_all()

    def pack_wall(self) -> float:
        """Pack LANE span: first pack start -> last pack end across the
        pool. This is the wall-clock footprint of the pack stage; with
        N workers the busy SUM (sum(pack_busy)) exceeds it once packs
        genuinely overlap, and busy/wall is the measured parallel
        speedup. Read after the workers joined."""
        t0s = [t for t in self.pack_t0 if t is not None]
        t1s = [t for t in self.pack_t1 if t is not None]
        if not t0s or not t1s:
            return 0.0
        return max(0.0, max(t1s) - min(t0s))

    def _pack_loop(self, widx: int) -> None:
        """One pack-pool worker. Workers race through pack_q (slab
        compute overlaps across cores — hm_pack_prefix drops the GIL)
        but emit strictly in slab order via the turn counter, so the
        dispatch stream is byte-identical to a single pack thread. The
        EOF token recirculates to drain siblings; exactly one worker
        claims it and forwards _DONE only after every real slab
        emitted."""
        try:
            while True:
                item = self._get(self.pack_q)
                if item is _DONE:
                    # siblings need the token too
                    self._put(self.pack_q, _DONE)
                    with self._pack_cv:
                        if self._pack_eof_claimed:
                            return
                        self._pack_eof_claimed = True
                    self._await_pack_turn(self.total_slabs)
                    self._put(self.disp_q, _DONE)
                    return
                seq, entries = item
                with Stage(
                    "pipeline.pack", self.stat, "t_pack", "pack",
                    open=self.open_id, slab=seq, parent="pipeline.spec",
                ) as sp:
                    packed = self.pack(entries)
                    sp.note(
                        D=packed.n_docs, N=packed.n_rows,
                        rows=int(packed.n_ops.sum()),
                    )
                self.pack_busy[widx] += sp.dur
                if self.pack_t0[widx] is None:
                    self.pack_t0[widx] = sp.t0
                self.pack_t1[widx] = sp.t0 + sp.dur
                _M_SLABS.add(1)
                # ordered emit: the turn-wait is backpressure, not busy
                self._await_pack_turn(seq)
                self._put(self.disp_q, (seq, entries, packed))
                self._bump_pack_turn()
        except _Abort:
            pass
        except BaseException as e:
            self._fail("pack", e)

    def _fetch_loop(self, ctx: FetchContext) -> None:
        try:
            while True:
                item = self._get(self.fetch_q)
                if item is _DONE:
                    # recirculate the token so sibling workers (fetch
                    # overlaps across chips) see it and drain too
                    self._put(self.fetch_q, _DONE)
                    return
                self.fetch(*item)
        except _Abort:
            pass
        except BaseException as e:
            self._fail("fetch", e)
            ctx.error = e

    # -- driver ---------------------------------------------------------

    def run(self, ctx: FetchContext) -> Tuple[List[Any], List[Any]]:
        """Run the pipeline to completion on the caller thread (which
        owns dispatch + doc init). Returns (memo_hits, fallbacks); the
        fetch thread may still be draining — `ctx` tracks it for the
        barrier. Raises PipelineError if any stage failed."""
        io_t = threading.Thread(
            target=self._io_loop, name="hm-pipe-io", daemon=True
        )
        pack_ts = [
            threading.Thread(
                target=self._pack_loop,
                args=(i,),
                name=f"hm-pipe-pack-{i}",
                daemon=True,
            )
            for i in range(self.pack_workers)
        ]
        fetch_ts = [
            threading.Thread(
                target=self._fetch_loop,
                args=(ctx,),
                name=f"hm-pipe-fetch-{i}",
                daemon=True,
            )
            for i in range(self.fetch_workers)
        ]
        ctx.threads = fetch_ts
        # consumers first, the producer last: every worker is alive
        # before the io thread can finish, so no two of one load's
        # threads ever share an OS thread id (a trace draws one lane
        # per id; a small load's io stage can be done in milliseconds)
        for t in pack_ts:
            t.start()
        for t in fetch_ts:
            t.start()
        io_t.start()
        try:
            while True:
                item = self._get(self.disp_q)
                if item is _DONE:
                    break
                seq, entries, batch = item
                self._put(
                    self.fetch_q, (seq, self.dispatch(seq, entries, batch))
                )
            self._put(self.fetch_q, _DONE)
        except _Abort:
            pass
        except BaseException as e:
            self._fail("dispatch", e)
        # upstream stages are done (or aborting): join them bounded
        io_t.join(_JOIN_S)
        for t in pack_ts:
            t.join(_JOIN_S)
        if self.error is not None:
            # drain so nothing pins batches/device refs, then take the
            # fetch workers down too — the load failed as a unit
            for t in fetch_ts:
                t.join(_JOIN_S)
            for q in (self.pack_q, self.disp_q, self.fetch_q):
                while True:
                    try:
                        q.get_nowait()
                    except queue.Empty:
                        break
            if (
                io_t.is_alive()
                or any(t.is_alive() for t in pack_ts)
                or any(t.is_alive() for t in fetch_ts)
            ):
                raise PipelineError(  # pragma: no cover - defensive
                    f"pipeline stage '{self.error_stage}' failed and "
                    "workers did not drain"
                ) from self.error
            raise PipelineError(
                f"bulk load pipeline stage '{self.error_stage}' failed"
            ) from self.error
        if io_t.is_alive() or any(t.is_alive() for t in pack_ts):
            raise PipelineError(  # pragma: no cover - defensive
                "pipeline workers did not drain"
            )
        return self.memo_hits, self.fallbacks
