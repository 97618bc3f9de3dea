"""ServeTier — reads served from HBM-resident state (ISSUE 11).

The read path of the Repo facade, rebuilt for "millions of users,
mostly readers": instead of materializing a doc host-side per request
(summary fetch + parse — the stubborn cold-open constant), the tier
keeps each warm doc's summary columns resident in device memory
(serve/resident.py) and answers reads with batched query kernels
(serve/kernels.py) over the whole concurrent read batch
(serve/batcher.py). The host half of a read: a `lookup`, `index` or
`len` read decodes one row (`_row_leaf`) or none; a `text` read joins
every live character, as array work over the resident entry's host
columns (`_join_text`: three takes, ops/columnar.decode_value_rows for
the whole run, one join — no Python call a character).

A write does not cost its doc its residency: a local change of a
shape whose lanes are closed-form (one element inserted, one key SET,
one element deleted) is noted on the doc's entry by the write path's
hook (`note_clock_moved`) and applied by the next read's flush, on the
device, to the lanes it already holds (`_advance`: one
`serve.advance{ops,rung}` span, counters `serve.advance_notes` /
`serve.advances` / `serve.advance_dispatches`); every other write
(remote patches, ticks, the shapes and cases `ResidencyCache.note`
refuses: `serve.advance_refusals{why}`, one series a reason) releases
the entry and the next read installs the doc again.

A flush is one `serve.batch{reads,cold}` span; below it, also when
nothing is cold: `serve.batch.attach{docs}` (the residency check, and
under it a `serve.advance` for each entry that had changes noted),
then ONE `serve.dispatch{kind,B,N}` a row bucket and round of the path
walk, with its blocking `serve.dispatch.fetch` (serve/kernels.py): the
query programs take their container as (object row, key), so a read's
string step and the question behind it leave together (`_resolve`;
counter `serve.fused_steps`), in the program of the widest answer the
group's reads ask for. Behind a seq_order, one `serve.decode{reads,
rows}` (the group's text joins and index / path steps; the answers go
out after it). Counters `serve.text_reads` / `serve.text_rows` give the
joins' work.

Read queries (all JSON-safe; `path` is map keys (str) / sequence
indices (int) from the root):

    {"kind": "lookup", "path": [..., key]}   -> leaf value / type marker
    {"kind": "index",  "path": [...], "index": i} -> element value
    {"kind": "text",   "path": [...]}        -> joined text string
    {"kind": "len",    "path": [...]}        -> entry / element count
    {"kind": "clock"}                        -> {actor: seq}
    {"kind": "history"}                      -> history length

`host_read` is the bit-identical twin (HM_SERVE=0 and the graceful-
degradation path): per-request host materialization through
snapshot_patch -> FrontendDoc -> traversal — exactly the cost the tier
amortizes away, kept observable so the fuzz tests can pin both paths
to the same answers. Clock/history queries sit on host metadata in
both modes (the device-resident clock matrix is PR 3's mirror; no
second copy here).

Degradation ladder (never an error to the reader): unresident or
unrebuildable doc -> host path (serve.fallbacks); device OOM during
install -> evict LRU + retry once (serve.evictions_pressure) -> host
path; admission queue full -> host path; a batch flush that raises (a
query kernel the device refuses) -> host path for every read still
pending, counted in serve.flush_errors — the reader gets the host
twin's value, and the counter says the device path is broken. A
repeated host-path read of a clock-unmoved doc hits the tier's host
memo — zero wire parse on the warm fallback too.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .. import telemetry
from ..crdt import clock as clockmod
from ..crdt.frontend_state import FrontendDoc
from ..models import Counter, Table, Text
from ..ops.columnar import VK_STR, decode_value, decode_value_rows
from ..utils.debug import log
from .batcher import ReadBatcher, ReadRequest
from .resident import REFUSALS, ResidencyCache, build_group, rung_of

READ_KINDS = ("lookup", "index", "text", "len", "clock", "history")

_MAX_PATH_ROUNDS = 64  # path depth bound (per-level batched dispatches)

# what a read asks of a round's dispatch (`ServeTier._ask`), narrowest
# answer first: kernels.KINDS[ask] is the program that gives it, and
# every wider one gives it too
_STEP, _COUNT, _ORDER = range(3)
_FINAL = {"lookup": _STEP, "len": _COUNT, "text": _ORDER, "index": _ORDER}

# the longest a flush waits for a writer to leave a cold doc's emission
# domain (clock moved, block not appended yet) before it lets the host
# path answer: an append is well under a millisecond, a durable ack
# (HM_ACK_DURABLE) one group commit
EMISSION_WAIT_S = 1.0

# serve.read_s: six edges a decade, the service plane's SLO (50 ms) and
# its half among them. The default decades (.., 10 ms, 50 ms, ..) read
# every read over 10 ms as one AT the SLO (a quantile is its bucket's
# upper edge), which sent the ladder up under 12-18 ms of healthy reads
READ_BUCKETS_S = tuple(
    round(m * 10.0 ** e, 9)
    for e in range(-4, 2) for m in (1.0, 1.5, 2.5, 3.5, 5.0, 7.5)
)


def _leaf(v: Any) -> Any:
    """JSON-safe leaf of a materialized value: containers collapse to
    type markers (reads address into them by path instead)."""
    if isinstance(v, Counter):
        return int(v)
    if isinstance(v, Text):
        return {"_type": "text"}
    if isinstance(v, Table):
        return {"_type": "table"}
    if isinstance(v, dict):
        return {"_type": "map"}
    if isinstance(v, list):
        return {"_type": "list"}
    return v


def _walk(tree: Any, steps: List) -> Any:
    """Follow `steps` through a materialized tree; None when the path
    breaks (missing key, index out of bounds, scalar mid-path)."""
    cur = tree
    for s in steps:
        if isinstance(s, str):
            if isinstance(cur, Table):
                cur = cur.by_id(s)
            elif isinstance(cur, dict):
                cur = cur.get(s)
            else:
                return None
        elif isinstance(s, int):
            if isinstance(cur, (list, Text)) and 0 <= s < len(cur):
                cur = cur[s]
            else:
                return None
        else:
            return None
    return cur


def host_value(doc, query: Dict) -> Any:
    """Evaluate one read against a materialized tree — the per-request
    host path (`tree` reuse is the tier's host-memo seam)."""
    return _eval_tree(_host_tree(doc), query)


def _host_tree(doc) -> Any:
    patch = doc.snapshot_patch()
    if patch is None:
        return None
    front = FrontendDoc()
    front.apply_patch(patch)
    return front.materialize()


def _eval_tree(tree: Any, query: Dict) -> Any:
    if tree is None:
        return None
    kind = query.get("kind")
    path = list(query.get("path") or [])
    if kind == "lookup":
        if not path or not isinstance(path[-1], str):
            return None
        container = _walk(tree, path[:-1])
        if isinstance(container, Table):
            return _leaf(container.by_id(path[-1]))
        if not isinstance(container, dict):
            return None
        if path[-1] not in container:
            return None
        return _leaf(container[path[-1]])
    target = _walk(tree, path)
    if kind == "text":
        return str(target) if isinstance(target, Text) else None
    if kind == "index":
        i = query.get("index")
        if not isinstance(i, int) or not isinstance(
            target, (list, Text)
        ) or not 0 <= i < len(target):
            return None
        return _leaf(target[i])
    if kind == "len":
        if isinstance(target, (dict, list, Text, Table)):
            return len(target)
        return None
    return None


def host_read(doc, query: Dict) -> Optional[Dict[str, Any]]:
    """The HM_SERVE=0 twin: one read, fully host-side, per request.
    Returns the same {"value": ...} payload the tier produces (None
    payload = doc unknown/not ready, same as the tier)."""
    kind = query.get("kind")
    if kind not in READ_KINDS:
        return None
    if kind == "clock":
        return {"value": clockmod.clock_to_strs(doc.clock)}
    if kind == "history":
        return {"value": doc.history_len}
    if not doc._announced:
        return None
    return {"value": host_value(doc, query)}


def _join_text(e, order) -> str:
    """The string of a `text` read: the resident entry's elements in
    `order` (the live rows the seq_order program returned), joined by
    array work over the entry's host columns. One take for the
    elements' winning value rows, one for their kinds, one for their
    codes; a text of strings is then one take of the page's strings
    table and one join — no Python call a character. A text that holds
    anything else goes through `decode_value_rows` (a fix-up pass a
    kind present): such an element contributes str(value), a counter
    element its value plus its INCs."""
    rows = e.elem_val[order]
    vkind = e.vkind[rows]
    t = e.tables
    if not np.count_nonzero(vkind != VK_STR):
        return "".join(t.chars[e.value[rows]].tolist())
    vals = decode_value_rows(
        vkind, e.value[rows], t.strings, t.floats, t.bigints
    )
    counters = np.nonzero(e.dt[rows] == 1)[0]
    for j, total in zip(
        counters.tolist(), e.inc_total[rows[counters]].tolist()
    ):
        vals[j] = (vals[j] or 0) + total  # fold accumulated INCs
    return "".join(map(str, vals))


class ServeTier:
    """One per RepoBackend (HM_SERVE=1, the default)."""

    def __init__(self, backend) -> None:
        self._back = backend
        self._cache = ResidencyCache()
        self._batcher = ReadBatcher(self._flush)
        # host fallback memo: doc_id -> (clock, materialized tree,
        # byte estimate). Shares the serving invalidation check with
        # the residency cache (clock equality) under the same lock
        # class; budgeted like the device half.
        self._host_memo: "OrderedDict[str, tuple]" = OrderedDict()
        self._host_memo_bytes = 0
        self._closed = False
        # when the flusher last came out of an install (perf_counter):
        # the reads admitted before it waited behind that install
        self._install_end = 0.0
        # why the last failed install degraded to the host path (the
        # Telemetry reply carries it: a worker that cannot reach its
        # device says so instead of quietly serving from host)
        self._last_install_error: Optional[str] = None
        reg = telemetry.REGISTRY
        inst = str(telemetry.next_instance())
        self._m: Dict[str, Any] = {
            k: reg.counter("serve." + k, inst=inst)
            for k in (
                "reads", "hits", "installs", "invalidations",
                "fallbacks", "evictions", "evictions_pressure",
                "batches", "memo_hits", "host_memo_hits", "dispatches",
                "overload_shed", "flush_errors",
                # reads whose string step and the question behind it
                # left in one dispatch
                "fused_steps",
                # installs by group (one pack, one upload) and by who
                # computed the docs' kernel lanes where the summary memo
                # did not hold them: the slab program on the device, or
                # its numpy twin (a CPU process under the loader's gate)
                "install_groups", "install_device_docs",
                "install_host_kernel_docs",
                # the text joins of the flushes: reads joined, and the
                # characters (element rows) they held
                "text_reads", "text_rows",
                # what writes cost the tier: reads whose doc had no
                # fresh entry when their flush looked, installs of a doc
                # that was resident before, and those of them that went
                # up a length rung (four times the lanes, other programs)
                "cold_reads", "reinstalls", "rung_promotions",
                # entries that follow local changes in place: changes
                # noted on an entry, ops applied to entries' lanes and
                # rows, program calls they took
                "advance_notes", "advances", "advance_dispatches",
            )
        }
        # why each write that met an entry released it all the same
        # (`mark_stale`): ONE counter, labelled by the reason
        self._refused: Dict[str, Any] = {
            why: reg.counter("serve.advance_refusals", inst=inst, why=why)
            for why in REFUSALS
        }
        for k in (
            "resident_docs", "resident_bytes", "resident_device_bytes",
            "queue_depth",
        ):
            self._m[k] = reg.gauge("serve." + k, inst=inst)
        self._hist = reg.histogram(
            "serve.read_s", buckets=READ_BUCKETS_S, inst=inst
        )
        # the service plane's p99 feed: reads answered from resident
        # state. A read that paid for its own doc's install, or waited
        # on this thread behind another's, is the cold reader's cost,
        # not pressure: counted as overload it walks the ladder to
        # BROWNOUT, which defers the very installs that would end the
        # cold reads
        self._hist_warm = reg.histogram(
            "serve.read_warm_s", buckets=READ_BUCKETS_S, inst=inst
        )

    # ------------------------------------------------------------------
    # public surface (RepoBackend routes reads here)

    def read_async(
        self, doc, query: Dict, cb: Callable[[Any], None]
    ) -> None:
        """Answer one read; `cb(payload)` fires on the batcher thread
        (or inline for metadata reads and degraded paths)."""
        self._m["reads"].add(1)
        kind = query.get("kind")
        req = ReadRequest(doc.id, dict(query), cb)
        req.t0 = time.perf_counter()
        req.span = telemetry.begin("serve.read", "serve", kind=kind)
        if kind == "clock":
            self._finish(req, clockmod.clock_to_strs(doc.clock))
            return
        if kind == "history":
            self._finish(req, doc.history_len)
            return
        if kind not in READ_KINDS:
            self._finish_raw(req, None)
            return
        if self._closed:
            self._m["fallbacks"].add(1)
            self._fallback(req, doc)
            return
        if not self._batcher.submit(req):
            # admission overflow is traffic pressure, not a device
            # degradation: its own signal (serve.overload_shed, never
            # serve.fallbacks), routed through the service plane — a
            # typed refusal in SHED, the host path below it
            self._m["overload_shed"].add(1)
            ctl = getattr(self._back, "overload", None)
            refusal = (
                ctl.refuse_overflow(query.get("tenant"))
                if ctl is not None else None
            )
            if refusal is not None:
                self._finish_raw(req, refusal)
            else:
                self._fallback(req, doc)
            return
        self._m["queue_depth"].set(self._batcher.depth)

    def read(self, doc, query: Dict, timeout: float = 30.0) -> Any:
        """Blocking convenience over read_async (bench, tools)."""
        done = threading.Event()
        slot: List[Any] = [None]

        def fin(payload):
            slot[0] = payload
            done.set()

        self.read_async(doc, query, fin)
        if not done.wait(timeout):
            raise TimeoutError("serve tier read timed out")
        return slot[0]

    def note_clock_moved(self, doc_id: str, event=None) -> None:
        """Write-path hook (patch emissions, live ticks): the doc's
        serving clock moved. A local change of a shape the resident
        entry can follow (serve/resident.py `ResidencyCache.note`) is
        noted on the entry, and the next read's flush applies it to
        the lanes the device holds (`_advance`); after anything else (a
        remote patch, a tick, a full bucket, a new key, ...) the entry
        can never serve again and is released, as the host memo row
        always is. Reads would catch a moved clock at their own check
        anyway — the hook makes the invalidation eager and the counters
        exact. Called under the doc's emission domain: bookkeeping
        only."""
        if event is not None and event["type"] == "LocalPatch":
            why = self._cache.note(
                doc_id, event["change"], event["patch"].clock
            )
        else:
            why = "remote"
        if why is None:
            self._m["advance_notes"].add(1)
        elif self._cache.mark_stale(doc_id):
            self._m["invalidations"].add(1)
            self._refused[why].add(1)
        with self._cache._lock:
            row = self._host_memo.pop(doc_id, None)
            if row is not None:
                self._host_memo_bytes -= row[2]

    def drop(self, doc_id: str) -> None:
        """close_doc/destroy: forget every cached read artifact."""
        self._cache.drop(doc_id)
        with self._cache._lock:
            row = self._host_memo.pop(doc_id, None)
            if row is not None:
                self._host_memo_bytes -= row[2]

    def residency_report(self) -> Dict[str, Any]:
        rep = self._cache.report()
        if self._last_install_error is not None:
            rep["last_install_error"] = self._last_install_error
        return rep

    @property
    def refusals(self) -> Dict[str, int]:
        """Writes that met an entry and released it, by reason."""
        return {
            why: int(c.value()) for why, c in self._refused.items()
            if c.value()
        }

    def flush_now(self, timeout: float = 5.0) -> bool:
        return self._batcher.flush_now(timeout)

    def close(self) -> None:
        self._closed = True
        self._batcher.close()
        self._cache.clear()
        telemetry.REGISTRY.retire(
            *self._m.values(), *self._refused.values(), self._hist,
            self._hist_warm,
        )

    # ------------------------------------------------------------------
    # the batch flush

    def _flush(self, reqs: List[ReadRequest]) -> None:
        """Resolve one admitted batch. Must never raise (a raised
        flush would re-queue the batch in the debouncer and double-
        fire callbacks): every failure lane degrades per-request. A
        flush that raises is a broken device path, not a broken read:
        it is counted (serve.flush_errors) and every read still
        pending is answered by the host twin — never None, which is
        the legitimate answer for a path that does not exist."""
        try:
            with telemetry.span(
                "serve.batch", "serve", reads=len(reqs)
            ) as sp:
                self._m["batches"].add(1)
                sp.note(cold=self._flush_inner(reqs))
        except Exception as e:
            self._m["flush_errors"].add(1)
            log("serve", f"batch flush failed: {e!r}")
            for r in reqs:
                if r.done:
                    continue
                doc = self._back.docs.get(r.doc_id)
                try:
                    if doc is not None:
                        self._fallback(r, doc)
                except Exception as e2:  # the host twin failed too
                    log("serve", f"host read {r.doc_id[:6]}: {e2!r}")
                self._finish_raw(r, None)  # no-op once answered
        finally:
            self._m["queue_depth"].set(self._batcher.depth)

    def _flush_inner(self, reqs: List[ReadRequest]) -> int:
        """Resolve the batch; the cold docs it installed."""
        by_doc: Dict[str, List[ReadRequest]] = {}
        for r in reqs:
            by_doc.setdefault(r.doc_id, []).append(r)
            if r.t0 < self._install_end:
                # admitted while this thread was installing another
                # flush's cold docs: it waited for that install, so it
                # paid an install's cost as the cold reader did, and is
                # no more pressure than that one (the cure is installs
                # off this thread, not BROWNOUT, which would hand the
                # stale docs of a written store to the host twin)
                r.cold = True
        ready: List[ReadRequest] = []
        cold: List = []  # (doc, clock, reqs) needing an install
        with telemetry.span(
            "serve.batch.attach", "serve", docs=len(by_doc)
        ):
            for doc_id, rs in by_doc.items():
                doc = self._back.docs.get(doc_id)
                if doc is None or not doc._announced:
                    for r in rs:
                        self._finish_raw(r, None)
                    continue
                clock = doc.clock
                entry, behind = self._fresh(doc_id, clock)
                if entry is None:
                    self._m["cold_reads"].add(len(rs))
                    cold.append((doc, clock, rs, behind))
                    continue
                self._m["hits"].add(len(rs))
                self._attach(entry, rs, ready)
        # warm requests dispatch BEFORE any cold doc's install runs:
        # a hot read's latency must not absorb a cold neighbor's
        # pack+kernel (the install cost belongs to the cold reader)
        if ready:
            self._resolve(ready)
        ready = []
        ctl = getattr(self._back, "overload", None)
        install: List = []
        for doc, clock, rs, behind in cold:
            for r in rs:
                r.cold = True
            if behind:
                # a live entry the clock has left behind: a writer is
                # between its clock move and its note (inside the doc's
                # emission domain). Once it has left, the entry has the
                # change noted and follows it: no install
                doc.emission.wait_out(EMISSION_WAIT_S)
                entry, _behind = self._fresh(doc.id, doc.clock)
                if entry is not None:
                    self._attach(entry, rs, ready)
                    continue
            if ctl is not None and ctl.defer_install(len(rs)):
                # brownout: cold installs shed first — the reads
                # still answer (host memo path), the device install
                # waits for the ladder to step down
                for r in rs:
                    self._fallback(r, doc)
                continue
            install.append((doc, clock, rs))
        entries = self._install(
            [(doc, clock) for doc, clock, _rs in install]
        )
        if install:
            self._install_end = time.perf_counter()
        for doc, _clock, rs in install:
            entry = entries.get(doc.id)
            if entry is None:
                self._m["fallbacks"].add(len(rs))
                for r in rs:
                    self._fallback(r, doc)
                continue
            self._attach(entry, rs, ready)
        if ready:
            self._resolve(ready)
        return len(entries)

    def _fresh(self, doc_id: str, clock: Dict[str, int]) -> tuple:
        """(the doc's entry if it serves `clock`, with the local
        changes it noted applied, else None: the doc has to be
        installed; whether a live entry is there all the same, at
        another clock)."""
        entry, deltas = self._cache.get_fresh(doc_id, clock)
        if entry is None or deltas is None:
            return None, entry is not None
        if entry.clock != clock and not self._advance(entry, deltas, clock):
            return None, False
        return entry, False

    def _advance(self, entry, deltas, clock: Dict[str, int]) -> bool:
        """Apply `deltas`, the local changes `entry` noted up to
        `clock`: its host half's rows (`ResidencyCache.follow`), then
        the `serve.advance` program over the lanes the device holds,
        which are donated to it (this thread, the flusher, is their
        only reader, and is between dispatches). False if a write
        released the entry since it was looked up, or the device
        refused (the entry is released): the doc installs as a stale
        one does."""
        from . import kernels

        with telemetry.span(
            "serve.advance", "serve", ops=len(deltas), rung=entry.bucket
        ):
            if not self._cache.follow(entry, deltas, clock):
                return False
            if not deltas:
                return True  # a change of no op: the clock alone
            desc = np.asarray(
                [d[:kernels.DELTA_WIDTH] for d in deltas], np.int32
            )
            try:
                entry.dev = kernels.advance(entry.dev, desc)
            except Exception as e:
                log("serve", f"advance {entry.doc_id[:6]} failed: {e!r}")
                self._last_install_error = repr(e)[:500]
                self._cache.mark_stale(entry.doc_id)
                return False
            self._m["advances"].add(len(desc))
            self._m["advance_dispatches"].add(
                -(-len(desc) // kernels.ADVANCE_OPS)
            )
        return True

    @staticmethod
    def _attach(entry, rs, ready) -> None:
        for r in rs:
            r.entry = entry
            r.obj_row = -1
            r.steps = list(r.query.get("path") or [])
            ready.append(r)

    def _install(self, cold: List) -> Dict[str, Any]:
        """Build + install the resident entries of a flush's cold docs
        `cold` = [(doc, clock)], a length rung (resident.rung_of) at a
        time, outside every lock. {doc id: entry} of the docs built;
        a doc the sidecars cannot rebuild (dirty/unbacked), or whose
        group failed, is absent (host path). A build that loses a clock
        race still serves this batch but is not cached."""
        groups: Dict[int, List] = {}
        docs = {}
        for doc, clock in cold:
            spec = self._back._serveable_spec(clock)
            if spec is None:
                # a local change moves the doc's clock and then appends
                # its block, both inside the doc's emission domain: a
                # flush that looks in between finds the feed one change
                # short of the clock. That is a writer at work, not a
                # doc the sidecars cannot serve: once it has left (it
                # may have, since the look), clock and feed agree again
                doc.emission.wait_out(EMISSION_WAIT_S)
                clock = doc.clock
                spec = self._back._serveable_spec(clock)
            if spec is None:
                continue
            docs[doc.id] = doc
            groups.setdefault(rung_of(spec), []).append(
                (doc.id, clock, spec)
            )
        built: Dict[str, Any] = {}
        for bucket in sorted(groups):
            for entry in self._build(groups[bucket], bucket):
                built[entry.doc_id] = entry
        if not built:
            return built
        self._m["installs"].add(len(built))
        evicted = 0
        for doc_id, entry in built.items():
            prior = self._cache.prior_bucket(doc_id)
            if prior is not None:
                self._m["reinstalls"].add(1)
                if entry.bucket > prior:
                    self._m["rung_promotions"].add(1)
            if docs[doc_id].clock == entry.clock:  # install-and-recheck
                evicted += len(self._cache.install(entry))
        if evicted:
            self._m["evictions"].add(evicted)
        self._m["resident_docs"].set(self._cache.resident_docs)
        self._m["resident_bytes"].set(self._cache.resident_bytes)
        self._m["resident_device_bytes"].set(self._cache.device_bytes)
        return built

    def _build(self, items: List, bucket: int) -> List:
        """One group's entries, with the OOM ladder: evict LRU + retry
        once, then nothing (host path). A group that fails for another
        reason is built again doc by doc, so that one corrupt sidecar
        sends its own doc to the host path and not its neighbours'."""
        self._m["install_groups"].add(1)

        def count(name: str, n: int) -> None:
            self._m[name].add(n)

        for attempt in (0, 1):
            try:
                return build_group(self._back, items, bucket, count)
            except Exception as e:
                if (
                    attempt == 0
                    and _looks_like_oom(e)
                    and self._cache.resident_docs > 0
                ):
                    # device memory pressure: shed LRU residents and
                    # give the install one more chance before degrading
                    shed = self._cache.evict_lru(serve_max_bytes_retry())
                    self._m["evictions_pressure"].add(len(shed))
                    log(
                        "serve",
                        f"install of {len(items)} docs hit device "
                        f"pressure; evicted {len(shed)} LRU entries, "
                        f"retrying",
                    )
                    continue
                # a deterministic build failure (corrupt sidecar, pack
                # bug) must NOT thrash healthy residents out of the
                # cache on every read of the one broken doc — only
                # genuine memory pressure earns a shed
                log(
                    "serve",
                    f"install {items[0][0][:6]} (+{len(items) - 1}) "
                    f"failed: {e!r}",
                )
                self._last_install_error = repr(e)[:500]
                if len(items) > 1 and not _looks_like_oom(e):
                    return [
                        entry for item in items
                        for entry in self._build([item], bucket)
                    ]
                return []
        return []

    # ------------------------------------------------------------------
    # batched path resolution + query dispatch

    def _resolve(self, reqs: List[ReadRequest]) -> None:
        """Walk the reads' paths in rounds. A round's reads of one row
        bucket leave in ONE dispatch: it takes each read's next string
        step and, where nothing but a question (or an int step) lies
        behind that, answers the question about the container the step
        led to, which the device resolves itself (`_ask`)."""
        from . import kernels

        live = [r for r in reqs if not r.done]
        for _round in range(_MAX_PATH_ROUNDS):
            if not live:
                return
            for r in live:
                r.ask = self._ask(r)
                if r.ask is None:
                    self._finish(r, None)
            for group in self._by_bucket([r for r in live if not r.done]):
                self._dispatch(kernels, group)
            # every round either finishes a request or consumes one of
            # its path steps, so this converges in <= depth rounds
            live = [r for r in reqs if not r.done]
        # pathological path depth: stop dispatching rounds, but keep
        # the twin contract — the host path answers what the kernel
        # walk did not finish (degrade, never a wrong None)
        for r in live:
            doc = self._back.docs.get(r.doc_id)
            if doc is None:
                self._finish_raw(r, None)
            else:
                self._m["fallbacks"].add(1)
                self._fallback(r, doc)

    def _ask(self, r: ReadRequest) -> Optional[int]:
        """What `r` asks of this round, as the index of the narrowest
        program that answers it (kernels.KINDS): _STEP (a string step
        with more path behind it, or a final `lookup`: the winner's
        row), _COUNT (a final `len`) or _ORDER (a final `text` /
        `index`, or an int step); the last two about `r.obj_row`
        (`r.qkey` -1) or about what one string step from it leads to
        (`r.qkey` the step's key index; the step leaves `r.steps`
        here). None: the host can tell that the path breaks."""
        r.qkey = -1
        if r.steps and isinstance(r.steps[0], str):
            # a key the doc never saw resolves host-side
            r.qkey = r.entry.key_index.get(r.steps.pop(0), -1)
            if r.qkey < 0:
                return None
        if not r.steps:
            ask = _FINAL.get(r.query.get("kind"))
            if ask == _STEP and r.qkey < 0:
                return None  # lookup with an exhausted path
        elif isinstance(r.steps[0], str):
            return _STEP
        else:
            ask = _ORDER if isinstance(r.steps[0], int) else None
        if ask is None or (r.qkey < 0 and not self._sound(r, r.obj_row)):
            return None
        return ask

    @staticmethod
    def _sound(r: ReadRequest, row: int) -> bool:
        """Whether the container at `row` is one of which `r` can ask
        what it asks next: an int step or an `index` a sequence, a
        `text` a text, a `len` any."""
        otype = r.entry.obj_type(row)
        kind = r.query.get("kind")
        if r.steps:
            return otype in ("list", "text")
        if kind == "index":
            return isinstance(r.query.get("index"), int) and otype in (
                "list", "text"
            )
        return kind != "text" or otype == "text"

    @staticmethod
    def _by_bucket(rs: List[ReadRequest]) -> List[List[ReadRequest]]:
        """The requests of one dispatch each: one row bucket, at most
        kernels.MAX_BATCH of them."""
        from .kernels import MAX_BATCH

        groups: Dict[int, List[ReadRequest]] = {}
        for r in rs:
            groups.setdefault(r.entry.bucket, []).append(r)
        return [
            group[at:at + MAX_BATCH]
            for group in groups.values()
            for at in range(0, len(group), MAX_BATCH)
        ]

    def _dispatch(self, kernels, group: List[ReadRequest]) -> None:
        """One dispatch answers the group, through the program of the
        widest answer any of its reads asks for (`lookup`s alone run
        map_lookup, a `len` among them makes it counts, a `text` or an
        int step seq_order); each read takes from its outputs what it
        asked for, and only the outputs some read uses are fetched.
        The group's host half runs to its end before any of its
        answers goes out; behind a seq_order it is one `serve.decode`
        span, which so holds no reader's callback."""
        widest = max(r.ask for r in group)
        keyed = any(r.qkey >= 0 for r in group)
        skip = () if keyed else (kernels.ROW, kernels.FOUND)
        if all(r.ask != _COUNT for r in group):
            skip += (kernels.N_MAP,)
        out = getattr(kernels, kernels.KINDS[widest])(
            [r.entry for r in group], [r.obj_row for r in group],
            [r.qkey for r in group], skip,
        )
        self._m["dispatches"].add(1)
        self._m["fused_steps"].add(
            sum(r.qkey >= 0 and r.ask != _STEP for r in group)
        )
        answers: List = []  # (request, value) this group finished
        texts = rows = 0
        with telemetry.span(
            "serve.decode", "serve",
            reads=sum(r.ask == _ORDER for r in group),
        ) if widest == _ORDER else telemetry.NOOP as sp:
            for i, r in enumerate(group):
                e = r.entry
                if r.qkey >= 0 and not self._take_step(
                    r, int(out[kernels.ROW][i]), out[kernels.FOUND][i],
                    answers,
                ):
                    continue
                if r.ask == _STEP:
                    continue  # the next round goes on from r.obj_row
                n = int(out[kernels.N_ELEMS][i])
                if r.ask == _COUNT:
                    if e.obj_type(r.obj_row) not in ("list", "text"):
                        n = int(out[kernels.N_MAP][i])
                    answers.append((r, n))
                    continue
                order = out[kernels.ORDER][i]
                if not r.steps and r.query.get("kind") == "text":
                    answers.append((r, _join_text(e, order[:n])))
                    texts += 1
                    rows += n
                    continue
                if r.steps:  # int path step: descend through it
                    idx, descend = r.steps.pop(0), True
                else:  # final "index" query on the resolved sequence
                    idx, descend = r.query.get("index"), False
                if not isinstance(idx, int) or not 0 <= idx < n:
                    answers.append((r, None))
                    continue
                w = int(e.elem_val[int(order[idx])])
                if not descend:
                    answers.append((r, self._row_leaf(e, w)))
                elif e.obj_type(w) is not None:
                    r.obj_row = w
                else:
                    answers.append((r, None))  # scalar mid-path
            sp.note(rows=rows)
        self._m["text_reads"].add(texts)
        self._m["text_rows"].add(rows)
        for r, value in answers:
            self._finish(r, value)

    def _take_step(self, r: ReadRequest, w: int, found, answers) -> bool:
        """The string step the dispatch took for `r`, which led to row
        `w`: answered here (into `answers`), or True: `r` goes on from
        the container `w`, of which its question can be asked."""
        if not found:
            answers.append((r, None))
        elif not r.steps and r.query.get("kind") == "lookup":
            answers.append((r, self._row_leaf(r.entry, w)))
        elif r.entry.obj_type(w) is None:
            answers.append((r, None))  # scalar mid-path
        elif r.ask != _STEP and not self._sound(r, w):
            answers.append((r, None))
        else:
            r.obj_row = w  # descend into the linked object
            return True
        return False

    # ------------------------------------------------------------------
    # host-side row decode (the host half of a device-served read)

    def _row_value(self, e, row: int) -> Any:
        v = decode_value(
            int(e.vkind[row]), int(e.value[row]), int(e.dt[row]),
            e.tables,
        )
        if int(e.dt[row]) == 1:  # counter: fold accumulated INCs
            v = (v or 0) + int(e.inc_total[row])
        return v

    def _row_leaf(self, e, row: int) -> Any:
        otype = e.obj_type(row)
        if otype is not None:
            return {"_type": otype}
        return self._row_value(e, row)

    # ------------------------------------------------------------------
    # degraded path + completion

    def _fallback(self, req: ReadRequest, doc) -> None:
        """Host-path read with the warm-doc memo: a clock-unmoved doc
        re-reads from its cached materialized tree — zero wire parse
        even when degraded."""
        if not doc._announced:
            self._finish_raw(req, None)
            return
        clock = doc.clock
        with self._cache._lock:
            row = self._host_memo.get(doc.id)
            tree = (
                row[1] if row is not None and row[0] == clock else None
            )
            if tree is not None:
                self._host_memo.move_to_end(doc.id)
        if tree is not None:
            self._m["host_memo_hits"].add(1)
        else:
            tree = _host_tree(doc)
            if tree is not None and doc.clock == clock:
                self._memoize_host(doc.id, clock, tree)
        self._finish(req, _eval_tree(tree, req.query))

    def _memoize_host(self, doc_id: str, clock, tree) -> None:
        from .resident import serve_max_bytes

        # byte estimate: clock rows + a flat per-change constant; the
        # cap is a budget, not an audit
        est = 256 + 96 * sum(clock.values())
        cap = serve_max_bytes()
        with self._cache._lock:
            old = self._host_memo.pop(doc_id, None)
            if old is not None:
                self._host_memo_bytes -= old[2]
            self._host_memo[doc_id] = (dict(clock), tree, est)
            self._host_memo_bytes += est
            while self._host_memo and self._host_memo_bytes > cap:
                _d, row = self._host_memo.popitem(last=False)
                self._host_memo_bytes -= row[2]

    def _finish(self, req: ReadRequest, value: Any) -> None:
        self._finish_raw(req, {"value": value})

    def _finish_raw(self, req: ReadRequest, payload: Any) -> None:
        if req.done:
            return
        req.done = True
        took = time.perf_counter() - req.t0
        self._hist.observe(took)
        if not req.cold:
            self._hist_warm.observe(took)
        if req.span is not None:
            req.span.end()
        try:
            # a reader's code on the flusher's thread: the one place
            # where a client can hold every other client
            with telemetry.span("serve.callback", "serve"):
                req.cb(payload)
        except Exception as e:  # a reader's cb must not kill the batch
            log("serve", f"read callback failed: {e!r}")


def serve_max_bytes_retry() -> int:
    """Bytes the OOM retry tries to free: half the budget — enough to
    matter, without flushing the whole cache for one hot doc."""
    from .resident import serve_max_bytes

    return max(1, serve_max_bytes() // 2)


def _looks_like_oom(e: Exception) -> bool:
    """Device allocation failures worth an evict-and-retry (XLA
    surfaces RESOURCE_EXHAUSTED through several exception types, so
    match on the message too)."""
    if isinstance(e, MemoryError):
        return True
    msg = str(e).lower()
    return "resource_exhausted" in msg or "out of memory" in msg
