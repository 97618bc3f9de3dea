"""Traffic driver `ycsb_rw_loop`: YCSB core workload A over a store made
resident whole (closed loop of client threads; half the operations
reads, half one-op updates; keys zipfian over every doc).

Set-up is `ycsb_read_loop`'s (corpus from the seed, cold open, the load
phase that makes the whole store resident), then the warm phase:
`warm_ops` operations of the mix itself through the client loop (the
hot docs are adopted by the live engine, get their local feed, are
re-installed one rung up), a burst of reads of each kind at each batch
size the window can ask for on docs of both rungs (every query program
and the install's programs compiled), and one read of every doc an
update left stale, so that the window starts with the store resident
whole. The window continues the clients' streams where the warm phase
left them.

An operation is one `Repo.read(url, query, cb)`, timed from send to
callback, or one `Repo.change(url, fn, message)` of ONE op, timed from
call to return: an insert of one character into the text (after this
client's previous insert in that doc, or at a uniformly drawn live
position) or a SET of one root key to a fresh integer; `message` is
`u<serial>`, by which the reference finds the update on disk.
`ops_per_s` is operations completed (reads answered + updates
acknowledged) over the time from the window's start to the last
completion; an operation started inside the window is finished and
counted. `failed` = reads that timed out, raised or came back as a
typed overload + updates that raised.

After the window the repo is closed and `benchmark/reference/rw_plain.py`
holds what is on disk and every answer of the sampled docs to the
replay of corpus + updates; a fresh `Repo` then opens 64 of the sampled
written docs in bulk and their summaries are compared too.
"""

from __future__ import annotations

import gc
import json
import queue
import random
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.drivers import ycsb_read_loop as rl
from benchmark.drivers.ycsb_read_loop import (  # noqa: F401
    KINDS, N_KEYS, PROGRAMS, Traffic, before_jax,
)
from benchmark.harness import Check, Window, log, span

_RAISED = object()
SERIALS_A_CLIENT = 10_000_000
FRESH0 = 1_000_000  # a SET's value: past every value of the corpus


# -- the traffic, from the seed ----------------------------------------------


class Stream:
    """One client's operations, drawn ahead from the seed: for each,
    read or update, the doc, and the read's query or the update's op.
    `at` is the next one; the warm phase and the window share it."""

    def __init__(self, cell, traffic: Traffic, tid: int, m: int) -> None:
        mix = cell.mix
        self.tid = tid
        self.at = 0
        self.docs, self.kinds, self.args = traffic.draw(tid, m, cell.seed)
        rng = np.random.default_rng([cell.seed, 0xA11CE, tid])
        self.update = rng.random(m) < float(mix["operations"]["update"])
        shares = {u["update"]: float(u["share"]) for u in mix["update_mix"]}
        self.insert = rng.random(m) < shares["insert"]
        self.after_prev = rng.random(m) < float(
            mix["typing"]["after_previous"])
        self.u = rng.random(m)
        self.chars = rng.integers(0, 26, m)
        self.keys = rng.integers(0, N_KEYS, m)
        self.prev: Dict[int, int] = {}  # doc -> place of my last insert


class Client(threading.Thread):
    """One client thread: `depth` operations in flight (a read is in
    flight until its callback, an update until its call returns), the
    next begun when one completes. Keeps every update it sent and every
    answer it got, for the reference."""

    def __init__(self, loop: "Loop", stream: Stream, depth: int,
                 ops: float) -> None:
        super().__init__(name=f"bench-client-{stream.tid}", daemon=True)
        self.loop = loop
        self.stream = stream
        self.depth = depth
        self.ops = ops
        self.done_t: List[float] = []
        self.is_update: List[bool] = []
        self.took: List[float] = []
        self.reads: List[Tuple[int, int, float, float, Any]] = []
        self.updates: List[Tuple[int, Any]] = []  # (doc, rw_plain.Update)
        self.failed = 0
        self.error: Optional[str] = None

    def run(self) -> None:
        from benchmark.reference.rw_plain import Update

        loop, st = self.loop, self.stream
        inbox: "queue.SimpleQueue" = queue.SimpleQueue()
        traffic, urls = loop.traffic, loop.urls
        read, change = loop.repo.read, loop.repo.change
        seq_key = traffic.seq_key
        begun = inflight = 0

        def send() -> None:
            nonlocal begun, inflight
            j = st.at
            st.at += 1
            begun += 1
            inflight += 1
            doc = int(st.docs[j])
            if not st.update[j]:
                qid, q = traffic.query(int(st.kinds[j]), int(st.args[j]))
                t0 = time.perf_counter()
                try:
                    read(urls[doc], q,
                         lambda v, doc=doc, qid=qid, t0=t0: inbox.put(
                             (doc, qid, t0, time.perf_counter(), v)))
                except Exception as e:  # a read that raises has failed
                    self.error = repr(e)
                    inbox.put((doc, qid, t0, time.perf_counter(), _RAISED))
                return
            serial = st.tid * SERIALS_A_CLIENT + j
            if st.insert[j]:
                op = {"kind": "ins", "v": chr(97 + int(st.chars[j]))}
                prev = st.prev.get(doc) if st.after_prev[j] else None
                u = float(st.u[j])

                def fn(d, op=op, prev=prev, u=u, doc=doc) -> None:
                    text = d[seq_key]
                    n = len(text)
                    i = min(prev + 1, n) if prev is not None \
                        else min(int(u * (n + 1)), n)
                    text.insert(i, op["v"])
                    st.prev[doc] = i
            else:
                op = {"kind": "set", "k": f"k{int(st.keys[j])}",
                      "v": FRESH0 + serial}

                def fn(d, op=op) -> None:
                    d[op["k"]] = op["v"]
            up = Update(serial, op, time.perf_counter())
            self.updates.append((doc, up))
            try:
                change(urls[doc], fn, f"u{serial}")
                up.acked = time.perf_counter()
                inbox.put((doc, -1, up.sent, up.acked, None))
            except Exception as e:  # an update that raises has failed
                self.error = repr(e)
                inbox.put((doc, -1, up.sent, time.perf_counter(), _RAISED))

        loop.start_gate.wait()
        while inflight < self.depth and begun < self.ops:
            send()
        while inflight:
            try:
                doc, qid, t0, t1, v = inbox.get(timeout=loop.read_timeout)
            except queue.Empty:
                self.failed += inflight  # timed out: nothing answers
                self.error = "read timed out"
                return
            inflight -= 1
            if v is _RAISED or (isinstance(v, dict) and "_overload" in v):
                self.failed += 1
            else:
                self.done_t.append(t1)
                self.took.append(t1 - t0)
                self.is_update.append(qid < 0)
                if qid >= 0:
                    self.reads.append((doc, qid, t0, t1, v))
            if time.perf_counter() < loop.t_end and begun < self.ops:
                send()


class Loop:
    """One run of the closed loop over the clients' streams: for
    `seconds`, or until each client has begun `ops` operations."""

    def __init__(self, cell, state, seconds: float, ops: float) -> None:
        self.repo = state["repo"]
        self.urls = state["urls"]
        self.traffic = state["traffic"]
        self.read_timeout = float(cell.mix["read_timeout_s"])
        streams = state["streams"]
        depth = max(1, int(cell.mix["outstanding"]) // len(streams))
        self.start_gate = threading.Event()
        self.t_end = float("inf")
        self.seconds = seconds
        self.clients = [Client(self, s, depth, ops) for s in streams]

    def run(self, during=None) -> float:
        """Returns the start. `during(t0)` runs on this thread while
        the clients work (the traced part)."""
        for c in self.clients:
            c.start()
        t0 = time.perf_counter()
        self.t_end = t0 + self.seconds
        self.start_gate.set()
        if during is not None:
            during(t0)
        for c in self.clients:
            c.join()
        return t0

    @property
    def failed(self) -> int:
        return sum(c.failed for c in self.clients)

    @property
    def errors(self) -> List[str]:
        return [c.error for c in self.clients if c.error]


# -- set-up ------------------------------------------------------------------


def setup(cell, job) -> Dict[str, Any]:
    from hypermerge_tpu.repo import Repo

    t0 = time.perf_counter()
    urls = job.finish()
    cell.notes["corpus_wait_s"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    with span("bench.facade.repo_init"):
        repo = Repo(path=cell.work + "/repo")
    state: Dict[str, Any] = {"repo": repo, "job": job, "urls": urls,
                             "log": []}
    try:
        with span("bench.loader.open_many"):
            repo.open_many(urls)
        with span("bench.loader.fetch_bulk_summaries"):
            repo.back.fetch_bulk_summaries()
        state["open_stats"] = dict(repo.back.last_bulk_stats)
        cell.notes["cold_open_s"] = round(time.perf_counter() - t0, 3)
        state["tier"] = repo.back.serve is not None
        with span("bench.serve.load"):
            state["install_s"] = rl._load_phase(cell, repo, urls)
        cell.notes["install_s"] = round(state["install_s"], 3)
        traffic = state["traffic"] = Traffic(cell, job)
        clients = int(cell.mix["clients"])
        # operations drawn ahead for each client: more than any chip
        # has completed in the warm phase and a window
        ahead = int(cell.mix["warm_ops"]) // clients + int(
            float(cell.args.seconds) * 3000) + 1000
        state["streams"] = [
            Stream(cell, traffic, i, ahead) for i in range(clients)
        ]
        t0 = time.perf_counter()
        with span("bench.serve.warm"):
            _warm(cell, state)
        cell.notes["warm_s"] = round(time.perf_counter() - t0, 3)
    except BaseException:
        repo.close()
        raise
    return state


def _keep(state, loop: Loop) -> None:
    """What the clients of one loop did, for the reference."""
    state["log"].extend(loop.clients)


def _written(state) -> Dict[int, int]:
    """{doc: updates sent to it so far}."""
    out: Dict[int, int] = {}
    for c in state["log"]:
        for doc, _up in c.updates:
            out[doc] = out.get(doc, 0) + 1
    return out


def _warm(cell, state) -> None:
    repo, urls = state["repo"], state["urls"]
    clients = int(cell.mix["clients"])
    rl._await_healthy(cell)
    loop = Loop(cell, state, float("inf"),
                max(1, int(cell.mix["warm_ops"]) // clients))
    loop.run()
    _keep(state, loop)
    if loop.failed:
        log(f"FAILED: {loop.failed} warm-up operations failed "
            f"({loop.errors[:2]}); ladder (transitions, state) "
            f"{rl._service(cell)}")
        raise SystemExit(5)
    written = _written(state)
    cell.notes["warm_docs_written"] = len(written)
    if state["tier"]:
        t0 = time.perf_counter()
        _settle(cell, state)
        _warm_programs(cell, state, written)
        _settle(cell, state)
        cell.notes["settle_and_programs_s"] = round(
            time.perf_counter() - t0, 3)
    rl._await_healthy(cell)
    cell.notes["resident"] = rl._resident(cell)
    cell.notes["service_setup"] = list(rl._service(cell))


def _settle(cell, state, rounds: int = 20) -> None:
    """One read of every doc that is not resident (an update left it
    stale), at most 16 at once (the page of a window's installs), with
    the ladder HEALTHY (under BROWNOUT an install is deferred and the
    host answers), until the whole store is resident again."""
    from hypermerge_tpu.utils.ids import validate_doc_url

    repo, urls = state["repo"], state["urls"]
    ids = state.setdefault(
        "doc_ids", [validate_doc_url(u) for u in urls])
    for _ in range(rounds):
        have = repo.back.serve.residency_report()["resident"]
        stale = [u for u, i in zip(urls, ids) if i not in have]
        if not stale:
            return
        for at in range(0, len(stale), 16):
            rl._await_healthy(cell)
            _read_all(cell, repo, stale[at:at + 16],
                      cell.mix["load"]["query"])
    log(f"FAILED: {len(stale)} docs are not resident after {rounds} "
        f"rounds of reads")
    raise SystemExit(5)


def _read_all(cell, repo, some_urls, query) -> None:
    """One read of each url, sent at once and awaited."""
    left = threading.Semaphore(0)
    for u in some_urls:
        repo.read(u, query, lambda _v: left.release())
    for _ in some_urls:
        if not left.acquire(timeout=float(cell.mix["read_timeout_s"])):
            log("FAILED: a warm-up read was not answered")
            raise SystemExit(5)


def _warm_programs(cell, state, written: Dict[int, int]) -> None:
    """Every ("serve", program, B, N) the window can ask for: a burst of
    b reads of one kind on docs of one length rung, for each kind, each
    batch size up to `outstanding` and each rung the store now holds (a
    doc never written; a written doc, resident one rung up). The flusher
    may cut a burst, so each is repeated until the program's key shows
    in the program table."""
    from hypermerge_tpu.backend.pipeline import SlabFormer
    from hypermerge_tpu.parallel import sharded
    from hypermerge_tpu.serve import kernels
    from hypermerge_tpu.serve.resident import SERVE_MIN_ROWS

    traffic, repo, urls = state["traffic"], state["repo"], state["urls"]
    ladder = getattr(kernels, "BATCH_BUCKETS", None) or [
        1 << i for i in range(13)
    ]
    most = int(cell.mix["outstanding"])
    sizes = [b for b in ladder if b < most] + [
        next(b for b in ladder if b >= most)
    ]
    by_rung: Dict[int, List[int]] = {}
    for doc, d in enumerate(state["job"].plan):
        rows = d["n_ops"] + written.get(doc, 0)
        by_rung.setdefault(
            SERVE_MIN_ROWS if rows <= SERVE_MIN_ROWS
            else SlabFormer.rung(rows), []).append(doc)
    rng = np.random.default_rng([cell.seed, 0xFACE])
    first: Dict[str, float] = {}
    for rung, docs in sorted(by_rung.items()):
        for kind in ("lookup", "text", "len"):
            _qid, q = traffic.query(KINDS.index(kind), 0)
            for b in sizes:
                t0 = time.perf_counter()
                key = ("serve", PROGRAMS[kind], b, rung)
                for attempt in range(40):
                    if key in sharded.trace_counts:
                        break
                    rl._await_healthy(cell)
                    # the flusher may take a burst whole or let its
                    # first read go alone: try it both ways
                    # (a rung that holds few docs: one doc several
                    # times; a dispatch's batch is its reads)
                    pick = rng.choice(len(docs), b + attempt % 2,
                                      replace=len(docs) <= b)
                    _read_all(cell, repo,
                              [urls[docs[int(i)]] for i in pick], q)
                else:
                    log(f"warm-up: no {PROGRAMS[kind]} program at "
                        f"{b} x {rung}")
                first[f"{PROGRAMS[kind]}_b{b}_n{rung}"] = round(
                    time.perf_counter() - t0, 3)
    cell.notes["warm_first_calls_s"] = first


# -- the window --------------------------------------------------------------


def window(cell, state, seconds: float) -> Window:
    repo = state["repo"]
    p99 = rl._read_p99(repo)
    state["resident0"] = rl._resident(cell)
    state["counters0"] = cell.counters()
    state["service0"] = rl._service(cell)
    loop = Loop(cell, state, seconds, float("inf"))
    trace_s = float(cell.mix["trace_seconds"])
    traced: List[float] = []
    traced_counters: List[Dict[str, float]] = []

    def during(t0: float) -> None:
        if not cell.tracer.on:
            return
        time.sleep(max(0.0, min(seconds / 2, seconds - trace_s - 1)))
        cell.tracer.start()
        traced.append(time.perf_counter())
        traced_counters.append(cell.counters())
        with span("bench.serve.rw_loop"):
            time.sleep(trace_s)
        traced_counters.append(cell.counters())
        traced.append(time.perf_counter())
        cell.tracer.stop()

    # The harness has just collected set-up's garbage; what is left
    # (the store, the docs the warm phase adopted) lives as long as
    # the process. Frozen, it is not walked again, so the window's
    # full collections walk what the window itself allocated: a few
    # of rising size (the same collector seconds in all) instead of
    # one of the whole heap in the middle and a second that falls on
    # the window's end in some runs and past it in others.
    gc.freeze()
    try:
        t0 = loop.run(during)
    finally:
        gc.unfreeze()
    _keep(state, loop)
    state["window_clients"] = loop.clients
    done = np.concatenate(
        [np.asarray(c.done_t, np.float64) for c in loop.clients])
    is_update = np.concatenate(
        [np.asarray(c.is_update, bool) for c in loop.clients])
    took = np.concatenate(
        [np.asarray(c.took, np.float64) for c in loop.clients])
    failed = loop.failed
    if loop.errors:
        log(f"client errors: {loop.errors[:3]}")
    completed = int(len(done))
    elapsed = float(done.max() - t0) if completed else seconds
    state["resident1"] = rl._resident(cell)
    state["counters1"] = c1 = cell.counters()
    state["service1"] = rl._service(cell)
    c0 = state["counters0"]
    reads = int(np.count_nonzero(~is_update))
    updates = completed - reads
    state["reads_answered"] = reads

    def ms(sel) -> List[float]:
        return (np.quantile(took[sel], [0.5, 0.99, 1.0]) * 1e3).round(
            3).tolist() if np.count_nonzero(sel) else []

    # operations completed in each second of the window: reads, updates
    secs = np.floor(done - t0).astype(int)
    by_second = [
        [int(np.count_nonzero((secs == s) & ~is_update)),
         int(np.count_nonzero((secs == s) & is_update))]
        for s in range(int(seconds))
    ]
    log(f"{reads} reads + {updates} updates in {elapsed:.2f}s by "
        f"{len(loop.clients)} clients x {loop.clients[0].depth}; ms "
        f"p50/p99/max: reads {ms(~is_update)}, updates {ms(is_update)}")
    log(f"[reads, updates] a second: {by_second}")
    cell.notes["read_ms_p50_p99_max"] = ms(~is_update)
    cell.notes["update_ms_p50_p99_max"] = ms(is_update)
    cell.notes["reads_updates"] = [reads, updates]
    cell.notes["by_second"] = by_second
    written = _written(state)
    cell.notes["docs_written"] = len(written)
    cell.notes["most_updates_a_doc"] = max(written.values(), default=0)
    for name in ("live.adopted", "serve.installs", "serve.install_groups",
                 "serve.install_device_docs", "serve.memo_hits",
                 "serve.invalidations", "serve.cold_reads",
                 "serve.reinstalls", "serve.rung_promotions",
                 "live.local_changes"):
        if name in c1:
            cell.notes[name] = int(c1[name] - c0.get(name, 0))
    obs: Dict[str, Any] = {
        "serve_read_p99_ms": None if p99 is None
        else 1e3 * p99.quantile(0.99),
        "reads": reads,
        "updates": updates,
    }
    if completed and "live.adopted" in c1:
        obs["adopts_per_kop"] = 1e3 * (
            c1["live.adopted"] - c0.get("live.adopted", 0)) / completed
    if "serve.cold_reads" in c1 and c1["serve.reads"] > c0["serve.reads"]:
        obs["cold_read_pct"] = 100.0 * (
            c1["serve.cold_reads"] - c0.get("serve.cold_reads", 0)
        ) / (c1["serve.reads"] - c0["serve.reads"])
    if len(traced) == 2:
        a, b = traced
        inside = (done >= a) & (done <= b)
        obs["traced_kops"] = float(np.count_nonzero(inside)) / 1e3
        obs["traced_kreads"] = float(
            np.count_nonzero(inside & ~is_update)) / 1e3
        obs["install_slabs"] = _traced_installs(cell.tracer.path)
    return Window(
        metrics={"ops_per_s": completed / elapsed if elapsed > 0 else 0.0},
        attempted=completed + failed,
        failed=failed,
        obs=obs,
    )


def _traced_installs(path) -> List[List[int]]:
    """[docs, padded rows] of each slab program the traced seconds'
    installs dispatched: the `D` / `N` tags of the `pipeline.enqueue`
    spans under their `serve.install.lanes`. [] without a trace."""
    if not path:
        return []
    from benchmark.readers import span_tree

    spans, _busy = span_tree.load(path)
    return [
        [int(s.args["D"]), int(s.args["N"])]
        for s in spans
        if s.name == "pipeline.enqueue" and "D" in s.args and "N" in s.args
    ]


# -- after the window --------------------------------------------------------


def verify(cell, state, win: Window) -> List[Check]:
    """Exact, every limit 0: the repo closed; every local feed on disk
    held to what the driver sent (all docs); the sampled docs' answers
    held to the replay at their admissible prefixes; 64 sampled written
    docs read back by a fresh repo; the counters of the guarantees."""
    from benchmark.reference import rw_plain

    job, traffic, urls = state["job"], state["traffic"], state["urls"]
    repo = state.pop("repo")
    t0 = time.perf_counter()
    with span("bench.facade.close"):
        repo.close()
    log(f"close {time.perf_counter() - t0:.2f}s")
    keys = [p.public_key for p in job.pairs]
    doc_of = {k: i for i, k in enumerate(keys)}
    t0 = time.perf_counter()
    feeds = rw_plain.local_feeds(cell.work + "/repo/feeds", keys)
    updates: Dict[int, List[Any]] = {}
    reads: Dict[int, List[Any]] = {}
    for c in state["log"]:
        for doc, up in c.updates:
            updates.setdefault(doc, []).append(up)
    for c in state["window_clients"]:
        for doc, qid, t_sent, t_ans, v in c.reads:
            reads.setdefault(doc, []).append(rw_plain.Read(
                rl._query_of(traffic, qid), t_sent, t_ans, v))
    written = sorted(updates)
    sample = _sample(cell, traffic, len(urls), set(written))
    sample_set = set(sample)
    total = dict.fromkeys(rw_plain.COUNTS, 0)
    total["updates_twice_or_unknown"] += sum(
        len(v) for k, v in feeds.items() if k not in doc_of)
    summaries: Dict[int, Dict[str, Any]] = {}
    compared = on_disk = shown = 0
    templates: Dict[Tuple[int, int], str] = {}
    touched = set(written) | {doc_of[k] for k in feeds if k in doc_of}
    for doc in sorted(touched | (sample_set & set(reads))):
        feed = feeds.get(keys[doc], [])
        corpus: List[dict] = []
        if doc in sample_set:  # replayed; the others: the disk alone
            d = job.plan[doc]
            t = (d["group"], d["template"])
            if t not in templates:
                templates[t] = json.dumps(job.templates[t[0]][t[1]])
            corpus = json.loads(templates[t].replace(
                _template_actor(job), keys[doc]))
        got = rw_plain.check_doc(
            corpus, feed, updates.get(doc, []),
            reads.get(doc, []) if doc in sample_set else [])
        on_disk += got["updates_on_disk"]
        for k in rw_plain.COUNTS:
            total[k] += got[k]
        if doc in sample_set:
            compared += len(reads.get(doc, []))
            if doc in touched:
                summaries[doc] = got["summary"]
            for ex in got["examples"][:max(0, 3 - shown)]:
                shown += 1
                log(f"doc {doc}: an answer outside its window: {ex}")
    log(f"reference {time.perf_counter() - t0:.2f}s: {on_disk} updates "
        f"on disk in {len(feeds)} local feeds; {compared} answers of "
        f"{len(sample)} sampled docs compared ({len(summaries)} of them "
        f"written)")
    t0 = time.perf_counter()
    reopened = _reopen(cell, state, [d for d in sample if d in summaries],
                       summaries)
    log(f"reopen {time.perf_counter() - t0:.2f}s: {reopened}")

    c0, c1 = state["counters0"], state["counters1"]

    def moved(*names) -> int:
        return int(sum(c1.get(n, 0) - c0.get(n, 0) for n in names))

    n = len(urls)
    tier = state["tier"] and "serve.reads" in c1
    s0, s1 = state["service0"], state["service1"]
    want = cell.mix["verify_sample_docs"]
    want_docs = min(n, int(want["hottest"]) + int(want["rest"]))
    return [
        Check("docs_sampled_short", max(0, want_docs - len(sample)), 0),
        Check("sampled_docs_unread", 0 if compared else len(sample), 0),
        Check("sampled_docs_written_short", max(0, min(
            int(want["written_at_least"]), len(written)) - len(summaries)),
            0),
        Check("answers_outside_their_window",
              total["answers_outside_their_window"], 0),
        Check("acked_lost", total["acked_lost"], 0),
        Check("updates_twice_or_unknown",
              total["updates_twice_or_unknown"], 0),
        Check("updates_out_of_order", total["updates_out_of_order"], 0),
        Check("reopen_summary_mismatches", reopened["mismatches"], 0),
        # a read the device path did not answer: it fell back, overflowed
        # or failed in its flush; with no tier at all, every read
        Check("host_answers",
              moved("serve.fallbacks", "serve.flush_errors",
                    "serve.overload_shed") if tier
              else state["reads_answered"], 0),
        Check("lanes_from_host_kernel",
              int(c1.get("serve.install_host_kernel_docs", 1)) if tier
              else 0, 0),
        Check("operations_refused",
              win.failed + moved("service.shed_reads"), 0),
        Check("ladder_not_healthy",
              (s1[0] - s0[0]) + s0[1] + s1[1], 0),
        Check("evictions_in_window",
              moved("serve.evictions", "serve.evictions_pressure"), 0),
        Check("docs_not_resident_at_start",
              max(0, n - state["resident0"]) if tier else 0, 0),
        Check("open_not_on_device",
              _off_device(cell, state["open_stats"])
              + reopened["off_device"], 0),
    ]


def _template_actor(job) -> str:
    """The actor name the corpus writer's templates carry in place of
    each doc's own key."""
    return job.templates[0][0][0]["actor"]


def _off_device(cell, stats) -> int:
    return int(stats["platform"] != ("cpu" if cell.rehearse else "tpu")) \
        + int(stats["host_slabs"] > 0)


def _sample(cell, traffic: Traffic, n: int, written) -> List[int]:
    """The docs whose answers are compared: the hottest of the run,
    then a seeded draw of the rest that holds written docs first, as
    many as the mix asks for (`written_at_least`, over the whole
    sample), hottest first."""
    want = cell.mix["verify_sample_docs"]
    hot = traffic.hottest(min(n, int(want["hottest"])))
    rest = sorted(set(range(n)) - set(hot))
    rng = random.Random(cell.seed)
    k = min(len(rest), int(want["rest"]))
    need = max(0, int(want["written_at_least"])
               - sum(1 for d in hot if d in written))
    rest_written = [d for d in rest if d in written]
    take = rng.sample(rest_written, min(len(rest_written), need, k))
    left = sorted(set(rest) - set(take))
    return hot + take + rng.sample(left, k - len(take))


def _reopen(cell, state, docs: List[int], summaries) -> Dict[str, int]:
    """A fresh repo on the same path opens `reopen_docs` of the sampled
    written docs through the bulk path; their summaries against the
    reference's. {mismatches, off_device}."""
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    docs = docs[:int(cell.mix["reopen_docs"])]
    urls = state["urls"]
    with span("bench.facade.repo_init"):
        repo = state["repo"] = Repo(path=cell.work + "/repo")
    with span("bench.loader.open_many"):
        repo.open_many([urls[d] for d in docs])
    with span("bench.loader.fetch_bulk_summaries"):
        summ = repo.back.fetch_bulk_summaries()
    stats = dict(repo.back.last_bulk_stats)
    bad = 0
    for d in docs:
        got = summ.doc(validate_doc_url(urls[d]))
        if got != summaries[d]:
            bad += 1
            if bad <= 3:
                log(f"reopened doc {d}: {got} != {summaries[d]}")
    return {"mismatches": bad + (0 if docs else 1), "docs": len(docs),
            "off_device": _off_device(cell, stats)}


def teardown(cell, state) -> None:
    repo = state.pop("repo", None)
    if repo is not None:
        with span("bench.facade.close"):
            repo.close()
