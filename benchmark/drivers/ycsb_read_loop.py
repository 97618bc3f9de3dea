"""Traffic driver `ycsb_read_loop`: YCSB core workload C over a store
made resident whole (closed loop of client threads, reads only, keys
zipfian over every doc).

Set-up writes the corpus from the seed, cold-opens it (`Repo.open_many`
+ `fetch_bulk_summaries`), runs YCSB's load phase (one read of every
doc in store order, at most HM_SERVE_QUEUE outstanding, in waves of
that many, so that every run installs the same groups) until the read
tier holds the whole store, then warms every query program the window
can ask for: a burst of reads of each kind at each batch size up to
`outstanding`, and `warm_reads` reads of the mix through the client
loop itself.

A request is one `Repo.read(url, query, cb)`, timed from send to
callback. `clients` threads each keep `outstanding / clients` reads in
flight and send the next when one completes. `ops_per_s` is the reads
answered over the time from the window's start to the last answer; a
read sent inside the window is finished and counted. Keys: rank r of
the zipfian (constant 0.99) is doc `perm[r]`, `perm` a permutation
drawn from the seed. The query of each read is drawn from the mix by
the seed too.
"""

from __future__ import annotations

import os
import queue
import random
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.drivers.cold_open_loop import before_jax  # noqa: F401
from benchmark.harness import Check, Window, log, span

ZIPF = 0.99
N_KEYS = 10
KINDS = ("text", "lookup", "len", "index")
# the query programs behind the kinds: every read resolves its path
# with a map_lookup, then text / index sort a sequence, len counts
PROGRAMS = {"text": "seq_order", "lookup": "map_lookup",
            "len": "counts", "index": "seq_order"}


# -- the traffic, from the seed ----------------------------------------------


class Traffic:
    """Who reads what: the scrambling permutation, the zipfian's
    cumulative weights, the mix's shares, the live length of every
    doc's text (from the corpus plan: inserts of its template)."""

    def __init__(self, cell, job) -> None:
        n = len(job.plan)
        self.n = n
        rng = np.random.default_rng([cell.seed, 0xC0FFEE])
        self.perm = rng.permutation(n)
        weights = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** ZIPF
        self.cdf = np.cumsum(weights)
        shares = {m["query"]["kind"]: float(m["share"])
                  for m in cell.mix["query_mix"]}
        self.kind_cdf = np.cumsum([shares[k] for k in KINDS])
        live: Dict[Tuple[int, int], int] = {}
        for d in job.plan:
            t = (d["group"], d["template"])
            if t not in live:
                live[t] = sum(
                    1 for c in job.templates[t[0]][t[1]]
                    for op in c["ops"] if op.get("i")
                )
        self.live = np.asarray(
            [live[(d["group"], d["template"])] for d in job.plan]
        )
        seq_key = cell.config["corpus"]["groups"][0]["seq_key"]
        self.fixed = {
            "text": {"kind": "text", "path": [seq_key]},
            "len": {"kind": "len", "path": [seq_key]},
        }
        self.lookups = [{"kind": "lookup", "path": [f"k{k}"]}
                        for k in range(N_KEYS)]
        self.seq_key = seq_key

    def draw(self, stream: int, m: int, seed: int):
        """`m` reads of one client: (doc, kind, argument) arrays. The
        argument is the key of a lookup, the position of an index."""
        rng = np.random.default_rng([seed, 0xBEEF, stream])
        ranks = np.searchsorted(self.cdf, rng.random(m) * self.cdf[-1])
        docs = self.perm[np.minimum(ranks, self.n - 1)]
        kinds = np.minimum(
            np.searchsorted(self.kind_cdf, rng.random(m), side="right"),
            len(KINDS) - 1,
        )
        u = rng.random(m)
        arg = np.where(
            kinds == KINDS.index("lookup"), (u * N_KEYS).astype(np.int64),
            (u * self.live[docs]).astype(np.int64),
        )
        return docs, kinds, arg

    def query(self, kind: int, arg: int) -> Tuple[int, dict]:
        """(a small id of the query, for the answers' table; the query)."""
        name = KINDS[kind]
        if name == "text":
            return 0, self.fixed["text"]
        if name == "len":
            return 1, self.fixed["len"]
        if name == "lookup":
            return 2 + arg, self.lookups[arg]
        return 2 + N_KEYS + arg, {
            "kind": "index", "path": [self.seq_key], "index": int(arg),
        }

    def hottest(self, k: int) -> List[int]:
        return [int(d) for d in self.perm[:k]]


# -- the closed loop ---------------------------------------------------------


class Client(threading.Thread):
    """One client thread: `depth` reads in flight, the next sent when
    one completes. Keeps, for the checks, the first answer of every
    (doc, query) it saw (a text outside the sample as its hash) and
    counts later answers that differ."""

    def __init__(self, loop: "Loop", tid: int, depth: int,
                 reads: int) -> None:
        super().__init__(name=f"bench-client-{tid}", daemon=True)
        self.loop = loop
        self.depth = depth
        self.docs, self.kinds, self.args = loop.traffic.draw(
            tid, reads, loop.seed
        )
        self.done_t: List[float] = []
        self.took: List[float] = []
        self.answers: Dict[Tuple[int, int], Any] = {}
        self.differ = 0
        self.failed = 0
        self.error: Optional[str] = None

    def run(self) -> None:
        loop = self.loop
        inbox: "queue.SimpleQueue" = queue.SimpleQueue()
        traffic, urls, read = loop.traffic, loop.urls, loop.repo.read
        keep = loop.sample
        nxt = inflight = 0

        def send() -> None:
            nonlocal nxt, inflight
            j = nxt
            nxt += 1
            inflight += 1
            doc = int(self.docs[j])
            qid, q = traffic.query(int(self.kinds[j]), int(self.args[j]))
            t0 = time.perf_counter()
            try:
                read(urls[doc], q,
                     lambda v, doc=doc, qid=qid, t0=t0: inbox.put(
                         (doc, qid, t0, time.perf_counter(), v)))
            except Exception as e:  # a read that raises is a failed read
                self.error = repr(e)
                inbox.put((doc, qid, t0, time.perf_counter(), _RAISED))

        loop.start_gate.wait()
        while inflight < self.depth and nxt < len(self.docs):
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
                if qid == 0 and doc not in keep:
                    v = hash(v)
                first = self.answers.setdefault((doc, qid), v)
                if first != v:
                    self.differ += 1
            if time.perf_counter() < loop.t_end and nxt < len(self.docs):
                send()


_RAISED = object()


class Loop:
    """One run of the closed loop for `seconds` (or until every client
    has sent its `reads`)."""

    def __init__(self, cell, state, seconds: float, reads: int,
                 stream0: int) -> None:
        self.repo = state["repo"]
        self.urls = state["urls"]
        self.traffic = state["traffic"]
        self.sample = state["sample_set"]
        self.seed = cell.seed
        self.read_timeout = float(cell.mix["read_timeout_s"])
        clients = int(cell.mix["clients"])
        depth = max(1, int(cell.mix["outstanding"]) // clients)
        self.start_gate = threading.Event()
        self.t_end = float("inf")
        self.seconds = seconds
        self.clients = [
            Client(self, stream0 + i, depth, reads) for i in range(clients)
        ]

    def run(self, during=None) -> float:
        """Returns the window's start. `during(t0)` runs on this thread
        while the clients read (the traced part)."""
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


# -- set-up ------------------------------------------------------------------


def setup(cell, job) -> Dict[str, Any]:
    from hypermerge_tpu.repo import Repo

    t0 = time.perf_counter()
    urls = job.finish()
    cell.notes["corpus_wait_s"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    with span("bench.facade.repo_init"):
        repo = Repo(path=cell.work + "/repo")
    state: Dict[str, Any] = {"repo": repo, "job": job, "urls": urls}
    try:
        with span("bench.loader.open_many"):
            repo.open_many(urls)
        with span("bench.loader.fetch_bulk_summaries"):
            repo.back.fetch_bulk_summaries()
        state["open_stats"] = dict(repo.back.last_bulk_stats)
        cell.notes["cold_open_s"] = round(time.perf_counter() - t0, 3)
        state["tier"] = repo.back.serve is not None
        with span("bench.serve.load"):
            state["install_s"] = _load_phase(cell, repo, urls)
        cell.notes["install_s"] = round(state["install_s"], 3)
        cell.notes["resident"] = _resident(cell)
        traffic = state["traffic"] = Traffic(cell, job)
        want = cell.mix["verify_sample_docs"]
        hot = traffic.hottest(min(len(urls), int(want["hottest"])))
        rest = sorted(set(range(len(urls))) - set(hot))
        rng = random.Random(cell.seed)
        state["sample"] = hot + rng.sample(
            rest, min(len(rest), int(want["rest"]))
        )
        state["sample_set"] = frozenset(state["sample"])
        t0 = time.perf_counter()
        with span("bench.serve.warm"):
            _warm(cell, state)
        cell.notes["warm_s"] = round(time.perf_counter() - t0, 3)
    except BaseException:
        repo.close()
        raise
    return state


def _load_phase(cell, repo, urls) -> float:
    """YCSB's load phase: one read of every doc in store order, in
    waves of HM_SERVE_QUEUE (the tier's admission bound), each awaited
    whole. Exits non-zero when the store is not resident in time."""
    if repo.back.serve is None:
        log("no read tier (HM_SERVE=0): nothing to load")
        return 0.0
    limit = float(cell.mix["load"]["timeout_s"])
    wave = int(os.environ.get("HM_SERVE_QUEUE", "4096"))
    query = cell.mix["load"]["query"]
    t0 = time.perf_counter()
    for at in range(0, len(urls), wave):
        part = urls[at:at + wave]
        left = threading.Semaphore(0)
        bad: List[Any] = []

        def done(v, bad=bad, left=left) -> None:
            if not isinstance(v, int):
                bad.append(v)
            left.release()

        for u in part:
            repo.read(u, query, done)
        for _ in part:
            if not left.acquire(timeout=max(
                0.0, limit - (time.perf_counter() - t0)
            )):
                log(f"FAILED: the store is not resident {limit:.0f} s "
                    f"after the cold open ({_resident(cell)} of "
                    f"{len(urls)} docs)")
                raise SystemExit(5)
        if bad:
            log(f"FAILED: the load phase got {bad[:3]!r}")
            raise SystemExit(5)
        log(f"load phase: {at + len(part)} docs read at "
            f"{time.perf_counter() - t0:.2f}s")
    took = time.perf_counter() - t0
    have = _resident(cell)
    log(f"load phase: {len(urls)} docs in {took:.2f}s, {have} resident")
    return took


def _resident(cell) -> int:
    return int(cell.counters().get("serve.resident_docs", 0))


def _service(cell) -> Tuple[int, int]:
    """(ladder transitions so far, its state now)."""
    c = cell.counters()
    return (int(c.get("service.transitions", 0)),
            int(c.get("service.state", 0)))


def _await_healthy(cell, limit: float = 30.0) -> None:
    """A first call of a query program compiles it, or loads it from
    the compile cache, for longer than the ladder's SLO: the reads that
    wait behind it read as pressure. Set-up lets the ladder step back
    down before it goes on, as a server does before it takes traffic."""
    t0 = time.perf_counter()
    while _service(cell)[1] != 0:
        if time.perf_counter() - t0 > limit:
            log(f"FAILED: the ladder is not HEALTHY {limit:.0f} s after "
                f"the last warm-up read")
            raise SystemExit(5)
        time.sleep(0.05)


def _warm(cell, state) -> None:
    """Every ("serve", program, B, N) the window can ask for, then
    `warm_reads` of the mix through the client loop. A flush's reads of
    one program share a dispatch at the pow2 over their number, so a
    burst of b reads of one kind compiles the program at b; the eager
    flusher may cut a burst, so each is repeated until the program's
    key shows in the program table."""
    traffic, repo, urls = state["traffic"], state["repo"], state["urls"]
    if state["tier"]:
        from hypermerge_tpu.parallel import sharded

        from hypermerge_tpu.serve import kernels

        # the batch axes a dispatch can have: the program's own ladder,
        # or (a program before PR 32) the powers of two
        ladder = getattr(kernels, "BATCH_BUCKETS", None) or [
            1 << i for i in range(13)
        ]
        most = int(cell.mix.get("warm_batch", cell.mix["outstanding"]))
        sizes = [b for b in ladder if b < most] + [
            next(b for b in ladder if b >= most)
        ]
        rng = np.random.default_rng([cell.seed, 0xFACE])
        first: Dict[str, float] = {}  # seconds of each first call
        for kind in ("lookup", "text", "len"):
            for b in sizes:
                t0 = time.perf_counter()
                for attempt in range(40):
                    have = {k[:3] for k in sharded.trace_counts}
                    if ("serve", PROGRAMS[kind], b) in have:
                        break
                    # the flusher may take a burst whole or let its
                    # first read go alone: try it both ways
                    _await_healthy(cell)
                    _burst(repo, urls, traffic, rng, kind,
                           b + attempt % 2)
                else:
                    log(f"warm-up: no {PROGRAMS[kind]} program at {b}")
                first[f"{PROGRAMS[kind]}_b{b}"] = round(
                    time.perf_counter() - t0, 3
                )
        cell.notes["warm_first_calls_s"] = first
    reads = int(cell.mix["warm_reads"])
    clients = int(cell.mix["clients"])
    _await_healthy(cell)
    loop = Loop(cell, state, float("inf"), max(1, reads // clients), 1000)
    loop.run()
    failed = sum(c.failed for c in loop.clients)
    if failed:
        log(f"FAILED: {failed} warm-up reads failed "
            f"({[c.error for c in loop.clients if c.error][:2]}); ladder "
            f"(transitions, state) {_service(cell)}")
        raise SystemExit(5)
    _await_healthy(cell)
    cell.notes["service_setup"] = list(_service(cell))


def _burst(repo, urls, traffic, rng, kind: str, b: int) -> None:
    """b reads of one kind sent at once and awaited."""
    docs = rng.integers(0, len(urls), b)
    left = threading.Semaphore(0)
    k = KINDS.index(kind)
    for d in docs:
        _qid, q = traffic.query(k, 0)
        repo.read(urls[int(d)], q, lambda _v: left.release())
    for _ in docs:
        left.acquire(timeout=60)


# -- the window --------------------------------------------------------------


def window(cell, state, seconds: float) -> Window:
    repo = state["repo"]
    p99 = _read_p99(repo)
    state["resident0"] = _resident(cell)
    state["counters0"] = cell.counters()
    state["service0"] = _service(cell)
    # reads drawn ahead for each client: more than any chip has answered
    loop = Loop(cell, state, seconds, int(seconds * 4000) + 1000, 0)
    trace_s = float(cell.mix["trace_seconds"])
    traced: List[float] = []

    def during(t0: float) -> None:
        if not cell.tracer.on:
            return
        time.sleep(max(0.0, min(seconds / 2, seconds - trace_s - 1)))
        cell.tracer.start()
        traced.append(time.perf_counter())
        with span("bench.serve.read_loop"):
            time.sleep(trace_s)
        traced.append(time.perf_counter())
        cell.tracer.stop()

    t0 = loop.run(during)
    done = np.sort(np.concatenate(
        [np.asarray(c.done_t, np.float64) for c in loop.clients]
    ))
    took = np.concatenate(
        [np.asarray(c.took, np.float64) for c in loop.clients]
    )
    failed = sum(c.failed for c in loop.clients)
    errors = [c.error for c in loop.clients if c.error]
    if errors:
        log(f"client errors: {errors[:3]}")
    answered = int(len(done))
    elapsed = float(done[-1] - t0) if answered else seconds
    state["clients"] = loop.clients
    state["resident1"] = _resident(cell)
    state["counters1"] = cell.counters()
    state["service1"] = _service(cell)
    # what a stall looks like from outside: the longest waits between
    # one answer and the next (the trace names what the host did then)
    gaps = np.diff(done) if answered > 1 else np.zeros(0)
    worst = np.argsort(gaps)[::-1][:5]
    stalls = [[round(float(done[i] - t0), 3), round(float(gaps[i]), 4)]
              for i in worst if gaps[i] >= 0.1]
    q = (np.quantile(took, [0.5, 0.99, 1.0]) * 1e3).round(3).tolist() \
        if answered else []
    log(f"{answered} reads in {elapsed:.2f}s by {len(loop.clients)} "
        f"clients x {loop.clients[0].depth}; send-to-callback ms "
        f"p50/p99/max {q}; gaps >= 0.1 s between answers: {stalls}")
    cell.notes["stalls"] = stalls
    cell.notes["client_ms_p50_p99_max"] = q
    obs: Dict[str, Any] = {
        "install_s": state["install_s"] if state["tier"] else None,
        "serve_read_p99_ms": None if p99 is None
        else 1e3 * p99.quantile(0.99),
        "reads": answered,
    }
    if len(traced) == 2:
        a, b = traced
        obs["traced_kreads"] = float(
            np.count_nonzero((done >= a) & (done <= b))
        ) / 1e3
    return Window(
        metrics={"ops_per_s": answered / elapsed if elapsed > 0 else 0.0},
        attempted=answered + failed,
        failed=failed,
        obs=obs,
    )


def _read_p99(repo):
    """The program's own quantile over its serve.read_s histogram since
    the last call (the ladder's reader, serve/overload.py): call once
    at the window's start, then `.quantile()` at its end."""
    tier = repo.back.serve
    if tier is None:
        return None
    from hypermerge_tpu.serve.overload import HistogramWindow

    p99 = HistogramWindow(tier._hist)
    p99.quantile()
    return p99


# -- after the window --------------------------------------------------------


def verify(cell, state, win: Window) -> List[Check]:
    """Exact, every limit 0. The sampled docs' answers against the plain
    reference; answers to one (doc, query) that differ, over all reads;
    the counters of the guarantees."""
    from benchmark.reference import read_plain

    job, traffic = state["job"], state["traffic"]
    merged: Dict[Tuple[int, int], Any] = {}
    differ = 0
    for c in state["clients"]:
        differ += c.differ
        for key, v in c.answers.items():
            if merged.setdefault(key, v) != v:
                differ += 1
    sample = state["sample_set"]
    objs: Dict[Tuple[int, int], Any] = {}
    wrong = compared = 0
    for (doc, qid), got in merged.items():
        if doc not in sample:
            continue
        d = job.plan[doc]
        t = (d["group"], d["template"])
        if t not in objs:
            objs[t] = read_plain.replay_objs(job.templates[t[0]][t[1]])
        want = read_plain.evaluate(objs[t], _query_of(traffic, qid))
        compared += 1
        if got != want:
            wrong += 1
            if wrong <= 3:
                log(f"doc {doc} query {qid}: {str(got)[:60]!r} != "
                    f"{str(want)[:60]!r}")
    log(f"{compared} answers of {len(sample)} sampled docs compared, "
        f"{len(merged)} distinct (doc, query) read")
    c0, c1 = state["counters0"], state["counters1"]

    def moved(*names) -> int:
        return int(sum(c1.get(n, 0) - c0.get(n, 0) for n in names))

    answered = win.attempted - win.failed
    n = len(state["urls"])
    tier = state["tier"] and "serve.reads" in c1
    t0, s0 = state["service0"]
    t1, s1 = state["service1"]
    want_docs = min(n, sum(int(v) for v in
                           cell.mix["verify_sample_docs"].values()))
    return [
        Check("docs_sampled_short", max(0, want_docs - len(sample)), 0),
        Check("sampled_docs_unread",
              0 if compared else len(sample), 0),
        Check("answer_mismatches", wrong, 0),
        Check("answers_that_differ", differ, 0),
        # a read the resident device state did not answer: it fell back,
        # overflowed, failed in its flush, or there is no tier at all
        Check("host_answers", max(0, answered - moved("serve.hits")), 0),
        Check("lanes_from_host_kernel",
              int(c1.get("serve.install_host_kernel_docs", 1)) if tier
              else 0, 0),
        Check("reads_refused",
              win.failed + moved("service.shed_reads"), 0),
        Check("ladder_not_healthy", (t1 - t0) + s0 + s1, 0),
        Check("installs_in_window", moved("serve.installs"), 0),
        Check("evictions_in_window",
              moved("serve.evictions", "serve.evictions_pressure"), 0),
        Check("docs_not_resident",
              (max(0, n - state["resident0"])
               + max(0, n - state["resident1"])) if tier else 0, 0),
        Check("open_not_on_device",
              int(state["open_stats"]["platform"]
                  != ("cpu" if cell.rehearse else "tpu"))
              + int(state["open_stats"]["host_slabs"] > 0), 0),
    ]


def _query_of(traffic: Traffic, qid: int) -> dict:
    if qid == 0:
        return traffic.fixed["text"]
    if qid == 1:
        return traffic.fixed["len"]
    if qid < 2 + N_KEYS:
        return traffic.lookups[qid - 2]
    return {"kind": "index", "path": [traffic.seq_key],
            "index": qid - 2 - N_KEYS}


def teardown(cell, state) -> None:
    repo = state.pop("repo", None)
    if repo is not None:
        with span("bench.facade.close"):
            repo.close()
