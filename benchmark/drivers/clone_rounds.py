"""Traffic driver `clone_rounds`: a second device clones a whole store
of shared documents from a peer over replication, round after round
(closed loop, one client, fixed work a round).

Peer A holds the store the corpus writer made (`Repo` on the corpus,
`open_many`, `fetch_bulk_summaries`) behind a `TcpSwarm` on loopback and
stays up. A round is, through the public API only:

  open      a fresh `Repo` B on an EMPTY directory of its own
            (`work/b-<n>`), `set_swarm(TcpSwarm())`, connect to A,
            `open_many(every url)`, one subscription a handle;
  converge  wait until every document's subscription has delivered an
            index of `changes` (ops / ops_per_change: every change of
            its feeds; the source's clock is the writer's plan);
  read      one `Repo.read(url, {"kind": "len", "path": [seq_key]}, cb)`
            of every document, in waves of HM_SERVE_QUEUE, until all are
            answered: B's read tier installs the cloned documents
            through the slab program on the device. The round ENDS at
            the last answer;
  close     B closes (its swarm with it), then one `gc.collect()` stands
            where a new process would start clean. Both lie inside the
            window, between two rounds.

Rounds start while the window's clock is under `--seconds`; one started
inside is finished and counted.
`ops_per_s` = rounds x docs x ops a doc / (end of the last round - start
of the first). `attempted` = documents x rounds, `failed` = documents
that did not converge or whose read was not answered within
`round_timeout_s`. The last round's B stays up for the checks, so its
close falls outside the window, as a cold-open loop's last close does.

Set-up: A's store from the seed, A's cold open, one warm round whose
read phase starts with bursts of 1, 3, 12 and 48 reads (a quarter of the
docs at most) and ends with a burst at every batch size (every install
and query program a timed round can ask for compiled or loaded). The heap is frozen for the
window, as `ycsb-a-zipf-closed` states it.
"""

from __future__ import annotations

import gc
import os
import random
import threading
import time
from typing import Any, Dict, List, Optional

from benchmark.drivers.cold_open_loop import before_jax  # noqa: F401
from benchmark.harness import Check, Window, log, span
from benchmark.reference.plainify import plain

# counters a round is described by (stderr and `setup`; no metric)
NOTED = (
    "live.adopted", "live.refused", "live.ticks", "live.tick_docs",
    "live.tick_changes", "live.inc_changes", "live.kernel_runs",
    "live.device_dispatches", "net.repl.frames_rx", "net.repl.blocks_rx",
    "net.repl.feeds_synced", "net.repl.antientropy_sweeps",
    "serve.installs", "serve.install_groups", "serve.install_device_docs",
    "serve.install_host_kernel_docs", "host.gc_full", "host.gc_full_s",
)


# replication's counters the checks `unsigned_accepted` and (through
# `sync.frames_per_feed`) the feeds cloned are decided by: there from
# the moment a repo has a swarm
DECIDED_BY = ("net.repl.unsigned_rx", "net.repl.blocks_rx",
              "net.repl.feeds_synced")


class Round:
    """One clone: B's repo, what its subscriptions and reads delivered,
    and the seconds of its phases."""

    def __init__(self, n: int, docs: int) -> None:
        self.n = n
        self.repo = None
        self.handles: List[Any] = []
        self.t0 = 0.0
        self.t_end = 0.0  # the last read's answer
        self.took: Dict[str, float] = {}
        self.left = set(range(docs))  # docs not yet at every change
        self.at: List[float] = []  # when each doc got there (clock)
        self.states: Dict[int, Any] = {}  # sampled docs: the last state
        self.answers: Dict[int, Any] = {}
        self.unanswered = 0
        self.trace: Optional[List[float]] = None  # [start, stop]

    @property
    def failed(self) -> int:
        return len(self.left) + self.unanswered

    @property
    def round_s(self) -> float:
        return self.t_end - self.t0


def _b_dir(cell, n: int) -> str:
    return os.path.join(cell.work, f"b-{n}")


# the warm round reads in bursts of these sizes, then the rest at once:
# an install of one doc and one of a page of 256, and a query dispatch
# at every batch size, whichever way a timed round's flusher cuts its
# burst
WARM_WAVES = (1, 3, 12, 48)


def _clone(cell, state, n: int, trace_at: Optional[float] = None,
           waves=()) -> Round:
    """One round. `trace_at`: seconds of this round's convergence after
    which the traced part starts (None: no trace in this round).
    `waves`: sizes of the read phase's first bursts (the warm round)."""
    from hypermerge_tpu.net.tcp import TcpSwarm
    from hypermerge_tpu.repo import Repo

    urls = state["urls"]
    sample = state["sample_set"]
    changes = state["changes"]
    timeout = float(cell.mix["round_timeout_s"])
    docs = len(urls)
    rnd = Round(n, docs)
    lock = threading.Lock()
    done = threading.Event()
    now = time.perf_counter

    def watch(i: int):
        keep = i in sample

        def on_value(value, index) -> None:
            if keep:
                rnd.states[i] = value
            if index >= changes:
                with lock:
                    if i in rnd.left:
                        rnd.left.discard(i)
                        rnd.at.append(now())
                    if not rnd.left:
                        done.set()
        return on_value

    rnd.t0 = t = now()
    with span("bench.clone.open"):
        os.makedirs(_b_dir(cell, n))
        rnd.repo = repo = Repo(path=_b_dir(cell, n))
        swarm = TcpSwarm()
        repo.set_swarm(swarm)
        swarm.connect(state["address"])
        rnd.handles = repo.open_many(urls)
        for i, h in enumerate(rnd.handles):
            h.subscribe(watch(i))
    rnd.took["open"] = now() - t
    t = now()
    with span("bench.clone.converge"):
        # the traced part begins late in the backfill: by the clock,
        # or where few enough docs are left (whichever comes first)
        few = 0.0
        if trace_at is not None and state.get("converge_s"):
            few = docs * (1.0 - trace_at / state["converge_s"])
        while not done.wait(0.05 if trace_at is not None else 1.0):
            waited = now() - t
            if waited > timeout:
                break
            if trace_at is not None and rnd.trace is None and (
                waited >= trace_at or len(rnd.left) <= few
            ):
                cell.tracer.start()
                rnd.trace = [now()]
    rnd.took["converge"] = now() - t
    if trace_at is not None and rnd.trace is None:
        cell.tracer.start()  # the backfill outran the plan: reads only
        rnd.trace = [now()]
    t = now()
    with span("bench.clone.read"):
        _read_all(cell, state, rnd, max(1.0, timeout - (t - rnd.t0)),
                  waves)
    rnd.t_end = now()
    rnd.took["read"] = rnd.t_end - t
    if rnd.trace is not None:
        cell.tracer.stop()
        rnd.trace.append(now())
    return rnd


def _read_all(cell, state, rnd: Round, limit: float, waves=()) -> None:
    """One `len` read of every doc, in waves of HM_SERVE_QUEUE (after
    the bursts of `waves`), each awaited whole."""
    urls = state["urls"]
    query = cell.mix["read"]["query"]
    wave = int(os.environ.get("HM_SERVE_QUEUE", "4096"))
    t0 = time.perf_counter()
    cuts = [0]
    for size in waves:
        # the bursts take a quarter of the docs at most: the rest is a
        # burst of a timed round's order (at rehearsal size too)
        if cuts[-1] + size > len(urls) // 4:
            break
        cuts.append(cuts[-1] + size)
    while cuts[-1] < len(urls):
        cuts.append(min(len(urls), cuts[-1] + wave))
    for at, end in zip(cuts, cuts[1:]):
        part = range(at, end)
        left = threading.Semaphore(0)

        def answered(v, i) -> None:
            rnd.answers[i] = v
            left.release()

        for i in part:
            rnd.repo.read(urls[i], query, lambda v, i=i: answered(v, i))
        for _ in part:
            if not left.acquire(timeout=max(
                0.0, limit - (time.perf_counter() - t0)
            )):
                break
    rnd.unanswered = len(urls) - len(rnd.answers)


def _close(cell, rnd: Round) -> None:
    """B goes away as a process would: closed, its garbage collected."""
    repo, rnd.repo = rnd.repo, None
    if repo is None:
        return
    t = time.perf_counter()
    with span("bench.clone.close"):
        for h in rnd.handles:
            h.close()
        rnd.handles = []
        repo.close()
        del repo
        gc.collect()
    rnd.took["close"] = time.perf_counter() - t


def _describe(cell, rnd: Round, c0, c1) -> Dict[str, Any]:
    out: Dict[str, Any] = {
        "round_s": round(rnd.round_s, 3),
        **{k: round(v, 3) for k, v in rnd.took.items()},
        "failed": rnd.failed,
    }
    if rnd.at:
        # seconds into the round at which the first doc, each quarter
        # of the docs and the last had every change, and the longest
        # wait between two docs (a backfill that sat out a timer)
        at = sorted(t - rnd.t0 for t in rnd.at)
        out["converged_at"] = [
            round(at[min(len(at) - 1, q * len(at) // 4)], 2)
            for q in range(5)]
        out["longest_gap"] = round(
            max((b - a for a, b in zip(at, at[1:])), default=0.0), 2)
    for name in NOTED:
        if name in c1 and c1[name] != c0.get(name, 0):
            out[name] = round(c1[name] - c0.get(name, 0), 3)
    log(f"round {rnd.n}: {out}")
    return out


# -- set-up ------------------------------------------------------------------


def setup(cell, job) -> Dict[str, Any]:
    from hypermerge_tpu.net.tcp import TcpSwarm
    from hypermerge_tpu.repo import Repo

    t0 = time.perf_counter()
    urls = job.finish()
    cell.notes["corpus_wait_s"] = round(time.perf_counter() - t0, 3)
    corpus = cell.config["corpus"]
    docs = len(urls)
    rng = random.Random(cell.seed)
    sample = sorted(rng.sample(
        range(docs), min(docs, int(cell.mix["verify_sample_docs"]))))
    t0 = time.perf_counter()
    with span("bench.facade.repo_init"):
        repo = Repo(path=cell.work + "/repo")
    state: Dict[str, Any] = {
        "a": repo, "job": job, "urls": urls, "sample": sample,
        "sample_set": frozenset(sample), "rounds": [], "kept": None,
        "changes": int(corpus["ops"]) // int(corpus["ops_per_change"]),
        "ops_a_round": sum(d["n_ops"] for d in job.plan),
    }
    try:
        swarm = TcpSwarm()
        repo.set_swarm(swarm)
        state["address"] = swarm.address
        lacks = sorted(set(DECIDED_BY) - set(cell.counters()))
        if lacks:
            # a program from before the deployment (the parent of the
            # PR that added it): nothing says what B accepted unsigned
            # or which feeds it holds whole, so the cell cannot be held
            # to its guarantees there. Fail now, not after a round
            log(f"FAILED: this program has no counter {lacks}: it cannot "
                f"run {cell.name}")
            raise SystemExit(5)
        with span("bench.loader.open_many"):
            repo.open_many(urls)
        with span("bench.loader.fetch_bulk_summaries"):
            repo.back.fetch_bulk_summaries()
        state["open_stats"] = dict(repo.back.last_bulk_stats)
        cell.notes["cold_open_s"] = round(time.perf_counter() - t0, 3)
        c0 = cell.counters()
        warm = _clone(cell, state, 0, waves=WARM_WAVES)
        if warm.failed:
            log(f"FAILED: the warm round left {len(warm.left)} docs short "
                f"and {warm.unanswered} reads unanswered")
            raise SystemExit(5)
        cell.notes["warm_programs"] = _warm_programs(cell, state, warm)
        _close(cell, warm)
        cell.notes["warm_round"] = _describe(cell, warm, c0, cell.counters())
        state["converge_s"] = warm.took["converge"]
        state["install_s"] = warm.took["read"]
    except BaseException:
        teardown(cell, state)
        raise
    return state


def _warm_programs(cell, state, rnd: Round) -> List[str]:
    """The `counts` query program at every batch size a timed round's
    flusher can cut its burst to: bursts of b reads of B's documents
    (all resident by now), repeated until the program's key shows in
    the program table, as `ycsb_rw_loop` warms its own. -> the keys
    found, for the notes."""
    from hypermerge_tpu.parallel import sharded

    try:
        from hypermerge_tpu.serve.kernels import BATCH_BUCKETS as sizes
    except ImportError:
        sizes = (1, 4, 16, 64, 256)

    def found():
        return sorted(k for k in list(sharded.trace_counts)
                      if k[:2] == ("serve", "counts"))

    urls, query = state["urls"], cell.mix["read"]["query"]
    rungs = {k[3] for k in found()}
    for rung in sorted(rungs):
        for below, b in zip((0,) + tuple(sizes), sizes):
            if below >= len(urls):
                break  # a whole round's burst fits the bucket before
            for attempt in range(20):
                if ("serve", "counts", b, rung) in sharded.trace_counts:
                    break
                # the flusher may take a burst whole or let its first
                # read go alone: try it both ways
                burst = urls[:min(len(urls), b + attempt % 2)]
                left = threading.Semaphore(0)
                for u in burst:
                    rnd.repo.read(u, query, lambda _v: left.release())
                for _ in burst:
                    if not left.acquire(timeout=60):
                        log("FAILED: a warm-up read was not answered")
                        raise SystemExit(5)
            else:
                log(f"warm-up: no counts program at {b} x {rung}")
    return [f"b{k[2]}_n{k[3]}" for k in found()]


# -- the window --------------------------------------------------------------


def window(cell, state, seconds: float) -> Window:
    docs = len(state["urls"])
    lead = float(cell.mix["trace_seconds"])
    rounds: List[Round] = state["rounds"]
    described: List[Dict[str, Any]] = []
    c_start = cell.counters()
    # the harness has just collected set-up's garbage; what is left (A's
    # store, the programs) lives as long as the process and is not
    # walked again by the collections inside the window
    gc.freeze()
    try:
        t0 = time.perf_counter()
        while True:
            n = len(rounds) + 1
            trace_at = max(0.0, state["converge_s"] - lead) \
                if cell.tracer.on and not rounds else None
            c0 = cell.counters()
            rnd = _clone(cell, state, n, trace_at)
            rounds.append(rnd)
            more = not rnd.failed and time.perf_counter() - t0 < seconds
            if more:
                _close(cell, rnd)
            described.append(_describe(cell, rnd, c0, cell.counters()))
            if not more:
                break
    finally:
        gc.unfreeze()
    state["kept"] = rounds[-1]
    c_end = cell.counters()
    elapsed = rounds[-1].t_end - t0
    done = [r for r in rounds if not r.failed]
    failed = sum(r.failed for r in rounds)
    cell.notes["rounds"] = described
    gc_s = c_end.get("host.gc_full_s", 0) - c_start.get("host.gc_full_s", 0)
    log(f"{len(done)} rounds of {docs} docs in {elapsed:.2f}s: "
        f"{[round(r.round_s, 2) for r in rounds]}; collector "
        f"{gc_s:.2f}s in the window")

    def moved(name: str) -> float:
        return c_end.get(name, 0) - c_start.get(name, 0)

    def mean(vals: List[float]) -> Optional[float]:
        return sum(vals) / len(vals) if vals else None

    obs: Dict[str, Any] = {
        "round_s": mean([r.round_s for r in done]),
        "converge_s": mean([r.took["converge"] for r in done]),
        "read_s": mean([r.took["read"] for r in done]),
        "install_s": state["install_s"],
        "rounds": len(done),
    }
    cloned = docs * len(rounds)
    if "live.adopted" in c_end:
        obs["adopts_per_doc"] = moved("live.adopted") / cloned
    if moved("live.kernel_runs") > 0:
        obs["device_tick_pct"] = 100.0 * moved(
            "live.device_dispatches") / moved("live.kernel_runs")
    traced = next((r for r in rounds if r.trace and len(r.trace) == 2), None)
    if traced is not None:
        from benchmark.drivers.ycsb_rw_loop import _traced_installs

        obs["traced_s"] = traced.trace[1] - traced.trace[0]
        obs["traced_kops"] = state["ops_a_round"] / 1e3
        obs["install_slabs"] = _traced_installs(cell.tracer.path)
        log(f"traced {obs['traced_s']:.2f}s of round {traced.n}: the last "
            f"{traced.trace[0] - traced.t0 - traced.took['open']:.2f}s.. of "
            f"its backfill and its reads")
    return Window(
        metrics={"ops_per_s": len(done) * state["ops_a_round"] / elapsed
                 if elapsed > 0 else 0.0},
        attempted=cloned,
        failed=failed,
        obs=obs,
    )


# -- after the window --------------------------------------------------------


def verify(cell, state, win: Window) -> List[Check]:
    """Exact, every limit 0. Every round: every doc converged and read,
    every answer and the sampled docs' values equal to the plain
    reference. The last round, whose B is still up: every doc's clock
    and the sampled values once more, as B serves them now; then B is
    closed and what it left on disk is held to A's by the file layout
    alone, and a fresh repo with no swarm reopens some of it."""
    from benchmark.reference import clone_plain

    job, urls, sample = state["job"], state["urls"], state["sample"]
    rounds: List[Round] = state["rounds"]
    seq_key = cell.config["corpus"].get("seq_key", "t")
    t0 = time.perf_counter()
    refs: Dict[int, Dict[str, Any]] = {}
    cache: Dict[tuple, Any] = {}
    for i in range(len(urls)):
        refs[i] = clone_plain.expect(job.doc_changes(i, cache), seq_key)
    log(f"reference {time.perf_counter() - t0:.2f}s: {len(refs)} docs")
    bad_value = bad_read = shown = 0
    for rnd in rounds:
        for i in range(len(urls)):
            if rnd.answers.get(i) != refs[i]["len"]:
                bad_read += 1
                if shown < 3:
                    shown += 1
                    log(f"round {rnd.n} doc {i}: read "
                        f"{rnd.answers.get(i)!r} != {refs[i]['len']}")
        for i in sample:
            got = plain(rnd.states.get(i))
            if got != refs[i]["value"]:
                bad_value += 1
                if shown < 3:
                    shown += 1
                    log(f"round {rnd.n} doc {i}: its value differs from "
                        f"the reference: {_differs(got, refs[i]['value'])}")
    last = state["kept"]
    bad_clock = _clocks(cell, last, urls, refs)
    for i in sample:
        if plain(last.handles[i].value(timeout=60)) != refs[i]["value"]:
            bad_value += 1
    c1 = cell.counters()
    _close(cell, last)
    state["kept"] = None
    keys = [[p.public_key for p in pairs] for pairs in job.pairs]
    disk = clone_plain.compare_stores(
        cell.work + "/repo/feeds", _b_dir(cell, last.n) + "/feeds",
        [k for doc in keys for k in doc])
    log(f"on disk: {disk}")
    reopened = _reopen(cell, state, last, refs)
    log(f"reopened: {reopened}")

    docs, n = len(urls), len(rounds)
    tier = "serve.reads" in c1
    stats = state["open_stats"]
    return [
        Check("docs_not_converged", sum(len(r.left) for r in rounds), 0),
        Check("reads_unanswered", sum(r.unanswered for r in rounds), 0),
        Check("docs_sampled_short",
              max(0, min(docs, int(cell.mix["verify_sample_docs"]))
                  - len(sample)), 0),
        Check("value_mismatches", bad_value, 0),
        Check("read_mismatches", bad_read, 0),
        Check("clock_mismatches", bad_clock, 0),
        # a read the device path did not answer, or lanes the host
        # kernel made; with no tier at all, every read
        Check("host_answers",
              int(sum(c1.get(k, 0) for k in (
                  "serve.fallbacks", "serve.flush_errors",
                  "serve.overload_shed"))) if tier else docs * n, 0),
        Check("lanes_from_host_kernel",
              int(c1.get("serve.install_host_kernel_docs", 1)) if tier
              else 0, 0),
        # every clone of a doc (the warm round's too) adopted by the
        # live engine, none refused or handed to the host OpSet
        Check("docs_not_live",
              int(max(0, docs * (n + 1) - c1.get("live.adopted", 0))
                  + c1.get("live.refused", 0)), 0),
        Check("unsigned_accepted",
              int(c1.get("net.repl.unsigned_rx", 0)) + disk["unsigned"], 0),
        Check("feeds_short_on_disk", disk["short"] + disk["differ"], 0),
        Check("reopen_mismatches", reopened["mismatches"], 0),
        Check("open_not_on_device",
              int(stats["platform"] != ("cpu" if cell.rehearse else "tpu"))
              + int(stats["host_slabs"] > 0), 0),
    ]


def _differs(got, want) -> str:
    """Where a doc's plain value leaves the reference's (for the log)."""
    if not isinstance(got, dict):
        return f"a {type(got).__name__}"
    out = []
    for k in sorted(set(got) | set(want)):
        g, w = got.get(k), want.get(k)
        if g == w:
            continue
        if isinstance(w, dict) and "__text__" in w and isinstance(g, dict):
            g, w = g.get("__text__", ""), w["__text__"]
            at = next((j for j, (a, b) in enumerate(zip(g, w)) if a != b),
                      min(len(g), len(w)))
            out.append(f"{k}: text of {len(g)} for {len(w)}, first "
                       f"difference at {at}")
        else:
            out.append(f"{k}: {g!r} for {w!r}")
    return "; ".join(out)


def _clocks(cell, rnd: Round, urls, refs) -> int:
    """Every doc's clock as B answers it (a `clock` read), against the
    reference's: B's own empty actor apart, feed for feed."""
    got: Dict[int, Any] = {}
    left = threading.Semaphore(0)
    for i, u in enumerate(urls):
        rnd.repo.read(u, {"kind": "clock"},
                      lambda v, i=i: (got.__setitem__(i, v), left.release()))
    for _ in urls:
        if not left.acquire(timeout=float(cell.mix["round_timeout_s"])):
            break
    bad = 0
    for i in range(len(urls)):
        clock = got.get(i)
        try:  # the answer: ["<actor>:<seq>", ...]
            have = {a: int(s) for a, _, s in (
                e.rpartition(":") for e in clock)}
        except (TypeError, ValueError, AttributeError):
            have = None
        if have is None or {
            a: s for a, s in have.items() if s
        } != refs[i]["clock"]:
            bad += 1
            if bad <= 3:
                log(f"doc {i}: clock {clock!r} != {refs[i]['clock']}")
    return bad


def _reopen(cell, state, last: Round, refs) -> Dict[str, int]:
    """A fresh repo with NO swarm on the directory B left opens
    `reopen_docs` of the sampled docs: what B holds survives it."""
    from hypermerge_tpu.repo import Repo

    docs = state["sample"][:int(cell.mix["reopen_docs"])]
    urls = state["urls"]
    with span("bench.facade.repo_init"):
        repo = Repo(path=_b_dir(cell, last.n))
    bad = 0
    try:
        with span("bench.loader.open_many"):
            handles = repo.open_many([urls[d] for d in docs])
        for d, h in zip(docs, handles):
            if plain(h.value(timeout=120)) != refs[d]["value"]:
                bad += 1
                if bad <= 3:
                    log(f"reopened doc {d} differs from the reference")
    finally:
        with span("bench.facade.close"):
            repo.close()
    return {"mismatches": bad + (0 if docs else 1), "docs": len(docs)}


def teardown(cell, state) -> None:
    kept = state.get("kept")
    if kept is not None:
        _close(cell, kept)
        state["kept"] = None
    for rnd in state.get("rounds", ()):
        _close(cell, rnd)
    a = state.pop("a", None)
    if a is not None:
        with span("bench.facade.close"):
            a.close()
