"""Traffic driver `catchup_rounds`: a peer that holds a store of shared
documents comes back online behind, round after round (closed loop, one
client, fixed work a round).

Peer A holds the whole store the corpus writer made (`Repo` on the
corpus, `open_many`, `fetch_bulk_summaries`) behind a `TcpSwarm` on
loopback and stays up. Peer B's directory is the SAME store cut
`blocks_held` blocks deep in every feed, as a clean stop leaves it
(`corpora/multi_writer_rounds_behind.py`); a round consumes a copy of
it, and set-up makes `rounds_prepared` copies, outside the window. A
round is, through the public API only:

  open        a fresh `Repo` B on its copy (`work/b-<n>`),
              `open_many(every url)`, `fetch_bulk_summaries` (B's bulk
              open of what it holds, on the TPU), one subscription a
              handle, until each has delivered the state B held;
  first read  one `Repo.read(url, {"kind": "len", "path": [seq_key]},
              cb)` of every document in waves of HM_SERVE_QUEUE, BEFORE
              B has a swarm: every entry becomes resident from the
              open's memo;
  converge    `set_swarm(TcpSwarm())`, connect to A, wait until every
              document's subscription has delivered an index of every
              change of its feeds;
  read        the same read of every document again, until the last
              answer: the entries the remote changes made stale are
              re-installed through the slab program on the device;
  close       B closes (its swarm with it), then one `gc.collect()`.

The close and the collection are inside EVERY round, the last one too:
what the checks need of B they take before the close (the states and
indexes its subscriptions delivered, its answers, the open's stats,
the counters) or from B's directory after the window.
Rounds start while the window's clock is under `--seconds`; one started
inside is finished and counted.
`ops_per_s` = rounds x docs x ops behind a doc / (end of the last
round's collection - start of the first round). `attempted` = documents
x rounds, `failed` = documents that did not converge, or whose first or
second read was not answered, within `round_timeout_s`.

Set-up: both stores from the seed, A's cold open, the copies of B's
directory, one warm round with `clone_rounds`' burst sizes in both read
phases (every install, re-install, advance and query program a timed
round can ask for compiled or loaded). The heap is frozen for the
window, as `clone-rounds-tcp` states it.
"""

from __future__ import annotations

import gc
import random
import shutil
import threading
import time
from typing import Any, Dict, List, Optional

from benchmark.drivers import clone_rounds
from benchmark.drivers.clone_rounds import (  # noqa: F401
    WARM_WAVES, _describe, _differs, _read_all, _warm_programs, before_jax,
)
from benchmark.harness import Check, Window, log, span
from benchmark.reference.plainify import plain

# what the checks are decided by, beside clone_rounds' own:
# `blocks_refetched` (a block that crossed for nothing extends no feed,
# so `blocks_rx` alone does not see it), `feeds_not_asked_from_len`,
# `sealed_heads_not_retired`
DECIDED_BY = clone_rounds.DECIDED_BY + (
    "net.repl.blocks_dup_rx", "net.repl.requests_from_len",
    "storage.feed.extended_sealed",
)

NOTED = (
    "live.adopt_held", "live.demoted", "serve.reinstalls",
    "serve.cold_reads", "storage.feed.log_writes",
    "frontend.remote_patches",
)


class Round(clone_rounds.Round):
    """One catch-up: B's repo, what its subscriptions and both reads
    delivered, and the seconds of its phases."""

    def __init__(self, n: int, docs: int) -> None:
        super().__init__(n, docs)
        self.first: Dict[int, Any] = {}  # the first reads' answers
        self.first_unanswered = 0
        self.indexes: List[List[int]] = [[] for _ in range(docs)]
        self.shown = threading.Event()  # every doc delivered once
        self.not_shown = docs
        self.open_stats: Dict[str, Any] = {}
        # B's tier, read before its close: entries a remote change
        # released (`refusals["remote"]`), the p99 of its serve.read_s
        self.stale_remote = 0
        self.read_p99_s: Optional[float] = None
        self.counters: Dict[str, float] = {}  # after the round's close
        self.moved: Dict[str, float] = {}  # NOTED + DECIDED_BY, the round's
        self.t_done = 0.0  # the end of the round's collection

    @property
    def failed(self) -> int:
        return len(self.left) + self.unanswered + self.first_unanswered

    @property
    def whole_s(self) -> float:
        """`round_s` (B's Repo() to the last answer, as a clone's) and
        the round's close and collection."""
        return self.t_done - self.t0


_b_dir = clone_rounds._b_dir  # work/b-<n>: round n's copy


def _catch_up(cell, state, n: int, trace_at: Optional[float] = None,
              waves=()) -> Round:
    """One round, its close and collection included. `trace_at` and
    `waves` as `clone_rounds._clone` takes them."""
    from hypermerge_tpu.net.tcp import TcpSwarm
    from hypermerge_tpu.repo import Repo

    urls = state["urls"]
    sample = state["sample_set"]
    changes = state["changes"]
    # (the warm round compiles, or loads, every program on its way)
    timeout = float(
        cell.mix["warm_timeout_s" if waves else "round_timeout_s"])
    docs = len(urls)
    rnd = Round(n, docs)
    lock = threading.Lock()
    done = threading.Event()
    now = time.perf_counter

    def left() -> float:
        return max(1.0, timeout - (now() - rnd.t0))

    def watch(i: int):
        keep = i in sample
        seen = rnd.indexes[i]

        def on_value(value, index) -> None:
            if keep:
                rnd.states[i] = value
            first = not seen
            seen.append(index)
            if first or index >= changes:
                with lock:
                    if first:
                        rnd.not_shown -= 1
                        if not rnd.not_shown:
                            rnd.shown.set()
                    if index >= changes and i in rnd.left:
                        rnd.left.discard(i)
                        rnd.at.append(now())
                        if not rnd.left:
                            done.set()
        return on_value

    c0 = cell.counters()
    state["unclosed"] = rnd  # teardown's, should the round raise
    rnd.t0 = t = now()
    with span("bench.catchup.open"):
        rnd.repo = repo = Repo(path=_b_dir(cell, n))
        rnd.handles = repo.open_many(urls)
        repo.back.fetch_bulk_summaries()
        rnd.open_stats = dict(repo.back.last_bulk_stats)
        for i, h in enumerate(rnd.handles):
            h.subscribe(watch(i))
        rnd.shown.wait(left())
    rnd.took["open"] = now() - t
    t = now()
    with span("bench.catchup.first_read"):
        _read_all(cell, state, rnd, left(), waves)
        rnd.first, rnd.answers = rnd.answers, {}
        rnd.first_unanswered = rnd.unanswered
    rnd.took["first_read"] = now() - t
    t = now()
    with span("bench.catchup.converge"):
        swarm = TcpSwarm()
        repo.set_swarm(swarm)
        swarm.connect(state["address"])
        few = 0.0
        if trace_at is not None and state.get("converge_s"):
            few = docs * (1.0 - trace_at / state["converge_s"])
        while not done.wait(0.05 if trace_at is not None else 1.0):
            waited = now() - t
            if now() - rnd.t0 > timeout:
                break
            if trace_at is not None and rnd.trace is None and (
                waited >= trace_at or len(rnd.left) <= few
            ):
                cell.tracer.start()
                rnd.trace = [now()]
    rnd.took["converge"] = now() - t
    if trace_at is not None and rnd.trace is None:
        cell.tracer.start()  # the catch-up outran the plan: reads only
        rnd.trace = [now()]
    t = now()
    with span("bench.catchup.read"):
        _read_all(cell, state, rnd, left(), waves)
    rnd.t_end = now()
    rnd.took["read"] = rnd.t_end - t
    if rnd.trace is not None:
        cell.tracer.stop()
        rnd.trace.append(now())
    if waves:  # the warm round: the query programs at every batch size
        cell.notes["warm_programs"] = _warm_programs(cell, state, rnd)
    tier = repo.back.serve
    if tier is not None:
        from hypermerge_tpu.serve.overload import HistogramWindow

        rnd.stale_remote = tier.refusals.get("remote", 0)
        rnd.read_p99_s = HistogramWindow(tier._hist).quantile(0.99)
    t = now()
    with span("bench.catchup.close"):
        for h in rnd.handles:
            h.close()
        rnd.handles = []
        rnd.repo = None
        repo.close()
        # every name of this frame that still reaches B (`h` is the
        # loops' last handle): with one left, the collection frees
        # nothing of B and its garbage rides into the next round
        del repo, swarm, tier, h
        gc.collect()
    rnd.t_done = now()
    rnd.took["close"] = rnd.t_done - t
    state["unclosed"] = None
    rnd.counters = c1 = cell.counters()
    rnd.moved = {k: c1[k] - c0.get(k, 0) for k in NOTED + DECIDED_BY
                 if c1.get(k, 0) != c0.get(k, 0)}
    return rnd


# -- set-up ------------------------------------------------------------------


def _program_or_exit(cell) -> None:
    """A program from before the deployment (the parent of the PR that
    added it) lacks a counter the checks are decided by: fail at
    set-up's start, cleanly, as `clone_rounds` does, before the stores
    are waited for. The counters are there from the moment a repo has
    a swarm, so a repo in memory says it."""
    from hypermerge_tpu.net.tcp import TcpSwarm
    from hypermerge_tpu.repo import Repo

    probe = Repo(memory=True)
    try:
        probe.set_swarm(TcpSwarm())
        lacks = sorted(set(DECIDED_BY) - set(cell.counters()))
    finally:
        probe.close()
    if lacks:
        log(f"FAILED: this program has no counter {lacks}: it cannot run "
            f"{cell.name}")
        raise SystemExit(5)


def setup(cell, job) -> Dict[str, Any]:
    from hypermerge_tpu.net.tcp import TcpSwarm
    from hypermerge_tpu.repo import Repo

    _program_or_exit(cell)
    t0 = time.perf_counter()
    urls = job.finish()
    cell.notes["corpus_wait_s"] = round(time.perf_counter() - t0, 3)
    corpus = cell.config["corpus"]
    docs = len(urls)
    rng = random.Random(cell.seed)
    sample = sorted(rng.sample(
        range(docs), min(docs, int(cell.mix["verify_sample_docs"]))))
    per_change = int(corpus["ops_per_change"])
    changes = int(corpus["ops"]) // per_change
    held = [job.held * d["writers"] for d in job.plan]
    t0 = time.perf_counter()
    with span("bench.facade.repo_init"):
        repo = Repo(path=cell.work + "/repo")
    state: Dict[str, Any] = {
        "a": repo, "job": job, "urls": urls, "sample": sample,
        "sample_set": frozenset(sample), "rounds": [],
        "changes": changes, "held": held,
        "feeds": sum(d["writers"] for d in job.plan),
        "ops_a_round": sum(
            (changes - h) * per_change for h in held),
    }
    try:
        swarm = TcpSwarm()
        repo.set_swarm(swarm)
        state["address"] = swarm.address
        with span("bench.loader.open_many"):
            repo.open_many(urls)
        with span("bench.loader.fetch_bulk_summaries"):
            repo.back.fetch_bulk_summaries()
        state["open_stats"] = dict(repo.back.last_bulk_stats)
        cell.notes["cold_open_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        prepared = int(cell.mix["rounds_prepared"])
        for n in range(prepared + 1):  # round 0 is the warm one
            shutil.copytree(job.behind_path, _b_dir(cell, n))
        state["prepared"] = prepared
        cell.notes["copies_s"] = round(time.perf_counter() - t0, 3)
        c0 = cell.counters()
        warm = _catch_up(cell, state, 0, waves=WARM_WAVES)
        cell.notes["warm_round"] = dict(
            _describe(cell, warm, c0, warm.counters),
            whole_s=round(warm.whole_s, 3))
        if warm.failed:
            log(f"FAILED: the warm round left {len(warm.left)} docs short, "
                f"{warm.first_unanswered} first and {warm.unanswered} "
                f"second reads unanswered")
            raise SystemExit(5)
        shutil.rmtree(_b_dir(cell, 0), ignore_errors=True)
        state["converge_s"] = warm.took["converge"]
        state["install_s"] = warm.took["first_read"]
    except BaseException:
        teardown(cell, state)
        raise
    return state


# -- the window --------------------------------------------------------------


def window(cell, state, seconds: float) -> Window:
    docs = len(state["urls"])
    lead = float(cell.mix["trace_seconds"])
    rounds: List[Round] = state["rounds"]
    described: List[Dict[str, Any]] = []
    c_start = cell.counters()
    gc.freeze()  # as clone_rounds: set-up's survivors are not walked again
    try:
        t0 = time.perf_counter()
        while len(rounds) < state["prepared"]:
            n = len(rounds) + 1
            trace_at = max(0.0, state["converge_s"] - lead) \
                if cell.tracer.on and not rounds else None
            c0 = cell.counters()
            rnd = _catch_up(cell, state, n, trace_at)
            rounds.append(rnd)
            described.append(dict(
                _describe(cell, rnd, c0, rnd.counters),
                whole_s=round(rnd.whole_s, 3),
                **{k: round(v, 3) for k, v in rnd.moved.items()}))
            if rnd.failed or time.perf_counter() - t0 >= seconds:
                break
    finally:
        gc.unfreeze()
    c_end = cell.counters()
    elapsed = rounds[-1].t_done - t0
    done = [r for r in rounds if not r.failed]
    failed = sum(r.failed for r in rounds)
    cell.notes["rounds"] = described
    gc_s = c_end.get("host.gc_full_s", 0) - c_start.get("host.gc_full_s", 0)
    log(f"{len(done)} rounds of {docs} docs in {elapsed:.2f}s: "
        f"{[round(r.whole_s, 2) for r in rounds]}; collector "
        f"{gc_s:.2f}s in the window")

    def moved(name: str) -> float:
        return c_end.get(name, 0) - c_start.get(name, 0)

    def mean(vals: List[float]) -> Optional[float]:
        return sum(vals) / len(vals) if vals else None

    def phase(name: str) -> Optional[float]:
        return mean([r.took[name] for r in done])

    obs: Dict[str, Any] = {
        "round_s": mean([r.round_s for r in done]),
        "open_s": phase("open"),
        "first_read_s": phase("first_read"),
        "converge_s": phase("converge"),
        "read_s": phase("read"),
        "close_s": phase("close"),
        "install_s": state["install_s"],
        "rounds": len(done),
        # B's bulk open of every round, for the `bulk_stats` reader
        "bulk_stats": [r.open_stats for r in rounds],
    }
    p99s = [r.read_p99_s for r in rounds if r.read_p99_s is not None]
    if p99s:
        obs["serve_read_p99_ms"] = 1e3 * mean(p99s)
    caught = docs * len(rounds)
    if "live.adopted" in c_end:
        obs["adopts_per_doc"] = moved("live.adopted") / caught
    if moved("live.tick_changes") > 0:
        # of the ticks' kernel runs, those on the device; 0 too where
        # the ticks ran no kernel at all (every change applied op by op)
        obs["device_tick_pct"] = 100.0 * moved(
            "live.device_dispatches") / max(1.0, moved("live.kernel_runs"))
        obs["inc_ops_pct"] = 100.0 * moved(
            "live.inc_changes") / moved("live.tick_changes")
    if moved("serve.reads") > 0 and "serve.cold_reads" in c_end:
        obs["cold_read_pct"] = 100.0 * moved(
            "serve.cold_reads") / moved("serve.reads")
    if "live.t_adopt_lock_free" in c_end:
        # lock_free holds pack + kernel + decode + reach, lock_held the
        # install: together an adoption's wall on the thread that ran it
        obs["adopt_round_s"] = (
            moved("live.t_adopt_lock_free") + moved("live.t_adopt_lock_held")
        ) / len(rounds)
    behind = state["feeds"] * (
        state["changes"] // state["job"].plan[0]["writers"]
        - state["job"].held)
    if behind:
        obs["blocks_rx_per_behind"] = mean(
            [r.moved.get("net.repl.blocks_rx", 0) / behind for r in rounds])
    if "serve.reads" in c_end:
        # entries the catch-up released (B writes nothing: a remote
        # patch or a live tick), each met by one of the second reads
        obs["stale_reinstall_pct"] = 100.0 * sum(
            r.stale_remote for r in rounds) / caught
    traced = next((r for r in rounds if r.trace and len(r.trace) == 2), None)
    if traced is not None:
        from benchmark.drivers.ycsb_rw_loop import _traced_installs

        obs["traced_s"] = traced.trace[1] - traced.trace[0]
        obs["traced_kops"] = state["ops_a_round"] / 1e3
        obs["install_slabs"] = _traced_installs(cell.tracer.path)
        log(f"traced {obs['traced_s']:.2f}s of round {traced.n}: the end "
            f"of its convergence and its second reads")
    return Window(
        metrics={"ops_per_s": len(done) * state["ops_a_round"] / elapsed
                 if elapsed > 0 else 0.0},
        attempted=caught,
        failed=failed,
        obs=obs,
    )


# -- after the window --------------------------------------------------------


def verify(cell, state, win: Window) -> List[Check]:
    """Exact, every limit 0. Every round: every doc shown, converged
    and read twice; the first answers against the replay of what B
    held, the second answers, the sampled docs' values and every clock
    (from B's directory) against the replay of ALL the changes on A's
    disk; what each subscription was delivered; B's bulk open on the
    device; the blocks that crossed; B's disk against A's. The last
    round's directory is reopened by a fresh repo with no swarm."""
    from benchmark.reference import catchup_plain

    job, urls, sample = state["job"], state["urls"], state["sample"]
    rounds: List[Round] = state["rounds"]
    seq_key = cell.config["corpus"].get("seq_key", "t")
    changes, held = state["changes"], state["held"]
    t0 = time.perf_counter()
    refs: Dict[int, Dict[str, Any]] = {}
    was: Dict[int, Any] = {}
    cache: Dict[tuple, Any] = {}
    for i in range(len(urls)):
        doc = job.doc_changes(i, cache)
        refs[i] = catchup_plain.expect(doc, seq_key)
        was[i] = catchup_plain.held(doc, held[i], seq_key)["len"]
    log(f"reference {time.perf_counter() - t0:.2f}s: {len(refs)} docs")
    keys = [[p.public_key for p in pairs] for pairs in job.pairs]
    flat = [k for doc in keys for k in doc]
    bad_first = bad_read = bad_value = bad_clock = short = shown = 0
    refetched = not_on_device = not_from_len = still_sealed = 0
    disk = dict.fromkeys(("short", "differ", "unsigned"), 0)

    def say(msg: str) -> None:
        nonlocal shown
        if shown < 6:
            shown += 1
            log(msg)

    for rnd in rounds:
        clocks = catchup_plain.disk_clocks(_b_dir(cell, rnd.n))
        for i in range(len(urls)):
            if rnd.first.get(i) != was[i]:
                bad_first += 1
                say(f"round {rnd.n} doc {i}: first read "
                    f"{rnd.first.get(i)!r} != {was[i]} (held)")
            if rnd.answers.get(i) != refs[i]["len"]:
                bad_read += 1
                say(f"round {rnd.n} doc {i}: read "
                    f"{rnd.answers.get(i)!r} != {refs[i]['len']}")
            fault = catchup_plain.delivery_fault(
                rnd.indexes[i], held[i], changes)
            if fault:
                short += 1
                say(f"round {rnd.n} doc {i}: {fault}: {rnd.indexes[i]}")
            if clocks.get(keys[i][0]) != refs[i]["clock"]:
                bad_clock += 1
                say(f"round {rnd.n} doc {i}: clock on disk "
                    f"{clocks.get(keys[i][0])!r} != {refs[i]['clock']}")
        for i in sample:
            got = plain(rnd.states.get(i))
            if got != refs[i]["value"]:
                bad_value += 1
                say(f"round {rnd.n} doc {i}: its value differs from the "
                    f"reference: {_differs(got, refs[i]['value'])}")
        refetched += int(rnd.moved.get("net.repl.blocks_dup_rx", 0)) + abs(
            int(rnd.moved.get("net.repl.blocks_rx", 0))
            - sum(changes - h for h in held))
        # every feed asked from the feed's own length (one from 0 shows
        # in `blocks_refetched` too), and every sealed head's entry in
        # heads.snap retired by the feed's first extension, once
        not_from_len += max(0, state["feeds"] - int(
            rnd.moved.get("net.repl.requests_from_len", 0)))
        still_sealed += abs(state["feeds"] - int(
            rnd.moved.get("storage.feed.extended_sealed", 0)))
        stats = rnd.open_stats
        not_on_device += int(
            stats.get("platform") != ("cpu" if cell.rehearse else "tpu")
        ) + int(stats.get("host_slabs", 1) > 0)
        found = catchup_plain.compare_stores(
            cell.work + "/repo/feeds", _b_dir(cell, rnd.n) + "/feeds", flat)
        log(f"round {rnd.n} on disk: {found}")
        for k in disk:
            disk[k] += found[k]
    reopened = _reopen(cell, state, rounds[-1], refs)
    log(f"reopened: {reopened}")

    docs, n = len(urls), len(rounds)
    c1 = rounds[-1].counters
    tier = "serve.reads" in c1
    a_stats = state["open_stats"]
    return [
        Check("docs_not_converged", sum(len(r.left) for r in rounds), 0),
        Check("reads_unanswered", sum(
            r.unanswered + r.first_unanswered for r in rounds), 0),
        Check("docs_sampled_short",
              max(0, min(docs, int(cell.mix["verify_sample_docs"]))
                  - len(sample)), 0),
        Check("first_read_mismatches", bad_first, 0),
        Check("read_mismatches", bad_read, 0),
        Check("value_mismatches", bad_value, 0),
        Check("clock_mismatches", bad_clock, 0),
        Check("patches_short", short, 0),
        Check("host_answers",
              int(sum(c1.get(k, 0) for k in (
                  "serve.fallbacks", "serve.flush_errors",
                  "serve.overload_shed"))) if tier else 2 * docs * n, 0),
        Check("lanes_from_host_kernel",
              int(c1.get("serve.install_host_kernel_docs", 1)) if tier
              else 0, 0),
        # every doc of every round (the warm round's too) adopted by the
        # live engine WITH the rows it held (`adopt_held` counts those
        # of `adopted`), none refused, none evicted to the host OpSet
        Check("docs_not_live",
              int(max(0, docs * (n + 1) - c1.get("live.adopt_held", 0))
                  + c1.get("live.refused", 0) + c1.get("live.demoted", 0)),
              0),
        Check("open_not_on_device",
              not_on_device + int(
                  a_stats["platform"] != ("cpu" if cell.rehearse else "tpu"))
              + int(a_stats["host_slabs"] > 0), 0),
        Check("unsigned_accepted",
              int(c1.get("net.repl.unsigned_rx", 0)) + disk["unsigned"], 0),
        Check("blocks_refetched", refetched, 0),
        Check("feeds_not_asked_from_len", not_from_len, 0),
        Check("sealed_heads_not_retired", still_sealed, 0),
        Check("feeds_short_on_disk", disk["short"] + disk["differ"], 0),
        Check("reopen_mismatches", reopened["mismatches"], 0),
    ]


def _reopen(cell, state, last: Round, refs) -> Dict[str, int]:
    """A fresh repo with NO swarm on the directory the last round left
    opens `reopen_docs` of the sampled docs through `open_many` +
    `fetch_bulk_summaries`: what B caught up on survives it."""
    from hypermerge_tpu.repo import Repo

    docs = state["sample"][:int(cell.mix["reopen_docs"])]
    urls = state["urls"]
    with span("bench.facade.repo_init"):
        repo = Repo(path=_b_dir(cell, last.n))
    bad = 0
    try:
        with span("bench.loader.open_many"):
            handles = repo.open_many([urls[d] for d in docs])
        with span("bench.loader.fetch_bulk_summaries"):
            repo.back.fetch_bulk_summaries()
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
    rnd = state.pop("unclosed", None)
    if rnd is not None and rnd.repo is not None:
        rnd.repo.close()  # a round that raised before its close
    a = state.pop("a", None)
    if a is not None:
        with span("bench.facade.close"):
            a.close()
