#!/usr/bin/env python3
"""chip_smoke.py — the store's main path, once, on the accelerator.

The quickest proof that hypermerge-tpu still starts on a TPU: the
flagship deployment (BASELINE.json config 4: 10,240 docs x 1,024 ops,
real feeds + `cols.slab` sidecars + sqlite, written from `--seed`) is
cold-opened through `Repo.open_many`, read through the resident tier,
edited through the live engine, written durably through the hub daemon,
crashed and recovered — every answer checked against the host OpSet /
`host_read` reference. It claims nothing about speed: the seconds it
prints are observations of one run.

    python chip_smoke.py              # needs a TPU; fails without one
    python chip_smoke.py --rehearse   # same stages, tiny, on the CPU

One process holds a chip at a time, so this parent NEVER imports JAX:
it runs its stages as child processes, one after another, each exiting
before the next starts.

    stage 0  probe child (a machine without the chip fails in seconds),
             native layer rebuilt from source, corpus written
    stage 1  "store" child: cold open, sampled docs == OpSet replay,
             reads == host_read, edits through the live engine (device
             dispatch), close, reopen
    stage 2  "store" child, fresh process: the same work again with
             zero persistent-compile-cache misses
    stage 3  hub daemon child (HM_FSYNC=1 HM_ACK_DURABLE=1), frontends
             here: acknowledged edits, reads, Telemetry `device` block,
             SIGKILL; then a "store" child recovers and reads every
             acknowledged edit back

Stdout is two JSON lines, printed only once every stage has passed: the
report (counters, seconds, per-stage detail), then, as the LAST line,
the verdict with exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failed check or child makes the exit code non-zero and prints
neither.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
FULL_DOCS, FULL_OPS = 10240, 1024
MIN_DOCS = 5120  # one full slab and a ragged tail, never fewer
REHEARSAL_DOCS, REHEARSAL_OPS = 48, 128
OPS_PER_CHANGE = 16  # ops.corpus.make_corpus default
N_SAMPLE = 66  # docs verified against OpSet / host_read (>= 64)
N_EDIT = 64  # tail-slab docs edited through the live engine
N_HUB_DOCS, N_HUB_EDITS = 8, 64
READ_THREADS = 8
# the checked reads are paced, all threads together, well under one
# tenant's default quota (HM_QUOTA_READS_S=512): the smoke is not a
# load test, and a reader inside its quota is never refused even if
# the ladder climbs to SHED
READS_PER_S = 200


def log(*a) -> None:
    print("[smoke]", *a, file=sys.stderr, flush=True)


def check(cond, what: str, detail=None) -> None:
    """A smoke check that fails the run (never an `assert`: -O would
    remove it)."""
    if not cond:
        raise SystemExit(
            f"chip_smoke: FAILED: {what}"
            + (f"\n  {detail!r}" if detail is not None else "")
        )


# ---------------------------------------------------------------------------
# plain references — independent of the code under test


def plain(v):
    """A materialized doc tree as JSON-safe plain data."""
    from hypermerge_tpu.models import Counter, Table, Text

    if isinstance(v, Text):
        return {"__text__": str(v)}
    if isinstance(v, Counter):
        return {"__counter__": int(v)}
    if isinstance(v, Table):
        return {"__table__": {k: plain(v.by_id(k)) for k in v.ids}}
    if isinstance(v, dict):
        return {k: plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [plain(x) for x in v]
    return v


def feed_changes(back, doc_id):
    """The doc's changes read back from its feeds (every cursor actor's
    window) — what the OpSet reference replays."""
    out = []
    for actor_id, seq in back.cursors.get(back.id, doc_id).items():
        actor = back._get_or_create_actor(actor_id)
        out.extend(actor.changes_in_window(0, seq))
    return out


def opset_reference(changes):
    """(value, clock, live elements, keyed entries) from a host OpSet
    replay. Keyed entries are counted straight off the changes —
    (object, key) groups that still hold a visible op under
    observed-remove — because the synthetic corpus also writes keyed
    SETs against its text object, which OpSet's materialized tree does
    not show but the summary kernel counts."""
    from hypermerge_tpu.crdt.change import Action
    from hypermerge_tpu.crdt.opset import OpSet

    o = OpSet()
    o.apply_changes(changes)
    check(not o.missing_deps(), "OpSet replay has missing deps")
    elems = sum(
        len(o._live_elems(obj))
        for obj in o.objects.values()
        if obj.is_sequence
    )
    groups = {}
    for c in o.history:  # causal order
        for i, op in enumerate(c.ops):
            if op.key is None or op.action == Action.INC:
                continue
            vis = groups.setdefault((op.obj, op.key), set())
            vis.difference_update(op.pred)
            if op.action == Action.SET or op.action.makes_object:
                vis.add(c.op_id(i))
    entries = sum(1 for vis in groups.values() if vis)
    return plain(o.materialize()), dict(o.clock), elems, entries


# ---------------------------------------------------------------------------
# children (these import JAX; the parent never does)


class CacheWatch:
    """Persistent-compile-cache traffic of this process: request/hit
    counts from jax.monitoring, program names from the compiler log."""

    def __init__(self) -> None:
        import logging

        import jax

        self.requests = 0
        self.hits = 0
        self.missed = []
        jax.monitoring.register_event_listener(self._event)
        watch = self

        class _Names(logging.Handler):
            def emit(self, record):
                if "CACHE MISS" in str(record.msg) and record.args:
                    watch.missed.append(str(record.args[0]))

        lg = logging.getLogger("jax._src.compiler")
        lg.setLevel(logging.DEBUG)
        lg.propagate = False  # names only: keep the debug log off stderr
        lg.addHandler(_Names())

    def _event(self, event, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def report(self):
        return {
            "requests": self.requests,
            "hits": self.hits,
            "misses": self.requests - self.hits,
            "missed_programs": sorted(set(self.missed)),
        }


def child_probe(args, _state: dict) -> dict:
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
        "default_backend": jax.default_backend(),
    }


def plan(n_docs: int, n_ops: int, seed: int, env: dict) -> dict:
    """Which docs play which part, drawn by --seed: verified samples
    from EVERY slab; edited and hub-written docs from the TAIL slab
    only (an edited doc outgrows its row bucket and re-buckets its
    whole slab — keeping that to the tail keeps the full slabs at the
    product shape); merge sources from slab 0. `env` is the children's
    environment: the slab size and the device threshold they will run
    under."""
    rng = random.Random(seed)
    slab = int(env.get("HM_BULK_SLAB", "4096"))
    slabs = [
        list(range(b, min(b + slab, n_docs)))
        for b in range(0, n_docs, slab)
    ]
    per = math.ceil(N_SAMPLE / len(slabs))
    sample = []
    for s in slabs:
        sample += rng.sample(s, min(per, len(s)))
    tail = list(slabs[-1])
    rng.shuffle(tail)
    n_edit = min(N_EDIT, len(tail) // 2)
    edit = tail[:n_edit]
    hub = tail[n_edit : n_edit + N_HUB_DOCS]
    check(len(hub) == N_HUB_DOCS, "tail slab too small for the plan")
    # the live engine's own rule (backend/live.py _kernel): a tick runs
    # on the device when docs x padded rows >= HM_DEVICE_MIN_CELLS, so
    # ONE doc qualifies once its rows pass half of that
    min_cells = int(env.get("HM_DEVICE_MIN_CELLS", "131072"))
    n_merge = max(5, math.ceil((min_cells // 2 + 1) / n_ops) + 1)
    merge = rng.sample(slabs[0], n_merge)
    return {
        "tail": slabs[-1],
        "sample": sample,
        "edit": edit,
        "hub": hub,
        "merge": merge,
    }


def _open_corpus(path, urls, expect_platform, n_devices):
    """Repo(path) -> open_many -> summary barrier, with the checks every
    cold open of the smoke must pass. Returns (repo, handles, summaries,
    stats, wall seconds)."""
    from hypermerge_tpu.repo import Repo

    t0 = time.perf_counter()
    repo = Repo(path=path)
    handles = repo.open_many(urls)
    summ = repo.back.fetch_bulk_summaries()
    wall = time.perf_counter() - t0
    stats = dict(repo.back.last_bulk_stats)
    n = len(urls)
    brief = {k: v for k, v in stats.items() if not k.startswith("t_")}
    log(f"cold open {n} docs in {wall:.2f}s: {stats}")
    check(stats["docs"] == stats["fast"] == n, "docs == fast == n", brief)
    check(stats["fallback"] == 0, "fallback == 0", brief)
    check(stats["host_slabs"] == 0, "host_slabs == 0", brief)
    check(stats["device_slabs"] == stats["slabs"], "every slab on device",
          brief)
    check(stats["platform"] == expect_platform, "slab platform", brief)
    check(len(summ.doc_ids) == n, "summaries cover the corpus")
    if n_devices > 1:
        check(stats.get("rr_devices") == n_devices, "rr_devices", brief)
        check(
            sum(stats["slabs_per_chip"]) == stats["rr_slabs"]
            == stats["device_slabs"],
            "slabs_per_chip sums to rr_slabs", brief,
        )
    return repo, handles, summ, stats, wall


def _verify_docs(repo, summ, handles, urls, idxs, what: str) -> None:
    """Summary counts, clock and handle.value() == OpSet replay of the
    same changes read back from the feeds."""
    from hypermerge_tpu.utils.ids import validate_doc_url

    for i in idxs:
        doc_id = validate_doc_url(urls[i])
        value, clock, elems, entries = opset_reference(
            feed_changes(repo.back, doc_id)
        )
        got = summ.doc(doc_id)
        want = {"elems": elems, "map_entries": entries, "clock": clock}
        check(got == want, f"{what}: summary of doc {i}", (got, want))
        check(
            plain(handles[i].value(timeout=60)) == value,
            f"{what}: value of doc {i} == OpSet",
        )
    log(f"{what}: {len(idxs)} docs == OpSet replay")


def _queries(rng, n_elems: int):
    """Every read kind; (query, may_be_none)."""
    return [
        ({"kind": "len", "path": []}, False),
        ({"kind": "len", "path": ["t"]}, False),
        ({"kind": "text", "path": ["t"]}, False),
        ({"kind": "index", "path": ["t"],
          "index": rng.randrange(n_elems)}, False),
        ({"kind": "lookup", "path": ["t"]}, False),
        ({"kind": "lookup", "path": ["no-such-key"]}, True),
        ({"kind": "clock"}, False),
        ({"kind": "history"}, False),
    ]


def _read_all(repo, url, queries):
    """One reader thread's share: (answers, client-side seconds each),
    one read every READ_THREADS / READS_PER_S seconds."""
    period = READ_THREADS / READS_PER_S
    got, took = [], []
    for q, _ in queries:
        t0 = time.perf_counter()
        got.append(repo.read(url, dict(q)))
        took.append(time.perf_counter() - t0)
        time.sleep(max(0.0, period - took[-1]))
    return got, took


def _check_reads(repo, doc_id, queries, got) -> int:
    """Each answer == serve.tier.host_read of the same query."""
    from hypermerge_tpu.serve.tier import host_read

    doc = repo.back.docs[doc_id]
    for (q, may_be_none), value in zip(queries, got):
        want = host_read(doc, dict(q))
        check(want is not None, "host_read answered", q)
        check(value == want["value"], f"read {q} == host_read",
              (value, want["value"]))
        check(may_be_none or value is not None, f"read {q} is not None")
    return len(queries)


def _counters(prefix: str) -> dict:
    from hypermerge_tpu import telemetry

    return {
        k[len(prefix):]: v
        for k, v in telemetry.snapshot().items()
        if k.startswith(prefix) and isinstance(v, (int, float))
    }


def _reads_stage(repo, summ, urls, idxs, seed: int) -> dict:
    """Warm one read per kind and bucket, wait for the service plane to
    report healthy, then the checked reads from READ_THREADS threads."""
    from concurrent.futures import ThreadPoolExecutor

    from hypermerge_tpu.serve.overload import STATE_NAMES
    from hypermerge_tpu.utils.ids import validate_doc_url

    rng = random.Random(seed + 1)
    ctl = repo.back.overload
    check(repo.back.serve is not None, "repo.back.serve is not None")
    check(ctl is not None, "service plane is on (HM_SERVICE default)")
    jobs = []
    for i in idxs:
        doc_id = validate_doc_url(urls[i])
        n_elems = summ.doc(doc_id)["elems"]
        jobs.append((urls[i], doc_id, _queries(rng, n_elems)))

    seen, stop = [], threading.Event()

    def watch():  # the ladder's transitions, as the smoke saw them
        prev, t0 = ctl.state(), time.perf_counter()
        while not stop.wait(0.02):
            cur = ctl.state()
            if cur != prev:
                seen.append([
                    round(time.perf_counter() - t0, 2),
                    STATE_NAMES[prev], STATE_NAMES[cur],
                ])
                prev = cur

    th = threading.Thread(target=watch, daemon=True)
    th.start()
    try:
        # warm-up: the first read of each ("serve", kind, B, N) bucket
        # compiles for seconds, which the ladder may read as overload
        # (BROWNOUT defers cold installs, SHED refuses reads) — so
        # compile and install everything BEFORE the checked reads
        t0 = time.perf_counter()
        for q, _ in jobs[0][2]:  # every kind alone: the B=1 programs
            _warm_read(repo, jobs[0][0], q)
        for url, _doc_id, queries in jobs:  # one read each: installs
            _warm_read(repo, url, queries[0][0])
        # BROWNOUT defers cold installs to the host path: once the
        # ladder is back down, read until every doc is resident
        deadline = time.monotonic() + 120
        while True:
            _wait_healthy(ctl, deadline)
            resident = repo.back.serve.residency_report()["resident"]
            cold = [j for j in jobs if j[1] not in resident]
            if not cold:
                break
            for url, _doc_id, queries in cold:
                _warm_read(repo, url, queries[0][0])
        warmed = _warm_buckets(repo)  # the B=2..READ_THREADS programs
        _wait_healthy(ctl, deadline)
        warm_s = time.perf_counter() - t0
        before = ctl.report()
        t0 = time.perf_counter()
        with ThreadPoolExecutor(READ_THREADS) as pool:
            done = list(pool.map(
                lambda j: _read_all(repo, j[0], j[2]), jobs
            ))
        reads_s = time.perf_counter() - t0
        after = ctl.report()
    finally:
        stop.set()
        th.join(5)
        log(f"ladder transitions during the reads stage: {seen}")
    # the references AFTER the reads: host materialization is seconds
    # of GIL-bound Python that would sit on the readers' latency
    n_reads = sum(
        _check_reads(repo, j[1], j[2], got)
        for j, (got, _took) in zip(jobs, done)
    )
    took = sorted(t for _got, ts in done for t in ts)
    read_ms = {
        "p50": round(1e3 * took[len(took) // 2], 2),
        "p99": round(1e3 * took[min(len(took) - 1,
                                    int(0.99 * len(took)))], 2),
        "max": round(1e3 * took[-1], 2),
    }
    check(n_reads >= 256 or len(idxs) < 32, "at least 256 checked reads")
    for k in ("shed_reads", "brownout_reads"):
        check(after[k] == before[k], f"no growth in {k} over the reads",
              (before, after))
    serve = _counters("serve.")
    check(serve["installs"] >= min(64, len(idxs)), "serve.installs", serve)
    check(serve["dispatches"] >= 1, "serve.dispatches >= 1", serve)
    for k in ("fallbacks", "evictions_pressure", "flush_errors"):
        check(serve[k] == 0, f"serve.{k} == 0", serve)
    log(f"reads: {n_reads} == host_read, paced at {READS_PER_S}/s from "
        f"{READ_THREADS} threads, client-side ms {read_ms} "
        f"(warm-up {warm_s:.2f}s incl. {len(warmed)} bucket programs)")
    return {
        "reads_checked": n_reads,
        "reads_s": round(reads_s, 3),
        "read_ms": read_ms,
        "reads_warm_s": round(warm_s, 3),
        "ladder_transitions": seen,
        "serve": {
            k: int(serve[k]) for k in (
                "installs", "dispatches", "fallbacks",
                "evictions_pressure", "flush_errors", "reads", "hits",
            )
        },
    }


def _warm_buckets(repo) -> list:
    """Compile every ("serve", kind, B, N) program the checked reads
    can ask for and the single warm-up reads did not (a batch's size
    depends on thread timing, so replaying traffic cannot promise
    every bucket): straight through the query kernels, over resident
    entries. Returns the buckets it compiled."""
    from hypermerge_tpu.parallel import sharded
    from hypermerge_tpu.serve import kernels

    entries = list(repo.back.serve._cache._entries.values())
    check(len(entries) >= READ_THREADS, "resident entries to warm with")
    added = []
    for n_rows in sorted({e.bucket for e in entries}):
        es = [e for e in entries if e.bucket == n_rows]
        B = 1
        while B <= READ_THREADS and B <= len(es):
            for kind in kernels.KINDS:
                if ("serve", kind, B, n_rows) not in sharded.trace_counts:
                    getattr(kernels, kind)(es[:B], [-1] * B, [0] * B)
                    added.append([kind, B, n_rows])
            B *= 2
    return added


def _wait_healthy(ctl, deadline: float) -> None:
    calm = 0
    while calm < 10:  # 10 consecutive healthy polls (~0.5 s)
        check(time.monotonic() < deadline,
              "service plane healthy after warm-up", ctl.report())
        calm = calm + 1 if ctl.state() == 0 else 0
        time.sleep(0.05)


def _warm_read(repo, url, q) -> None:
    from hypermerge_tpu.serve.overload import Overload

    try:
        repo.read(url, dict(q))
    except Overload:
        pass  # SHED during the compile storm: that is what warm-up is for


def _edit_docs(repo, handles, urls, idxs) -> dict:
    """A burst of local edits on bulk-loaded docs: adoption by the live
    engine (no host replay), values == OpSet, expected state returned
    for the reopen checks."""
    from hypermerge_tpu.models import Counter
    from hypermerge_tpu.utils.ids import validate_doc_url

    live0 = dict(repo.back.live.stats)
    for i in idxs:
        def edit(d, i=i):
            d["smoke"] = i
            d["t"].insert(0, "S")
            d["hits"] = Counter(i)

        repo.change(urls[i], edit)
        repo.change(urls[i], lambda d: d.increment("hits", 2))
    repo.back.live.flush_now(timeout=60)
    live = dict(repo.back.live.stats)
    check(live["adopted"] - live0["adopted"] >= len(idxs),
          "every edited doc adopted by the live engine", live)
    check(live["refused"] == 0, "live.refused == 0", live)
    expected = {}
    for i in idxs:
        doc_id = validate_doc_url(urls[i])
        value = plain(handles[i].value(timeout=60))
        ref, _clock, _e, _m = opset_reference(
            feed_changes(repo.back, doc_id)
        )
        check(value == ref, f"edited doc {i} == OpSet")
        check(value["smoke"] == i and value["hits"] == {
            "__counter__": i + 2}, f"edits applied to doc {i}", value)
        check(repo.back.docs[doc_id].opset is None,
              f"edited doc {i} took no host replay")
        expected[urls[i]] = value
    log(f"edits: {len(idxs)} docs edited through the live engine == OpSet")
    return expected


def _hot_doc_burst(repo, summ, urls, idxs):
    """The burst the live engine's own rule sends to the device: a hot
    doc adopts other docs' writers (`repo.merge`), each a remote burst
    of one whole history, until its padded rows clear
    HM_DEVICE_MIN_CELLS on their own. One merge per tick (each is
    applied before the next is sent), so a second process that repeats
    the burst dispatches exactly the same program shapes. Returns
    (url, value, report); the value is checked against an OpSet replay
    of every merged feed."""
    from hypermerge_tpu.models import Counter
    from hypermerge_tpu.utils.ids import validate_doc_url

    live0 = dict(repo.back.live.stats)
    url = repo.create({"title": "hot doc", "hits": Counter(0)})
    repo.change(url, lambda d: d.increment("hits", 3))
    repo.close_doc(url)  # reopened lazily, so the engine can adopt it
    handle = repo.open(url)
    check(plain(handle.value(timeout=60))["hits"] == {"__counter__": 3},
          "hot doc reopened lazily")
    doc = repo.back.docs[validate_doc_url(url)]
    t0 = time.perf_counter()
    for i in idxs:
        want = summ.doc(validate_doc_url(urls[i]))["clock"]
        repo.merge(url, urls[i])
        deadline = time.monotonic() + 120
        while any(doc.clock.get(a, 0) < s for a, s in want.items()):
            check(time.monotonic() < deadline, f"history of doc {i} merged")
            time.sleep(0.002)
        check(repo.back.live.flush_now(timeout=120), "live engine drained")
    merge_s = time.perf_counter() - t0
    live = dict(repo.back.live.stats)
    check(live["device_dispatches"] - live0["device_dispatches"] >= 1,
          "live.device_dispatches rose", (live0, live))
    check(live["refused"] == 0, "live.refused == 0", live)
    value = plain(handle.value(timeout=120))
    ref, _c, _e, _m = opset_reference(
        feed_changes(repo.back, validate_doc_url(url))
    )
    check(value == ref, "hot doc == OpSet replay of every merged feed")
    log(f"live: {len(idxs)} histories merged into a hot doc in "
        f"{merge_s:.2f}s == OpSet, device dispatches "
        f"{live0['device_dispatches']} -> {live['device_dispatches']}")
    return url, value, {
        "merged": len(idxs),
        "merge_s": round(merge_s, 3),
        "live": {k: live[k] for k in (
            "adopted", "refused", "ticks", "kernel_runs",
            "device_dispatches", "local_changes",
        )},
    }


def _summary_rows(summ, doc_id):
    """One doc's rows of the slab summary arrays (copies)."""
    arrays, j = summ.arrays(doc_id)
    return {
        k: arrays[k][j].copy() for k in (
            "map_winner", "elem_live", "elem_order", "n_live_elems",
            "n_map_entries", "clock",
        )
    }


def _hbm_peak():
    import jax

    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out.append(ms.get("peak_bytes_in_use"))
    return out


def child_first(args, state: dict) -> dict:
    """Stage 1: holds the chip for the whole main path."""
    import jax

    from hypermerge_tpu.utils.ids import validate_doc_url

    t_start = args.t_start
    watch = CacheWatch()
    expect = "cpu" if args.rehearse else "tpu"
    log("jax.default_backend() =", jax.default_backend(), jax.devices())
    check(jax.default_backend() == expect, "jax.default_backend()")
    n_devices = len(jax.devices())
    urls, p = state["urls"], state["plan"]
    out = {"n_devices": n_devices}

    repo, handles, summ, stats, wall = _open_corpus(
        args.repo, urls, expect, n_devices
    )
    out["cold_open_s"] = round(wall, 3)
    out["setup_s"] = round(time.perf_counter() - t_start, 3)
    out["bulk_stats"] = {
        k: stats.get(k) for k in (
            "docs", "fast", "fallback", "device_slabs",
            "host_slabs", "platform", "pack_workers", "rr_devices",
            "rr_slabs", "slabs_per_chip", "wall_critical_path",
        ) if k in stats
    }
    _verify_docs(repo, summ, handles, urls, p["sample"], "cold open")
    out.update(_reads_stage(repo, summ, urls, p["sample"], args.seed))

    # the clock mirror's device matrix (dense docs x actors): one
    # whole-corpus union against the host merge of the same clocks
    t0 = time.perf_counter()
    union = repo.back.clocks.union_query(repo.back.id)
    want = {}
    for d in summ.doc_ids:
        for a, s in summ.doc(d)["clock"].items():
            want[a] = max(want.get(a, 0), s)
    check(union == want, "clock mirror union == host merge")
    out["mirror_union_s"] = round(time.perf_counter() - t0, 3)

    expected = _edit_docs(repo, handles, urls, p["edit"])

    hot_url, expected_hot, out["live"] = _hot_doc_burst(
        repo, summ, urls, p["merge"]
    )
    if n_devices > 1:
        # where the long-lived device state sits (a finding for the
        # one-chip-per-worker work, not a check)
        out["mesh"] = {"state_devices": {
            "serve": sorted({
                d.id for e in repo.back.serve._cache._entries.values()
                for d in e.dev.devices()
            }),
            "clock_mirror": sorted(
                d.id for d in repo.back.clocks.mirror._matrix.devices()
            ),
        }}
        mesh0_summaries = {
            i: _summary_rows(summ, validate_doc_url(urls[i]))
            for i in p["sample"]
        }
        mesh0_docs = {
            i: summ.doc(validate_doc_url(urls[i])) for i in p["sample"]
        }
        tail = set(p["tail"])
    repo.close()

    # reopen in the same process: the edits are there
    repo, handles, summ, stats, wall = _open_corpus(
        args.repo, urls, expect, n_devices
    )
    out["reopen_s"] = round(wall, 3)
    for i in p["edit"]:
        check(plain(handles[i].value(timeout=60)) == expected[urls[i]],
              f"edited doc {i} survived the reopen")
        check(len(summ.doc(validate_doc_url(urls[i]))["clock"]) == 2,
              f"edited doc {i} has two writers")
    check(plain(repo.open(hot_url).value(timeout=120)) == expected_hot,
          "hot doc survived the reopen")
    repo.close()
    log("reopen: every edit read back")

    if n_devices > 1:
        # the same open on one device: identical summaries
        os.environ["HM_MESH"] = "0"
        try:
            import numpy as np

            from hypermerge_tpu.repo import Repo

            one = Repo(path=args.repo)
            one.open_many(urls)
            s1 = one.back.fetch_bulk_summaries()
            for i in p["sample"]:
                if i in p["edit"]:
                    continue
                rows = _summary_rows(s1, validate_doc_url(urls[i]))
                # (an unedited doc of the re-bucketed tail slab is
                # padded to the new row bucket: np.array_equal says no)
                check(i in tail or all(
                    np.array_equal(rows[k], mesh0_summaries[i][k])
                    for k in rows
                ), f"doc {i}: mesh summary rows == HM_MESH=0")
                check(s1.doc(validate_doc_url(urls[i])) == mesh0_docs[i],
                      f"doc {i}: mesh summary == HM_MESH=0")
            one.close()
        finally:
            del os.environ["HM_MESH"]

    out["peak_hbm_bytes"] = _hbm_peak()
    out["compile_cache"] = watch.report()
    out["stage_s"] = round(time.perf_counter() - t_start, 3)
    state.update(hot_url=hot_url, expected=expected,
                 expected_hot=expected_hot)
    return out


def child_second(args, state: dict) -> dict:
    """Stage 2: a fresh process runs the same programs (bulk, serve,
    live) and must find every one in the persistent compile cache."""
    import jax

    from hypermerge_tpu.utils.ids import validate_doc_url

    t_start = args.t_start
    watch = CacheWatch()
    expect = "cpu" if args.rehearse else "tpu"
    check(jax.default_backend() == expect, "jax.default_backend()")
    urls, p = state["urls"], state["plan"]
    repo, handles, summ, stats, wall = _open_corpus(
        args.repo, urls, expect, len(jax.devices())
    )
    out = {
        "cold_open_s": round(wall, 3),
        "setup_s": round(time.perf_counter() - t_start, 3),
    }
    rng = random.Random(args.seed + 2)
    unedited = [i for i in p["sample"] if i not in p["edit"]]
    n = 0
    for i in unedited[:2]:  # ten reads, every kernel kind, B=1
        doc_id = validate_doc_url(urls[i])
        qs = _queries(rng, summ.doc(doc_id)["elems"])[:5]
        n += _check_reads(
            repo, doc_id, qs, _read_all(repo, urls[i], qs)[0]
        )
    out["reads_checked"] = n
    check(plain(repo.open(state["hot_url"]).value(timeout=120))
          == state["expected_hot"], "hot doc as stage 1 left it")
    # the same burst on a second hot doc: the live program again
    _url, _value, out["live"] = _hot_doc_burst(
        repo, summ, urls, p["merge"]
    )
    repo.close()
    cache = watch.report()
    out["compile_cache"] = cache
    log("compile cache, second process:", cache)
    check(cache["requests"] > 0 and cache["hits"] > 0,
          "the second process used the persistent cache", cache)
    check(cache["misses"] == 0,
          "zero persistent-cache misses in the second process", cache)
    out["stage_s"] = round(time.perf_counter() - t_start, 3)
    return out


def child_recover(args, state: dict) -> dict:
    """After the daemon's SIGKILL: recovery runs, every acknowledged
    edit is read back, the two-writer docs agree with OpSet."""
    import jax

    from hypermerge_tpu.utils.ids import validate_doc_url

    t_start = args.t_start
    expect = "cpu" if args.rehearse else "tpu"
    urls, p = state["urls"], state["plan"]
    repo, handles, summ, stats, wall = _open_corpus(
        args.repo, urls, expect, len(jax.devices())
    )
    rep = repo.back.recovery_report
    check(rep is not None, "crash recovery ran on reopen")
    lost = 0
    for url, key, val in state["acked"]:
        i = urls.index(url)
        if plain(handles[i].value(timeout=60)).get(key) != val:
            lost += 1
    check(lost == 0, "acked_lost == 0", lost)
    _verify_docs(repo, summ, handles, urls, p["hub"], "after recovery")
    for i in p["hub"]:
        check(len(summ.doc(validate_doc_url(urls[i]))["clock"]) == 2,
              f"hub doc {i} has two writers")
    repo.close()
    return {
        "cold_open_s": round(wall, 3),
        "acked": len(state["acked"]),
        "acked_lost": lost,
        "recovery": {
            k: v for k, v in rep.items()
            if isinstance(v, (int, float, str, bool))
        },
        "stage_s": round(time.perf_counter() - t_start, 3),
    }


CHILDREN = {
    "probe": child_probe,
    "first": child_first,
    "second": child_second,
    "recover": child_recover,
}


def child_main(args) -> int:
    args.t_start = time.perf_counter()  # set-up counts the jax import
    sys.path.insert(0, HERE)
    state_path = os.path.join(args.work, "state.json")
    state = {}
    if os.path.exists(state_path):  # (the probe runs before there is one)
        with open(state_path) as fh:
            state = json.load(fh)
    result = CHILDREN[args.child](args, state)
    if state:
        with open(state_path, "w") as fh:
            json.dump(state, fh)
    with open(os.path.join(args.work, f"{args.child}.json"), "w") as fh:
        json.dump(result, fh)
    return 0


# ---------------------------------------------------------------------------
# the parent (JAX-free)


def child_env(args, work: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (HERE, env.get("PYTHONPATH")) if p
    )
    # explicit, so that JAX raises rather than settles for the CPU
    env["JAX_PLATFORMS"] = "cpu" if args.rehearse else "tpu"
    if args.rehearse:
        # tiny docs must still take the device paths; and the
        # cached-second-process check needs a cache, which the CPU
        # backend only keeps where the variable places one
        env["HM_DEVICE_MIN_CELLS"] = "0"
        env["HM_LIVE_INC_BUDGET"] = "0"
        env["HM_BULK_SLAB"] = "32"
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(work, "jax_cache")
    return env


def run_child(name: str, args, work: str, env: dict) -> dict:
    """One stage as its own process; its failure is the smoke's."""
    cmd = [
        sys.executable, os.path.abspath(__file__), "--child", name,
        "--work", work, "--repo", os.path.join(work, "repo"),
        "--seed", str(args.seed), "--docs", str(args.docs),
        "--ops", str(args.ops),
    ] + (["--rehearse"] if args.rehearse else [])
    t0 = time.perf_counter()
    log(f"--- child {name}")
    rc = subprocess.run(cmd, env=env, cwd=HERE, stdout=sys.stderr).returncode
    check(rc == 0, f"child {name!r} exited with code {rc}")
    with open(os.path.join(work, f"{name}.json")) as fh:
        out = json.load(fh)
    out["process_s"] = round(time.perf_counter() - t0, 3)
    return out


def wait_for(fn, what: str, timeout: float = 120.0):
    deadline = time.monotonic() + timeout
    while True:
        v = fn()
        if v:
            return v
        check(time.monotonic() < deadline, f"timed out: {what}")
        time.sleep(0.02)


def hub_stage(args, work: str, env: dict, state: dict) -> dict:
    """Stage 3: the hub daemon holds the chip; the frontends live in
    this JAX-free parent. A writer connection edits, a SEPARATE
    observer connection sees each edit (the writer's own handle is
    optimistic) — that sighting is the acknowledgement."""
    from hypermerge_tpu.net.ipc import connect_frontend

    t_start = time.perf_counter()
    sock_dir = tempfile.mkdtemp(prefix="hmsmoke")  # short unix path
    sock = os.path.join(sock_dir, "hub.sock")
    denv = dict(env, HM_WORKERS="0", HM_FSYNC="1", HM_ACK_DURABLE="1")
    proc = subprocess.Popen(
        [sys.executable, "-m", "hypermerge_tpu.net.ipc",
         os.path.join(work, "repo"), sock, "--hub"],
        env=denv, cwd=HERE, stdout=sys.stderr,
    )
    closers = []
    try:
        wait_for(
            lambda: os.path.exists(sock) or proc.poll() is not None,
            "hub daemon socket",
        )
        check(proc.poll() is None, "hub daemon is running")
        urls = [state["urls"][i] for i in state["plan"]["hub"]]
        writer, close_w = connect_frontend(sock)
        closers.append(close_w)
        observer, close_o = connect_frontend(sock)
        closers.append(close_o)
        wh = {u: writer.open(u) for u in urls}
        oh = {u: observer.open(u) for u in urls}
        for u in urls:
            wait_for(lambda u=u: "t" in (wh[u].value(timeout=300) or {}),
                     "writer sees the doc", 300)
            wait_for(lambda u=u: "t" in (oh[u].value(timeout=300) or {}),
                     "observer sees the doc", 300)
        acked = []
        t0 = time.perf_counter()
        for k in range(N_HUB_EDITS):
            u, key = urls[k % len(urls)], f"w{k}"

            def edit(d, k=k, key=key):
                d[key] = k
                d["t"].insert(0, "H")

            writer.change(u, edit)
            wait_for(lambda: oh[u].value().get(key) == k,
                     f"observer sees edit {k}", 120)
            acked.append([u, key, k])
        edits_s = time.perf_counter() - t0
        n_reads = 0
        for u in urls:  # reads answer the observer's own state
            seen = oh[u].value()
            for q, want in (
                ({"kind": "text", "path": ["t"]}, str(seen["t"])),
                ({"kind": "len", "path": ["t"]}, len(seen["t"])),
                ({"kind": "lookup", "path": [acked[-1][1]]},
                 seen.get(acked[-1][1])),
                ({"kind": "lookup", "path": ["t"]}, {"_type": "text"}),
            ):
                got = observer.read(u, q, timeout=120)
                check(got == want, f"front.read {q} == observer state",
                      (got, want))
                n_reads += 1
        box = []
        observer.telemetry(box.append)
        payload = wait_for(lambda: box and box[0], "Telemetry reply")
        device = payload.get("device")
        log("hub Telemetry device block:", device)
        check(device is not None, "Telemetry reply has a device block")
        check(device["platform"] == ("cpu" if args.rehearse else "tpu"),
              "hub daemon computes on the expected platform", device)
        counters = payload["counters"]
        check(counters.get("serve.fallbacks", 0) == 0,
              "hub serve.fallbacks == 0")
        check(counters.get("serve.flush_errors", 0) == 0,
              "hub serve.flush_errors == 0")
        # crash: no clean close, no final flush — after the last ack
        proc.send_signal(signal.SIGKILL)
        proc.wait(30)
        state["acked"] = acked
        log(f"hub: {len(acked)} edits acknowledged in {edits_s:.2f}s, "
            f"{n_reads} reads == observer state; daemon SIGKILLed")
        return {
            "acked": len(acked),
            "edits_s": round(edits_s, 3),
            "reads_checked": n_reads,
            "device": device,
            "serve_dispatches": int(counters.get("serve.dispatches", 0)),
            "stage_s": round(time.perf_counter() - t_start, 3),
        }
    finally:
        for close in closers:
            close()
        if proc.poll() is None:
            proc.kill()
            proc.wait(30)
        shutil.rmtree(sock_dir, ignore_errors=True)


def build_native() -> None:
    """Rebuild the native layer from source: a stale or foreign .so in
    the tree must not mask a build that no longer works."""
    native_dir = os.path.join(HERE, "hypermerge_tpu", "native")
    rc = subprocess.run(
        ["make", "-B", "-C", native_dir], stdout=sys.stderr
    ).returncode
    check(rc == 0, "make -B -C hypermerge_tpu/native")
    from hypermerge_tpu import native

    check(native.caps() == 7, "native.caps() == 7", native.caps())
    check(native.pack_drops_gil(), "native.pack_drops_gil()")
    check(native.codec_drops_gil(), "native.codec_drops_gil()")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="the same stages at 48 docs x 128 ops on the CPU")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--docs", type=int, default=None,
                    help=f"cut the corpus (never below {MIN_DOCS}; "
                    "the output says reduced)")
    ap.add_argument("--ops", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    ap.add_argument("--repo", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.rehearse:
        args.docs, args.ops = REHEARSAL_DOCS, REHEARSAL_OPS
    else:
        args.docs = FULL_DOCS if args.docs is None else args.docs
        args.ops = FULL_OPS
        check(args.docs >= MIN_DOCS, f"--docs is at least {MIN_DOCS}")
    if args.child:
        return child_main(args)

    if not os.path.isdir(os.path.join(HERE, "hypermerge_tpu")):
        print("chip_smoke: the hypermerge_tpu package is not beside this "
              "script; nothing to run", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    t_start = time.perf_counter()
    root = os.path.join(HERE, ".smoke")
    os.makedirs(root, exist_ok=True)
    work = tempfile.mkdtemp(prefix="run-", dir=root)
    env = child_env(args, work)
    try:
        # stage 0: a machine without the chip fails HERE, in seconds
        device = run_child("probe", args, work, env)
        log("device:", device)
        check(args.rehearse or device["platform"] == "tpu",
              "JAX found a TPU", device)
        check(not args.rehearse or device["platform"] == "cpu",
              "the rehearsal runs on the CPU", device)
        build_native()
        from hypermerge_tpu.ops.corpus import make_corpus

        t0 = time.perf_counter()
        urls = make_corpus(
            os.path.join(work, "repo"), args.docs, args.ops,
            ops_per_change=OPS_PER_CHANGE, seed=args.seed,
        )
        corpus_s = time.perf_counter() - t0
        log(f"corpus: {args.docs} docs x {args.ops} ops in {corpus_s:.1f}s")
        state = {
            "urls": urls,
            "plan": plan(args.docs, args.ops, args.seed, env),
        }
        with open(os.path.join(work, "state.json"), "w") as fh:
            json.dump(state, fh)

        first = run_child("first", args, work, env)
        second = run_child("second", args, work, env)
        with open(os.path.join(work, "state.json")) as fh:
            state = json.load(fh)
        hub = hub_stage(args, work, env, state)
        with open(os.path.join(work, "state.json"), "w") as fh:
            json.dump(state, fh)
        recover = run_child("recover", args, work, env)
        check("jax" not in sys.modules, "the parent never imported jax")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    verdict = {
        "ok": True,
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    }
    report = {
        "ok": True,
        "platform": device["platform"],
        "device_kind": device["kind"],
        "n_devices": device["count"],
        "docs": args.docs,
        "ops_per_doc": args.ops,
        "reduced": (
            {"docs": args.docs}
            if not args.rehearse and args.docs != FULL_DOCS else {}
        ),
        "seed": args.seed,
        "corpus_s": round(corpus_s, 3),
        "host_slabs": first["bulk_stats"]["host_slabs"],
        "fallback": first["bulk_stats"]["fallback"],
        "serve": first["serve"],
        "live": first["live"]["live"],
        "acked_lost": recover["acked_lost"],
        "cache_misses_cached_process": second["compile_cache"]["misses"],
        "setup_s": {"first_process": first["setup_s"],
                    "cached_process": second["setup_s"]},
        "cold_open_s": {"first_process": first["cold_open_s"],
                        "cached_process": second["cold_open_s"]},
        "peak_hbm_bytes": first["peak_hbm_bytes"],
        "stage1_store": first,
        "stage2_cached": second,
        "stage3_hub": hub,
        "stage3_recover": recover,
        "total_s": round(time.perf_counter() - t_start, 3),
    }
    if args.rehearse:
        report["rehearsal"] = True
    if device["count"] > 1:
        report["slabs_per_chip"] = first["bulk_stats"]["slabs_per_chip"]
    # two lines, both only after every stage passed: the report, then
    # the verdict, which is the LAST line and holds exactly these keys
    print(json.dumps(report))
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
