"""Traffic driver `cold_open_loop`: whole cold opens of a corpus on
disk, one after another (closed loop, one client).

A request is: a fresh `Repo` on the corpus, `open_many(every url)`,
`fetch_bulk_summaries()`, `close()`. `ops_per_s` is the ops of the
opens completed over the time from the window's start to the last
completion; an open that started inside the window is finished and
counted. Set-up writes the corpus from the seed (in a pool, while JAX
starts), and makes one open so that every slab program is compiled and
the files are in the page cache, as they are for a peer that restarts.
"""

from __future__ import annotations

import os
import random
import time
from typing import Any, Dict, List

from benchmark.harness import Check, Window, log, span
from benchmark.reference.plainify import plain


def before_jax(cell):
    t0 = time.perf_counter()
    job = cell.corpus_job()
    cell.notes["corpus_start_s"] = round(time.perf_counter() - t0, 3)
    return job


def _open(cell, urls):
    """One request. Returns (repo, handles, summaries, stats, seconds);
    the caller closes the repo."""
    from hypermerge_tpu.repo import Repo

    t0 = time.perf_counter()
    with span("bench.facade.repo_init"):
        repo = Repo(path=cell.work + "/repo")
    with span("bench.loader.open_many"):
        handles = repo.open_many(urls)
    with span("bench.loader.fetch_bulk_summaries"):
        summ = repo.back.fetch_bulk_summaries()
    took = time.perf_counter() - t0
    return repo, handles, summ, dict(repo.back.last_bulk_stats), took


def setup(cell, job) -> Dict[str, Any]:
    t0 = time.perf_counter()
    urls = job.finish()
    cell.notes["corpus_wait_s"] = round(time.perf_counter() - t0, 3)
    n_ops = sum(d["n_ops"] for d in job.plan)
    took: List[float] = []
    for _ in range(int(cell.mix.get("warm_opens", 1))):
        repo, _h, _s, stats, dt = _open(cell, urls)
        with span("bench.facade.close"):
            repo.close()
        took.append(round(dt, 3))
    cell.notes["warm_open_s"] = took
    return {"job": job, "urls": urls, "n_ops": n_ops, "last": None}


def window(cell, state, seconds: float) -> Window:
    urls = state["urls"]
    opens: List[Dict[str, Any]] = []
    t0 = time.perf_counter()
    last_done = t0
    while time.perf_counter() - t0 < seconds:
        if state["last"] is not None:  # one store, one process at a time
            with span("bench.facade.close"):
                state["last"][0].close()
            state["last"] = None
        traced = cell.tracer.on and not opens  # trace one whole open
        if traced:
            cell.tracer.start()
        repo, handles, summ, stats, took = _open(cell, urls)
        last_done = time.perf_counter()
        if traced:
            cell.tracer.stop()
        # the last open stays up for the check of its outputs, so its
        # close falls outside the window; every other close is inside
        state["last"] = (repo, handles, summ)
        opens.append({"took_s": took, "stats": stats})
    elapsed = last_done - t0
    done = len(opens)
    log(f"{done} opens in {elapsed:.2f}s: {[round(o['took_s'], 2) for o in opens]}")
    slabs = _slab_shapes(state["job"].plan)
    return Window(
        metrics={"ops_per_s": done * state["n_ops"] / elapsed},
        attempted=done,
        failed=0,
        obs={
            "bulk_stats": [o["stats"] for o in opens],
            "opens": done,
            "open_s": [o["took_s"] for o in opens],
            "bulk_slabs": slabs,
            "traced_opens": 1,
        },
    )


def _slab_shapes(plan):
    """[docs, padded rows] of each bulk slab, from the corpus plan and
    the program's slab size (HM_BULK_SLAB, default 4096): what
    counts/bulk_slab.py reckons bytes from."""
    slab = int(os.environ.get("HM_BULK_SLAB", "4096"))
    shapes = []
    for b in range(0, len(plan), slab):
        docs = plan[b:b + slab]
        rows = max(d["n_ops"] for d in docs)
        shapes.append([len(docs), 1 << max(0, (rows - 1).bit_length())])
    return shapes


def verify(cell, state, win: Window) -> List[Check]:
    """Exact, against the plain reference's replay of the changes the
    corpus writer put on disk: a seeded sample of docs (values,
    summaries) from every slab, all summaries present, no slab on the
    host twin."""
    from hypermerge_tpu.utils.ids import validate_doc_url

    repo, handles, summ = state["last"]
    urls, job = state["urls"], state["job"]
    n = len(urls)
    rng = random.Random(cell.seed)
    k = min(n, int(cell.mix["verify_sample_docs"]))
    sample = sorted(rng.sample(range(n), k))
    bad_summary = bad_value = 0
    refs: Dict[tuple, Any] = {}
    for i in sample:
        ref = job.doc_reference(i, refs)
        got = summ.doc(validate_doc_url(urls[i]))
        want = {k2: ref[k2] for k2 in ("elems", "map_entries", "clock")}
        if got != want:
            bad_summary += 1
            log(f"summary of doc {i}: {got} != {want}")
        if plain(handles[i].value(timeout=120)) != ref["value"]:
            bad_value += 1
            log(f"value of doc {i} differs from the reference")
    stats = win.obs["bulk_stats"]
    return [
        Check("docs_sampled_short", max(0, min(64, n) - len(sample)), 0),
        Check("summary_mismatches", bad_summary, 0),
        Check("value_mismatches", bad_value, 0),
        Check("summaries_missing", n - len(summ.doc_ids), 0),
        Check("host_slabs", sum(s["host_slabs"] for s in stats), 0),
        Check("docs_off_fast_path",
              sum(n - s["fast"] + s["fallback"] for s in stats), 0),
        Check("opens_not_on_device",
              sum(1 for s in stats
                  if s["platform"] != ("cpu" if cell.rehearse else "tpu")),
              0),
    ]


def teardown(cell, state) -> None:
    if state.get("last") is not None:
        state["last"][0].close()
        state["last"] = None
