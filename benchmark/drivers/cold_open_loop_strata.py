"""Traffic driver `cold_open_loop_strata`: `cold_open_loop`'s closed
loop of whole cold opens, over a store whose documents differ in length.

The request, the set-up, the window and `ops_per_s` are
`cold_open_loop`'s, by import. Three things differ:

- before anything is written or opened, the driver asks the program
  for the cell budget of one slab (`backend.bulk_loader.SLAB_CELLS`). A
  program that has none pads every slab to its longest doc and would
  ask for tens of GB for this store: the run stops there, exit code 5;
- the window's `obs` carries `doc_rows`, every doc's op count from the
  corpus plan (what counts/bulk_rows.py reckons least bytes from), and
  its `bulk_slabs` are read off the traced open: the `D` and `N` tags
  of its `pipeline.enqueue` spans on the device, in dispatch order
  (the readers that match a trace's k-th program to its k-th slab need
  the slabs that ran, and `cold_open_loop` reckons store-order chunks;
  an untraced run has none, and no reader that asks). `verify` holds
  them to what the program says it dispatched
  (`last_bulk_stats.slab_shapes`), and that to the plan: every doc has
  a place in a slab with rows for it;
- `verify` draws its sample by octave of length (the mix's
  `verify_by_octave`: docs compared an octave, fewer where an octave
  holds few) and always compares the longest doc: a uniform sample of
  a store whose ten longest docs are a thousandth of it would meet
  none of them in most runs. `cold_open_loop`'s checks and limits
  (all 0), and four of its own: an octave unsampled, the longest doc
  uncompared, a doc without a slab, traced slabs unlike the stats'.
"""

from __future__ import annotations

import random
import sys
from typing import Any, Dict, List

from benchmark.drivers import cold_open_loop as base
from benchmark.harness import Check, Window, log
from benchmark.reference.plainify import plain

setup = base.setup
teardown = base.teardown


def before_jax(cell):
    from hypermerge_tpu.backend import bulk_loader

    if getattr(bulk_loader, "SLAB_CELLS", None) is None:
        print(
            "benchmark: this program has no slab cell budget "
            "(backend.bulk_loader.SLAB_CELLS): it would pad every slab "
            f"of {cell.name} to its longest doc; not run",
            file=sys.stderr, flush=True,
        )
        raise SystemExit(5)
    return base.before_jax(cell)


def window(cell, state, seconds: float) -> Window:
    win = base.window(cell, state, seconds)
    win.obs["doc_rows"] = [d["n_ops"] for d in state["job"].plan]
    enqueued = traced_slabs(cell.tracer.path)
    win.obs["enqueued_slabs"] = [[d, n] for d, n, _host in enqueued]
    win.obs["bulk_slabs"] = [[d, n] for d, n, host in enqueued if not host]
    return win


def traced_slabs(path) -> List[List[int]]:
    """[docs, rows, answered by the host twin] of the slabs of the
    traced open, in the order they were enqueued: the tags of its
    `pipeline.enqueue` spans. [] without a trace, or from a program
    that does not tag them."""
    if not path:
        return []
    from benchmark.readers import span_tree

    try:
        tree = span_tree.Tree(span_tree.load(path)[0])
    except LookupError:
        return []
    return [
        [int(s.args["D"]), int(s.args["N"]), int(s.args.get("host", 0))]
        for s in sorted(tree.named(("pipeline.enqueue",)),
                        key=lambda s: s.t0)
        if "D" in s.args and "N" in s.args
    ]


def unplaced_docs(doc_rows: List[int], slabs) -> int:
    """Docs that slabs of these [docs, rows] cannot hold, each doc in
    one slab of at least its rows: the longest docs first, each into
    the slab of the most rows that has room."""
    room = sorted(([int(n), int(d)] for d, n in slabs), reverse=True)
    unplaced = 0
    for rows in sorted(doc_rows, reverse=True):
        for slot in room:
            if slot[0] >= rows and slot[1]:
                slot[1] -= 1
                break
        else:
            unplaced += 1
    return unplaced


def _octave(cell, n_ops: int) -> int:
    """k of the octave [octave0 * 2^k, octave0 * 2^(k+1)) that holds
    a doc of `n_ops` ops."""
    return (n_ops // int(cell.config["corpus"]["octave0"])).bit_length() - 1


def strata_sample(cell, plan) -> List[int]:
    """The docs to compare: `verify_by_octave[k]` of octave k (all of
    an octave that holds fewer), drawn from the seed, and the longest
    doc."""
    by_octave: Dict[int, List[int]] = {}
    for i, d in enumerate(plan):
        by_octave.setdefault(_octave(cell, d["n_ops"]), []).append(i)
    rng = random.Random(cell.seed)
    sample = {max(range(len(plan)), key=lambda i: plan[i]["n_ops"])}
    for k, want in enumerate(cell.mix["verify_by_octave"]):
        docs = by_octave.get(k, [])
        sample.update(rng.sample(docs, min(int(want), len(docs))))
    return sorted(sample)


def verify(cell, state, win: Window) -> List[Check]:
    """Exact, against the plain reference's replay of the changes the
    corpus writer put on disk: docs of every octave and the longest
    doc (values, summaries), all summaries present, no slab on the
    host twin."""
    from hypermerge_tpu.utils.ids import validate_doc_url

    repo, handles, summ = state["last"]
    urls, job = state["urls"], state["job"]
    n = len(urls)
    sample = strata_sample(cell, job.plan)
    longest = max(d["n_ops"] for d in job.plan)
    octaves = len({_octave(cell, job.plan[i]["n_ops"]) for i in sample})
    log(f"verify: {len(sample)} docs of {octaves} octaves, "
        f"the longest {longest} ops")
    bad_summary = bad_value = 0
    refs: Dict[Any, Any] = {}
    for i in sample:
        ref = job.doc_reference(i, refs)
        got = summ.doc(validate_doc_url(urls[i]))
        want = {k: ref[k] for k in ("elems", "map_entries", "clock")}
        if got != want:
            bad_summary += 1
            log(f"summary of doc {i}: {got} != {want}")
        if plain(handles[i].value(timeout=300)) != ref["value"]:
            bad_value += 1
            log(f"value of doc {i} ({job.plan[i]['n_ops']} ops) differs "
                "from the reference")
    stats = win.obs["bulk_stats"]
    # the traced open is the window's first
    said = [list(shape) for shape in stats[0]["slab_shapes"]]
    traced = win.obs["enqueued_slabs"] or said
    return [
        Check("docs_without_a_slab", max(
            unplaced_docs(win.obs["doc_rows"], s["slab_shapes"])
            for s in stats), 0),
        Check("traced_slabs_unlike_stats", int(traced != said), 0),
        Check("octaves_unsampled",
              len(cell.mix["verify_by_octave"]) - octaves, 0),
        Check("longest_doc_uncompared",
              int(all(job.plan[i]["n_ops"] != longest for i in sample)), 0),
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
