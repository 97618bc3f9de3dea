"""The multi-writer deployment `collab-10k` (ISSUE 26) at rehearsal
size, on the CPU: the corpus writer holds what the config says, and a
cold open through `Repo.open_many` with the device paths forced gives,
for every doc of every class (the 32-writer ones included), the state
three independent algorithms agree on: the slab kernel, the plain
reference's replay and the host OpSet.

Counts and states only; no clock is asserted.
"""

import contextlib
import glob
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.corpora import multi_writer_rounds as mwr  # noqa: E402
from benchmark.reference import crdt_plain  # noqa: E402
from benchmark.reference.plainify import plain  # noqa: E402

SEED = 2147483659  # over 2**31: the driver's seeds are large
CLASSES = (1, 2, 3, 8, 32)
ENV = {"HM_DEVICE_MIN_CELLS": "0", "HM_BULK_SLAB": "32",
       "HM_LIVE_INC_BUDGET": "0"}


def _rehearsal_corpus() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "collab-10k.json")) as fh:
        cfg = json.load(fh)
    return dict(cfg["corpus"], **cfg["rehearsal"]["corpus"])


@contextlib.contextmanager
def _env():
    """ENV set for the block (the device paths forced)."""
    with pytest.MonkeyPatch.context() as mp:
        for k, v in ENV.items():
            mp.setenv(k, v)
        yield


def _open(path, urls, reverse_cursors=False):
    """One cold open -> (repo, handles, summaries, stats)."""
    from hypermerge_tpu.repo import Repo

    repo = Repo(path=path)
    if reverse_cursors:
        get = repo.back.cursors.get_multiple

        def reversed_rows(repo_id, doc_ids):
            return {d: dict(reversed(list(c.items())))
                    for d, c in get(repo_id, doc_ids).items()}

        repo.back.cursors.get_multiple = reversed_rows
    handles = repo.open_many(urls)
    summ = repo.back.fetch_bulk_summaries()
    return repo, handles, summ, dict(repo.back.last_bulk_stats)


def _states(job, urls, handles, summ):
    """[(summary, value)] of every doc, as the benchmark compares them."""
    from hypermerge_tpu.utils.ids import validate_doc_url

    return [
        (summ.doc(validate_doc_url(u)), plain(h.value(timeout=120)))
        for u, h in zip(urls, handles)
    ]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """The rehearsal corpus on disk, opened once with every slab on the
    device path: (job, urls, states, stats)."""
    tmp = tmp_path_factory.mktemp("collab")
    job = mwr.CorpusJob(str(tmp / "repo"), _rehearsal_corpus(), SEED, 2)
    try:
        urls = job.start().finish()
    except BaseException:
        job.abort()
        raise
    with _env():
        repo, handles, summ, stats = _open(str(tmp / "repo"), urls)
        try:
            states = _states(job, urls, handles, summ)
        finally:
            repo.close()
    return job, urls, states, stats


def _docs_of(job, writers):
    return [i for i, d in enumerate(job.plan) if d["writers"] == writers]


@pytest.mark.parametrize("writers", CLASSES)
def test_three_algorithms_agree(corpus, writers):
    """Every doc of the class: the cold open's summary and value equal
    the plain reference's replay and the host OpSet's."""
    from hypermerge_tpu.crdt.change import Change
    from hypermerge_tpu.crdt.opset import OpSet

    job, _urls, states, stats = corpus
    docs = _docs_of(job, writers)
    assert docs
    cache = {}
    for i in docs:
        changes = job.doc_changes(i, cache)
        ref = crdt_plain.replay(changes)
        assert len(ref["clock"]) == writers  # every writer wrote
        summary, value = states[i]
        assert summary == {
            k: ref[k] for k in ("elems", "map_entries", "clock")}, i
        assert value == ref["value"], i
        host = OpSet()
        host.apply_changes([Change.from_json(c) for c in changes])
        assert plain(host.materialize()) == ref["value"], i
    assert stats["host_slabs"] == 0 and stats["fallback"] == 0
    assert stats["fast"] == len(job.plan)


def test_stats_of_a_multi_writer_open(corpus):
    job, _urls, _states, stats = corpus
    assert stats["pack_general_docs"] == len(job.plan)
    assert stats["pack_general_pct"] == 100.0
    assert stats["feeds"] == job.n_feeds == sum(
        d["writers"] for d in job.plan)
    assert stats["a_loc_max"] == 32
    assert stats["pred_max"] >= 16
    assert stats["cols_bulk_pct"] == 100.0  # one v3 image a feed


def test_key_order_decides_the_state(corpus):
    """Two docs of one two-writer template whose keys sort in opposite
    orders: the same changes but for the names, two different texts,
    each equal to its own reference (no replay is renamed across
    docs)."""
    job, _urls, states, _stats = corpus
    by_order = {}
    for i in _docs_of(job, 2):
        if job.plan[i]["template"] != 0:
            continue
        a, b = (p.public_key for p in job.pairs[i])
        by_order.setdefault(a < b, i)
    assert set(by_order) == {True, False}
    i, j = by_order[True], by_order[False]
    cache = {}
    texts = []
    for d in (i, j):
        ref = crdt_plain.replay(job.doc_changes(d, cache))
        assert states[d][1] == ref["value"]
        texts.append(states[d][1]["t"]["__text__"])
    assert texts[0] != texts[1]
    assert sorted(texts[0]) == sorted(texts[1])  # the same letters


def test_feed_order_does_not_decide_the_state(corpus):
    """The same store opened with every doc's cursor rows in reversed
    actor order: the same state for every doc."""
    job, urls, states, _stats = corpus
    with _env():
        repo, handles, summ, stats = _open(
            job.path, urls, reverse_cursors=True)
        try:
            again = _states(job, urls, handles, summ)
        finally:
            repo.close()
    assert stats["pack_general_pct"] == 100.0
    assert again == states


def test_writer_is_deterministic_in_the_seed():
    corpus = _rehearsal_corpus()
    a = mwr.class_templates(corpus, SEED)
    assert a == mwr.class_templates(corpus, SEED)
    assert a != mwr.class_templates(corpus, SEED + 1)
    plan = mwr.doc_plan(corpus, SEED)
    assert plan == mwr.doc_plan(corpus, SEED)
    other = mwr.doc_plan(corpus, SEED + 1)
    assert [d["key_seeds"] for d in plan] != [d["key_seeds"] for d in other]
    assert [d["writers"] for d in plan] != [d["writers"] for d in other]
    # every slab of the open holds every class
    for b in range(0, len(plan) - 31, 32):
        assert len({d["writers"] for d in plan[b:b + 32]}) >= 3


@pytest.mark.parametrize("writers", CLASSES)
def test_corpus_holds_what_the_config_says(writers):
    """Rounds of W concurrent changes on equal counters, W siblings
    under each round's anchor, concurrent SETs of one key, DELs."""
    corpus = _rehearsal_corpus()
    c = [cls["writers"] for cls in corpus["classes"]].index(writers)
    per = int(corpus["ops_per_change"])
    for changes in mwr.class_templates(corpus, SEED)[c]:
        assert sum(len(ch["ops"]) for ch in changes) == corpus["ops"]
        assert changes[0]["actor"] == "writer00"
        assert changes[0]["ops"][0]["a"] == 2 and changes[0]["deps"] == {}
        assert {ch["actor"] for ch in changes} == {
            f"writer{w:02d}" for w in range(writers)}
        rounds = {}
        for ch in changes[1:]:
            rounds.setdefault(ch["startOp"], []).append(ch)
        assert sorted(rounds) == [1 + per * r
                                  for r in range(1, len(rounds) + 1)]
        sizes = [len(r) for _s, r in sorted(rounds.items())]
        assert all(n == writers for n in sizes[:-1]) and sizes[-1] <= writers
        concurrent_sets = dels = 0
        before = {"writer00": 1}
        for start, members in sorted(rounds.items()):
            # one anchor a round, W siblings under it, equal counters
            firsts = [ch["ops"][0] for ch in members]
            assert all(op.get("i") for op in firsts)
            assert len({op["r"] for op in firsts}) == 1
            assert len({ch["actor"] for ch in members}) == len(members)
            for ch in members:
                # a change depends on the round before, never on its own
                want = {a: s for a, s in before.items()
                        if a != ch["actor"]}
                want.setdefault("writer00", 1)
                want.pop(ch["actor"], None)
                assert ch["deps"] == want
            before = {ch["actor"]: ch["seq"] for ch in members}
            keys = [{op["k"] for op in ch["ops"] if "k" in op}
                    for ch in members]
            concurrent_sets += any(
                a & b for n, a in enumerate(keys) for b in keys[n + 1:])
            dels += sum(op["a"] == 5 for ch in members for op in ch["ops"])
        if writers > 1:
            assert concurrent_sets >= 1
        assert dels >= 1
    if writers > 1:
        # at the config's own size: after a concurrent round one SET
        # supersedes several visible SETs of its key at once
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "collab-10k.json")) as fh:
            full = json.load(fh)["corpus"]
        kw = {k: full[k] for k in mwr._DRAW_KEYS}
        changes = mwr.template_changes(full["ops"], writers, SEED, **kw)
        assert len(changes) == 64
        assert any(len(op.get("p", ())) > 1
                   for ch in changes for op in ch["ops"])


def _traced_open(tmp, urls):
    """One cold open under the profiler -> (span tree, stats, the
    FeedColumns of every feed it read)."""
    import jax

    from benchmark.readers import span_tree

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    trace = os.path.join(tmp, "trace-%d" % len(os.listdir(tmp)))
    jax.profiler.start_trace(trace, profiler_options=opts)
    try:
        repo, _handles, _summ, stats = _open(os.path.join(tmp, "repo"), urls)
        columns = [a.columns() for a in repo.back.actors.values()]
        repo.close()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        trace, "plugins", "profile", "*", "*.xplane.pb"))
    spans, _busy = span_tree.load(path)
    return span_tree.Tree(spans), stats, columns


def test_one_two_writer_doc_sends_its_slab_through_the_general_pack(
        tmp_path):
    """32 single-writer docs and one two-writer doc, slabs of 32: the
    slab with the two-writer doc packs through the general path, the
    other through the prefix path; the new spans nest under
    `pipeline.pack`, and the general pack's stages add up to it. The
    general slab's feeds are gathered by the native entry, or all by
    its numpy twin under HM_NATIVE_PACK=0, and neither builds a feed's
    dense row matrix."""
    from benchmark.readers import bulk_stats, span_tree
    from hypermerge_tpu import native

    corpus = dict(_rehearsal_corpus(), classes=[
        {"writers": 1, "count": 32}, {"writers": 2, "count": 1}])
    job = mwr.CorpusJob(str(tmp_path / "repo"), corpus, SEED, 1)
    urls = job.start().finish()
    where = [d["writers"] for d in job.plan].index(2) // 32
    with _env():
        tree, stats, columns = _traced_open(str(tmp_path), urls)
        single = [u for u, d in zip(urls, job.plan) if d["writers"] == 1]
        _tree1, stats1, _columns = _traced_open(str(tmp_path), single)
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("HM_NATIVE_PACK", "0")
            repo0, _h, _summ, stats0 = _open(str(tmp_path / "repo"), urls)
            columns += [a.columns() for a in repo0.back.actors.values()]
            repo0.close()
    n_general = 32 if where == 0 else 1
    assert stats["pack_general_docs"] == n_general
    assert stats["pack_general_pct"] == round(100.0 * n_general / 33, 3)
    assert stats1["pack_general_docs"] == 0
    assert stats1["pack_general_pct"] == 0.0
    assert stats1["a_loc_max"] == 4 and stats["a_loc_max"] == 4
    packs = {s.slab: s for s in tree.named(("pipeline.pack",))}
    assert sorted(packs) == [0, 1]
    (general,) = tree.named(("pipeline.pack.general",))
    (prefix,) = tree.named(("pipeline.pack.prefix",))
    assert general.slab == where and prefix.slab == 1 - where
    assert general.parent is packs[where]
    assert prefix.parent is packs[1 - where]
    assert general.args["docs"] == n_general
    assert general.args["feeds"] == n_general + 1
    assert general.args["rows"] == corpus["ops"] * n_general
    # who gathered the general slab's feeds: the native entry where the
    # library is there, the numpy twin under HM_NATIVE_PACK=0; nothing
    # is counted where no slab took the general path
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "pack.general_native_pct.json")) as fh:
        native_pct = json.load(fh)["params"]
    has_native = native.pack_lib() is not None
    for st, is_native in ((stats, has_native), (stats0, False)):
        counted = (st["pack_gather_native_feeds"],
                   st["pack_gather_twin_feeds"])
        assert sum(counted) == general.args["feeds"]
        assert counted[0 if is_native else 1] == general.args["feeds"]
        assert st["pack_gather_native_pct"] == (100.0 if is_native else 0.0)
        assert bulk_stats.read(native_pct, {"bulk_stats": [st]}) == (
            st["pack_gather_native_pct"])
    (gather,) = tree.named(("pipeline.pack.gather",))
    assert gather.args["native"] == stats["pack_gather_native_feeds"]
    assert (stats1["pack_gather_native_feeds"],
            stats1["pack_gather_twin_feeds"],
            stats1["pack_gather_native_pct"]) == (0, 0, 0.0)
    older = {k: v for k, v in stats.items()
             if not k.startswith("pack_gather_")}
    assert bulk_stats.read(native_pct, {"bulk_stats": [older]}) is None
    # who judged the feeds at the prefix pack's gate (ISSUE 35): the
    # slab with the two-writer doc leaves at the structural pass and
    # judges none; the other slab's feeds go to the native call, or to
    # the numpy twin under HM_NATIVE_PACK=0
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "pack.gate_native_pct.json")) as fh:
        gate_pct = json.load(fh)["params"]
    n_prefix = 33 - n_general
    for st, is_native, feeds in ((stats, has_native, n_prefix),
                                 (stats1, has_native, 32),
                                 (stats0, False, n_prefix)):
        counted = (st["pack_gate_native_feeds"], st["pack_gate_twin_feeds"])
        assert counted == ((feeds, 0) if is_native else (0, feeds))
        assert st["pack_gate_native_pct"] == (100.0 if is_native else 0.0)
        assert bulk_stats.read(gate_pct, {"bulk_stats": [st]}) == (
            st["pack_gate_native_pct"])
    gates = {g.parent.slab: g for g in tree.named(("pipeline.pack.gate",))}
    assert gates[where].args["prefix"] == 0
    assert gates[where].args["native"] == 0
    assert gates[1 - where].args["prefix"] == 1
    assert gates[1 - where].args["native"] == (
        stats["pack_gate_native_feeds"])
    older = {k: v for k, v in stats.items()
             if not k.startswith("pack_gate_")}
    assert bulk_stats.read(gate_pct, {"bulk_stats": [older]}) is None
    # no pack of the open built a feed's dense [n, 14] matrix
    assert len(columns) == 2 * (general.args["feeds"] + 32)
    assert all(fc.planes is not None and fc.rows is None for fc in columns)
    stages = [s for s in tree.members if s.parent is general]
    assert {s.name for s in stages} == {
        "pipeline.pack." + n
        for n in ("tables", "gather", "sort", "resolve", "emit")}
    assert sum(s.dur for s in stages) <= general.dur
    enq = tree.named(("pipeline.enqueue",))
    assert all({"A", "K", "P"} <= set(s.args) for s in enq)
    # the metric files read them: seconds here, nothing in a
    # single-writer open
    for name in ("pack.general_s", "pack.general_sort_s",
                 "pack.general_resolve_s"):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as fh:
            names = tuple(json.load(fh)["params"]["names"])
        assert tree.named(names) and not _tree1.named(names)
    assert span_tree.PROGRAM.match("pipeline.pack.general")


def test_phase_of_ops_knows_two_programs_of_one_shape():
    """Two slabs of one [D, N] that differ in the actor bucket and the
    pred bucket are two programs: `phase_of_ops` lowers both, and an
    instruction they book differently reads `mixed`."""
    import numpy as np

    from hypermerge_tpu.ops import crdt_kernels
    from hypermerge_tpu.ops.columnar import pack_docs

    from hypermerge_tpu.crdt.change import Change

    corpus = _rehearsal_corpus()
    kw = {k: corpus[k] for k in mwr._DRAW_KEYS if k in corpus}
    small = mwr.template_changes(64, 2, 5, **kw)
    wide = mwr.template_changes(64, 8, 6, **kw)
    D, N = 4, 64
    shape = (D, N, True)
    crdt_kernels._dispatched.pop(shape, None)
    sigs = set()
    for history in (small, wide):
        batch = pack_docs(
            [[Change.from_json(c) for c in history]] * D, n_rows=N)
        assert batch.n_docs == D and batch.n_rows == N
        assert not np.any(batch.cols["action"] == 6)  # lean: no INC
        crdt_kernels.run_batch_full(batch, lean=True)
        sigs.add((crdt_kernels.actor_bucket(batch),
                  batch.psrc.shape[1]))
    assert len(sigs) == 2  # (A, P) differ
    programs = crdt_kernels._dispatched[shape]
    assert len(programs) == 2
    each = [crdt_kernels._phases_of_program(*p) for p in programs.values()]
    got = crdt_kernels.phase_of_ops(D, N, True)
    assert set(got) == set(each[0]) | set(each[1])
    for name, phase in got.items():
        seen = {e[name] for e in each if name in e}
        assert phase == (seen.pop() if len(seen) == 1 else "mixed")
    assert set(got.values()) <= set(crdt_kernels.PHASES) | {
        "mixed", "unscoped"}
    assert crdt_kernels.phase_of_ops(D, N, False) == {}


@pytest.mark.parametrize("control", (False, True))
def test_rehearsal_of_the_cell(control):
    """`benchmark/run.py --workload coldopen.collab --rehearse`: exit 0
    and `correct`; with `--control` exit 1 through `host_slabs`."""
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", "coldopen.collab", "--seed", "7", "--seconds", "3",
           "--trace", "0", "--rehearse"] + (["--control"] if control else [])
    env = {k: v for k, v in os.environ.items() if not k.startswith("HM_")}
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=120,
                         env=env, cwd=ROOT)
    line = json.loads(out.stdout.strip().splitlines()[-1])
    bad = [c["name"] for c in line["checks"] if c["value"] > c["limit"]]
    assert line["workload"] == "coldopen.collab" and line["rehearsal"]
    assert line["metrics"] == {}
    if control:
        assert out.returncode == 1 and line["correct"] is False
        assert bad == ["host_slabs"]
    else:
        assert out.returncode == 0 and line["correct"] is True, out.stderr
        assert bad == []
    # the cell's newest per-layer metric (ISSUE 29) is data only: its
    # file names a reader the benchmark has, and the benchmark lists it
    # for this cell alone
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "pack.general_native_pct.json")) as fh:
        spec = json.load(fh)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        (entry,) = [m for m in json.load(fh)["per_layer"]
                    if m["name"] == spec["name"]]
    assert spec["reader"] == "bulk_stats"
    assert spec["params"] == {"key": "pack_gather_native_pct"}
    # (the cells a later PR joined to the metric follow its own)
    assert entry["workloads"][:1] == spec["cells"] == [line["workload"]]
    assert (entry["unit"], entry["better"], entry["layer"],
            entry["moves"]) == ("%", "higher", "pack", "ops_per_s")
