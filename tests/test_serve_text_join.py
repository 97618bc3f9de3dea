"""The read tier's text join as array work (ISSUE 33): `_join_text`
gives, for every input, the string the per-row decode gave (the oracle
below: one `_row_value` call a character, as `_dispatch_orders` had
it); a flush says what it is made of (`serve.batch.attach`,
`serve.dispatch`, `serve.decode`, the counters `serve.text_reads` /
`serve.text_rows`); and the three metric files of the issue read a
number from a rehearsal-size traced run of `reads.resident`. CPU, small
sizes; counts and answers only, no clock is asserted.
"""

import argparse
import json
import os
import sys
import time
from unittest import mock

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from hypermerge_tpu import telemetry  # noqa: E402
from hypermerge_tpu.models import Counter, Text  # noqa: E402
from hypermerge_tpu.ops import columnar as col  # noqa: E402
from hypermerge_tpu.repo import Repo  # noqa: E402
from hypermerge_tpu.serve import host_read, kernels, resident  # noqa: E402
from hypermerge_tpu.serve.batcher import ReadRequest  # noqa: E402
from hypermerge_tpu.serve.tier import ServeTier, _join_text  # noqa: E402
from hypermerge_tpu.telemetry import trace as ttrace  # noqa: E402
from hypermerge_tpu.utils.ids import validate_doc_url  # noqa: E402

BIG = 2**70 + 3


def oracle(e, order) -> str:
    """The join as the tier had it before: one decode call a row."""
    return "".join(
        str(ServeTier._row_value(None, e, int(e.elem_val[row])))
        for row in order
    )


def resolve(e, path):
    """(order, n) of the sequence at `path` in the resident entry `e`,
    through the query programs themselves."""
    obj = -1
    for step in path:
        if isinstance(step, str):
            rows, found = kernels.map_lookup([e], [obj], [e.key_index[step]])
            assert found[0]
            obj = int(rows[0])
        else:
            out = kernels.seq_order([e], [obj], [-1])
            assert 0 <= step < int(out[kernels.N_ELEMS][0])
            obj = int(e.elem_val[int(out[kernels.ORDER][0][step])])
    out = kernels.seq_order([e], [obj], [-1])
    return out[kernels.ORDER][0], int(out[kernels.N_ELEMS][0])


@pytest.fixture(scope="module")
def repo():
    r = Repo(memory=True)
    yield r
    r.close()


def _every_kind(d):
    d["t"] = Text("ab")
    d["t"].insert(1, [None, 7, True, False, 2.5, "x", "several", BIG, -3])


def _deleted(d):
    d["t"] = Text("hello world")
    d["t"].delete(2, 4)
    d["t"].insert(3, "XY")


def _nested(d):
    d["deep"] = {"x": {"t": Text("nested text")}}


def _in_a_list(d):
    d["l"] = [Text("first"), 5, Text("second one")]


def _plain(d):
    d["t"] = Text("the quick brown fox jumps over the lazy dog " * 20)


TEXTS = {
    "every-value-kind": (_every_kind, ["t"],
                         "aNone7TrueFalse2.5xseveral%d-3b" % BIG),
    "deleted-elements": (_deleted, ["t"], "hewXYorld"),
    "empty": (lambda d: d.__setitem__("t", Text("")), ["t"], ""),
    "nested-by-path": (_nested, ["deep", "x", "t"], "nested text"),
    "through-an-int-step": (_in_a_list, ["l", 2], "second one"),
    "strings-only": (_plain, ["t"],
                     "the quick brown fox jumps over the lazy dog " * 20),
}


@pytest.mark.parametrize("case", sorted(TEXTS))
def test_join_equals_the_per_row_decode(repo, case):
    """The tier's answer, the array join on its entry, the per-row
    oracle on the same entry, the host twin and the hand-worked string
    are one string."""
    edit, path, want = TEXTS[case]
    url = repo.create()
    repo.change(url, edit)
    q = {"kind": "text", "path": path}
    assert repo.read(url, q) == want
    doc = repo.back.docs[validate_doc_url(url)]
    assert host_read(doc, q)["value"] == want
    e, _noted = repo.back.serve._cache.get_fresh(doc.id, doc.clock)
    order, n = resolve(e, path)
    assert _join_text(e, order[:n]) == oracle(e, order[:n]) == want


LIST = [1, "x", False, None, 2.5, BIG, "several", Counter(4)]


@pytest.fixture(scope="module")
def list_url(repo):
    url = repo.create()
    repo.change(url, lambda d: d.__setitem__("l", list(LIST)))
    return url


@pytest.mark.parametrize("i", range(-1, len(LIST) + 1))
def test_list_read_through_index(repo, list_url, i):
    """`index` keeps its single-row decode (`_row_leaf`): every kind,
    and both ends out of range, against the host twin."""
    q = {"kind": "index", "path": ["l"], "index": i}
    want = (
        None if not 0 <= i < len(LIST)
        else 4 if i == len(LIST) - 1 else LIST[i]
    )
    got = repo.read(list_url, q)
    assert got == want and type(got) is type(want)
    doc = repo.back.docs[validate_doc_url(list_url)]
    assert host_read(doc, q)["value"] == want


class _Batch:
    strings = ["a", "bc", "", "\x00z"]
    floats = [0.5, -1e300]
    bigints = [BIG, -BIG]


_Tables = resident._Tables(_Batch)


class _Entry:
    """A resident entry's host half, made by hand: rows the frontend's
    proxies cannot write (a counter inside a text, with INCs)."""

    tables = _Tables

    def __init__(self, rows, elem_val=None):
        # rows: (vkind, code, dt, inc_total)
        cols = np.asarray(rows, np.int64).reshape(-1, 4)
        self.vkind = cols[:, 0].astype(np.int8)
        self.value = cols[:, 1].astype(np.int32)
        self.dt = cols[:, 2].astype(np.int8)
        self.inc_total = cols[:, 3].astype(np.int32)
        self.elem_val = np.asarray(
            range(len(cols)) if elem_val is None else elem_val, np.int32
        )


S, I, N, B, F, G = (col.VK_STR, col.VK_INT, col.VK_NONE, col.VK_BOOL,
                    col.VK_FLOAT, col.VK_BIGINT)
HAND = {
    "counter-with-incs": (
        [(S, 0, 0, 0), (I, 10, 1, 32), (S, 1, 0, 0)], None, "a42bc"),
    "counter-of-none-and-negative-incs": (
        [(N, 0, 1, -5), (I, 3, 1, 0), (I, -3, 0, 9)], None, "-53-3"),
    "all-one-table-kind-floats": (
        [(F, 1, 0, 0), (F, 0, 0, 0)], None, "-1e+3000.5"),
    "all-bigints": ([(G, 1, 0, 0), (G, 0, 0, 0)], None, f"-{BIG}{BIG}"),
    "bools-nones-ints": (
        [(B, 1, 0, 0), (B, 0, 0, 0), (N, 0, 0, 0), (I, 0, 0, 0)], None,
        "TrueFalseNone0"),
    "strings-with-empty-and-nul": (
        [(S, 3, 0, 0), (S, 2, 0, 0), (S, 1, 0, 0)], None, "\x00zbc"),
    "one-string": ([(S, 1, 0, 0)], None, "bc"),
    # element 0's winning value is row 2 (an element SET over it)
    "elem-val-indirection": (
        [(S, 0, 0, 0), (S, 1, 0, 0), (I, 9, 0, 0)], [2, 1, 2], "9bc9"),
    "no-rows": ([], None, ""),
}


@pytest.mark.parametrize("case", sorted(HAND))
def test_join_on_hand_made_rows(case):
    rows, elem_val, want = HAND[case]
    e = _Entry(rows, elem_val)
    order = np.arange(len(e.elem_val), dtype=np.int32)
    assert _join_text(e, order) == oracle(e, order) == want
    back = order[::-1]
    assert _join_text(e, back) == oracle(e, back)


@pytest.mark.parametrize("case", sorted(HAND))
def test_decode_value_rows_equals_decode_value(case):
    """The lifted vector decoder against the scalar one, value for
    value and type for type (True is not 1)."""
    e = _Entry(HAND[case][0])
    t = e.tables
    got = col.decode_value_rows(
        e.vkind, e.value, t.strings, t.floats, t.bigints
    )
    want = [
        col.decode_value(int(k), int(v), 0, t)
        for k, v in zip(e.vkind, e.value)
    ]
    assert got == want
    assert [type(x) for x in got] == [type(x) for x in want]


# -- what a flush is made of -------------------------------------------------


def _flush(repo, reads):
    """ONE batch flush of `reads` = [(url, query)]; the answers."""
    got = {}
    reqs = []
    for i, (u, q) in enumerate(reads):
        req = ReadRequest(
            validate_doc_url(u), dict(q),
            lambda p, i=i: got.__setitem__(i, p["value"]),
        )
        req.t0 = time.perf_counter()
        reqs.append(req)
    repo.back.serve._flush(reqs)
    return [got[i] for i in range(len(reads))]


def test_a_warm_flush_has_attach_dispatch_and_decode_spans(repo):
    """Two text reads of 5 and 12 characters, one index read and one
    lookup, nothing cold: under the flush's `serve.batch` one
    `serve.batch.attach{docs=2}`, ONE `serve.dispatch{kind,B,N}` (with
    its `serve.dispatch.fetch`): the seq_order program takes every
    read's string step and answers the three sequence reads behind it;
    one `serve.decode{reads=3,rows=17}`; and the counters moved by 2
    text reads, 17 rows and 3 fused steps."""
    a = repo.create()
    repo.change(a, lambda d: d.__setitem__("t", Text("hello")))
    b = repo.create()
    repo.change(b, lambda d: d.__setitem__("t", Text("twelve chars")))
    text = {"kind": "text", "path": ["t"]}
    reads = [
        (a, text), (b, text),
        (b, {"kind": "index", "path": ["t"], "index": 3}),
        (a, {"kind": "lookup", "path": ["t"]}),
    ]
    _flush(repo, reads)  # installs both docs
    was_on = ttrace.enabled()
    ttrace.reset()
    ttrace.enable()
    try:
        before = telemetry.snapshot()
        answers = _flush(repo, reads)
        after = telemetry.snapshot()
        events = [e for e in telemetry.trace_events() if e[0] == "X"]
    finally:
        if not was_on:
            ttrace.disable()
        ttrace.reset()
    assert answers == ["hello", "twelve chars", "l", {"_type": "text"}]

    def moved(name):
        return after["serve." + name] - before.get("serve." + name, 0)

    assert (moved("text_reads"), moved("text_rows")) == (2, 17)
    assert (moved("batches"), moved("installs")) == (1, 0)
    (batch,) = [e for e in events if e[1] == "serve.batch"]
    assert batch[6] == {"reads": 4, "cold": 0}
    inside = [
        e for e in events
        if e is not batch and e[5] == batch[5] and e[3] >= batch[3]
        and e[3] + e[4] <= batch[3] + batch[4]
    ]
    by_name = {}
    for e in inside:
        by_name.setdefault(e[1], []).append(e[6])
    assert by_name["serve.batch.attach"] == [{"docs": 2}]
    # every read's first step is a string key, and the widest answer
    # asked for behind it is an order: the one dispatch is seq_order's
    assert by_name["serve.dispatch"] == [
        {"kind": "seq_order", "B": 4, "N": 64},
    ]
    assert len(by_name["serve.dispatch.fetch"]) == 1
    assert by_name["serve.decode"] == [{"reads": 3, "rows": 17}]
    assert (moved("dispatches"), moved("fused_steps")) == (1, 3)
    # ISSUE 34: a dispatch is stack -> call -> fetch, one after the
    # other and nothing else; a read's callback is a span of its own,
    # on the flusher's thread; every one has its thread's CPU seconds
    inside.sort(key=lambda e: e[3])
    for d in (e for e in inside if e[1] == "serve.dispatch"):
        kids = [e for e in inside if e is not d and e[3] >= d[3]
                and e[3] + e[4] <= d[3] + d[4]]
        assert [e[1] for e in kids] == [
            "serve.dispatch.stack", "serve.dispatch.call",
            "serve.dispatch.fetch"]
        for x, y in zip(kids, kids[1:]):
            assert x[3] + x[4] <= y[3]
        assert sum(e[4] for e in kids) <= d[4]
    assert len(by_name["serve.callback"]) == len(reads)
    assert all(e[7] is not None and 0 <= e[7] <= e[4] for e in inside)
    # a read is begun by its caller and ended by the flusher; here
    # both are this thread, so it has a CPU value like the others
    assert batch[7] is not None and batch[7] <= batch[4]


def test_metric_files_read_a_rehearsal_size_traced_run(tmp_path, monkeypatch):
    """`reads.resident` at its rehearsal size, traced, through the
    harness's own cell, driver and readers (`benchmark/selftest/run.py`
    is not edited): `serve.decode_s`, `serve.dispatch_s` and
    `serve.text_rows_per_read` each find a number, the last the
    counters' ratio; laid over a program without the spans and the
    counters (the parent) the readers find nothing and do not raise."""
    from benchmark import harness
    from benchmark.readers import span_tree

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    args = argparse.Namespace(
        workload="reads.resident", seed=2147483999, seconds=2.0, trace=1,
        rehearse=True, control=False, mix=None,
    )
    names = ("serve.decode_s", "serve.dispatch_s", "serve.text_rows_per_read")
    # ISSUE 34: the dispatch's split, the callbacks, and the CPU clock
    cpu_names = (
        "serve.stack_s", "serve.call_s", "serve.fetch_s",
        "serve.fetch_cpu_pct", "serve.python_offcpu_s", "serve.callback_s",
        "serve.dispatch_max_s",
    )
    names += cpu_names
    listed = {m["name"]: m for m in bench["per_layer"]}
    with mock.patch.dict(os.environ):
        cell = harness.Cell(args, bench, time.perf_counter())
        cell.work = str(tmp_path / "run")
        cell.tracer = harness.Tracer(True, str(tmp_path / "trace"))
        harness.apply_env(cell)
        driver = harness.load_module("drivers", cell.mix["driver"])
        os.makedirs(cell.work)
        early = driver.before_jax(cell)
        cell.cache_watch = harness.CacheWatch()
        state = driver.setup(cell, early)
        try:
            before = cell.counters()
            win = driver.window(cell, state, float(args.seconds))
            cell.tracer.close()
            after = cell.counters()
        finally:
            cell.tracer.close()
            driver.teardown(cell, state)
    assert win.failed == 0 and win.attempted > 0
    assert cell.tracer.path is not None
    monkeypatch.setattr(span_tree, "newest_trace", lambda: cell.tracer.path)
    obs = dict(win.obs, trace={"busy_s": 0.0}, counters_before=before,
               counters_after=after)
    cell.bench = dict(bench, per_layer=[listed[n] for n in names])
    got = harness.layer_metrics(cell, obs)
    assert set(got) == set(names)
    assert got["serve.decode_s"]["value"] > 0.0
    assert got["serve.dispatch_s"]["value"] > 0.0
    split = sum(got[n]["value"] for n in
                ("serve.stack_s", "serve.call_s", "serve.fetch_s"))
    assert 0.0 < split <= got["serve.dispatch_s"]["value"]
    assert 0.0 <= got["serve.fetch_cpu_pct"]["value"] <= 100.0
    assert got["serve.python_offcpu_s"]["value"] >= -1e-4
    assert got["serve.callback_s"]["value"] > 0.0
    assert (got["serve.dispatch_max_s"]["value"]
            >= got["serve.dispatch_s"]["value"])
    reads = after["serve.text_reads"] - before["serve.text_reads"]
    rows = after["serve.text_rows"] - before["serve.text_rows"]
    assert reads > 0
    assert got["serve.text_rows_per_read"]["value"] == rows / reads
    spans, _busy = span_tree.load(cell.tracer.path)
    tags = {
        s.name: set(s.args) for s in spans if s.name.startswith("serve.")
    }
    assert tags["serve.dispatch"] >= {"kind", "B", "N"}
    assert tags["serve.decode"] >= {"reads", "rows"}
    assert tags["serve.batch.attach"] >= {"docs"}
    # every entry and its file agree, and list the cell alone
    for n in names:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               n + ".json")) as fh:
            spec = json.load(fh)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert listed[n][key] == spec[key], (n, key)
        # (a later cell that reports the metric is appended to the
        # benchmark's list; the accepted file's note stays as it was)
        assert listed[n]["workloads"][:1] == spec["cells"] == [
            "reads.resident"]
    # the parent: its trace holds program spans but none of these, its
    # counters lack the two
    older = {k: v for k, v in after.items() if "text_" not in k}
    obs_parent = dict(obs, counters_before=older, counters_after=older)
    real_load = span_tree.load
    monkeypatch.setattr(span_tree, "load", lambda p: (
        [span_tree.Span(s.name, s.t0, s.t1, s.line, {
            k: v for k, v in s.args.items() if k != "cpu_us"})
         for s in real_load(p)[0]
         if s.name in ("serve.batch", "serve.read")], []))
    assert harness.layer_metrics(cell, obs_parent) == {}


# -- one dispatch a row bucket (ISSUE 46) ------------------------------------
# `seq_order` and `counts` take their container as (object row, key
# index) and resolve it on the device; a round's reads of one row
# bucket leave in one dispatch, by the widest answer they ask for


def _nested(d):
    d["s"] = 3
    d["t"] = Text("outer text")
    d["e"] = Text("")
    d["el"] = []
    d["l"] = [1, {"x": Text("in a list"), "n": 9}, [7, 8, 9], "str"]
    d["m"] = {"n": {"t": Text("deep text"), "l": [4, 5], "k": 5, "o": {}}}
    d["gone"] = Text("deleted")
    del d["gone"]


@pytest.fixture(scope="module")
def nested(repo):
    """(url, doc, resident entry) of a doc with maps, lists and texts
    inside one another, an empty text, an empty list, a deleted key."""
    url = repo.create()
    repo.change(url, _nested)
    assert repo.read(url, {"kind": "len", "path": []}) == 6
    doc_id = validate_doc_url(url)
    return url, repo.back.docs[doc_id], repo.back.serve._cache._entries[doc_id]


def _containers(e):
    return [-1] + [r for r in range(e.n) if e.obj_type(r) is not None]


@pytest.mark.parametrize("kind", ["seq_order", "counts"])
def test_a_keyed_program_equals_the_lookup_then_the_unkeyed(nested, kind):
    """For every (container, key) of the doc, and a key index the doc
    never had: the program asked by (object, key) gives map_lookup's
    row and found, and for the rest what it gives when asked about
    that row itself; nothing found (never set, deleted) counts 0, and
    a scalar winner owns no rows; the pad rows of the batch too."""
    _url, _doc, e = nested
    program = getattr(kernels, kind)
    keys = sorted(e.key_index.values()) + [len(e.key_index) + 5]
    pairs = [(obj, k) for obj in _containers(e) for k in keys]
    seen = {"seq": 0, "empty": 0, "scalar": 0, "missing": 0}
    for at in range(0, len(pairs), 60):
        part = pairs[at:at + 60]  # 60 of a batch of 64: four pad rows
        es = [e] * len(part)
        qobj, qkey = [p[0] for p in part], [p[1] for p in part]
        row, found = kernels.map_lookup(es, qobj, qkey)
        keyed = program(es, qobj, qkey)
        assert (keyed[kernels.ROW] == row).all()
        assert (keyed[kernels.FOUND] == found).all()
        plain = program(
            es, [int(r) if f else kernels.NO_OBJ
                 for r, f in zip(row, found)], [-1] * len(part),
        )
        for out in range(kernels.N_ELEMS, len(keyed)):
            assert (keyed[out] == plain[out]).all(), out
        assert not found[len(part):].any()
        for i, (f, r) in enumerate(zip(found, row)):
            n, n_map = (int(keyed[o][i])
                        for o in (kernels.N_ELEMS, kernels.N_MAP))
            if i >= len(part) or not f:
                assert (n, n_map) == (0, 0)
                seen["missing"] += 1
            elif e.obj_type(int(r)) is None:
                assert (n, n_map) == (0, 0)
                seen["scalar"] += 1
            elif e.obj_type(int(r)) in ("list", "text"):
                seen["seq" if n else "empty"] += 1
    assert all(seen.values()), seen


QUERIES = {
    "text-1": {"kind": "text", "path": ["t"]},
    "text-3": {"kind": "text", "path": ["m", "n", "t"]},
    "text-behind-int": {"kind": "text", "path": ["l", 1, "x"]},
    "text-empty": {"kind": "text", "path": ["e"]},
    "text-of-list": {"kind": "text", "path": ["l"]},
    "text-of-scalar": {"kind": "text", "path": ["s"]},
    "text-of-root": {"kind": "text", "path": []},
    "text-deleted": {"kind": "text", "path": ["gone"]},
    "text-never-seen": {"kind": "text", "path": ["nope"]},
    "lookup-1": {"kind": "lookup", "path": ["s"]},
    "lookup-1-object": {"kind": "lookup", "path": ["m"]},
    "lookup-3": {"kind": "lookup", "path": ["m", "n", "k"]},
    "lookup-behind-int": {"kind": "lookup", "path": ["l", 1, "n"]},
    "lookup-int-last": {"kind": "lookup", "path": ["l", 0]},
    "lookup-scalar-mid-path": {"kind": "lookup", "path": ["s", "x"]},
    "lookup-no-path": {"kind": "lookup", "path": []},
    "len-0": {"kind": "len", "path": []},
    "len-1-text": {"kind": "len", "path": ["t"]},
    "len-1-list": {"kind": "len", "path": ["l"]},
    "len-1-map": {"kind": "len", "path": ["m"]},
    "len-1-empty": {"kind": "len", "path": ["el"]},
    "len-3-list": {"kind": "len", "path": ["m", "n", "l"]},
    "len-3-empty-map": {"kind": "len", "path": ["m", "n", "o"]},
    "len-behind-int": {"kind": "len", "path": ["l", 2]},
    "len-of-scalar": {"kind": "len", "path": ["s"]},
    "len-of-element": {"kind": "len", "path": ["l", 0]},
    "index-1": {"kind": "index", "path": ["t"], "index": 4},
    "index-1-object": {"kind": "index", "path": ["l"], "index": 1},
    "index-3": {"kind": "index", "path": ["m", "n", "l"], "index": 1},
    "index-behind-int": {"kind": "index", "path": ["l", 2], "index": 2},
    "index-past-end": {"kind": "index", "path": ["l"], "index": 4},
    "index-of-empty": {"kind": "index", "path": ["el"], "index": 0},
    "index-of-map": {"kind": "index", "path": ["m"], "index": 0},
    "index-not-int": {"kind": "index", "path": ["l"], "index": "1"},
    "int-step-on-map": {"kind": "len", "path": ["m", 0]},
    "int-step-past-end": {"kind": "text", "path": ["l", 9, "x"]},
    "odd-step": {"kind": "len", "path": ["m", 1.5]},
}


@pytest.fixture(scope="module")
def together(repo, nested):
    """Every query above in ONE flush (a `lookup` and a `text` of one
    doc among them, of course): {name: answer}."""
    url = nested[0]
    return dict(zip(QUERIES, _flush(repo, [(url, q) for q in
                                           QUERIES.values()])))


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_the_tier_answers_as_its_host_twin(repo, nested, together, name):
    """A read flushed alone and the same read flushed among all the
    others (another program, other outputs fetched) both give what the
    per-request host path gives."""
    url, doc, _e = nested
    want = host_read(doc, QUERIES[name])["value"]
    assert _flush(repo, [(url, QUERIES[name])]) == [want]
    assert together[name] == want
    if name.endswith(("-1", "-3", "-0")) or "behind" in name:
        assert want is not None


def _programs_of(repo, reads, monkeypatch):
    """[(program kind, B, outputs skipped)] of ONE warm flush of
    `reads`, with its answers and the counters it moved."""
    calls = []
    for kind in kernels.KINDS:
        def spy(es, qobj, qkey, skip=(), kind=kind,
                real=getattr(kernels, kind)):
            calls.append((kind, kernels.batch_bucket(len(es)),
                          tuple(skip)))
            return real(es, qobj, qkey, skip)
        monkeypatch.setattr(kernels, kind, spy)
    before = telemetry.snapshot()
    answers = _flush(repo, reads)
    after = telemetry.snapshot()
    moved = {k: after["serve." + k] - before.get("serve." + k, 0)
             for k in ("dispatches", "fused_steps", "batches",
                       "host_memo_hits", "fallbacks")}
    return calls, answers, moved


ROWS, NO_MAP = (kernels.ROW, kernels.FOUND), (kernels.N_MAP,)
TEXT = {"kind": "text", "path": ["t"]}
LEN = {"kind": "len", "path": ["t"]}
KEY = {"kind": "lookup", "path": ["s"]}
FLUSHES = {
    # the cell's mix on one bucket
    "mix": ([TEXT, TEXT, TEXT, KEY, LEN],
            [("seq_order", 16, ())], 4),
    "texts": ([TEXT, TEXT], [("seq_order", 4, NO_MAP)], 2),
    "text-and-lookup-of-one-doc": (
        [TEXT, KEY], [("seq_order", 4, NO_MAP)], 1),
    "lens": ([LEN] * 5, [("counts", 16, ())], 5),
    "len-and-lookup": ([LEN, KEY], [("counts", 4, ())], 1),
    "lookups": ([KEY] * 3, [("map_lookup", 4, NO_MAP)], 0),
    "root-len": ([{"kind": "len", "path": []}],
                 [("counts", 1, ROWS)], 0),
    "root-len-and-lookup": (
        [{"kind": "len", "path": []}, KEY], [("counts", 4, ())], 0),
    # a path of three string steps: a round a step, the last one fused
    "three-steps": (
        [{"kind": "text", "path": ["m", "n", "t"]}, KEY],
        [("map_lookup", 4, NO_MAP), ("map_lookup", 1, NO_MAP),
         ("seq_order", 1, NO_MAP)], 1),
    # an int step behind a string step leaves with it; what lies
    # behind the int step takes the next round
    "int-behind-string": (
        [{"kind": "len", "path": ["l", 2]}],
        [("seq_order", 1, NO_MAP), ("counts", 1, ROWS)], 1),
    "text-behind-int": (
        [{"kind": "text", "path": ["l", 1, "x"]}],
        [("seq_order", 1, NO_MAP), ("seq_order", 1, NO_MAP)], 2),
    # what the host can tell goes nowhere
    "never-seen": ([{"kind": "text", "path": ["nope"]},
                    {"kind": "text", "path": []}], [], 0),
}


@pytest.mark.parametrize("name", sorted(FLUSHES))
def test_a_flush_makes_one_dispatch_a_round(repo, nested, monkeypatch, name):
    """The programs one flush runs, their batch, and the outputs it
    left on the device: one dispatch a round of the longest path, of
    the widest kind its reads ask for."""
    url, doc, _e = nested
    queries, programs, fused = FLUSHES[name]
    calls, answers, moved = _programs_of(
        repo, [(url, q) for q in queries], monkeypatch
    )
    assert calls == programs
    assert answers == [host_read(doc, q)["value"] for q in queries]
    assert moved == {
        "dispatches": len(programs), "fused_steps": fused, "batches": 1,
        "host_memo_hits": 0, "fallbacks": 0,
    }


def test_two_row_buckets_take_a_dispatch_each(repo, nested, monkeypatch):
    """The mix over a short doc and a long one: one seq_order a row
    bucket, whatever the reads of a bucket ask for."""
    url = nested[0]
    long = repo.create()
    repo.change(long, lambda d: d.__setitem__("t", Text("x" * 300)))
    assert repo.read(long, LEN) == 300
    reads = [(url, TEXT), (long, KEY), (long, LEN), (url, KEY), (long, TEXT)]
    calls, answers, moved = _programs_of(repo, reads, monkeypatch)
    assert sorted(calls) == [("seq_order", 4, ()), ("seq_order", 4, NO_MAP)]
    assert answers == ["outer text", None, 300, 3, "x" * 300]
    assert (moved["dispatches"], moved["fused_steps"]) == (2, 3)


def test_dispatches_per_batch_reads_one_for_the_mix(repo, nested):
    """The metric the issue adds is a data file for the `counter_ratio`
    reader: its entry in BENCHMARK.json agrees with it, two flushes of
    the cell's mix read 1.0, and a program without the counters (none
    is new: the parent has both) would read nothing and not raise."""
    from benchmark.readers import counter_ratio

    name = "serve.dispatches_per_batch"
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as fh:
        spec = json.load(fh)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = bench["per_layer"][-1]
    assert entry["name"] == spec["name"] == name
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert entry["workloads"] == spec["cells"] == [
        "reads.resident", "rw.ycsb-a"]
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    url = nested[0]
    mix = [(url, q) for q in (TEXT, TEXT, TEXT, KEY, LEN)]
    before = telemetry.snapshot()
    _flush(repo, mix)
    _flush(repo, mix[2:])
    obs = {"counters_before": before,
           "counters_after": telemetry.snapshot()}
    assert counter_ratio.read(spec["params"], obs) == 1.0
    assert counter_ratio.read(spec["params"], {
        "counters_before": {}, "counters_after": {"serve.batches": 2},
    }) is None
