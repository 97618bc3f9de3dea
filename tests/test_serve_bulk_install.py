"""The read tier's batched install (ISSUE 32): a flush's cold docs are
built a length rung at a time (one pack, the kernel lanes of the whole
group at once from the summary memo or ONE run of the slab program, one
upload), and every read of every doc equals both the host twin and the
plain reference (benchmark/reference/read_plain.py), which imports
nothing of the program. CPU, small sizes, seeded; counts and answers
only, no clock is asserted.
"""

import contextlib
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.corpora import single_writer_templates as swt  # noqa: E402
from benchmark.reference import read_plain  # noqa: E402
from hypermerge_tpu import telemetry  # noqa: E402
from hypermerge_tpu.models import Counter, Text  # noqa: E402
from hypermerge_tpu.repo import Repo  # noqa: E402
from hypermerge_tpu.serve import host_read  # noqa: E402
from hypermerge_tpu.serve.batcher import ReadRequest  # noqa: E402
from hypermerge_tpu.utils.ids import validate_doc_url  # noqa: E402

SEED = 2147483693  # over 2**31: the driver's seeds are large
# two length rungs: 48 ops -> the 64-row floor, 200 ops -> 256 rows
GROUPS = [
    {"count": 7, "ops": 48, "distinct": 3, "ops_per_change": 16,
     "seq_frac": 0.85, "n_keys": 10, "seq_key": "t", "seq_type": "text",
     "refs": "random"},
    {"count": 6, "ops": 200, "distinct": 3, "ops_per_change": 16,
     "seq_frac": 0.85, "n_keys": 10, "seq_key": "t", "seq_type": "text",
     "refs": "random"},
]
N_DOCS = 13
COUNTERS = (
    "installs", "install_groups", "memo_hits", "install_device_docs",
    "install_host_kernel_docs", "fallbacks", "evictions",
    "evictions_pressure", "flush_errors",
)


def counters():
    snap = telemetry.snapshot()
    return {k: snap.get("serve." + k, 0) for k in COUNTERS}


def moved(before):
    after = counters()
    return {k: after[k] - before[k] for k in COUNTERS}


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """(job, urls): 13 signed single-writer docs of two rungs on disk."""
    tmp = tmp_path_factory.mktemp("serve-bulk")
    job = swt.CorpusJob(
        str(tmp / "repo"), {"sign": True, "groups": GROUPS}, SEED, 2
    )
    try:
        urls = job.start().finish()
    except BaseException:
        job.abort()
        raise
    return job, urls


@contextlib.contextmanager
def opened(corpus, **env):
    """The corpus cold-opened and its summaries fetched (which fills the
    summary memo unless `HM_SUMMARY_MEMO_MB=0`), under `env`."""
    job, urls = corpus
    with pytest.MonkeyPatch.context() as mp:
        for k, v in env.items():
            mp.setenv(k, v)
        repo = Repo(path=job.path)
        try:
            repo.open_many(urls)
            repo.back.fetch_bulk_summaries()
            yield repo
        finally:
            repo.close()


def flush_together(repo, urls, query):
    """One read of every doc in ONE batch flush (the debouncer would cut
    a burst wherever its thread woke). {url: value}."""
    got = {}
    reqs = []
    for u in urls:
        req = ReadRequest(
            validate_doc_url(u), dict(query),
            lambda p, u=u: got.__setitem__(u, p["value"]),
        )
        req.t0 = time.perf_counter()
        reqs.append(req)
    repo.back.serve._flush(reqs)
    assert len(got) == len(urls)
    return got


def queries(objs):
    """Every query kind, every root key, the ends of the text."""
    n = read_plain.evaluate(objs, {"kind": "len", "path": ["t"]})
    out = [
        {"kind": "text", "path": ["t"]},
        {"kind": "len", "path": ["t"]},
        {"kind": "len", "path": []},
        {"kind": "lookup", "path": ["t"]},
        {"kind": "lookup", "path": ["nope"]},
        {"kind": "index", "path": ["t"], "index": n},  # one past the end
    ]
    out += [{"kind": "lookup", "path": [f"k{k}"]} for k in range(10)]
    out += [{"kind": "index", "path": ["t"], "index": i}
            for i in sorted({0, n // 2, n - 1})]
    return out


def check_every_read(repo, corpus):
    job, urls = corpus
    objs = {}
    for i, u in enumerate(urls):
        d = job.plan[i]
        t = (d["group"], d["template"])
        if t not in objs:
            objs[t] = read_plain.replay_objs(job.templates[t[0]][t[1]])
        doc = repo.back.docs[validate_doc_url(u)]
        for q in queries(objs[t]):
            got = repo.read(u, q)
            assert got == host_read(doc, q)["value"], (i, q)
            assert got == read_plain.evaluate(objs[t], q), (i, q)
    # the text is not trivially empty
    assert all(
        len(repo.read(u, {"kind": "text", "path": ["t"]})) > 20
        for u in urls
    )


@pytest.mark.parametrize("memo", [True, False], ids=["memo", "no-memo"])
@pytest.mark.parametrize(
    "lanes", ["device", "host-kernel"],
)
def test_two_rungs_install_as_two_groups(corpus, memo, lanes):
    """13 cold docs of two rungs in one flush: two groups, every doc
    installed, its kernel lanes from the memo or from ONE kernel run a
    group, and every read right."""
    env = {"HM_SUMMARY_MEMO_MB": "256" if memo else "0"}
    if lanes == "device":
        env["HM_DEVICE_MIN_CELLS"] = "0"
    _job, urls = corpus
    with opened(corpus, **env) as repo:
        c0 = counters()
        got = flush_together(repo, urls, {"kind": "len", "path": ["t"]})
        d = moved(c0)
        assert d["install_groups"] == 2
        assert d["installs"] == N_DOCS
        assert d["fallbacks"] == d["flush_errors"] == 0
        kernel_docs = 0 if memo else N_DOCS
        assert d["memo_hits"] == N_DOCS - kernel_docs
        by = "install_device_docs" if lanes == "device" \
            else "install_host_kernel_docs"
        other = "install_host_kernel_docs" if lanes == "device" \
            else "install_device_docs"
        assert d[by] == kernel_docs and d[other] == 0
        assert all(isinstance(v, int) and v > 20 for v in got.values())
        rep = repo.back.serve.residency_report()
        assert len(rep["resident"]) == N_DOCS
        assert {r["rows"] for r in rep["resident"].values()} == {48, 200}
        buckets = {
            e.bucket for e in repo.back.serve._cache._entries.values()
        }
        assert buckets == {64, 256}
        c1 = counters()
        check_every_read(repo, corpus)
        assert moved(c1)["installs"] == 0  # all warm: nothing rebuilt


def test_memo_and_kernel_docs_share_a_group(corpus):
    """A group in which the memo holds some docs and not others: one
    pack, one kernel run, one upload; the memo's docs keep its lanes."""
    _job, urls = corpus
    with opened(corpus, HM_DEVICE_MIN_CELLS="0") as repo:
        memo = repo.back.loader._summary_memo
        for u in urls[::2]:
            del memo[validate_doc_url(u)]
        c0 = counters()
        flush_together(repo, urls, {"kind": "text", "path": ["t"]})
        d = moved(c0)
        assert d["install_groups"] == 2
        assert d["memo_hits"] == N_DOCS - len(urls[::2])
        assert d["install_device_docs"] == len(urls[::2])
        check_every_read(repo, corpus)


def test_a_group_is_built_a_page_at_a_time(corpus, monkeypatch):
    """Groups over a page: every page is packed at one of PAGE_DOCS, so
    the install programs have those doc shapes whatever a flush held;
    a flush of more reads than MAX_BATCH goes in several dispatches."""
    from hypermerge_tpu.parallel import sharded
    from hypermerge_tpu.serve import kernels, resident

    monkeypatch.setattr(resident, "PAGE_DOCS", (2, 4))
    monkeypatch.setattr(kernels, "BATCH_BUCKETS", (1, 4))
    monkeypatch.setattr(kernels, "MAX_BATCH", 4)
    _job, urls = corpus
    with opened(corpus, HM_DEVICE_MIN_CELLS="0",
                HM_SUMMARY_MEMO_MB="0") as repo:
        sharded.clear_program_cache()
        c0 = counters()
        d0 = telemetry.snapshot()["serve.dispatches"]
        flush_together(repo, urls, {"kind": "len", "path": ["t"]})
        d = moved(c0)
        assert d["install_groups"] == 2 and d["installs"] == N_DOCS
        assert d["install_device_docs"] == N_DOCS
        # 7 docs of 64 rows and 6 of 256: 2 + 2 dispatches, each the
        # keyed counts program (the `len` behind its string step)
        assert telemetry.snapshot()["serve.dispatches"] - d0 == 4
        shapes = {(k[1], k[2]) for k in sharded.trace_counts
                  if k[0] == "serve"}
        assert shapes == {
            ("install_split", 4), ("install_split", 2),
            ("install_merge", 4), ("install_merge", 2),
            ("counts", 4),
        }
        check_every_read(repo, corpus)
    sharded.clear_program_cache()


def _rich(repo):
    """Docs whose lanes the memo's wire cannot carry: INC totals, an
    element overwritten by a SET, nesting."""
    url = repo.create({"c": Counter(3), "n": 41, "t": Text("hey there")})
    repo.change(url, lambda d: d.increment("c", 4))
    repo.change(url, lambda d: d.__setitem__("list", [1, "x", False]))
    repo.change(url, lambda d: d["list"].__setitem__(1, "y"))
    repo.change(url, lambda d: d.__setitem__("deep", {"er": {"v": 7}}))
    return url


RICH_QUERIES = [
    ({"kind": "lookup", "path": ["c"]}, 7),
    ({"kind": "lookup", "path": ["n"]}, 41),
    ({"kind": "text", "path": ["t"]}, "hey there"),
    ({"kind": "index", "path": ["list"], "index": 1}, "y"),
    ({"kind": "len", "path": ["list"]}, 3),
    ({"kind": "lookup", "path": ["deep", "er", "v"]}, 7),
    ({"kind": "lookup", "path": ["deep"]}, {"_type": "map"}),
]


@pytest.mark.parametrize("lanes", ["device", "host-kernel"])
def test_group_with_counters_and_element_sets(monkeypatch, lanes):
    """Three docs with INCs and element SETs in one group: the kernel's
    other lanes (INC totals, element winners) reach the host half."""
    if lanes == "device":
        monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "0")
    repo = Repo(memory=True)
    try:
        urls = [_rich(repo) for _ in range(3)]
        c0 = counters()
        flush_together(repo, urls, {"kind": "len", "path": []})
        d = moved(c0)
        assert d["install_groups"] == 1 and d["installs"] == 3
        assert d["memo_hits"] == 0
        for u in urls:
            doc = repo.back.docs[validate_doc_url(u)]
            for q, want in RICH_QUERIES:
                assert repo.read(u, q) == want == host_read(doc, q)["value"]
    finally:
        repo.close()


def test_clock_moved_mid_build_serves_once_and_is_not_cached(monkeypatch):
    """A doc edited while its group builds: this batch is answered from
    the built lanes (right as of admission), nothing is cached, and the
    next read installs the new state."""
    from hypermerge_tpu.serve import tier as tiermod

    repo = Repo(memory=True)
    try:
        url = _rich(repo)
        other = _rich(repo)
        real = tiermod.build_group
        edits = []

        def build_then_edit(backend, items, bucket, count):
            entries = real(backend, items, bucket, count)
            if not edits:
                edits.append(1)
                repo.change(url, lambda d: d.__setitem__("n", 99))
            return entries

        monkeypatch.setattr(tiermod, "build_group", build_then_edit)
        c0 = counters()
        got = flush_together(
            repo, [url, other], {"kind": "lookup", "path": ["n"]}
        )
        assert got == {url: 41, other: 41}
        rep = repo.back.serve.residency_report()["resident"]
        assert validate_doc_url(other) in rep
        assert validate_doc_url(url) not in rep
        assert moved(c0)["installs"] == 2
        assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 99
        assert moved(c0)["installs"] == 3
        assert validate_doc_url(url) in (
            repo.back.serve.residency_report()["resident"]
        )
    finally:
        repo.close()


def test_eviction_acts_doc_by_doc(corpus):
    """A budget that holds four of a group's docs: the LRU sheds the
    others one by one, the rest still answer from the device, and an
    evicted doc installs again on its next read."""
    _job, urls = corpus
    with opened(corpus, HM_SERVE_MAX_BYTES="30000") as repo:
        c0 = counters()
        flush_together(repo, urls, {"kind": "len", "path": ["t"]})
        d = moved(c0)
        rep = repo.back.serve.residency_report()
        assert d["installs"] == N_DOCS and d["evictions"] > 0
        assert len(rep["resident"]) + d["evictions"] == N_DOCS
        assert len(rep["evicted"]) == d["evictions"]
        assert 0 < rep["bytes"] <= 30000
        snap = telemetry.snapshot()
        assert 0 < snap["serve.resident_device_bytes"] <= rep["bytes"]
        check_every_read(repo, corpus)
        assert moved(c0)["installs"] > N_DOCS  # evicted docs came back
        assert moved(c0)["fallbacks"] == 0


@pytest.mark.parametrize("always", [False, True], ids=["once", "always"])
def test_oom_on_a_group_upload(corpus, monkeypatch, always):
    """The device refuses a group's upload: LRU entries are shed and the
    group built once more; refused again, its reads take the host path
    with the right answers."""
    from hypermerge_tpu.serve import resident

    _job, urls = corpus
    short, long_ = urls[:7], urls[7:]
    with opened(corpus) as repo:
        flush_together(repo, short, {"kind": "len", "path": ["t"]})
        real = resident._to_device
        fails = []

        def refuse(arr):
            if always or not fails:
                fails.append(1)
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
            return real(arr)

        monkeypatch.setattr(resident, "_to_device", refuse)
        c0 = counters()
        got = flush_together(repo, long_, {"kind": "len", "path": ["t"]})
        d = moved(c0)
        assert d["evictions_pressure"] > 0
        assert d["install_groups"] == 1 and len(fails) == 2 - (not always)
        assert d["installs"] == (0 if always else len(long_))
        assert d["fallbacks"] == (len(long_) if always else 0)
        doc = repo.back.docs[validate_doc_url(long_[0])]
        want = host_read(doc, {"kind": "len", "path": ["t"]})["value"]
        assert got[long_[0]] == want > 20


def test_a_corrupt_doc_does_not_send_its_group_to_the_host(
    corpus, monkeypatch
):
    """A group whose build fails for another reason than memory is
    built again doc by doc: the broken doc alone falls back."""
    from hypermerge_tpu.serve import tier as tiermod

    _job, urls = corpus
    bad = validate_doc_url(urls[8])
    real = tiermod.build_group

    def build(backend, items, bucket, count):
        if any(doc_id == bad for doc_id, _c, _s in items):
            raise ValueError("corrupt sidecar (not oom)")
        return real(backend, items, bucket, count)

    with opened(corpus) as repo:
        monkeypatch.setattr(tiermod, "build_group", build)
        c0 = counters()
        flush_together(repo, urls, {"kind": "len", "path": ["t"]})
        d = moved(c0)
        assert d["installs"] == N_DOCS - 1 and d["fallbacks"] == 1
        assert d["evictions_pressure"] == 0
        assert bad not in repo.back.serve.residency_report()["resident"]
        assert "corrupt sidecar" in (
            repo.back.serve.residency_report()["last_install_error"]
        )
        check_every_read(repo, corpus)


def test_one_trace_per_program_key(corpus):
    """Install and query programs live in the shared table, traced once
    a key however many groups and flushes ran."""
    from hypermerge_tpu.parallel import sharded

    _job, urls = corpus
    with opened(corpus, HM_DEVICE_MIN_CELLS="0",
                HM_SUMMARY_MEMO_MB="0") as repo:
        for part in (urls[:3], urls[3:7], urls[7:10], urls[10:]):
            flush_together(repo, part, {"kind": "text", "path": ["t"]})
        flush_together(repo, urls, {"kind": "len", "path": []})
    keys = {k: v for k, v in sharded.trace_counts.items()
            if k[0] == "serve"}
    kinds = {k[1] for k in keys}
    assert {"install_merge", "install_split", "seq_order",
            "map_lookup", "counts"} <= kinds
    assert all(v == 1 for v in keys.values()), keys


def test_repo_read_timeout_bounds_a_tier_that_never_answers(monkeypatch):
    repo = Repo(memory=True)
    try:
        url = _rich(repo)
        assert repo.read(url, {"kind": "lookup", "path": ["n"]},
                         timeout=5.0) == 41
        monkeypatch.setattr(
            repo.back.serve, "read_async", lambda doc, query, cb: None
        )
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError):
            repo.read(url, {"kind": "lookup", "path": ["n"]}, timeout=0.2)
        assert time.perf_counter() - t0 < 5.0
    finally:
        repo.close()


# -- the yardstick's new pieces (benchmark/), on hand-worked inputs ----------


def test_read_plain_on_a_hand_worked_history():
    a, b = "aaa", "bbb"
    changes = [
        {"actor": a, "seq": 1, "startOp": 1, "deps": {}, "ops": [
            {"a": 2, "o": "0@_root", "k": "t"},
            {"a": 4, "o": f"1@{a}", "r": "0@_head", "i": True, "v": "x"},
            {"a": 4, "o": f"1@{a}", "r": f"2@{a}", "i": True, "v": "y"},
            {"a": 4, "o": "0@_root", "k": "n", "v": 1}]},
        # b, concurrent with a's seq 2: inserts after x, sets n, deletes y
        {"actor": b, "seq": 1, "startOp": 5, "deps": {a: 1}, "ops": [
            {"a": 4, "o": f"1@{a}", "r": f"2@{a}", "i": True, "v": "B"},
            {"a": 4, "o": "0@_root", "k": "n", "v": 2, "p": [f"4@{a}"]},
            {"a": 5, "o": f"1@{a}", "r": f"3@{a}", "p": [f"3@{a}"]}]},
        {"actor": a, "seq": 2, "startOp": 5, "deps": {}, "ops": [
            {"a": 4, "o": f"1@{a}", "r": f"2@{a}", "i": True, "v": "A"},
            {"a": 4, "o": "0@_root", "k": "n", "v": 3, "p": [f"4@{a}"]}]},
    ]
    objs = read_plain.replay_objs(list(reversed(changes)))
    ev = read_plain.evaluate
    # after x: siblings B (5@bbb) > A (5@aaa) > y (3@aaa, deleted)
    assert ev(objs, {"kind": "text", "path": ["t"]}) == "xBA"
    assert ev(objs, {"kind": "len", "path": ["t"]}) == 3
    assert ev(objs, {"kind": "len", "path": []}) == 2
    assert ev(objs, {"kind": "index", "path": ["t"], "index": 1}) == "B"
    assert ev(objs, {"kind": "index", "path": ["t"], "index": 3}) is None
    assert ev(objs, {"kind": "lookup", "path": ["n"]}) == 2  # 5@bbb wins
    assert ev(objs, {"kind": "lookup", "path": ["t"]}) == {"_type": "text"}
    assert ev(objs, {"kind": "lookup", "path": ["n", "deeper"]}) is None
    assert ev(objs, {"kind": "text", "path": ["n"]}) is None


@pytest.mark.parametrize("kind,batch,rows,want", [
    ("seq_order", 16, 1024, 16 * 4 * 1024 * 4 + 16 * 1024 * 4 + 16 * 4),
    ("map_lookup", 4, 1024, 4 * 3 * 1024 * 4 + 4 * 4 + 4),
    ("counts", 1, 256, 4 * 256 * 4 + 2 * 4),
])
def test_serve_query_least_bytes(kind, batch, rows, want):
    from benchmark.counts import serve_query

    assert serve_query.bytes_moved(kind, batch, rows) == want


def test_counter_and_obs_readers():
    from benchmark.readers import counter_ratio, obs_value

    obs = {
        "counters_before": {"serve.reads": 10, "serve.batches": 5,
                            "serve.installs": 72, "serve.install_groups": 3},
        "counters_after": {"serve.reads": 110, "serve.batches": 25,
                           "serve.installs": 72, "serve.install_groups": 3,
                           "serve.fallbacks": 2, "serve.flush_errors": 0},
        "install_s": 1.5,
    }
    read = counter_ratio.read
    assert read({"num": ["serve.reads"], "den": ["serve.batches"]}, obs) == 5.0
    assert read({"num": ["serve.installs"], "den": ["serve.install_groups"],
                 "at": "before"}, obs) == 24.0
    assert read({"num": ["serve.fallbacks", "serve.flush_errors"]}, obs) == 2.0
    # a counter the program lacks (the parent commit), a zero divisor
    assert read({"num": ["serve.nope"]}, obs) is None
    assert read({"num": ["serve.reads"], "den": ["serve.installs"]},
                obs) is None
    assert obs_value.read({"key": "install_s"}, obs) == 1.5
    assert obs_value.read({"key": "absent"}, obs) is None


@pytest.mark.parametrize("modules,shapes,took_us", [
    # a flush of lookup round + seq_order group (before ISSUE 46)
    ([("jit_serve_seq_order_b16_n1024(7)", 0, 100),
      ("jit_serve_seq_order_b4_n1024(8)", 200, 50),
      ("jit_serve_map_lookup_b16_n1024(9)", 300, 10)],
     [(16, 1024), (4, 1024)], 150),
    # a flush that is ONE keyed seq_order and never runs map_lookup
    ([("jit_serve_seq_order_b16_n1024(7)", 0, 100)], [(16, 1024)], 100),
], ids=["lookup-then-order", "keyed-order-alone"])
def test_serve_roofline_counts_each_dispatch_at_its_shape(
    monkeypatch, modules, shapes, took_us
):
    from benchmark import trace_reduce
    from benchmark.counts import serve_query
    from benchmark.readers import serve_roofline, span_tree

    us = 1e3  # ns
    planes = [
        ("/device:TPU:0", [("XLA Modules", [
            (name, at * us, dur * us) for name, at, dur in modules])]),
        ("/device:TPU:1", [("XLA Modules", [
            ("jit_serve_seq_order_b64_n1024(7)", 0, 100 * us)])]),
        ("/host:CPU", [("python", [("bench.serve.read_loop", 0, 400 * us)])]),
    ]
    monkeypatch.setattr(span_tree, "newest_trace", lambda: "x.pb")
    monkeypatch.setattr(trace_reduce, "load", lambda path: planes)
    obs = {"trace": {"busy_s": 1}, "device_kind": "TPU v5 lite",
           "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}}
    work = sum(serve_query.bytes_moved("seq_order", b, n) for b, n in shapes)
    got = serve_roofline.read(
        {"kind": "seq_order", "peak": "hbm_bytes_per_s"}, obs
    )
    assert got == pytest.approx(100 * (work / 819e9) / (took_us * 1e-6))
    assert 0 < got < 100
    assert serve_roofline.read(
        {"kind": "counts", "peak": "hbm_bytes_per_s"}, obs
    ) is None
