"""The read-serving tier (serve/): residency, batched query kernels,
degradation ladder, and the facade wiring (ISSUE 11).

Twin-equality fuzz lives in tests/test_serve_twin.py; the lockdep-
instrumented race suite in tests/test_serve_races.py.
"""

import threading

import pytest

from hypermerge_tpu import telemetry
from hypermerge_tpu.models import Counter, Text
from hypermerge_tpu.repo import Repo
from hypermerge_tpu.serve import READ_KINDS, host_read
from hypermerge_tpu.utils import keys as keymod
from hypermerge_tpu.utils.ids import to_doc_url, validate_doc_url


def snap():
    return telemetry.snapshot()


def serve_counter(name):
    return snap().get("serve." + name, 0)


@pytest.fixture
def repo():
    r = Repo(memory=True)
    yield r
    r.close()


def _seed(repo):
    url = repo.create({"title": "hello", "n": 41, "pi": 2.5, "yes": True})
    repo.change(url, lambda d: d.__setitem__("text", Text("hey there")))
    repo.change(url, lambda d: d.__setitem__("list", [1, "x", False]))
    repo.change(
        url, lambda d: d.__setitem__("nested", {"deep": {"v": 7}})
    )
    return url


# ---------------------------------------------------------------------------
# read kinds


def test_read_kinds_against_materialized(repo):
    url = _seed(repo)
    doc = repo.doc(url)
    assert repo.read(url, {"kind": "text", "path": ["text"]}) == str(
        doc["text"]
    )
    assert repo.read(url, {"kind": "lookup", "path": ["title"]}) == "hello"
    assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 41
    assert repo.read(url, {"kind": "lookup", "path": ["pi"]}) == 2.5
    assert repo.read(url, {"kind": "lookup", "path": ["yes"]}) is True
    assert (
        repo.read(url, {"kind": "lookup", "path": ["nested", "deep", "v"]})
        == 7
    )
    assert repo.read(url, {"kind": "index", "path": ["list"], "index": 1}) == "x"
    assert repo.read(url, {"kind": "index", "path": ["text"], "index": 0}) == "h"
    assert repo.read(url, {"kind": "len", "path": []}) == len(doc)
    assert repo.read(url, {"kind": "len", "path": ["list"]}) == 3
    assert repo.read(url, {"kind": "len", "path": ["text"]}) == len(
        doc["text"]
    )
    assert repo.read(url, {"kind": "history"}) == 4
    clock = repo.read(url, {"kind": "clock"})
    assert isinstance(clock, list) and len(clock) == 1


def test_read_markers_and_misses(repo):
    url = _seed(repo)
    # containers collapse to type markers
    assert repo.read(url, {"kind": "lookup", "path": ["nested"]}) == {
        "_type": "map"
    }
    assert repo.read(url, {"kind": "lookup", "path": ["list"]}) == {
        "_type": "list"
    }
    assert repo.read(url, {"kind": "lookup", "path": ["text"]}) == {
        "_type": "text"
    }
    # broken paths answer None, never an error
    assert repo.read(url, {"kind": "lookup", "path": ["nope"]}) is None
    assert repo.read(url, {"kind": "lookup", "path": ["n", "deeper"]}) is None
    assert repo.read(url, {"kind": "text", "path": ["list"]}) is None
    assert (
        repo.read(url, {"kind": "index", "path": ["list"], "index": 99})
        is None
    )
    assert repo.read(url, {"kind": "len", "path": ["n"]}) is None
    assert repo.read(url, {"kind": "wat", "path": []}) is None


def test_counter_reads_fold_increments(repo):
    url = repo.create()
    repo.change(url, lambda d: d.__setitem__("c", Counter(3)))
    repo.change(url, lambda d: d.increment("c", 4))
    assert repo.read(url, {"kind": "lookup", "path": ["c"]}) == 7


def test_read_unknown_doc_is_none_and_creates_nothing(repo):
    url = to_doc_url(keymod.create().public_key)
    n_docs = len(repo.back.docs)
    assert repo.read(url, {"kind": "lookup", "path": ["a"]}) is None
    assert len(repo.back.docs) == n_docs  # no phantom doc materialized


def test_read_async_callback(repo):
    url = _seed(repo)
    done = threading.Event()
    got = []

    def cb(value):
        got.append(value)
        done.set()

    repo.read(url, {"kind": "lookup", "path": ["n"]}, cb)
    assert done.wait(10)
    assert got == [41]


# ---------------------------------------------------------------------------
# residency lifecycle


def test_install_then_hits(repo):
    url = _seed(repo)
    h0, i0 = serve_counter("hits"), serve_counter("installs")
    for _ in range(3):
        assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 41
    assert serve_counter("installs") == i0 + 1
    assert serve_counter("hits") >= h0 + 2
    assert repo.back.serve.residency_report()["resident"]


def test_write_invalidates_and_rebuilds(repo):
    url = _seed(repo)
    assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 41
    inv0, ins0 = serve_counter("invalidations"), serve_counter("installs")
    # a write the entry cannot follow (a key its page never saw)
    repo.change(url, lambda d: d.__setitem__("fresh", 42))
    assert serve_counter("invalidations") == inv0 + 1
    assert repo.read(url, {"kind": "lookup", "path": ["fresh"]}) == 42
    assert serve_counter("installs") == ins0 + 1
    # one it can: noted at the write, applied by the read, no install
    adv0 = serve_counter("advances")
    repo.change(url, lambda d: d.__setitem__("n", 42))
    assert serve_counter("invalidations") == inv0 + 1
    assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 42
    assert serve_counter("installs") == ins0 + 1
    assert serve_counter("advances") == adv0 + 1


def test_byte_budget_evicts_lru(repo, monkeypatch):
    monkeypatch.setenv("HM_SERVE_MAX_BYTES", "4000")
    urls = [_seed(repo) for _ in range(4)]
    for u in urls:
        assert repo.read(u, {"kind": "lookup", "path": ["n"]}) == 41
    assert serve_counter("evictions") > 0
    rep = repo.back.serve.residency_report()
    assert rep["evicted"]
    assert rep["bytes"] <= 4000
    # evicted docs reinstall on demand, still correct
    assert repo.read(urls[0], {"kind": "lookup", "path": ["title"]}) == (
        "hello"
    )


def test_close_doc_drops_residency(repo):
    url = _seed(repo)
    repo.read(url, {"kind": "lookup", "path": ["n"]})
    doc_id = validate_doc_url(url)
    assert repo.back.serve.residency_report()["resident"]
    repo.close_doc(url)
    rep = repo.back.serve.residency_report()
    assert doc_id not in rep["resident"]


# ---------------------------------------------------------------------------
# degradation ladder


def test_device_oom_evicts_and_retries_once(repo, monkeypatch):
    from hypermerge_tpu.serve import resident

    warm = _seed(repo)
    assert repo.read(warm, {"kind": "lookup", "path": ["n"]}) == 41
    url = _seed(repo)
    real = resident._to_device
    fails = {"n": 1}

    def flaky(arr):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return real(arr)

    monkeypatch.setattr(resident, "_to_device", flaky)
    p0, f0 = serve_counter("evictions_pressure"), serve_counter("fallbacks")
    # first install attempt OOMs -> LRU shed -> retry succeeds
    assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 41
    assert serve_counter("evictions_pressure") > p0
    assert serve_counter("fallbacks") == f0


def test_device_oom_twice_degrades_to_host(repo, monkeypatch):
    from hypermerge_tpu.serve import resident

    warm = _seed(repo)
    repo.read(warm, {"kind": "lookup", "path": ["n"]})
    url = _seed(repo)

    def dead(arr):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    monkeypatch.setattr(resident, "_to_device", dead)
    f0 = serve_counter("fallbacks")
    # reader still gets the right answer — never an error
    assert repo.read(url, {"kind": "text", "path": ["text"]}) == "hey there"
    assert serve_counter("fallbacks") > f0


def test_unserveable_doc_falls_back_with_host_memo(repo, monkeypatch):
    url = _seed(repo)
    monkeypatch.setattr(
        repo.back, "_serveable_spec", lambda clock: None
    )
    f0, m0 = serve_counter("fallbacks"), serve_counter("host_memo_hits")
    assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 41
    # clock unmoved: the second degraded read hits the host memo —
    # zero snapshot decode / wire parse
    assert repo.read(url, {"kind": "lookup", "path": ["title"]}) == "hello"
    assert serve_counter("fallbacks") >= f0 + 2
    assert serve_counter("host_memo_hits") >= m0 + 1


def test_admission_overflow_degrades(monkeypatch):
    # queue overflow is TRAFFIC pressure, not a device degradation:
    # it counts serve.overload_shed (the service plane's signal),
    # never serve.fallbacks (ISSUE 20 satellite) — and the read still
    # answers correctly from the host path
    monkeypatch.setenv("HM_SERVE_QUEUE", "0")  # cap reads at tier init
    repo = Repo(memory=True)
    try:
        url = _seed(repo)
        f0 = serve_counter("fallbacks")
        s0 = serve_counter("overload_shed")
        assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 41
        assert serve_counter("overload_shed") == s0 + 1
        assert serve_counter("fallbacks") == f0
    finally:
        repo.close()


# ---------------------------------------------------------------------------
# batched kernels + program table


def test_program_table_traces_once():
    from hypermerge_tpu.parallel import sharded

    r = Repo(memory=True)
    try:
        urls = [r.create({"i": i}) for i in range(4)]
        for i, u in enumerate(urls):
            r.change(u, lambda d, i=i: d.__setitem__("t", Text(f"x{i}")))
        for _ in range(3):
            for u in urls:
                assert r.read(u, {"kind": "text", "path": ["t"]})
        keys = {
            k: v for k, v in sharded.trace_counts.items()
            if k[0] == "serve"
        }
        assert keys, "serve programs should live in the shared table"
        assert all(v == 1 for v in keys.values()), keys
    finally:
        r.close()


def test_concurrent_reads_batch(repo):
    urls = [_seed(repo) for _ in range(4)]
    b0, r0 = serve_counter("batches"), serve_counter("reads")
    out = {}

    def reader(n):
        for j in range(8):
            u = urls[(n + j) % len(urls)]
            out[(n, j)] = repo.read(u, {"kind": "text", "path": ["text"]})

    ts = [threading.Thread(target=reader, args=(n,)) for n in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert all(v == "hey there" for v in out.values())
    reads = serve_counter("reads") - r0
    batches = serve_counter("batches") - b0
    assert reads == 64
    # the debounce window must coalesce at least some of the storm
    assert batches < reads


# ---------------------------------------------------------------------------
# memo wiring + introspection surfaces


def test_bulk_summary_memo_feeds_installs(tmp_path):
    path = str(tmp_path / "repo")
    r = Repo(path=path)
    urls = [r.create({"i": i}) for i in range(3)]
    for i, u in enumerate(urls):
        r.change(u, lambda d, i=i: d.__setitem__("t", Text(f"doc{i}")))
    r.close()
    r = Repo(path=path)
    try:
        r.open_many(urls)
        r.back.fetch_bulk_summaries()  # populates the per-doc memo
        m0 = serve_counter("memo_hits")
        for i, u in enumerate(urls):
            assert r.read(u, {"kind": "text", "path": ["t"]}) == f"doc{i}"
        # installs reused the bulk loader's memo'd summary lanes
        # (clock unmoved): no second host kernel run
        assert serve_counter("memo_hits") >= m0 + len(urls)
    finally:
        r.close()


def test_telemetry_query_carries_residency(repo):
    url = _seed(repo)
    repo.read(url, {"kind": "lookup", "path": ["n"]})
    got = []
    repo.telemetry(got.append)
    assert got and "serve" in got[0]
    assert got[0]["serve"]["resident"]
    assert any(
        k.startswith("serve.") for k in got[0]["counters"]
    )


def test_host_read_twin_smoke(repo):
    url = _seed(repo)
    doc = repo.back.docs[validate_doc_url(url)]
    for q in (
        {"kind": "text", "path": ["text"]},
        {"kind": "lookup", "path": ["title"]},
        {"kind": "len", "path": []},
        {"kind": "history"},
    ):
        assert host_read(doc, q) == {"value": repo.read(url, q)}
    assert set(READ_KINDS) == {
        "lookup", "index", "text", "len", "clock", "history"
    }


def test_serve_off_is_host_twin(monkeypatch):
    monkeypatch.setenv("HM_SERVE", "0")
    r = Repo(memory=True)
    try:
        assert r.back.serve is None
        url = r.create({"a": 1})
        r.change(url, lambda d: d.__setitem__("t", Text("plain")))
        assert r.read(url, {"kind": "text", "path": ["t"]}) == "plain"
        assert r.read(url, {"kind": "lookup", "path": ["a"]}) == 1
    finally:
        r.close()


def test_read_after_tier_close_degrades(repo):
    """A read racing (or following) tier shutdown degrades to the host
    path with the right answer — never a dropped callback/timeout."""
    url = _seed(repo)
    assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 41
    repo.back.serve.close()
    # post-close reads answer inline off the host path (the tier's
    # labeled counters are already retired from the registry)
    assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 41
    assert repo.read(url, {"kind": "text", "path": ["text"]}) == (
        "hey there"
    )


def test_non_oom_install_failure_does_not_shed(repo, monkeypatch):
    """A deterministic build failure (corrupt sidecar, pack bug) falls
    back to host WITHOUT evicting healthy residents — only genuine
    memory pressure earns the evict-and-retry."""
    from hypermerge_tpu.serve import tier as tiermod

    urls = [_seed(repo) for _ in range(3)]
    for u in urls:
        assert repo.read(u, {"kind": "lookup", "path": ["n"]}) == 41
    n0 = repo.back.serve._cache.resident_docs

    def broken(backend, items, bucket, count):
        raise ValueError("corrupt sidecar (not oom)")

    monkeypatch.setattr(tiermod, "build_group", broken)
    cold = _seed(repo)
    p0 = serve_counter("evictions_pressure")
    f0 = serve_counter("fallbacks")
    assert repo.read(cold, {"kind": "lookup", "path": ["n"]}) == 41
    assert serve_counter("fallbacks") > f0
    assert serve_counter("evictions_pressure") == p0
    assert repo.back.serve._cache.resident_docs == n0


def test_write_releases_resident_bytes(repo):
    """mark_stale frees the invalidated entry's device arrays at the
    write, not at the next LRU pass."""
    url = _seed(repo)
    assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 41
    b0 = repo.back.serve._cache.resident_bytes
    assert b0 > 0
    repo.change(url, lambda d: d.__setitem__("fresh", 99))
    assert repo.back.serve._cache.resident_bytes < b0
    assert repo.read(url, {"kind": "lookup", "path": ["fresh"]}) == 99
    # a write the entry follows keeps it, a row's bytes larger
    b1 = repo.back.serve._cache.resident_bytes
    repo.change(url, lambda d: d.__setitem__("n", 99))
    assert repo.back.serve._cache.resident_bytes == b1
    assert repo.read(url, {"kind": "lookup", "path": ["n"]}) == 99
    assert 0 < repo.back.serve._cache.resident_bytes - b1 < 1024


# ---------------------------------------------------------------------------
# nothing on the device path fails silently


def test_refused_serve_kernel_answers_host_value(repo, monkeypatch):
    """A query kernel the device refuses (it raises inside the batch
    flush) must not turn reads into None — the legitimate answer for a
    path that does not exist. Every pending read gets the host twin's
    value and serve.flush_errors says the device path is broken."""
    from hypermerge_tpu.serve import kernels

    url = _seed(repo)
    doc = repo.back.docs[validate_doc_url(url)]

    def refuse(*_a, **_k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    for name in ("map_lookup", "seq_order", "counts"):
        monkeypatch.setattr(kernels, name, refuse)
    e0 = serve_counter("flush_errors")
    queries = [
        {"kind": "lookup", "path": ["n"]},
        {"kind": "text", "path": ["text"]},
        {"kind": "index", "path": ["list"], "index": 1},
        {"kind": "len", "path": ["list"]},
        {"kind": "lookup", "path": ["nested", "deep", "v"]},
    ]
    for q in queries:
        want = host_read(doc, q)["value"]
        assert want is not None
        assert repo.read(url, q) == want
    assert serve_counter("flush_errors") >= e0 + len(queries)
    # a path that does not exist still answers None — from the twin
    assert repo.read(url, {"kind": "lookup", "path": ["nope"]}) is None


def test_serve_tier_that_cannot_construct_fails_the_repo(monkeypatch):
    """No silent host-only repo: a ServeTier constructor that raises
    fails RepoBackend(...) — unless the operator chose HM_SERVE=0."""
    from hypermerge_tpu import serve
    from hypermerge_tpu.backend.repo_backend import RepoBackend

    def broken(_backend):
        raise RuntimeError("no usable jax backend")

    monkeypatch.setattr(serve, "ServeTier", broken)
    with pytest.raises(RuntimeError, match="no usable jax backend"):
        RepoBackend(memory=True)
    monkeypatch.setenv("HM_SERVE", "0")
    back = RepoBackend(memory=True)
    try:
        assert back.serve is None
    finally:
        back.close()
