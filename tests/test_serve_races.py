"""Serving-tier race drivers under HM_LOCKDEP=1 (ISSUE 11).

Concurrent writers, readers, and eviction churn exercise every serve
lock (serve.cache, serve.batch) against the engine/doc/store locks;
the module teardown asserts the observed lock-order graph is clean —
no potential deadlock cycle, no hierarchy inversion — even though no
deadlock fired. The chaos test also pins the freshness contract: a
read issued after a patch was delivered NEVER returns state older
than that patch.
"""

import threading

import pytest

from hypermerge_tpu.models import Text
from hypermerge_tpu.repo import Repo
from lockdep_fixture import lockdep_suite
from racedep_fixture import racedep_suite

_lockdep = lockdep_suite()
# eviction churn + invalidation races under the lockset detector
# (tests/racedep_fixture.py): the serve-tier guard rows verified live
_racedep = racedep_suite()


@pytest.fixture
def repo():
    r = Repo(memory=True)
    yield r
    r.close()


def test_eviction_churn_race(repo, monkeypatch):
    """Readers over more docs than the byte budget holds: every read
    races installs + LRU evictions of the others. Values must stay
    correct and the lock graph clean."""
    monkeypatch.setenv("HM_SERVE_MAX_BYTES", "4000")
    urls = []
    for i in range(6):
        u = repo.create({"i": i})
        repo.change(u, lambda d, i=i: d.__setitem__("t", Text(f"doc{i}")))
        urls.append(u)
    errors = []

    def reader(n):
        try:
            for j in range(10):
                i = (n + j) % len(urls)
                v = repo.read(urls[i], {"kind": "text", "path": ["t"]})
                assert v == f"doc{i}", v
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)

    ts = [threading.Thread(target=reader, args=(n,)) for n in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors


def test_invalidation_race(repo):
    """Writers move clocks while readers install/serve: a read may see
    the pre- or post-edit value of a CONCURRENT edit, but never a
    value that contradicts the doc's committed history (values only
    ever grow through the append-only script below)."""
    url = repo.create()
    repo.change(url, lambda d: d.__setitem__("n", 0))
    stop = threading.Event()
    errors = []

    def writer():
        try:
            for i in range(1, 30):
                repo.change(url, lambda d, i=i: d.__setitem__("n", i))
        finally:
            stop.set()

    def reader():
        last = -1
        try:
            while not stop.is_set() or last < 0:
                v = repo.read(url, {"kind": "lookup", "path": ["n"]})
                assert v is not None and v >= last, (v, last)
                last = v
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)

    ts = [threading.Thread(target=reader) for _ in range(3)]
    w = threading.Thread(target=writer)
    for t in ts:
        t.start()
    w.start()
    w.join()
    for t in ts:
        t.join()
    assert not errors


def test_no_stale_read_past_delivered_patch(repo):
    """The live-edit-during-read chaos test: a watcher records each
    delivered patch's text length; every read issued AFTER a delivery
    must reflect at least that much text (the serving clock moved
    before the patch reached the frontend, so a resident entry built
    earlier can never serve the newer read)."""
    url = repo.create()
    repo.change(url, lambda d: d.__setitem__("t", Text("")))
    seen = [0]  # longest delivered text, updated by the watcher

    def watch(state, _idx):
        t = state.get("t")
        if isinstance(t, Text) and len(t) > seen[0]:
            seen[0] = len(t)

    handle = repo.watch(url, watch)
    errors = []
    stop = threading.Event()

    def writer():
        try:
            for i in range(40):
                repo.change(
                    url,
                    lambda d, i=i: d["t"].insert(len(d["t"]), "x"),
                )
        finally:
            stop.set()

    def reader():
        try:
            while not stop.is_set():
                floor = seen[0]  # delivered BEFORE this read is issued
                v = repo.read(url, {"kind": "text", "path": ["t"]})
                assert v is not None and len(v) >= floor, (len(v), floor)
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)

    rs = [threading.Thread(target=reader) for _ in range(2)]
    w = threading.Thread(target=writer)
    for t in rs:
        t.start()
    w.start()
    w.join()
    for t in rs:
        t.join()
    handle.close()
    assert not errors
    # the final read observes the full 40-char text
    assert repo.read(url, {"kind": "text", "path": ["t"]}) == "x" * 40


def _serve(name):
    from hypermerge_tpu import telemetry

    return telemetry.snapshot().get("serve." + name, 0)


def test_read_between_clock_move_and_note_waits_and_follows(
    repo, monkeypatch
):
    """A writer held between its clock move and its note (inside the
    doc's emission domain): a flush that looks then finds the entry a
    change behind. It waits for the writer, applies what the writer
    noted and answers exactly: no install, no host answer, and the
    entry never ahead of the doc's clock."""
    import time

    from hypermerge_tpu.crdt import clock as clockmod
    from hypermerge_tpu.serve.batcher import ReadRequest
    from hypermerge_tpu.serve.tier import ServeTier
    from hypermerge_tpu.utils.ids import validate_doc_url

    monkeypatch.setenv("HM_SERVICE_FORCE", "healthy")
    url = repo.create()
    repo.change(url, lambda d: d.__setitem__("t", Text("ab")))
    doc_id = validate_doc_url(url)
    tier = repo.back.serve
    assert repo.read(url, {"kind": "text", "path": ["t"]}) == "ab"
    inside, go = threading.Event(), threading.Event()
    real = ServeTier.note_clock_moved

    def held(self, doc_id, event=None):
        inside.set()
        assert go.wait(30)
        real(self, doc_id, event)

    monkeypatch.setattr(ServeTier, "note_clock_moved", held)
    c0 = {k: _serve(k) for k in ("installs", "fallbacks", "flush_errors")}
    a0, w0 = _serve("advances"), _serve("cold_reads")
    want = "ab"
    try:
        for i in range(40):
            inside.clear()
            go.clear()
            ch = chr(99 + i % 20)
            want = want[:1] + ch + want[1:]
            writer = threading.Thread(
                target=repo.change,
                args=(url, lambda d, ch=ch: d["t"].insert(1, ch)))
            writer.start()
            assert inside.wait(30)
            doc = repo.back.docs[doc_id]
            entry = tier._cache._entries[doc_id]
            assert entry.clock != doc.clock  # moved, and nothing noted
            got = []
            req = ReadRequest(doc_id, {"kind": "text", "path": ["t"]},
                              lambda p: got.append(p["value"]))
            req.t0 = time.perf_counter()
            flush = threading.Thread(target=tier._flush, args=([req],))
            flush.start()
            time.sleep(0.0005)
            go.set()
            flush.join(30)
            writer.join(30)
            assert got == [want]
            assert tier._cache._entries[doc_id] is entry
            assert entry.clock == doc.clock
            assert clockmod.gte(doc.clock, entry.clock)
    finally:
        go.set()
    assert {k: _serve(k) - v for k, v in c0.items()} == {
        "installs": 0, "fallbacks": 0, "flush_errors": 0}
    assert _serve("advances") - a0 == 40
    assert _serve("cold_reads") - w0 == 40  # each looked, and waited


def _flush_one(tier, doc_id, query):
    """One read through a flush of its own: (thread, its answers)."""
    import time

    from hypermerge_tpu.serve.batcher import ReadRequest

    got = []
    req = ReadRequest(doc_id, query, lambda p: got.append(p["value"]))
    req.t0 = time.perf_counter()
    flush = threading.Thread(target=tier._flush, args=([req],))
    flush.start()
    return flush, got


@pytest.mark.parametrize("when", ["after_the_look", "as_rows_are_made"])
def test_entry_released_between_look_and_advance_installs(
    repo, monkeypatch, when
):
    """A flush looked its doc's entry up (`get_fresh`: lanes at C0,
    one change noted up to C1, the doc at C1) and a drainer releases
    the entry before the flush has applied the change (`mark_stale`: a
    remote patch, a refusal, a tick): what the entry had noted went
    with it, so the read is answered by an install at the doc's clock,
    never by the lanes at C0."""
    from hypermerge_tpu.serve import resident
    from hypermerge_tpu.utils.ids import validate_doc_url

    monkeypatch.setenv("HM_SERVICE_FORCE", "healthy")
    url = repo.create()
    repo.change(url, lambda d: d.__setitem__("t", Text("ab")))
    doc_id = validate_doc_url(url)
    tier = repo.back.serve
    cache = tier._cache
    query = {"kind": "text", "path": ["t"]}
    assert repo.read(url, query) == "ab"
    entry = cache._entries[doc_id]
    repo.change(url, lambda d: d["t"].insert(1, "X"))
    assert cache._entries[doc_id] is entry and doc_id in cache._noted
    c0 = {k: _serve(k) for k in ("installs", "advances", "fallbacks")}
    released = []

    def release():
        if not released:
            released.append(cache.mark_stale(doc_id))

    if when == "after_the_look":
        look = cache.get_fresh

        def looked(*a):
            out = look(*a)
            release()
            return out

        monkeypatch.setattr(cache, "get_fresh", looked)
    else:
        made = resident.ResidentDoc.followed

        def making(self, *a):
            release()
            return made(self, *a)

        monkeypatch.setattr(resident.ResidentDoc, "followed", making)
    flush, got = _flush_one(tier, doc_id, query)
    flush.join(30)
    assert released == [True]
    assert got == ["aXb"]
    assert cache._entries[doc_id] is not entry
    assert entry.clock != repo.back.docs[doc_id].clock  # never written
    assert {k: _serve(k) - v for k, v in c0.items()} == {
        "installs": 1, "advances": 0, "fallbacks": 0}


def test_change_noted_while_an_advance_is_under_way_stays_noted(
    repo, monkeypatch
):
    """The flush makes an entry's new rows outside the cache lock: a
    change the writer notes meanwhile is neither applied to the read
    under way (it answers at the clock it looked at) nor lost (the
    next read applies it): no install either time."""
    from hypermerge_tpu.serve import resident
    from hypermerge_tpu.utils.ids import validate_doc_url

    monkeypatch.setenv("HM_SERVICE_FORCE", "healthy")
    url = repo.create({"k": 0})
    repo.change(url, lambda d: d.__setitem__("t", Text("ab")))
    doc_id = validate_doc_url(url)
    tier = repo.back.serve
    cache = tier._cache
    query = {"kind": "text", "path": ["t"]}
    assert repo.read(url, query) == "ab"
    entry = cache._entries[doc_id]
    repo.change(url, lambda d: d["t"].insert(1, "X"))
    inside, go = threading.Event(), threading.Event()
    made = resident.ResidentDoc.followed

    def making(self, *a):
        out = made(self, *a)
        if not inside.is_set():
            inside.set()
            assert go.wait(30)
        return out

    monkeypatch.setattr(resident.ResidentDoc, "followed", making)
    c0 = {k: _serve(k) for k in ("installs", "advances", "invalidations")}
    flush, got = _flush_one(tier, doc_id, query)
    try:
        assert inside.wait(30)
        repo.change(url, lambda d: d["t"].insert(2, "Y"))  # after X's row
        repo.change(url, lambda d: d.__setitem__("k", 1))
        assert len(cache._noted[doc_id].deltas) == 3
    finally:
        go.set()
    flush.join(30)
    assert got == ["aXb"]
    assert cache._entries[doc_id] is entry
    assert len(cache._noted[doc_id].deltas) == 2
    assert entry.clock != repo.back.docs[doc_id].clock
    assert repo.read(url, query) == "aXYb"
    assert repo.read(url, {"kind": "lookup", "path": ["k"]}) == 1
    assert entry.clock == repo.back.docs[doc_id].clock
    assert doc_id not in cache._noted
    assert {k: _serve(k) - v for k, v in c0.items()} == {
        "installs": 0, "advances": 3, "invalidations": 0}


def test_remote_patches_between_noted_changes(repo, monkeypatch):
    """A writer's local changes (noted on the entry) with a fork's
    edits merged in between them (remote patches: the entry and what it
    noted are released) and readers, all on one doc: every text read is
    one the doc went through (the writer's characters in order, the
    fork's likewise), no entry is ever ahead of the doc's clock when it
    serves, and the last read is exact."""
    from helpers import wait_until
    from hypermerge_tpu.crdt import clock as clockmod
    from hypermerge_tpu.utils.ids import validate_doc_url

    monkeypatch.setenv("HM_SERVICE_FORCE", "healthy")
    url = repo.create()
    repo.change(url, lambda d: d.__setitem__("t", Text("|")))
    doc_id = validate_doc_url(url)
    other = repo.fork(url)
    tier = repo.back.serve
    errors = []
    stop = threading.Event()
    resolve = tier._resolve

    def checked(reqs):
        for r in reqs:
            doc = repo.back.docs.get(r.doc_id)
            if doc is not None and not clockmod.gte(
                doc.clock, r.entry.clock
            ):
                errors.append(("entry ahead", r.entry.clock, doc.clock))
        resolve(reqs)

    monkeypatch.setattr(tier, "_resolve", checked)
    LOCAL, REMOTE = 60, 12

    def writer():
        # after the bar a b c ... in order; before it, from the fork,
        # 0 1 2 ...: a remote patch after every fifth local change
        try:
            for i in range(LOCAL):
                # every local change meets an entry (a reader's
                # install lands once the writes pause)
                wait_until(lambda: doc_id in tier._cache._entries)
                repo.change(url, lambda d, i=i: d["t"].insert(
                    len(d["t"]), chr(97 + i % 26)))
                if i % 5 == 4:
                    j = i // 5
                    repo.change(other, lambda d, j=j: d["t"].insert(
                        j, chr(48 + j % 10)))
                    repo.merge(url, other)
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)
        finally:
            stop.set()

    def reader():
        seen = (0, 0)
        try:
            while not stop.is_set():
                v = repo.read(url, {"kind": "text", "path": ["t"]})
                left, _bar, right = v.partition("|")
                assert left == "".join(
                    chr(48 + i % 10) for i in range(len(left))), v
                assert right == "".join(
                    chr(97 + i % 26) for i in range(len(right))), v
                assert len(left) >= seen[0] and len(right) >= seen[1], v
                seen = (len(left), len(right))
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)

    n0, r0 = _serve("advance_notes"), _serve("advance_refusals")
    ts = [threading.Thread(target=f) for f in (writer, reader, reader)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors[:3]
    want = ("".join(chr(48 + i % 10) for i in range(REMOTE)) + "|"
            + "".join(chr(97 + i % 26) for i in range(LOCAL)))
    assert repo.read(url, {"kind": "text", "path": ["t"]}) == want
    assert _serve("advance_notes") - n0 >= LOCAL - REMOTE
    # the merges, and once a bucket that was full
    assert _serve("advance_refusals") - r0 == sum(tier.refusals.values())
    assert tier.refusals["remote"] > 0 and set(tier.refusals) <= {
        "remote", "full"}
