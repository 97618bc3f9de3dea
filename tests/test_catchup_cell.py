"""A peer that comes back online behind (ISSUE 44, `catchup-1k3a` /
`sync.catchup`) at rehearsal size, on the CPU: the cell's rehearsal and
its control; the cut store tied to the whole one; B held to
`benchmark/reference/catchup_plain.py` (which imports nothing of the
program) and to the host OpSet whatever order the feeds' tails arrive
in; behind by 0, 1, 8, 31 and 32 blocks of 32; tails that land while
the held docs open; a tail that does not extend the sealed chain; the
caught-up directory reopened with no swarm; a catch-up stopped half way
and started again; the new spans and counters, each new metric file on
a hand-worked `obs`, the disk check on hand-damaged copies and the
parent's clean failure. Counts and states only; every wait has a limit
of its own.
"""

import itertools
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.corpora import multi_writer_rounds_behind as mwrb  # noqa: E402
from benchmark.reference import catchup_plain, clone_plain  # noqa: E402
from benchmark.reference import crdt_plain  # noqa: E402
from benchmark.reference.plainify import plain  # noqa: E402
from hypermerge_tpu import telemetry  # noqa: E402
from hypermerge_tpu.net.tcp import TcpSwarm  # noqa: E402
from hypermerge_tpu.repo import Repo  # noqa: E402

SEEDS = (2147483659, 2147483693, 7)  # two over 2**31, as the driver's
# 48 changes of 4 ops a doc: 16 blocks a feed, 12 held, behind by 4
OPS, PER_CHANGE, DOCS, HELD = 192, 4, 4, 12
CHANGES = OPS // PER_CHANGE
BLOCKS = CHANGES // 3
WAIT_S = 60.0
LEN_T = {"kind": "len", "path": ["t"]}


def spec(docs=DOCS, held=HELD, per_change=PER_CHANGE):
    return {"writer": "multi_writer_rounds_behind", "sign": True,
            "ops": OPS, "ops_per_change": per_change, "blocks_held": held,
            "seq_frac": 0.85, "del_frac": 0.1, "n_keys": 10, "seq_key": "t",
            "distinct": 2, "classes": [{"writers": 3, "count": docs}]}


class Stores:
    """Both stores from one seed; peer A cold-opened behind a TcpSwarm."""

    def __init__(self, path, seed, **kw):
        self.path = str(path)
        self.spec = spec(**kw)
        self.job = mwrb.CorpusJob(self.path, self.spec, seed, 2)
        try:
            self.urls = self.job.start().finish()
        except BaseException:
            self.job.abort()
            raise
        self.held = self.job.held
        self.changes = OPS // self.spec["ops_per_change"]
        self.repo = Repo(path=self.path)
        self.swarm = TcpSwarm()
        self.repo.set_swarm(self.swarm)
        self.repo.open_many(self.urls)
        self.repo.back.fetch_bulk_summaries()
        self.keys = [[p.public_key for p in pairs]
                     for pairs in self.job.pairs]
        self._cache = {}

    def doc_changes(self, doc):
        return self.job.doc_changes(doc, self._cache)

    def ref(self, doc):
        return catchup_plain.expect(self.doc_changes(doc), "t")

    def was(self, doc):
        return catchup_plain.held(self.doc_changes(doc), 3 * self.held, "t")

    def opset_value(self, doc):
        from hypermerge_tpu.crdt.change import Change
        from hypermerge_tpu.crdt.opset import OpSet

        opset = OpSet()
        opset.apply_changes(
            [Change.from_json(c) for c in self.doc_changes(doc)])
        return plain(opset.materialize())

    def feed(self, key):
        """(blocks, {length: signature}) of one of A's feeds."""
        feed = self.repo.back.feeds.open_feed(key)
        blocks = feed.get_batch(0, feed.length)
        sigs = {n: feed.integrity.record_for(feed, n)[2]
                for n in range(1, len(blocks) + 1)}
        return blocks, sigs

    def behind(self, path):
        """A copy of B's directory, as a round consumes one."""
        shutil.copytree(self.job.behind_path, str(path))
        return str(path)

    def close(self):
        self.repo.close()


@pytest.fixture(scope="module", params=SEEDS)
def stores(request, tmp_path_factory):
    s = Stores(tmp_path_factory.mktemp("a") / "repo", request.param)
    yield s
    s.close()


@pytest.fixture(scope="module")
def one(tmp_path_factory):
    s = Stores(tmp_path_factory.mktemp("a1") / "repo", SEEDS[0])
    yield s
    s.close()


def watch_all(handles, changes=CHANGES):
    """Subscribe every handle -> (the indexes each was delivered, a wait
    for every handle to have delivered `changes`)."""
    seen = [[] for _ in handles]
    left = set(range(len(handles)))
    lock, done = threading.Lock(), threading.Event()

    def watch(i):
        def on_value(_value, index):
            seen[i].append(index)
            if index >= changes:
                with lock:
                    left.discard(i)
                    if not left:
                        done.set()
        return on_value

    for i, h in enumerate(handles):
        h.subscribe(watch(i))
    if not handles:
        done.set()
    return seen, lambda limit=WAIT_S: done.wait(limit)


def come_back(path, s, before_open=False):
    """B: a fresh repo on a copy of the behind directory, every url
    opened, then (or first) joined to A. -> (repo, handles, indexes,
    wait)."""
    repo = Repo(path=s.behind(path))

    def join():
        swarm = TcpSwarm()
        repo.set_swarm(swarm)
        swarm.connect(s.swarm.address)

    if before_open:
        join()
    handles = repo.open_many(s.urls)
    repo.back.fetch_bulk_summaries()
    seen, wait = watch_all(handles, s.changes)
    if not before_open:
        join()
    return repo, handles, seen, wait


def counters():
    return {k: v for k, v in telemetry.snapshot().items()
            if isinstance(v, (int, float))}


# -- the cell's rehearsal and its control ------------------------------------


def run_cell(*more):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "sync.catchup", "--seed", str(SEEDS[0]),
         "--seconds", "2", "--trace", "0", "--rehearse", *more],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    try:
        return json.loads(out.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        raise AssertionError(out.stderr[-2000:])


@pytest.mark.parametrize("control", (False, True), ids=("sound", "control"))
def test_rehearsal_of_the_cell(control):
    """The sound rehearsal (24 docs x 192 ops, behind by a quarter) is
    `correct`; under the mix's control (`HM_LIVE=0`) exactly
    `docs_not_live` fails."""
    line = run_cell(*(["--control"] if control else []))
    bad = [c["name"] for c in line["checks"] if c["value"] > c["limit"]]
    assert line["metrics"] == {} and line["failed"] == 0
    assert line["attempted"] % 24 == 0 and line["attempted"] >= 24
    if control:
        assert line["correct"] is False and bad == ["docs_not_live"]
        return
    assert line["correct"] is True and bad == []
    assert {"feeds_not_asked_from_len", "sealed_heads_not_retired"} <= {
        c["name"] for c in line["checks"]}
    rounds = line["setup"]["rounds"]
    assert rounds and line["setup"]["warm_round"]["failed"] == 0
    for r in rounds:  # a round's fixed work, by the program's counters
        assert r["live.adopted"] == r["live.adopt_held"] == 24
        assert r["net.repl.blocks_rx"] == 72  # 72 feeds x 1 block behind
        assert r["net.repl.requests_from_len"] == 72
        assert r["storage.feed.extended_sealed"] == 72
        assert r["serve.reinstalls"] == 24
        assert r["whole_s"] == pytest.approx(
            r["round_s"] + r["close"], abs=0.01)  # round_s ends at the
        # last answer, as a clone's; the close and the gc are close_s
        assert "net.repl.blocks_dup_rx" not in r
        assert {"open", "first_read", "converge", "read", "close"} <= set(r)
    assert line["counts"]["compile"]["window"]["requests"] == 0


# -- the cut tied to the whole ------------------------------------------------


def test_the_cut_store_plus_the_tails_is_the_whole_store(one):
    """B's block logs plus the tails of A's feeds are A's logs byte for
    byte, B's `.sig` chain is the head of A's, its `.len` says what it
    holds, and its snapshot of heads is sealed over every feed; the
    reference's replay of prefix-then-tails equals `crdt_plain`'s
    replay of all the changes for every arrival order of the tails."""
    from hypermerge_tpu.storage.feed import HeadSnapshot

    a_root = one.path + "/feeds"
    b_root = one.job.behind_path + "/feeds"
    sealed = HeadSnapshot(b_root).sealed()
    for keys in one.keys:
        for key in keys:
            a, b = (clone_plain.feed_path(r, key) for r in (a_root, b_root))
            whole, cut = (clone_plain.feed_blocks(p) for p in (a, b))
            assert len(whole) == BLOCKS and len(cut) == HELD
            with open(a, "rb") as fa, open(b, "rb") as fb:
                raw_a, raw_b = fa.read(), fb.read()
            tail = b"".join(
                len(x).to_bytes(4, "little") + x for x in whole[HELD:])
            assert raw_b + tail == raw_a
            with open(a + ".sig", "rb") as fa, open(b + ".sig", "rb") as fb:
                assert fa.read()[:HELD * 104] == fb.read()
            assert clone_plain.signed_length(b + ".sig") == HELD
            assert sealed[key] == (HELD, len(raw_b))
    assert len(sealed) == 3 * DOCS
    cache = {}
    for doc in range(DOCS):
        changes = one.job.doc_changes(doc, cache)
        prefix = one.job.prefix_changes(doc, cache)
        tails = one.job.tails(doc, cache)
        assert prefix == changes[:3 * HELD]
        assert sorted(map(len, tails)) == [BLOCKS - HELD] * 3
        whole = crdt_plain.replay(changes)
        for order in itertools.permutations(range(3)):
            got = catchup_plain.caught_up(prefix, tails, order)
            assert got["value"] == whole["value"]
            assert got["clock"] == whole["clock"]
        assert catchup_plain.held(changes, 3 * HELD)["clock"] == dict.fromkeys(
            one.keys[doc], HELD)


# -- B, whatever order the tails arrive in ------------------------------------

ORDERS = ("root_first", "root_last", "interleaved")


def deliveries(order, start, end):
    """[(feed, from, to)]: the extents of each feed's tail in arrival
    order. Feed 0 is the root actor's."""
    whole = [(f, start, end) for f in range(3)]
    if order == "root_first":
        return whole
    if order == "root_last":
        return whole[::-1]
    return [(f, n, n + 1) for n in range(start, end) for f in (1, 2, 0)]


@pytest.mark.parametrize("order", ORDERS)
def test_arrival_order_does_not_matter(stores, tmp_path, order):
    """B opens what it holds (every doc equal to the reference's replay
    of the prefix) and receives its feeds' signed tails in the given
    order, each verified before storage: every doc ends equal to
    `catchup_plain` and to the host OpSet, adopted WITH its history by
    the live engine, its subscription delivered the held state and then
    rising indexes up to every change."""
    s = stores
    repo = Repo(path=s.behind(tmp_path / "b"))
    try:
        c0 = counters()
        handles = repo.open_many(s.urls)
        repo.back.fetch_bulk_summaries()
        seen, wait = watch_all(handles)
        for doc, h in enumerate(handles):
            assert plain(h.value(timeout=WAIT_S)) == s.was(doc)["value"]
            assert repo.read(s.urls[doc], LEN_T, timeout=WAIT_S) == (
                s.was(doc)["len"])
        for doc in range(DOCS):
            feeds = [s.feed(k) for k in s.keys[doc]]
            for f, start, end in deliveries(order, HELD, BLOCKS):
                blocks, sigs = feeds[f]
                feed = repo.back.feeds.open_feed(s.keys[doc][f])
                assert feed.append_verified(
                    start, blocks[start:end], end, sigs[end])
        assert wait(), [x[-1:] for x in seen]
        cache = {}
        for doc, h in enumerate(handles):
            got = plain(h.value(timeout=WAIT_S))
            want = catchup_plain.caught_up(
                s.job.prefix_changes(doc, cache), s.job.tails(doc, cache),
                {"root_last": (2, 1, 0)}.get(order, (0, 1, 2)))
            assert got == want["value"] == s.ref(doc)["value"]
            assert got == s.opset_value(doc)
            assert repo.read(s.urls[doc], LEN_T, timeout=WAIT_S) == (
                want["len"])
            assert catchup_plain.delivery_fault(
                seen[doc], 3 * HELD, CHANGES) is None, seen[doc]
        moved = {k: v - c0.get(k, 0) for k, v in counters().items()}
        assert moved["live.adopted"] == moved["live.adopt_held"] == DOCS
        assert moved["live.refused"] == moved["live.demoted"] == 0
        assert moved["storage.feed.extended_sealed"] == 3 * DOCS
    finally:
        repo.close()


# -- behind by 0, 1, 8, 31 and 32 blocks of 32 --------------------------------


@pytest.mark.parametrize("behind", (0, 1, 8, 31, 32))
def test_behind_by(tmp_path, behind):
    """Feeds of 32 blocks (96 changes of 2 ops), B `behind` blocks short
    in every feed: nothing to fetch at 0, a clone at 32 (the writer
    then leaves B's directory with no feed and no row). Over TCP every
    doc converges, no block B held crosses again, every Request starts
    at the feed's own length, and B's disk ends equal to A's."""
    held = 32 - behind
    s = Stores(tmp_path / "repo", SEEDS[1], docs=2, held=held, per_change=2)
    try:
        c0 = counters()
        repo, handles, seen, wait = come_back(tmp_path / "b", s)
        try:
            assert wait(), [x[-1:] for x in seen]
            for doc, h in enumerate(handles):
                assert plain(h.value(timeout=WAIT_S)) == s.ref(doc)["value"]
                assert repo.read(s.urls[doc], LEN_T, timeout=WAIT_S) == (
                    s.ref(doc)["len"])
                if held:
                    assert catchup_plain.delivery_fault(
                        seen[doc], 3 * held, s.changes) is None, seen[doc]
            time.sleep(0.3)  # a frame that crossed for nothing is late
            moved = {k: v - c0.get(k, 0) for k, v in counters().items()}
        finally:
            repo.close()
        assert moved["net.repl.blocks_rx"] == 6 * behind
        assert moved["net.repl.blocks_dup_rx"] == 0
        assert moved["net.repl.requests_from_len"] == (
            6 if 0 < behind < 32 else 0)
        assert moved["storage.feed.extended_sealed"] == (
            6 if 0 < behind < 32 else 0)
        assert moved["live.refused"] == 0
        disk = catchup_plain.compare_stores(
            s.path + "/feeds", str(tmp_path / "b" / "feeds"),
            [k for keys in s.keys for k in keys])
        assert (disk["feeds"], disk["blocks"]) == (6, 6 * 32)
        assert (disk["short"], disk["differ"], disk["unsigned"]) == (0, 0, 0)
    finally:
        s.close()


# -- tails that land while the held docs open ---------------------------------


def test_the_swarm_joined_before_open_many(one, tmp_path):
    """B has its swarm and its peer BEFORE `open_many`: A's tails land
    wherever they fall in the open. Every doc converges to the
    reference, nothing crosses twice, and the directory B leaves equals
    A's."""
    c0 = counters()
    repo, handles, seen, wait = come_back(
        tmp_path / "b", one, before_open=True)
    try:
        assert wait(), [x[-1:] for x in seen]
        for doc, h in enumerate(handles):
            assert plain(h.value(timeout=WAIT_S)) == one.ref(doc)["value"]
            assert repo.read(one.urls[doc], LEN_T, timeout=WAIT_S) == (
                one.ref(doc)["len"])
            assert all(b > a for a, b in zip(seen[doc], seen[doc][1:]))
        moved = {k: v - c0.get(k, 0) for k, v in counters().items()}
    finally:
        repo.close()
    assert moved["net.repl.blocks_rx"] == 3 * DOCS * (BLOCKS - HELD)
    assert moved["net.repl.blocks_dup_rx"] == 0 == moved["live.refused"]
    disk = catchup_plain.compare_stores(
        one.path + "/feeds", str(tmp_path / "b" / "feeds"),
        [k for keys in one.keys for k in keys])
    assert (disk["short"], disk["differ"], disk["unsigned"]) == (0, 0, 0)


@pytest.mark.parametrize("when", ("after_io", "after_spec"))
def test_a_tail_that_lands_while_the_held_doc_opens(
    one, tmp_path, monkeypatch, when
):
    """The held-doc twin of PR 40's repair: every feed's tail is stored
    while `open_many` is between the io stage's sidecars and the docs'
    specs (`after_io`), or between a doc's spec and the load's barrier
    (`after_spec`). The docs open at whatever the spec saw, the rest is
    admitted by the sync at the load's end: every doc ends equal to the
    reference, live-managed, no change applied twice."""
    from hypermerge_tpu.backend import bulk_loader, repo_backend

    s = one
    repo = Repo(path=s.behind(tmp_path / "b"))
    feeds = [[s.feed(k) for k in keys] for keys in s.keys]
    landed = set()

    def land(doc):
        if doc in landed:
            return
        landed.add(doc)
        for f in (1, 2, 0):
            blocks, sigs = feeds[doc][f]
            feed = repo.back.feeds.open_feed(s.keys[doc][f])
            assert feed.append_verified(
                HELD, blocks[HELD:], BLOCKS, sigs[BLOCKS])

    try:
        if when == "after_io":
            opened = bulk_loader.BulkLoader._open_feeds

            def open_feeds(self, docs, cursor_map):
                opened(self, docs, cursor_map)
                for doc in range(DOCS):
                    land(doc)

            monkeypatch.setattr(
                bulk_loader.BulkLoader, "_open_feeds", open_feeds)
        else:
            spec_of = repo_backend.RepoBackend._doc_feed_spec
            ids = [k[0] for k in s.keys]

            def feed_spec(self, doc_id, contiguous, cursor=None):
                out = spec_of(self, doc_id, contiguous, cursor)
                land(ids.index(doc_id))
                return out

            monkeypatch.setattr(
                repo_backend.RepoBackend, "_doc_feed_spec", feed_spec)
        handles = repo.open_many(s.urls)
        repo.back.fetch_bulk_summaries()
        monkeypatch.undo()
        assert landed == set(range(DOCS))
        seen, wait = watch_all(handles)
        assert wait(), [x[-1:] for x in seen]
        for doc, h in enumerate(handles):
            assert plain(h.value(timeout=WAIT_S)) == s.ref(doc)["value"]
            assert repo.read(s.urls[doc], LEN_T, timeout=WAIT_S) == (
                s.ref(doc)["len"])
            assert all(b > a for a, b in zip(seen[doc], seen[doc][1:]))
        assert repo.back.live.stats["refused"] == 0
    finally:
        repo.close()
    again = Repo(path=str(tmp_path / "b"))
    try:
        for doc, h in enumerate(again.open_many(s.urls)):
            assert plain(h.value(timeout=WAIT_S)) == s.ref(doc)["value"]
    finally:
        again.close()


# -- the trust boundary on a sealed feed --------------------------------------


def test_a_tail_that_does_not_extend_the_sealed_chain_is_refused(
    one, tmp_path
):
    """A tail with a forged block, with another feed's signature, or
    that leaves a gap after what B holds, is refused whole: B's log,
    `.len` and `.sig` stay as the clean stop left them, and the true
    tail is accepted afterwards."""
    s = one
    repo = Repo(path=s.behind(tmp_path / "b"))
    try:
        repo.open_many(s.urls)
        key = s.keys[0][1]
        blocks, sigs = s.feed(key)
        _other, other_sigs = s.feed(s.keys[0][2])
        feed = repo.back.feeds.open_feed(key)
        path = clone_plain.feed_path(str(tmp_path / "b" / "feeds"), key)

        def on_disk():
            out = []
            for ext in ("", ".len", ".sig"):
                with open(path + ext, "rb") as fh:
                    out.append(fh.read())
            return out

        before = on_disk()
        tail = blocks[HELD:]
        forged = [tail[0][:-1] + bytes([tail[0][-1] ^ 1])] + tail[1:]
        c0 = counters()
        assert not feed.append_verified(HELD, forged, BLOCKS, sigs[BLOCKS])
        assert not feed.append_verified(
            HELD, tail, BLOCKS, other_sigs[BLOCKS])
        assert not feed.append_verified(
            HELD + 1, tail[1:], BLOCKS, sigs[BLOCKS])  # a gap
        assert feed.length == HELD and on_disk() == before
        assert counters().get("storage.feed.extended_sealed", 0) == c0.get(
            "storage.feed.extended_sealed", 0)
        assert feed.append_verified(HELD, tail, BLOCKS, sigs[BLOCKS])
        assert feed.length == BLOCKS
        assert counters()["storage.feed.extended_sealed"] == c0.get(
            "storage.feed.extended_sealed", 0) + 1
    finally:
        repo.close()
    a = clone_plain.feed_path(s.path + "/feeds", key)
    assert clone_plain.feed_blocks(path) == clone_plain.feed_blocks(a)
    assert clone_plain.signed_length(path + ".sig") == BLOCKS


# -- what B leaves, and a catch-up cut short ----------------------------------


def test_catch_up_over_tcp_and_reopen_without_a_swarm(one, tmp_path):
    """The round as the cell runs it: B shows what it holds with no
    swarm, catches up over TCP, reads again, closes; its disk equals
    A's, its clock rows are the reference's, and a fresh repo with no
    swarm reopens the same values through `open_many` +
    `fetch_bulk_summaries` with the snapshot of heads trusted again."""
    s = one
    repo = Repo(path=s.behind(tmp_path / "b"))
    try:
        handles = repo.open_many(s.urls)
        repo.back.fetch_bulk_summaries()
        stats = dict(repo.back.last_bulk_stats)
        assert stats["heads_snapshot_feeds"] == 3 * DOCS
        seen, wait = watch_all(handles)
        for doc, url in enumerate(s.urls):
            assert repo.read(url, LEN_T, timeout=WAIT_S) == s.was(doc)["len"]
        swarm = TcpSwarm()
        repo.set_swarm(swarm)
        swarm.connect(s.swarm.address)
        assert wait(), [x[-1:] for x in seen]
        for doc, url in enumerate(s.urls):
            assert repo.read(url, LEN_T, timeout=WAIT_S) == s.ref(doc)["len"]
            assert catchup_plain.delivery_fault(
                seen[doc], 3 * HELD, CHANGES) is None, seen[doc]
    finally:
        repo.close()
    b = str(tmp_path / "b")
    disk = catchup_plain.compare_stores(
        s.path + "/feeds", b + "/feeds", [k for ks in s.keys for k in ks])
    assert disk["feeds"] == 3 * DOCS and disk["blocks"] == DOCS * CHANGES
    assert (disk["short"], disk["differ"], disk["unsigned"]) == (0, 0, 0)
    clocks = catchup_plain.disk_clocks(b)
    for doc, keys in enumerate(s.keys):
        assert clocks[keys[0]] == s.ref(doc)["clock"]
    again = Repo(path=b)
    try:
        handles = again.open_many(s.urls)
        again.back.fetch_bulk_summaries()
        stats = dict(again.back.last_bulk_stats)
        assert stats["heads_snapshot_feeds"] == 3 * DOCS
        assert stats["heads_probed_feeds"] == 0
        for doc, h in enumerate(handles):
            assert plain(h.value(timeout=WAIT_S)) == s.ref(doc)["value"]
    finally:
        again.close()


def test_a_destroyed_swarms_accepter_ends():
    """`close()` alone leaves a thread that is inside `accept()` blocked
    there for good: destroy wakes it, and it has ended when destroy
    returns."""
    swarm = TcpSwarm()
    accepter = swarm._accepter
    assert accepter.is_alive()
    swarm.destroy()
    assert not accepter.is_alive()


def test_a_closed_peer_is_collected(one, tmp_path):
    """B catches up over TCP and closes: once its names are dropped, one
    collection frees its backend (documents, actors, the live engine's
    columns). A thread that outlives the close keeps all of it, round
    after round, for every later collection to walk."""
    import gc
    import weakref

    repo, handles, seen, wait = come_back(tmp_path / "b", one)
    assert wait(), [x[-1:] for x in seen]
    back = weakref.ref(repo.back)
    for h in handles:
        h.close()
    repo.close()
    del repo, handles, h, seen, wait
    gc.collect()
    assert back() is None


def test_b_stopped_in_mid_catch_up_asks_from_its_new_lengths(one, tmp_path):
    """B receives half of every tail (as a link that dropped would
    leave it), stops cleanly, and starts again: it opens at its new
    lengths, asks every feed from there, receives only what it still
    lacks, and converges."""
    s = one
    half = HELD + (BLOCKS - HELD) // 2
    b = s.behind(tmp_path / "b")
    repo = Repo(path=b)
    try:
        handles = repo.open_many(s.urls)
        seen, wait = watch_all(handles, 3 * half)
        for doc in range(DOCS):
            for f in range(3):
                blocks, sigs = s.feed(s.keys[doc][f])
                feed = repo.back.feeds.open_feed(s.keys[doc][f])
                assert feed.append_verified(
                    HELD, blocks[HELD:half], half, sigs[half])
        assert wait(), [x[-1:] for x in seen]
    finally:
        repo.close()
    for keys in s.keys:
        for key in keys:
            path = clone_plain.feed_path(b + "/feeds", key)
            assert len(clone_plain.feed_blocks(path)) == half
            assert clone_plain.signed_length(path + ".sig") == half
    c0 = counters()
    repo = Repo(path=b)
    try:
        handles = repo.open_many(s.urls)
        repo.back.fetch_bulk_summaries()
        seen, wait = watch_all(handles)
        swarm = TcpSwarm()
        repo.set_swarm(swarm)
        swarm.connect(s.swarm.address)
        assert wait(), [x[-1:] for x in seen]
        for doc, h in enumerate(handles):
            assert plain(h.value(timeout=WAIT_S)) == s.ref(doc)["value"]
            assert seen[doc][0] == 3 * half
        moved = {k: v - c0.get(k, 0) for k, v in counters().items()}
    finally:
        repo.close()
    assert moved["net.repl.blocks_rx"] == 3 * DOCS * (BLOCKS - half)
    assert moved["net.repl.requests_from_len"] == 3 * DOCS
    assert moved["net.repl.blocks_dup_rx"] == 0


def test_a_subscriber_is_delivered_a_lazy_docs_state_once(one, tmp_path):
    """`Handle.subscribe` on a bulk-opened doc: the poke resolves the
    doc and its state is delivered ONCE (on the parent the subscriber
    got it twice: from the push and from `subscribe` itself); a handle
    that already has its state delivers it at once, as before."""
    repo = Repo(path=one.behind(tmp_path / "b"))
    try:
        handles = repo.open_many(one.urls)
        seen, _wait = watch_all(handles)
        deadline = time.monotonic() + WAIT_S
        while not all(seen) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert seen == [[3 * HELD]] * DOCS
        late = []
        other = repo.open(one.urls[0])
        assert other.value(timeout=WAIT_S) is not None
        other.subscribe(lambda _v, index: late.append(index))
        assert late == [3 * HELD]
    finally:
        repo.close()


def test_a_push_that_races_subscribe_is_delivered_once():
    """A push from another thread that lands right after `subscribe`
    made `fn` the subscriber: it finds `fn` itself, and `subscribe`
    does not hand the same state over again (it did, where `subscribe`
    read what the handle had AFTER publishing `fn`); a push that lands
    before is what `subscribe` owes. Neither is lost."""
    from hypermerge_tpu.frontend.handle import Handle

    pushes = []

    class Racing(Handle):
        def __setattr__(self, name, value):
            super().__setattr__(name, value)
            if name == "value_fn" and value is not None:
                t = threading.Thread(target=self.push, args=("late", 7))
                t.start()
                t.join(0.3)  # held off until `subscribe`'s step is over
                pushes.append(t)

    df = SimpleNamespace(doc_id="d" * 8, url="hypermerge:/d", poke=lambda: 0)
    got = []
    h = Racing(df)
    h.subscribe(lambda state, index: got.append((state, index)))
    pushes[0].join(WAIT_S)
    assert got == [("late", 7)]
    got.clear()
    h = Handle(df)
    h.push("early", 3)
    h.subscribe(lambda state, index: got.append((state, index)))
    assert got == [("early", 3)]


# -- the disk check on hand-damaged copies ------------------------------------


def test_compare_stores_sees_each_damage(one, tmp_path):
    keys = list(one.keys[0])
    src = one.path + "/feeds"
    root = str(tmp_path / "feeds")
    shutil.copytree(src, root)
    same = catchup_plain.compare_stores(src, root, keys)
    assert (same["feeds"], same["blocks"]) == (3, 3 * BLOCKS)
    assert (same["short"], same["differ"], same["unsigned"]) == (0, 0, 0)
    # a peer's chain: ONE record over the blocks held, one over the tail
    sig = clone_plain.feed_path(root, keys[0]) + ".sig"
    with open(sig, "rb") as fh:
        chain = fh.read()
    with open(sig, "wb") as fh:
        fh.write(chain[(HELD - 1) * 104:HELD * 104] + chain[-104:])
    ok = catchup_plain.compare_stores(src, root, keys)
    assert (ok["short"], ok["differ"], ok["unsigned"]) == (0, 0, 0)
    # a record that is not A's; a stale `.len`; a flipped byte in a
    # block held before the catch-up; a block torn off
    with open(sig, "r+b") as fh:
        fh.seek(50)
        fh.write(b"\x00\x01")
    bad = catchup_plain.compare_stores(src, root, keys)
    assert (bad["short"], bad["differ"], bad["unsigned"]) == (0, 1, 0)
    with open(clone_plain.feed_path(root, keys[1]) + ".len", "r+b") as fh:
        fh.write((HELD).to_bytes(8, "little"))
    log = clone_plain.feed_path(root, keys[2])
    with open(log, "r+b") as fh:
        fh.seek(10)
        byte = fh.read(1)
        fh.seek(10)
        fh.write(bytes([byte[0] ^ 1]))
    bad = catchup_plain.compare_stores(src, root, keys)
    assert (bad["short"], bad["differ"], bad["unsigned"]) == (0, 3, 0)
    with open(log, "r+b") as fh:
        fh.truncate(os.path.getsize(log) - 1)
    bad = catchup_plain.compare_stores(src, root, keys)
    assert (bad["short"], bad["differ"], bad["unsigned"]) == (1, 2, 1)


@pytest.mark.parametrize("indexes,fault", (
    ((36, 39, 48), None),
    ((36, 48), None),
    ((), "nothing"),
    ((39, 48), "first"),
    ((36, 36, 48), "twice"),
    ((36, 42, 40, 48), "twice"),
    ((36, 47), "last"),
))
def test_delivery_fault(indexes, fault):
    got = catchup_plain.delivery_fault(list(indexes), 36, 48)
    assert (got is None) if fault is None else (fault in got)


# -- the parent's clean failure ------------------------------------------------


def test_a_program_without_the_counters_fails_at_once(tmp_path, monkeypatch):
    """`blocks_refetched` is decided by a counter this PR adds: on a
    program that lacks it (the parent) set-up exits 5 at its start,
    before the stores are waited for, and leaves nothing open."""
    from benchmark.drivers import catchup_rounds

    job = mwrb.CorpusJob(str(tmp_path / "repo"), spec(2), SEEDS[2], 2).start()
    assert "net.repl.blocks_dup_rx" in catchup_rounds.DECIDED_BY
    monkeypatch.setattr(catchup_rounds, "DECIDED_BY",
                        catchup_rounds.DECIDED_BY + ("net.repl.no_such",))
    cell = SimpleNamespace(
        work=str(tmp_path), seed=SEEDS[2], notes={}, name="sync.catchup",
        config={"corpus": spec(2)}, mix={"verify_sample_docs": 2},
        counters=lambda: harness.Cell.counters(None))
    try:
        with pytest.raises(SystemExit) as failed:
            catchup_rounds.setup(cell, job)
        assert failed.value.code == 5 and cell.notes == {}
    finally:
        job.finish()
    Repo(path=str(tmp_path / "repo")).close()  # nothing holds A's lock


# -- the new spans and counters -----------------------------------------------

SPANS = {"live.adopt": ("outcome", "rows", "held"),
         "frontend.remote_patch": ("diffs",)}
COUNTERS = ("live.adopt_held", "net.repl.requests_from_len",
            "storage.feed.extended_sealed", "frontend.remote_patches")


def by_name():
    events = {}
    for ev in telemetry.trace_events():
        if ev[0] == "X":
            events.setdefault(ev[1], []).append(ev)
    return events


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One catch-up over TCP under the ring tracer, as the cell runs it
    (both reads), then a frame A sends again: (events by name, counter
    deltas, what the duplicate moved)."""
    s = Stores(tmp_path_factory.mktemp("ta") / "repo", SEEDS[0])
    telemetry.enable_tracing()
    telemetry.reset_trace()
    c0 = counters()
    repo = Repo(path=s.behind(tmp_path_factory.mktemp("tb") / "b"))
    try:
        handles = repo.open_many(s.urls)
        repo.back.fetch_bulk_summaries()
        seen, wait = watch_all(handles)
        for url in s.urls:
            repo.read(url, LEN_T, timeout=WAIT_S)
        swarm = TcpSwarm()
        repo.set_swarm(swarm)
        swarm.connect(s.swarm.address)
        assert wait()
        repo.back.live.flush_now()
        for url in s.urls:
            repo.read(url, LEN_T, timeout=WAIT_S)
        c1 = counters()
        events = by_name()
        # a frame B already holds arrives again (a retransmission)
        rm = repo.back.network.replication
        key = s.keys[0][1]
        feed = repo.back.feeds.open_feed(key)
        blocks, sigs = s.feed(key)
        import base64

        rm._on_blocks(
            type("Peer", (), {"id": "peer-a"})(), feed.discovery_id, HELD,
            [base64.b64encode(b).decode() for b in blocks[HELD:]], BLOCKS,
            base64.b64encode(sigs[BLOCKS]).decode(), BLOCKS)
        c2 = counters()
    finally:
        telemetry.disable_tracing()
        telemetry.reset_trace()
        repo.close()
        s.close()
    moved = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
    dup = {k: c2.get(k, 0) - c1.get(k, 0) for k in c2}
    return events, moved, dup


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_is_recorded_with_its_tags(traced, name):
    events, _moved, _dup = traced
    assert events.get(name), f"no {name} span in the ring"
    for ev in events[name]:
        assert set(SPANS[name]) <= set(ev[6] or {}), (name, ev[6])


def test_spans_add_up_to_the_counters(traced):
    events, moved, dup = traced
    adopts = events["live.adopt"]
    assert len(adopts) == DOCS == moved["live.adopted"]
    assert all(e[6]["held"] == 1 and e[6]["rows"] >= HELD * 3 * PER_CHANGE
               and e[6]["outcome"] == "ok" for e in adopts)
    assert moved["live.adopt_held"] == DOCS
    assert len(events["frontend.remote_patch"]) == moved[
        "frontend.remote_patches"] >= DOCS
    assert moved["net.repl.requests_from_len"] == 3 * DOCS
    assert moved["net.repl.blocks_rx"] == 3 * DOCS * (BLOCKS - HELD)
    assert moved["net.repl.blocks_dup_rx"] == 0
    assert moved["storage.feed.extended_sealed"] == 3 * DOCS
    assert moved["storage.feed.log_writes"] == 3 * DOCS  # one a frame
    # the one series of released entries (B wrote nothing: all remote)
    assert moved["serve.advance_refusals"] == DOCS == moved[
        "serve.reinstalls"]
    assert moved["serve.cold_reads"] == 2 * DOCS
    assert moved["live.inc_changes"] == moved["live.tick_changes"] > 0
    # the frame that came again: every block of it a duplicate, no
    # block stored, no feed extended
    assert dup["net.repl.blocks_dup_rx"] == BLOCKS - HELD
    assert dup["net.repl.blocks_rx"] == dup["storage.feed.log_writes"] == 0


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_costs_nothing_with_no_sink(name):
    assert not telemetry.tracing_enabled()
    assert telemetry.span(name) is telemetry.NOOP
    assert telemetry.begin(name) is telemetry.NOOP


@pytest.mark.parametrize("name", COUNTERS)
def test_counter_moves_in_a_catch_up(traced, name):
    _events, moved, _dup = traced
    assert moved[name] > 0


# -- each new metric file on a hand-worked obs --------------------------------

HAND_OBS = {
    "open_s": 2.5, "first_read_s": 0.75, "close_s": 1.25,
    "inc_ops_pct": 100.0, "adopt_round_s": 4.5,
    "blocks_rx_per_behind": 1.0, "stale_reinstall_pct": 100.0,
    "counters_before": {"frontend.remote_patches": 100,
                        "live.adopted": 256},
    "counters_after": {"frontend.remote_patches": 2148,
                       "live.adopted": 768},
}
HAND_WORKED = {
    "sync.open_s": 2.5, "sync.first_read_s": 0.75, "sync.close_s": 1.25,
    "sync.inc_ops_pct": 100.0, "sync.adopt_round_s": 4.5,
    "sync.blocks_rx_per_behind": 1.0, "sync.stale_reinstall_pct": 100.0,
    "sync.patches_per_doc": 4.0,  # 2,048 patches for 512 docs
}
JOINED = (
    "ops_per_s", "host.gc_s.sync", "storage.blocks_per_log_write",
    "storage.blocks_per_log_read", "serve.batch_s", "serve.dispatch_s",
    "serve.reads_per_batch", "serve.host_answers", "serve.install_s",
    "serve.install_docs_per_group", "serve.install_kernel_s_per_kop",
    "serve.install_roofline", "serve.reinstall_s",
    "serve.reinstall_docs_per_group", "serve.cold_read_pct",
    "device.idle_pct.read", "compile.in_window.open",
    "compile.misses_setup",
    # B's bulk open of every round by the loader's own stats, and the
    # tier's p99 (REVIEW, PR 44): counters, read over the whole window
    "loader.heads_snapshot_pct", "loader.cols_bulk_pct",
    "pack.general_docs_pct", "pack.general_native_pct",
    "loader.slab_waste_x", "loader.slab_programs", "serve.read_p99_ms",
)


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("metric", sorted(HAND_WORKED))
def test_metric_file_on_a_hand_worked_obs(metric):
    spec_ = harness.load_json("layer_metrics", metric + ".json")
    entry = next(m for m in bench_json()["per_layer"] if m["name"] == metric)
    assert spec_["cells"] == entry["workloads"] == ["sync.catchup"]
    assert (spec_["moves"], entry["moves"]) == ("ops_per_s", "ops_per_s")
    assert (spec_["layer"], spec_["unit"], spec_["better"], spec_["source"]
            ) == (entry["layer"], entry["unit"], entry["better"],
                  entry["source"])
    assert spec_["reader"] in ("obs_value", "counter_ratio")
    reader = harness.load_module("readers", spec_["reader"])
    got = reader.read(spec_.get("params") or {}, dict(HAND_OBS))
    assert got == pytest.approx(HAND_WORKED[metric])
    # the parent's program: no such counters, no driver numbers
    bare = {"counters_before": {}, "counters_after": {"serve.reads": 3}}
    assert reader.read(spec_.get("params") or {}, bare) is None


def test_the_cell_in_the_benchmark():
    bench = bench_json()
    cell = next(w for w in bench["workloads"] if w["name"] == "sync.catchup")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "catchup-1k3a", "catchup-rounds-tcp", 1)
    cfg = next(c for c in bench["configs"] if c["name"] == "catchup-1k3a")
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        config = json.load(fh)
    assert config["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert config["reduced"] == cfg["reduced"]
    assert not any(k.startswith("HM_") for k in config["env"])
    corpus = config["corpus"]
    with open(os.path.join(ROOT, "benchmark/configs/clone-1k3a.json")) as fh:
        clone = json.load(fh)["corpus"]
    for k in ("ops", "ops_per_change", "seq_frac", "del_frac", "n_keys",
              "seq_key", "distinct", "sign"):
        assert corpus[k] == clone[k], k  # the shapes of clone-1k3a
    assert [c["writers"] for c in corpus["classes"]] == [3]
    assert (corpus["blocks_held"], config["blocks_behind"]) == (24, 8)
    listed = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    names = [n for n, m in listed.items()
             if "sync.catchup" in m.get("workloads", ())]
    for name in JOINED + tuple(HAND_WORKED):
        assert name in names, name
    for name, m in listed.items():
        if name.startswith("sync."):
            assert "sync.catchup" in m["workloads"], name
    for name in names:  # appended at the end of a list that was there
        if name not in HAND_WORKED:
            assert listed[name]["workloads"][-1] == "sync.catchup"
        if name != "ops_per_s":
            spec_ = harness.load_json("layer_metrics", name + ".json")
            harness.load_module("readers", spec_["reader"])
    assert len(bench["configs"]) == 7 and len(bench["workloads"]) == 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
