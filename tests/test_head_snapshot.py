"""The head snapshot (storage/feed.py HeadSnapshot, `feeds/heads.snap`).

A clean close seals the head (block count, log end) of every feed the
session changed or learned; a bulk cold open answers its feeds' heads
from that one file and probes (`.len` + `stat`) only what the snapshot
cannot vouch for. It must be a pure change of HOW: summaries equal an
open without it; a dirty store never reads one; a failed or crashed
write of it leaves the old valid file or none, beside the crash
marker; every mutator of a log's length shows in the next one; a
session that only reads writes nothing.

Counts and states only; no clock is asserted.
"""

import errno
import os
import shutil
import struct
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from hypermerge_tpu.ops.corpus import make_corpus  # noqa: E402
from hypermerge_tpu.repo import Repo  # noqa: E402
from hypermerge_tpu.storage import faults as F  # noqa: E402
from hypermerge_tpu.storage import feed as feedmod  # noqa: E402
from hypermerge_tpu.storage.feed import (  # noqa: E402
    FeedStore,
    FileFeedStorage,
    HeadSnapshot,
    file_storage_fn,
)
from hypermerge_tpu.utils import keys as keymod  # noqa: E402
from hypermerge_tpu.utils.ids import (  # noqa: E402
    root_actor_id,
    validate_doc_url,
)

SHAPES = ["single_writer", "three_feeds"]


def _corpus(path, shape, docs=6):
    """A store as the corpus writers leave it: logs, `.len`, `.sig`,
    one cols.slab, sqlite rows; no snapshot. Returns (urls, feeds)."""
    if shape == "single_writer":
        return make_corpus(str(path), docs, 64), docs
    from benchmark.corpora import multi_writer_rounds as mwr

    corpus = {
        "sign": True, "ops": 64, "ops_per_change": 16, "seq_frac": 0.85,
        "del_frac": 0.1, "n_keys": 10, "seq_key": "t", "distinct": 2,
        "classes": [{"writers": 3, "count": docs}],
    }
    job = mwr.CorpusJob(str(path), corpus, 2147483659, 1)
    try:
        urls = job.start().finish()
    except BaseException:
        job.abort()
        raise
    return urls, 3 * docs


def _snap_path(path):
    return os.path.join(str(path), "feeds", HeadSnapshot.NAME)


def _probe(path):
    """name -> (count, end) as 10,240 probes would answer: every
    non-empty feed's `.len`, which must agree with its log's size."""
    out = {}
    root = os.path.join(str(path), "feeds")
    for d, _dirs, files in os.walk(root):
        for f in files:
            if not f.endswith(".len"):
                continue
            with open(os.path.join(d, f), "rb") as fh:
                count, end = struct.unpack("<QQ", fh.read(16))
            assert os.stat(os.path.join(d, f[:-4])).st_size == end, f
            if count:
                out[f[:-4]] = (count, end)
    return out


def _sealed(path):
    """The snapshot on disk as a next session would trust it, or None."""
    snap = HeadSnapshot(os.path.join(str(path), "feeds"))
    return snap.sealed() if snap.status() == "found" else None


def _open(path, urls):
    repo = Repo(path=str(path))
    handles = repo.open_many(urls)
    summ = repo.back.fetch_bulk_summaries()
    return repo, handles, summ, dict(repo.back.last_bulk_stats)


def _summaries(summ, urls):
    return [summ.doc(validate_doc_url(u)) for u in urls]


def _edit(repo, url, key, value):
    repo.change(url, lambda d: d.__setitem__(key, value))
    if repo.back.live is not None:
        repo.back.live.flush_now()
    repo.back._stores.flush_now()
    repo.back._cache_syncs.flush_now()


def _tree(path):
    """(relative name, size, mtime) of everything under feeds/."""
    out = []
    root = os.path.join(str(path), "feeds")
    for d, _dirs, files in os.walk(root):
        for f in files:
            st = os.stat(os.path.join(d, f))
            out.append((os.path.relpath(os.path.join(d, f), root),
                        st.st_size, st.st_mtime_ns))
    return sorted(out)


# ---------------------------------------------------------------------------
# (1) the write path seals it; no open has to build it


def test_write_path_store_holds_snapshot_equal_to_probes(tmp_path):
    repo = Repo(path=str(tmp_path))
    urls = [repo.create({"n": i}) for i in range(4)]
    for i, u in enumerate(urls):
        _edit(repo, u, "m", i)
    repo.close()
    assert not os.path.exists(os.path.join(str(tmp_path), "repo.dirty"))
    sealed = _sealed(tmp_path)
    assert sealed is not None and sealed == _probe(tmp_path)
    for u in urls:
        assert root_actor_id(validate_doc_url(u)) in sealed
    # and the very next bulk open is answered by it, whole
    repo, _h, _s, stats = _open(tmp_path, urls)
    assert stats["heads_snapshot_feeds"] == len(urls)
    assert stats["heads_probed_feeds"] == 0
    assert stats["heads_snapshot_pct"] == 100.0
    repo.close()


# ---------------------------------------------------------------------------
# (2) (3) a corpus writer's store: probe all, seal, then ask the snapshot


@pytest.mark.parametrize("shape", SHAPES)
def test_first_open_probes_and_seals_second_asks_snapshot(tmp_path, shape):
    urls, n_feeds = _corpus(tmp_path, shape)
    assert not os.path.exists(_snap_path(tmp_path))
    repo, _h, _s, stats = _open(tmp_path, urls)
    assert stats["heads_snapshot_feeds"] == 0
    assert stats["heads_probed_feeds"] == n_feeds
    assert stats["heads_snapshot_pct"] == 0.0
    repo.close()
    assert _sealed(tmp_path) == _probe(tmp_path)
    assert len(_sealed(tmp_path)) == n_feeds
    repo, _h, _s, stats = _open(tmp_path, urls)
    assert stats["heads_snapshot_feeds"] == n_feeds
    assert stats["heads_probed_feeds"] == 0
    assert stats["heads_snapshot_pct"] == 100.0
    assert stats["cols_bulk_pct"] == 100.0
    repo.close()


@pytest.mark.parametrize("shape", SHAPES)
def test_snapshot_open_touches_no_len_and_equals_probed_open(
    tmp_path, shape, monkeypatch
):
    urls, n_feeds = _corpus(tmp_path / "a", shape)
    repo, _h, _s, _stats = _open(tmp_path / "a", urls)
    repo.close()  # seals
    shutil.copytree(tmp_path / "a", tmp_path / "b")
    os.remove(_snap_path(tmp_path / "b"))

    probes = []
    shortcut = FileFeedStorage._try_count_shortcut

    def counted(self):
        probes.append(self.path)
        return shortcut(self)

    monkeypatch.setattr(FileFeedStorage, "_try_count_shortcut", counted)
    repo_a, handles_a, summ_a, stats_a = _open(tmp_path / "a", urls)
    names = {root_actor_id(validate_doc_url(u)) for u in urls}
    assert stats_a["heads_snapshot_pct"] == 100.0
    # not one `.len` of the corpus was opened (the repo's own ledger
    # feed is a single-feed open and keeps its probe)
    corpus_feeds = set(_sealed(tmp_path / "a"))
    assert names <= corpus_feeds and len(corpus_feeds) == n_feeds
    assert [p for p in probes if os.path.basename(p) in corpus_feeds] == []
    probes.clear()
    repo_b, handles_b, summ_b, stats_b = _open(tmp_path / "b", urls)
    assert stats_b["heads_snapshot_pct"] == 0.0
    assert len(
        [p for p in probes if os.path.basename(p) in corpus_feeds]
    ) == n_feeds
    assert _summaries(summ_a, urls) == _summaries(summ_b, urls)
    assert stats_a["fast"] == stats_b["fast"] == len(urls)
    assert stats_a["fallback"] == stats_b["fallback"] == 0
    for ha, hb in zip(handles_a, handles_b):
        assert ha.value(timeout=60) == hb.value(timeout=60)
    repo_a.close()
    repo_b.close()


# ---------------------------------------------------------------------------
# (4) (9) a dirty store never reads one


def _crashed_copy(tmp_path):
    """A store closed cleanly (snapshot sealed), then a session that
    appended a block the snapshot does not hold and was killed: the
    copy holds the marker, the block, and the OLD snapshot."""
    live = tmp_path / "live"
    urls = make_corpus(str(live), 4, 64)
    repo, _h, _s, _stats = _open(live, urls)
    repo.close()
    before = _sealed(live)
    repo, _h, _s, _stats = _open(live, urls)
    _edit(repo, urls[0], "after", "crash")
    repo.back.durability.flush_now()
    shutil.copytree(live, tmp_path / "crashed")
    repo.close()
    crashed = tmp_path / "crashed"
    assert os.path.exists(os.path.join(str(crashed), "repo.dirty"))
    assert _sealed(crashed) == before  # valid, and stale: the edit
    # went into a feed of the session's own writer, which it lacks
    (new,) = set(_probe(crashed)) - set(before)
    return crashed, urls, new


def test_dirty_open_discards_then_clean_close_reseals(tmp_path):
    crashed, urls, new = _crashed_copy(tmp_path)
    repo = Repo(path=str(crashed))
    # gone before any doc opens, whatever recovery found
    assert not os.path.exists(_snap_path(crashed))
    assert repo.back.recovery_report is not None
    assert repo.back.feeds.heads.status() == "discarded"
    handles = repo.open_many(urls)
    repo.back.fetch_bulk_summaries()
    stats = repo.back.last_bulk_stats
    assert stats["heads_snapshot_feeds"] == 0
    # (recovery already sized the crashed writer's feed when it sealed
    # its unsigned tail: that one counts in neither)
    assert stats["heads_probed_feeds"] >= len(urls)
    assert handles[0].value(timeout=60)["after"] == "crash"
    repo.close()
    sealed = _sealed(crashed)
    assert sealed == _probe(crashed) and sealed[new][0] >= 1
    repo, handles, _s, stats = _open(crashed, urls)
    assert stats["heads_snapshot_feeds"] == len(urls) + 1
    assert stats["heads_snapshot_pct"] == 100.0
    assert handles[0].value(timeout=60)["after"] == "crash"
    repo.close()


def test_recovery_skipped_trusts_no_snapshot(tmp_path, monkeypatch):
    crashed, urls, _new = _crashed_copy(tmp_path)
    monkeypatch.setenv("HM_RECOVER", "0")
    repo = Repo(path=str(crashed))
    assert repo.back.recovery_report is None  # skipped
    assert not os.path.exists(_snap_path(crashed))
    assert repo.back.feeds.heads.status() == "discarded"
    handles = repo.open_many(urls)
    repo.back.fetch_bulk_summaries()
    stats = repo.back.last_bulk_stats
    assert stats["heads_snapshot_feeds"] == 0
    assert stats["heads_probed_feeds"] == len(urls) + 1
    assert handles[0].value(timeout=60)["after"] == "crash"
    repo.close()
    # a session that never recovered vouches for nothing at its close
    assert not os.path.exists(_snap_path(crashed))


# ---------------------------------------------------------------------------
# (5) a crash or a disk fault inside the snapshot's own write


def test_crash_at_every_point_of_the_seal(tmp_path, monkeypatch):
    """Kill -9 at every boundary from the first byte of the snapshot's
    write to the end of the close: while the marker stands the file is
    the old valid one, the new valid one or none (never torn, and never
    read: the reopen discards it); once the marker is gone it is the
    new one, equal to the probes."""
    monkeypatch.setenv("HM_FSYNC", "1")
    work = tmp_path / "work"
    repo = Repo(path=str(work))
    url = repo.create({"n": 0})
    _edit(repo, url, "n", 1)
    repo.close()
    old = _sealed(work)
    assert old == _probe(work)
    shutil.copytree(work, tmp_path / "base")
    rec = F.CrashRecorder(str(work))
    with F.activate(recorder=rec):
        repo = Repo(path=str(work))
        _edit(repo, url, "n", 2)
        repo.close()
    new = _sealed(work)
    assert new == _probe(work) and new != old
    rel = os.path.join("feeds", HeadSnapshot.NAME)
    touching = [
        i for i, ev in enumerate(rec.events)
        if rel in ev[1:3] or rel + ".tmp" in ev[1:3]
    ]
    kinds = [rec.events[i][0] for i in touching]
    assert F.FSYNC in kinds and kinds[-1] == F.REPLACE, kinds
    seen = set()
    for k in range(touching[0], rec.n_points):
        dst = tmp_path / f"k{k}"
        rec.materialize(str(dst), k, base=str(tmp_path / "base"))
        marker = os.path.exists(os.path.join(str(dst), "repo.dirty"))
        got = _sealed(dst)
        if not marker:
            assert got == new, k
            seen.add("clean")
            continue
        assert got in (old, new), (k, got)
        seen.add("old" if got == old else "new")
        r2 = Repo(path=str(dst))
        assert r2.back.recovery_report is not None, k
        assert r2.back.feeds.heads.status() == "discarded", k
        assert not os.path.exists(_snap_path(dst)), k
        assert (r2.doc(url) or {}).get("n") == 2, k
        r2.close()
        # what that session sealed, if it touched a feed, is true
        again, probes = _sealed(dst) or {}, _probe(dst)
        assert all(probes[n] == e for n, e in again.items()), k
    assert seen == {"old", "new", "clean"}


@pytest.mark.parametrize("fault", ["write", "fsync", "rename"])
def test_failed_seal_leaves_no_snapshot_and_a_clean_close(
    tmp_path, monkeypatch, fault
):
    monkeypatch.setenv("HM_FSYNC", "1")
    urls = make_corpus(str(tmp_path), 4, 64)
    repo, _h, _s, _stats = _open(tmp_path, urls)
    repo.close()
    assert _sealed(tmp_path) is not None
    repo, _h, _s, _stats = _open(tmp_path, urls)
    _edit(repo, urls[1], "k", "v")
    plan = None
    if fault == "rename":
        def refuse(src, dst):
            raise OSError(errno.ENOSPC, os.strerror(errno.ENOSPC), dst)

        monkeypatch.setattr(feedmod, "io_replace", refuse)
    else:
        plan = F.DiskFaultPlan(
            seed=7, errnos=(errno.ENOSPC,),
            path_filter=HeadSnapshot.NAME,
            **{fault + "_error_p": 1.0},
        )
    if plan is not None:
        with F.activate(plan=plan):
            repo.close()
        assert sum(plan.stats.values()) >= 1, plan.stats
    else:
        repo.close()
    # never a stale one, and no leftover tmp; the close stayed clean
    assert not os.path.exists(_snap_path(tmp_path))
    assert not os.path.exists(_snap_path(tmp_path) + ".tmp")
    assert not os.path.exists(os.path.join(str(tmp_path), "repo.dirty"))
    monkeypatch.undo()
    repo, handles, _s, stats = _open(tmp_path, urls)
    assert repo.back.recovery_report is None
    assert stats["heads_snapshot_feeds"] == 0  # the next open probes
    assert handles[1].value(timeout=60)["k"] == "v"
    repo.close()
    assert _sealed(tmp_path) == _probe(tmp_path)


# ---------------------------------------------------------------------------
# (6) a file that does not parse whole is ignored, probed, rewritten


def _damage(raw, how):
    if how == "torn":
        return raw[: len(raw) - 17]
    if how == "short":
        return raw[:11]
    if how == "bad_checksum":
        flipped = bytearray(raw)
        flipped[-3] ^= 0x40  # a log end: the entries no longer sum
        return bytes(flipped)
    assert how == "wrong_version"
    hdr = HeadSnapshot._HDR
    magic, version, n, crc = hdr.unpack_from(raw, 0)
    return hdr.pack(magic, version + 1, n, crc) + raw[hdr.size:]


@pytest.mark.parametrize(
    "how", ["torn", "short", "bad_checksum", "wrong_version"]
)
def test_unparsable_snapshot_is_ignored_probed_rewritten(tmp_path, how):
    urls = make_corpus(str(tmp_path), 5, 64)
    repo, _h, summ, _stats = _open(tmp_path, urls)
    want = _summaries(summ, urls)
    repo.close()
    good = _sealed(tmp_path)
    with open(_snap_path(tmp_path), "rb") as fh:
        raw = fh.read()
    with open(_snap_path(tmp_path), "wb") as fh:
        fh.write(_damage(raw, how))
    assert _sealed(tmp_path) is None
    repo, _h, summ, stats = _open(tmp_path, urls)
    assert repo.back.feeds.heads.status() == "discarded"
    assert stats["heads_snapshot_feeds"] == 0
    assert stats["heads_probed_feeds"] == len(urls)
    assert _summaries(summ, urls) == want
    repo.close()
    assert _sealed(tmp_path) == good
    repo, _h, _s, stats = _open(tmp_path, urls)
    assert stats["heads_snapshot_pct"] == 100.0
    repo.close()


# ---------------------------------------------------------------------------
# (7) every mutator of a log's length shows in the next snapshot


def _store(root):
    return FeedStore(file_storage_fn(str(root)))


def _sealed_root(root):
    snap = HeadSnapshot(str(root))
    return snap.sealed() if snap.status() == "found" else None


@pytest.fixture
def two_feeds(tmp_path):
    """A feeds root holding feeds a (5 blocks) and b (3), sealed."""
    root = tmp_path / "feeds"
    pairs = [keymod.create(bytes([i + 1]) * 32) for i in range(2)]
    store = _store(root)
    for pair, n in zip(pairs, (5, 3)):
        feed = store.create(pair)
        for i in range(n):
            feed.append(b"block-%d" % i)
    store.close()
    assert store.heads.seal()
    a, b = (p.public_key for p in pairs)
    sealed = _sealed_root(root)
    assert {k: v[0] for k, v in sealed.items()} == {a: 5, b: 3}
    return root, pairs, sealed


def _mutate(store, pairs, how):
    a = pairs[0].public_key
    if how == "append":
        store.create(pairs[0]).append(b"one more")
        return 6
    if how == "truncate_to":
        assert store.open_feed(a)._storage.truncate_to(2) == 3
        return 2
    if how == "repair":
        st = store.open_feed(a)._storage
        with open(st.path, "ab") as fh:
            fh.write(struct.pack("<I", 1000) + b"torn")  # a torn tail
        assert st.repair(write=True)["bytes_truncated"] == 8
        return 5
    if how == "destroy":
        store.open_feed(a).destroy()
        return None
    assert how == "remove"  # a feed never opened this session
    store.remove(a)
    return None


@pytest.mark.parametrize(
    "how", ["append", "truncate_to", "repair", "destroy", "remove"]
)
def test_mutators_show_in_the_next_snapshot(two_feeds, how):
    root, pairs, sealed = two_feeds
    a, b = (p.public_key for p in pairs)
    store = _store(root)
    want = _mutate(store, pairs, how)
    # told: the sealed entry no longer answers for the feed
    fresh = FileFeedStorage(
        os.path.join(str(root), a[:2], a), heads=store.heads
    )
    assert store.heads.resolve(fresh) is False
    store.close()
    if how == "repair":
        # nothing changed (the tail was torn before and after): the
        # snapshot is as it was, and is not rewritten
        before = os.stat(os.path.join(str(root), HeadSnapshot.NAME))
        assert store.heads.seal()
        after = os.stat(os.path.join(str(root), HeadSnapshot.NAME))
        assert before.st_mtime_ns == after.st_mtime_ns
    else:
        assert store.heads.seal()
    got = _sealed_root(root)
    assert got[b] == sealed[b]
    if want is None:
        assert a not in got
    else:
        assert got[a][0] == want
        st = FileFeedStorage(os.path.join(str(root), a[:2], a))
        assert (len(st), os.stat(st.path).st_size) == got[a]


def test_doc_destroy_and_edit_show_in_the_next_snapshot(tmp_path):
    repo = Repo(path=str(tmp_path))
    keep, gone = repo.create({"n": 0}), repo.create({"n": 0})
    _edit(repo, keep, "n", 1)
    repo.close()
    before = _sealed(tmp_path)
    k, g = (root_actor_id(validate_doc_url(u)) for u in (keep, gone))
    assert k in before and g in before
    repo = Repo(path=str(tmp_path))
    _edit(repo, keep, "n", 2)
    repo.destroy(gone)
    repo.close()
    after = _sealed(tmp_path)
    assert after == _probe(tmp_path)
    assert after[k][0] == before[k][0] + 1 and g not in after


def test_read_only_session_writes_nothing_under_feeds(tmp_path):
    urls = make_corpus(str(tmp_path), 6, 64)
    repo, _h, _s, _stats = _open(tmp_path, urls)
    repo.close()  # the sealing open: probes, and writes the snapshot
    with open(_snap_path(tmp_path), "rb") as fh:
        raw = fh.read()
    tree = _tree(tmp_path)
    repo, handles, _s, stats = _open(tmp_path, urls)
    assert stats["heads_snapshot_pct"] == 100.0
    assert handles[0].value(timeout=60) is not None
    repo.close()
    assert _tree(tmp_path) == tree  # names, sizes and mtimes
    with open(_snap_path(tmp_path), "rb") as fh:
        assert fh.read() == raw


# ---------------------------------------------------------------------------
# (8) the staleness rule compares with the snapshot's count


@pytest.mark.parametrize("which", ["ahead", "behind"])
@pytest.mark.parametrize("shape", SHAPES)
def test_sidecar_off_the_snapshots_count_loads_single(
    tmp_path, shape, which
):
    import numpy as np

    from hypermerge_tpu.storage import colcache
    from hypermerge_tpu.storage.colcache import (
        FeedColumnCache,
        SlabColumnStorage,
        pack_v2_record,
    )
    from hypermerge_tpu.storage.slab import KIND_IMAGE, CorpusSlab

    urls, n_feeds = _corpus(tmp_path, shape)
    repo, handles, summ, _stats = _open(tmp_path, urls)
    want = _summaries(summ, urls)
    values = [h.value(timeout=60) for h in handles]
    name = root_actor_id(validate_doc_url(urls[2]))
    changes = repo.back.actors[name].changes_in_window(0, float("inf"))
    repo.close()
    sealed = _sealed(tmp_path)
    # the sidecar of one feed moves while the store is closed; its log,
    # its `.len` and the snapshot's entry stay
    slab = CorpusSlab(os.path.join(str(tmp_path), "feeds", "cols.slab"))
    cc = FeedColumnCache(SlabColumnStorage(slab, name), writer=name)
    if which == "behind":
        cc.reset()
        for c in changes[:-2]:
            cc.append_change(c)
        cc.compact()
    else:
        record = pack_v2_record(
            np.zeros((0, colcache.ROW_FIELDS), np.int32),
            np.zeros((0, colcache.PRED_FIELDS), np.int32), [], 1,
        )
        slab.append(KIND_IMAGE, name, slab.image_bytes(name) + record)
        cc.compact()
    slab.close()
    assert _sealed(tmp_path) == sealed
    repo, handles, summ, stats = _open(tmp_path, urls)
    assert stats["heads_snapshot_feeds"] == n_feeds
    assert stats["heads_probed_feeds"] == 0
    assert stats["cols_single_feeds"] == 1
    assert stats["cols_bulk_feeds"] == n_feeds - 1
    assert stats["fallback"] == 0
    actor = repo.back.actors[name]
    assert actor.columns().n_changes == actor.seq_head == sealed[name][0]
    assert _summaries(summ, urls) == want
    assert [h.value(timeout=60) for h in handles] == values
    repo.close()


# ---------------------------------------------------------------------------
# counters, span tag, and the scrub tool's line


def test_counters_and_span_tag(tmp_path):
    from hypermerge_tpu import telemetry
    from hypermerge_tpu.telemetry import trace as ttrace

    urls = make_corpus(str(tmp_path), 8, 64)
    repo, _h, _s, _stats = _open(tmp_path, urls)
    repo.close()
    os.remove(os.path.join(str(tmp_path), "feeds", "heads.snap"))
    repo = Repo(path=str(tmp_path))
    repo.open_many(urls[:3])  # probes three, and seals them
    repo.close()
    was_on = ttrace.enabled()
    ttrace.reset()
    ttrace.enable()
    try:
        before = telemetry.snapshot()
        repo, _h, _s, stats = _open(tmp_path, urls)
        after = telemetry.snapshot()
        spans = [
            e[6] for e in telemetry.trace_events()
            if e[1] == "storage.columns.heads"
        ]
    finally:
        if not was_on:
            ttrace.disable()
        ttrace.reset()
    assert stats["heads_snapshot_feeds"] == 3
    assert stats["heads_probed_feeds"] == 5
    assert stats["heads_snapshot_pct"] == 37.5
    for key, n in (
        ("loader.heads_snapshot_feeds", 3), ("loader.heads_probed_feeds", 5),
    ):
        assert after.get(key, 0) - before.get(key, 0) == n, key
    assert sum(a["feeds"] for a in spans) == 8
    assert sum(a["probed"] for a in spans) == 5
    repo.close()
    assert len(_sealed(tmp_path)) == 8  # the five joined the three


def test_memory_store_has_no_snapshot():
    repo = Repo(memory=True)
    assert repo.back.feeds.heads is None
    url = repo.create({"n": 1})
    repo.open_many([url])
    stats = repo.back.last_bulk_stats
    assert stats.get("heads_snapshot_feeds", 0) == 0
    assert stats.get("heads_snapshot_pct", 0.0) == 0.0
    repo.close()


def test_scrub_tool_reports_the_snapshot(tmp_path):
    import json
    import subprocess

    crashed, _urls, _new = _crashed_copy(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def scrub(path):
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "scrub.py"),
             str(path), "--json"],
            capture_output=True, text=True, env=env, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    assert scrub(crashed)["head_snapshot"] == "discarded"
    # (a session that skipped the backend's own recovery seals none)
    assert scrub(crashed)["head_snapshot"] == "absent"
    # the live store was closed cleanly all along
    assert scrub(tmp_path / "live")["head_snapshot"] == "found"
