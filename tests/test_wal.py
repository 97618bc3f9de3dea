"""Group-commit WAL (storage/wal.py): counters, crash matrix, bounded
recovery.

The seeded crash matrix of tests/test_crash.py extended to the shared
journal — every byte the WAL writes goes through the storage/faults.py
io seam, so kill -9 / power-cut replays cover journal writes, the
group-commit fsync, fsync LIES, and the checkpoint tmp+rename:

  - a durable commit window is ONE journal fsync however many feeds
    are dirty (the counter-pinned O(1) acceptance gate; legacy group
    flush was O(dirty feeds));
  - power cut at every write/fsync/checkpoint prefix recovers with
    acked_lost=0 at HM_FSYNC>=1: acked bytes the cut dropped from the
    (unfsynced-at-ack) per-feed logs replay from the fsynced journal;
  - a torn journal tail parses as end-of-journal (torn records were
    never acked), and a crash mid-checkpoint leaves either the old
    journal (idempotent replay) or the new one (logs already durable);
  - the generation stamp bounds recovery: a crashed session's scan
    opens only the journal's dirty-name ledger, not every sidecar in
    the repo (counted by test), and a clean-shutdown journal left
    behind with a stale crash marker yields a ZERO-feed scan.
"""

import os

import pytest

from hypermerge_tpu.storage import faults as F
from hypermerge_tpu.storage import wal as walmod
from hypermerge_tpu.storage.durability import DurabilityManager
from hypermerge_tpu.storage.feed import FileFeedStorage
from hypermerge_tpu.storage.wal import WriteAheadLog, read_journal

from helpers import wait_until


def _fsyncs(rec, start=0):
    """Honest FSYNC events per path since event index `start`."""
    out = {}
    for ev in rec.events[start:]:
        if ev[0] == F.FSYNC and not ev[2]:
            out[ev[1]] = out.get(ev[1], 0) + 1
    return out


# ---------------------------------------------------------------------------
# O(1) fsyncs per commit window (the counter-pinned acceptance gate)


@pytest.mark.parametrize("n_feeds", [2, 8])
def test_tier1_window_is_one_journal_fsync(
    tmp_path, monkeypatch, n_feeds
):
    """However many feeds a tier-1 window dirties, durability costs
    ONE journal fsync — and ZERO per-feed log fsyncs (those defer to
    checkpoint, off the ack path)."""
    monkeypatch.setenv("HM_FSYNC", "1")
    monkeypatch.setenv("HM_FSYNC_MS", "10000")  # we drive the flush
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    with F.activate(recorder=rec):
        os.makedirs(str(work))
        dm = DurabilityManager()
        wal = WriteAheadLog(str(work / "wal.log"), tier=1)
        dm.attach_wal(wal)
        stores = [
            FileFeedStorage(
                str(work / "feeds" / "ab" / f"feed{i}"), durability=dm
            )
            for i in range(n_feeds)
        ]
        mark = len(rec.events)
        for s in stores:
            s.append(b"block")  # journal-routed: no per-feed fsync
        assert dm.sync_now() >= 1  # ONE commit window, driven directly
        counts = _fsyncs(rec, mark)
        assert counts.get("wal.log") == 1, counts
        assert not any(p.startswith("feeds/") for p in counts), counts
        dm.close()


def test_tier2_concurrent_commits_share_leader_fsync(
    tmp_path, monkeypatch
):
    """Leader/follower group commit: concurrent committers (disjoint
    docs since the emission split) ride ONE fsync when the gather
    window covers them — strictly fewer fsyncs than appends."""
    import threading

    monkeypatch.setenv("HM_FSYNC", "2")
    monkeypatch.setenv("HM_WAL_MS", "30")
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    with F.activate(recorder=rec):
        os.makedirs(str(work))
        dm = DurabilityManager()
        dm.attach_wal(WriteAheadLog(str(work / "wal.log"), tier=2))
        stores = [
            FileFeedStorage(
                str(work / "feeds" / "ab" / f"feed{i}"), durability=dm
            )
            for i in range(8)
        ]
        mark = len(rec.events)
        barrier = threading.Barrier(8)

        def commit_one(s):
            barrier.wait()
            s.append(b"durable-block")  # tier 2: blocks until durable

        ts = [
            threading.Thread(target=commit_one, args=(s,))
            for s in stores
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        counts = _fsyncs(rec, mark)
        assert 1 <= counts.get("wal.log", 0) < 8, counts
        assert not any(p.startswith("feeds/") for p in counts), counts
        dm.close()


# ---------------------------------------------------------------------------
# power-cut matrix over the journal: acked_lost=0 at HM_FSYNC>=1


def _acked_repo_workload(work, monkeypatch, tier="1"):
    """Disk repo, 3 docs, interleaved edits; ack point = durability
    flush. Returns (recorder, url_list, acked list of
    (event_index, edits_per_doc))."""
    from hypermerge_tpu.repo import Repo

    monkeypatch.setenv("HM_FSYNC", tier)
    rec = F.CrashRecorder(str(work))
    acked = []
    with F.activate(recorder=rec):
        repo = Repo(path=str(work))
        urls = [repo.create({"edits": []}) for _ in range(3)]
        for i in range(4):
            for url in urls:
                repo.change(url, lambda d, i=i: d["edits"].append(i))
            if repo.back.live is not None:
                repo.back.live.flush_now()
            repo.back._stores.flush_now()
            repo.back._cache_syncs.flush_now()
            repo.back.durability.flush_now()  # the durable ack
            acked.append((len(rec.events), i + 1))
        # one UN-acked trailing edit: gives the torn-tail test a
        # journal append after the last ack to tear into
        repo.change(urls[0], lambda d: d["edits"].append(4))
        if repo.back.live is not None:
            repo.back.live.flush_now()
        # crash: no close
    return rec, repo, urls, acked


def test_powercut_replays_acked_blocks_from_journal(
    tmp_path, monkeypatch
):
    """THE WAL value proposition at tier 1: the per-feed logs are
    page-cache-only at ack time, so a power cut eats them — but every
    acked edit comes back because its bytes are in the fsynced
    journal. acked_lost == 0 at every ack boundary."""
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    work = tmp_path / "work"
    rec, _repo, urls, acked = _acked_repo_workload(
        work, monkeypatch, tier="1"
    )
    for k, want in [acked[0], acked[2], acked[3]]:
        dst = str(tmp_path / f"cut{k}")
        rec.materialize(dst, k, powercut=True)
        repo2 = Repo(path=dst)
        try:
            rep = repo2.back.recovery_report
            assert rep is not None and rep["wal"]["present"] == 1, rep
            for url in urls:
                doc_id = validate_doc_url(url)
                assert doc_id in repo2.back.clocks.all_doc_ids(
                    repo2.back.id
                ), (k, "doc lost")
                h = repo2.open(url)
                v = h.value(timeout=30)
                edits = list(v.get("edits", []))
                # gapless AND nothing acked lost
                assert edits[:want] == list(range(want)), (
                    k, want, edits,
                )
        finally:
            repo2.close()


def test_powercut_matrix_every_prefix_never_raises(
    tmp_path, monkeypatch
):
    """Kill/power-cut at EVERY sampled journal-era prefix: reopen
    (journal replay included) never raises and each doc reads back a
    gapless prefix of its acked edits."""
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    work = tmp_path / "work"
    rec, _repo, urls, acked = _acked_repo_workload(
        work, monkeypatch, tier="1"
    )
    n = len(rec.events)
    step = max(1, n // 12)
    for k in range(0, n + 1, step):
        for powercut in (False, True):
            dst = str(tmp_path / f"c{k}_{int(powercut)}")
            rec.materialize(dst, k, powercut=powercut)
            repo2 = Repo(path=dst)  # never raises
            try:
                hi = max((m for e, m in acked if e <= k), default=0)
                for url in urls:
                    doc_id = validate_doc_url(url)
                    if doc_id not in repo2.back.clocks.all_doc_ids(
                        repo2.back.id
                    ):
                        # crashed before this doc's first commit; the
                        # acked_lost gate still applies (hi == 0 then)
                        assert not (powercut and hi), (k, doc_id)
                        continue
                    v = repo2.doc(url)
                    edits = list((v or {}).get("edits", []))
                    assert edits == list(range(len(edits))), (k, edits)
                    if powercut:
                        # acked_lost == 0: everything flushed before
                        # the cut survived it
                        assert len(edits) >= hi, (k, len(edits), hi)
            finally:
                repo2.close()


def test_torn_journal_tail_recovers_acked_prefix(
    tmp_path, monkeypatch
):
    """A crash mid-journal-write (partial record bytes on disk) parses
    as end-of-journal: recovery replays the acked prefix, reports the
    torn bytes, and never raises."""
    from hypermerge_tpu.repo import Repo

    work = tmp_path / "work"
    rec, _repo, urls, acked = _acked_repo_workload(
        work, monkeypatch, tier="1"
    )
    # find a journal APPEND event after the last ack and tear inside it
    k_ack, want = acked[-1]
    torn = None
    for idx in range(k_ack, len(rec.events)):
        ev = rec.events[idx]
        if ev[0] in (F.APPEND, F.WRITE) and ev[1] == "wal.log":
            torn = idx
            break
    if torn is None:
        pytest.skip("no journal append after the last ack")
    dst = str(tmp_path / "torn")
    rec.materialize(dst, torn, partial_last=3)  # 3 bytes of the record
    repo2 = Repo(path=dst)
    try:
        rep = repo2.back.recovery_report
        assert rep is not None, rep
        for url in urls:
            edits = list((repo2.doc(url) or {}).get("edits", []))
            assert edits[:want] == list(range(want)), (want, edits)
    finally:
        repo2.close()


def test_crash_mid_checkpoint_recovers(tmp_path, monkeypatch):
    """HM_WAL_MAX_BYTES small enough that the workload checkpoints:
    crashing at every prefix across the checkpoint's fsync+rotate
    window recovers cleanly — the old journal replays idempotently or
    the new one finds the logs already durable."""
    from hypermerge_tpu.repo import Repo

    monkeypatch.setenv("HM_WAL_MAX_BYTES", "2048")
    work = tmp_path / "work"
    rec, _repo, urls, acked = _acked_repo_workload(
        work, monkeypatch, tier="1"
    )
    replaces = [
        i
        for i, ev in enumerate(rec.events)
        if ev[0] == F.REPLACE and ev[2] == "wal.log"
    ]
    assert replaces, "workload never checkpointed — lower the cap"
    points = set()
    for r in replaces:  # bracket every rotation tightly
        points.update(
            p for p in range(r - 3, r + 3) if 0 <= p <= len(rec.events)
        )
    from hypermerge_tpu.utils.ids import validate_doc_url

    for k in sorted(points):
        for powercut in (False, True):
            dst = str(tmp_path / f"ck{k}_{int(powercut)}")
            rec.materialize(dst, k, powercut=powercut)
            repo2 = Repo(path=dst)  # never raises
            try:
                hi = max((m for e, m in acked if e <= k), default=0)
                for url in urls:
                    doc_id = validate_doc_url(url)
                    if doc_id not in repo2.back.clocks.all_doc_ids(
                        repo2.back.id
                    ):
                        assert not (powercut and hi), (k, doc_id)
                        continue
                    edits = list(
                        (repo2.doc(url) or {}).get("edits", [])
                    )
                    assert edits == list(range(len(edits))), (k, edits)
                    if powercut:
                        assert len(edits) >= hi, (k, len(edits), hi)
            finally:
                repo2.close()


def test_fsync_lie_on_journal_loses_only_unacked(
    tmp_path, monkeypatch
):
    """A LYING journal fsync is the worst durable-tier failure: the
    commit claims durability the platter never got. The power-cut
    replay drops those bytes — recovery still never raises and the doc
    stays a gapless prefix (the lie IS data loss; what the WAL must
    guarantee is no corruption and no gap)."""
    from hypermerge_tpu.repo import Repo

    monkeypatch.setenv("HM_FSYNC", "1")
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    plan = F.DiskFaultPlan(
        seed=11, fsync_lie_p=1.0, path_filter="wal.log", after=1
    )
    with F.activate(plan=plan, recorder=rec):
        repo = Repo(path=str(work))
        url = repo.create({"edits": []})
        for i in range(4):
            repo.change(url, lambda d, i=i: d["edits"].append(i))
        if repo.back.live is not None:
            repo.back.live.flush_now()
        repo.back._stores.flush_now()
        repo.back.durability.flush_now()
        k = len(rec.events)
    dst = str(tmp_path / "cut")
    rec.materialize(dst, k, powercut=True)
    repo2 = Repo(path=dst)
    try:
        edits = list((repo2.doc(url) or {}).get("edits", []))
        assert edits == list(range(len(edits)))  # gapless, no raise
    finally:
        repo2.close()


# ---------------------------------------------------------------------------
# journal parsing units


def test_read_journal_roundtrip_and_torn_tail(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path, tier=1)
    wal.append("feedA", 0, b"alpha")
    wal.append("feedB", 0, b"beta")
    wal.append("feedA", 1, b"gamma")
    header, dirty, records, torn = read_journal(path)
    assert header is not None and header["tier"] == 1
    assert header["session"] == wal.session
    assert dirty == {"feedA", "feedB"}
    assert records == [
        ("feedA", 0, b"alpha"),
        ("feedB", 0, b"beta"),
        ("feedA", 1, b"gamma"),
    ]
    assert torn == 0
    # tear the tail mid-record: the parse stops cleanly before it
    size = os.path.getsize(path)
    with open(path, "r+b") as fh:
        fh.truncate(size - 4)
    _h, dirty2, records2, torn2 = read_journal(path)
    assert records2 == records[:2]
    assert torn2 > 0
    assert "feedA" in dirty2 and "feedB" in dirty2
    # garbage instead of a record header: also end-of-journal
    with open(path, "ab") as fh:
        fh.write(os.urandom(64))
    _h, _d, records3, torn3 = read_journal(path)
    assert records3 == records[:2] and torn3 > 0
    wal.close()


def test_checkpoint_preserves_dirty_ledger_and_carries_tail(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path, tier=1)

    class _Store:
        synced = 0

        def sync(self):
            type(self).synced += 1

    s = _Store()
    wal.append("feedA", 0, b"a" * 100, storage=s)
    wal.append("feedB", 0, b"b" * 100, storage=s)
    out = wal.checkpoint()
    assert out["synced_feeds"] == 2
    header, dirty, records, torn = read_journal(path)
    # records drained into the (now-synced) logs; the session ledger
    # survives the rotation so recovery bounding still knows the set
    assert records == [] and torn == 0
    assert dirty == {"feedA", "feedB"}
    assert header["session"] == wal.session
    # post-checkpoint appends land in the fresh journal
    wal.append("feedC", 0, b"c", storage=s)
    _h, dirty2, records2, _t = read_journal(path)
    assert ("feedC", 0, b"c") in records2
    assert dirty2 == {"feedA", "feedB", "feedC"}
    wal.close()


# ---------------------------------------------------------------------------
# the generation stamp bounds recovery (the 100k-feed constant)


def _count_recovery_stores(monkeypatch):
    """Counts per-feed storages the NEXT recovery opens."""
    from hypermerge_tpu.storage import scrub

    opened = []
    real = scrub._recover_repo

    def counting(back, repair):
        fn = back.feeds._storage_fn

        def wrapped(name):
            opened.append(name)
            return fn(name)

        monkeypatch.setattr(back.feeds, "_storage_fn", wrapped)
        try:
            return real(back, repair)
        finally:
            monkeypatch.setattr(back.feeds, "_storage_fn", fn)

    monkeypatch.setattr(scrub, "_recover_repo", counting)
    return opened


def test_bounded_recovery_opens_only_session_dirty_feeds(
    tmp_path, monkeypatch
):
    """Session 1 creates MANY docs and closes clean; session 2 edits
    ONE doc and crashes. Recovery must scrub only the crashed
    session's dirty ledger — the untouched sidecars stay unopened
    (generation stamp honored)."""
    from hypermerge_tpu.repo import Repo

    monkeypatch.setenv("HM_FSYNC", "1")
    path = str(tmp_path / "r")
    repo = Repo(path=path)
    urls = [repo.create({"n": i}) for i in range(20)]
    if repo.back.live is not None:
        repo.back.live.flush_now()
    repo.close()  # clean: marker removed, journal reset

    repo2 = Repo(path=path)
    repo2.change(urls[0], lambda d: d.__setitem__("n", 99))
    if repo2.back.live is not None:
        repo2.back.live.flush_now()
    repo2.back._stores.flush_now()
    repo2.back.durability.flush_now()
    del repo2  # crash: marker + journal left behind

    opened = _count_recovery_stores(monkeypatch)
    repo3 = Repo(path=path)
    try:
        rep = repo3.back.recovery_report
        assert rep is not None, "marker gone — no crash simulated"
        assert rep["wal"]["bounded"] == 1, rep["wal"]
        assert rep["feeds_skipped"] >= 19, rep
        # only the crashed session's feeds were opened (the edited
        # doc's actor feed; NOT the other 19 docs' sidecars)
        assert 0 < len(set(opened)) <= 3, sorted(set(opened))
        assert (repo3.doc(urls[0]) or {}).get("n") == 99
    finally:
        repo3.close()


def test_stale_marker_after_clean_shutdown_scans_nothing(
    tmp_path, monkeypatch
):
    """A clean shutdown resets the journal to its bare header exactly
    so that a stale crash marker (close crashed AFTER the final
    checkpoint but before the marker removal) yields a ZERO-feed
    bounded scan instead of a whole-repo sidecar sweep."""
    from hypermerge_tpu.repo import Repo

    monkeypatch.setenv("HM_FSYNC", "1")
    path = str(tmp_path / "r")
    repo = Repo(path=path)
    urls = [repo.create({"n": i}) for i in range(10)]
    if repo.back.live is not None:
        repo.back.live.flush_now()
    repo.close()
    # the clean close left the truncated journal: bare header, same
    # session id
    header, dirty, records, torn = read_journal(
        os.path.join(path, "wal.log")
    )
    assert header is not None and not dirty and not records and not torn
    # resurrect the crash marker as a failed close would leave it
    with open(os.path.join(path, "repo.dirty"), "wb") as fh:
        fh.write(str(header["session"]).encode())

    opened = _count_recovery_stores(monkeypatch)
    repo2 = Repo(path=path)
    try:
        rep = repo2.back.recovery_report
        assert rep is not None and rep["wal"]["bounded"] == 1, rep
        assert rep["feeds_skipped"] >= 10, rep
        assert opened == [], opened  # the whole-repo scan was skipped
        for i, url in enumerate(urls):
            assert (repo2.doc(url) or {}).get("n") == i
    finally:
        repo2.close()


def test_unbounded_when_marker_mismatches_journal(tmp_path, monkeypatch):
    """Bounding must never skip real damage: a journal that does NOT
    provably belong to the crashed session (stamp mismatch) falls back
    to the full scan."""
    from hypermerge_tpu.repo import Repo

    monkeypatch.setenv("HM_FSYNC", "1")
    path = str(tmp_path / "r")
    repo = Repo(path=path)
    repo.create({"n": 1})
    if repo.back.live is not None:
        repo.back.live.flush_now()
    repo.back._stores.flush_now()
    repo.back.durability.flush_now()
    del repo  # crash
    # corrupt the stamp: marker no longer matches the journal header
    with open(os.path.join(path, "repo.dirty"), "wb") as fh:
        fh.write(b"some-other-session")
    repo2 = Repo(path=path)
    try:
        rep = repo2.back.recovery_report
        assert rep is not None
        assert rep["wal"]["bounded"] == 0, rep["wal"]
        assert rep.get("feeds_skipped", 0) == 0, rep
    finally:
        repo2.close()


def test_ack_durable_echo_is_powercut_durable(tmp_path, monkeypatch):
    """HM_ACK_DURABLE=1 at tier 1: the LocalPatch echo IS a durable
    ack — every echoed edit survives a power cut with NO explicit
    flush anywhere (the bench config_writers pacing contract)."""
    from hypermerge_tpu.repo import Repo

    monkeypatch.setenv("HM_FSYNC", "1")
    monkeypatch.setenv("HM_ACK_DURABLE", "1")
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    with F.activate(recorder=rec):
        repo = Repo(path=str(work))
        url = repo.create({"edits": []})
        done = []
        h = repo.watch(
            url, lambda d, _i: done.append(len(d.get("edits", [])))
        )
        for i in range(5):
            repo.change(url, lambda d, i=i: d["edits"].append(i))
        if repo.back.live is not None:
            repo.back.live.flush_now()
        wait_until(lambda: done and max(done) == 5)
        repo.back._stores.flush_now()
        h.close()
        k = len(rec.events)
        # crash: NO durability.flush_now() — the echoes were the acks
    dst = str(tmp_path / "cut")
    rec.materialize(dst, k, powercut=True)
    repo2 = Repo(path=dst)
    try:
        edits = list((repo2.doc(url) or {}).get("edits", []))
        assert edits == list(range(5)), edits
    finally:
        repo2.close()


# ---------------------------------------------------------------------------
# hardening regressions: checkpoint/commit/replay failure paths, dry-run
# preview fidelity, and the journal-less stale-stamp hazard


class _SyncProbe:
    """Checkpoint-pending stand-in: counts syncs, optionally fails."""

    def __init__(self, fail=False):
        self.fail = fail
        self.synced = 0

    def sync(self):
        if self.fail:
            raise OSError("EIO")
        self.synced += 1


def test_checkpoint_sync_failure_keeps_all_remaining_pending(tmp_path):
    """A checkpoint aborted by one feed's failed sync must re-add the
    failing feed AND every not-yet-synced one behind it — dropping
    them would let a later successful rotation discard K_APPEND
    records whose logs never reached the platter."""
    wal = WriteAheadLog(str(tmp_path / "wal.log"), tier=1)
    a, b, c = _SyncProbe(), _SyncProbe(fail=True), _SyncProbe()
    assert wal.append("aa", 0, b"x", a) is not None
    assert wal.append("bb", 0, b"x", b) is not None
    assert wal.append("cc", 0, b"x", c) is not None
    out = wal.checkpoint()
    assert out["synced_feeds"] == 1  # only `aa` reached the platter
    assert a.synced == 1 and c.synced == 0
    assert set(wal._ckpt_pending) == {"bb", "cc"}, wal._ckpt_pending
    # the journal was NOT rotated: every record is still replayable
    _h, dirty, records, _t = read_journal(str(tmp_path / "wal.log"))
    assert {n for n, _i, _d in records} == {"aa", "bb", "cc"}
    b.fail = False
    out2 = wal.checkpoint()
    assert out2["synced_feeds"] == 2 and not wal._ckpt_pending


def test_commit_after_failed_close_raises_not_acks(
    tmp_path, monkeypatch
):
    """A committer woken by closure WITHOUT a covering fsync (failed
    close) must raise — returning would grant a durable ack for bytes
    that never reached the platter."""
    wal = WriteAheadLog(str(tmp_path / "wal.log"), tier=2)
    end = wal.append("aa", 0, b"x")
    wal.commit(end)  # healthy baseline: fsync works
    end2 = wal.append("aa", 1, b"y")

    def broken_fsync(_fh):
        raise OSError("EIO")

    monkeypatch.setattr(walmod, "io_fsync", broken_fsync)
    assert wal.close() is False  # final sync failed
    with pytest.raises(OSError):
        wal.commit(end2)


def _bare_back(work, storage_fn):
    """Minimal recover() target: path + feeds._storage_fn."""
    from types import SimpleNamespace

    return SimpleNamespace(
        path=str(work),
        feeds=SimpleNamespace(_storage_fn=storage_fn),
        durability=SimpleNamespace(),
    )


def test_dry_run_replay_preview_matches_repair_on_gap(tmp_path):
    """tools/scrub.py --dry-run must preview exactly what repair will
    append: a journal with a GAP (records for indices the log can
    never reach sequentially) replays only the contiguous extension."""
    work = tmp_path / "w"
    os.makedirs(str(work / "feeds" / "aa"))
    st = FileFeedStorage(str(work / "feeds" / "aa" / "aafeed"))
    st.append(b"b0")
    st.close()
    wal = WriteAheadLog(str(work / "wal.log"), tier=1)
    assert wal.append("aafeed", 1, b"b1") is not None  # contiguous
    assert wal.append("aafeed", 3, b"b3") is not None  # gap: no idx 2
    wal.sync()  # durable journal; no close (crash)

    def fn(name):
        return FileFeedStorage(str(work / "feeds" / "aa" / name))

    dry = walmod.recover(_bare_back(work, fn), repair=False)
    real = walmod.recover(_bare_back(work, fn), repair=True)
    assert dry["replay_would"] == 1, dry
    assert real["replayed"] == 1 and real["skipped"] == 1, real
    assert dry["replay_would"] == real["replayed"]


def test_replay_sync_failure_preserves_journal(tmp_path):
    """recover() must NOT consume the journal when a replayed feed's
    fsync failed: the replayed block exists only in page cache, and
    the journal is its one durable copy until a later recovery (or
    checkpoint) lands it."""
    work = tmp_path / "w"
    os.makedirs(str(work / "feeds" / "aa"))
    wal = WriteAheadLog(str(work / "wal.log"), tier=1)
    assert wal.append("aafeed", 0, b"b0") is not None
    wal.sync()  # crash: no close

    class _FailingSyncStorage(FileFeedStorage):
        def sync(self):
            raise OSError("EIO")

    def failing_fn(name):
        return _FailingSyncStorage(str(work / "feeds" / "aa" / name))

    def ok_fn(name):
        return FileFeedStorage(str(work / "feeds" / "aa" / name))

    rep = walmod.recover(_bare_back(work, failing_fn), repair=True)
    assert rep["replayed"] == 1 and rep.get("replay_sync_failed") == 1
    assert os.path.exists(str(work / "wal.log"))  # NOT consumed
    # a later healthy recovery consumes it (block already in the log)
    rep2 = walmod.recover(_bare_back(work, ok_fn), repair=True)
    assert rep2["skipped"] == 1 and "replay_sync_failed" not in rep2
    assert not os.path.exists(str(work / "wal.log"))


def test_journalless_session_write_invalidates_stale_stamp(
    tmp_path, monkeypatch
):
    """A writable HM_RECOVER=0 session preserves the crashed marker +
    journal for a manual scrub — but its own journal-less writes are
    OUTSIDE that journal's dirty ledger. The first write must break
    the stamp match, so a crash of THIS session recovers with the
    full sidecar scan instead of trusting the stale ledger."""
    from hypermerge_tpu.repo import Repo

    monkeypatch.setenv("HM_FSYNC", "1")
    path = str(tmp_path / "r")
    repo = Repo(path=path)
    url = repo.create({"n": 1})
    if repo.back.live is not None:
        repo.back.live.flush_now()
    repo.back._stores.flush_now()
    repo.back.durability.flush_now()
    del repo  # crash A: marker(stamp A) + wal.log(A) left behind

    monkeypatch.setenv("HM_RECOVER", "0")
    repo2 = Repo(path=path)
    assert repo2.back.recovery_report is None  # recovery skipped
    assert repo2.back.durability.wal is None  # journal-less session
    with open(os.path.join(path, "repo.dirty"), "rb") as fh:
        stamp_before = fh.read()
    repo2.change(url, lambda d: d.__setitem__("n", 2))
    if repo2.back.live is not None:
        repo2.back.live.flush_now()
    repo2.back._stores.flush_now()
    repo2.back.durability.flush_now()
    with open(os.path.join(path, "repo.dirty"), "rb") as fh:
        stamp_after = fh.read()
    assert stamp_after == stamp_before + b"+journalless"
    del repo2  # crash B: damaged feeds are NOT in A's ledger

    monkeypatch.setenv("HM_RECOVER", "1")
    repo3 = Repo(path=path)
    try:
        rep = repo3.back.recovery_report
        assert rep is not None, "marker gone — no crash simulated"
        # stale ledger refused: full scan, nothing skipped
        assert rep["wal"]["session_match"] == 0, rep["wal"]
        assert rep["wal"]["bounded"] == 0, rep["wal"]
        assert rep.get("feeds_skipped", 0) == 0, rep
        assert (repo3.doc(url) or {}).get("n") == 2
    finally:
        repo3.close()


def test_concurrent_append_and_sync_share_write_handles(tmp_path):
    """The cached write handles are shared between the appender and
    the WAL checkpoint thread's sync(): interleaved use must leave a
    consistent .len sidecar (pre-lock, a seek/write interleaving
    could tear it or close an fd mid-fsync)."""
    import threading

    st = FileFeedStorage(str(tmp_path / "ab" / "feed"))
    stop = threading.Event()
    errs = []

    def syncer():
        while not stop.is_set():
            try:
                st.sync()
            except Exception as e:  # noqa: BLE001 - any escape fails
                errs.append(e)
                return

    t = threading.Thread(target=syncer)
    t.start()
    try:
        for i in range(400):
            st.append(b"b" * (i % 17 + 1))
    finally:
        stop.set()
        t.join(10)
    assert not errs, errs
    st.close()
    fresh = FileFeedStorage(st.path)
    assert fresh._try_count_shortcut(), ".len torn or stale"
    assert len(fresh) == 400
    fresh.close()


def test_commit_ack_covers_unjournaled_legacy_appends(
    tmp_path, monkeypatch
):
    """HM_ACK_DURABLE: commit_ack's journal fsync only vouches for
    blocks the journal holds. An append that fell back to the legacy
    path (broken journal) was mark_dirty'd instead — commit_ack must
    drain the legacy barrier too, or the durable ack covers bytes
    that exist only in page cache."""
    monkeypatch.setenv("HM_FSYNC", "1")
    monkeypatch.setenv("HM_FSYNC_MS", "10000")  # no background flush
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    with F.activate(recorder=rec):
        os.makedirs(str(work))
        dm = DurabilityManager()
        wal = WriteAheadLog(str(work / "wal.log"), tier=1)
        dm.attach_wal(wal)
        st = FileFeedStorage(
            str(work / "feeds" / "ab" / "feed0"), durability=dm
        )
        # break the journal mid-session: appends now fall back to the
        # legacy per-feed path (mark_dirty), and wal.sync() is a
        # silent no-op (_synced already covers the frozen _end)
        with wal._cv:
            wal._closed = True
        st.append(b"block")
        mark = len(rec.events)
        dm.commit_ack()  # the durable ack point
        counts = _fsyncs(rec, mark)
        assert any(
            p.startswith("feeds/") for p in counts
        ), f"legacy append not fsynced at ack: {counts}"
        dm.close()


# ---------------------------------------------------------------------------
# a run of blocks (FileFeedStorage.append_many, ISSUE 42): every block
# journaled with its index, and durable before `.len` describes it


@pytest.mark.parametrize("journal", (True, False), ids=("wal", "no-wal"))
@pytest.mark.parametrize("tier", (1, 2))
def test_append_many_is_durable_as_the_tier_has_it(
    tmp_path, monkeypatch, tier, journal
):
    """One extension of five blocks onto a log of two. With a journal:
    it holds every block under its own index, and at tier 2 its commit
    fsync comes before the `.len` record (tier 1: the window's ONE
    fsync, no per-feed fsync). Without one: tier 2 fsyncs the log ONCE,
    before `.len`; tier 1 marks the storage dirty for the group
    flush."""
    monkeypatch.setenv("HM_FSYNC", str(tier))
    monkeypatch.setenv("HM_FSYNC_MS", "10000")  # we drive the flush
    work = tmp_path / "work"
    blocks = [b"ext-%d" % i * (i + 1) for i in range(5)]
    rec = F.CrashRecorder(str(work))
    with F.activate(recorder=rec):
        os.makedirs(str(work))
        dm = DurabilityManager()
        if journal:
            dm.attach_wal(WriteAheadLog(str(work / "wal.log"), tier=tier))
        st = FileFeedStorage(
            str(work / "feeds" / "ab" / "feed0"), durability=dm
        )
        st.append(b"zero")
        st.append(b"one")
        mark = len(rec.events)
        st.append_many(blocks)
        events = rec.events[mark:]
        if tier == 1:
            assert dm.sync_now() >= 1
        counts = _fsyncs(rec, mark)
        journaled = read_journal(str(work / "wal.log"))  # ere the close
        dm.close()
    log, sidecar = "feeds/ab/feed0", "feeds/ab/feed0.len"
    log_writes = [e for e in events if e[1] == log and e[0] == F.WRITE]
    assert len(log_writes) == 1  # the run is ONE write of the log
    assert len([e for e in events if e[1] == sidecar]) == 1
    at = {(e[0], e[1]): i for i, e in enumerate(events)}
    if journal:
        _h, _dirty, records, torn = journaled
        assert not torn
        assert [r for r in records if r[1] >= 2] == [
            ("feed0", 2 + i, b) for i, b in enumerate(blocks)
        ]
        assert log not in counts and counts["wal.log"] >= 1
        if tier == 2:
            assert at[F.FSYNC, "wal.log"] < at[F.WRITE, sidecar]
    elif tier == 2:
        assert counts == {log: 1}
        assert at[F.WRITE, log] < at[F.FSYNC, log] < at[F.WRITE, sidecar]
    else:
        assert counts.get(log) == 1  # the group flush found it dirty
    assert FileFeedStorage(str(work / log)).get_range(0, 9) == [
        b"zero", b"one"] + blocks


# ---------------------------------------------------------------------------
# the crash matrix crossed with the sharded write plane: kill -9 a
# worker PROCESS mid-burst and hold the same gate — acked_lost=0


def test_worker_sigkill_midburst_acked_lost_zero(tmp_path):
    """SIGKILL the worker that OWNS a hot doc's shard mid-burst under
    HM_FSYNC=1 + durable acks: the hub supervises a respawn, the fresh
    worker replays its journal prefix, and every edit whose durable
    ack the writer received survives (acked_lost=0). The one write in
    flight INSIDE the dead worker is allowed to vanish — it was never
    acked — and a brand-new connection both reads the recovered doc
    and writes to it (the backend mints it a fresh actor; grants died
    with the worker and are never resurrected).

    The ack signal is a second OBSERVER connection's watch state: the
    writer's own handle fans out each change preview optimistically,
    but the observer's value moves only when the backend's patch
    broadcast arrives — and under HM_ACK_DURABLE that broadcast is
    gated on the WAL group commit covering the edit."""
    import signal
    import subprocess
    import sys
    import tempfile
    import threading
    import time

    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    sock = tempfile.mktemp(suffix=".sock")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": repo_root,
        "HM_FSYNC": "1",
        "HM_ACK_DURABLE": "1",
        "HM_WAL_MS": "3",
        "HM_WORKERS": "2",
        "HM_WORKER_RESPAWN_MS": "100",
    }
    proc = subprocess.Popen(
        [sys.executable, "-m", "hypermerge_tpu.net.ipc",
         str(tmp_path / "repo"), sock, "--hub"],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        env=env,
        cwd=repo_root,
    )
    lines = []
    threading.Thread(
        target=lambda: lines.extend(iter(proc.stdout.readline, "")),
        daemon=True,
    ).start()

    def _sync(fn, timeout=30):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if fn():
                return True
            time.sleep(0.02)
        return False

    def _val(handle):
        try:
            return handle.value(timeout=0.2)
        except TimeoutError:
            return None

    closers = []
    try:
        assert _sync(lambda: os.path.exists(sock)), "daemon not up"
        assert _sync(
            lambda: sum("worker" in ln for ln in lines) >= 2
        ), lines
        pids = {}
        for ln in list(lines):
            parts = ln.split()
            if parts[:1] == ["worker"] and "respawned" not in parts:
                pids[int(parts[1])] = int(parts[3])

        from hypermerge_tpu.net.ipc import _shard_of, connect_frontend

        front, close = connect_frontend(sock)
        closers.append(close)
        url = front.create({"edits": {}})
        h = front.open(url)
        assert _sync(lambda: "edits" in (_val(h) or {}))
        owner = _shard_of(url[len("hypermerge:/"):], 2)

        # the durable-ack probe: a read-only connection whose value
        # only the backend's (durability-gated) patch pushes can move
        obs, close_obs = connect_frontend(sock)
        closers.append(close_obs)
        hobs = obs.open(url)
        assert _sync(lambda: "edits" in (_val(hobs) or {}))

        def _acked(key, val, timeout=10):
            return _sync(
                lambda: (_val(hobs) or {})
                .get("edits", {}).get(key) == val,
                timeout=timeout,
            )

        acked = []
        for i in range(8):  # ack-paced burst: durable echo gates each
            front.change(
                url, lambda d, i=i: d["edits"].__setitem__(str(i), i)
            )
            assert _acked(str(i), i), f"edit {i} never acked"
            acked.append(str(i))

        os.kill(pids[owner], signal.SIGKILL)  # mid-burst: kill -9
        # the next write races worker-death detection: it either lands
        # after the respawn (hub buffered it) or was swallowed by the
        # dying socket — it only joins the gate if its ack came back
        front.change(
            url, lambda d: d["edits"].__setitem__("post-kill", 1)
        )
        if _acked("post-kill", 1, timeout=5):
            acked.append("post-kill")

        assert _sync(
            lambda: any("respawned" in ln for ln in lines)
        ), "hub never respawned the killed worker"

        # a brand-new connection sees every acked edit: the respawned
        # worker replayed them from the journal prefix (acked_lost=0)
        f2, close2 = connect_frontend(sock)
        closers.append(close2)
        h2 = f2.open(url)
        assert _sync(lambda: "edits" in (_val(h2) or {}))

        def _lost():
            edits = (_val(h2) or {}).get("edits", {})
            return [k for k in acked if k not in edits]

        assert _sync(lambda: not _lost(), timeout=20), (
            f"acked edits lost across worker kill -9: {_lost()}"
        )
        # ...and can WRITE: the backend mints the new connection a
        # fresh actor rather than resurrecting a dead grant
        f2.change(
            url, lambda d: d["edits"].__setitem__("fresh", 1)
        )
        assert _sync(
            lambda: (_val(h2) or {})
            .get("edits", {}).get("fresh") == 1,
            timeout=15,
        ), "respawned worker refuses new writers"
    finally:
        for close in closers:
            try:
                close()
            except Exception:
                pass
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)
        if os.path.exists(sock):
            os.remove(sock)
