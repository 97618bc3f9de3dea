"""Crash-consistent storage: seeded disk faults + kill-anywhere matrix.

The storage twin of tests/test_chaos.py. A CrashRecorder
(storage/faults.py) records the write/fsync/commit schedule of a
workload; every prefix replays as a simulated crash and the recovery
invariants are asserted on reopen:

  - reopen never raises (whatever boundary the crash landed on);
  - recovered state is a gapless PREFIX of acknowledged state;
  - anything acknowledged under the durable tier (HM_FSYNC) survives
    a simulated power cut;
  - a crashed-then-recovered repo reconverges bit-identically to a
    clean twin after resync (HM_LIVE=1/0 both).

Plus deterministic fault-plan units (same seed = same schedule) and
targeted ENOSPC/EIO injection on the append paths.
"""

import os

import pytest

from hypermerge_tpu.storage import faults as F
from hypermerge_tpu.storage.feed import FileFeedStorage

from helpers import plainify, wait_until


def _mk_storage(root, name="feed"):
    return FileFeedStorage(os.path.join(str(root), "ab", name))


# ---------------------------------------------------------------------------
# fault-plan determinism


def test_fault_plan_same_seed_same_schedule():
    def fates(seed):
        plan = F.DiskFaultPlan(
            seed=seed, write_error_p=0.2, torn_write_p=0.2,
            fsync_error_p=0.1, fsync_lie_p=0.2,
        )
        out = []
        for i in range(40):
            out.append(plan.write_fate("a/log", 64 + i))
            out.append(plan.fsync_fate("a/log"))
        return out

    assert fates(7) == fates(7)
    assert fates(7) != fates(8)  # and the seed actually matters


def test_fault_plan_per_path_streams_independent():
    """Which op of a path faults must not depend on how OTHER paths
    interleave (the per-direction-stream property of net FaultPlan)."""
    plan1 = F.DiskFaultPlan(seed=3, write_error_p=0.3)
    solo = [plan1.write_fate("x", 8) for _ in range(20)]
    plan2 = F.DiskFaultPlan(seed=3, write_error_p=0.3)
    mixed = []
    for _ in range(20):
        mixed.append(plan2.write_fate("x", 8))
        plan2.write_fate("y", 8)  # interleaved traffic on another path
    assert solo == mixed


def test_fault_plan_after_grace_period():
    plan = F.DiskFaultPlan(seed=1, write_error_p=1.0, after=3)
    for _ in range(3):
        assert plan.write_fate("p", 4)[0] == "ok"
    assert plan.write_fate("p", 4)[0] == "error"


# ---------------------------------------------------------------------------
# targeted ENOSPC / EIO / torn-write injection


def test_feed_append_enospc_keeps_memory_consistent(tmp_path):
    s = _mk_storage(tmp_path)
    for i in range(3):
        s.append(b"block-%d" % i)
    plan = F.DiskFaultPlan(seed=0, write_error_p=1.0)
    with F.activate(plan=plan):
        with pytest.raises(OSError):
            s.append(b"doomed")
    assert len(s) == 3  # in-memory state did not run ahead
    s.append(b"block-3")  # next append heals the (possibly torn) tail
    s2 = _mk_storage(tmp_path)
    assert len(s2) == 4
    assert [s2.get(i) for i in range(4)] == [
        b"block-0", b"block-1", b"block-2", b"block-3",
    ]


def test_feed_append_torn_write_heals(tmp_path):
    s = _mk_storage(tmp_path)
    s.append(b"healthy")
    plan = F.DiskFaultPlan(seed=5, torn_write_p=1.0)
    with F.activate(plan=plan):
        with pytest.raises(OSError):
            s.append(b"torn-block-payload")
    # torn bytes are on disk past the logical end; a fresh open ignores
    # them and the next append overwrites them
    assert len(_mk_storage(tmp_path)) == 1
    s.append(b"after")
    s3 = _mk_storage(tmp_path)
    assert [s3.get(i) for i in range(2)] == [b"healthy", b"after"]


def test_actor_write_change_enospc_no_phantom(tmp_path):
    """A failed feed append must not leave a phantom change in the
    actor's memory (seq continuity would break for every later write)."""
    from hypermerge_tpu.backend.actor import Actor
    from hypermerge_tpu.crdt.change import Change
    from hypermerge_tpu.storage.feed import Feed
    from hypermerge_tpu.utils import keys as keymod

    pair = keymod.create()
    feed = Feed(
        pair.public_key, _mk_storage(tmp_path), pair.secret_key
    )
    events = []
    actor = Actor(feed, events.append)

    def change(seq):
        return Change(
            actor=pair.public_key, seq=seq, start_op=seq, deps={},
            ops=[], message="",
        )

    actor.write_change(change(1))
    plan = F.DiskFaultPlan(seed=0, write_error_p=1.0)
    with F.activate(plan=plan):
        with pytest.raises(OSError):
            actor.write_change(change(2))
    assert actor.seq_head == 1
    actor.write_change(change(2))  # same seq retries cleanly
    assert actor.seq_head == 2
    assert feed.length == 2


def test_colcache_enospc_requeues_table_lines(tmp_path):
    """Interner table lines taken for a commit that failed must go back
    on the pending queue — otherwise later commits reference table
    indices the file never defines."""
    from hypermerge_tpu.storage.colcache import (
        FeedColumnCache,
        FileColumnStorageV2,
    )
    from hypermerge_tpu.crdt.change import Change, Op, Action, ROOT

    path = str(tmp_path / "ab" / "feed.cols2")
    cc = FeedColumnCache(FileColumnStorageV2(path), writer="w" * 16)

    def change(seq, key, val):
        return Change(
            actor="w" * 16, seq=seq, start_op=seq, deps={},
            ops=[Op(Action.SET, ROOT, key=key, value=val)],
        )

    cc.append_change(change(1, "a", "hello"))
    plan = F.DiskFaultPlan(seed=2, write_error_p=1.0)
    with F.activate(plan=plan):
        with pytest.raises(OSError):
            cc.append_change(change(2, "b", "world"))
    cc.append_change(change(2, "b", "world"))  # retry after space frees
    cc2 = FeedColumnCache(FileColumnStorageV2(path), writer="w" * 16)
    fc = cc2.columns()
    assert fc.n_changes == 2
    assert "world" in fc.strings  # the requeued table line landed


# ---------------------------------------------------------------------------
# per-format crash matrices (every write boundary is a crash point)


def test_feed_crash_matrix(tmp_path):
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    acked = []  # (event index, blocks acked)
    with F.activate(recorder=rec):
        s = FileFeedStorage(str(work / "ab" / "feed"))
        for i in range(6):
            s.append(b"payload-%d-%s" % (i, b"x" * i))
            acked.append((rec.n_points - 1, i + 1))
    n = rec.n_points
    for k in range(n):
        dst = str(tmp_path / f"c{k}")
        rec.materialize(dst, k)
        s2 = FileFeedStorage(os.path.join(dst, "ab", "feed"))
        got = len(s2)  # reopen never raises
        # gapless prefix of acknowledged state
        full_acked = max((m for e, m in acked if e <= k), default=0)
        assert got <= full_acked + 1  # +1: the append being torn
        for i in range(got):
            assert s2.get(i) == b"payload-%d-%s" % (i, b"x" * i)
        s2.append(b"heal")  # the next append always heals the tail
        s3 = FileFeedStorage(os.path.join(dst, "ab", "feed"))
        assert len(s3) == got + 1
        assert s3.get(got) == b"heal"


def test_feed_crash_matrix_intra_write_tears(tmp_path):
    """Crashes INSIDE a write syscall (partial byte prefixes) heal the
    same way as boundary crashes."""
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    with F.activate(recorder=rec):
        s = FileFeedStorage(str(work / "ab" / "feed"))
        for i in range(3):
            s.append(b"0123456789abcdef-%d" % i)
    n = rec.n_points - 1
    for k in range(n):
        for cut in (1, 3):
            dst = str(tmp_path / f"t{k}_{cut}")
            rec.materialize(dst, k, partial_last=cut)
            s2 = FileFeedStorage(os.path.join(dst, "ab", "feed"))
            got = len(s2)
            for i in range(got):
                assert s2.get(i) == b"0123456789abcdef-%d" % i
            s2.append(b"heal")
            assert len(
                FileFeedStorage(os.path.join(dst, "ab", "feed"))
            ) == got + 1


@pytest.mark.parametrize("cut", (None, 1, 5, 26, 27, 60),
                         ids=lambda c: "boundary" if c is None else f"tear{c}")
def test_feed_crash_matrix_of_a_run(tmp_path, cut):
    """An extension stored as ONE write (append_many): a crash at any
    boundary, or INSIDE the write, leaves a gapless prefix of the
    acknowledged blocks plus at most a prefix of the run's own blocks,
    each whole (a crash between two of the appends left the same), and
    the next append heals the tail."""
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    acked = [b"solo-0", b"solo-1"]
    run = [b"run-%d-%s" % (i, b"y" * (9 * i)) for i in range(5)]
    with F.activate(recorder=rec):
        s = FileFeedStorage(str(work / "ab" / "feed"))
        for b in acked:
            s.append(b)
        before = rec.n_points - 1  # events until the run's own
        s.append_many(run)
    want = acked + run
    for k in range(rec.n_points - (cut is not None)):
        dst = str(tmp_path / f"r{k}")
        rec.materialize(dst, k, partial_last=cut)
        path = os.path.join(dst, "ab", "feed")
        s2 = FileFeedStorage(path)
        got = len(s2)  # reopen never raises
        assert s2.get_range(0, got) == want[:got]
        if k >= before:
            assert got >= len(acked)  # nothing acknowledged is lost
        if k == rec.n_points - 1:
            assert got == len(want)
        s2.append(b"heal")
        s3 = FileFeedStorage(path)
        assert len(s3) == got + 1 and s3.get(got) == b"heal"


def test_slab_crash_matrix(tmp_path):
    from hypermerge_tpu.storage.slab import (
        CorpusSlab,
        KIND_IMAGE,
        KIND_RECORD,
    )

    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    payloads = {"feedA": [], "feedB": []}
    with F.activate(recorder=rec):
        slab = CorpusSlab(str(work / "cols.slab"))
        for i in range(3):
            for name in ("feedA", "feedB"):
                kind = KIND_IMAGE if i == 0 else KIND_RECORD
                payload = b"%s-%d-%s" % (name.encode(), i, b"y" * 7)
                slab.append(kind, name, payload)
                if kind == KIND_IMAGE:
                    payloads[name] = [payload]
                else:
                    payloads[name].append(payload)
        slab.close()
    n = rec.n_points
    for k in range(n):
        dst = str(tmp_path / f"s{k}")
        rec.materialize(dst, k)
        s2 = CorpusSlab(os.path.join(dst, "cols.slab"))
        names = s2.feed_names()  # loading IS the repair; never raises
        for name in names:
            got = s2.image_bytes(name)
            # the recovered image must be a concatenation of a prefix
            # of that feed's appended segments
            acc = b""
            ok = got == b""
            for p in payloads[name]:
                acc += p
                if got == acc:
                    ok = True
            assert ok, (k, name, got)
        # and the slab stays appendable (heals its torn tail)
        s2.append(KIND_RECORD, "feedA", b"heal")
        assert s2.image_bytes("feedA").endswith(b"heal")
        s2.close()


def test_colcache_commit_matrix(tmp_path):
    import numpy as np

    from hypermerge_tpu.storage.colcache import (
        FileColumnStorageV2,
        PRED_FIELDS,
        ROW_FIELDS,
    )

    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    with F.activate(recorder=rec):
        st = FileColumnStorageV2(str(work / "ab" / "f.cols2"))
        for i in range(5):
            rows = np.full((2, ROW_FIELDS), i, np.int32)
            preds = np.zeros((1, PRED_FIELDS), np.int32)
            st.commit_change(rows, preds, ['{"t":"k","v":"k%d"}' % i], 0)
    n = rec.n_points
    for k in range(n):
        dst = str(tmp_path / f"c{k}")
        rec.materialize(dst, k)
        st2 = FileColumnStorageV2(os.path.join(dst, "ab", "f.cols2"))
        rows, preds, tables, commits = st2.load()  # never raises
        m = len(commits)
        assert m <= 5
        # only COMPLETE commits are honored: rows/preds/tables all
        # consistent with the last commit record
        assert len(rows) == 2 * m
        assert len(preds) == m
        assert len(tables) == m
        if m:
            assert int(rows[-1, 0]) == m - 1


# ---------------------------------------------------------------------------
# durability tiers + power-cut model


def test_powercut_drops_unfsynced_tail_kill9_does_not(tmp_path):
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    with F.activate(recorder=rec):
        s = FileFeedStorage(str(work / "ab" / "feed"))
        s.append(b"first")
        s.sync()  # honest fsync: durable from here
        s.append(b"second")  # flushed, never fsynced
    k = rec.n_points - 1
    rec.materialize(str(tmp_path / "kill9"), k)
    assert len(FileFeedStorage(str(tmp_path / "kill9/ab/feed"))) == 2
    rec.materialize(str(tmp_path / "cut"), k, powercut=True)
    s2 = FileFeedStorage(str(tmp_path / "cut/ab/feed"))
    assert len(s2) == 1  # only the fsynced prefix survived
    assert s2.get(0) == b"first"


def test_fsync_tier2_makes_acked_appends_powercut_durable(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("HM_FSYNC", "2")
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    marks = []
    with F.activate(recorder=rec):
        s = FileFeedStorage(str(work / "ab" / "feed"))
        for i in range(4):
            s.append(b"durable-%d" % i)
            marks.append((rec.n_points - 1, i + 1))
    for k, acked in marks:
        dst = str(tmp_path / f"p{k}")
        rec.materialize(dst, k, powercut=True)
        s2 = FileFeedStorage(os.path.join(dst, "ab", "feed"))
        assert len(s2) >= acked  # every acked append survived the cut
        for i in range(acked):
            assert s2.get(i) == b"durable-%d" % i


def test_fsync_lie_is_visible_to_powercut_only(tmp_path, monkeypatch):
    monkeypatch.setenv("HM_FSYNC", "2")
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    plan = F.DiskFaultPlan(seed=0, fsync_lie_p=1.0)
    with F.activate(plan=plan, recorder=rec):
        s = FileFeedStorage(str(work / "ab" / "feed"))
        s.append(b"claimed-durable")  # the fsync LIED
    k = rec.n_points - 1
    rec.materialize(str(tmp_path / "cut"), k, powercut=True)
    s2 = FileFeedStorage(str(tmp_path / "cut/ab/feed"))
    assert len(s2) == 0  # the lie dropped the bytes at the cut
    s2.append(b"heal")  # and reopen still heals
    assert len(s2) == 1
    assert plan.stats["fsync_lies"] >= 1


def test_fsync_eio_surfaces(tmp_path, monkeypatch):
    monkeypatch.setenv("HM_FSYNC", "2")
    plan = F.DiskFaultPlan(seed=0, fsync_error_p=1.0)
    s = _mk_storage(tmp_path)
    with F.activate(plan=plan):
        with pytest.raises(OSError):
            s.append(b"x")


def test_group_fsync_tier1_barrier(tmp_path, monkeypatch):
    """Tier 1: appends mark dirty; the durability barrier fsyncs every
    dirty log, so sqlite rows committed after it can never describe
    unfsynced bytes."""
    from hypermerge_tpu.storage.durability import DurabilityManager

    monkeypatch.setenv("HM_FSYNC", "1")
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    dm = DurabilityManager()
    with F.activate(recorder=rec):
        s = FileFeedStorage(
            str(work / "ab" / "feed"), durability=dm
        )
        s.append(b"one")
        s.append(b"two")
        dm.barrier()  # the pre-sqlite sync point
        mark = rec.n_points
        s.append(b"three")  # dirty again, not yet synced
    dm.close()
    rec.materialize(str(tmp_path / "cut"), mark, powercut=True)
    s2 = FileFeedStorage(str(tmp_path / "cut/ab/feed"))
    assert len(s2) == 2  # everything before the barrier survived


# ---------------------------------------------------------------------------
# sqlite-vs-feed reconciliation + recovery-on-open wiring


def _mk_repo_with_doc(path, n_edits=5):
    from hypermerge_tpu.repo import Repo

    repo = Repo(path=str(path))
    url = repo.create({"edits": []})
    for i in range(n_edits):
        repo.change(url, lambda d, i=i: d["edits"].append(i))
    if repo.back.live is not None:
        repo.back.live.flush_now()
    return repo, url


def test_clocks_ahead_of_feeds_reconciled_on_open(tmp_path):
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    repo, url = _mk_repo_with_doc(tmp_path / "r")
    doc_id = validate_doc_url(url)
    actor = max(
        repo.back.docs[doc_id].clock.items(), key=lambda kv: kv[1]
    )[0]
    repo.close()

    # clocks-ahead skew: drop the feed's last two blocks out-of-band
    # (the unrecoverable direction a power cut can produce), then mark
    # the repo crashed so recovery runs on open
    feed_path = str(tmp_path / "r" / "feeds" / actor[:2] / actor)
    s = FileFeedStorage(feed_path)
    n = len(s)
    s.truncate_to(n - 2)
    open(str(tmp_path / "r" / "repo.dirty"), "wb").close()

    repo2 = Repo(path=str(tmp_path / "r"))
    try:
        rep = repo2.back.recovery_report
        assert rep is not None and rep["clock_rows_clamped"] >= 1, rep
        assert (
            repo2.back.clocks.get(repo2.back.id, doc_id)[actor] == n - 2
        )
        h = repo2.open(url)
        v = h.value(timeout=30)
        edits = v.get("edits", [])
        # a gapless prefix of the acknowledged edits
        assert list(edits) == list(range(len(edits)))
        from hypermerge_tpu.storage.scrub import last_report

        assert last_report(str(tmp_path / "r")) is not None
    finally:
        repo2.close()


def test_clean_close_skips_recovery(tmp_path):
    from hypermerge_tpu.repo import Repo

    repo, url = _mk_repo_with_doc(tmp_path / "r")
    repo.close()
    assert not os.path.exists(str(tmp_path / "r" / "repo.dirty"))
    repo2 = Repo(path=str(tmp_path / "r"))
    try:
        assert repo2.back.recovery_report is None
        assert os.path.exists(str(tmp_path / "r" / "repo.dirty"))
    finally:
        repo2.close()


def test_actor_keys_persist_across_reopen(tmp_path):
    """Writable actors stay writable across restarts — the crashed
    session's feed can be sealed AND extended (no per-session actor
    churn, no permanently unreplicable unsigned tail)."""
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    repo, url = _mk_repo_with_doc(tmp_path / "r", n_edits=3)
    doc_id = validate_doc_url(url)
    actors_before = set(repo.back.cursors.get(repo.back.id, doc_id))
    repo.close()
    repo2 = Repo(path=str(tmp_path / "r"))
    try:
        h = repo2.open(url)
        assert h.value(timeout=30) is not None
        repo2.change(url, lambda d: d["edits"].append(99))
        if repo2.back.live is not None:
            repo2.back.live.flush_now()
        doc = repo2.back.docs[doc_id]
        wait_until(lambda: sum(doc.clock.values()) >= 5)
        actors_after = set(
            repo2.back.cursors.get(repo2.back.id, doc_id)
        )
        # the reopened session wrote through an EXISTING actor
        assert actors_after == actors_before
    finally:
        repo2.close()


def test_scrub_seals_unsigned_tail_on_writable_feed(tmp_path):
    """Crash recovery re-signs a writable feed's crash-orphaned lazy-
    signing tail: the next audit is clean with zero block loss."""
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.storage.integrity import AUDIT_OK

    repo, url = _mk_repo_with_doc(tmp_path / "r", n_edits=4)
    # crash: no close(), no seal — writable feeds keep unsigned tails
    # (sign_interval is 1024). Settle debounced flushers first so the
    # on-disk state is complete, then drop the repo without closing.
    repo.back._stores.flush_now()
    repo.back._cache_syncs.flush_now()
    del repo

    repo2 = Repo(path=str(tmp_path / "r"))
    try:
        rep = repo2.back.recovery_report
        assert rep is not None
        assert rep["unsigned_tails_sealed"] >= 1, rep
        for pk in repo2.back.feed_info.all_public_ids():
            feed = repo2.back.feeds.open_feed(pk)
            if feed.length:
                assert feed.audit_status() == AUDIT_OK, pk
    finally:
        repo2.close()


# ---------------------------------------------------------------------------
# whole-repo kill-anywhere matrix


def _sample_points(n, want=14):
    step = max(1, n // want)
    return sorted(set(range(0, n, step)) | {n})


@pytest.mark.parametrize("live", ["1", "0"])
def test_whole_repo_kill_anywhere(tmp_path, monkeypatch, live):
    """Mixed workload under a CrashRecorder; every sampled prefix
    reopens with zero recovery-invariant violations: reopen (incl.
    recovery) never raises, the doc reads back a gapless prefix of the
    acked edits, and the repo stays writable."""
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    monkeypatch.setenv("HM_LIVE", live)
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    acked = []
    with F.activate(recorder=rec):
        repo = Repo(path=str(work))
        url = repo.create({"edits": []})
        for i in range(8):
            repo.change(url, lambda d, i=i: d["edits"].append(i))
            if repo.back.live is not None:
                repo.back.live.flush_now()
            repo.back._stores.flush_now()
            repo.back._cache_syncs.flush_now()
            acked.append((rec.n_points - 1, i + 1))
        k_max = rec.n_points - 1
        repo.close()
    doc_id = validate_doc_url(url)
    for k in _sample_points(k_max):
        dst = str(tmp_path / f"c{k}")
        rec.materialize(dst, k)
        repo2 = Repo(path=dst)  # reopen + recovery: must not raise
        try:
            if doc_id not in repo2.back.clocks.all_doc_ids(
                repo2.back.id
            ):
                continue  # crashed before the doc's first commit
            h = repo2.open(url)
            v = h.value(timeout=30)
            edits = list(v.get("edits", []))
            # gapless prefix of acknowledged state, bounded by the
            # crash point's ack level (+1 for the in-flight edit)
            assert edits == list(range(len(edits))), (k, edits)
            hi = max((m for e, m in acked if e <= k), default=0)
            assert len(edits) <= hi + 1, (k, len(edits), hi)
            # the recovered repo stays writable
            repo2.change(url, lambda d: d["edits"].append(777))
            wait_until(
                lambda: 777 in (repo2.doc(url) or {}).get("edits", [])
            )
        finally:
            repo2.close()


@pytest.mark.parametrize("live", ["1", "0"])
def test_crash_recover_reconverges_with_clean_twin(
    tmp_path, monkeypatch, live
):
    """A crashed-then-recovered repo, resynced against a clean twin
    holding the full acked history, reconverges bit-identically —
    including blocks the recovery truncated (they re-replicate)."""
    from hypermerge_tpu.net.swarm import LoopbackHub, LoopbackSwarm
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    monkeypatch.setenv("HM_LIVE", live)
    hub = LoopbackHub()
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    rb = Repo(memory=True)
    rb.set_swarm(LoopbackSwarm(hub))
    with F.activate(recorder=rec):
        ra = Repo(path=str(work))
        sa = LoopbackSwarm(hub)
        ra.set_swarm(sa)
        url = ra.create({"edits": []})
        hb = rb.open(url)
        assert hb.value(timeout=30) is not None
        for i in range(6):
            ra.change(url, lambda d, i=i: d["edits"].append(i))
            if i % 2 == 0:
                hb.change(lambda d, i=i: d["edits"].append(100 + i))
        want = 6 + 3
        wait_until(
            lambda: len((rb.doc(url) or {}).get("edits", [])) >= want
            and len((ra.doc(url) or {}).get("edits", [])) >= want,
            timeout=60,
        )
        doc_id = validate_doc_url(url)
        twin = plainify(rb.doc(url))
        twin_clock = dict(rb.back.docs[doc_id].clock)
        k_max = rec.n_points - 1
        sa.destroy()
        ra.close()

    for k in _sample_points(k_max, want=3):
        dst = str(tmp_path / f"c{k}")
        rec.materialize(dst, k)
        r2 = Repo(path=dst)
        s2 = LoopbackSwarm(hub)
        try:
            r2.set_swarm(s2)
            h2 = r2.open(url)
            assert h2.value(timeout=60) is not None

            def converged():
                d2 = r2.back.docs.get(doc_id)
                if d2 is None or dict(d2.clock) != twin_clock:
                    return False
                return plainify(r2.doc(url)) == twin

            wait_until(converged, timeout=60)
        finally:
            r2.close()
            s2.destroy()
    rb.close()


def test_durable_tier_repo_acked_edits_survive_powercut(
    tmp_path, monkeypatch
):
    """HM_FSYNC=2 end to end: every edit acked (change + engine/store
    flush) before the cut is present after a POWER-CUT replay."""
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    monkeypatch.setenv("HM_FSYNC", "2")
    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    acked = []
    with F.activate(recorder=rec):
        repo = Repo(path=str(work))
        url = repo.create({"edits": []})
        for i in range(5):
            repo.change(url, lambda d, i=i: d["edits"].append(i))
            if repo.back.live is not None:
                repo.back.live.flush_now()
            repo.back._stores.flush_now()
            repo.back._cache_syncs.flush_now()
            repo.back.durability.flush_now()
            acked.append((rec.n_points - 1, i + 1))
        k_max = rec.n_points - 1
    doc_id = validate_doc_url(url)
    for k, want in [acked[1], acked[3], (k_max, 5)]:
        dst = str(tmp_path / f"p{k}")
        rec.materialize(dst, k, powercut=True)
        repo2 = Repo(path=dst)
        try:
            assert doc_id in repo2.back.clocks.all_doc_ids(
                repo2.back.id
            ), k
            h = repo2.open(url)
            v = h.value(timeout=30)
            edits = list(v.get("edits", []))
            assert edits[:want] == list(range(want)), (k, want, edits)
        finally:
            repo2.close()


@pytest.mark.slow
@pytest.mark.parametrize("live", ["1", "0"])
def test_multi_cycle_crash_recover_soak(tmp_path, monkeypatch, live):
    """Crash -> recover -> keep editing -> crash again, several cycles:
    recovery must compose with itself (a recovered repo is a normal
    repo), and the doc stays a gapless prefix throughout."""
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    import shutil

    monkeypatch.setenv("HM_LIVE", live)
    path = tmp_path / "r0"
    url = None
    next_val = 0
    for cycle in range(4):
        # snapshot the pre-workload state: cycle N's replay overlays
        # its events onto what cycle N-1's recovery produced
        base = None
        if os.path.exists(str(path)):
            base = str(tmp_path / f"base{cycle}")
            shutil.copytree(str(path), base)
        rec = F.CrashRecorder(str(path))
        with F.activate(recorder=rec):
            repo = Repo(path=str(path))
            if url is None:
                url = repo.create({"edits": []})
            else:
                h = repo.open(url)
                v = h.value(timeout=30)
                edits = list(v.get("edits", []))
                assert edits == list(range(len(edits))), (cycle, edits)
                next_val = len(edits)
            for i in range(5):
                repo.change(
                    url,
                    lambda d, v=next_val + i: d["edits"].append(v),
                )
            if repo.back.live is not None:
                repo.back.live.flush_now()
            repo.back._stores.flush_now()
            repo.back._cache_syncs.flush_now()
            k_max = rec.n_points - 1
            repo.close()
        # crash at a seeded mid-workload boundary; the recovered dir
        # REPLACES the repo for the next cycle — recovery must rewrite
        # any state the truncation invalidated, because cycle N+1
        # starts from what cycle N's recovery produced.
        import random

        k = random.Random(cycle).randrange(k_max // 2, k_max + 1)
        nxt = tmp_path / f"r{cycle + 1}"
        rec.materialize(str(nxt), k, base=base)
        path = nxt
    repo = Repo(path=str(path))
    try:
        h = repo.open(url)
        v = h.value(timeout=30)
        edits = list(v.get("edits", []))
        assert edits == list(range(len(edits)))
        repo.change(url, lambda d: d["edits"].append(999))
        wait_until(
            lambda: 999 in (repo.doc(url) or {}).get("edits", [])
        )
    finally:
        repo.close()


# ---------------------------------------------------------------------------
# anti-entropy sweep (net/replication.py HM_ANTIENTROPY_S)


def test_antientropy_sweep_recovers_lost_tail_frames(monkeypatch):
    """App-layer frame loss on a SURVIVING connection: the gap-driven
    protocol would only recover at the next tail flush or reconnect;
    the anti-entropy FeedLength re-announce bounds it by the sweep."""
    from hypermerge_tpu.net.faults import FaultPlan, FaultSwarm
    from hypermerge_tpu.net.swarm import LoopbackHub, LoopbackSwarm
    from hypermerge_tpu.repo import Repo

    monkeypatch.setenv("HM_ANTIENTROPY_S", "3600")  # manual sweeps
    hub = LoopbackHub()
    plan = FaultPlan(seed=1, events=[(1, "partition_rx"), (2, "heal")])
    ra, rb = Repo(memory=True), Repo(memory=True)
    fb = FaultSwarm(LoopbackSwarm(hub), plan)
    try:
        ra.set_swarm(LoopbackSwarm(hub))
        rb.set_swarm(fb)
        url = ra.create({"edits": []})
        hb = rb.open(url)
        assert hb.value(timeout=30) is not None
        ra.change(url, lambda d: d["edits"].append(0))
        wait_until(
            lambda: len((rb.doc(url) or {}).get("edits", [])) == 1
        )
        fb.tick()  # partition_rx: frames TO b silently drop
        for i in range(1, 4):
            ra.change(url, lambda d, i=i: d["edits"].append(i))
        # drain EVERY debounced sender while the partition still eats
        # frames: a gossip flush landing after the heal would recover
        # b without the sweep (and flake this test)
        ra.back.network.replication.flush_now()
        ra.back._gossip.flush_now()
        ra.back._stores.flush_now()
        fb.tick()  # heal — but the tail frames are already lost
        import time

        time.sleep(0.2)
        assert len((rb.doc(url) or {}).get("edits", [])) == 1  # stale
        sent = ra.back.network.replication.sweep_now()
        assert sent >= 1
        wait_until(
            lambda: len((rb.doc(url) or {}).get("edits", [])) == 4,
            timeout=30,
        )
    finally:
        ra.close()
        rb.close()
        fb.destroy()


def test_antientropy_timer_runs_sweeps(monkeypatch):
    from hypermerge_tpu.net.swarm import LoopbackHub, LoopbackSwarm
    from hypermerge_tpu.repo import Repo

    monkeypatch.setenv("HM_ANTIENTROPY_S", "0.05")
    hub = LoopbackHub()
    ra, rb = Repo(memory=True), Repo(memory=True)
    try:
        ra.set_swarm(LoopbackSwarm(hub))
        rb.set_swarm(LoopbackSwarm(hub))
        url = ra.create({"n": 1})
        assert rb.open(url).value(timeout=30) is not None
        wait_until(
            lambda: ra.back.network.replication.stats[
                "antientropy_sweeps"
            ]
            >= 2,
            timeout=30,
        )
    finally:
        ra.close()
        rb.close()


# ---------------------------------------------------------------------------
# review regressions: marker durability, barrier failure, dry-run report


def test_dirty_marker_survives_powercut(tmp_path):
    """The crash marker is fsynced at open: even a power cut cannot
    erase it, so the reopen after one always runs recovery (tier 0
    depends on that to reconcile clocks with feeds)."""
    from hypermerge_tpu.repo import Repo

    work = tmp_path / "work"
    rec = F.CrashRecorder(str(work))
    with F.activate(recorder=rec):
        repo = Repo(path=str(work))
        url = repo.create({"n": 1})
        if repo.back.live is not None:
            repo.back.live.flush_now()
        repo.back._stores.flush_now()
        k_max = rec.n_points - 1
        # crash: no close
    dst = str(tmp_path / "cut")
    rec.materialize(dst, k_max, powercut=True)
    assert os.path.exists(os.path.join(dst, "repo.dirty"))
    repo2 = Repo(path=dst)
    try:
        assert repo2.back.recovery_report is not None
    finally:
        repo2.close()


def test_durability_barrier_raises_on_fsync_error(
    tmp_path, monkeypatch
):
    """A failed group fsync must SURFACE from barrier(): the store
    flusher must not commit clock rows for bytes that never reached
    the platter (the debouncer re-queues and retries)."""
    from hypermerge_tpu.storage.durability import DurabilityManager

    monkeypatch.setenv("HM_FSYNC", "1")
    dm = DurabilityManager()
    s = FileFeedStorage(
        str(tmp_path / "ab" / "feed"), durability=dm
    )
    s.append(b"one")
    plan = F.DiskFaultPlan(seed=0, fsync_error_p=1.0)
    with F.activate(plan=plan):
        with pytest.raises(OSError):
            dm.barrier()
    # the storage stayed dirty: a later barrier (fault cleared)
    # makes it durable
    assert dm.sync_now() >= 1 or dm.barrier() is None
    dm.close()


def test_dry_run_reports_would_do_repairs(tmp_path, monkeypatch):
    """recover_repo(repair=False) must report seals/truncations/sig
    repairs it WOULD perform — without touching disk."""
    from hypermerge_tpu.backend.repo_backend import RepoBackend
    from hypermerge_tpu.storage.scrub import recover_repo

    repo, url = _mk_repo_with_doc(tmp_path / "r", n_edits=4)
    repo.back._stores.flush_now()
    repo.back._cache_syncs.flush_now()
    del repo  # crash: unsigned tails remain

    monkeypatch.setenv("HM_RECOVER", "0")
    back = RepoBackend(path=str(tmp_path / "r"))
    try:
        dry = recover_repo(back, repair=False)
        assert dry["unsigned_tails_sealed"] >= 1, dry
        assert dry["per_feed"], dry
        # nothing was written: a second dry run sees the same damage
        again = recover_repo(back, repair=False)
        assert (
            again["unsigned_tails_sealed"]
            == dry["unsigned_tails_sealed"]
        )
        real = recover_repo(back, repair=True)
        assert real["unsigned_tails_sealed"] >= 1
        after = recover_repo(back, repair=False)
        assert after["unsigned_tails_sealed"] == 0, after
    finally:
        back.close()
