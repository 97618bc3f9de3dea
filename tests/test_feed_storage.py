"""FileFeedStorage: block-count index shortcut + torn-tail healing."""

import errno
import os
import struct

import pytest

from hypermerge_tpu import telemetry
from hypermerge_tpu.storage import faults
from hypermerge_tpu.storage.feed import FileFeedStorage, MemoryFeedStorage


def _mk(tmp_path, blocks):
    path = str(tmp_path / "ab" / "feed")
    s = FileFeedStorage(path)
    for b in blocks:
        s.append(b)
    return path


def test_len_index_shortcut(tmp_path):
    path = _mk(tmp_path, [b"one", b"two", b"three"])
    assert os.path.exists(path + ".len")
    s2 = FileFeedStorage(path)
    assert len(s2) == 3  # count via .len + stat, no scan
    assert not s2._scanned
    assert s2.get(1) == b"two"  # offsets built on demand


def test_stale_len_index_falls_back_to_scan(tmp_path):
    path = _mk(tmp_path, [b"aa", b"bb"])
    with open(path + ".len", "wb") as fh:
        fh.write(struct.pack("<QQ", 99, 12345))  # wrong end offset
    s2 = FileFeedStorage(path)
    assert len(s2) == 2  # mismatch detected -> full scan
    assert s2.get(0) == b"aa"


def test_torn_tail_with_stale_len_heals(tmp_path):
    path = _mk(tmp_path, [b"aa", b"bb"])
    # simulate a crash mid-append: partial block bytes, .len not updated
    with open(path, "ab") as fh:
        fh.write(b"\x50\x00\x00\x00parti")  # claims 80 bytes, has 5
    s2 = FileFeedStorage(path)
    assert len(s2) == 2  # size mismatch -> scan -> torn tail dropped
    # appending over the torn tail truncates it and re-indexes
    s2.append(b"cc")
    s3 = FileFeedStorage(path)
    assert len(s3) == 3
    assert [s3.get(i) for i in range(3)] == [b"aa", b"bb", b"cc"]


def test_legacy_log_without_len_index(tmp_path):
    path = _mk(tmp_path, [b"x", b"y"])
    os.remove(path + ".len")
    s2 = FileFeedStorage(path)
    assert len(s2) == 2  # full scan fallback
    s2.append(b"z")  # append recreates the index
    assert os.path.exists(path + ".len")
    assert len(FileFeedStorage(path)) == 3


# -- a run of blocks in one I/O (ISSUE 42) -----------------------------------

RUNS = {
    "1": [b"only"],
    "2": [b"first", b"second" * 40],
    "32": [bytes([i]) * 1400 for i in range(32)],  # a replicated frame
    "1024": [b"%d" % i * (i % 7) for i in range(1024)],  # HM_REPL_CHUNK
    "empty-blocks": [b"", b"x", b"", b""],
}


def _moved(before):
    after = telemetry.snapshot()
    return {
        k.rsplit(".", 1)[1]: after.get(k, 0) - before.get(k, 0)
        for k in (
            "storage.feed.log_writes", "storage.feed.blocks_written",
            "storage.feed.log_opens", "storage.feed.blocks_read",
        )
    }


def _on_disk(path):
    with open(path, "rb") as fh, open(path + ".len", "rb") as lfh:
        return fh.read(), lfh.read()


def _index(s):
    return list(s._offsets), list(s._sizes), s._end, s._count


@pytest.mark.parametrize("tail", ("clean", "torn-tail"))
@pytest.mark.parametrize("run", sorted(RUNS))
def test_append_many_leaves_what_the_appends_leave(tmp_path, run, tail):
    """The log, `.len` and the in-memory index after ONE append_many
    are byte for byte those of the same blocks appended one by one,
    onto a clean log and onto a torn tail; and it was one write."""
    blocks = RUNS[run]
    stores = []
    for name in ("one-by-one", "many"):
        path = _mk(tmp_path / name, [b"aa", b"bb"])
        if tail == "torn-tail":
            with open(path, "ab") as fh:  # a crash mid-append
                fh.write(struct.pack("<I", 1 << 20) + b"z" * 100_000)
        stores.append(FileFeedStorage(path))
    one, many = stores
    for b in blocks:
        one.append(b)
    before = telemetry.snapshot()
    many.append_many(blocks)
    assert _moved(before) == {
        "log_writes": 1, "blocks_written": len(blocks),
        "log_opens": 0, "blocks_read": 0,
    }
    assert _on_disk(many.path) == _on_disk(one.path)
    assert _index(many) == _index(one)
    fresh = FileFeedStorage(many.path)
    assert len(fresh) == 2 + len(blocks) and not fresh._scanned
    assert fresh.get_range(0, len(fresh)) == [b"aa", b"bb"] + blocks
    many.append(b"next")  # and the log goes on from there
    assert FileFeedStorage(many.path).get(2 + len(blocks)) == b"next"


def test_append_many_of_nothing_touches_nothing(tmp_path):
    s = FileFeedStorage(str(tmp_path / "ab" / "feed"))
    before = telemetry.snapshot()
    s.append_many([])
    assert _moved(before)["log_writes"] == 0
    assert not os.path.exists(s.path) and len(s) == 0


@pytest.mark.parametrize("err", (errno.ENOSPC, errno.EIO))
@pytest.mark.parametrize("fate", ("error", "torn"))
def test_append_many_fault_in_the_one_write(tmp_path, fate, err):
    """ENOSPC / EIO in the middle of the run's one write: memory stays
    at the pre-append end (nothing of the run is there, nothing is
    counted), a fresh open sees the old log, and the next append
    overwrites the torn tail. (A torn write leaves a prefix of the
    run's blocks behind the old end, as a crash in the middle of the
    appends did: the scan of a fresh open admits them, whole.)"""
    path = _mk(tmp_path, [b"aa", b"bb"])
    s = FileFeedStorage(path)
    assert len(s) == 2
    was = (s._end, s._count)
    plan = faults.DiskFaultPlan(
        seed=5, errnos=(err,),
        write_error_p=1.0 if fate == "error" else 0.0,
        torn_write_p=1.0 if fate == "torn" else 0.0,
    )
    before = telemetry.snapshot()
    with faults.activate(plan=plan):
        with pytest.raises(OSError) as failed:
            s.append_many(RUNS["32"])
    assert failed.value.errno == err
    assert (s._end, s._count, len(s._offsets)) == (*was, 2)
    assert _moved(before)["log_writes"] == 0
    left = FileFeedStorage(path)
    assert 2 <= len(left) < 34 and (fate == "torn" or len(left) == 2)
    assert left.get_range(0, 34) == (
        [b"aa", b"bb"] + RUNS["32"])[:len(left)]
    s.append_many(RUNS["2"])  # heals: seeks to the old end, truncates
    fresh = FileFeedStorage(path)
    assert len(fresh) == 4 and not fresh._scanned  # `.len` is right
    assert fresh.get_range(0, 9) == [b"aa", b"bb"] + RUNS["2"]
    assert os.path.getsize(path) == fresh._end


@pytest.mark.parametrize("kind", ("file", "memory"))
def test_get_range_is_the_gets(tmp_path, kind):
    blocks = RUNS["empty-blocks"] + RUNS["32"]
    if kind == "file":
        s = FileFeedStorage(_mk(tmp_path, blocks))
    else:
        s = MemoryFeedStorage()
        s.append_many(blocks)
    n = len(blocks)
    for start, end in ((0, n), (0, 1), (3, 17), (n - 1, n), (5, 5),
                       (n, n + 3), (n - 2, n + 50)):
        before = telemetry.snapshot()
        got = s.get_range(start, end)
        assert got == [s.get(i) for i in range(start, min(end, n))]
        assert s.block_sizes(start, end) == [len(b) for b in got]
        if kind == "file":  # one open a range (the gets: one each)
            moved = _moved(before)
            assert moved["log_opens"] == bool(got) + len(got)
            assert moved["blocks_read"] == 2 * len(got)


def test_get_range_stops_where_the_log_does(tmp_path):
    """`.len` promises three blocks and the log's size agrees, but the
    second header is torn: the scan parses one block, `get` beyond it
    raises, and a range gives the blocks there are."""
    from hypermerge_tpu.storage.feed import Feed

    path = _mk(tmp_path, [b"aa", b"bb", b"cc"])
    with open(path, "r+b") as fh:
        fh.seek(4 + 2)
        fh.write(struct.pack("<I", 1 << 20))
    s = FileFeedStorage(path)
    assert len(s) == 3  # `.len` and the stat agree
    assert s.get_range(0, 3) == [b"aa"] and s.block_sizes(0, 3) == [2]
    assert s.get_range(1, 3) == [] and s.block_sizes(1, 3) == []
    with pytest.raises(IndexError):
        s.get(1)
    feed = Feed("k" * 44, s)
    assert feed.get_batch(0, 3) == [b"aa"] == feed.read_all()
    assert feed.block_sizes(0, 3) == [2]
