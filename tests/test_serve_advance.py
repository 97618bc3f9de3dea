"""A resident entry follows local changes in place (ISSUE 38): for
seeded streams of local changes every query kind answers the same from
the advanced entry as from a fresh `build_group` at the same clock and
as `benchmark/reference/rw_plain.py` (which imports nothing of the
program) replaying the blocks on disk; whatever the entry cannot follow
falls to `mark_stale` and still answers right; a page's shared tables
are never written. CPU, small sizes, seeded; counts and answers only.
"""

import os
import random
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import read_plain, rw_plain  # noqa: E402
from benchmark.reference.crdt_plain import _apply  # noqa: E402
from hypermerge_tpu import telemetry  # noqa: E402
from hypermerge_tpu.models import Counter, Text  # noqa: E402
from hypermerge_tpu.repo import Repo  # noqa: E402
from hypermerge_tpu.serve import kernels, resident  # noqa: E402
from hypermerge_tpu.serve.batcher import ReadRequest  # noqa: E402
from hypermerge_tpu.utils.ids import validate_doc_url  # noqa: E402
from test_rw_cell import (  # noqa: E402
    SEED, corpus_changes, group, pin_ladder, serve, write_corpus,
)
from test_serve_bulk_install import flush_together  # noqa: E402

TEXT = {"kind": "text", "path": ["t"]}


def queries(n):
    """Every query kind, every root key, the ends of a text of n."""
    out = [TEXT, {"kind": "len", "path": ["t"]}, {"kind": "len", "path": []},
           {"kind": "lookup", "path": ["t"]},
           {"kind": "lookup", "path": ["nope"]},
           {"kind": "index", "path": ["t"], "index": n}]
    out += [{"kind": "lookup", "path": [f"k{k}"]} for k in range(10)]
    out += [{"kind": "index", "path": ["t"], "index": i}
            for i in sorted({0, n // 2, max(0, n - 1)})]
    return out


def entry_of(repo, url):
    return repo.back.serve._cache._entries.get(validate_doc_url(url))


def answers_of(tier, entry, qs):
    """The answers the tier's own resolution gives over `entry`."""
    got = [None] * len(qs)
    reqs = []
    for i, q in enumerate(qs):
        r = ReadRequest(entry.doc_id, dict(q),
                        lambda p, i=i: got.__setitem__(i, p["value"]))
        r.t0 = time.perf_counter()
        reqs.append(r)
    ready = []
    tier._attach(entry, reqs, ready)
    tier._resolve(ready)
    return got


def fresh_entry(repo, url):
    """What an install would build for the doc right now."""
    back = repo.back
    doc = back.docs[validate_doc_url(url)]
    clock = doc.clock
    spec = back._serveable_spec(clock)
    (entry,) = resident.build_group(
        back, [(doc.id, clock, spec)], resident.rung_of(spec),
        lambda name, n: None,
    )
    return entry


def order_of(entry):
    """{object row: its live element rows in order} off the lanes."""
    lanes = np.asarray(entry.dev)[:, : entry.n]
    out = {}
    elems = (lanes[kernels.L_INSERT] == 1) & (lanes[kernels.L_LIVE] != 0)
    for obj in set(lanes[kernels.L_OBJ][elems].tolist()):
        rows = np.nonzero(elems & (lanes[kernels.L_OBJ] == obj))[0]
        out[obj] = rows[
            np.argsort(-lanes[kernels.L_RANK][rows], kind="stable")
        ].tolist()
    return out


def assert_same_lanes(advanced, fresh):
    """The advanced entry holds what a re-install would: the same rows
    (a local op is the newest of its doc, so both number it last), the
    same structural lanes, the same order of every object's live
    elements (the ranks themselves differ: an install counts nodes to
    the chain's end, an advance makes room), the same host half."""
    assert (advanced.n, advanced.clock) == (fresh.n, fresh.clock)
    a = np.asarray(advanced.dev)[:, : advanced.n]
    f = np.asarray(fresh.dev)[:, : fresh.n]
    for lane in (kernels.L_LIVE, kernels.L_OBJ, kernels.L_INSERT,
                 kernels.L_MAPWIN):
        assert (a[lane] == f[lane]).all(), lane

    def keys(e, lane):  # a page numbers its keys its own way
        names = {i: k for k, i in e.key_index.items()}
        return [names.get(i) for i in lane.tolist()]
    assert keys(advanced, a[kernels.L_KEY]) == keys(fresh, f[kernels.L_KEY])
    assert order_of(advanced) == order_of(fresh)
    for name in ("action", "vkind", "dt", "ctr", "inc_total", "elem_val"):
        assert (getattr(advanced, name) == getattr(fresh, name)).all(), name
    def acts(e):  # a page numbers its actors its own way too
        names = {i: a for a, i in e.actors.items()}
        return [names[i] for i in e.actor.tolist()]
    assert acts(advanced) == acts(fresh)


VALUES = [7, 10**6 + 17, -40000, 2**40 + 5, 2.5, True, None, "k",
          "a string no table holds", "é"]


def step(rng, state, j):
    """One seeded local change as (fn over the doc, ops it makes)."""
    n = state["n"]
    u = rng.random()
    ch = chr(97 + j % 26)
    if u < 0.15:
        state["n"], state["prev"] = n + 1, 0
        return lambda d: d["t"].insert(0, ch)
    if u < 0.45:
        at = min(state["prev"] + 1, n)
        state["n"], state["prev"] = n + 1, at
        return lambda d: d["t"].insert(at, ch)
    if u < 0.6:
        at = rng.randrange(n + 1)
        state["n"], state["prev"] = n + 1, at
        return lambda d: d["t"].insert(at, ch)
    if u < 0.66:  # two characters in ONE change, the second after the first
        at = rng.randrange(n + 1)
        state["n"], state["prev"] = n + 2, at + 1
        return lambda d: d["t"].insert(at, ch + ch.upper())
    if u < 0.72:  # a character and a key in one change: two objects
        at = rng.randrange(n + 1)
        state["n"], state["prev"] = n + 1, at
        key = f"k{rng.randrange(10)}"

        def both(d):
            d["t"].insert(at, ch)
            d[key] = j
        return both
    if u < 0.82 and n:
        at = rng.randrange(n)
        state["n"], state["prev"] = n - 1, max(0, at - 1)
        return lambda d: d["t"].delete(at)
    key, v = f"k{rng.randrange(10)}", VALUES[rng.randrange(len(VALUES))]
    return lambda d: d.__setitem__(key, v)


@pytest.mark.parametrize("memo", ["memo", "kernel"])
def test_advanced_entry_equals_reinstall_and_reference(
    tmp_path, monkeypatch, memo
):
    """Seeded streams (insert at the head / after the previous / at a
    drawn place, two ops a change, two objects a change, DEL, SET of
    each root key with every kind of value, runs of 1-70 writes between
    reads) over a doc installed from the loader's memo (pseudo-ranks)
    or by the slab program (list ranks) through the prefix pack, then,
    once its bucket is full, one rung up through the general pack."""
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "0")
    if memo == "kernel":
        monkeypatch.setenv("HM_SUMMARY_MEMO_MB", "0")
    pin_ladder(monkeypatch)
    job, urls = write_corpus(tmp_path, [group(2, 100), group(1, 256)])
    repo = Repo(path=job.path)
    tier = repo.back.serve
    asked = {u: [] for u in urls}  # (changes made before, query, answer)
    c0 = {k: serve(k) for k in (
        "advances", "advance_dispatches", "advance_refusals", "reinstalls",
        "rung_promotions", "fallbacks", "flush_errors",
        "install_host_kernel_docs")}
    try:
        repo.open_many(urls)
        repo.back.fetch_bulk_summaries()
        flush_together(repo, urls, {"kind": "len", "path": ["t"]})
        assert serve("memo_hits") > 0 or memo == "kernel"
        for d, url in enumerate(urls):
            rng = random.Random(SEED + d)
            state = {"n": repo.read(url, {"kind": "len", "path": ["t"]}),
                     "prev": 0}
            made = 0
            while made < 330:
                for _ in range(rng.randrange(1, 71)):
                    repo.change(url, step(rng, state, made), f"u{made}")
                    made += 1
                qs = queries(state["n"])
                got = [repo.read(url, q) for q in qs]
                assert got[1] == state["n"]
                entry = entry_of(repo, url)
                fresh = fresh_entry(repo, url)
                assert got == answers_of(tier, fresh, qs), (url, made)
                assert_same_lanes(entry, fresh)
                asked[url] += [(made, q, a) for q, a in zip(qs, got)]
    finally:
        repo.close()
    moved = {k: serve(k) - v for k, v in c0.items()}
    assert moved["advances"] > 600 and moved["advance_dispatches"] > 10
    # a run of over 64 ops, a full bucket: released, installed again
    assert moved["rung_promotions"] >= 3
    assert set(tier.refusals) >= {"full", "cap"}
    assert moved["reinstalls"] == moved["advance_refusals"] == sum(
        tier.refusals.values())
    assert (moved["fallbacks"], moved["flush_errors"],
            moved["install_host_kernel_docs"]) == (0, 0, 0)
    # the reference: the blocks on disk, decoded and replayed by itself
    keys = [p.public_key for p in job.pairs]
    feeds = rw_plain.local_feeds(job.path + "/feeds", keys)
    for d, url in enumerate(urls):
        objs = read_plain.replay_objs(corpus_changes(job, d))
        reads = iter(asked[url])
        nxt = next(reads)
        for k, c in enumerate([None] + feeds[keys[d]]):
            if c is not None:
                for i, op in enumerate(c["ops"]):
                    _apply(objs, (c["startOp"] + i, c["actor"]), op)
            while nxt is not None and nxt[0] == k:
                assert read_plain.evaluate(objs, nxt[1]) == nxt[2], nxt[:2]
                nxt = next(reads, None)
        assert nxt is None


def test_what_an_entry_cannot_follow_is_released_and_answers_right(
    monkeypatch,
):
    """Each refusal reason: the write takes `mark_stale`, the next read
    installs and is right."""
    pin_ladder(monkeypatch)
    repo = Repo(memory=True)
    tier = repo.back.serve
    try:
        url = repo.create({"n": 0, "c": Counter(1), "l": [1, 2]})
        repo.change(url, lambda d: d.__setitem__("t", Text("ab")))

        def refused(why, fn, query, want):
            assert repo.read(url, {"kind": "len", "path": []}) is not None
            assert entry_of(repo, url) is not None
            r0 = {k: serve(k) for k in (
                "advance_refusals", "invalidations", "reinstalls")}
            before = tier.refusals.get(why, 0)
            fn()
            assert entry_of(repo, url) is None, why
            assert tier.refusals.get(why, 0) == before + 1, tier.refusals
            assert repo.read(url, query) == want, why
            assert {k: serve(k) - v for k, v in r0.items()} == {
                "advance_refusals": 1, "invalidations": 1, "reinstalls": 1}

        ch = lambda fn: (lambda: repo.change(url, fn))
        # a key the page's key table does not hold
        refused("key", ch(lambda d: d.__setitem__("fresh", 1)),
                {"kind": "lookup", "path": ["fresh"]}, 1)
        # shapes with no closed form: MAKE, INC, a SET on an element, a
        # DEL of a key, an object made in a list
        refused("shape", ch(lambda d: d.__setitem__("n", {"x": 1})),
                {"kind": "lookup", "path": ["n", "x"]}, 1)
        refused("shape", ch(lambda d: d.increment("c", 4)),
                {"kind": "lookup", "path": ["c"]}, 5)
        refused("shape", ch(lambda d: d["l"].__setitem__(0, 9)),
                {"kind": "index", "path": ["l"], "index": 0}, 9)
        refused("shape", ch(lambda d: d.__delitem__("n")),
                {"kind": "lookup", "path": ["n"]}, None)
        refused("shape", ch(lambda d: d["l"].insert(0, [5])),
                {"kind": "index", "path": ["l", 0], "index": 0}, 5)
        # a remote patch: a fork's edit merged back
        other = repo.fork(url)
        repo.change(other, lambda d: d["t"].insert(2, "Z"))
        refused("remote", lambda: repo.merge(url, other), TEXT, "abZ")
        # over the cap of noted ops, in one change
        refused("cap", ch(lambda d: d["t"].insert(0, "x" * 65)),
                {"kind": "len", "path": ["t"]}, 68)
        # followed all the same: the shapes that have one
        r0 = serve("advance_refusals")
        repo.change(url, lambda d: d["t"].insert(1, "q"))
        repo.change(url, lambda d: d.__setitem__("fresh", "v"))
        repo.change(url, lambda d: d["l"].__delitem__(1))
        repo.change(url, lambda d: d["l"].append(3))
        assert repo.read(url, {"kind": "lookup", "path": ["fresh"]}) == "v"
        assert repo.read(url, {"kind": "len", "path": ["t"]}) == 69
        assert repo.read(url, {"kind": "len", "path": ["l"]}) == 3
        assert repo.read(
            url, {"kind": "index", "path": ["l"], "index": 2}) == 3
        assert serve("advance_refusals") == r0
    finally:
        repo.close()


def test_a_full_bucket_and_a_missed_clock_are_released(tmp_path, monkeypatch):
    """`full`: no row left at the rung. `clock`: the entry is not at
    the clock the change was made at (it missed a change)."""
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "0")
    pin_ladder(monkeypatch)
    job, urls = write_corpus(tmp_path, [group(1, 64), group(1, 48)])
    repo = Repo(path=job.path)
    tier = repo.back.serve
    try:
        repo.open_many(urls)
        repo.back.fetch_bulk_summaries()
        flush_together(repo, urls, {"kind": "len", "path": ["t"]})
        full, missed = urls
        assert (entry_of(repo, full).n, entry_of(repo, full).bucket) == (
            64, 64)
        before = repo.read(full, TEXT)
        repo.change(full, lambda d: d["t"].insert(0, "Z"))
        assert tier.refusals == {"full": 1}
        assert repo.read(full, TEXT) == "Z" + before
        assert entry_of(repo, full).bucket == 256
        # a change the hook never heard of: the next one finds the
        # entry a clock behind and releases it
        hook = tier.note_clock_moved
        monkeypatch.setattr(tier, "note_clock_moved", lambda *a, **k: None)
        before = repo.read(missed, TEXT)
        repo.change(missed, lambda d: d["t"].insert(0, "A"))
        monkeypatch.setattr(tier, "note_clock_moved", hook)
        assert entry_of(repo, missed).n == 48
        repo.change(missed, lambda d: d["t"].insert(0, "B"))
        assert tier.refusals == {"full": 1, "clock": 1}
        assert repo.read(missed, TEXT) == "BA" + before
    finally:
        repo.close()


def test_a_neighbour_s_page_tables_are_never_written(tmp_path, monkeypatch):
    """Two docs installed in one page share its tables; an advance that
    needs a string, a float, a big integer and an actor they do not
    hold copies them for its entry alone."""
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "0")
    pin_ladder(monkeypatch)
    job, urls = write_corpus(tmp_path, [group(2, 48)])
    repo = Repo(path=job.path)
    try:
        repo.open_many(urls)
        repo.back.fetch_bulk_summaries()
        flush_together(repo, urls, {"kind": "len", "path": ["t"]})
        mine, other = (entry_of(repo, u) for u in urls)
        page = other.tables
        assert mine.tables is page and not page.owned
        held = (list(page.strings), list(page.floats), list(page.bigints),
                page.chars.tolist(), dict(other.actors),
                dict(mine.key_index))
        assert mine.actors is other.actors
        text = repo.read(urls[0], TEXT)
        repo.change(urls[0], lambda d: d["t"].insert(0, "☃"))
        repo.change(urls[0], lambda d: d.__setitem__("k1", 0.125))
        repo.change(urls[0], lambda d: d.__setitem__("k2", 2**50))
        repo.change(urls[0], lambda d: d.__setitem__("k3", "not a char"))
        assert repo.read(urls[0], TEXT) == "☃" + text
        assert [repo.read(urls[0], {"kind": "lookup", "path": [k]})
                for k in ("k1", "k2", "k3")] == [0.125, 2**50, "not a char"]
        assert serve("advances") >= 4
        assert entry_of(repo, urls[0]) is mine  # followed, not rebuilt
        assert mine.tables is not page and mine.tables.owned
        assert mine.tables.strings[-2:] == ["☃", "not a char"]
        assert len(mine.actors) == len(held[4]) + 1
        assert mine.actors is not other.actors
        assert entry_of(repo, urls[1]).tables is page and not page.owned
        assert (list(page.strings), list(page.floats), list(page.bigints),
                page.chars.tolist(), dict(other.actors),
                dict(other.key_index)) == held
        # the neighbour still reads through them
        assert repo.read(urls[1], TEXT) is not None
    finally:
        repo.close()


def test_one_advance_program_a_row_bucket_traced_once():
    """One program a row bucket, traced once: a short run of ops is
    padded to it, a long one takes it several times."""
    import jax.numpy as jnp

    from hypermerge_tpu.parallel import sharded

    N = 128  # no other test's bucket
    lanes = np.zeros((kernels.N_LANES, N), np.int32)
    lanes[kernels.L_OBJ] = -3
    # row 0: a text made at the root; rows 1, 2: its elements "a" "b"
    lanes[kernels.L_OBJ, :3] = (-1, 0, 0)
    lanes[kernels.L_INSERT, 1:3] = 1
    lanes[kernels.L_LIVE, 1:3] = 1
    lanes[kernels.L_RANK, :3] = (3, 2, 1)
    lanes[kernels.L_KEY] = -1
    lanes[kernels.L_KEY, 0] = 0
    lanes[kernels.L_MAPWIN, 0] = 1
    desc = np.asarray([
        (kernels.D_INSERT, 3, 0, 1, -1),   # after row 1
        (kernels.D_INSERT, 4, 0, -1, -1),  # at the head
        (kernels.D_DEL, 5, 0, 2, -1),      # row 2 removed
        (kernels.D_SET, 6, -1, -1, 0),     # the root key again
    ], np.int32)

    def order(out):
        live = (out[kernels.L_INSERT] == 1) & (out[kernels.L_LIVE] != 0)
        rows = np.nonzero(live)[0]
        return rows[np.argsort(-out[kernels.L_RANK][rows])].tolist()

    out = np.asarray(kernels.advance(jnp.asarray(lanes), desc))
    assert order(out) == [4, 1, 3]
    assert np.nonzero(out[kernels.L_MAPWIN])[0].tolist() == [6]
    assert out[kernels.L_OBJ, 3:7].tolist() == [0, 0, 0, -1]
    assert (out[:, 7:] == lanes[:, 7:]).all()
    # 21 more ops, each after the one before: three calls, no trace
    run = np.asarray(
        [(kernels.D_INSERT, r, 0, r - 1, -1) for r in range(7, 28)],
        np.int32)
    assert 2 * kernels.ADVANCE_OPS < len(run) <= 3 * kernels.ADVANCE_OPS
    out = np.asarray(kernels.advance(jnp.asarray(out), run))
    assert order(out) == [4, 1, 3] + list(range(7, 28))
    key = ("serve", "advance", kernels.ADVANCE_OPS, N)
    assert sharded.trace_counts[key] == 1
    assert [k for k in sharded.trace_counts
            if k[:2] == ("serve", "advance") and k[3] == N] == [key]
