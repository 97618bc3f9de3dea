"""Slab-granular column load (storage/colcache.py load_slab_images).

A bulk open loads the column sidecars of a whole doc chunk in one pass
over `cols.slab`: views of the mapping instead of a bytes copy and a
parse per feed. It must be a pure change of HOW: every FeedColumns
equals, field by field, what the per-feed loader (`_ensure_loaded` +
`columns()`) gives; every feed the pass cannot take (a v2 tail, several
segments, a legacy `.cols2`, memory storage, a torn image, a sidecar
ahead of or behind its feed) goes down the per-feed path in the same
chunk and the counters say so; and the mapping the views point into
outlives every append to, and the close of, the slab.
"""

import ctypes
import json
import random
import shutil

import numpy as np
import pytest

from helpers import Site, opset_replay_state, plainify, random_mutation
from hypermerge_tpu import native, telemetry
from hypermerge_tpu.crdt.change import ROOT, Action, Change, Op, OpId
from hypermerge_tpu.ops.columnar import COLUMNS, pack_docs_columns
from hypermerge_tpu.ops.corpus import make_corpus
from hypermerge_tpu.repo import Repo
from hypermerge_tpu.storage import colcache
from hypermerge_tpu.storage.colcache import (
    PLANE_NAMES,
    FeedColumnCache,
    MemoryColumnStorage,
    SlabColumnStorage,
    file_column_storage_fn,
    load_slab_images,
    pack_v2_record,
    pack_v3_checkpoint,
    parse_v3_checkpoint,
)
from hypermerge_tpu.storage.slab import KIND_IMAGE, KIND_RECORD, CorpusSlab
from hypermerge_tpu.utils.ids import root_actor_id, validate_doc_url

INF = float("inf")


# ---------------------------------------------------------------------------
# storage level: load_slab_images against the per-feed loader


def _history(seed, actor="actor00", n_mut=15):
    r = random.Random(seed)
    site = Site(actor)
    for _ in range(n_mut):
        random_mutation(site, r)
    return list(site.opset.history)


def _set_change(actor, seq, key, value):
    return Change(
        actor=actor, seq=seq, start_op=seq, deps={},
        ops=(Op(action=Action.SET, obj=ROOT, key=key, value=value),),
    )


def _seeded(seed):
    return [("f%d" % i, "actor00", _history(seed + i)) for i in range(5)]


def _empty_feed(_seed):
    return [("empty", "actor00", []), ("full", "actor00", _history(3))]


def _corrupt_flag(seed):
    h = _history(seed)
    return [("bad", "actor00", h[:2] + [None] + h[2:])]


def _distinct_tables(_seed):
    # every feed: its own writer, keys, strings, floats and bigints
    return [
        (
            "d%d" % i,
            "writer%02d" % i,
            [
                _set_change("writer%02d" % i, 1, "k%d" % i, "s%d" % i),
                _set_change("writer%02d" % i, 2, "f%d" % i, i + 0.5),
                _set_change("writer%02d" % i, 3, "b%d" % i, 2**40 + i),
            ],
        )
        for i in range(6)
    ]


def _shared_tables(seed):
    # one history under one writer (blobs identical to the byte) and
    # the same keys and strings under other writers (only the leading
    # actor line differs)
    feeds = [("s%d" % i, "actor00", _history(seed)) for i in range(4)]
    for i in range(4):
        w = "other%02d" % i
        feeds.append((
            "o%d" % i, w,
            [_set_change(w, 1, "title", "same"), _set_change(w, 2, "n", 7)],
        ))
    return feeds


def _empty_among_full(_seed):
    return [
        ("full0", "actor00", _history(3)), ("empty", "actor00", []),
        ("full1", "actor00", _history(4)),
    ]


def _corrupt_among_many(seed):
    # the flag in the middle of ONE feed, whole feeds on both sides
    h = _history(seed)
    feeds = [("g%d" % i, "actor00", _history(seed + i)) for i in range(4)]
    feeds[2:2] = [("bad", "actor00", h[:2] + [None] + h[2:])]
    return feeds


def _lead_lines(n):
    """A feed whose tables blob opens with `n` actor lines: its own and
    those of n - 1 peers whose objects it writes into."""

    def make(_seed):
        w = "w00"
        ops = (Op(action=Action.SET, obj=ROOT, key="t", value="x"),) + tuple(
            Op(action=Action.SET, obj=OpId(1, "peer%02d" % i), key="k",
               value=i)
            for i in range(1, n)
        )
        first = Change(actor=w, seq=1, start_op=1, deps={}, ops=ops)
        return [
            ("lead%d" % n, w, [first, _set_change(w, 2, "n", n)]),
            ("plain", "actor00", _history(4)),
        ]

    return make


def _escaped_actor(_seed):
    # writer ids json.dumps has to escape: no canonical actor line
    return [
        ("quote", 'we"ird\\actor', [_set_change('we"ird\\actor', 1, "k", 1)]),
        ("umlaut", "act\u00f6r", [_set_change("act\u00f6r", 1, "k", 2)]),
        ("plain", "actor00", _history(4)),
    ]


def _respelled_lead(_seed):
    return [
        (n, "actor00", _history(5 + i))
        for i, n in enumerate(["trailing_space", "spaced", "plain"])
    ]


def _respell(slab):
    """The same tables under another spelling of the lead actor line:
    one that still opens like an actor line, one that does not."""
    for name, line in (
        ("trailing_space", '{"t":"a","v":"actor00" }'),
        ("spaced", '{"t": "a", "v": "actor00"}'),
    ):
        planes, preds, row_ends, flags, tables, _end, _meta = (
            parse_v3_checkpoint(slab.image_bytes(name))
        )
        assert tables[0] == '{"t":"a","v":"actor00"}'
        blob = ("\n".join([line] + tables[1:]) + "\n").encode("utf-8")
        slab.append(KIND_IMAGE, name, pack_v3_checkpoint(
            planes, preds, row_ends, flags, blob
        ))


CASES = {
    "seeded": _seeded,
    "empty_feed": _empty_feed,
    "empty_among_full": _empty_among_full,
    "corrupt_flag": _corrupt_flag,
    "corrupt_among_many": _corrupt_among_many,
    "distinct_tables": _distinct_tables,
    "shared_tables": _shared_tables,
    "lead_lines_1": _lead_lines(1),
    "lead_lines_3": _lead_lines(3),
    "lead_lines_32": _lead_lines(32),
    "escaped_actor": _escaped_actor,
    "respelled_lead": _respelled_lead,
}
# what a case does to its slab once the images are written
REWRITES = {"respelled_lead": _respell}


def _write_images(root, feeds, rewrite=None):
    """Each feed's history as ONE v3 image segment of a fresh slab."""
    fn = file_column_storage_fn(str(root))
    for name, writer, changes in feeds:
        cc = FeedColumnCache(fn(name), writer=writer)
        for c in changes:
            cc.append_change(c)
        cc.compact()
        cc.close()
    if rewrite is not None:
        rewrite(fn.slab)
    fn.slab.close()


def _caches(root, feeds):
    fn = file_column_storage_fn(str(root))
    return fn.slab, [
        FeedColumnCache(fn(name), writer=writer)
        for name, writer, _c in feeds
    ]


def _same_array(a, b, what):
    assert a.dtype == b.dtype, what
    assert a.shape == b.shape, what
    assert np.array_equal(a, b), what


def assert_same_columns(got, want, what=""):
    """FeedColumns `got` (slab-granular) == `want` (per feed), field by
    field; `plane_meta` by what it points at."""
    assert (got.rows is None) == (want.rows is None), what
    if want.rows is not None:
        _same_array(got.rows, want.rows, what + " rows")
    _same_array(got.preds, want.preds, what + " preds")
    _same_array(got.row_ends, want.row_ends, what + " row_ends")
    for table in ("actors", "keys", "strings", "floats", "bigints"):
        assert getattr(got, table) == getattr(want, table), (what, table)
    assert got.n_changes == want.n_changes, what
    assert got.ok_prefix_len == want.ok_prefix_len, what
    assert (got.planes is None) == (want.planes is None), what
    if want.planes is not None:
        assert list(got.planes) == list(want.planes) == list(PLANE_NAMES)
        for name in PLANE_NAMES:
            _same_array(got.planes[name], want.planes[name], what + name)
    assert (got.plane_meta is None) == (want.plane_meta is None), what
    if want.plane_meta is not None:
        _same_array(got.plane_meta[1], want.plane_meta[1], what + " offs")
        _same_array(got.plane_meta[2], want.plane_meta[2], what + " dts")
        for fc in (got, want):  # the pointers the native pack derives
            base, offs = fc.plane_meta[0], fc.plane_meta[1]
            for pi, name in enumerate(PLANE_NAMES):
                p = fc.planes[name]
                assert ctypes.string_at(
                    base + int(offs[pi]), p.nbytes
                ) == p.tobytes(), (what, name)


@pytest.mark.parametrize("case", sorted(CASES))
def test_bulk_load_equals_per_feed_load(tmp_path, case):
    feeds = CASES[case](11)
    _write_images(tmp_path, feeds, REWRITES.get(case))

    slab_w, per_feed = _caches(tmp_path, feeds)
    want = [cc.columns() for cc in per_feed]

    slab, bulk = _caches(tmp_path, feeds)
    heads = [len(ch) for _n, _w, ch in feeds]
    done = load_slab_images(slab, bulk, heads)
    assert done == [True] * len(feeds)
    for (name, _w, _c), cc, w in zip(feeds, bulk, want):
        assert cc.loaded
        got = cc.columns()
        assert_same_columns(got, w, f"{case}/{name}: ")
        # zero-copy: the planes are views of the slab's mapping
        assert not got.planes["action"].flags.writeable
        assert not got.planes["action"].flags.owndata
    slab.close()
    slab_w.close()


def _bulk_loaded(root, feeds):
    _write_images(root, feeds)
    slab, caches = _caches(root, feeds)
    assert all(load_slab_images(
        slab, caches, [len(ch) for _n, _w, ch in feeds]
    ))
    return slab, caches


_PLANES_BUILT = telemetry.counter("loader.cols_planes_built")

_ACTOR_LINES = {
    "one": ["actor00"],
    "three": ["w00", "peer01", "peer02"],
    "thirty_two": ["w%02d" % i for i in range(32)],
    "repeated": ["w00", "w01", "w00"],
    "none": [],
    "escape": ['we"ird'],
    "backslash": ["back\\slash"],
    "control": ["tab\tbed"],
    "not_ascii": ["act\u00f6r"],
    "second_of_three_escaped": ["w00", 'we"ird', "w02"],
}


@pytest.mark.parametrize("case", sorted(_ACTOR_LINES))
def test_lead_actor_lines_are_sliced_where_canonical(case, monkeypatch):
    """_lead_actors against the parse it replaces: the same actors and
    the same cut whatever the lines hold, and no parse at all where
    every lead line has the writers' own form."""
    actors = _ACTOR_LINES[case]
    lead = "".join(
        json.dumps({"t": "a", "v": a}, separators=(",", ":")) + "\n"
        for a in actors
    )
    rest = '{"t":"k","v":"title"}\n{"t":"s","v":"{\\"t\\":\\"a\\"}"}\n'
    parsed = []
    orig = colcache._parse_tables

    def spy(lines):
        parsed.append(lines)
        return orig(lines)

    monkeypatch.setattr(colcache, "_parse_tables", spy)
    got, cut = colcache._lead_actors((lead + rest).encode("utf-8"))
    assert cut == len(lead.encode("utf-8"))
    assert list(dict.fromkeys(got)) == orig(lead.splitlines())["a"].items
    canonical = all(
        a.isascii() and a.isprintable() and not set(a) & set('"\\')
        for a in actors
    )
    assert bool(parsed) == (not canonical)


def test_lead_line_of_another_spelling_is_parsed():
    blob = b'{"t":"a","v":"actor00" }\n{"t":"a","v":"peer"}\n{"t":"k","v":"x"}\n'
    got, cut = colcache._lead_actors(blob)
    assert got == ["actor00", "peer"] and blob[cut:] == b'{"t":"k","v":"x"}\n'
    # a last actor line without its newline stays with the rest
    got, cut = colcache._lead_actors(b'{"t":"a","v":"a"}\n{"t":"a","v":"b"}')
    assert got == ["a"] and cut == 18


def test_corrupt_flag_clamps_its_feed_alone(tmp_path):
    feeds = _corrupt_among_many(11)
    slab, caches = _bulk_loaded(tmp_path, feeds)
    for (name, _w, changes), cc in zip(feeds, caches):
        fc = cc.columns()
        assert fc.n_changes == len(changes)
        assert fc.ok_prefix_len == (2 if name == "bad" else len(changes))
        assert len(fc.row_ends) == len(changes) + 1 and fc.row_ends[0] == 0
    slab.close()


def test_commit_slices_are_read_only_and_an_append_touches_no_sibling(
    tmp_path,
):
    """The feeds of a chunk slice ONE commits array and ONE row_ends
    array: nobody may write through a slice, and the feed that grows
    gets arrays of its own."""
    feeds = _seeded(7)
    slab, caches = _bulk_loaded(tmp_path, feeds)
    fcs = [cc.columns() for cc in caches]
    for cc, fc in zip(caches, fcs):
        assert not fc.row_ends.flags.writeable
        assert not cc._commits_arr.flags.writeable
        with pytest.raises(ValueError):
            fc.row_ends[0] = 1
        with pytest.raises(ValueError):
            cc._commits_arr[0, 0] = 1
    before = [
        (fc.row_ends.copy(), cc._commits_arr.copy())
        for cc, fc in zip(caches, fcs)
    ]
    n = fcs[1].n_changes
    caches[1].append_change(_set_change("actor00", n + 1, "after", "load"))
    grown = caches[1].columns()
    assert grown.n_changes == n + 1 == grown.ok_prefix_len
    assert np.array_equal(grown.row_ends[:-1], before[1][0])
    assert grown.row_ends[-1] == before[1][0][-1] + 1
    for cc, fc, (row_ends, commits) in zip(caches, fcs, before):
        # the snapshots handed out before the append, the grown feed's
        # included, and every sibling's cache
        assert np.array_equal(fc.row_ends, row_ends)
        if cc is not caches[1]:
            assert np.array_equal(cc._commits_arr, commits)
            assert cc.columns() is fc
    slab.close()


def test_row_counts_and_windows_build_no_plane(tmp_path):
    feeds = _seeded(9)
    slab, caches = _bulk_loaded(tmp_path, feeds)
    slab_w, per_feed = _caches(tmp_path, feeds)
    before = _PLANES_BUILT.value()
    for cc, ref in zip(caches, per_feed):
        fc, want = cc.columns(), ref.columns()
        assert isinstance(fc.planes, colcache._ImagePlanes)
        assert list(fc.planes) == list(PLANE_NAMES)
        assert len(fc.planes) == len(PLANE_NAMES)
        assert fc.n_rows == want.n_rows == cc._base_rows
        assert cc._n_rows_total == want.n_rows
        assert cc.n_changes == want.n_changes
        assert fc.window(0, INF) == want.window(0, INF)
        assert fc.window(2, 5) == want.window(2, 5)
        assert fc.changes_in_window(1, INF) == want.changes_in_window(1, INF)
        assert fc.planes._built == {}
        assert fc.seqs_contiguous() and want.seqs_contiguous()
        assert list(fc.planes._built) == ["seq"]
    # the open's own check of `seq` is no numpy reader of the planes
    assert _PLANES_BUILT.value() == before
    fc = caches[0].columns()
    ctr = fc.plane("ctr")
    assert fc.plane("ctr") is ctr and fc.planes["ctr"] is ctr  # kept
    assert set(fc.planes._built) == {"seq", "ctr"}
    fc.plane("obj_a")
    assert _PLANES_BUILT.value() == before + 1  # a feed counts once
    with pytest.raises(KeyError):
        fc.planes["no_such_plane"]
    slab.close()
    slab_w.close()


def test_shared_tables_are_parsed_once_and_copied_on_write(tmp_path):
    feeds = _shared_tables(5)
    _write_images(tmp_path, feeds)
    slab, caches = _caches(tmp_path, feeds)
    heads = [len(ch) for _n, _w, ch in feeds]
    assert all(load_slab_images(slab, caches, heads))
    a, b = caches[4], caches[5]  # same keys/strings, other writers
    fa, fb = a.columns(), b.columns()
    assert fa.keys is fb.keys and fa.strings is fb.strings  # shared
    assert fa.actors != fb.actors
    keys_before = list(fb.keys)
    # a live append to ONE of them must not leak into the other, nor
    # into the FeedColumns handed out before it
    a.append_change(_set_change("other00", 3, "fresh-key", "fresh"))
    assert "fresh-key" in a.columns().keys
    assert fb.keys == keys_before == b.columns().keys
    assert fa.keys == keys_before
    assert "fresh" not in b.columns().strings
    slab.close()


def test_only_whole_level_images_load_in_bulk(tmp_path):
    """What the pass must leave to the per-feed loader, at the storage
    level: a v2 tail (as a record segment, and inside the image), a
    truncated image, a foreign block, a head that disagrees, a cache
    that loaded meanwhile. Each then loads per feed as it always did."""
    feeds = [(n, "actor00", _history(20 + i)) for i, n in enumerate(
        ["clean", "tail_segment", "tail_inside", "short", "foreign",
         "ahead", "behind", "raced"]
    )]
    _write_images(tmp_path, feeds)
    heads = {n: len(ch) for n, _w, ch in feeds}
    record = pack_v2_record(
        np.zeros((0, colcache.ROW_FIELDS), np.int32),
        np.zeros((0, colcache.PRED_FIELDS), np.int32), [], 1,
    )
    slab = CorpusSlab(str(tmp_path / "cols.slab"))
    slab.append(KIND_RECORD, "tail_segment", record)
    slab.append(
        KIND_IMAGE, "tail_inside", slab.image_bytes("tail_inside") + record
    )
    slab.append(KIND_IMAGE, "short", slab.image_bytes("short")[:-9])
    slab.append(KIND_IMAGE, "foreign", b"not a v3 block at all, just bytes")
    slab.close()
    heads["tail_segment"] += 1
    heads["tail_inside"] += 1
    heads["ahead"] -= 1  # the feed holds one change fewer
    heads["behind"] += 2  # and here two more

    slab, caches = _caches(tmp_path, feeds)
    by_name = {n: cc for (n, _w, _c), cc in zip(feeds, caches)}
    by_name["raced"].columns()  # loaded before the pass gets to it
    done = load_slab_images(slab, caches, [heads[n] for n, _w, _c in feeds])
    assert dict(zip(heads, done)) == {
        n: n == "clean" for n in heads
    }
    for n, cc in by_name.items():
        assert cc.loaded == (n in ("clean", "raced")), n
    # and the per-feed loader still serves each of them
    assert by_name["tail_segment"].columns().n_changes == heads["tail_segment"]
    assert by_name["tail_inside"].columns().n_changes == heads["tail_inside"]
    assert by_name["short"].columns().n_changes == 0
    assert by_name["foreign"].columns().n_changes == 0
    slab.close()


def test_a_cache_that_loads_during_the_pass_is_skipped(tmp_path, monkeypatch):
    """Between the extents (read under the slab's lock) and its
    hand-over a cache may load itself (an append raced the open): the
    pass leaves it as it is and says so."""
    feeds = _seeded(13)
    _write_images(tmp_path, feeds)
    slab, caches = _caches(tmp_path, feeds)
    extents = slab.image_extents

    def racing(names):
        out = extents(names)
        caches[2].append_change(
            _set_change("actor00", len(feeds[2][2]) + 1, "raced", 1)
        )
        return out

    monkeypatch.setattr(slab, "image_extents", racing)
    done = load_slab_images(
        slab, caches, [len(ch) for _n, _w, ch in feeds]
    )
    assert done == [True, True, False, True, True]
    raced = caches[2].columns()
    assert raced.n_changes == len(feeds[2][2]) + 1
    assert "raced" in raced.keys
    assert raced.planes is None or not isinstance(
        raced.planes, colcache._ImagePlanes
    )
    slab.close()


# ---------------------------------------------------------------------------
# the loader: a mixed chunk, staleness, appends after the load, twins


def _actors(back, urls):
    return [
        back._get_or_create_actor(root_actor_id(validate_doc_url(u)))
        for u in urls
    ]


def _per_feed_only(monkeypatch):
    """The loader with the slab-granular pass switched off: what every
    feed gave before it existed."""
    from hypermerge_tpu.backend import bulk_loader

    monkeypatch.setattr(
        bulk_loader, "load_slab_images",
        lambda slab, caches, heads: [False] * len(caches),
    )


def _mixed_corpus(path):
    """12 checkpointed single-writer docs, eight of them then put into
    the states a long-lived store holds. Returns (urls, {state: index})."""
    urls = make_corpus(str(path), 12, 64)
    names = [root_actor_id(validate_doc_url(u)) for u in urls]
    state = {
        "v2_tail": 0, "several_segments": 1, "legacy_cols2": 2,
        "truncated": 3, "ahead": 4, "behind": 5, "memory": 6,
        "tail_inside": 7,
    }
    feeds = path / "feeds"
    # legacy: the feed leaves the slab for a per-feed `.cols2` file
    slab = CorpusSlab(str(feeds / "cols.slab"))
    images = {n: slab.image_bytes(n) for n in slab.feed_names()}
    slab.destroy()
    slab = CorpusSlab(str(feeds / "cols.slab"))
    legacy = names[state["legacy_cols2"]]
    for n, raw in images.items():
        if n == legacy:
            d = feeds / n[:2]
            d.mkdir(exist_ok=True)
            (d / (n + ".cols2")).write_bytes(raw)
        else:
            slab.append(KIND_IMAGE, n, raw)
    slab.close()
    repo = Repo(path=str(path))
    changes = {
        key: repo.back._get_or_create_actor(
            names[state[key]]
        ).changes_in_window(0, INF)
        for key in ("v2_tail", "several_segments", "behind")
    }
    repo.close()

    slab = CorpusSlab(str(feeds / "cols.slab"))
    record = pack_v2_record(
        np.zeros((0, colcache.ROW_FIELDS), np.int32),
        np.zeros((0, colcache.PRED_FIELDS), np.int32), [], 1,
    )

    def rewrite(key, n_image, n_records):
        """The feed's sidecar as an image of its first changes plus a
        record segment for each of the next."""
        n = names[state[key]]
        cc = FeedColumnCache(SlabColumnStorage(slab, n), writer=n)
        cc.reset()
        for c in changes[key][:n_image]:
            cc.append_change(c)
        cc.compact()
        for c in changes[key][n_image:n_image + n_records]:
            cc.append_change(c)

    # level with the feed, the last change(s) as live appends leave them
    rewrite("v2_tail", len(changes["v2_tail"]) - 1, 1)
    rewrite("several_segments", len(changes["several_segments"]) - 2, 2)
    # behind: the image of a prefix
    rewrite("behind", len(changes["behind"]) - 2, 0)
    # ahead: one (corrupt-flag) change more than the feed holds
    n = names[state["ahead"]]
    slab.append(KIND_IMAGE, n, images[n] + record)
    FeedColumnCache(SlabColumnStorage(slab, n), writer=n).compact()
    n = names[state["truncated"]]
    slab.append(KIND_IMAGE, n, images[n][:-7])
    n = names[state["tail_inside"]]
    slab.append(KIND_IMAGE, n, images[n] + record)
    slab.close()
    return urls, state


def test_mixed_chunk_splits_per_feed(tmp_path, monkeypatch):
    src = tmp_path / "src"
    urls, state = _mixed_corpus(src)
    shutil.copytree(src, tmp_path / "a")
    shutil.copytree(src, tmp_path / "b")

    def load(path, per_feed_only):
        if per_feed_only:
            _per_feed_only(monkeypatch)
        repo = Repo(path=str(path))
        actors = _actors(repo.back, urls)
        mem = actors[state["memory"]]
        mem._colcache = FeedColumnCache(
            MemoryColumnStorage(), writer=mem.id
        )
        counts = repo.back.loader._prefetch_columns(actors)
        return repo, actors, counts

    repo_a, actors_a, counts_a = load(tmp_path / "a", False)
    # exactly the eight altered feeds went down the per-feed path
    assert counts_a == (len(urls) - len(state), len(state))
    for i, a in enumerate(actors_a):
        assert a.colcache.loaded
    repo_b, actors_b, counts_b = load(tmp_path / "b", True)
    assert counts_b == (0, len(urls))
    by_index = {i: s for s, i in state.items()}
    for i, (a, b) in enumerate(zip(actors_a, actors_b)):
        assert_same_columns(
            a.columns(), b.columns(), by_index.get(i, "clean") + ": "
        )
        assert a.columns().n_changes == a.seq_head  # level with its feed
    # the legacy file migrated into the slab as it always did
    legacy = actors_a[state["legacy_cols2"]].id
    assert not (
        tmp_path / "a" / "feeds" / legacy[:2] / (legacy + ".cols2")
    ).exists()
    repo_a.close()
    repo_b.close()


@pytest.mark.parametrize("which", ["ahead", "behind"])
def test_stale_sidecar_is_rebuilt_or_caught_up(tmp_path, which):
    urls, state = _mixed_corpus(tmp_path)
    repo = Repo(path=str(tmp_path))
    i = state[which]
    handles = repo.open_many(urls)
    repo.back.fetch_bulk_summaries()
    stats = repo.back.last_bulk_stats
    assert stats["cols_single_feeds"] >= 1
    assert stats["fallback"] == 0
    actor = repo.back.actors[root_actor_id(validate_doc_url(urls[i]))]
    fc = actor.columns()
    assert fc.n_changes == actor.seq_head == fc.ok_prefix_len
    clean = handles[9].value()
    assert handles[i].value().keys() == clean.keys()
    repo.close()


def test_a_feed_that_loads_during_the_pass_counts_as_single(
    tmp_path, monkeypatch
):
    urls = make_corpus(str(tmp_path), 6, 64)
    repo = Repo(path=str(tmp_path))
    actors = _actors(repo.back, urls)
    slab = repo.back._col_slab
    extents = slab.image_extents

    def racing(names):
        out = extents(names)
        actors[4].columns()
        return out

    monkeypatch.setattr(slab, "image_extents", racing)
    assert repo.back.loader._prefetch_columns(actors) == (5, 1)
    assert all(a.colcache.loaded for a in actors)
    repo.close()


def test_counters_and_span_tag(tmp_path):
    from hypermerge_tpu import telemetry
    from hypermerge_tpu.telemetry import trace as ttrace

    urls = make_corpus(str(tmp_path), 10, 64)
    was_on = ttrace.enabled()
    ttrace.reset()
    ttrace.enable()
    try:
        before = telemetry.snapshot()
        repo = Repo(path=str(tmp_path))
        repo.open_many(urls)
        repo.back.fetch_bulk_summaries()
        stats = dict(repo.back.last_bulk_stats)
        after = telemetry.snapshot()
        spans = [
            e[6] for e in telemetry.trace_events()
            if e[1] == "storage.columns.load"
        ]
    finally:
        if not was_on:
            ttrace.disable()
        ttrace.reset()
    assert stats["cols_bulk_feeds"] == 10
    assert stats["cols_single_feeds"] == 0
    assert stats["cols_bulk_pct"] == 100.0
    built = 0 if native.pack_lib() is not None else 10
    assert stats["cols_planes_built"] == built
    assert stats["cols_planes_built_pct"] == 10.0 * built
    for name, want in (
        ("loader.cols_bulk_feeds", 10), ("loader.cols_single_feeds", 0),
        ("loader.cols_planes_built", built),
    ):
        assert after.get(name, 0) - before.get(name, 0) == want, name
    assert spans
    assert sum(a["bulk"] for a in spans) == 10
    assert sum(a["feeds"] for a in spans) == 10
    repo.close()


def _pack_bytes(fcs):
    batch = pack_docs_columns(
        [[(fc, 0, INF)] for fc in fcs], n_docs=len(fcs)
    )
    out = {name: batch.cols[name].tobytes() for name in COLUMNS}
    out.update(psrc=batch.psrc.tobytes(), ptgt=batch.ptgt.tobytes())
    return out


def test_views_outlive_appends_and_close(tmp_path):
    urls = make_corpus(str(tmp_path), 8, 64)
    repo = Repo(path=str(tmp_path))
    handles = repo.open_many(urls)
    repo.back.fetch_bulk_summaries()
    assert repo.back.last_bulk_stats["cols_bulk_feeds"] == 8
    actors = _actors(repo.back, urls)
    fcs = [a.columns() for a in actors]
    assert all(fc.plane_meta is not None for fc in fcs)
    packed = _pack_bytes(fcs)
    rows = [fc.ensure_rows().copy() for fc in fcs]

    slab = repo.back._col_slab
    mapping = slab._mm
    # a live edit (the doc's new writer feed gets a record segment) and
    # an append to a bulk-loaded feed's own sidecar: each goes into the
    # slab, which drops (not closes) the mapping the views point into
    handles[3].change(lambda d: d.__setitem__("after", "load"))
    for a in list(repo.back.actors.values()):
        a.sync_cache()
    assert slab._mm is not mapping
    mapping = slab._mapped()
    actors[0].colcache.append_change(
        _set_change(actors[0].id, fcs[0].n_changes + 1, "after", "load")
    )
    assert slab._mm is not mapping
    assert not mapping.closed
    grown = actors[0].colcache.columns()
    assert grown.n_changes == fcs[0].n_changes + 1
    assert np.array_equal(grown.ensure_rows()[: len(rows[0])], rows[0])
    # a raw append and a remap for new reads beside the old views
    slab.append(KIND_IMAGE, "someone-else", b"x" * 100)
    assert slab.image_bytes("someone-else") == b"x" * 100
    for fc, want in zip(fcs, rows):
        assert np.array_equal(fc.ensure_rows(), want)
    assert _pack_bytes(fcs) == packed
    repo.close()  # CorpusSlab.close(): compact + drop, no BufferError
    assert slab._mm is None
    for fc, want in zip(fcs, rows):  # still readable after the close
        assert np.array_equal(
            colcache.rows_from_planes(fc.planes), want
        )
    assert _pack_bytes(fcs) == packed
    # the edit is on disk: a fresh open reads it back, feed 0's sidecar
    # (now ahead of its feed) is rebuilt, the new writer's loads per feed
    repo = Repo(path=str(tmp_path))
    h = repo.open_many(urls)
    repo.back.fetch_bulk_summaries()
    assert h[3].value()["after"] == "load"
    assert "after" not in h[0].value()
    stats = repo.back.last_bulk_stats
    assert stats["cols_bulk_feeds"] == 7 and stats["cols_single_feeds"] == 2
    repo.close()


def test_packs_bit_identical_with_counters(tmp_path, monkeypatch):
    """The mixed corpus through the pipeline under the numpy and the
    native pack: the same summaries, column counters and fast/fallback
    counts, and every doc's summary and value equal the host OpSet
    replay of its feeds. (Bar `map_entries`: make_corpus's synthetic
    histories aim map-key SETs at the text object, which the kernel
    counts and the OpSet ignores, so that count is held equal between
    the packs only.)"""
    urls, _state = _mixed_corpus(tmp_path / "src")
    out = {}
    for pack in ("0", "1"):
        path = tmp_path / ("m" + pack)
        shutil.copytree(tmp_path / "src", path)
        monkeypatch.setenv("HM_NATIVE_PACK", pack)
        repo = Repo(path=str(path))
        handles = repo.open_many(urls)
        summ = repo.back.fetch_bulk_summaries()
        stats = dict(repo.back.last_bulk_stats)
        ids = [validate_doc_url(u) for u in urls]
        states = [
            (summ.doc(d), plainify(h.value(timeout=60)))
            for d, h in zip(ids, handles)
        ]
        replayed = [
            opset_replay_state(repo.back._bulk_history_loader(d)())
            for d in ids
        ]
        for (summary, value), (want, want_value) in zip(states, replayed):
            assert dict(summary, map_entries=None) == dict(
                want, map_entries=None
            )
            assert value == want_value
        cols = {k: stats[k] for k in stats if k.startswith("cols_")}
        # the slab's rows-backed feeds send either pack down the
        # row-matrix path, which widens the five images' planes
        assert cols.pop("cols_planes_built") == 5
        assert cols.pop("cols_planes_built_pct") == 100.0
        out[pack] = (states, cols, stats["fast"], stats["fallback"])
        repo.close()
    assert out["0"] == out["1"]
    assert out["1"][1] == {
        "cols_bulk_feeds": 5, "cols_single_feeds": 7,
        "cols_bulk_pct": round(100 * 5 / 12, 3),
    }
