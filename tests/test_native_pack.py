"""Native C++ bulk pack (hm_pack_prefix, hm_pack_gather) vs the numpy
twins.

The cold-open pack stage has two implementations of each of its hot
loops: the C++ entry points that read the feeds' narrow planes where
they lie (native/src/hm_native.cpp: the prefix path's whole emit, the
general path's gather), and the numpy code in ops/columnar.py that
remains both the fallback and the correctness reference. These tests
pin them BIT-identical — same values, same wire dtypes — over fuzzed
histories covering the prefix-single fast path, every value-kind lane,
empty/padded docs, and the general sorted-composite path over every
kind of feed it is handed (images of one slab, planes of their own,
dense rows), held to the per-op pack beside it."""

import dataclasses
import random

import numpy as np
import pytest

from helpers import Site, random_mutation, sync
from hypermerge_tpu import native
from hypermerge_tpu.models import Counter, Text
from hypermerge_tpu.ops import columnar
from hypermerge_tpu.ops.columnar import (
    COLUMNS,
    pack_docs,
    pack_docs_columns,
)
from hypermerge_tpu.storage.colcache import (
    FeedColumnCache,
    FileColumnStorageV2,
    MemoryColumnStorage,
    file_column_storage_fn,
    load_slab_images,
    planes_from_rows,
)

INF = float("inf")

needs_pack = pytest.mark.skipif(
    native.pack_lib() is None, reason="native pack layer unavailable"
)


def _single_writer_history(seed, n_mut=30):
    r = random.Random(seed)
    site = Site(f"actor{seed % 7:02d}")
    for _ in range(n_mut):
        random_mutation(site, r)
    # widen value coverage: floats, bools, bigints, >int16 inline ints
    site.change(lambda d: d.__setitem__("f", 3.25 + seed))
    site.change(lambda d: d.__setitem__("b", True))
    site.change(lambda d: d.__setitem__("big", 2**40 + seed))
    site.change(lambda d: d.__setitem__("wide", 2**20 + seed))
    return list(site.opset.history)


def _plane_cache(tmp_path, name, history):
    """A compacted (v3 checkpoint) cache: plane-backed with plane_meta,
    i.e. exactly what a bulk cold open hands the pack."""
    path = str(tmp_path / name)
    writer = history[0].actor
    cc = FeedColumnCache(FileColumnStorageV2(path), writer=writer)
    for c in sorted(history, key=lambda c: (c.actor, c.seq)):
        cc.append_change(c)
    cc.compact()
    cc.close()
    return FeedColumnCache(FileColumnStorageV2(path), writer=writer)


def _assert_batches_identical(a, b):
    for name in COLUMNS:
        assert a.cols[name].dtype == b.cols[name].dtype, name
        assert np.array_equal(a.cols[name], b.cols[name]), name
    assert a.psrc.dtype == b.psrc.dtype
    assert np.array_equal(a.psrc, b.psrc)
    assert np.array_equal(a.ptgt, b.ptgt)
    assert np.array_equal(a.n_ops, b.n_ops)
    assert np.array_equal(a.doc_actors, b.doc_actors)
    assert a.actors == b.actors and a.keys == b.keys
    assert a.strings == b.strings
    assert a.floats == b.floats and a.bigints == b.bigints
    if a.slot is not None or b.slot is not None:
        assert np.array_equal(a.slot, b.slot)


def _spy_native(monkeypatch):
    """Whether each call of a native pack entry (the prefix path's
    emit, the general path's gather) gave a result, in call order."""
    calls = []
    for entry in ("_native_pack_prefix", "_native_gather"):
        orig = getattr(columnar, entry)

        def spy(*a, _orig=orig, **k):
            out = _orig(*a, **k)
            calls.append(bool(out))
            return out

        monkeypatch.setattr(columnar, entry, spy)
    return calls


def _pack_both(monkeypatch, specs, counted=True, **kw):
    """(native_batch, numpy_batch); with `counted`, the slab's native
    entry ran, gave its result, and the numpy run reached none."""
    calls = _spy_native(monkeypatch)
    monkeypatch.setenv("HM_NATIVE_PACK", "1")
    b_native = pack_docs_columns(specs, **kw)
    n_native = len(calls)
    monkeypatch.setenv("HM_NATIVE_PACK", "0")
    b_numpy = pack_docs_columns(specs, **kw)
    if counted:
        assert calls and all(calls), "native entry point was not used"
        assert len(calls) == n_native, "HM_NATIVE_PACK=0 ran a native entry"
    return b_native, b_numpy


@needs_pack
def test_prefix_single_fuzz_bit_identical(tmp_path, monkeypatch):
    """The dominant cold-open shape: single-writer plane-backed feeds,
    whole-prefix windows — the native path must be exercised and agree
    bit-for-bit (values AND dtypes) with the numpy twin."""
    caches = [
        _plane_cache(tmp_path, f"f{seed}", _single_writer_history(seed))
        for seed in range(6)
    ]
    specs = [[(cc.columns(), 0, INF)] for cc in caches]
    assert all(s[0][0].planes is not None for s in specs)
    assert all(s[0][0].plane_meta is not None for s in specs)
    b_native, b_numpy = _pack_both(monkeypatch, specs)
    _assert_batches_identical(b_native, b_numpy)
    for cc in caches:
        cc.close()


@needs_pack
def test_prefix_single_padded_and_partial_windows(tmp_path, monkeypatch):
    """Doc-axis padding (slab buckets) and partial end_seq windows."""
    caches = [
        _plane_cache(tmp_path, f"p{seed}", _single_writer_history(seed))
        for seed in (11, 12)
    ]
    fcs = [cc.columns() for cc in caches]
    half = max(1, fcs[1].n_changes // 2)
    specs = [[(fcs[0], 0, INF)], [(fcs[1], 0, half)]]
    b_native, b_numpy = _pack_both(
        monkeypatch, specs, n_docs=8, n_rows=512, n_pred=128
    )
    assert b_native.n_docs == 8
    _assert_batches_identical(b_native, b_numpy)
    for cc in caches:
        cc.close()


@needs_pack
def test_shared_feed_and_empty_doc(tmp_path, monkeypatch):
    """Two docs sharing one feed object, plus a zero-change window."""
    cc = _plane_cache(tmp_path, "s0", _single_writer_history(3))
    fc = cc.columns()
    specs = [[(fc, 0, INF)], [(fc, 0, INF)], [(fc, 0, 0)]]
    b_native, b_numpy = _pack_both(monkeypatch, specs)
    assert int(b_native.n_ops[2]) == 0
    _assert_batches_identical(b_native, b_numpy)
    cc.close()


# ---------------------------------------------------------------------------
# the general pack: native gather == numpy twin == the per-op pack


def _multi_history(seed, n_actors=3, n_mut=30):
    """One doc's history under several writers, syncing now and then
    (concurrent edits, cross-feed references and preds, every value
    kind)."""
    r = random.Random(seed)
    sites = [Site(f"actor{i:02d}") for i in range(n_actors)]
    sites[0].change(lambda d: d.__setitem__("f", 2.5 + seed))
    sites[0].change(lambda d: d.__setitem__("big", 2**40 + seed))
    sites[0].change(lambda d: d.__setitem__("wide", 2**20 + seed))
    sync(*sites)
    for _ in range(n_mut):
        random_mutation(r.choice(sites), r)
        if r.random() < 0.3:
            sync(*sites)
    sync(*sites)
    return list(sites[0].opset.history)


def _tied_history(n_writers=32, rounds=3):
    """`n_writers` writers in rounds of concurrent changes on EQUAL
    counters: every writer inserts after the same element and sets the
    same key, so only the actor breaks the ties."""
    sites = [Site(f"w{i:02d}") for i in range(n_writers)]
    sites[0].change(lambda d: d.__setitem__("t", Text("ab")))
    sync(*sites)
    for rnd in range(rounds):
        for i, site in enumerate(sites):
            site.change(lambda d, i=i: d["t"].insert(1, chr(97 + i % 26)))
            site.change(lambda d, i=i, rnd=rnd: d.__setitem__("k", i + rnd))
        sync(*sites)
    return list(sites[0].opset.history)


def _by_actor(history):
    feeds = {}
    for c in sorted(history, key=lambda c: (c.actor, c.seq)):
        feeds.setdefault(c.actor, []).append(c)
    return feeds


def _rows_feeds(history):
    """{actor: FeedColumns} rows-backed (MemoryColumnStorage: `rows`
    set, `planes` None): what a live doc or a v2 record tail hands the
    pack."""
    out = {}
    for actor, changes in _by_actor(history).items():
        cc = FeedColumnCache(MemoryColumnStorage(), writer=actor)
        for c in changes:
            cc.append_change(c)
        out[actor] = cc.columns()
        assert out[actor].planes is None and out[actor].rows is not None
    return out


def _image_feeds(root, histories):
    """[{actor: FeedColumns}] through ONE slab's images (views of its
    mapping, `plane_meta` set): what a bulk cold open hands the pack."""
    fn = file_column_storage_fn(str(root))
    names = []
    for d, history in enumerate(histories):
        for actor, changes in _by_actor(history).items():
            cc = FeedColumnCache(fn(f"d{d}-{actor}"), writer=actor)
            for c in changes:
                cc.append_change(c)
            cc.compact()
            cc.close()
            names.append((d, actor, len(changes)))
    fn.slab.close()
    fn = file_column_storage_fn(str(root))
    caches = [
        FeedColumnCache(fn(f"d{d}-{actor}"), writer=actor)
        for d, actor, _n in names
    ]
    assert all(load_slab_images(fn.slab, caches, [n for *_x, n in names]))
    out = [{} for _ in histories]
    for (d, actor, _n), cc in zip(names, caches):
        out[d][actor] = cc.columns()
        assert out[d][actor].plane_meta is not None
    return out


def _own_planes(fc):
    """The same feed plane-backed WITHOUT plane_meta: every plane an
    array of its own (not slices of one buffer)."""
    return dataclasses.replace(
        fc, rows=None, planes=planes_from_rows(fc.ensure_rows().copy()),
        plane_meta=None,
    )


def _whole(feeds):
    return [(fc, 0, INF) for fc in feeds.values()]


def _case_slab_image(root):
    hists = [_multi_history(seed) for seed in (31, 32, 33)]
    return [_whole(f) for f in _image_feeds(root, hists)], hists, {}


def _case_rows_backed(_root):
    hists = [_multi_history(seed) for seed in (21, 22, 23)]
    return [_whole(_rows_feeds(h)) for h in hists], hists, {}


def _case_both_kinds_in_one_slab(root):
    hists = [_multi_history(seed) for seed in (41, 42, 43)]
    image = _image_feeds(root, hists)
    rows = [_rows_feeds(h) for h in hists]
    specs = [_whole(image[0]), _whole(rows[1])]
    # and within one doc: the first writer's feed an image, the rest rows
    first = sorted(image[2])[0]
    specs.append(_whole({**rows[2], first: image[2][first]}))
    return specs, hists, {}


def _case_planes_without_meta(_root):
    hists = [_multi_history(seed) for seed in (51, 52)]
    specs = [
        [(_own_planes(fc), 0, INF) for fc in _rows_feeds(h).values()]
        for h in hists
    ]
    assert all(
        fc.planes is not None and fc.rows is None and fc.plane_meta is None
        for spec in specs for fc, _s, _e in spec
    )
    return specs, hists, {}


def _case_late_windows(root):
    """Windows (s, e] with s > 0 and a finite e: what falls outside
    drops, and what referenced it with it."""
    hists = [_multi_history(seed, n_mut=40) for seed in (61, 62)]
    specs, sliced = [], []
    for feeds, kind in zip(
        (_image_feeds(root, hists[:1])[0], _rows_feeds(hists[1])),
        ((1, 2), (0, 1)),
    ):
        spec, kept = [], []
        for i, (actor, fc) in enumerate(sorted(feeds.items())):
            s = kind[0] if i else kind[1]
            e = max(s + 1, fc.n_changes - 1)
            spec.append((fc, s, e))
            kept.append((actor, s, e))
        specs.append(spec)
        sliced.append(kept)
    hists = [
        [c for c in h for a, s, e in kept if c.actor == a and s < c.seq <= e]
        for h, kept in zip(hists, sliced)
    ]
    return specs, hists, {}


def _case_feed_listed_twice(root):
    hists = [_multi_history(71), _multi_history(72)]
    image = _image_feeds(root, hists[:1])[0]
    rows = _rows_feeds(hists[1])
    specs = [_whole(image) + _whole(image)[:1], _whole(rows)[-1:] + _whole(rows)]
    return specs, hists, {}


def _case_empty_window(root):
    """A feed read up to seq 0, one read from its end, a doc of nothing
    but such windows, and a feed with no change at all."""
    hists = [_multi_history(81), _multi_history(82)]
    image = _image_feeds(root, hists[:1])[0]
    rows = _rows_feeds(hists[1])
    a0, a1 = sorted(image)[:2]
    empty = FeedColumnCache(MemoryColumnStorage(), writer="nobody").columns()
    specs = [
        [(fc, 0, 0 if a == a1 else INF) for a, fc in image.items()],
        [(fc, fc.n_changes, INF) for fc in rows.values()],
        _whole(rows) + [(empty, 0, INF)],
    ]
    hists = [[c for c in hists[0] if c.actor != a1], [], hists[1]]
    return specs, hists, {}


def _case_empty_key_table_last(_root):
    """A feed of keyless ops only, last in the flat key LUT: its offset
    equals the LUT's length (tests/test_advice_fixes.py)."""
    a, b = Site("actorA"), Site("actorB")
    a.change(lambda d: d.__setitem__("t", Text("x")))
    sync(a, b)
    b.change(lambda d: d["t"].insert(1, "y"))
    sync(a, b)
    history = list(a.opset.history)
    feeds = _rows_feeds(history)
    assert not feeds["actorB"].keys
    return [[(feeds["actorA"], 0, INF), (feeds["actorB"], 0, INF)]], [
        history], {}


def _case_dropped_by_the_fixpoint(root):
    """The first writer's feed is not in the window: every op in a
    container it made, or after an element it made, drops, and so does
    what referenced those."""
    hists = [_multi_history(91), _multi_history(92)]
    specs, kept = [], []
    for feeds, h in zip(
        (_image_feeds(root, hists[:1])[0], _rows_feeds(hists[1])), hists
    ):
        keep = sorted(feeds)[1:]
        specs.append([(feeds[a], 0, INF) for a in keep])
        kept.append([c for c in h if c.actor in keep])
    return specs, kept, {}


def _case_32_writers_tied(root):
    history = _tied_history()
    assert len({c.actor for c in history}) == 32
    return [
        _whole(_image_feeds(root, [history])[0]),
        _whole(_rows_feeds(history)),
    ], [history, history], {}


def _case_padded_buckets(root):
    hists = [_multi_history(seed) for seed in (95, 96)]
    specs = [_whole(_image_feeds(root, hists[:1])[0]),
             _whole(_rows_feeds(hists[1]))]
    return specs, hists, {"n_docs": 8, "n_rows": 512, "n_pred": 256}


GENERAL_CASES = {
    "slab_image": _case_slab_image,
    "rows_backed": _case_rows_backed,
    "both_kinds_in_one_slab": _case_both_kinds_in_one_slab,
    "planes_without_meta": _case_planes_without_meta,
    "late_windows": _case_late_windows,
    "feed_listed_twice": _case_feed_listed_twice,
    "empty_window": _case_empty_window,
    "empty_key_table_last": _case_empty_key_table_last,
    "dropped_by_the_fixpoint": _case_dropped_by_the_fixpoint,
    "32_writers_tied": _case_32_writers_tied,
    "padded_buckets": _case_padded_buckets,
}


def _decoded(batch, d):
    from hypermerge_tpu.crdt.frontend_state import FrontendDoc
    from hypermerge_tpu.ops.host_kernel import run_batch_host
    from hypermerge_tpu.ops.materialize import DecodedBatch, decode_patch

    from helpers import plainify

    front = FrontendDoc()
    front.apply_patch(
        decode_patch(DecodedBatch(batch, run_batch_host(batch)), d)
    )
    return plainify(front.materialize())


@needs_pack
@pytest.mark.parametrize("case", sorted(GENERAL_CASES))
def test_general_pack_native_gather_twin_and_per_op_pack(
    case, tmp_path, monkeypatch
):
    """The general pack over every kind of feed and window it is handed:
    the native gather (counted as used) and the numpy twin give the same
    batch bit for bit, and it holds the docs the per-op pack
    (`pack_docs`) makes of the same changes: the same op counts, the
    same decoded states. (`rows_backed` was
    test_multi_actor_general_path_unchanged.)"""
    from hypermerge_tpu.storage.colcache import PLANE_NAMES

    assert columnar._GATHER_PLANES == PLANE_NAMES[:-1]  # all but `flags`
    specs, hists, kw = GENERAL_CASES[case](tmp_path)
    b_native, b_numpy = _pack_both(monkeypatch, specs, **kw)
    assert b_native.packed_by == b_numpy.packed_by == "general"
    _assert_batches_identical(b_native, b_numpy)
    _assert_batches_identical(b_numpy, b_native)
    n_feeds = len({id(fc) for spec in specs for fc, _s, _e in spec})
    assert b_native.gather_feeds == (n_feeds, 0)
    assert b_numpy.gather_feeds == (0, n_feeds)
    for name, want in kw.items():
        got = {"n_docs": b_native.n_docs, "n_rows": b_native.n_rows,
               "n_pred": b_native.psrc.shape[1]}[name]
        assert got == want
    b_ref = pack_docs(hists)
    D = len(hists)
    assert b_native.n_ops[:D].tolist() == b_ref.n_ops.tolist()
    assert not b_native.n_ops[D:].any()
    for d in range(D):
        assert _decoded(b_native, d) == _decoded(b_ref, d), d
    if case != "empty_window":
        # nothing here made a feed's dense matrix: a cold open keeps none
        assert all(
            fc.rows is None
            for spec in specs for fc, _s, _e in spec
            if fc.plane_meta is not None
        )


@needs_pack
def test_pack_releases_gil(tmp_path, monkeypatch):
    """The hm_pack_prefix binding must DROP the GIL (ctypes.CDLL
    foreign-call semantics) — the streaming slab pipeline's pack
    worker relies on it to overlap packing with sidecar IO. Two
    checks: (1) a Python thread keeps making progress while packs run
    (GIL actually released — meaningful even on one core); (2) with
    >=2 cores, two concurrent packs on DISTINCT output buffers overlap
    in wall time."""
    import os
    import threading
    import time

    from hypermerge_tpu import native
    from hypermerge_tpu.ops.synth import synth_changes

    assert native.pack_drops_gil()
    monkeypatch.setenv("HM_NATIVE_PACK", "1")

    # one sizeable plane-backed feed; packs of 8 whole-prefix windows
    # of it spend their time inside the native batch entry
    history = synth_changes(
        40_000, n_actors=1, ops_per_change=64, text_frac=0.5, seed=9
    )
    cc = _plane_cache(tmp_path, "gil", history)
    fc = cc.columns()
    assert fc.planes is not None

    def one_pack():
        specs = [[(fc, 0, INF)] for _ in range(8)]
        b = pack_docs_columns(specs)
        assert b.n_rows >= 40_000

    one_pack()  # warm the interner memos / allocator

    # -- (1) GIL-progress: a spinner thread must not starve ------------
    stop = [False]
    spins = [0]

    def spinner():
        while not stop[0]:
            spins[0] += 1

    t = threading.Thread(target=spinner, daemon=True)
    t.start()
    time.sleep(0.02)  # let it settle
    spins[0] = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.4:
        one_pack()
    held_spins = spins[0]
    stop[0] = True
    t.join(5)
    # a GIL-holding native call would leave the spinner almost no
    # iterations; released, it runs freely (other core) or timeslices
    assert held_spins > 10_000, (
        f"spinner starved during native packs ({held_spins} iters): "
        "is the pack binding holding the GIL?"
    )

    # -- (2) wall-time overlap of two concurrent packs -----------------
    if (os.cpu_count() or 1) < 2:
        cc.close()
        pytest.skip("single core: wall-time overlap is unmeasurable")

    def packs(n):
        for _ in range(n):
            one_pack()

    # min serial vs min concurrent across attempts: unrelated machine
    # load inflates both, the minima are what the scheduling allows
    best_serial = best_conc = None
    for _attempt in range(5):
        t0 = time.perf_counter()
        packs(6)
        serial = time.perf_counter() - t0
        ts = [
            threading.Thread(target=packs, args=(3,), daemon=True)
            for _ in range(2)
        ]
        t0 = time.perf_counter()
        for th in ts:
            th.start()
        for th in ts:
            th.join(60)
        conc = time.perf_counter() - t0
        best_serial = min(serial, best_serial or serial)
        best_conc = min(conc, best_conc or conc)
        if best_conc < 0.9 * best_serial:
            break
    cc.close()
    ratio = best_conc / max(best_serial, 1e-9)
    if ratio >= 0.9:
        # the spinner above already PROVED the GIL drops; wall-time
        # overlap additionally needs a genuinely idle second core,
        # which a loaded CI box can't promise — don't flake the suite
        pytest.skip(
            f"GIL release proven by spinner, but no idle core to show "
            f"wall overlap (conc/serial={ratio:.2f})"
        )
    # reaching here means the overlap was actually observed (< 0.9);
    # the hard GIL enforcement is the spinner assert above


@needs_pack
def test_gather_releases_gil(tmp_path, monkeypatch):
    """hm_pack_gather runs with the GIL dropped: a second Python thread
    advances WHILE the native gather of a general slab runs (counted
    inside the calls only; the numpy stages around them prove nothing
    about the binding)."""
    import threading
    import time

    from hypermerge_tpu.ops.synth import synth_changes

    assert native.pack_drops_gil()
    monkeypatch.setenv("HM_NATIVE_PACK", "1")
    history = synth_changes(
        60_000, n_actors=3, ops_per_change=64, text_frac=0.5, seed=9
    )
    feeds = _image_feeds(tmp_path, [history])[0]
    specs = [_whole(feeds) for _ in range(8)]

    stop = [False]
    spins = [0]

    def spinner():
        while not stop[0]:
            spins[0] += 1

    inside = {"spins": 0, "s": 0.0, "calls": 0}
    orig = columnar._native_gather

    def timed_gather(*a, **k):
        n0, t0 = spins[0], time.perf_counter()
        out = orig(*a, **k)
        inside["s"] += time.perf_counter() - t0
        inside["spins"] += spins[0] - n0
        inside["calls"] += bool(out)
        return out

    monkeypatch.setattr(columnar, "_native_gather", timed_gather)
    assert pack_docs_columns(specs).n_rows >= 60_000  # warm
    t = threading.Thread(target=spinner, daemon=True)
    t.start()
    time.sleep(0.02)
    inside.update(spins=0, s=0.0, calls=0)
    t0 = time.perf_counter()
    while inside["s"] < 0.2 and time.perf_counter() - t0 < 20:
        pack_docs_columns(specs)
    stop[0] = True
    t.join(5)
    assert inside["calls"] >= 1
    # a binding that held the GIL would leave the spinner NO iteration
    # inside the calls (a C call never yields it); released, it gets the
    # other core or its share of this one
    assert inside["spins"] > 2_000, (
        f"spinner starved inside {inside['calls']} native gathers "
        f"({inside['spins']} iterations in {inside['s']:.3f} s): is "
        "hm_pack_gather bound with the GIL held?"
    )


@needs_pack
@pytest.mark.parametrize("backing", ["image", "rows"])
def test_overrunning_sidecar_reaches_no_native_loop(
    backing, tmp_path, monkeypatch
):
    """A corrupt sidecar whose row_ends claim more rows than its planes
    hold fails in the gather as it did before there was a native one
    (ValueError), and no native loop is entered, so nothing is read
    outside the mapping; a pred whose src row lies past the planes is
    in no window and is ignored by both gathers alike."""
    history = _multi_history(97)
    feeds = (
        _image_feeds(tmp_path, [history])[0]
        if backing == "image"
        else _rows_feeds(history)
    )
    first = sorted(feeds)[0]
    fc = feeds[first]
    assert fc.n_changes >= 2 and len(fc.preds)
    calls = _spy_native(monkeypatch)

    ends = fc.row_ends.copy()
    ends[-1] = fc.n_rows + 7
    bad = {**feeds, first: dataclasses.replace(fc, row_ends=ends)}
    for env in ("1", "0"):
        monkeypatch.setenv("HM_NATIVE_PACK", env)
        with pytest.raises(ValueError, match="overruns"):
            pack_docs_columns([_whole(bad)])
    assert calls == []

    preds = np.concatenate(
        [fc.preds, [[fc.n_rows + 3, 1, 0]]], axis=0, dtype=np.int32
    )
    stray = {**feeds, first: dataclasses.replace(fc, preds=preds)}
    b_stray = _pack_both(monkeypatch, [_whole(stray)])
    b_clean = _pack_both(monkeypatch, [_whole(feeds)])
    for got, want in zip(b_stray, b_clean):
        _assert_batches_identical(got, want)
    _assert_batches_identical(*b_stray)


@needs_pack
def test_counter_and_text_kinds_roundtrip(tmp_path, monkeypatch):
    """INC lanes (dt/ref) and text inserts through both twins, then a
    full device-twin decode to pin semantic equality too."""
    site = Site("actor00")
    site.change(lambda d: d.__setitem__("n", Counter(2)))
    site.change(lambda d: d.increment("n", 5))
    site.change(lambda d: d.__setitem__("t", Text("hey")))
    site.change(lambda d: d["t"].insert(3, "!"))
    cc = _plane_cache(tmp_path, "c0", list(site.opset.history))
    specs = [[(cc.columns(), 0, INF)]]
    b_native, b_numpy = _pack_both(monkeypatch, specs)
    _assert_batches_identical(b_native, b_numpy)
    got = _decoded(b_native, 0)
    assert got["n"] == ("__counter__", 7)
    assert got["t"] == ("__text__", "hey!")
    cc.close()
