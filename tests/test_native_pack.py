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
from hypermerge_tpu import native, telemetry
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


def _per_feed_feeds(root, histories):
    """The feeds _image_feeds wrote under `root`, each through the
    per-feed loader: a bytes copy of its image, every plane an array
    from the start (what every load gave before the slab-granular
    pass handed out lazy planes)."""
    fn = file_column_storage_fn(str(root))
    return [
        {
            actor: FeedColumnCache(
                fn(f"d{d}-{actor}"), writer=actor
            ).columns()
            for actor in _by_actor(history)
        }
        for d, history in enumerate(histories)
    ]


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
@pytest.mark.parametrize("path", ["prefix", "general"])
def test_bulk_loaded_feeds_pack_natively_and_build_no_plane(
    path, tmp_path, monkeypatch
):
    """A slab-granular load hands the pack planes nobody has made yet
    (colcache._ImagePlanes). The native gate, the native prefix pack
    and the native gather read the feeds' plane_meta and make none; the
    batch is, bit for bit, the one the per-feed loader's columns give."""
    hists = [
        _single_writer_history(seed) if path == "prefix"
        else _multi_history(seed)
        for seed in (61, 62, 63, 64)
    ]
    bulk = _image_feeds(tmp_path, hists)
    ref = _per_feed_feeds(tmp_path, hists)
    calls = _spy_native(monkeypatch)
    monkeypatch.setenv("HM_NATIVE_PACK", "1")
    built = telemetry.counter("loader.cols_planes_built")
    before = built.value()
    got = pack_docs_columns([_whole(f) for f in bulk])
    assert calls and all(calls), "native entry point was not used"
    assert built.value() == before
    assert all(
        fc.planes._built == {} for feeds in bulk for fc in feeds.values()
    )
    want = pack_docs_columns([_whole(f) for f in ref])
    _assert_batches_identical(got, want)
    # the numpy twin reads them through the mapping: counted, a feed once
    monkeypatch.setenv("HM_NATIVE_PACK", "0")
    _assert_batches_identical(
        pack_docs_columns([_whole(f) for f in bulk]), want
    )
    assert built.value() == before + sum(len(f) for f in bulk)


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

    from hypermerge_tpu import native, telemetry
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


# ---------------------------------------------------------------------------
# the prefix pack's gate: hm_prefix_gate == _prefix_single_ok, feed by feed

_GATE_DTYPES = sorted(columnar._DT_CODE, key=columnar._DT_CODE.get)


def _gate_feed(n, fault=None, dtype=np.int16, backing="planes", n_preds=5):
    """A FeedColumns of `n` rows holding what the gate reads (and the
    `action` plane its row count comes from), sound unless `fault`
    names the way it fails; `backing` "planes": arrays of their own,
    "image": slices of one buffer behind one-byte tags, so no plane is
    aligned, described by plane_meta as a v3 image's are."""
    from hypermerge_tpu.storage.colcache import PLANE_NAMES, FeedColumns

    r = random.Random(n * 31 + len(fault or ""))
    cols = {name: np.zeros(n, np.int64) for name in PLANE_NAMES}
    cols["ctr"] = np.arange(1, n + 1)
    cols["obj_a"] = np.asarray([r.choice((0, -1)) for _ in range(n)], np.int64)
    cols["ref_a"] = np.asarray(
        [r.choice((0, -2, -3)) for _ in range(n)], np.int64
    )
    preds = np.zeros((n_preds if n else 0, 3), np.int32)
    if n:
        preds[:, 0] = sorted(r.randrange(n) for _ in range(len(preds)))
        preds[:, 1] = 1
    at = r.randrange(n) if n else 0
    if fault == "foreign_obj_a":
        cols["obj_a"][at] = 1
    elif fault == "foreign_ref_a":
        cols["ref_a"][at] = 2
    elif fault == "ctr_gap":
        cols["ctr"][at:] += 1
    elif fault == "ctr_not_from_1":
        cols["ctr"] = cols["ctr"] - 1 if dtype == np.uint8 else cols["ctr"] + 1
    elif fault == "foreign_pred":
        preds[r.randrange(len(preds)), 2] = 1
    elif fault == "ctr_too_narrow":
        pass  # the caller hands a dtype that cannot hold n: values wrap
    else:
        assert fault is None, fault
    signed = np.int8 if dtype == np.uint8 else dtype
    dts = {name: np.dtype(np.uint8) for name in PLANE_NAMES}
    dts.update(ctr=np.dtype(dtype), obj_a=np.dtype(signed),
               ref_a=np.dtype(signed))
    if dtype == np.uint8 and fault != "foreign_obj_a":
        cols["obj_a"][:] = 0  # an all-zero plane narrows to uint8
        dts["obj_a"] = np.dtype(np.uint8)
    with np.errstate(over="ignore"):
        planes = {k: v.astype(dts[k]) for k, v in cols.items()}
    meta = None
    if backing == "image":
        size = sum(1 + p.nbytes for p in planes.values()) + 1 + preds.nbytes
        buf = np.zeros(size, np.uint8)
        offs = np.empty(len(PLANE_NAMES), np.int64)
        code = np.empty(len(PLANE_NAMES), np.uint8)
        pos = 0
        for i, name in enumerate(PLANE_NAMES):
            p = planes[name]
            pos += 1
            offs[i], code[i] = pos, columnar._DT_CODE[p.dtype]
            buf[pos:pos + p.nbytes] = p.view(np.uint8)
            planes[name] = buf[pos:pos + p.nbytes].view(p.dtype)
            pos += p.nbytes
        pos += 1
        buf[pos:pos + preds.nbytes] = preds.reshape(-1).view(np.uint8)
        preds = buf[pos:pos + preds.nbytes].view(np.int32).reshape(-1, 3)
        meta = (columnar._ptr(buf), offs, code, buf)
    return FeedColumns(
        rows=None, preds=preds, actors=["w"], keys=[], strings=[],
        floats=[], bigints=[], n_changes=1, ok_prefix_len=1,
        row_ends=np.asarray([0, n], np.int64), planes=planes,
        plane_meta=meta,
    )


def _twin_verdict(fc):
    """_prefix_single_ok's verdict, leaving no latch behind."""
    ok = columnar._prefix_single_ok(fc)
    del fc._prefix_single_ok
    return ok


GATE_CASES = {
    "sound": (40, None, True),
    "foreign_obj_a": (40, "foreign_obj_a", False),
    "foreign_ref_a": (40, "foreign_ref_a", False),
    "ctr_gap": (40, "ctr_gap", False),
    "ctr_not_from_1": (40, "ctr_not_from_1", False),
    "foreign_pred": (40, "foreign_pred", False),
    "empty": (0, None, True),
    "one_row": (1, None, True),
    "one_row_foreign": (1, "foreign_ref_a", False),
    "long": (3000, None, True),
    "long_gap_late": (3000, "ctr_gap", False),
}


@needs_pack
@pytest.mark.parametrize("backing", ["planes", "image"])
@pytest.mark.parametrize("case", sorted(GATE_CASES))
def test_gate_native_verdict_is_the_twins(case, backing, monkeypatch):
    """hm_prefix_gate and _prefix_single_ok give one verdict: a sound
    feed, each way to fail, an empty feed, a feed of one row; planes of
    their own and unaligned slices of one image. The native verdict is
    latched, and the slab's verdict is the feed's."""
    monkeypatch.setenv("HM_NATIVE_PACK", "1")
    n, fault, want = GATE_CASES[case]
    dtype = np.int16 if n > 100 else np.int8
    fc = _gate_feed(n, fault, dtype, backing)
    assert _twin_verdict(fc) is want
    assert columnar._prefix_single_slab([[(fc, 0, INF)]]) == (want, 1, 0)
    assert fc._prefix_single_ok is want


@needs_pack
@pytest.mark.parametrize("dtype", _GATE_DTYPES, ids=str)
@pytest.mark.parametrize("backing", ["planes", "image"])
def test_gate_reads_every_narrow_dtype(dtype, backing, monkeypatch):
    """Every dtype a v3 image narrows a plane to (_DT_CODE), sound and
    failing each way, fuzzed: the native verdict of a slab's feeds is
    the twin's, feed by feed."""
    monkeypatch.setenv("HM_NATIVE_PACK", "1")
    r = random.Random(str(dtype) + backing)
    faults = [None, "foreign_obj_a", "foreign_ref_a", "ctr_gap",
              "ctr_not_from_1", "foreign_pred"]
    top = 100 if np.dtype(dtype).itemsize == 1 else 700
    fcs = [
        _gate_feed(r.randrange(2, top), r.choice(faults), dtype, backing)
        for _ in range(40)
    ]
    want = [_twin_verdict(fc) for fc in fcs]
    assert True in want and False in want
    for fc, ok in zip(fcs, want):
        assert columnar._prefix_single_slab([[(fc, 0, INF)]]) == (ok, 1, 0)
        assert fc._prefix_single_ok is ok


@needs_pack
def test_gate_ctr_too_narrow_for_its_rows_answers_no(monkeypatch):
    """A ctr plane whose dtype cannot hold the feed's row count (a
    corrupt sidecar: the writer narrows to what holds the column)
    answers "no" and raises nothing: its wrapped values are not the
    dense counters the prefix pack resolves references by."""
    monkeypatch.setenv("HM_NATIVE_PACK", "1")
    for dtype, n in ((np.int8, 200), (np.uint8, 300), (np.int16, 40_000)):
        fc = _gate_feed(n, "ctr_too_narrow", dtype)
        assert fc.planes["ctr"].dtype == dtype and fc.n_rows == n
        assert columnar._prefix_single_slab([[(fc, 0, INF)]]) == (
            False, 1, 0)


class _NoPlanes(dict):
    def __getitem__(self, name):
        raise AssertionError(f"the gate read plane {name!r}")

    get = __getitem__


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("how", ["two_feeds", "late_start"])
def test_gate_mixed_slab_reads_no_plane(where, how, monkeypatch):
    """One doc of two feeds (or one read from past its start) anywhere
    in a slab: the structural pass answers, before any plane or pred is
    read and with no native call."""
    from hypermerge_tpu.storage.colcache import FeedColumns

    class _NoPreds:
        def __getattr__(self, name):
            raise AssertionError(f"the gate read preds.{name}")

    def untouchable():
        return FeedColumns(
            rows=None, preds=_NoPreds(), actors=["w"], keys=[], strings=[],
            floats=[], bigints=[], n_changes=1, ok_prefix_len=1,
            row_ends=np.asarray([0, 4], np.int64), planes=_NoPlanes(),
        )

    monkeypatch.setattr(
        columnar, "_gate_sources",
        lambda fcs: pytest.fail("the gate described feeds"),
    )
    specs = [[(untouchable(), 0, INF)] for _ in range(9)]
    odd = (
        [(untouchable(), 0, INF), (untouchable(), 0, INF)]
        if how == "two_feeds"
        else [(untouchable(), 1, INF)]
    )
    specs[{"first": 0, "middle": 4, "last": 8}[where]] = odd
    assert columnar._prefix_single_slab(specs) == (False, 0, 0)


@needs_pack
def test_gate_stops_at_the_first_feed_that_fails(monkeypatch):
    """A slab of single-feed docs of which one fails: the feeds before
    it are judged and latched, the feeds after it are not (the slab's
    verdict is all the caller asked for), so the case costs no more
    than the feed-by-feed gate did."""
    monkeypatch.setenv("HM_NATIVE_PACK", "1")
    fcs = [_gate_feed(30 + i, None, np.int8, "image") for i in range(8)]
    fcs[3] = _gate_feed(33, "foreign_ref_a", np.int8, "image")
    specs = [[(fc, 0, INF)] for fc in fcs]
    assert columnar._prefix_single_slab(specs) == (False, 4, 0)
    latches = [getattr(fc, "_prefix_single_ok", None) for fc in fcs]
    assert latches == [True, True, True, False] + [None] * 4
    # the failed latch answers the next gate with no call at all
    monkeypatch.setattr(
        columnar, "_gate_sources",
        lambda fcs: pytest.fail("a latched failure was judged again"),
    )
    assert columnar._prefix_single_slab(specs) == (False, 0, 0)


def _gate_counters():
    from hypermerge_tpu import telemetry

    snap = telemetry.snapshot()
    return (snap.get("pipeline.pack_gate_native_feeds", 0),
            snap.get("pipeline.pack_gate_twin_feeds", 0))


@pytest.mark.parametrize("arm", ["rows_backed", "native_pack_off", "mixed"])
def test_gate_twin_arm_is_chosen_from_what_it_sees(arm, tmp_path,
                                                   monkeypatch):
    """A rows-backed feed and HM_NATIVE_PACK=0 take the numpy twin and
    count as twin feeds (batch.gate_feeds, the telemetry counters); a
    slab of both kinds splits feed by feed; the packed batch is the
    same bytes whoever judged."""
    monkeypatch.setenv("HM_NATIVE_PACK", "0" if arm == "native_pack_off"
                       else "1")
    has_native = native.pack_lib() is not None
    histories = [_single_writer_history(s) for s in (21, 22, 23, 24)]
    imaged = [_plane_cache(tmp_path, f"g{i}", h)
              for i, h in enumerate(histories)]
    rows = [next(iter(_rows_feeds(h).values())) for h in histories]
    if arm == "rows_backed":
        fcs, want = rows, (0, 4)
    elif arm == "native_pack_off":
        fcs, want = [cc.columns() for cc in imaged], (0, 4)
    else:
        fcs = [imaged[0].columns(), rows[1], imaged[2].columns(), rows[3]]
        want = (2, 2) if has_native else (0, 4)
    specs = [[(fc, 0, INF)] for fc in fcs]
    before = _gate_counters()
    batch = pack_docs_columns(specs)
    after = _gate_counters()
    assert batch.packed_by == "prefix" and batch.gate_feeds == want
    assert (after[0] - before[0], after[1] - before[1]) == want
    assert all(fc._prefix_single_ok is True for fc in fcs)
    # every feed carries its latch: a second gate judges none
    again = pack_docs_columns(specs)
    assert again.gate_feeds == (0, 0) and _gate_counters() == after
    _assert_batches_identical(batch, again)
    for fc in fcs:
        del fc._prefix_single_ok
    monkeypatch.setenv("HM_NATIVE_PACK", "0")
    _assert_batches_identical(batch, pack_docs_columns(specs))
    for cc in imaged:
        cc.close()


@needs_pack
def test_gate_latches_the_native_verdict_and_calls_once(tmp_path,
                                                        monkeypatch):
    """The latch is set from the native verdict, and a second gate over
    the same feeds makes no native call and builds no table."""
    monkeypatch.setenv("HM_NATIVE_PACK", "1")
    caches = [_plane_cache(tmp_path, f"l{s}", _single_writer_history(s))
              for s in (31, 32, 33)]
    fcs = [cc.columns() for cc in caches]
    specs = [[(fc, 0, INF)] for fc in fcs] + [[(fcs[0], 0, INF)]]
    monkeypatch.setattr(
        columnar, "_prefix_single_ok",
        lambda fc: pytest.fail("the twin judged a plane-backed feed"),
    )
    assert not any(hasattr(fc, "_prefix_single_ok") for fc in fcs)
    # a feed two docs share is judged once
    assert columnar._prefix_single_slab(specs) == (True, 3, 0)
    assert all(fc._prefix_single_ok is True for fc in fcs)
    monkeypatch.setattr(
        columnar, "_gate_sources",
        lambda fcs: pytest.fail("a latched feed was described again"),
    )
    assert columnar._prefix_single_slab(specs) == (True, 0, 0)
    for cc in caches:
        cc.close()


@needs_pack
def test_gate_releases_gil(monkeypatch):
    """hm_prefix_gate runs with the GIL dropped: a second Python thread
    advances WHILE the native gate of a slab runs (counted inside the
    calls only, the pattern of test_gather_releases_gil)."""
    import threading
    import time

    assert native.pack_drops_gil()
    monkeypatch.setenv("HM_NATIVE_PACK", "1")
    fcs = [_gate_feed(2_000_000, None, np.int32, "image", n_preds=50_000)
           for _ in range(4)]
    specs = [[(fc, 0, INF)] for fc in fcs]
    lib = columnar._native_pack_lib()
    stop = [False]
    spins = [0]

    def spinner():
        while not stop[0]:
            spins[0] += 1

    inside = {"spins": 0, "s": 0.0, "calls": 0}

    class _Timed:
        def __getattr__(self, name):
            return getattr(lib, name)

        def hm_prefix_gate(self, *a):
            n0, t0 = spins[0], time.perf_counter()
            rc = lib.hm_prefix_gate(*a)
            inside["s"] += time.perf_counter() - t0
            inside["spins"] += spins[0] - n0
            inside["calls"] += rc == 0
            return rc

    monkeypatch.setattr(columnar, "_native_pack_lib", lambda: _Timed())

    def gate():
        for fc in fcs:
            fc.__dict__.pop("_prefix_single_ok", None)
        assert columnar._prefix_single_slab(specs) == (True, 4, 0)

    gate()  # warm
    t = threading.Thread(target=spinner, daemon=True)
    t.start()
    time.sleep(0.02)
    inside.update(spins=0, s=0.0, calls=0)
    t0 = time.perf_counter()
    while inside["s"] < 0.2 and time.perf_counter() - t0 < 20:
        gate()
    stop[0] = True
    t.join(5)
    assert inside["calls"] >= 1
    # a binding that held the GIL would leave the spinner NO iteration
    # inside the calls (a C call never yields it)
    assert inside["spins"] > 2_000, (
        f"spinner starved inside {inside['calls']} native gates "
        f"({inside['spins']} iterations in {inside['s']:.3f} s): is "
        "hm_prefix_gate bound with the GIL held?"
    )
