"""Native C++ bulk pack (hm_pack_prefix) vs the numpy twin.

The cold-open pack stage has two implementations: the C++ batch entry
point that emits the padded column planes straight from the feeds'
checkpoint planes (native/src/hm_native.cpp), and the numpy scatter in
ops/columnar.py that remains both the fallback and the correctness
reference. These tests pin them BIT-identical — same values, same wire
dtypes — over fuzzed histories covering the prefix-single fast path,
every value-kind lane, empty/padded docs, and (through the general
sorted-composite path, which the native entry must leave untouched)
multi-actor tie-break lanes."""

import random

import numpy as np
import pytest

from helpers import Site, random_mutation, sync
from hypermerge_tpu import native
from hypermerge_tpu.models import Counter, Text
from hypermerge_tpu.ops import columnar
from hypermerge_tpu.ops.columnar import COLUMNS, pack_docs_columns
from hypermerge_tpu.storage.colcache import (
    FeedColumnCache,
    FileColumnStorageV2,
    MemoryColumnStorage,
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


def _pack_both(monkeypatch, specs, counted=True, **kw):
    """(native_batch, numpy_batch, native_call_count)."""
    calls = []
    orig = columnar._native_pack_prefix

    def spy(*a, **k):
        out = orig(*a, **k)
        calls.append(bool(out))
        return out

    monkeypatch.setattr(columnar, "_native_pack_prefix", spy)
    monkeypatch.setenv("HM_NATIVE_PACK", "1")
    b_native = pack_docs_columns(specs, **kw)
    monkeypatch.setenv("HM_NATIVE_PACK", "0")
    b_numpy = pack_docs_columns(specs, **kw)
    if counted:
        assert calls and all(calls), "native entry point was not used"
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


def test_multi_actor_general_path_unchanged(monkeypatch):
    """Multi-actor histories take the general sorted-composite path; the
    native toggle must not change a single bit there either (the fuzz
    corpus of test_bulk_cold_start runs with the toggle's default)."""
    specs = []
    for seed in (21, 22, 23):
        r = random.Random(seed)
        sites = [Site(f"actor{i:02d}") for i in range(3)]
        for _ in range(30):
            random_mutation(r.choice(sites), r)
            if r.random() < 0.3:
                sync(*sites)
        sync(*sites)
        caches = {}
        for c in sorted(
            sites[0].opset.history, key=lambda c: (c.actor, c.seq)
        ):
            cc = caches.setdefault(
                c.actor,
                FeedColumnCache(MemoryColumnStorage(), writer=c.actor),
            )
            cc.append_change(c)
        specs.append([(cc.columns(), 0, INF) for cc in caches.values()])
    b_native, b_numpy = _pack_both(monkeypatch, specs, counted=False)
    _assert_batches_identical(b_native, b_numpy)


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
def test_counter_and_text_kinds_roundtrip(tmp_path, monkeypatch):
    """INC lanes (dt/ref) and text inserts through both twins, then a
    full device-twin decode to pin semantic equality too."""
    from hypermerge_tpu.crdt.frontend_state import FrontendDoc
    from hypermerge_tpu.ops.host_kernel import run_batch_host
    from hypermerge_tpu.ops.materialize import DecodedBatch, decode_patch

    site = Site("actor00")
    site.change(lambda d: d.__setitem__("n", Counter(2)))
    site.change(lambda d: d.increment("n", 5))
    site.change(lambda d: d.__setitem__("t", Text("hey")))
    site.change(lambda d: d["t"].insert(3, "!"))
    cc = _plane_cache(tmp_path, "c0", list(site.opset.history))
    specs = [[(cc.columns(), 0, INF)]]
    b_native, b_numpy = _pack_both(monkeypatch, specs)
    _assert_batches_identical(b_native, b_numpy)
    dec = DecodedBatch(b_native, run_batch_host(b_native))
    front = FrontendDoc()
    front.apply_patch(decode_patch(dec, 0))
    from helpers import plainify

    got = plainify(front.materialize())
    assert got["n"] == ("__counter__", 7)
    assert got["t"] == ("__text__", "hey!")
    cc.close()
