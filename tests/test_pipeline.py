"""Streaming slab pipeline (backend/pipeline.py, driven by
backend/bulk_loader.py): equivalence with the host OpSet replay,
failure-path hygiene, and round-robin device dispatch.

The pipeline overlaps IO, pack, dispatch, and fetch across slabs, so a
bulk cold open costs ~max(stage), not sum(stages) — and every doc it
opens must carry the summary and the value the host OpSet replay of its
feeds gives, whichever pack (numpy reference or native) fed it. A stage
failure must fail the whole load as a unit: no hung worker threads, no
pending device refs, queues drained.
"""

import random
import shutil
import threading
import time

import pytest

from helpers import opset_replay_state, plainify
from hypermerge_tpu.backend.pipeline import PipelineError
from hypermerge_tpu.models import Counter, Text
from hypermerge_tpu.repo import Repo
from hypermerge_tpu.utils.ids import to_doc_url, validate_doc_url


def _make_corpus(path, n_docs=14, seed=7):
    """Single-writer docs of varied size/shape (maps, text, counters)
    so slabs bucket at different [D, N] shapes and every value lane is
    exercised."""
    r = random.Random(seed)
    repo = Repo(path=str(path))
    urls = []
    for i in range(n_docs):
        u = repo.create({"i": i, "t": Text(f"doc{i}:"), "hits": Counter(0)})
        for k in range(r.randrange(1, 9)):
            kind = r.randrange(3)
            if kind == 0:
                repo.change(
                    u, lambda d, k=k: d.__setitem__(f"k{k}", k * 3)
                )
            elif kind == 1:
                repo.change(
                    u, lambda d, k=k: d["t"].insert(0, f"<{k}>")
                )
            else:
                repo.change(u, lambda d: d.increment("hits", 2))
        urls.append(u)
    want = {u: plainify(repo.doc(u)) for u in urls}
    repo.close()
    return urls, want


def _add_gap_doc(path):
    """One doc with a seq gap in its feed: must fall back to host
    replay (fallback accounting)."""
    from hypermerge_tpu.crdt.change import Action, Change, Op, ROOT
    from hypermerge_tpu.storage import block as blockmod

    repo = Repo(path=str(path))
    url = repo.create({"gap": True})
    doc_id = validate_doc_url(url)
    actor = repo.back.actors[doc_id]
    head = actor.seq_head
    max_op = max(
        c.max_op for c in actor.changes_in_window(0, float("inf"))
    )
    actor.feed._append_raw(
        blockmod.pack(
            Change(
                actor=doc_id,
                seq=head + 2,  # head+1 never written
                start_op=max_op + 1,
                deps={},
                ops=(Op(action=Action.SET, obj=ROOT, key="late", value=1),),
            ).to_json()
        )
    )
    repo.close()
    return url


def _doc_summary_bytes(summ, doc_id):
    arrays, j = summ.arrays(doc_id)
    out = {
        k: arrays[k][j].tobytes()
        for k in ("map_winner", "elem_live", "elem_order")
    }
    out["n_live"] = int(arrays["n_live_elems"][j])
    out["n_map"] = int(arrays["n_map_entries"][j])
    out["clock"] = summ.doc(doc_id)["clock"]
    return out


def _memo_snapshot(back):
    out = {}
    for doc_id, m in back.loader._summary_memo.items():
        out[doc_id] = {
            "clock": dict(m["clock"]),
            "N": m["N"],
            "n_live": m["n_live"],
            "n_map": m["n_map"],
            "mw_bits": m["mw_bits"].tobytes(),
            "el_bits": m["el_bits"].tobytes(),
            "order": m["order"].tobytes(),
            "clock_row": m["clock_row"].tobytes(),
        }
    return out


def _load_twice(path, ids, native_pack, monkeypatch, slab):
    """Two bulk loads in one backend (the second is all memo hits);
    returns per-doc summary bytes for both, the memo snapshot, the
    counts of each load, and each fast doc's (summary, value) as the
    load gave it beside the host OpSet replay's."""
    monkeypatch.setenv("HM_NATIVE_PACK", native_pack)
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")  # force device path
    repo = Repo(path=str(path))
    back = repo.back
    back.load_documents_bulk(ids, slab=slab)
    stats1 = dict(back.last_bulk_stats)
    s1 = back.fetch_bulk_summaries()
    first = {d: _doc_summary_bytes(s1, d) for d in s1.doc_ids}
    memo = _memo_snapshot(back)
    for doc_id in ids:
        back.close_doc(doc_id)
    back.load_documents_bulk(ids, slab=slab)
    stats2 = dict(back.last_bulk_stats)
    s2 = back.fetch_bulk_summaries()
    second = {d: _doc_summary_bytes(s2, d) for d in s2.doc_ids}
    replay = {}
    for d in s2.doc_ids:
        replay[d] = (
            (s2.doc(d), plainify(repo.doc(to_doc_url(d)))),
            opset_replay_state(back._bulk_history_loader(d)()),
        )
    repo.close()
    counts = [
        {k: st[k] for k in ("docs", "fast", "memo", "fallback")}
        for st in (stats1, stats2)
    ]
    return first, second, memo, counts, replay


def test_pipeline_matches_opset_replay_fuzz(tmp_path, monkeypatch):
    """Fuzzed docs across >=3 slab boundaries, against the real
    reference: every fast doc's summary (elems, map entries, clock) and
    value equal the host OpSet replay of its feeds — on the first
    (packed + dispatched) AND second (memo-served) loads — and the
    numpy and the native pack give byte-identical summary arrays, memo
    contents and doc/fast/fallback counts under the pipeline."""
    src = tmp_path / "src"
    urls, want = _make_corpus(src, n_docs=14)
    gap_url = _add_gap_doc(src)
    ids = [validate_doc_url(u) for u in urls] + [validate_doc_url(gap_url)]

    results = {}
    for pack in ("0", "1"):
        copy = tmp_path / f"repo{pack}"
        shutil.copytree(src, copy)
        results[pack] = _load_twice(
            copy, ids, pack, monkeypatch, slab=4
        )  # 14 fast docs / slab 4 -> 4 slabs (3+ boundaries)

    first0, second0, memo0, counts0, replay0 = results["0"]
    first1, second1, memo1, counts1, replay1 = results["1"]
    assert counts0 == counts1
    assert counts0[0]["fallback"] == 1
    assert counts0[1]["memo"] == counts0[1]["fast"]  # 2nd load: all memo
    assert set(first0) == set(first1) and len(first0) == 14
    for d in first0:
        assert first0[d] == first1[d], f"first-load summary differs: {d}"
    for d in second0:
        assert second0[d] == second1[d], f"memo-load summary differs: {d}"
        assert first0[d] == second0[d], f"memo row != fetched row: {d}"
    assert memo0 == memo1
    by_id = {validate_doc_url(u): want[u] for u in urls}
    for replay in (replay0, replay1):
        assert len(replay) == 14
        for d, (loaded, replayed) in replay.items():
            assert loaded == replayed, d
            assert loaded[1] == by_id[d], d


def test_pipeline_matches_interactive_state(tmp_path, monkeypatch):
    """Pipelined bulk loads materialize the same doc values the writer
    saw (end-to-end through handles, not just summary arrays)."""
    urls, want = _make_corpus(tmp_path / "r", n_docs=9, seed=3)
    repo = Repo(path=str(tmp_path / "r"))
    ids = [validate_doc_url(u) for u in urls]
    repo.back.load_documents_bulk(ids, slab=2)
    summ = repo.back.fetch_bulk_summaries()
    assert len(summ.doc_ids) == 9
    for u in urls:
        assert plainify(repo.doc(u)) == want[u]
    repo.close()


def _assert_pipe_threads_drained(deadline_s=10.0):
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        alive = [
            t.name
            for t in threading.enumerate()
            if t.name.startswith("hm-pipe-")
        ]
        if not alive:
            return
        time.sleep(0.02)
    raise AssertionError(f"pipeline workers leaked: {alive}")


def _call_with_timeout(fn, timeout_s=90.0):
    """Run fn on a worker and re-raise its outcome; a hang fails the
    test instead of wedging the whole suite."""
    box = {}

    def runner():
        try:
            box["ret"] = fn()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            box["exc"] = e

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    t.join(timeout_s)
    assert not t.is_alive(), "bulk load hung"
    if "exc" in box:
        raise box["exc"]
    return box.get("ret")


def test_pipeline_pack_failure_fails_load_cleanly(tmp_path, monkeypatch):
    """A slab whose pack raises must fail the bulk load as a unit: the
    error propagates, every worker drains, and no device refs linger in
    the pending list."""
    import hypermerge_tpu.ops.columnar as columnar

    urls, _want = _make_corpus(tmp_path / "r", n_docs=12, seed=11)
    ids = [validate_doc_url(u) for u in urls]

    real = columnar.pack_docs_columns
    calls = {"n": 0}

    def boom(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("boom-pack")
        return real(*a, **kw)

    monkeypatch.setattr(columnar, "pack_docs_columns", boom)
    repo = Repo(path=str(tmp_path / "r"))
    with pytest.raises(PipelineError) as ei:
        _call_with_timeout(
            lambda: repo.back.load_documents_bulk(ids, slab=4)
        )
    assert "boom-pack" in repr(ei.value.__cause__)
    _assert_pipe_threads_drained()
    assert repo.back.loader._pending_summaries == []
    assert repo.back.loader._fetch_ctx is None
    repo.close()

    # the corpus itself is intact: a fresh backend loads it fine
    monkeypatch.setattr(columnar, "pack_docs_columns", real)
    repo2 = Repo(path=str(tmp_path / "r"))
    repo2.back.load_documents_bulk(ids, slab=4)
    summ = repo2.back.fetch_bulk_summaries()
    assert len(summ.doc_ids) == 12
    repo2.close()


def test_pipeline_fetch_failure_fails_cleanly(tmp_path, monkeypatch):
    """A slab whose summary fetch raises must surface the error (at the
    load or at the barrier, wherever the overlap window puts it) and
    leave no hung workers or pending refs."""
    from hypermerge_tpu.backend.bulk_loader import BulkLoader

    urls, _want = _make_corpus(tmp_path / "r", n_docs=10, seed=13)
    ids = [validate_doc_url(u) for u in urls]
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")  # real device fetches

    real = BulkLoader._fetch_slab
    calls = {"n": 0}

    def boom(self, entry):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("boom-fetch")
        return real(self, entry)

    monkeypatch.setattr(BulkLoader, "_fetch_slab", boom)
    repo = Repo(path=str(tmp_path / "r"))

    def load_and_barrier():
        repo.back.load_documents_bulk(ids, slab=4)
        repo.back.fetch_bulk_summaries()

    with pytest.raises(PipelineError) as ei:
        _call_with_timeout(load_and_barrier)
    assert "boom-fetch" in repr(ei.value.__cause__)
    _assert_pipe_threads_drained()
    assert repo.back.loader._pending_summaries == []
    assert repo.back.loader._fetch_ctx is None
    repo.close()


def test_round_robin_slabs_across_devices(tmp_path, monkeypatch):
    """With >1 visible device successive slabs
    land whole on successive devices (rr_slabs accounting), with
    results identical to the interactive state."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >1 (virtual) device")
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")
    urls, want = _make_corpus(tmp_path / "r", n_docs=6, seed=5)
    repo = Repo(path=str(tmp_path / "r"))
    ids = [validate_doc_url(u) for u in urls]
    repo.back.load_documents_bulk(ids, slab=2)
    summ = repo.back.fetch_bulk_summaries()
    stats = repo.back.last_bulk_stats
    assert stats.get("rr_slabs") == 3, stats
    assert stats.get("rr_devices") == len(jax.devices()), stats
    assert stats.get("sharded_slabs") is None
    assert len(summ.doc_ids) == 6
    for u in urls:
        assert plainify(repo.doc(u)) == want[u]
    repo.close()


def test_slab_round_robin_cycles_and_bounds_inflight():
    """Unit: the scheduler cycles devices and never holds more than
    `depth` unfetched summaries per device."""
    import jax
    import numpy as np

    from hypermerge_tpu.ops.columnar import pack_docs
    from hypermerge_tpu.ops.materialize import fetch_summary
    from hypermerge_tpu.ops.synth import synth_changes
    from hypermerge_tpu.parallel.sharded import SlabRoundRobin

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs >1 (virtual) device")
    rr = SlabRoundRobin(devices[:2], depth=1)
    batches = [
        pack_docs([synth_changes(48, n_actors=1, ops_per_change=8, seed=s)])
        for s in range(5)
    ]
    wires = []
    for b in batches:
        _out, wire = rr.dispatch(b, lean=False)
        wires.append((b, wire))
        for q in rr._inflight.values():
            assert len(q) <= 1
    assert rr._next == 5 % 2
    rr.drain()
    # every slab decodes (placement did not corrupt anything)
    for b, wire in wires:
        arrays = fetch_summary(wire, b, lean=False)
        assert int(np.asarray(arrays["n_map_entries"][0])) >= 0


def test_pipeline_per_chip_stats(tmp_path, monkeypatch):
    """Mesh-aware stats: the pipelined bulk load reports per-chip
    dispatch/fetch busy times and slab placement alongside the stage
    totals."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >1 (virtual) device")
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")
    urls, want = _make_corpus(tmp_path / "r", n_docs=6, seed=7)
    repo = Repo(path=str(tmp_path / "r"))
    ids = [validate_doc_url(u) for u in urls]
    repo.back.load_documents_bulk(ids, slab=2)
    repo.back.fetch_bulk_summaries()
    stats = repo.back.last_bulk_stats
    n = len(jax.devices())
    assert len(stats["t_dispatch_chips"]) == n, stats
    assert len(stats["slabs_per_chip"]) == n
    assert sum(stats["slabs_per_chip"]) == stats["rr_slabs"] == 3
    # every dispatched slab's busy time is attributed to its chip
    assert sum(
        1 for t in stats["t_dispatch_chips"] if t > 0
    ) == sum(1 for s in stats["slabs_per_chip"] if s > 0)
    assert len(stats.get("t_fetch_chips", [])) == n, stats
    assert sum(stats["t_fetch_chips"]) > 0
    # nothing may pin slab wires beyond the barrier
    assert all(not q for q in repo.back.loader._rr._inflight.values())
    for u in urls:
        assert plainify(repo.doc(u)) == want[u]
    repo.close()


def _load_once(path, ids, monkeypatch, slab, workers):
    """One pipelined bulk load with a pack pool of `workers`."""
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")
    monkeypatch.setenv("HM_PACK_WORKERS", workers)
    repo = Repo(path=str(path))
    back = repo.back
    back.load_documents_bulk(ids, slab=slab)
    stats = dict(back.last_bulk_stats)
    summ = back.fetch_bulk_summaries()
    out = {d: _doc_summary_bytes(summ, d) for d in summ.doc_ids}
    repo.close()
    _assert_pipe_threads_drained()
    return out, stats


def test_pipeline_pack_worker_matrix(tmp_path, monkeypatch):
    """HM_PACK_WORKERS={0,1,4} over a ragged-tail corpus (10 docs /
    slab 4 -> 4+4+2): every pool size produces summaries byte-identical
    to the one-worker baseline, and the pool reports its shape
    (pack_workers, per-worker busy lanes, lane wall)."""
    from hypermerge_tpu.backend.pipeline import pack_worker_count

    src = tmp_path / "src"
    urls, _want = _make_corpus(src, n_docs=10, seed=19)
    ids = [validate_doc_url(u) for u in urls]

    results = {}
    for i, workers in enumerate(("1", "0", "4")):
        copy = tmp_path / f"m{i}"
        shutil.copytree(src, copy)
        out, stats = _load_once(copy, ids, monkeypatch, 4, workers)
        want_pool = pack_worker_count()  # env still set from _load_once
        assert stats["pack_workers"] == want_pool
        if workers != "0":
            assert stats["pack_workers"] == int(workers)
        assert len(stats["t_pack_busy_per_worker"]) == want_pool
        assert stats["t_pack_wall"] >= 0.0
        assert sum(stats["t_pack_busy_per_worker"]) >= 0.0
        results[workers] = out
    base = results["1"]
    for cfg, out in results.items():
        assert set(out) == set(base), cfg
        for d in base:
            assert out[d] == base[d], (cfg, d)


@pytest.mark.slow
def test_pipeline_pack_pool_large_shape(tmp_path, monkeypatch):
    """Largest-shape tier: a wider corpus across many slabs with the
    full pool (4 workers) — still byte-identical to one pack worker,
    pool accounting intact."""
    src = tmp_path / "src"
    urls, _want = _make_corpus(src, n_docs=42, seed=23)
    ids = [validate_doc_url(u) for u in urls]

    copy0 = tmp_path / "one"
    shutil.copytree(src, copy0)
    base, _ = _load_once(copy0, ids, monkeypatch, 8, "1")

    copy1 = tmp_path / "pool"
    shutil.copytree(src, copy1)
    out, stats = _load_once(copy1, ids, monkeypatch, 8, "4")
    assert stats["pack_workers"] == 4
    assert len(stats["t_pack_busy_per_worker"]) == 4
    assert set(out) == set(base) and len(out) == 42
    for d in base:
        assert out[d] == base[d], d


def test_pipeline_stats_report_busy_and_critical_path(tmp_path, monkeypatch):
    """A load reports per-stage busy time (t_*_busy) and the
    overlapped wall critical path alongside the canonical keys."""
    urls, _want = _make_corpus(tmp_path / "r", n_docs=5, seed=2)
    repo = Repo(path=str(tmp_path / "r"))
    ids = [validate_doc_url(u) for u in urls]
    repo.back.load_documents_bulk(ids, slab=2)
    repo.back.fetch_bulk_summaries()
    stats = repo.back.last_bulk_stats
    for k in ("t_io", "t_pack", "t_dispatch"):
        assert k in stats
    assert stats["wall_critical_path"] >= 0.0
    assert "t_fetch" in stats and "t_fetch_busy" in stats
    repo.close()


@pytest.mark.parametrize("pack", ["0", "1"], ids=["numpy", "native"])
def test_bulk_stats_say_which_kernel_ran(tmp_path, monkeypatch, pack):
    """last_bulk_stats names what ran each slab — the device program
    (and on which platform) or the numpy twin below
    HM_DEVICE_MIN_CELLS — whichever pack fed it: a load that quietly
    ran on the host must be visible in the stats."""
    urls, _want = _make_corpus(tmp_path, n_docs=10)
    ids = [validate_doc_url(u) for u in urls]
    monkeypatch.setenv("HM_NATIVE_PACK", pack)

    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")  # every slab: device
    repo = Repo(path=str(tmp_path))
    repo.back.load_documents_bulk(ids, slab=4)
    repo.back.fetch_bulk_summaries()
    st = dict(repo.back.last_bulk_stats)
    repo.close()
    assert (st["device_slabs"], st["host_slabs"]) == (3, 0)
    assert st["platform"] == "cpu"

    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", str(2**30))  # none
    repo = Repo(path=str(tmp_path))
    repo.back.load_documents_bulk(ids, slab=4)
    repo.back.fetch_bulk_summaries()
    st = dict(repo.back.last_bulk_stats)
    repo.close()
    assert (st["device_slabs"], st["host_slabs"]) == (0, 3)
    assert st["platform"] is None  # no slab reached a device


@pytest.mark.parametrize("pack", ["0", "1"], ids=["numpy", "native"])
def test_bulk_stats_say_how_the_columns_loaded(tmp_path, monkeypatch, pack):
    """last_bulk_stats counts, under either pack, the column sidecars that
    loaded slab-granular (one v3 image a feed, colcache.load_slab_images)
    and those that loaded feed by feed: a checkpointed corpus is all
    bulk, a store the live path wrote (v2 records only) all single, and
    a re-open of docs whose caches are loaded counts neither."""
    from hypermerge_tpu.ops.corpus import make_corpus

    monkeypatch.setenv("HM_NATIVE_PACK", pack)

    def cols(st):
        return (
            st["cols_bulk_feeds"], st["cols_single_feeds"],
            st["cols_bulk_pct"],
        )

    urls = make_corpus(str(tmp_path / "ckpt"), 10, 64)
    ids = [validate_doc_url(u) for u in urls]
    repo = Repo(path=str(tmp_path / "ckpt"))
    repo.back.load_documents_bulk(ids, slab=4)
    repo.back.fetch_bulk_summaries()
    st = dict(repo.back.last_bulk_stats)
    assert cols(st) == (10, 0, 100.0)
    for d in ids[:3]:
        repo.back.docs.pop(d)  # forget three docs; their actors stay
    repo.back.load_documents_bulk(ids, slab=4)
    assert cols(repo.back.last_bulk_stats) == (0, 0, 0.0)
    repo.close()

    urls, _want = _make_corpus(tmp_path / "live", n_docs=6)
    ids = [validate_doc_url(u) for u in urls]
    repo = Repo(path=str(tmp_path / "live"))
    repo.back.load_documents_bulk(ids, slab=4)
    repo.back.fetch_bulk_summaries()
    assert cols(repo.back.last_bulk_stats) == (0, 6, 0.0)
    repo.close()


# -- slab formation by length (pipeline.SlabFormer) ------------------------


def _form(rows, slab, cells, min_cells=0):
    """(slabs emitted as the stream went, slabs of the flush): each a
    list of (store index, rows)."""
    from hypermerge_tpu.backend.pipeline import SlabFormer

    former = SlabFormer(slab, cells, min_cells)
    full = []
    for i, n in enumerate(rows):
        got = former.add(n, (i, n))
        if got is not None:
            full.append(got)
    return full, former.flush()


def _padded(slab):
    from hypermerge_tpu.ops.columnar import round_up_pow2 as pow2

    return pow2(len(slab)) * pow2(max(n for _i, n in slab))


@pytest.mark.parametrize("n_ops,n_docs,slab", [
    (1024, 10240, 4096),  # flagship-1w: [4096] x 2 + [2048]
    (1024, 5120, 4096),   # collab-10k: [4096] + [1024]
    (128, 72, 32),        # their rehearsal blocks: [32] x 2 + [8]
    (5, 7, 3),
])
def test_a_store_of_one_length_forms_chunks_in_store_order(
    n_ops, n_docs, slab
):
    """Docs of one length stream through the former as chunks of `slab`
    docs in store order, the tail at the flush: the slabs a loader
    that knew nothing of length would cut."""
    full, rest = _form([n_ops] * n_docs, slab, 4096 * 1024)
    ids = [[i for i, _n in s] for s in full + rest]
    assert ids == [
        list(range(b, min(b + slab, n_docs)))
        for b in range(0, n_docs, slab)
    ]
    assert len(rest) == (1 if n_docs % slab else 0)


def _longtail_rows(seed, counts=(512, 256, 128, 64, 32, 16, 8, 4, 3, 2)):
    """Octave k: counts[k] docs of [16 * 2^k, 32 * 2^k) rows, shuffled."""
    rng = random.Random(seed)
    rows = [
        rng.randrange(16 << k, 32 << k)
        for k, c in enumerate(counts) for _ in range(c)
    ]
    rng.shuffle(rows)
    return rows


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("min_cells", [0, 4096, 60000])
def test_a_mixed_store_forms_slabs_by_length(seed, min_cells):
    """Every doc lands in exactly one slab, in store order inside it,
    with docs of its own rung (or, merged upward, of rungs below); no
    slab counts more cells than the budget; what forms over the gate
    costs under 4 cells a real row."""
    from hypermerge_tpu.backend.pipeline import SlabFormer

    rows = _longtail_rows(seed)
    cells = 256 * 256  # the budget: 256 docs of the lowest rung
    full, rest = _form(rows, 256, cells, min_cells)
    slabs = full + rest
    seen = sorted(i for s in slabs for i, _n in s)
    assert seen == list(range(len(rows)))
    for s in slabs:
        assert [i for i, _n in s] == sorted(i for i, _n in s)
        assert _padded(s) <= cells
        top = SlabFormer.rung(max(n for _i, n in s))
        assert all(SlabFormer.rung(n) <= top for _i, n in s)
        if s in full or min_cells == 0:  # never merged: one rung
            assert {SlabFormer.rung(n) for _i, n in s} == {top}
    assert sum(map(_padded, slabs)) < 4 * sum(rows)
    # the stream, not a sort, decides the order: a rung's slab is
    # emitted as its last doc arrives, before the docs after it
    for s in full:
        later = [r for r in full if r is not s and r[0][0] > s[-1][0]]
        assert all(full.index(r) > full.index(s) for r in later)


def test_a_thin_remainder_joins_the_rung_above_where_it_fits():
    """Under the gate, a rung's remainder joins the next occupied
    rung's if the two fit one slab of that rung (store order kept);
    where they do not it is dispatched thin, and so is the top rung's,
    which has nowhere to go."""
    cells, gate = 1 << 16, 1 << 12
    # 3 docs of 10 rows (rung 256: [4, 16] = 64 cells, thin), 2 of
    # 3,000 rows (rung 4,096: capacity 16)
    full, rest = _form([10, 3000, 10, 10, 3000], 64, cells, gate)
    assert full == []
    assert rest == [[(0, 10), (1, 3000), (2, 10), (3, 10), (4, 3000)]]
    # the rung above is full with its own: the thin one goes as it is
    rows = [10] * 3 + [3000] * 15
    full, rest = _form(rows, 64, cells, gate)
    assert [len(s) for s in full + rest] == [15, 3]
    # and a thin top rung is dispatched thin: a store of one length
    # keeps the tail it always had
    full, rest = _form([10] * 67, 64, cells, gate)
    assert [len(s) for s in full + rest] == [64, 3]
    # a remainder over the gate stays where it is
    full, rest = _form([200] * 40 + [3000], 64, cells, gate)
    assert [len(s) for s in rest] == [1, 40]  # highest rung first


def test_former_capacity_follows_the_cell_budget():
    from hypermerge_tpu.backend.bulk_loader import SLAB_CELLS
    from hypermerge_tpu.backend.pipeline import SlabFormer

    former = SlabFormer(4096, SLAB_CELLS)
    assert SLAB_CELLS == 4096 * 1024
    assert [former.capacity(t) for t in (
        256, 1024, 4096, 131072, 262144)] == [4096, 4096, 1024, 32, 16]
    assert former.capacity(SLAB_CELLS * 4) == 1  # one doc over it all
    # a step of 4 up to 65,536 rows, every power of two from there
    assert [SlabFormer.rung(n) for n in (
        0, 1, 256, 257, 1024, 1025, 65536, 65537, 131072, 131073,
        262143, 262145)] == [
        256, 256, 256, 1024, 1024, 4096, 65536, 131072, 131072, 262144,
        262144, 524288]
    assert SlabFormer(32, SLAB_CELLS).capacity(1024) == 32
