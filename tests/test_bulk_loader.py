"""The bulk loader as a module (backend/bulk_loader.py): one schedule
whatever pack the host has, the stats the benchmark reads, and the
direction of its imports."""

import ast
import json
import pathlib

import pytest

from helpers import opset_replay_state, plainify
from hypermerge_tpu.models import Counter, Text
from hypermerge_tpu.repo import Repo
from hypermerge_tpu.utils.ids import to_doc_url, validate_doc_url

ROOT = pathlib.Path(__file__).resolve().parent.parent
LAYER_METRICS = ROOT / "benchmark" / "layer_metrics"
# what benchmark/drivers/cold_open_loop.py's checks read of each open
DRIVER_KEYS = {
    "host_slabs": int, "device_slabs": int, "fast": int, "fallback": int,
    "platform": str,
}


def _bulk_stats_keys():
    """The `key` of every per-layer metric whose reader is
    benchmark/readers/bulk_stats.py, by metric name."""
    out = {}
    for path in sorted(LAYER_METRICS.glob("*.json")):
        spec = json.loads(path.read_text())
        if spec.get("reader") == "bulk_stats":
            out[spec["name"]] = spec["params"]["key"]
    return out


def _corpus(path, n_docs):
    repo = Repo(path=str(path))
    urls = []
    for i in range(n_docs):
        u = repo.create({"i": i, "t": Text(f"doc{i}:"), "hits": Counter(0)})
        repo.change(u, lambda d, i=i: d.__setitem__("k", i * 3))
        repo.change(u, lambda d: d["t"].insert(0, "<>"))
        urls.append(u)
    repo.close()
    return [validate_doc_url(u) for u in urls]


def _load(path, ids, slab):
    """(stats, {doc id: (summary, value)}, {doc id: OpSet replay's})."""
    repo = Repo(path=str(path))
    back = repo.back
    back.load_documents_bulk(ids, slab=slab)
    summ = back.fetch_bulk_summaries()
    stats = dict(back.last_bulk_stats)
    got = {
        d: (summ.doc(d), plainify(repo.doc(to_doc_url(d)))) for d in ids
    }
    want = {
        d: opset_replay_state(back._bulk_history_loader(d)()) for d in ids
    }
    repo.close()
    return stats, got, want


def test_host_without_native_pack_runs_the_pipeline(tmp_path, monkeypatch):
    """A host whose native pack does not load runs the same pipeline
    with the numpy pack on one pack worker, and its docs equal the host
    OpSet replay of their feeds."""
    from hypermerge_tpu import native

    ids = _corpus(tmp_path, 7)
    monkeypatch.setattr(native, "pack_lib", lambda: None)
    assert not native.pack_drops_gil() and not native.pack_parallel_ok()
    monkeypatch.delenv("HM_PACK_WORKERS", raising=False)
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")
    stats, got, want = _load(tmp_path, ids, slab=3)
    assert stats["pack_workers"] == 1
    assert len(stats["t_pack_busy_per_worker"]) == 1
    assert (stats["device_slabs"], stats["fast"]) == (3, 7)
    assert got == want


@pytest.fixture(scope="module")
def small_load_stats(tmp_path_factory):
    path = tmp_path_factory.mktemp("stats")
    ids = _corpus(path, 5)
    mp = pytest.MonkeyPatch()
    mp.setenv("HM_DEVICE_MIN_CELLS", "1")
    try:
        stats, got, want = _load(path, ids, slab=2)
    finally:
        mp.undo()
    assert got == want
    return stats


@pytest.mark.parametrize("metric", sorted(_bulk_stats_keys()))
def test_bulk_stats_hold_what_the_benchmark_reads(small_load_stats, metric):
    """Every per-layer metric the benchmark reads through the
    `bulk_stats` reader finds its key in last_bulk_stats after a load,
    holding a number (the reader takes a median and a float of it)."""
    key = _bulk_stats_keys()[metric]
    assert key in small_load_stats, (metric, key)
    value = small_load_stats[key]
    assert isinstance(value, (int, float)) and not isinstance(value, bool)


def test_bulk_stats_hold_what_the_driver_checks(small_load_stats):
    """The keys the benchmark's driver sums and compares for `correct`
    (host_slabs, fast, fallback, platform) and device_slabs."""
    for key, kind in DRIVER_KEYS.items():
        assert isinstance(small_load_stats[key], kind), key
    assert small_load_stats["platform"] == "cpu"


def test_gate_native_pct_is_data_only_and_lists_the_single_writer_cells():
    """ISSUE 35's per-layer metric is a data file over a reader the
    benchmark has, listed for the three cells whose every slab runs the
    whole gate (the stores of single-writer docs) and no other."""
    spec = json.loads((LAYER_METRICS / "pack.gate_native_pct.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # (the last entry of PR 35's benchmark; later PRs append after it)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "pack.gate_native_pct")
    assert entry["name"] == spec["name"] == "pack.gate_native_pct"
    assert spec["reader"] == "bulk_stats"
    assert spec["params"] == {"key": "pack_gate_native_pct"}
    assert entry["workloads"] == spec["cells"] == [
        "coldopen.flagship", "coldopen.flagship-x4", "coldopen.longtail"]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == (
        "%", "higher", "program_counter", "pack", "ops_per_s")
    single_writer = {c["name"] for c in bench["configs"]
                     if "single-writer" in c["why"]}
    assert {w["name"] for w in bench["workloads"]
            if w["config"] in single_writer
            and w["traffic"].startswith("coldopen")} == set(spec["cells"])


def test_loader_imports_point_one_way():
    """repo_backend -> bulk_loader, never back: the loader's module
    names repo_backend in no import, and RepoBackend holds no load path
    of its own."""
    from hypermerge_tpu.backend import bulk_loader
    from hypermerge_tpu.backend.repo_backend import RepoBackend

    tree = ast.parse(pathlib.Path(bulk_loader.__file__).read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            names = [node.module or ""] + [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        else:
            continue
        assert not any("repo_backend" in n for n in names), names
    assert not [
        a for a in dir(RepoBackend)
        if a.startswith(("_load_slabs", "_dispatch_slab"))
    ]
    for name in ("load_documents_bulk", "fetch_bulk_summaries",
                 "last_bulk_stats", "summary_memo_row"):
        assert hasattr(RepoBackend, name)


# -- slabs formed by length ------------------------------------------------


def _ragged_corpus(path, n_docs, seed=11):
    """Docs whose op rows differ by two orders of magnitude."""
    import random

    r = random.Random(seed)
    repo = Repo(path=str(path))
    urls = []
    for i in range(n_docs):
        u = repo.create({"i": i, "t": Text(""), "hits": Counter(0)})
        size = r.choice((1, 1, 1, 6, 6, 30, 120))
        repo.change(u, lambda d, i=i, size=size: d["t"].insert(
            0, "".join(chr(97 + (i + k) % 26) for k in range(size))))
        repo.change(u, lambda d, i=i: d.__setitem__("k", i))
        if i % 3 == 0:
            repo.change(u, lambda d: d.increment("hits", 2))
        urls.append(u)
    repo.close()
    return [validate_doc_url(u) for u in urls]


def _record_dispatches(monkeypatch):
    """[(doc ids, (D, N), real rows, program)] of every slab a load
    dispatches, in order."""
    from hypermerge_tpu.backend.bulk_loader import BulkLoader
    from hypermerge_tpu.ops.crdt_kernels import bucket_doc_actors

    seen = []
    inner = BulkLoader._dispatch

    def dispatch(self, seq, chunk, batch, *rest):
        entry = inner(self, seq, chunk, batch, *rest)
        _da, a, k = bucket_doc_actors(batch)
        seen.append((
            [e[0].id for e in chunk], batch.shape, int(batch.n_ops.sum()),
            batch.shape + (a, k, batch.psrc.shape[1], entry[4]),
        ))
        return entry

    monkeypatch.setattr(BulkLoader, "_dispatch", dispatch)
    return seen


def test_a_store_of_one_length_loads_in_store_order_chunks(
    tmp_path, monkeypatch
):
    """Docs of one length: the slabs are chunks of `slab` docs in store
    order, each packed with the doc axis at its pow2 and the rows left
    to the pack, as before slabs were formed by length: the same calls
    of the same pack, so the same bytes."""
    from hypermerge_tpu.ops import columnar

    ids = _corpus(tmp_path, 7)
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")
    seen = _record_dispatches(monkeypatch)
    packs = []
    inner = columnar.pack_docs_columns

    def pack(specs, **kw):
        packs.append(kw)
        return inner(specs, **kw)

    monkeypatch.setattr(columnar, "pack_docs_columns", pack)
    stats, got, want = _load(tmp_path, ids, slab=3)
    assert got == want
    assert [s[0] for s in seen] == [ids[0:3], ids[3:6], ids[6:7]]
    assert packs == [{"n_docs": d, "n_rows": None} for d in (4, 4, 1)]
    assert len({s[1][1] for s in seen}) == 1  # one row bucket
    assert stats["slabs"] == 3 and stats["slab_programs"] == 2
    assert stats["slab_shapes"] == tuple(s[1] for s in seen)


@pytest.mark.parametrize("pack", ["1", "0"])
def test_a_ragged_store_loads_in_slabs_formed_by_length(
    tmp_path, monkeypatch, pack
):
    """Docs of 10-300 rows under a ladder and a budget cut to their
    size: every doc in exactly one slab, store order inside it, no slab
    over the budget or on the host twin, the stats the slabs that were
    dispatched; and every summary and value what a load in store-order
    chunks gives, and what the host OpSet replays."""
    from hypermerge_tpu.backend import bulk_loader, pipeline

    ids = _ragged_corpus(tmp_path, 40)
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")
    monkeypatch.setenv("HM_NATIVE_PACK", pack)
    seen = _record_dispatches(monkeypatch)
    cells = 16 * 32
    monkeypatch.setattr(pipeline, "ROW_RUNGS", (16, 64, 256))
    monkeypatch.setattr(bulk_loader, "SLAB_CELLS", cells)
    stats, got, want = _load(tmp_path, ids, slab=16)
    assert got == want
    order = {d: i for i, d in enumerate(ids)}
    assert sorted(d for s in seen for d in s[0]) == sorted(ids)
    shapes = [s[1] for s in seen]
    assert len({n for _d, n in shapes}) >= 3  # several rungs met
    for docs, (d, n), _real, _prog in seen:
        assert [order[x] for x in docs] == sorted(order[x] for x in docs)
        assert d * n <= cells
    assert stats["host_slabs"] == 0 and stats["device_slabs"] == len(seen)
    assert stats["fast"] == len(ids) and stats["fallback"] == 0
    assert stats["slabs"] == len(seen)
    assert stats["slab_shapes"] == tuple(shapes)
    assert stats["cells_padded"] == sum(d * n for d, n in shapes)
    assert stats["rows_real"] == sum(s[2] for s in seen)
    assert stats["slab_waste_x"] == round(
        stats["cells_padded"] / stats["rows_real"], 3)
    assert stats["slab_programs"] == len({s[3] for s in seen})
    assert stats["t_form"] >= 0.0

    # the same docs in store-order chunks (one rung holds them all)
    seen.clear()
    monkeypatch.setattr(pipeline, "ROW_RUNGS", (1 << 20,))
    monkeypatch.setattr(bulk_loader, "SLAB_CELLS", 1 << 40)
    stats2, got2, _want = _load(tmp_path, ids, slab=16)
    assert [s[0] for s in seen] == [ids[0:16], ids[16:32], ids[32:40]]
    assert got2 == got
    assert stats2["cells_padded"] > stats["cells_padded"]


@pytest.mark.parametrize("vmem_from", [None, 128])
def test_a_load_counts_its_cells_by_rga_arm(tmp_path, monkeypatch, vmem_from):
    """`rga_vmem_cells` / `rga_xla_cells` split a load's dispatched
    padded cells by where each slab's program runs rga_order's rounds:
    all of them through XLA's gather off a TPU, and those of the slabs
    the selector takes (here: rows from 128, the Pallas interpreter)
    out of VMEM, with every summary and value what the OpSet replays."""
    from hypermerge_tpu.backend import bulk_loader, pipeline
    from hypermerge_tpu.ops import compile_cache, crdt_kernels as ck

    ids = _ragged_corpus(tmp_path, 40)
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")
    monkeypatch.setattr(pipeline, "ROW_RUNGS", (16, 64, 256))
    monkeypatch.setattr(bulk_loader, "SLAB_CELLS", 16 * 32)
    if vmem_from:
        monkeypatch.setattr(
            ck, "rga_rounds_in_vmem", lambda n: n >= vmem_from
        )
        # a jit of its own: the module's may hold these shapes' programs
        # traced with the other arm
        for name in ("materialize_full_device",
                     "materialize_full_lean_device"):
            monkeypatch.setattr(ck, name, compile_cache.jit(
                getattr(ck, name).__wrapped__, static_argnames=("A", "K")
            ))
    stats, got, want = _load(tmp_path, ids, slab=16)
    assert got == want
    in_vmem = sum(
        d * n for d, n in stats["slab_shapes"] if n >= (vmem_from or 1e9)
    )
    assert bool(in_vmem) == bool(vmem_from)
    assert stats["rga_vmem_cells"] == in_vmem
    assert stats["rga_xla_cells"] == stats["cells_padded"] - in_vmem
    assert stats["rga_vmem_cells_pct"] == round(
        100.0 * in_vmem / stats["cells_padded"], 3)


def _pred_specs(set_shares, n_ops=96):
    """One single-writer feed spec a share: a text and `share` of the
    later ops SETs of ten root keys, each superseding the key's last."""
    from benchmark.corpora.single_writer_templates import (
        _TEMPLATE_ACTOR, template_changes,
    )
    from hypermerge_tpu.crdt.change import Change
    from hypermerge_tpu.storage.colcache import (
        FeedColumnCache, MemoryColumnStorage,
    )

    specs = []
    for i, share in enumerate(set_shares):
        cc = FeedColumnCache(MemoryColumnStorage(), writer=_TEMPLATE_ACTOR)
        for c in template_changes(n_ops, seed=7 + i, seq_frac=1.0 - share):
            cc.append_change(Change.from_json(c))
        specs.append([(cc.columns(), 0, float("inf"))])
    return specs


@pytest.mark.parametrize("shares, n_pred", [
    ((0.0, 0.0), 32),  # no pred at all: the floor, rows / 4
    ((0.05, 0.1), 32),  # a pow2 of 16 under the floor
    ((0.15, 0.2), 32),  # the yardstick's share: the floor is its pow2
    ((0.6, 0.1), 64),  # over the floor: the pow2 over the widest doc
])
def test_slab_pred_axis_follows_the_rows(shares, n_pred):
    """A slab's pred axis is at least its rows / PRED_ROWS, so stores
    of one law ask for one program a slab shape whatever their seeds;
    the edges are the pack's, the columns added are empty, and a slab
    that wide already is the pack's own batch."""
    import numpy as np

    from hypermerge_tpu.backend import bulk_loader
    from hypermerge_tpu.ops import columnar

    specs = _pred_specs(shares)
    plain = columnar.pack_docs_columns(specs, n_docs=2)
    batch = bulk_loader.pack_slab(specs)
    assert batch.shape == plain.shape == (2, 128)
    assert batch.psrc.shape == batch.ptgt.shape == (2, n_pred)
    P = plain.psrc.shape[1]
    assert P <= n_pred
    for name in ("psrc", "ptgt"):
        got, want = getattr(batch, name), getattr(plain, name)
        assert got.dtype == want.dtype
        assert np.array_equal(got[:, :P], want)
        assert (got[:, P:] == -1).all()
    for name, col in plain.cols.items():
        assert np.array_equal(batch.cols[name], col), name
