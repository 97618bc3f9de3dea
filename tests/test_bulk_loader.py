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
    assert stats["pipeline"] == 1
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
    assert small_load_stats["pipeline"] == 1


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
