"""The long-tail deployment `longtail-10k` (ISSUE 30) at rehearsal size,
on the CPU: the corpus writer holds the law the config states, and a
cold open through `Repo.open_many`, its slabs formed by length and every
one on the device path, gives for docs of every octave (the longest doc
among them, in a slab of 65,536 rows, where the summary wire counts in
4 bytes and orders in 16 bits) the state the plain reference replays.

Counts and states only; no clock is asserted.
"""

import contextlib
import glob
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.corpora import single_writer_longtail as swl  # noqa: E402
from benchmark.reference.plainify import plain  # noqa: E402

SEED = 2147483693  # over 2**31: the driver's seeds are large
ENV = {"HM_DEVICE_MIN_CELLS": "0", "HM_BULK_SLAB": "32",
       "HM_LIVE_INC_BUDGET": "0"}
# the rehearsal's law (octave0 16) carried five octaves further, to a
# longest doc of 32,768-65,535 ops: rows >= 2**15 are where the summary
# wire changes its layout
OCTAVES = (24, 12, 6, 4, 3, 2, 2, 1, 1, 1, 1, 1)


def _config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "longtail-10k.json")) as fh:
        return json.load(fh)


def _rehearsal_corpus(octaves=None) -> dict:
    cfg = _config()
    corpus = dict(cfg["corpus"], **cfg["rehearsal"]["corpus"])
    if octaves is not None:
        corpus["octaves"] = list(octaves)
    return corpus


@contextlib.contextmanager
def _env():
    with pytest.MonkeyPatch.context() as mp:
        for k, v in ENV.items():
            mp.setenv(k, v)
        yield


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """The twelve-octave corpus on disk, opened once with every slab on
    the device path: (job, urls, {doc: (summary, value)} of the docs
    compared, stats, span tree of the open)."""
    import jax

    from benchmark.readers import span_tree
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    tmp = tmp_path_factory.mktemp("longtail")
    job = swl.CorpusJob(str(tmp / "repo"), _rehearsal_corpus(OCTAVES),
                        SEED, 2)
    try:
        urls = job.start().finish()
    except BaseException:
        job.abort()
        raise
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with _env():
        jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
        try:
            repo = Repo(path=str(tmp / "repo"))
            handles = repo.open_many(urls)
            summ = repo.back.fetch_bulk_summaries()
            stats = dict(repo.back.last_bulk_stats)
        finally:
            jax.profiler.stop_trace()
        try:
            # two docs an octave, and the longest
            by_octave = {}
            for i, d in enumerate(job.plan):
                by_octave.setdefault(
                    swl.octave_of(job.corpus, d["n_ops"]), []).append(i)
            states = {
                i: (summ.doc(validate_doc_url(urls[i])),
                    plain(handles[i].value(timeout=300)))
                for docs in by_octave.values() for i in docs[:2]
            }
        finally:
            repo.close()
    (path,) = glob.glob(str(tmp / "trace" / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    tree = span_tree.Tree(span_tree.load(path)[0])
    tree.path = path
    return job, urls, states, stats, tree


@pytest.mark.parametrize("octave", range(len(OCTAVES)))
def test_the_open_equals_the_plain_replay(corpus, octave):
    """Docs of the octave: the cold open's summary and value equal the
    plain reference's replay of the changes the writer put on disk."""
    job, _urls, states, _stats, _tree = corpus
    docs = [i for i in states
            if swl.octave_of(job.corpus, job.plan[i]["n_ops"]) == octave]
    assert docs
    cache = {}
    for i in docs:
        ref = job.doc_reference(i, cache)
        summary, value = states[i]
        assert summary == {
            k: ref[k] for k in ("elems", "map_entries", "clock")}, i
        assert value == ref["value"], i
        assert ref["elems"] > 0.7 * job.plan[i]["n_ops"]  # no DELs


def test_the_longest_doc_meets_the_wide_summary_wire(corpus):
    from hypermerge_tpu.ops.crdt_kernels import summary_wire_spec

    job, _urls, states, stats, _tree = corpus
    longest = max(range(len(job.plan)), key=lambda i: job.plan[i]["n_ops"])
    assert longest in states and job.plan[longest]["n_ops"] >= 2 ** 15
    assert (2, 65536) in stats["slab_shapes"]  # octaves 10 and 11
    spec = summary_wire_spec(65536, 4, True)
    assert (spec["count_bytes"], spec["order_bits"]) == (4, 16)


def test_stats_of_a_long_tail_open(corpus):
    """Slabs by rung, every one on the device, no doc off the fast
    path; the stats hold the slabs that were dispatched."""
    job, _urls, _states, stats, _tree = corpus
    assert stats["host_slabs"] == 0 and stats["fallback"] == 0
    assert stats["fast"] == len(job.plan) == sum(OCTAVES)
    assert stats["pack_general_docs"] == 0  # single-writer: prefix pack
    shapes = stats["slab_shapes"]
    assert stats["slabs"] == len(shapes) == stats["device_slabs"]
    # octaves 0-3 fill the lowest rung (46 docs: a full slab of 32 and
    # the rest), every pair of octaves above it a rung of its own
    assert sorted(n for _d, n in shapes) == [
        256, 256, 1024, 4096, 16384, 65536]
    assert stats["rows_real"] == sum(d["n_ops"] for d in job.plan)
    assert stats["cells_padded"] == sum(d * n for d, n in shapes)
    assert 1.0 < stats["slab_waste_x"] < 4.0
    assert stats["slab_programs"] == len(set(shapes))
    # ops/warmup.py reckons the same ladder from the op counts alone
    from hypermerge_tpu.ops.warmup import bulk_shapes

    with _env():
        assert bulk_shapes([d["n_ops"] for d in job.plan]) == list(
            dict.fromkeys(shapes))


def test_spans_of_a_long_tail_open(corpus):
    """`pipeline.form` around each chunk's binning and the flush; `D`,
    `N` on every slab's pack and enqueue."""
    job, _urls, _states, stats, tree = corpus
    forms = tree.named(("pipeline.form",))
    assert sum(f.args["docs"] for f in forms) == len(job.plan)
    assert sum(f.args["slabs"] for f in forms) == stats["slabs"]
    assert [f.args["flush"] for f in forms][-1] == 1
    for name in ("pipeline.pack", "pipeline.enqueue"):
        got = [(s.args["D"], s.args["N"]) for s in sorted(
            tree.named((name,)), key=lambda s: s.args.get("slab", s.t0))]
        assert sorted(got) == sorted(stats["slab_shapes"]), name
    assert stats["t_form"] > 0.0


def test_profile_trace_prints_each_slabs_shape(corpus):
    """scripts/profile_trace.py --by slab: `[D x N]`, real rows and
    cells a row of every slab, from the pack span's tags."""
    import importlib.util
    import io

    from benchmark.readers import span_tree

    _job, _urls, _states, stats, tree = corpus
    spec = importlib.util.spec_from_file_location(
        "profile_trace", os.path.join(ROOT, "scripts", "profile_trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = io.StringIO()
    mod._open_view(tree, [], span_tree, out)
    text = out.getvalue()
    for k, (d, n) in enumerate(stats["slab_shapes"]):
        assert f"slab {k}: waited " in text
        assert f" between stages; [{d} x {n}], " in text, text
    assert text.count(" real rows, ") == stats["slabs"]
    # the io thread's chunks are followed apart from the slabs they feed
    forms = tree.named(("pipeline.form",))
    assert sum(s.args["slabs"] for s in forms) == stats["slabs"]
    for s in forms:
        assert "slab" not in s.args
        assert f"chunk {s.args['chunk']}: " in text
    slab_lines = text[text.index("slab 0: waited"):]
    assert "pipeline.io" not in slab_lines
    assert text.count("pipeline.form") == len(forms)
    assert text.count(" cells a row\n") == stats["slabs"]


def test_law_and_store_order_come_from_the_seed():
    cfg = _config()
    corpus = cfg["corpus"]
    groups = swl.groups_of(corpus, SEED)
    assert groups == swl.groups_of(corpus, SEED)
    assert groups != swl.groups_of(corpus, SEED + 1)
    assert sum(g["count"] for g in groups) == cfg["docs"] == 10240
    assert len(groups) == sum(min(16, c) for c in corpus["octaves"])
    for k, count in enumerate(corpus["octaves"]):
        mine = [g for g in groups if g["octave"] == k]
        assert sum(g["count"] for g in mine) == count
        assert all(64 << k <= g["ops"] < 128 << k for g in mine)
        assert swl.octave_of(corpus, mine[0]["ops"]) == k
    ops = sum(g["count"] * g["ops"] for g in groups)
    assert 5.5e6 < ops < 6.5e6
    # store order: a permutation the seed decides, lengths at random
    small = _rehearsal_corpus()
    a = swl.CorpusJob("unused", small, SEED, 1).plan
    b = swl.CorpusJob("unused", small, SEED, 1).plan
    c = swl.CorpusJob("unused", small, SEED + 1, 1).plan
    assert a == b and a != c
    assert sorted(d["key_seed"] for d in a) == sorted(
        d["key_seed"] for d in swl.swt.doc_plan(
            dict(small, groups=swl.groups_of(small, SEED)), SEED))
    rows = [d["n_ops"] for d in a]
    assert rows != sorted(rows) and rows != sorted(rows, reverse=True)
    assert len(a) == sum(small["octaves"])


def test_histories_are_typing_runs_with_jumps():
    changes = swl.longtail_changes(4000, 5, run_frac=0.9)
    assert changes == swl.longtail_changes(4000, 5, run_frac=0.9)
    ops = [op for ch in changes for op in ch["ops"]]
    assert len(ops) == 4000 and all(len(ch["ops"]) <= 16 for ch in changes)
    inserts = [(i + 1, op) for i, op in enumerate(ops) if op.get("i")]
    after_previous = sum(
        op["r"] == f"{prev}@actor00"
        for (prev, _p), (_c, op) in zip(inserts, inserts[1:]))
    assert 0.80 < len(inserts) / len(ops) < 0.90
    assert 0.87 < after_previous / (len(inserts) - 1) < 0.95
    sets = [op for op in ops if "k" in op and op["a"] == 4]
    assert all(op["a"] != 5 for op in ops)  # no DELs
    assert sum("p" in op for op in sets) >= len(sets) - 10


@pytest.mark.parametrize("control", (False, True))
def test_rehearsal_of_the_cell(control):
    """`benchmark/run.py --workload coldopen.longtail --rehearse`: exit
    0 and `correct`, docs of every octave and the longest compared;
    with `--control` exit 1 through `host_slabs`."""
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", "coldopen.longtail", "--seed", str(SEED),
           "--seconds", "2", "--trace", "0", "--rehearse"] + (
               ["--control"] if control else [])
    env = {k: v for k, v in os.environ.items() if not k.startswith("HM_")}
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=180,
                         env=env, cwd=ROOT)
    line = json.loads(out.stdout.strip().splitlines()[-1])
    bad = [c["name"] for c in line["checks"] if c["value"] > c["limit"]]
    assert line["workload"] == "coldopen.longtail" and line["rehearsal"]
    assert line["metrics"] == {}
    assert {c["name"] for c in line["checks"]} >= {
        "octaves_unsampled", "longest_doc_uncompared", "host_slabs",
        "docs_without_a_slab", "traced_slabs_unlike_stats"}
    if control:
        assert out.returncode == 1 and line["correct"] is False
        assert bad == ["host_slabs"]
    else:
        assert out.returncode == 0 and line["correct"] is True, out.stderr
        assert bad == []


@pytest.mark.parametrize("docs, slabs, unplaced", [
    ([100, 100, 900, 5000], [(2, 128), (1, 1024), (1, 8192)], 0),
    ([100, 100, 900, 5000], [(4, 8192)], 0),  # one slab holds them all
    ([100, 100, 900, 5000], [(2, 128), (2, 1024)], 1),  # no rows for 5,000
    ([100, 100, 900, 5000], [(1, 128), (1, 1024), (1, 8192)], 1),
    ([100, 100, 900, 5000], [(8, 64), (1, 8192)], 3),
    ([], [], 0),
])
def test_every_doc_has_a_place_in_the_slabs(docs, slabs, unplaced):
    """The driver's check of `slab_shapes` against the plan: each doc
    in one slab of at least its rows, the doc axis its room."""
    from benchmark.drivers import cold_open_loop_strata as drv

    assert drv.unplaced_docs(docs, slabs) == unplaced


def test_traced_slabs_come_from_the_enqueue_tags(corpus):
    """`bulk_slabs` of a traced run: D and N of the traced open's
    `pipeline.enqueue` spans in dispatch order, which is what the
    program's `slab_shapes` says; every doc of the plan has a place in
    them; no trace, no slabs."""
    from benchmark.drivers import cold_open_loop_strata as drv

    job, _urls, _states, stats, tree = corpus
    assert drv.traced_slabs(None) == []
    got = drv.traced_slabs(tree.path)
    assert got == [[d, n, 0] for d, n in stats["slab_shapes"]]
    slabs = [s[:2] for s in got]
    rows = [d["n_ops"] for d in job.plan]
    assert drv.unplaced_docs(rows, slabs) == 0
    assert drv.unplaced_docs(rows, slabs[1:]) > 0


def test_a_program_without_a_cell_budget_is_not_run(tmp_path):
    """The driver stops before any corpus is written or opened, exit
    code 5 and one line, where the program has no slab cell budget."""
    script = (
        "import sys; sys.path.insert(0, %r)\n"
        "from hypermerge_tpu.backend import bulk_loader\n"
        "del bulk_loader.SLAB_CELLS\n"
        "from benchmark import run\n"
        "sys.exit(run.main(['--workload', 'coldopen.longtail', '--seed',"
        " '7', '--seconds', '1', '--trace', '0', '--rehearse']))\n" % ROOT
    )
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=120,
                         cwd=str(tmp_path))
    assert out.returncode == 5, out.stderr
    assert out.stdout == ""
    assert "no slab cell budget" in out.stderr


def test_the_cell_and_its_metrics_are_listed():
    """BENCHMARK.json: the configuration, the cell, its four metrics as
    data files over readers the benchmark has, and no list it should
    not be on."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = "coldopen.longtail"
    (entry,) = [w for w in bench["workloads"] if w["name"] == cell]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "longtail-10k", "coldopen-loop-strata", 1)
    (cfg,) = [c for c in bench["configs"] if c["name"] == "longtail-10k"]
    assert cfg["reduced"] == [] == _config()["reduced"]
    assert cfg["source"] == _config()["source"]
    listed = {m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", ())}
    assert "kernel.bulk_roofline" not in listed
    # its reader pairs a slab's io spans with its pack by one tag,
    # which names a chunk of docs on the one and a formed slab on the
    # other: the same thing only in a store of one length
    assert "loader.queue_wait_s" not in listed
    assert not {n for n in listed if n.startswith("pack.general_")} - {
        "pack.general_docs_pct"}
    new = {
        "loader.slab_waste_x": ("bulk_stats", {"key": "slab_waste_x"}),
        "loader.slab_programs": ("bulk_stats", {"key": "slab_programs"}),
        "loader.form_s": ("span_tree", {"measure": "total",
                                        "names": ["pipeline.form"]}),
        "kernel.rows_roofline": ("roofline", None),
    }
    assert set(new) <= listed
    for name, (reader, params) in new.items():
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as fh:
            spec = json.load(fh)
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert spec["reader"] == reader
        assert params is None or spec["params"] == params
        # (the cells a later PR joined to the metric follow its own)
        assert m["workloads"][:len(spec["cells"])] == spec["cells"]
        assert cell in spec["cells"]
        assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
            spec["unit"], spec["better"], spec["layer"], spec["moves"])


def test_bulk_rows_counts_real_rows_only():
    """counts/bulk_rows.py against a hand count, and through the
    roofline reader: padding is no part of the least bytes."""
    from benchmark.counts import bulk_rows, bulk_slab
    from benchmark.readers import roofline

    # 100 rows: 100 * 44 in, 100 * (2 + 7) / 8 out; 1,025 rows: 11 bits
    assert bulk_rows.bytes_moved([100]) == 4400 + 112.5
    assert bulk_rows.bytes_moved([100, 1025]) == (
        4512.5 + 1025 * 44 + 1025 * 13 / 8)
    rows = [70, 300, 5000]
    padded = [[4, 8192]]  # the slab a store-order loader would form
    assert bulk_rows.bytes_moved(rows) < bulk_slab.bytes_moved(padded) / 5
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "kernel.rows_roofline.json")) as fh:
        params = json.load(fh)["params"]
    obs = {
        "trace": {"programs": {"jit_materialize_full_lean_device": 2e-3,
                               "jit_other": 1.0}},
        "doc_rows": rows, "traced_opens": 1, "device_kind": "TPU v5 lite",
        "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}},
    }
    want = 100.0 * (bulk_rows.bytes_moved(rows) / 819e9) / 2e-3
    assert roofline.read(params, obs) == pytest.approx(want)
    assert roofline.read(params, dict(obs, doc_rows=None)) is None
