"""The span tree of a cold open, as the benchmark's readers see it
(ISSUE 24): counts and structure only, no clock asserted.

- a rehearsal-size cold open under `jax.profiler.start_trace` puts the
  program's own spans into the `.xplane.pb`; `readers/span_tree.py`
  rebuilds one tree per open from `open` / `slab` / nesting;
- hand-made spans with known gaps pin the idle attribution, the
  self-time and the chain-wait arithmetic;
- `readers/trace_scope_time.py` on a hand-made trace and hand-made HLO
  books a fusion of two scopes to `mixed`, and the real slab program's
  instructions carry every phase of `crdt_kernels.PHASES`.
"""

import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.readers import span_tree, trace_scope_time  # noqa: E402
from benchmark.readers.span_tree import Span, Tree  # noqa: E402

from hypermerge_tpu.ops import crdt_kernels  # noqa: E402

N_DOCS, N_OPS, SLAB = 40, 64, 16  # slabs of 16, 16, 8 docs
STAGES = ("pipeline.io", "pipeline.spec", "pipeline.pack",
          "pipeline.dispatch", "pipeline.fetch")


@pytest.fixture(scope="module")
def traced_open(tmp_path_factory):
    """Two cold opens of one small corpus in one profiler session, every
    slab on the device path. Returns (spans, stats of the last open)."""
    import jax

    from hypermerge_tpu.ops.corpus import make_corpus
    from hypermerge_tpu.repo import Repo

    tmp = tmp_path_factory.mktemp("traced")
    env = {"HM_DEVICE_MIN_CELLS": "0", "HM_BULK_SLAB": str(SLAB)}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        urls = make_corpus(str(tmp / "repo"), N_DOCS, N_OPS)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
        try:
            for _ in range(2):
                repo = Repo(path=str(tmp / "repo"))
                repo.open_many(urls)
                repo.back.fetch_bulk_summaries()
                stats = dict(repo.back.last_bulk_stats)
                repo.close()
        finally:
            jax.profiler.stop_trace()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    (path,) = glob.glob(
        str(tmp / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    spans, busy = span_tree.load(path)
    assert busy == []  # no TPU plane in a CPU trace
    return spans, stats


def test_one_root_per_open(traced_open):
    spans, _ = traced_open
    roots = [s for s in spans if s.name == span_tree.ROOT]
    assert len(roots) == 2
    ids = [s.args["open"] for s in roots]
    assert len(set(ids)) == 2  # a per-process sequence: the request id
    for oid in ids:
        tree = Tree(spans, open_id=oid)
        assert tree.root.args["open"] == oid
        assert [s for s in tree.members if s.parent is None] == [tree.root]
    # with no id asked for, the tree is the latest open's
    assert Tree(spans).open == max(ids)


def test_every_span_of_the_open_carries_its_id(traced_open):
    spans, _ = traced_open
    outside = {"repo.init", "repo.close", "host.gc"}
    for s in spans:
        if s.name.split(".")[0] in ("pipeline", "frontend"):
            assert "open" in s.args, s
        elif s.name.startswith("storage.") and "feeds" in s.args:
            assert "open" in s.args and "slab" in s.args, s
        elif s.name not in outside and s.name.startswith("repo."):
            assert "open" in s.args, s
    tree = Tree(spans)
    names = {s.name for s in tree.members}
    assert names >= {
        "repo.open_many", "frontend.open_many.handles",
        "pipeline.bulk_load", "pipeline.register", "pipeline.io",
        "storage.feeds.open", "storage.columns.load", "pipeline.spec",
        "pipeline.pack", "pipeline.wait", "pipeline.dispatch",
        "pipeline.narrow", "pipeline.upload", "pipeline.enqueue",
        "pipeline.init_docs", "pipeline.fetch", "pipeline.clock_rows",
        "pipeline.barrier",
    }
    assert {"repo.init", "repo.close"} <= {s.name for s in spans}
    # stage and slab granularity: tens of spans an open, none per doc
    # (ISSUE 34: seven more a slab, the gate, the prefix pack's stages
    # and the widen)
    assert len(tree.members) < 48 * len(tree.slabs())


@pytest.mark.parametrize("slab", [0, 1, 2])
def test_slab_chain_in_causal_order(traced_open, slab):
    spans, _ = traced_open
    tree = Tree(spans)
    assert tree.slabs() == [0, 1, 2]
    chain = tree.chain(slab)
    assert tuple(s.name for s in chain) == STAGES
    for a, b in zip(chain, chain[1:]):
        assert a.t1 <= b.t0, (a, b)  # each stage ends before the next
    io, spec, pack, dispatch, fetch = chain
    # the walk crosses threads: io thread, pack pool, caller, fetch
    assert io.line == spec.line
    assert len({io.line, pack.line, dispatch.line, fetch.line}) == 4
    assert dispatch.line == tree.root.line
    # cause: nesting on the caller's thread, `parent=` across threads
    assert pack.parent is spec and fetch.parent is dispatch
    assert dispatch.parent.name == "pipeline.bulk_load"
    # (with several devices, as under this suite's 8-device CPU mesh,
    # the round-robin scheduler's `mesh.dispatch` sits between)
    under = [s.name for s in tree.members
             if s.line == dispatch.line and s.name.startswith("pipeline.")
             and dispatch.t0 <= s.t0 and s.t1 <= dispatch.t1
             and s is not dispatch]
    assert under == ["pipeline.narrow", "pipeline.upload",
                     "pipeline.enqueue", "pipeline.init_docs"]
    docs = [s for s in tree.members if s.name == "pipeline.init_docs"
            and s.slab == slab]
    assert [s.args["docs"] for s in docs] == [(16, 16, 8)[slab]]
    assert tree.chain_wait(slab) >= 0.0


def test_wait_never_overlaps_its_own_slabs_busy_span(traced_open):
    spans, _ = traced_open
    tree = Tree(spans)
    waits = [s for s in tree.members if s.name == "pipeline.wait"]
    assert {s.args["q"] for s in waits} == {
        "pack", "turn", "dispatch", "fetch"}
    assert {s.args["side"] for s in waits} == {"put", "get"}
    with_slab = [s for s in waits if s.slab is not None]
    assert with_slab and all(s.args["side"] == "put" for s in with_slab)
    for w in with_slab:
        for b in tree.chain(w.slab):
            assert w.t1 <= b.t0 or b.t1 <= w.t0, (w, b)


def test_span_metrics_read_from_the_tree(traced_open, monkeypatch):
    """Every span_tree metric file of the cold-open cell finds
    something to read in a traced open (the device ones need a TPU
    plane and read None here)."""
    import json

    spans, _ = traced_open
    monkeypatch.setattr(span_tree, "tree_of", lambda obs: (Tree(spans), []))
    read = {}
    for f in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics",
                                    "*.json")):
        spec = json.load(open(f))
        if spec["reader"] == "span_tree":
            read[spec["name"]] = span_tree.read(spec["params"], {})
    device = {"device.head_idle_s.open", "device.idle_attributed_pct.open"}
    # the general pack's spans: none in a single-writer open (a
    # multi-writer one reads them, tests/test_collab_corpus.py)
    general = {n for n in read if n.startswith("pack.general_")}
    assert "pack.general_s" in general
    nothing = device | general
    assert set(read) == nothing | {
        "facade.self_s.open", "loader.register_s",
        "loader.first_dispatch_s", "loader.queue_wait_s",
        "loader.io_feeds_s", "loader.io_columns_s", "loader.upload_s",
        "loader.doc_init_s", "host.gc_s.open", "loader.heads_s",
        "loader.form_s", "pack.gate_s", "pack.prefix_native_s",
        "pack.widen_s",
    }
    for name, value in read.items():
        if name in nothing:
            assert value is None
        elif name != "host.gc_s.open":  # a tiny open may collect nothing
            assert value is not None and value >= 0.0, name


def test_cols_bulk_pct_reads_through_bulk_stats(traced_open):
    """`loader.cols_bulk_pct` (ISSUE 25) is data only: its metric file
    names the `bulk_stats` reader, which takes the median of the opens'
    `last_bulk_stats["cols_bulk_pct"]` from a recorded `obs`, and finds
    nothing (None, no raise) in the stats of a program that lacks it."""
    import json

    from benchmark.readers import bulk_stats

    _spans, stats = traced_open
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "loader.cols_bulk_pct.json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "bulk_stats"
    assert (stats["cols_bulk_feeds"], stats["cols_single_feeds"]) == (
        N_DOCS, 0)
    obs = {"bulk_stats": [stats, dict(stats, cols_bulk_pct=50.0), stats]}
    assert bulk_stats.read(spec["params"], obs) == 100.0
    older = {k: v for k, v in stats.items() if not k.startswith("cols_")}
    assert bulk_stats.read(spec["params"], {"bulk_stats": [older]}) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == spec["name"]]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key


def test_a_trace_without_program_spans_reads_none(monkeypatch):
    """Laid over the program before PR 24 the readers find nothing and
    do not raise."""
    monkeypatch.setattr(span_tree, "newest_trace", lambda: None)
    assert span_tree.read({"measure": "first_dispatch"}, {}) is None
    assert trace_scope_time.read(
        {"match": ["x"], "scope": "wire", "shapes": "bulk_slabs"},
        {"bulk_slabs": [[16, 64]]}) is None
    with pytest.raises(LookupError):
        Tree([Span("bench.loader.open_many", 0.0, 1.0, 0, {})])


def test_dispatch_stats_come_from_its_child_spans(traced_open):
    """t_narrow / t_upload / t_dispatch are the seconds of the dispatch
    stage's child spans (one clock pair a stage), with tracing on or
    off; every key the loader's stats had is still there."""
    _, stats = traced_open
    for key in ("t_sql", "t_io", "t_spec", "t_pack", "t_narrow",
                "t_upload", "t_dispatch", "t_fetch_busy", "t_fetch",
                "t_pack_wall", "wall_critical_path"):
        assert key in stats, key
    for key in ("t_io", "t_pack", "t_narrow", "t_upload", "t_dispatch",
                "t_fetch_busy"):
        assert stats[key] > 0.0, key
    assert stats["device_slabs"] == 3 and stats["host_slabs"] == 0


# -- hand-made spans: the arithmetic ------------------------------------


def _handmade():
    """One open on a 10 s clock. Caller thread `c`, io thread `i`, pack
    thread `p`, fetch thread `f`; device busy 4-5 s and 7-8 s."""
    o = {"open": 9}
    s0, s1 = dict(o, slab=0), dict(o, slab=1)
    spans = [
        Span("repo.init", -0.5, -0.1, "c", {}),
        Span("repo.open_many", 0.0, 9.0, "c", dict(o)),
        Span("frontend.open_many.handles", 0.1, 0.4, "c", dict(o)),
        Span("pipeline.bulk_load", 0.5, 9.0, "c", dict(o)),
        Span("pipeline.register", 0.5, 1.0, "c", dict(o)),
        Span("pipeline.io", 1.0, 2.0, "i", dict(s0, parent="pipeline.bulk_load")),
        Span("storage.feeds.open", 1.0, 1.25, "i", dict(s0)),
        Span("storage.columns.load", 1.25, 2.0, "i", dict(s0)),
        Span("pipeline.io", 2.0, 3.0, "i", dict(s1, parent="pipeline.bulk_load")),
        Span("pipeline.pack", 2.5, 3.5, "p", dict(s0, parent="pipeline.io")),
        Span("pipeline.wait", 1.0, 3.5, "c", dict(o, q="dispatch", side="get")),
        Span("pipeline.dispatch", 3.5, 4.0, "c", dict(s0)),
        Span("pipeline.enqueue", 3.75, 4.0, "c", dict(s0)),
        Span("pipeline.pack", 5.0, 6.0, "p", dict(s1, parent="pipeline.io")),
        Span("pipeline.fetch", 4.0, 5.5, "f", dict(s0, parent="pipeline.dispatch")),
        Span("pipeline.dispatch", 6.5, 7.0, "c", dict(s1)),
        Span("pipeline.fetch", 7.0, 8.5, "f", dict(s1, parent="pipeline.dispatch")),
        Span("pipeline.barrier", 9.0, 9.5, "c", dict(o, parent="repo.open_many")),
        Span("host.gc", 0.6, 0.85, "c", dict(o, gen=2)),
        Span("host.gc", 5.25, 5.5, "x", {"gen": 2}),  # a thread with no context
        Span("host.gc", 20.0, 21.0, "x", {"gen": 2}),  # after the open
    ]
    return Tree(spans), [(4.0, 5.0), (7.0, 8.0)]


def test_handmade_tree_parents_and_depth():
    tree, _ = _handmade()
    by = {(s.name, s.slab): s for s in tree.members if s.name != "host.gc"}
    assert by[("pipeline.register", None)].parent.name == "pipeline.bulk_load"
    assert by[("storage.columns.load", 0)].parent is by[("pipeline.io", 0)]
    assert by[("pipeline.io", 1)].parent.name == "pipeline.bulk_load"
    # `parent=` picks the namesake of the same slab
    assert by[("pipeline.pack", 1)].parent is by[("pipeline.io", 1)]
    assert by[("pipeline.fetch", 0)].parent is by[("pipeline.dispatch", 0)]
    assert by[("pipeline.barrier", None)].parent is tree.root
    assert by[("pipeline.enqueue", 0)].depth == 3
    assert tree.end == 9.5


def test_handmade_self_time():
    tree, _ = _handmade()
    by = {(s.name, s.slab): s for s in tree.members if s.name != "host.gc"}
    # root 9.0 s, minus handles 0.3 and bulk_load 8.5 on its thread
    assert tree.self_s(tree.root) == pytest.approx(0.2)
    # register 0.5 s, a 0.25 s collection inside it
    assert tree.self_s(by[("pipeline.register", None)]) == pytest.approx(0.25)
    assert tree.self_s(by[("pipeline.io", 0)]) == pytest.approx(0.0)
    init = tree.before_root("repo.init")
    assert init is not None and tree.self_s(init) == pytest.approx(0.4)
    # the facade metric: root self + handles + repo.init before the root
    facade = sum(tree.self_s(s) for s in tree.named(
        ("repo.open_many", "frontend.open_many.handles"))) + tree.self_s(init)
    assert facade == pytest.approx(0.2 + 0.3 + 0.4)


def test_handmade_totals_and_chain_wait():
    tree, _ = _handmade()
    assert tree.total(("pipeline.io",)) == pytest.approx(2.0)
    assert tree.total(("pipeline.io",), slab=1) == pytest.approx(1.0)
    # host.gc: the one inside the open by id, the one inside its extent
    # by time, not the one after it
    assert tree.total(("host.gc",)) == pytest.approx(0.5)
    assert tree.first_dispatch() == pytest.approx(4.0)
    # slab 0: io 1-2, pack 2.5-3.5, dispatch 3.5-4, fetch 4-5.5:
    # 4.5 s end to end, 4.0 s busy
    assert tree.chain_wait(0) == pytest.approx(0.5)
    # slab 1: io 2-3, pack 5-6, dispatch 6.5-7, fetch 7-8.5
    assert tree.chain_wait(1) == pytest.approx(2.0 + 0.5)


def test_handmade_idle_attribution():
    tree, busy = _handmade()
    assert tree.idle_gaps(busy) == [(0.0, 4.0), (5.0, 7.0), (8.0, 9.5)]
    assert tree.head_idle(busy) == pytest.approx(4.0)
    table = tree.idle_table(busy)
    assert sum(r[1] for r in table) == pytest.approx(7.5)
    got = [(round(at, 2), round(secs, 2), name, slab)
           for at, secs, name, slab in table]
    assert got == [
        (0.0, 0.1, "repo.open_many (self)", None),
        (0.1, 0.3, "frontend.open_many.handles", None),
        (0.4, 0.1, "repo.open_many (self)", None),
        (0.5, 0.1, "pipeline.register", None),
        (0.6, 0.25, "host.gc", None),  # the innermost span
        (0.85, 0.15, "pipeline.register", None),
        # work beats the caller's wait; the lowest slab beats slab 1's io
        (1.0, 0.25, "storage.feeds.open", 0),
        (1.25, 0.75, "storage.columns.load", 0),
        (2.0, 0.5, "pipeline.io", 1),
        (2.5, 1.0, "pipeline.pack", 0),
        (3.5, 0.25, "pipeline.dispatch", 0),
        (3.75, 0.25, "pipeline.enqueue", 0),
        # work that feeds the device beats slab 0's fetch behind it
        (5.0, 1.0, "pipeline.pack", 1),
        (6.0, 0.5, "pipeline.bulk_load", None),
        (6.5, 0.5, "pipeline.dispatch", 1),
        (8.0, 0.5, "pipeline.fetch", 1),
        (8.5, 0.5, "pipeline.bulk_load", None),
        (9.0, 0.5, "pipeline.barrier", None),
    ]
    named = sum(r[1] for r in table if not r[2].startswith("repo.open_many"))
    assert 100.0 * named / 7.5 == pytest.approx(100.0 * 7.3 / 7.5)


def test_ring_file_gives_the_same_tree():
    """scripts/profile_trace.py --by slab reads a ring (Chrome) file
    through the same tree."""
    tree, _ = _handmade()
    events = [
        {"ph": "X", "name": s.name, "ts": s.t0 * 1e6,
         "dur": s.dur * 1e6, "tid": s.line, "args": s.args}
        for s in tree.all
    ] + [{"ph": "M", "name": "thread_name", "tid": "c", "args": {}},
         {"ph": "X", "name": "bench.loader.open_many", "ts": 0, "dur": 5}]
    again = Tree(span_tree.from_chrome(events))
    assert [(s.name, s.slab, s.depth) for s in again.members] == [
        (s.name, s.slab, s.depth) for s in tree.members]
    assert again.chain_wait(1) == pytest.approx(2.5)


# -- kernel seconds by scope ---------------------------------------------

_HLO = """\
HloModule jit_materialize_full_lean_device, is_scheduled=true

%fused_computation.1 (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  ROOT %g = s32[8]{0} gather(%p0), metadata={op_name="jit(f)/vmap(rga_order)/gather"}
}

%fused_computation.2 (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  %a = s32[8]{0} add(%p0, %p0), metadata={op_name="jit(f)/vmap(lww)/add"}
  ROOT %b = s32[8]{0} add(%a, %a), metadata={op_name="jit(f)/vmap(elem_values)/add"}
}

%fused_computation.3 (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  ROOT %c = s32[8]{0} convert(%p0), metadata={op_name="jit(f)/convert_element_type"}
}

ENTRY %main.9 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/vmap(rga_order)/gather"}
  %fusion.2 = s32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/vmap(lww)/add"}
  %fusion.3 = s32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3
  %sort.4 = s32[8]{0} sort(%fusion.3), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(f)/wire/sort"}
  ROOT %copy.5 = s32[8]{0} copy(%sort.4), metadata={op_name="jit(f)/vmap(clock)/a;jit(f)/vmap(wire)/b"}
}
"""


def test_phases_of_handmade_hlo():
    got = crdt_kernels.phases_of_hlo(_HLO)
    assert got == {
        "%x": "unscoped",
        "%fusion.1": "rga_order",
        "%fusion.2": "mixed",  # lww + elem_values fused together
        "%fusion.3": "unscoped",
        "%sort.4": "wire",
        "%copy.5": "mixed",  # one instruction merged from two scopes
    }


def test_scope_seconds_books_a_two_scope_fusion_to_mixed():
    phases = crdt_kernels.phases_of_hlo(_HLO)
    modules = [("jit_materialize_full_lean_device(77)", 100.0, 50.0),
               ("jit_other(3)", 200.0, 10.0),
               ("jit_materialize_full_lean_device(78)", 300.0, 20.0)]
    ops = [
        ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %x), kind=kLoop", 100.0, 10.0),
        ("%fusion.2 = s32[8]{0} fusion(s32[8]{0} %fusion.1)", 110.0, 20.0),
        ("%sort.4 = s32[8]{0} sort(s32[8]{0} %fusion.3)", 130.0, 5.0),
        ("%unknown.9 = s32[8]{0} add(s32[8]{0} %q)", 135.0, 5.0),
        ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %y)", 205.0, 5.0),  # other
        ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %x), kind=kLoop", 300.0, 20.0),
    ]
    asked = []

    def phase_of_ops(n_docs, n_rows, lean):
        asked.append((n_docs, n_rows, lean))
        return phases

    got = trace_scope_time.scope_seconds(
        modules, ops, [(16, 64), (8, 64)], ["materialize_full"], phase_of_ops)
    assert asked == [(16, 64, True), (8, 64, True)]  # k-th program, k-th slab
    assert got == {"rga_order": 30.0, "mixed": 20.0, "wire": 5.0,
                   "unscoped": 5.0, "uncovered": 10.0}
    # nothing dropped: the scopes add up to the matched programs' time
    assert sum(got.values()) == 50.0 + 20.0
    # a shape the program never dispatched: nothing to read
    assert trace_scope_time.scope_seconds(
        modules, ops, [(16, 64)], ["materialize_full"],
        lambda *a: {}) is None


def test_real_program_carries_every_phase(traced_open):
    """The slab program the traced open dispatched, compiled again for
    its shape: every phase of PHASES owns some instruction, and the
    scopes changed nothing a cache key sees (test_summary_wire and the
    cold-start tests pin the results)."""
    got = crdt_kernels.phase_of_ops(SLAB, N_OPS, True)
    assert got, sorted(crdt_kernels._dispatched)
    assert set(got.values()) >= set(crdt_kernels.PHASES) - {"counters"}
    assert set(got.values()) <= set(crdt_kernels.PHASES) | {
        "mixed", "unscoped"}
    assert crdt_kernels.phase_of_ops(SLAB, N_OPS * 64, True) == {}


def test_profile_trace_by_slab(traced_open, tmp_path, capsys):
    """scripts/profile_trace.py --by slab: the same lanes, by request
    and slab, from a ring file."""
    import importlib.util

    spans, _ = traced_open
    spec = importlib.util.spec_from_file_location(
        "profile_trace", os.path.join(ROOT, "scripts", "profile_trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    events = [
        {"ph": "X", "name": s.name, "ts": s.t0 * 1e6, "dur": s.dur * 1e6,
         "tid": s.line, "args": s.args} for s in spans
    ]
    assert mod.slab_view(events, [])
    out = capsys.readouterr().out
    for k in range(3):
        assert f"slab {k}: waited" in out
    assert "pipeline.register" in out and "pipeline.wait, thread-seconds" in out
    # ISSUE 34: `cpu` and `off` (wall - CPU) beside each span's seconds,
    # the pack's children down to the prefix stages
    rows = [ln for ln in out.splitlines() if " pipeline.pack " in ln
            or "pipeline.pack.prefix.native" in ln]
    # (two opens of three slabs)
    assert len(rows) == 12 and all(" cpu " in r and " off " in r
                                   and "-" not in r for r in rows)
    # a ring file carries the CPU microseconds as `tdur`; a span
    # without it prints dashes
    ring = [dict({k: v for k, v in e.items() if k != "args"},
                 args={k: v for k, v in e["args"].items() if k != "cpu_us"},
                 **({"tdur": e["args"]["cpu_us"]}
                    if e["name"] != "pipeline.pack" else {}))
            for e in events]
    assert mod.slab_view(ring, [])
    again = capsys.readouterr().out.splitlines()
    assert [ln for ln in again if "pipeline.pack.prefix.native" in ln] == [
        r for r in rows if "pipeline.pack.prefix.native" in r]
    assert all("cpu        -  off        -" in ln for ln in again
               if " pipeline.pack " in ln)
    assert not mod.slab_view(
        [e for e in events if e["name"] != "repo.open_many"], [])


# -- the second clock: CPU seconds beside wall seconds (ISSUE 34) ----------

PREFIX_STAGES = ("pipeline.pack.prefix.tables", "pipeline.pack.prefix.preds",
                 "pipeline.pack.prefix.native", "pipeline.pack.prefix.emit")


def test_pack_is_split_where_its_seconds_are(traced_open):
    """Every `pipeline.pack` holds one gate, one prefix path whose
    stages follow one another without overlap and sum to it, and one
    widen; all carry the open's and the slab's ids; none is per doc."""
    spans, _ = traced_open
    tree = Tree(spans)
    for k in tree.slabs():
        (pack,) = tree.named(("pipeline.pack",), k)
        under = sorted(
            (s for s in tree.members
             if s.line == pack.line and s is not pack
             and pack.t0 <= s.t0 and s.t1 <= pack.t1),
            key=lambda s: s.t0)
        assert all(s.slab == k and s.args["open"] == tree.open
                   for s in under)
        kids = [s for s in under if s.parent is pack]
        assert [s.name for s in kids] == [
            "pipeline.pack.gate", "pipeline.pack.prefix",
            "pipeline.pack.widen"]
        gate, prefix, widen = kids
        assert gate.args["docs"] == prefix.args["docs"] == (16, 16, 8)[k]
        assert gate.args["prefix"] == 1 and widen.args["P"] == N_OPS // 4
        stages = [s for s in under if s.parent is prefix]
        names = [s.name for s in stages]
        # tables twice: the interning loops, then the pointer table
        # just before the native call
        assert names == [PREFIX_STAGES[0], PREFIX_STAGES[1],
                         PREFIX_STAGES[0], *PREFIX_STAGES[2:]]
        assert stages[3].args["native"] == 1
        for a, b in zip(stages, stages[1:]):
            assert a.t1 <= b.t0, (a, b)
        covered = sum(s.dur for s in stages)
        assert covered <= prefix.dur
        # a 16-doc slab packs in under a millisecond: the span
        # overhead between stages is the rest
        assert covered >= 0.95 * prefix.dur or prefix.dur - covered < 5e-4


def test_cpu_us_on_every_span_that_has_one(traced_open):
    """`cpu_us` comes back from the profiler as an integer stat, at
    most the span's wall seconds (+ 1 ms), on every span that began
    and ended on one thread: all of an open's."""
    spans, _ = traced_open
    tree = Tree(spans)
    for s in tree.members:
        assert "cpu_us" in s.args, s
    for s in spans:
        if "cpu_us" in s.args:
            assert isinstance(s.args["cpu_us"], int)
            assert 0 <= s.args["cpu_us"] <= s.dur * 1e6 + 1000, s


def _cpu_spans():
    """Hand-made: a flusher thread `f` with two flushes, a client `c`.
    (name, t0, t1, line, cpu seconds or None)."""
    rows = [
        ("serve.batch", 0.0, 10.0, "f", 4.0),       # off 6.0
        ("serve.dispatch", 1.0, 7.0, "f", 1.0),
        ("serve.dispatch.stack", 1.0, 2.0, "f", 0.5),
        ("serve.dispatch.call", 2.0, 4.0, "f", 0.5),  # off 1.5
        ("serve.dispatch.fetch", 4.0, 7.0, "f", 0.0),  # off 3.0
        ("serve.callback", 8.0, 9.0, "f", 1.0),
        ("serve.batch", 20.0, 24.0, "f", 3.0),      # off 1.0
        ("serve.dispatch", 20.0, 23.0, "f", 2.5),
        ("serve.dispatch.fetch", 21.0, 23.0, "f", 1.5),  # off 0.5
        ("serve.callback", 23.0, 23.5, "f", 0.5),
        # another thread's fetch: inside the first batch by the clock,
        # not on its thread, so `except` leaves it alone
        ("serve.dispatch.fetch", 5.0, 6.0, "c", 0.0),
        ("serve.read", 0.5, 9.0, "c", None),        # ended on `f`
    ]
    return [
        Span(n, a, b, line, {} if cpu is None else {"cpu_us": int(cpu * 1e6)})
        for n, a, b, line, cpu in rows
    ]


@pytest.mark.parametrize("params, want, parent", [
    # offcpu with `except`: (6.0 + 1.0) - (1.5 + 3.0 + 0.5)
    ({"measure": "offcpu", "names": ["serve.batch"],
      "except": ["serve.dispatch.call", "serve.dispatch.fetch"]}, 2.0, None),
    # ... a flush
    ({"measure": "offcpu", "names": ["serve.batch"], "per": ["serve.batch"],
      "except": ["serve.dispatch.call", "serve.dispatch.fetch"]}, 1.0, None),
    # no `except`; a span without the stat counts for neither clock
    ({"measure": "offcpu", "names": ["serve.batch", "serve.read"]}, 7.0,
     None),
    # cpu of the three fetches 1.5 s over their 6 s
    ({"measure": "cpu_pct", "names": ["serve.dispatch.fetch"]}, 25.0, None),
    # none of the named spans carries the stat: None, not 0
    ({"measure": "offcpu", "names": ["serve.read"]}, None, None),
    ({"measure": "cpu_pct", "names": ["serve.read"]}, None, None),
    # the two wall-clock measures read the parent's trace too
    ({"measure": "minus", "names": ["serve.dispatch"],
      "less": ["serve.dispatch.fetch"]}, 3.0, 3.0),
    ({"measure": "minus", "names": ["serve.callback"],
      "per": ["serve.batch"]}, 0.75, 0.75),
    ({"measure": "max", "names": ["serve.dispatch"]}, 6.0, 6.0),
    # no span of the name
    ({"measure": "max", "names": ["serve.decode"]}, None, None),
    ({"measure": "minus", "names": ["serve.callback"],
      "per": ["serve.install"]}, None, None),
], ids=["offcpu-except", "offcpu-per", "offcpu-plain", "cpu_pct",
        "offcpu-no-stat", "cpu_pct-no-stat", "minus", "minus-per", "max",
        "max-nothing", "per-nothing"])
def test_span_cpu_measures_on_handmade_spans(params, want, parent,
                                             monkeypatch):
    """`readers/span_cpu.py` over the traced seconds (`window`): its
    four measures, and over the same spans without `cpu_us` (the
    parent's trace): None from the two that read the CPU clock."""
    from benchmark.readers import span_cpu

    spans = _cpu_spans()
    bare = [Span(s.name, s.t0, s.t1, s.line, {}) for s in spans]
    monkeypatch.setattr(span_tree, "newest_trace", lambda: "x.pb")
    for trace, expect in ((spans, want), (bare, parent)):
        monkeypatch.setattr(span_tree, "load", lambda p, t=trace: (t, []))
        got = span_cpu.read(dict(params, window=True), {"trace": {"a": 1}})
        assert got == (None if expect is None else pytest.approx(expect))
    assert span_cpu.read(dict(params, window=True), {}) is None  # untraced


def test_span_cpu_reads_the_traced_open(traced_open, monkeypatch):
    """Every new metric file of the cold-open cells (ISSUE 34) finds a
    number in a traced open, through its own reader; entry and file
    agree; over the same open without `cpu_us` and without the new
    spans (the parent) each reads None and none raises."""
    import json

    from benchmark.readers import span_cpu

    spans, _ = traced_open
    new = ("pack.offcpu_s", "loader.io_offcpu_s", "loader.caller_offcpu_s",
           "pack.gate_s", "pack.prefix_native_s", "pack.prefix_python_s",
           "pack.widen_s")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        listed = {m["name"]: m for m in json.load(fh)["per_layer"]}
    readers = {"span_cpu": span_cpu, "span_tree": span_tree}
    added = ("pipeline.pack.gate", "pipeline.pack.widen") + PREFIX_STAGES
    old = [Span(s.name, s.t0, s.t1, s.line,
                {k: v for k, v in s.args.items() if k != "cpu_us"})
           for s in spans if s.name not in added]
    for name in new:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as fh:
            spec = json.load(fh)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert listed[name][key] == spec[key], (name, key)
        assert listed[name]["workloads"] == spec["cells"]
        assert "reads.resident" not in spec["cells"]
        read = readers[spec["reader"]].read
        monkeypatch.setattr(span_tree, "tree_of",
                            lambda obs: (Tree(spans), []))
        value = read(spec["params"], {})
        assert value is not None and value >= 0.0, name
        monkeypatch.setattr(span_tree, "tree_of",
                            lambda obs: (Tree(old), []))
        if name != "pack.prefix_python_s":
            assert read(spec["params"], {}) is None, name
    tree = Tree(spans)
    # the io thread and the caller wait (files, queues); what they read
    # is what the tree holds
    packs = tree.named(("pipeline.pack",))
    assert span_cpu.read({"measure": "offcpu", "names": ["pipeline.pack"]},
                         {}) is None  # `old` is in place: no stat
    monkeypatch.setattr(span_tree, "tree_of", lambda obs: (tree, []))
    assert span_cpu.read(
        {"measure": "offcpu", "names": ["pipeline.pack"]}, {}
    ) == pytest.approx(sum(s.dur - s.args["cpu_us"] / 1e6 for s in packs))
