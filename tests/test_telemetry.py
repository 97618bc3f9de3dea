"""Round-13 observability (ISSUE 9): the unified telemetry layer.

Pins, in order: registry exactness under concurrency (the per-thread
shards are also the fix for the unlocked ``stats[...] +=`` races the
old ad-hoc dicts carried), trace-ring wraparound, golden Chrome-trace
and Prometheus exporters, the migrated stats dicts' key/shape
compatibility, the Telemetry IPC query, HM_TRACE env activation, the
acceptance trace (spans from live + pipeline + net + storage in one
run), and the hot-path overhead regression (disabled spans are a
shared no-op; a registry counter bump stays micro-budget on the
config2 live-edit path)."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from hypermerge_tpu import telemetry
from hypermerge_tpu.telemetry import trace as ttrace
from hypermerge_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture
def tracer():
    """Isolated tracing window: fresh ring, enabled, restored after."""
    was_on = ttrace.enabled()
    ttrace.reset()
    ttrace.enable()
    yield ttrace
    if not was_on:
        ttrace.disable()
    ttrace.reset()


# ---------------------------------------------------------------------------
# registry


def test_counter_concurrent_adds_exact():
    reg = MetricsRegistry()
    c = reg.counter("t.hammer")
    N, T = 20000, 8

    def worker():
        for _ in range(N):
            c.add(1)

    ts = [threading.Thread(target=worker) for _ in range(T)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # EXACT, not approximate: each thread owns its shard, merge on read
    assert c.value() == N * T


def test_float_counter_concurrent_adds_exact():
    """The t_resync_ms shape: float accumulation from many threads
    (the old dict += from reader threads could lose increments)."""
    reg = MetricsRegistry()
    c = reg.counter("t.ms")
    N, T = 5000, 6

    def worker():
        for _ in range(N):
            c.add(0.5)

    ts = [threading.Thread(target=worker) for _ in range(T)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value() == N * T * 0.5


def test_histogram_concurrent_observes_exact():
    reg = MetricsRegistry()
    h = reg.histogram("t.h", buckets=(1.0, 10.0))
    T, N = 6, 3000

    def worker(i):
        for j in range(N):
            h.observe((0.5, 5.0, 50.0)[(i + j) % 3])

    ts = [
        threading.Thread(target=worker, args=(i,)) for i in range(T)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    v = h.value()
    assert v["count"] == T * N
    assert sum(v["buckets"]) == T * N
    total = T * N // 3
    assert v["buckets"] == [total, total, total]


def test_registry_get_or_create_and_labels():
    reg = MetricsRegistry()
    a = reg.counter("x.c", inst="1")
    b = reg.counter("x.c", inst="1")
    other = reg.counter("x.c", inst="2")
    assert a is b and a is not other
    a.add(3)
    other.add(4)
    reg.gauge("x.g").set(7)
    snap = reg.snapshot()
    # aggregated across label sets, int-ness preserved
    assert snap["x.c"] == 7 and isinstance(snap["x.c"], int)
    assert snap["x.g"] == 7


def test_retire_folds_into_closed_aggregate():
    """Open/close cycles must not grow the registry a label set per
    lifecycle — retire() folds counters into inst="closed" while the
    process totals (snapshot) stay exact."""
    reg = MetricsRegistry()
    for i in range(5):
        c = reg.counter("live.ticks", inst=str(i))
        g = reg.gauge("live.live_docs", inst=str(i))
        c.add(10)
        g.set(3)
        reg.retire(c, g)
        reg.retire(c, g)  # idempotent: no double-fold
    assert reg.snapshot()["live.ticks"] == 50
    # one aggregate series survives, not five (+ no dead gauges)
    assert len(reg.series()) == 1


def test_engine_close_retires_series():
    from hypermerge_tpu import telemetry
    from hypermerge_tpu.repo import Repo

    repo = Repo(memory=True)
    eng = repo.back.live
    if eng is None:
        repo.close()
        pytest.skip("live engine off (HM_LIVE=0)")
    labeled = {
        (m.name, m.labels)
        for m in telemetry.REGISTRY.series()
        if m in set(eng._m.values())
    }
    assert labeled  # registered while open
    repo.close()
    live_series = set(eng._m.values())
    assert not any(
        m in live_series for m in telemetry.REGISTRY.series()
    )
    # the historical dict stays readable after close (handle-based)
    assert "ticks" in eng.stats


def test_reset_zeroes_in_place_keeping_handles():
    """reset() must not blind module-level cached handles (net.tcp.*,
    pipeline.* are created once at import): series zero in place and
    keep reporting."""
    reg = MetricsRegistry()
    c = reg.counter("x.c")
    g = reg.gauge("x.g")
    c.add(5)
    g.set(3)
    reg.reset()
    assert reg.snapshot() == {"x.c": 0, "x.g": 0}
    c.add(2)  # the cached handle is still live and visible
    assert reg.snapshot()["x.c"] == 2


def test_snapshot_rounds_floats():
    reg = MetricsRegistry()
    reg.counter("x.t").add(0.1)
    reg.counter("x.t").add(0.2)
    v = reg.snapshot()["x.t"]
    assert v == round(v, 6)


# ---------------------------------------------------------------------------
# trace ring


def test_trace_ring_wraparound():
    r = ttrace._Ring(16)
    for i in range(40):
        r.add(("X", f"s{i}", "", float(i), 1.0, 0, None))
    got = r.events()
    # the LAST 16 events, oldest first
    assert [e[1] for e in got] == [f"s{i}" for i in range(24, 40)]
    assert len(r) == 16


def test_span_begin_end_tags(tracer):
    sp = telemetry.begin("t.window", cat="net", a=1)
    time.sleep(0.001)
    sp.end(b=2)
    with telemetry.span("t.block", cat="live"):
        pass
    telemetry.instant("t.point", cat="storage", k="v")
    evs = telemetry.trace_events()
    by_name = {e[1]: e for e in evs}
    ph, name, cat, ts, dur, tid, args, cpu_us = by_name["t.window"]
    assert ph == "X" and cat == "net"
    assert dur >= 1000  # the 1ms sleep, in µs
    assert 0 <= cpu_us <= dur  # begun and ended on this thread
    assert args == {"a": 1, "b": 2}  # begin tags merged with end tags
    assert by_name["t.block"][0] == "X"
    assert by_name["t.point"][0] == "i"
    assert by_name["t.point"][6] == {"k": "v"}
    assert by_name["t.point"][7] is None  # an instant has no CPU value


def test_disabled_span_is_shared_noop():
    was_on = ttrace.enabled()
    ttrace.disable()
    try:
        # no allocation: every disabled span() IS the same singleton
        assert telemetry.span("a") is telemetry.span("b")
        assert telemetry.begin("c") is telemetry.NOOP
        n0 = telemetry.event_count()
        with telemetry.span("d"):
            pass
        telemetry.instant("e")
        assert telemetry.event_count() == n0  # nothing recorded
        # and cheap: 100k disabled spans well under any hot-path budget
        t0 = time.perf_counter()
        for _ in range(100_000):
            with telemetry.span("f"):
                pass
        assert time.perf_counter() - t0 < 1.0
    finally:
        if was_on:
            ttrace.enable()


# ---------------------------------------------------------------------------
# golden exporters


def test_chrome_trace_golden(tracer, tmp_path):
    with telemetry.span("live.tick", cat="live", docs=3):
        pass
    telemetry.instant("net.resync", cat="net", ms=5)
    # cause and request (ISSUE 24): the ids ride as the event's args
    with telemetry.span("pipeline.io", "pipeline", open=7, slab=2,
                        parent="pipeline.bulk_load"):
        with telemetry.span("storage.feeds.open", "storage", feeds=4):
            pass
    path = str(tmp_path / "t.json")
    telemetry.flush_trace(path)
    doc = json.load(open(path))
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["name"] for m in meta} >= {"process_name", "thread_name"}
    x, feeds, io = [e for e in evs if e["ph"] == "X"]
    assert x["name"] == "live.tick" and x["cat"] == "live"
    assert x["args"] == {"docs": 3}  # the CPU value is no tag
    assert set(x) == {"ph", "name", "cat", "ts", "dur", "tdur", "pid",
                      "tid", "args"}
    # `tdur` (Trace Event Format: the thread-clock duration): the CPU
    # microseconds of the span's thread inside it, under its wall `dur`
    for e in (x, feeds, io):
        assert 0 <= e["tdur"] <= e["dur"]
    assert feeds["tdur"] <= io["tdur"]  # the child's CPU is the parent's
    (i,) = [e for e in evs if e["ph"] == "i"]
    assert i["name"] == "net.resync" and i["s"] == "t"
    assert "tdur" not in i and "dur" not in i
    assert io["name"] == "pipeline.io" and io["args"] == {
        "open": 7, "slab": 2, "parent": "pipeline.bulk_load"}
    # the child inherits the request and slab ids, not the parent tag
    assert feeds["name"] == "storage.feeds.open" and feeds["args"] == {
        "feeds": 4, "open": 7, "slab": 2}
    assert io["ts"] <= feeds["ts"] and io["tid"] == feeds["tid"]


def test_ids_are_inherited_per_thread(tracer):
    """`open` / `slab` come down from the span a thread is in (entered
    with `with`); another thread starts with none, and `open_id()` is
    the enclosing open's id or the next of the sequence."""
    got = {}

    def worker():
        got["other"] = telemetry.open_id()
        with telemetry.span("t.other", open=got["other"]):
            with telemetry.span("t.other.child"):
                pass

    with telemetry.span("t.root", open=telemetry.open_id(), docs=5) as root:
        assert telemetry.open_id() == root.args["open"]
        t = threading.Thread(target=worker)
        t.start()
        t.join(10)
        assert not t.is_alive()
        with telemetry.span("t.slab", slab=1):
            sp = telemetry.begin("t.leaf", docs=2)  # begin() inherits too
            sp.end(n=3)
    assert telemetry.open_id() not in (root.args["open"], got["other"])
    by = {e[1]: e[6] for e in telemetry.trace_events()}
    oid = root.args["open"]
    assert by["t.root"] == {"open": oid, "docs": 5}
    assert by["t.slab"] == {"slab": 1, "open": oid}
    assert by["t.leaf"] == {"docs": 2, "open": oid, "slab": 1, "n": 3}
    assert by["t.other"] == {"open": got["other"]} and got["other"] != oid
    assert by["t.other.child"] == {"open": got["other"]}


def test_timed_reads_the_clock_with_tracing_off():
    """A stage that feeds a stat gets its seconds whether or not a sink
    is on, and the seconds of the spans under it by name (`kids`): one
    clock pair a stage."""
    was_on = ttrace.enabled()
    ttrace.disable()
    try:
        n0 = telemetry.event_count()
        with telemetry.timed("t.stage", "pipeline") as sp:
            with telemetry.timed("t.a"):
                with telemetry.timed("t.b"):
                    time.sleep(0.002)
            with telemetry.timed("t.a"):
                pass
            with telemetry.span("t.noop") as noop:  # off: not a handle
                noop.note(docs=1)
        assert noop is telemetry.NOOP
        assert telemetry.event_count() == n0  # nothing recorded
        assert set(sp.kids) == {"t.a", "t.b"}  # descendants, by name
        assert sp.dur >= sp.kids["t.a"] >= sp.kids["t.b"] >= 0.002
        assert sp.cpu is None  # no sink: the CPU clock was not read
    finally:
        if was_on:
            ttrace.enable()


def _spin(seconds: float) -> None:
    """Busy for `seconds`, ahead of the suite's other workers where the
    box lets this thread be (SCHED_FIFO, root): under `-n 6` a plain
    spin shares its core and reads 35-72% CPU through no fault of the
    span's clock (ROADMAP D14). Where it may not, a plain spin."""
    try:
        os.sched_setscheduler(0, os.SCHED_FIFO, os.sched_param(1))
    except (AttributeError, OSError):
        back = False
    else:
        back = True
    try:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            pass
    finally:
        if back:
            os.sched_setscheduler(0, os.SCHED_OTHER, os.sched_param(0))


def test_span_cpu_tells_work_from_waiting(tracer):
    """The second clock (ISSUE 34): a thread that sleeps inside a span
    reads `cpu` far under `dur`, one that spins reads them equal within
    20%; both sinks get the value (the ring's 8th field, the exported
    `tdur`; the profiler's `cpu_us` is held by
    test_span_reaches_a_running_profiler_session)."""
    with telemetry.span("t.sleep") as sl:
        time.sleep(0.05)
    assert sl.dur >= 0.05 and sl.cpu is not None and sl.cpu < 0.2 * sl.dur
    for _ in range(5):  # a neighbour may take the core mid-spin: retry
        with telemetry.span("t.spin") as sn:
            _spin(0.05)
        assert sn.cpu <= sn.dur
        if sn.cpu >= 0.8 * sn.dur:
            break
    else:
        pytest.fail(f"a spinning span read cpu {sn.cpu} of dur {sn.dur}")
    by = {e[1]: e for e in telemetry.trace_events()}
    assert by["t.sleep"][7] == pytest.approx(sl.cpu * 1e6)
    assert by["t.spin"][7] == pytest.approx(sn.cpu * 1e6)
    out = {e["name"]: e for e in telemetry.chrome_trace_events(
        telemetry.trace_events())}
    assert out["t.sleep"]["tdur"] == pytest.approx(sl.cpu * 1e6, abs=1e-3)
    assert out["t.sleep"]["tdur"] < 0.2 * out["t.sleep"]["dur"]


def test_span_ended_on_another_thread_has_no_cpu_value(tracer):
    """A thread's CPU clock is its own: `serve.read` is begun by the
    caller and ended by the flusher, and records wall seconds only."""
    sp = telemetry.begin("t.cross", cat="serve")
    t = threading.Thread(target=sp.end)
    t.start()
    t.join(10)
    assert not t.is_alive() and sp.dur > 0 and sp.cpu is None
    (ev,) = [e for e in telemetry.trace_events() if e[1] == "t.cross"]
    assert ev[7] is None
    (out,) = [e for e in telemetry.chrome_trace_events([ev])
              if e["ph"] == "X"]
    assert "tdur" not in out and out["dur"] > 0
    # `host.gc` spans record it like any other
    import gc

    gc.collect()
    (g,) = [e for e in telemetry.trace_events() if e[1] == "host.gc"]
    assert g[7] is not None and 0 <= g[7] <= g[4]


def test_no_cpu_clock_read_with_both_sinks_off(monkeypatch):
    """Off, the untraced path gains no clock read: `span()` is NOOP and
    `timed()` (always a real handle, for `Stage`) never calls
    `thread_time_ns`; on, a span reads it once at each end; on a
    platform without the clock a span has no CPU value, never a wrong
    one."""
    calls = []
    real = ttrace._thread_ns

    def counted():
        calls.append(1)
        return real()

    monkeypatch.setattr(ttrace, "_thread_ns", counted)
    was_on = ttrace.enabled()
    ttrace.disable()
    try:
        assert telemetry.span("t.off") is telemetry.NOOP
        with telemetry.timed("t.stage", "pipeline") as sp:
            with telemetry.timed("t.kid"):
                pass
        sp2 = telemetry.timed("t.begin_end")
        sp2.end()
        assert calls == [] and sp.cpu is None and sp2.cpu is None
        assert sp.dur > 0 and set(sp.kids) == {"t.kid"}
        ttrace.enable()
        with telemetry.timed("t.stage", "pipeline") as sp:
            pass
        assert len(calls) == 2 and sp.cpu is not None
        with telemetry.span("t.span") as sp:
            pass
        assert len(calls) == 4 and 0 <= sp.cpu <= sp.dur
        monkeypatch.setattr(ttrace, "_thread_ns", None)
        with telemetry.span("t.no_clock") as sp:
            pass
        assert sp.cpu is None and sp.dur > 0
    finally:
        ttrace.disable()
        ttrace.reset()
        if was_on:
            ttrace.enable()


def test_import_telemetry_imports_no_jax():
    """The seam looks the profiler up only once jax is loaded: the
    package itself stays dependency-free, and with ring and profiler
    both off `span()` is the shared NOOP."""
    code = (
        "import sys\n"
        "import hypermerge_tpu.telemetry as t\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "assert t.span('a.b') is t.NOOP and t.begin('a.c') is t.NOOP\n"
        "assert t.trace.begin is t.trace.span\n"
        "import gc\n"
        "assert t.trace._gc_hook in gc.callbacks\n"
        "print('ok')\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "HM_TRACE"}
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=60,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_span_reaches_a_running_profiler_session(tmp_path):
    """Ring off, profiler on: the span is live and lands in the
    `.xplane.pb` with its ids as the event's stats; after the session
    `span()` is the NOOP again."""
    import glob

    import jax
    from jax.profiler import ProfileData

    was_on = ttrace.enabled()
    ttrace.disable()
    try:
        assert telemetry.span("t.before") is telemetry.NOOP
        jax.profiler.start_trace(str(tmp_path))
        try:
            n0 = telemetry.event_count()
            with telemetry.span("pipeline.pack", "pipeline", open=4,
                                slab=1) as sp:
                sp.note(docs=9)
            telemetry.instant("live.demote", cat="live", k="v")
            assert sp is not telemetry.NOOP
            assert telemetry.event_count() == n0  # the ring stays off
        finally:
            jax.profiler.stop_trace()
        assert telemetry.span("t.after") is telemetry.NOOP
    finally:
        if was_on:
            ttrace.enable()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {
        e.name: dict(e.stats)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name in ("pipeline.pack", "live.demote")
    }
    # the span's CPU microseconds ride as one more stat, set at its end
    cpu_us = found["pipeline.pack"].pop("cpu_us")
    assert isinstance(cpu_us, int) and 0 <= cpu_us <= sp.dur * 1e6 + 1000
    assert cpu_us == int(sp.cpu * 1e6)
    assert found == {
        "pipeline.pack": {"open": 4, "slab": 1, "docs": 9},
        "live.demote": {"k": "v"},
    }


def test_full_collections_are_spans_and_counts(tracer):
    """One gc hook: a generation-2 collection is a `host.gc` span and
    bumps host.gc_full / host.gc_full_s; younger generations are not
    timed."""
    import gc

    before = telemetry.snapshot()
    gc.collect(0)
    gc.collect(1)
    assert not [e for e in telemetry.trace_events() if e[1] == "host.gc"]
    with telemetry.span("pipeline.io", "pipeline", open=5, slab=0):
        gc.collect()
    after = telemetry.snapshot()
    assert after["host.gc_full"] == before.get("host.gc_full", 0) + 1
    assert after["host.gc_full_s"] > before.get("host.gc_full_s", 0.0)
    (ev,) = [e for e in telemetry.trace_events() if e[1] == "host.gc"]
    assert ev[2] == "host" and ev[6]["gen"] == 2 and "collected" in ev[6]
    assert ev[6]["open"] == 5 and ev[6]["slab"] == 0  # inside the open
    assert gc.callbacks.count(ttrace._gc_hook) == 1
    telemetry.install_gc_hook()  # idempotent
    assert gc.callbacks.count(ttrace._gc_hook) == 1


def test_change_span_carries_its_op_count(tracer):
    """`frontend.change` > `frontend.change.resolve{ops}`: the local
    change as the caller waits on it, and the run of the change fn
    over the doc with the intents it recorded."""
    from hypermerge_tpu.repo import Repo

    repo = Repo(memory=True)
    try:
        url = repo.create({"items": []})
        ttrace.reset()

        def paste(d):
            for i in range(5):
                d["items"].append(i)

        repo.change(url, paste)
        repo.change(url, lambda d: None)  # no mutation: no request
    finally:
        repo.close()
    evs = telemetry.trace_events()
    resolve = [e for e in evs if e[1] == "frontend.change.resolve"]
    change = [e for e in evs if e[1] == "frontend.change"]
    assert [e[6]["ops"] for e in resolve] == [5, 0]
    assert len(change) == 2 and all(e[2] == "frontend" for e in change)
    for outer, inner in zip(change, resolve):  # the child nests in it
        assert outer[5] == inner[5]
        assert outer[3] <= inner[3]
        assert inner[3] + inner[4] <= outer[3] + outer[4] + 1.0


def test_prometheus_golden():
    reg = MetricsRegistry()
    reg.counter("live.ticks", inst="1").add(3)
    reg.gauge("live.live_docs").set(2)
    h = reg.histogram("live.tick_s", buckets=(0.01, 0.1))
    for v in (0.005, 0.05, 5.0):
        h.observe(v)
    from hypermerge_tpu.telemetry import prometheus_text

    assert prometheus_text(reg) == (
        "# TYPE hm_live_live_docs gauge\n"
        "hm_live_live_docs 2\n"
        "# TYPE hm_live_tick_s histogram\n"
        'hm_live_tick_s_bucket{le="0.01"} 1\n'
        'hm_live_tick_s_bucket{le="0.1"} 2\n'
        'hm_live_tick_s_bucket{le="+Inf"} 3\n'
        "hm_live_tick_s_sum 5.055\n"
        "hm_live_tick_s_count 3\n"
        "# TYPE hm_live_ticks counter\n"
        'hm_live_ticks{inst="1"} 3\n'
    )


# ---------------------------------------------------------------------------
# migrated stats dicts: shape compatibility + races closed


def test_live_engine_stats_keys_unchanged():
    from hypermerge_tpu.repo import Repo

    repo = Repo(memory=True)
    try:
        eng = repo.back.live
        if eng is None:
            pytest.skip("live engine off (HM_LIVE=0)")
        assert list(eng.stats) == [
            "adopted", "refused", "ticks", "tick_docs", "tick_changes",
            "inc_changes", "kernel_runs", "device_dispatches",
            "local_changes", "adopt_retries", "demoted", "readopted",
            "adopt_held",  # PR 44: adoptions of a doc that had history
            "inc_ops", "seq_ops",  # PR 45: ops a tick applied one at a
            # time; ops through _apply_seq_state (the liveness vector)
            "live_bytes", "live_docs",
            "t_live_append", "t_live_apply", "t_live_kernel",
            "t_live_decode", "t_live_diff",
            "t_adopt_pack", "t_adopt_kernel", "t_adopt_decode",
            "t_adopt_reach", "t_adopt_lock_free", "t_adopt_lock_held",
        ]
        # int counters stay ints (bench JSON bit-compatibility)
        assert isinstance(eng.stats["adopted"], int)
        assert isinstance(eng.stats["t_live_append"], float)
    finally:
        repo.close()


def test_replication_stats_shape_and_race_closed():
    from hypermerge_tpu.net.replication import ReplicationManager

    rm = ReplicationManager(feeds=None, on_discovery=lambda *a: None)
    try:
        assert set(rm.stats) == {
            "resyncs", "t_resync_ms", "antientropy_sweeps",
            # round 19: wire frame counters exposed for the fleet
            # bench's per-peer frame-amplification measurement
            "frames_tx", "frames_rx",
        }
        # the exact race the migration closes: t_resync_ms += from
        # many reader threads at once
        T, N = 8, 2000

        def worker():
            for _ in range(N):
                rm._m["t_resync_ms"].add(1.0)

        ts = [threading.Thread(target=worker) for _ in range(T)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert rm.stats["t_resync_ms"] == T * N
    finally:
        rm.close()


def test_supervisor_stats_shape():
    from hypermerge_tpu.net.resilience import SessionSupervisor

    sup = SessionSupervisor(dial=lambda a: None, deliver=lambda d, x: None)
    assert sup.stats == {"dials": 0, "reconnects": 0}
    sup.stop()


# ---------------------------------------------------------------------------
# the IPC/serve seam


def test_backend_answers_telemetry_query():
    from hypermerge_tpu.backend.repo_backend import RepoBackend

    from helpers import wait_until

    back = RepoBackend(memory=True)
    try:
        got = []
        back.subscribe(got.append)
        back.handle_query(7, {"type": "Telemetry"})
        wait_until(
            lambda: any(
                m.get("type") == "Reply" and m.get("queryId") == 7
                for m in got
            )
        )
        (reply,) = [m for m in got if m.get("type") == "Reply"]
        payload = reply["payload"]
        assert isinstance(payload["counters"], dict)
        assert "time" in payload and "tracing" in payload
        # JSON-serializable end to end (it rides the unix socket)
        json.dumps(payload)
    finally:
        back.close()


# ---------------------------------------------------------------------------
# HM_TRACE env activation (subprocess: import-time hook + atexit write)


def test_hm_trace_env_writes_file_at_exit(tmp_path):
    out = str(tmp_path / "trace.json")
    env = {
        **os.environ,
        "HM_TRACE": out,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.dirname(os.path.dirname(__file__)),
    }
    code = (
        "from hypermerge_tpu import telemetry\n"
        "assert telemetry.tracing_enabled()\n"
        "with telemetry.span('live.tick', cat='live'):\n"
        "    pass\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    doc = json.load(open(out))
    assert any(
        e.get("name") == "live.tick" and e.get("ph") == "X"
        for e in doc["traceEvents"]
    )


# ---------------------------------------------------------------------------
# acceptance: one run's trace carries live + pipeline + net + storage


def test_trace_spans_every_subsystem(tracer, tmp_path):
    """A bulk cold open + a TCP live-edit burst under tracing produces
    spans from the live, pipeline, net, and storage subsystems in one
    Perfetto-loadable file (ISSUE 9 acceptance)."""
    from hypermerge_tpu.net.tcp import TcpSwarm
    from hypermerge_tpu.ops.corpus import make_corpus
    from hypermerge_tpu.repo import Repo

    from helpers import wait_until

    path = str(tmp_path / "repo")
    urls = make_corpus(path, 16, 16)
    repo = Repo(path=path)
    repo.open_many(urls)
    repo.back.fetch_bulk_summaries()
    repo.close()

    ra, rb = Repo(memory=True), Repo(memory=True)
    sa, sb = TcpSwarm(), TcpSwarm()
    try:
        ra.set_swarm(sa)
        rb.set_swarm(sb)
        sb.connect(sa.address)
        u = ra.create({"edits": []})
        h = rb.open(u)
        for i in range(10):
            ra.change(u, lambda d, i=i: d["edits"].append(i))
        wait_until(
            lambda: (h.value() or {}).get("edits", [])[9:] == [9],
            timeout=30,
        )
    finally:
        ra.close()
        rb.close()
        sa.destroy()
        sb.destroy()

    cats = {e[2] for e in telemetry.trace_events()}
    assert {"live", "pipeline", "net", "storage"} <= cats, cats
    out = str(tmp_path / "t.json")
    telemetry.flush_trace(out)
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    assert {e.get("cat") for e in evs if e.get("ph") == "X"} >= {
        "pipeline", "storage"
    }
    # every event carries the fields Perfetto requires
    for e in evs:
        assert {"ph", "name", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert "ts" in e and "dur" in e


# ---------------------------------------------------------------------------
# overhead regression (the config2 live-edit hot path budget)


def test_counter_overhead_config2_budget():
    """Registry on vs off on the live-edit hot path, bounded delta:
    one edit on the config2 path bumps ~10 counters (tick + append +
    apply + frame counters), so the per-add cost must stay micro-scale
    or telemetry would show up in config2_edits_per_s. Pin per-add
    under 2µs (min over trials — the scheduler can't make code FASTER)
    and under 30x a raw dict bump; at the bound, telemetry costs
    <20µs/edit, ~2% of config2's ~1ms/edit."""
    reg = MetricsRegistry()
    c = reg.counter("hot.path")
    d = {"hot.path": 0}
    N = 50_000

    def t_counter():
        add = c.add
        t0 = time.perf_counter()
        for _ in range(N):
            add(1)
        return time.perf_counter() - t0

    def t_dict():
        t0 = time.perf_counter()
        for _ in range(N):
            d["hot.path"] += 1
        return time.perf_counter() - t0

    counter_s = min(t_counter() for _ in range(5))
    dict_s = min(t_dict() for _ in range(5))
    assert counter_s / N < 2e-6, f"{counter_s / N * 1e9:.0f}ns/add"
    assert counter_s < max(dict_s * 30, N * 1e-6), (
        f"counter {counter_s:.4f}s vs dict {dict_s:.4f}s"
    )


def test_counter_contention_bounded():
    """Sharded adds must not serialize: 8 threads hammering ONE
    counter finish in wall time comparable to one thread's work (a
    lock-per-add implementation would blow this bound under the GIL's
    contention pathologies)."""
    reg = MetricsRegistry()
    c = reg.counter("hot.contended")
    T, N = 8, 20_000

    def worker():
        add = c.add
        for _ in range(N):
            add(1)

    ts = [threading.Thread(target=worker) for _ in range(T)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    assert c.value() == T * N
    assert wall < 5.0, f"contended adds took {wall:.2f}s"
