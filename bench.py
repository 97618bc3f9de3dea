"""Benchmark: the cold-start PRODUCT path, disk -> materialized summaries.

Primary metric (BASELINE configs 3/4): a corpus of BENCH_DOCS docs x
BENCH_OPS ops each — real feeds, sidecars, and sqlite rows on disk
(ops/corpus.py, validated byte-equivalent to the interactive write path
in tests/test_corpus.py) — opened with `Repo.open_many` in a FRESH
RepoBackend and materialized to host through `fetch_bulk_summaries()`
(the bulk path's honest barrier: after it, every doc renders host-side
with no further device work). Nothing is pre-packed or pre-warmed: the
timed region includes sqlite cursor/clock loads, sidecar IO, columnar
packing, device transfer, kernel, and the summary fetch.

Two timed passes:
  cold_first_process — first open in this process (XLA compile overlaps
    the untimed corpus setup via ops/warmup.py; with a warm persistent
    compile cache the warmup is itself a no-op)
  steady_state       — second fresh RepoBackend over the same disk state
    (compile cached; OS page cache warm). This is the headline: it is
    what any long-lived deployment pays per cold open.

Also measured (VERDICT r3 item 6):
  config1_change_latency_us — interactive single-op change latency
  config5_union_100k_ms     — 100k-doc ClockStore clock-union on device
  multichip_8_s             — MEASURED multi-chip cold open of the same
    corpus over the mesh scheduler (config_mesh: in-process when >=2
    devices are visible, else a subprocess on an 8-device virtual CPU
    host platform — the same mesh the tier-1 matrix pins bit-identical).
    Retires the old projection formula, which survives only as the
    clearly-labeled `projection_8chip_reference_s` field.

Baseline = the framework's own host incremental OpSet replay of the same
per-doc histories (the reference publishes no numbers, BASELINE.md; the
reference's own cold start is the same work in Node+Immutable.js).

The timed path runs the streaming slab pipeline (backend/pipeline.py,
the product default): per-slab IO, native pack, device dispatch, and
summary fetch overlap, so the wall clock is the reported
`wall_critical_path` (~max(stage)) and the per-stage numbers are BUSY
times (`t_*_busy` aliases). HM_PIPELINE=0 restores the serial twin,
where the same keys are back-to-back wall times.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"configs": {...}}. Env: BENCH_DOCS (default 10240), BENCH_OPS (1024),
BENCH_HOST_DOCS (8), BENCH_DIR (corpus location, default a fresh tmpdir),
BENCH_COLDOPEN_DOCS / BENCH_COLDOPEN_OPS / BENCH_COLDOPEN_WORKERS (the
config_coldopen pack-plane gate: 10x-corpus cold open, serial vs pooled
pack — see _config_coldopen).
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))


def _open_and_materialize(path, urls):
    from hypermerge_tpu.repo import Repo

    t0 = time.perf_counter()
    repo = Repo(path=path)
    handles = repo.open_many(urls)
    summaries = repo.back.fetch_bulk_summaries()
    dt = time.perf_counter() - t0
    n = len(summaries.doc_ids)
    assert n == len(urls), f"only {n}/{len(urls)} docs materialized"
    assert len(handles) == len(urls)
    stats = dict(repo.back.last_bulk_stats)
    # spot-check: summaries carry real content
    probe = summaries.doc(summaries.doc_ids[0])
    assert probe["elems"] > 0 and probe["clock"], probe
    repo.close()
    return dt, stats


_MESH_CHILD = r"""
import json, os, sys, time

# the virtual device count must be in XLA_FLAGS BEFORE any jax backend
# initializes (the parent set JAX_PLATFORMS=cpu and the flag in env)
sys.path.insert(0, sys.argv[1])
tmp = sys.argv[2]
n_pass = int(sys.argv[3])

import jax  # noqa: E402

with open(os.path.join(tmp, "corpus.json")) as fh:
    urls = json.load(fh)["urls"]

from hypermerge_tpu.parallel.mesh import device_topology  # noqa: E402
from hypermerge_tpu.repo import Repo  # noqa: E402

best = None
stats = None
for _ in range(n_pass):
    t0 = time.perf_counter()
    repo = Repo(path=tmp)
    handles = repo.open_many(urls)
    summaries = repo.back.fetch_bulk_summaries()
    dt = time.perf_counter() - t0
    assert len(summaries.doc_ids) == len(urls)
    s = dict(repo.back.last_bulk_stats)
    repo.close()
    if best is None or dt < best:
        best, stats = dt, s
print(json.dumps({
    "multichip_s": round(best, 2),
    "devices": len(jax.devices()),
    "topology": device_topology(),
    "stats": stats,
}), flush=True)
"""


def _config_mesh(tmp, n_passes=2):
    """MEASURED multi-chip cold open of the SAME on-disk corpus the
    primary metric used — the number that retires the 8-chip
    projection. With >=2 devices already visible the open runs
    in-process; a single-device box (a one-chip bench host)
    re-runs it in a subprocess on an 8-device virtual CPU host platform
    (`--xla_force_host_platform_device_count=8` — the same mesh the
    tier-1 test matrix pins bit-identical to the single-device twin).
    Either way the wall clock is a real overlapped run over the mesh
    scheduler (slab streaming + per-chip queues), not a divide-by-N
    formula. Returns (seconds, mode, devices, topology, stats)."""
    import subprocess

    import jax

    from hypermerge_tpu.parallel.mesh import device_topology

    with open(os.path.join(tmp, "corpus.json")) as fh:
        urls = json.load(fh)["urls"]

    def _mesh_slab(n_chips):
        """Slab size that spreads the corpus across every chip:
        docs/chips rounded DOWN to a pow2 (streaming parallelism is
        per-slab — the default 4096 slab would pin a 10k-doc load to
        3 chips). An explicit HM_BULK_SLAB always wins."""
        if os.environ.get("HM_BULK_SLAB"):
            return os.environ["HM_BULK_SLAB"]
        per = max(1, len(urls) // max(1, n_chips))
        return str(max(256, 1 << (per.bit_length() - 1)))

    if len(jax.devices()) >= 2:
        slab_save = os.environ.get("HM_BULK_SLAB")
        os.environ["HM_BULK_SLAB"] = _mesh_slab(len(jax.devices()))
        try:
            best = None
            stats = None
            for _ in range(n_passes):
                dt, s = _open_and_materialize(tmp, urls)
                if best is None or dt < best:
                    best, stats = dt, s
        finally:
            if slab_save is None:
                os.environ.pop("HM_BULK_SLAB", None)
            else:
                os.environ["HM_BULK_SLAB"] = slab_save
        return (
            round(best, 2),
            "in_process",
            len(jax.devices()),
            device_topology(),
            stats,
        )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["HM_BULK_SLAB"] = _mesh_slab(8)
    proc = subprocess.run(
        [
            sys.executable, "-c", _MESH_CHILD,
            str(Path(__file__).parent), tmp, str(n_passes),
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh child failed rc={proc.returncode}: "
            f"{proc.stderr[-800:]}"
        )
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    return (
        out["multichip_s"],
        "subprocess_cpu8",
        out["devices"],
        out["topology"],
        out["stats"],
    )


def _config_lockdebt():
    """The write-plane blocking debt, measured: a durable burst of
    local edits across several docs on a disk-backed repo, run with
    lockdep instrumentation on so the blocking seams (fsync, sqlite
    commit, debouncer waits) charge their wall time to every lock
    class held at entry. Returns the per-lock-class
    `lock.held_blocking_ms.*` deltas (ms) for BOTH durable tiers:

      fsync_group      HM_FSYNC=1 — durability debounced off-thread;
                       the engine-lock entry shows what the emission
                       path itself blocks on
      fsync_per_append HM_FSYNC=2 — the inline-durability worst case:
                       every acked append fsyncs under the emission
                       lock

    The `live_engine` entry IS the ROADMAP write-plane gate as a
    number: feed-append / clock-commit time spent under the ONE
    engine lock — the per-doc emission-domain split is gated on the
    tier-1 figure reading zero and judged against the tier-2 figure
    it must dissolve into per-doc domains."""
    import tempfile as _tempfile

    from hypermerge_tpu import telemetry
    from hypermerge_tpu.analysis import lockdep
    from hypermerge_tpu.repo import Repo

    prefix = "lock.held_blocking_ms."

    def snap():
        return {
            k[len(prefix):]: v
            for k, v in telemetry.snapshot().items()
            if k.startswith(prefix)
        }

    def burst(tier: str):
        os.environ["HM_FSYNC"] = tier
        tmp = _tempfile.mkdtemp(prefix="hm-lockdebt-")
        try:
            before = snap()
            repo = Repo(path=os.path.join(tmp, "repo"))
            try:
                urls = [repo.create({"n": 0}) for _ in range(8)]
                for i in range(40):
                    for url in urls:
                        repo.change(
                            url, lambda d: d.__setitem__("n", i)
                        )
                back = repo.back
                if back.live is not None:
                    back.live.flush_now()
                back._stores.flush_now()
                back.durability.flush_now()
            finally:
                repo.close()
            after = snap()
            debt = {
                k: round(after.get(k, 0.0) - before.get(k, 0.0), 3)
                for k in after
                if after.get(k, 0.0) - before.get(k, 0.0) > 0
            }
            # the gate reads zero only when the key exists to read
            debt.setdefault("live_engine", 0.0)
            return debt
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    was = lockdep.enabled()
    env_fsync = os.environ.get("HM_FSYNC")
    lockdep.enable(True)  # fresh repos below get instrumented locks
    try:
        return {
            "fsync_group": burst("1"),
            "fsync_per_append": burst("2"),
        }
    finally:
        lockdep.enable(was)
        if env_fsync is None:
            os.environ.pop("HM_FSYNC", None)
        else:
            os.environ["HM_FSYNC"] = env_fsync


_WRITER_CHILD = r"""
import json, sys, threading, time

sock, n_edits = sys.argv[1], int(sys.argv[2])

from hypermerge_tpu.net.ipc import connect_frontend

front, close = connect_frontend(sock)
url = front.create({"n": 0})
h = front.open(url)
h.value(timeout=60)

latest = [0]
done = threading.Event()
goal = [None]

def on_state(_state, index):
    if index > latest[0]:
        latest[0] = index
    if goal[0] is not None and latest[0] >= goal[0]:
        done.set()

h.subscribe(on_state)
print("ready", flush=True)
sys.stdin.readline()  # the coordinator's "go"

# each change round-trips: the frontend keeps ONE request in flight
# and the backend's LocalPatch echo (with the bumped history index)
# releases the next — so `n_edits` acked edits means the history
# index advances by n_edits over the ready base
base = latest[0]
goal[0] = base + n_edits
t0 = time.perf_counter()
for i in range(n_edits):
    front.change(url, lambda d, _i=i: d.__setitem__("n", _i))
ok = done.wait(timeout=120)
dt = time.perf_counter() - t0
print(json.dumps({"edits": n_edits, "secs": dt, "acked": ok}), flush=True)
close()
"""


_HOTDOC_CHILD = r"""
import hashlib, json, sys, time

sock, url = sys.argv[1], sys.argv[2]
idx, n_edits, n_writers = (
    int(sys.argv[3]), int(sys.argv[4]), int(sys.argv[5])
)

from hypermerge_tpu.net.ipc import connect_frontend

front, close = connect_frontend(sock)
h = front.open(url)

def val(timeout=0.2):
    try:
        return h.value(timeout=timeout)
    except TimeoutError:
        return None

deadline = time.time() + 60
while time.time() < deadline:
    v = val()
    if v is not None and "edits" in v:
        break
    time.sleep(0.02)
else:
    raise SystemExit("shared doc never materialized")

print("ready", flush=True)
sys.stdin.readline()  # the coordinator's "go"

# ack-paced on ONE shared doc: every writer holds its own actor (the
# hub's many-writer plane), writes its own keys, and releases the next
# edit only when the previous one's patch echo landed
t0 = time.perf_counter()
for i in range(n_edits):
    key = "%d.%d" % (idx, i)
    front.change(
        url, lambda d, _k=key, _i=i: d["edits"].__setitem__(_k, _i)
    )
    deadline = time.time() + 120
    while time.time() < deadline:
        v = val()
        if v is not None and key in v["edits"]:
            break
        time.sleep(0.001)
own_secs = time.perf_counter() - t0

# convergence barrier: every writer's view must reach ALL writers'
# edits, then hash the canonical JSON — the coordinator asserts the 8
# digests are BIT-identical
want = n_writers * n_edits
deadline = time.time() + 180
v = None
while time.time() < deadline:
    v = val()
    if v is not None and len(v.get("edits", {})) >= want:
        break
    time.sleep(0.02)
blob = json.dumps(v, sort_keys=True, separators=(",", ":"))
print(
    json.dumps({
        "edits": n_edits,
        "secs": own_secs,
        "acked": v is not None and len(v.get("edits", {})) >= want,
        "digest": hashlib.sha256(blob.encode("utf-8")).hexdigest(),
    }),
    flush=True,
)
close()
"""


def _writer_daemon_env(workers="0"):
    """The config_writers daemon environment: durable acks over the
    group-commit WAL in throughput posture (HM_WAL_MS=30 gather: the
    window, not this container's nearly-free fsync, is the amortized
    unit — so writer-count scaling measures group commit, not the CI
    box's single-core ceiling). `workers` picks the sharded write
    plane (HM_WORKERS worker processes); both knobs yield to the
    caller's env, so a multicore TPU host can run the scaling sweep
    sharded (HM_WORKERS=4) or at interactive latency (HM_WAL_MS=3)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["HM_FSYNC"] = "1"
    env["HM_ACK_DURABLE"] = "1"
    env.setdefault("HM_WAL_MS", "30")
    env.setdefault("HM_WORKERS", workers)
    env["PYTHONPATH"] = str(Path(__file__).parent)
    return env


def _config_writers(n_edits=200, counts=(1, 8, 32)):
    """The many-writer write plane, measured end to end: N frontend
    PROCESSES, each editing its own doc over IPC against ONE hub-mode
    daemon (net/ipc.py --hub) on a disk-backed repo at HM_FSYNC=1 with
    DURABLE acks (HM_ACK_DURABLE=1: every LocalPatch echo waits for
    the WAL group commit covering its append, HM_WAL_MS=3 gather).
    Every writer's edit loop is ack-paced (one request in flight; the
    durable echo releases the next), so a single writer pays the full
    {emission + commit window + fsync} per edit, and aggregate edits/s
    scales with writer count only if (a) disjoint docs' {patch -> feed
    append -> push} pipelines really run concurrently (the per-doc
    emission domains, backend/emission.py — the old engine-lock plane
    serialized them) and (b) concurrent committers share the leader's
    ONE journal fsync per window (storage/wal.py group commit — the
    old group flush was O(dirty feeds)). The daemon runs in-process
    (HM_WORKERS=0) by default so the single-core CI box measures the
    write plane, not the worker-hop IPC tax; export HM_WORKERS=N to
    run the sweep through the sharded plane on a multicore host.
    Returns per-count aggregate durable edits/s, the 1 -> max
    scaling factor (the ROADMAP gate: >= 3x at 8), and the 8 -> 32
    factor (group-commit gate: >= 2.5x — the shared gather window
    must keep amortizing as the herd quadruples)."""
    import tempfile as _tempfile

    results = {}
    per_writer = {}
    for n_writers in counts:
        tmp = _tempfile.mkdtemp(prefix="hm-writers-")
        sock = os.path.join(tmp, "daemon.sock")
        env = _writer_daemon_env()
        daemon = subprocess.Popen(
            [
                sys.executable, "-m", "hypermerge_tpu.net.ipc",
                os.path.join(tmp, "repo"), sock, "--hub",
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
            text=True,
        )
        writers = []
        try:
            line = daemon.stdout.readline()
            if "ready" not in line:
                raise RuntimeError(f"daemon failed to start: {line!r}")
            writers = [
                subprocess.Popen(
                    [sys.executable, "-c", _WRITER_CHILD, sock,
                     str(n_edits)],
                    env=env,
                    stdin=subprocess.PIPE,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
                for _ in range(n_writers)
            ]
            for w in writers:
                if w.stdout.readline().strip() != "ready":
                    raise RuntimeError(
                        f"writer failed: {w.stderr.read()[-500:]}"
                    )
            for w in writers:  # all docs open: release the herd
                w.stdin.write("go\n")
                w.stdin.flush()
            outs = [json.loads(w.stdout.readline()) for w in writers]
            if not all(o["acked"] for o in outs):
                raise RuntimeError("writer timed out waiting for acks")
            wall = max(o["secs"] for o in outs)
            results[n_writers] = round(n_writers * n_edits / wall, 1)
            per_writer[n_writers] = [round(o["secs"], 3) for o in outs]
        finally:
            for w in writers:
                w.kill()
            daemon.terminate()
            try:
                daemon.wait(timeout=10)
            except subprocess.TimeoutExpired:
                daemon.kill()
            shutil.rmtree(tmp, ignore_errors=True)
    lo, hi = min(counts), max(counts)
    out = {
        "edits_per_s": results,
        "scaling": round(results[hi] / max(results[lo], 1e-9), 2),
        "writer_secs": per_writer,
        "n_edits": n_edits,
    }
    if 8 in results and 32 in results:
        # the group-commit gate: the shared gather window must keep
        # amortizing the journal flush as the herd quadruples
        out["scaling_8_32"] = round(
            results[32] / max(results[8], 1e-9), 2
        )
    return out


def _config_writers_hotdoc(n_edits=60, n_writers=8):
    """The many-writer HOT-DOC plane: 8 frontend PROCESSES all editing
    ONE shared doc against one hub daemon (each connection holds its
    OWN actor — the hub tags Create/Open/NeedsActorId with the
    connection key and the backend mints per-connection actors), ack-
    paced, durable acks. Unlike the scaling sweep this one runs the
    SHARDED write plane (HM_WORKERS=2): the gate here is semantic —
    every tagged Ready, per-connection actor grant, and cross-writer
    patch must survive the hub -> worker hop — so the bench exercises
    it end to end. Returns aggregate durable edits/s plus the
    convergence verdict: after the herd drains, every writer hashes
    its canonical JSON view and all digests must be BIT-identical."""
    import tempfile as _tempfile

    tmp = _tempfile.mkdtemp(prefix="hm-hotdoc-")
    sock = os.path.join(tmp, "daemon.sock")
    env = _writer_daemon_env(workers="2")
    daemon = subprocess.Popen(
        [
            sys.executable, "-m", "hypermerge_tpu.net.ipc",
            os.path.join(tmp, "repo"), sock, "--hub",
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
    )
    writers = []
    close = None
    try:
        line = daemon.stdout.readline()
        if "ready" not in line:
            raise RuntimeError(f"daemon failed to start: {line!r}")
        from hypermerge_tpu.net.ipc import connect_frontend

        front, close = connect_frontend(sock)
        url = front.create({"edits": {}})
        # a round-trip on the same ordered channel proves the daemon
        # registered the doc before any child tries to open it
        got = []
        front.materialize(url, 1, got.append)
        deadline = time.time() + 60
        while not got and time.time() < deadline:
            time.sleep(0.02)
        if not got:
            raise RuntimeError("daemon never acked the shared doc")
        writers = [
            subprocess.Popen(
                [sys.executable, "-c", _HOTDOC_CHILD, sock, url,
                 str(idx), str(n_edits), str(n_writers)],
                env=env,
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
            for idx in range(n_writers)
        ]
        for w in writers:
            if w.stdout.readline().strip() != "ready":
                raise RuntimeError(
                    f"hotdoc writer failed: {w.stderr.read()[-500:]}"
                )
        for w in writers:  # all views materialized: release the herd
            w.stdin.write("go\n")
            w.stdin.flush()
        outs = [json.loads(w.stdout.readline()) for w in writers]
        if not all(o["acked"] for o in outs):
            raise RuntimeError("hotdoc writer never converged")
        digests = {o["digest"] for o in outs}
        if len(digests) != 1:
            raise RuntimeError(
                f"hotdoc views DIVERGED: {sorted(digests)}"
            )
        wall = max(o["secs"] for o in outs)
        return {
            "edits_per_s": round(n_writers * n_edits / wall, 1),
            "converged": True,
            "digest": next(iter(digests)),
            "n_writers": n_writers,
            "n_edits": n_edits,
        }
    finally:
        if close is not None:
            close()
        for w in writers:
            w.kill()
        daemon.terminate()
        try:
            daemon.wait(timeout=10)
        except subprocess.TimeoutExpired:
            daemon.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def _config1_change_latency():
    """Interactive path: µs per single-op change on a live doc."""
    from hypermerge_tpu.repo import Repo

    repo = Repo(memory=True)
    url = repo.create({"n": 0})
    ts = []
    for i in range(300):
        t0 = time.perf_counter()
        repo.change(url, lambda d: d.__setitem__("n", i))
        ts.append(time.perf_counter() - t0)
    repo.close()
    ts.sort()
    return ts[len(ts) // 2] * 1e6  # median µs


def _config2_convergence(n_docs=10, n_edits=50):
    """BASELINE config 2: two repos, concurrent edits on shared docs,
    wall-clock to full convergence over encrypted TCP on localhost."""
    import time as _t

    from hypermerge_tpu.net.tcp import TcpSwarm
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.utils.ids import validate_doc_url

    ra, rb = Repo(memory=True), Repo(memory=True)
    sa, sb = TcpSwarm(), TcpSwarm()
    try:
        return _config2_run(ra, rb, sa, sb, n_docs, n_edits)
    finally:
        # fail-soft callers keep the process alive: never leak live
        # repos/sockets into the remaining configs
        ra.close()
        rb.close()
        sa.destroy()
        sb.destroy()


def _live_stats(*repos):
    """Aggregated live-apply engine stats across repos (zeros when the
    engine is off): ticks, docs/tick, coalesced changes, t_live_*."""
    out = {}
    for r in repos:
        eng = getattr(r.back, "live", None)
        if eng is None:
            continue
        for k, v in eng.stats.items():
            out[k] = round(out.get(k, 0) + v, 6)
    if out.get("ticks"):
        out["docs_per_tick"] = round(out["tick_docs"] / out["ticks"], 2)
        out["changes_per_tick"] = round(
            out["tick_changes"] / out["ticks"], 2
        )
    return out


def _config2_run(ra, rb, sa, sb, n_docs, n_edits):
    import time as _t

    from hypermerge_tpu.utils.ids import validate_doc_url

    ra.set_swarm(sa)
    rb.set_swarm(sb)
    sb.connect(sa.address)
    urls = [ra.create({"edits": []}) for _ in range(n_docs)]
    handles = [rb.open(u) for u in urls]
    ids = [validate_doc_url(u) for u in urls]

    t0 = _t.perf_counter()
    for i in range(n_edits):
        for u in urls:
            ra.change(u, lambda d, i=i: d["edits"].append(i))
        if i % 5 == 0:
            for h in handles:
                h.change(lambda d, i=i: d["edits"].append(1000 + i))
    # converged: every doc on B holds both sides' edits
    want = n_edits + (n_edits + 4) // 5
    deadline = _t.perf_counter() + 120
    while _t.perf_counter() < deadline:
        vals = [h.value() for h in handles]
        if all(
            v is not None and len(v.get("edits", [])) >= want
            for v in vals
        ):
            break
        _t.sleep(0.01)
    else:
        raise AssertionError("config2 did not converge")
    # and A sees B's edits too
    deadline = _t.perf_counter() + 120
    while _t.perf_counter() < deadline:
        if all(
            len(ra.doc(u).get("edits", [])) >= want for u in urls
        ):
            break
        _t.sleep(0.01)
    else:
        raise AssertionError("config2: A never saw B's edits")
    dt = _t.perf_counter() - t0
    total_edits = n_docs * want
    return dt, total_edits / dt, _live_stats(ra, rb)


def _config_churn(n_docs=6, n_edits=40):
    """BASELINE round-10 robustness config: burst edits on shared docs
    over TCP while a seeded FaultPlan (net/faults.py) kills the link
    mid-burst — twice — and the supervised redial (net/resilience.py)
    restores replication with NO manual reconnect. Reports convergence
    wall clock plus the churn counters: supervisor reconnects,
    replication resyncs + t_resync_ms, injected frame drops."""
    import time as _t

    from hypermerge_tpu.net.faults import FaultPlan, FaultSwarm
    from hypermerge_tpu.net.tcp import TcpSwarm
    from hypermerge_tpu.repo import Repo

    env_save = {
        k: os.environ.get(k)
        for k in ("HM_REDIAL_BASE_MS", "HM_REDIAL_MAX_S")
    }
    # everything after the env writes sits inside the try: a
    # constructor failure must not leak the redial overrides (or live
    # repos/sockets) into the remaining fail-soft bench configs
    ra = rb = sa = fb = None
    try:
        os.environ["HM_REDIAL_BASE_MS"] = "50"
        os.environ["HM_REDIAL_MAX_S"] = "1"
        plan = FaultPlan(
            seed=10,
            events=[(1, "kill"), (2, "heal"), (3, "kill"), (4, "heal")],
        )
        ra, rb = Repo(memory=True), Repo(memory=True)
        sa, sbi = TcpSwarm(), TcpSwarm()
        fb = FaultSwarm(sbi, plan)
        ra.set_swarm(sa)
        rb.set_swarm(fb)
        fb.connect(sa.address)
        urls = [ra.create({"edits": []}) for _ in range(n_docs)]
        handles = [rb.open(u) for u in urls]
        for h in handles:
            assert h.value(timeout=30) is not None

        t0 = _t.perf_counter()
        quarter = max(1, n_edits // 4)
        for i in range(n_edits):
            for u in urls:
                ra.change(u, lambda d, i=i: d["edits"].append(i))
            if i % 5 == 0:
                for h in handles:
                    h.change(lambda d, i=i: d["edits"].append(1000 + i))
            if i % quarter == quarter - 1:
                fb.tick()  # kill/heal schedule fires mid-burst
        while plan.tick < 4:
            fb.tick()  # link healed for the convergence wait
        want = n_edits + (n_edits + 4) // 5
        deadline = _t.perf_counter() + 120
        while _t.perf_counter() < deadline:
            vals = [h.value() for h in handles]
            if all(
                v is not None and len(v.get("edits", [])) >= want
                for v in vals
            ) and all(
                len(ra.doc(u).get("edits", [])) >= want for u in urls
            ):
                break
            _t.sleep(0.01)
        else:
            raise AssertionError("config_churn did not converge")
        dt = _t.perf_counter() - t0
        ra_stats = ra.back.network.replication.stats
        rb_stats = rb.back.network.replication.stats
        counters = {
            "reconnects": sbi.supervisor.stats["reconnects"],
            "resyncs": round(
                ra_stats["resyncs"] + rb_stats["resyncs"]
            ),
            "t_resync_ms": round(
                ra_stats["t_resync_ms"] + rb_stats["t_resync_ms"], 1
            ),
            "frames_dropped_injected": fb.stats[
                "frames_dropped_injected"
            ],
        }
        assert counters["reconnects"] >= 1, counters
        return dt, n_docs * want / dt, counters
    finally:
        for r in (ra, rb):
            if r is not None:
                r.close()
        for s in (fb, sa):
            if s is not None:
                s.destroy()
        for k, v in env_save.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _config_swarm(n_peers=None, n_edits=24):
    """BASELINE round-19 fleet config: N in-process daemons joined
    ONLY through the DHT (net/discovery/ — no explicit connect()
    anywhere), a subset killed and healed by a seeded FaultPlan
    mid-burst, bounded gossip fanout active. Measures the wall from
    first edit to every surviving peer holding the creator's doc
    BIT-IDENTICAL, mean DHT lookup hops, and per-peer frame
    amplification (replication frames sent per edit per peer) — the
    number HM_GOSSIP_FANOUT must bound regardless of peer count."""
    import time as _t

    from hypermerge_tpu import telemetry as _tele
    from hypermerge_tpu.net.discovery import DhtNode, DhtSwarm
    from hypermerge_tpu.net.faults import FaultPlan, FaultSwarm
    from hypermerge_tpu.repo import Repo

    if n_peers is None:
        n_peers = int(os.environ.get("BENCH_SWARM_PEERS", "16"))
    fanout = 4
    env_save = {
        k: os.environ.get(k)
        for k in (
            "HM_REDIAL_BASE_MS", "HM_REDIAL_MAX_S", "HM_DHT_ANNOUNCE_S",
            "HM_DHT_LOOKUP_S", "HM_GOSSIP_FANOUT",
            "HM_GOSSIP_RESHUFFLE_S", "HM_NET_PING_S",
        )
    }
    boot = None
    repos, swarms, faulted = [], [], []
    try:
        os.environ["HM_REDIAL_BASE_MS"] = "50"
        os.environ["HM_REDIAL_MAX_S"] = "1"
        os.environ["HM_DHT_ANNOUNCE_S"] = "0.5"
        os.environ["HM_DHT_LOOKUP_S"] = "0.5"
        os.environ["HM_GOSSIP_FANOUT"] = str(fanout)
        os.environ["HM_GOSSIP_RESHUFFLE_S"] = "0.5"
        os.environ["HM_NET_PING_S"] = "0"  # N^2 keepalive threads off
        boot = DhtNode()
        # ~1/5 of the fleet churns: seeded kill mid-burst, heal after
        n_churn = max(1, n_peers // 5)
        for i in range(n_peers):
            r = Repo(memory=True)
            sw = DhtSwarm(bootstrap=[boot.address])
            if 0 < i <= n_churn:  # never the creator
                plan = FaultPlan(
                    seed=19 + i, events=[(1, "kill"), (2, "heal")]
                )
                sw = FaultSwarm(sw, plan)
                faulted.append(sw)
            r.set_swarm(sw)
            repos.append(r)
            swarms.append(sw)
        url = repos[0].create({"edits": []})
        handles = [r.open(url) for r in repos[1:]]
        for h in handles:
            # pure-DHT discovery: announce/lookup walks find the
            # creator (and each other) with no addresses exchanged
            assert h.value(timeout=120) is not None
        frames0 = [
            r.back.network.replication.stats["frames_tx"] for r in repos
        ]
        snap0 = _tele.snapshot()
        t0 = _t.perf_counter()
        third = max(1, n_edits // 3)
        for i in range(n_edits):
            repos[0].change(url, lambda d, i=i: d["edits"].append(i))
            if i == third:
                for fs in faulted:
                    fs.tick()  # kill fires: churned peers drop
            if i == 2 * third:
                for fs in faulted:
                    fs.tick()  # heal: supervised redial + resync
        for fs in faulted:
            while fs.plan.tick < 2:
                fs.tick()
        deadline = _t.perf_counter() + 180
        want = list(range(n_edits))
        while _t.perf_counter() < deadline:
            vals = [h.value() for h in handles]
            if all(
                v is not None and v.get("edits") == want for v in vals
            ):
                break
            _t.sleep(0.02)
        else:
            raise AssertionError("config_swarm did not converge")
        dt = _t.perf_counter() - t0
        # acked state must be BIT-identical across every peer
        blobs = {
            json.dumps(h.value(), sort_keys=True) for h in handles
        }
        blobs.add(json.dumps(repos[0].doc(url), sort_keys=True))
        assert len(blobs) == 1, "diverged doc state across peers"
        frames = [
            r.back.network.replication.stats["frames_tx"] - f0
            for r, f0 in zip(repos, frames0)
        ]
        amp = [f / n_edits for f in frames]
        snap1 = _tele.snapshot()
        lookups = snap1.get("dht.lookups", 0) - snap0.get(
            "dht.lookups", 0
        )
        hops = snap1.get("dht.lookup_hops", 0) - snap0.get(
            "dht.lookup_hops", 0
        )
        counters = {
            "peers": n_peers,
            "churned": len(faulted),
            "fanout": fanout,
            "frame_amp_max": round(max(amp), 1),
            "frame_amp_mean": round(sum(amp) / len(amp), 1),
            "lookup_hops_mean": round(hops / max(lookups, 1), 2),
            "reconnects": sum(
                sup.stats["reconnects"]
                for sup in (
                    getattr(sw, "supervisor", None) for sw in swarms
                )
                if sup is not None
            ),
        }
        # the fleet claim: per-peer frames stay O(fanout), not O(peers)
        # (generous slack for relay hops + announce/length frames)
        assert counters["frame_amp_max"] <= 4 * fanout + 8, counters
        return dt, counters
    finally:
        for r in repos:
            try:
                r.close()
            except Exception:
                pass
        for sw in swarms:
            try:
                sw.destroy()
            except Exception:
                pass
        if boot is not None:
            boot.close()
        for k, v in env_save.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _config_fleet1000():
    """THIS round's scaling config: does the per-peer steady-state
    bill stay flat from 100 to 1000 peers? Two parts:

    1. A REAL mini-fleet on the async transport (HM_NET_ASYNC=1,
       HM_CURSOR_DELTA=1): measures threads per daemon (the selector
       loop must not spend a thread per connection), real cold-join
       walls, and the live delta/suppressed cursor split.
    2. A deterministic SEEDED simulation of the steady-state gossip
       period at N=100 and N=1000 using the production GossipSampler
       + the delta-cursor ledger rule (send only entries the target
       has not acked; all-caught-up suppresses the frame; max-wins
       merge). frames/peer/period must stay flat within 2x across the
       10x fleet — O(fanout), not O(peers). Cold-join p99 at N=1000 is
       extrapolated from the real samples by the Kademlia hop ratio
       log(1000)/log(n_real) (labelled simulated in BASELINE.md)."""
    import math
    import random as _rnd
    import threading as _th
    import time as _t

    from hypermerge_tpu import telemetry as _tele
    from hypermerge_tpu.net.aio import get_loop
    from hypermerge_tpu.net.discovery import (
        DhtNode, DhtSwarm, GossipSampler,
    )
    from hypermerge_tpu.repo import Repo

    t_start = _t.perf_counter()
    fanout = 4
    n_real = int(os.environ.get("BENCH_FLEET_REAL_PEERS", "12"))
    env_save = {
        k: os.environ.get(k)
        for k in (
            "HM_NET_ASYNC", "HM_CURSOR_DELTA", "HM_REDIAL_BASE_MS",
            "HM_REDIAL_MAX_S", "HM_DHT_ANNOUNCE_S", "HM_DHT_LOOKUP_S",
            "HM_GOSSIP_FANOUT", "HM_GOSSIP_RESHUFFLE_S", "HM_NET_PING_S",
        )
    }
    boot = None
    repos, swarms = [], []
    try:
        os.environ["HM_NET_ASYNC"] = "1"
        os.environ["HM_CURSOR_DELTA"] = "1"
        os.environ["HM_REDIAL_BASE_MS"] = "50"
        os.environ["HM_REDIAL_MAX_S"] = "1"
        os.environ["HM_DHT_ANNOUNCE_S"] = "0.5"
        os.environ["HM_DHT_LOOKUP_S"] = "0.5"
        os.environ["HM_GOSSIP_FANOUT"] = str(fanout)
        os.environ["HM_GOSSIP_RESHUFFLE_S"] = "0.5"
        os.environ["HM_NET_PING_S"] = "0"
        # the loop singleton and its dispatch pool are process-wide
        # infra: create them BEFORE the census so the count charges
        # per-daemon cost only
        get_loop()
        boot = DhtNode()
        snap0 = _tele.snapshot()
        threads0 = _th.active_count()
        for _i in range(n_real):
            r = Repo(memory=True)
            sw = DhtSwarm(bootstrap=[boot.address])
            r.set_swarm(sw)
            repos.append(r)
            swarms.append(sw)
        url = repos[0].create({"edits": []})
        t_open = _t.perf_counter()
        handles = [r.open(url) for r in repos[1:]]
        join_s = [None] * len(handles)
        deadline = _t.perf_counter() + 120
        while any(j is None for j in join_s):
            assert _t.perf_counter() < deadline, "cold joins stalled"
            for i, h in enumerate(handles):
                if join_s[i] is not None:
                    continue
                try:
                    if h.value(timeout=0.01) is not None:
                        join_s[i] = _t.perf_counter() - t_open
                except TimeoutError:
                    pass
            _t.sleep(0.02)
        # a short steady-state burst so the cursor split has signal
        for i in range(24):
            repos[0].change(url, lambda d, i=i: d["edits"].append(i))
        want = list(range(24))
        deadline = _t.perf_counter() + 60
        while _t.perf_counter() < deadline:
            if all(
                (h.value() or {}).get("edits") == want for h in handles
            ):
                break
            _t.sleep(0.02)
        else:
            raise AssertionError("config_fleet1000 burst did not converge")
        threads_per_daemon = (_th.active_count() - threads0) / n_real
        snap1 = _tele.snapshot()

        def _grew(name):
            return snap1.get(name, 0) - snap0.get(name, 0)

        aio_conns = snap1.get("net.aio.conns", 0)
        delta_tx = _grew("net.cursor.delta_tx")
        suppressed = _grew("net.cursor.suppressed")
        full_tx = _grew("net.cursor.full_tx")
    finally:
        for r in repos:
            try:
                r.close()
            except Exception:
                pass
        for sw in swarms:
            try:
                sw.destroy()
            except Exception:
                pass
        if boot is not None:
            boot.close()
        for k, v in env_save.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # -- part 2: seeded steady-state period model, N=100 vs N=1000 ----
    class _P:
        __slots__ = ("id",)

        def __init__(self, i):
            self.id = f"p{i:04d}"

    def frames_per_peer_period(n, periods=24):
        peers = [_P(i) for i in range(n)]
        others = [peers[:i] + peers[i + 1:] for i in range(n)]
        # reshuffle every round: the production sampler reshuffles its
        # subset every HM_GOSSIP_RESHUFFLE_S — a frozen subset strands
        # any peer outside the writer's reach (exactly what the real
        # anti-entropy sweep + reshuffle exist to repair)
        samplers = [
            GossipSampler(fanout=fanout, reshuffle_s=0.0, seed=1000 + i)
            for i in range(n)
        ]
        clocks = [{} for _ in range(n)]  # actor -> seq (max-wins)
        ledgers = [{} for _ in range(n)]  # target -> {actor: seq} sent
        frames = 0
        counted_from = periods // 2  # let the relay pipeline fill

        def _round(p, count):
            nonlocal frames
            sends = []
            for i in range(n):
                for tgt in samplers[i].sample("doc", others[i]):
                    j = int(tgt.id[1:])
                    sent = ledgers[i].setdefault(j, {})
                    delta = {
                        a: s for a, s in clocks[i].items()
                        if sent.get(a, -1) < s
                    }
                    if not delta:
                        continue  # all caught up: frame suppressed
                    sent.update(delta)
                    sends.append((j, delta))
                    if count:
                        frames += 1
            for j, delta in sends:  # synchronous round: apply after
                for a, s in delta.items():
                    if clocks[j].get(a, -1) < s:
                        clocks[j][a] = s

        for p in range(periods):
            clocks[0]["w"] = p + 1  # one edit per period at the writer
            _round(p, p >= counted_from)
        # drain: no new edits — the fleet must converge BIT-identically
        # (every peer holds the writer's exact clock) within the relay
        # diameter, or the delta ledger dropped an entry somewhere
        for _ in range(30):
            if all(c == clocks[0] for c in clocks):
                break
            _round(periods, False)
        else:
            raise AssertionError(
                f"simulated {n}-peer fleet never converged"
            )
        fpp = frames / (n * (periods - counted_from))
        # one edit per period, so frames/peer/period IS the per-edit
        # frame amplification: the soak's O(fanout) gate must hold at
        # simulated 1000-peer scale too
        assert fpp <= 4 * fanout + 8, fpp
        return fpp

    f100 = frames_per_peer_period(100)
    f1000 = frames_per_peer_period(1000)

    # -- cold-join p99 at N=1000: real samples scaled by hop ratio ----
    rnd = _rnd.Random(1000)
    hop_scale = math.log(1000) / math.log(max(n_real, 2))
    sims = sorted(
        rnd.choice(join_s) * hop_scale * rnd.uniform(0.8, 1.25)
        for _ in range(1000)
    )
    coldjoin_p99 = sims[int(len(sims) * 0.99)]

    out = {
        "real_peers": n_real,
        "threads_per_daemon": round(threads_per_daemon, 2),
        "aio_conns": aio_conns,
        "cursor_full_tx": full_tx,
        "cursor_delta_tx": delta_tx,
        "cursor_suppressed": suppressed,
        "frames_per_peer_period_100": round(f100, 3),
        "frames_per_peer_period_1000": round(f1000, 3),
        "frames_flat_ratio": round(f1000 / max(f100, 1e-9), 2),
        "coldjoin_p99_s": round(coldjoin_p99, 2),
    }
    # the scaling claims: 10x the fleet must not move the per-peer
    # steady-state bill (within 2x), and steady state must run on
    # delta/suppressed frames, not full cursor maps
    assert out["frames_flat_ratio"] <= 2.0, out
    assert delta_tx + suppressed > 0, out
    return round(_t.perf_counter() - t_start, 2), out


_CRASH_CHILD = r"""
import os, sys
sys.path.insert(0, sys.argv[2])
from hypermerge_tpu.repo import Repo

repo = Repo(path=sys.argv[1])
url = repo.create({"edits": []})
print("URL", url, flush=True)
i = 0
while True:
    repo.change(url, lambda d, i=i: d["edits"].append(i))
    if repo.back.live is not None:
        repo.back.live.flush_now()
    repo.back.durability.flush_now()
    print("ACK", i, flush=True)  # durable under HM_FSYNC>=1
    i += 1
"""


def _config_crash(n_acked=150):
    """BASELINE round-11 robustness config: `kill -9` a writer daemon
    mid-burst and measure the reopen+recovery path. A child process
    appends edits to a disk repo under HM_FSYNC=1 (group fsync),
    acking each edit only after the durability flusher settles; the
    parent SIGKILLs it mid-burst, reopens the repo (crash recovery
    runs on open), and verifies the recovered doc holds a gapless
    prefix covering every acked edit. Reports `t_recover_ms` (reopen ->
    doc readable), `blocks_truncated`/`scrub_repairs` from the
    recovery report, and the acked-edit loss bound (must be 0)."""
    import signal
    import subprocess
    import tempfile as _tf
    import time as _t

    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.storage.scrub import last_report

    tmp = _tf.mkdtemp(prefix="hm_crash")
    env = dict(os.environ)
    env["HM_FSYNC"] = "1"
    env.setdefault("JAX_PLATFORMS", "cpu")  # the child never dispatches
    proc = subprocess.Popen(
        [sys.executable, "-c", _CRASH_CHILD, tmp, str(Path(__file__).parent)],
        stdout=subprocess.PIPE,
        env=env,
        text=True,
    )
    url = None
    acked = -1
    try:
        for line in proc.stdout:
            parts = line.split()
            if parts and parts[0] == "URL":
                url = parts[1]
            elif parts and parts[0] == "ACK":
                acked = int(parts[1])
                if acked + 1 >= n_acked:
                    break
        # mid-burst hard kill: no atexit, no close(), no final flush
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        assert url is not None and acked >= 0, (url, acked)

        t0 = _t.perf_counter()
        repo = Repo(path=tmp)
        try:
            report = repo.back.recovery_report or {}
            h = repo.open(url)
            v = h.value(timeout=60)
            t_recover_ms = (_t.perf_counter() - t0) * 1e3
            edits = v.get("edits", [])
            # gapless prefix, nothing acked lost
            assert list(edits) == list(range(len(edits))), edits[:20]
            assert len(edits) >= acked + 1, (len(edits), acked)
            from hypermerge_tpu.storage import scrub as scrub_mod

            # item-count repairs from the scrub report's own counter
            # list (no hand-copied drift), byte totals kept separate
            byte_keys = ("bytes_truncated", "sig_fragment_bytes")
            counters = {
                "acked": acked + 1,
                "recovered_edits": len(edits),
                "acked_lost": max(0, acked + 1 - len(edits)),
                # whole acked blocks dropped: writable feeds never
                # lose blocks in recovery (the loss bound), so this
                # is expected 0 — it is the invariant, not dead code
                "blocks_truncated": report.get(
                    "tail_blocks_dropped", 0
                ),
                "bytes_truncated": report.get("bytes_truncated", 0),
                "scrub_repairs": sum(
                    report.get(k, 0)
                    for k in scrub_mod._COUNTERS
                    if k != "feeds" and k not in byte_keys
                ),
                "recovery_ran": 1 if repo.back.recovery_report else 0,
            }
            assert counters["recovery_ran"] == 1, counters
            assert last_report(tmp) is not None
            return t_recover_ms, counters
        finally:
            repo.close()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        shutil.rmtree(tmp, ignore_errors=True)


def _config6_live_burst(n_ops=8192, n_burst=256):
    """Live-apply on ONE hot text-trace doc (the single-doc shape of
    config6, on the LIVE path): a stored n_ops-op doc opens lazily,
    then a remote burst of n_burst single-op edits applies through the
    per-tick engine. Reports first-edit latency (the cliff BENCH_r05
    measured as a full host replay), burst edits/s, and the engine's
    per-stage tick budget. HM_LIVE=0 turns this into a measurement of
    the host replay cliff itself."""
    import tempfile as _tf
    import time as _t

    from hypermerge_tpu.crdt.frontend_state import FrontendDoc
    from hypermerge_tpu.crdt.opset import OpSet
    from hypermerge_tpu.repo import Repo

    tmp = _tf.mkdtemp(prefix="hm_live6")
    try:
        repo = Repo(path=tmp)
        url = repo.create({"t": ""})
        # seed the stored trace in chunked changes (setup, untimed)
        from hypermerge_tpu.models import Text

        repo.change(url, lambda d: d.__setitem__("t", Text("seed")))
        chunk = 64
        for base in range(0, n_ops, chunk):
            repo.change(
                url,
                lambda d, base=base: d["t"].insert(
                    len(d["t"]), "x" * chunk
                ),
            )
        from hypermerge_tpu.utils.ids import validate_doc_url

        doc_id = validate_doc_url(url)
        stored = []
        back_doc = repo.back.docs[doc_id]
        for actor_id, end in back_doc.clock.items():
            actor = repo.back._get_or_create_actor(actor_id)
            stored.extend(actor.changes_in_window(0, end))
        repo.close()

        repo2 = Repo(path=tmp)
        h = repo2.open(url)
        assert h.value(timeout=60) is not None
        doc = repo2.back.docs[doc_id]
        # a synthetic peer continues the doc with single-op edits
        peer_opset = OpSet()
        peer_front = FrontendDoc()
        peer_front.apply_patch(peer_opset.apply_changes(stored))
        peer = "livepeer00000001"
        seqs = [0]

        def peer_edit():
            seqs[0] += 1
            req, _ = peer_front.change(
                lambda d: d["t"].insert(len(d["t"]), "!"),
                peer,
                seqs[0],
            )
            ch, patch = peer_opset.apply_local_request(req)
            peer_front.apply_patch(patch)
            return ch

        first = peer_edit()
        # pre-generate the burst so the timed region measures the
        # APPLY path (the peer-side OpSet generator is O(doc) per edit
        # and would otherwise serialize the stream into 1-change ticks)
        burst = [peer_edit() for _ in range(n_burst)]

        t0 = _t.perf_counter()
        doc.apply_remote_changes([first])
        while doc.clock.get(peer, 0) < 1:
            _t.sleep(0.0005)
        if repo2.back.live is not None:
            repo2.back.live.flush_now()
        first_ms = (_t.perf_counter() - t0) * 1e3

        t0 = _t.perf_counter()
        for base in range(0, n_burst, 32):  # replication-chunk shaped
            doc.apply_remote_changes(burst[base : base + 32])
        while doc.clock.get(peer, 0) < 1 + n_burst:
            _t.sleep(0.0005)
        if repo2.back.live is not None:
            repo2.back.live.flush_now()
        dt = _t.perf_counter() - t0
        stats = _live_stats(repo2)
        repo2.close()
        return first_ms, n_burst / dt, stats
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _config6_demote_readopt(n_ops=4096, n_docs=3, rounds=3):
    """Demote -> re-edit cycle (the HM_LIVE_MAX_BYTES lifecycle): N
    stored text docs open lazily, each takes a live local edit
    (adopt); a byte cap below one doc's footprint demotes every idle
    doc after its tick, so each round-robin edit RE-adopts a demoted
    doc from its sidecars. Reports the median re-adoption edit latency
    (ms) and the engine's demote/readopt counters — the trajectory
    metric for the byte-bounded live engine."""
    import tempfile as _tf
    import time as _t

    from hypermerge_tpu.models import Text
    from hypermerge_tpu.repo import Repo

    tmp = _tf.mkdtemp(prefix="hm_dem6")
    old = os.environ.get("HM_LIVE_MAX_BYTES")
    repo2 = None
    try:
        repo = Repo(path=tmp)
        urls = []
        chunk = 64
        for _i in range(n_docs):
            url = repo.create({"t": ""})
            repo.change(url, lambda d: d.__setitem__("t", Text("seed")))
            for _base in range(0, n_ops, chunk):
                repo.change(
                    url,
                    lambda d: d["t"].insert(len(d["t"]), "x" * chunk),
                )
            urls.append(url)
        repo.close()

        os.environ["HM_LIVE_MAX_BYTES"] = "1"  # only the MRU survives
        repo2 = Repo(path=tmp)
        handles = repo2.open_many(urls)
        for h in handles:
            assert h.value(timeout=60) is not None
        eng = repo2.back.live
        if eng is None:
            return None  # HM_LIVE=0: no lifecycle to measure
        for u in urls:  # round 0: first adoption of every doc
            repo2.change(u, lambda d: d["t"].insert(len(d["t"]), "!"))
            eng.flush_now()
        lats = []
        for _rnd in range(rounds):
            for u in urls:
                t0 = _t.perf_counter()
                repo2.change(
                    u, lambda d: d["t"].insert(len(d["t"]), "?")
                )
                lats.append((_t.perf_counter() - t0) * 1e3)
                eng.flush_now()  # tick + budget pass demotes the rest
        lats.sort()
        stats = _live_stats(repo2)
        assert stats.get("readopted", 0) >= rounds * (n_docs - 1), stats
        return lats[len(lats) // 2], stats
    finally:
        if repo2 is not None:
            repo2.close()
        if old is None:
            os.environ.pop("HM_LIVE_MAX_BYTES", None)
        else:
            os.environ["HM_LIVE_MAX_BYTES"] = old
        shutil.rmtree(tmp, ignore_errors=True)


def _config_coldopen(n_docs, n_ops):
    """Pack-plane scaling gate (ISSUE 19): a cold open at ~10x the
    primary corpus, once with the pack serialized (HM_PACK_WORKERS=1)
    and once with the full pool (=4, BENCH_COLDOPEN_WORKERS), same disk
    state. Reports the pool shape, per-worker busy lanes, the pool's
    lane wall, and two derived gates:

      coldopen_pack_speedup — sum(per-worker busy) / pack lane wall of
        the pooled pass: the pool's REALIZED parallelism. The >=3x
        target applies on a >=4-core host; a 1-2 core box reports its
        honest (lower) number rather than asserting.
      coldopen_pack_bound   — the pooled pack lane wall no longer
        dominates: pack_wall <= max(io busy, dispatch busy), i.e. the
        cold open is bounded by slab IO / device dispatch, not by the
        host pack.

    Scale with BENCH_COLDOPEN_DOCS (default 10x BENCH_DOCS) and
    BENCH_COLDOPEN_OPS (default 256 — ops/doc shrinks so the 10x doc
    axis, which is what shards across pack workers, carries the
    scaling). The serialized pass's pack busy is also reported so
    serial-vs-pool wall math stays possible downstream."""
    from hypermerge_tpu.ops.corpus import make_corpus

    co_docs = int(
        os.environ.get("BENCH_COLDOPEN_DOCS", str(n_docs * 10))
    )
    co_ops = int(os.environ.get("BENCH_COLDOPEN_OPS", "256"))
    workers = int(os.environ.get("BENCH_COLDOPEN_WORKERS", "4"))
    co_tmp = tempfile.mkdtemp(prefix="hm_bench_co")

    def _pass(n):
        old = os.environ.get("HM_PACK_WORKERS")
        os.environ["HM_PACK_WORKERS"] = str(n)
        try:
            return _open_and_materialize(co_tmp, urls)
        finally:
            if old is None:
                os.environ.pop("HM_PACK_WORKERS", None)
            else:
                os.environ["HM_PACK_WORKERS"] = old

    try:
        urls = make_corpus(co_tmp, co_docs, co_ops, threads=16)
        dt_serial, st_serial = _pass(1)
        dt_pool, st_pool = _pass(workers)
        if not st_pool.get("pipeline"):
            return None  # serial twin: no pack plane to measure
        lanes = [
            float(b)
            for b in (st_pool.get("t_pack_busy_per_worker") or [])
        ]
        pack_wall = float(st_pool.get("t_pack_wall", 0.0))
        serial_busy = float(
            st_serial.get("t_pack_busy", st_serial.get("t_pack", 0.0))
        )
        io_b = float(st_pool.get("t_io_busy", st_pool.get("t_io", 0.0)))
        disp_b = float(
            st_pool.get("t_dispatch_busy", st_pool.get("t_dispatch", 0.0))
        )
        return {
            "config_coldopen_s": round(dt_pool, 2),
            "config_coldopen_serial_s": round(dt_serial, 2),
            "docs": co_docs,
            "ops_per_doc": co_ops,
            "cores": os.cpu_count() or 1,
            "pack_workers": st_pool.get("pack_workers"),
            "t_pack_busy_per_worker": lanes,
            "t_pack_wall": round(pack_wall, 3),
            "t_pack_serial_busy": round(serial_busy, 3),
            "t_io_busy": round(io_b, 3),
            "t_dispatch_busy": round(disp_b, 3),
            "coldopen_pack_speedup": (
                round(sum(lanes) / pack_wall, 2) if pack_wall > 0 else None
            ),
            "coldopen_pack_bound": bool(pack_wall <= max(io_b, disp_b)),
        }
    finally:
        shutil.rmtree(co_tmp, ignore_errors=True)


def _config_read(tmp, urls):
    """BASELINE round-15 serving config (ISSUE 11): N concurrent
    reader threads point-read the stored corpus through the
    HBM-resident serving tier — a hot/cold mix (90% of reads over a
    32-doc hot set, 10% uniform over BENCH_READ_DOCS docs). Reports
    read QPS, p50/p99 read latency from the telemetry histogram
    (serve.read_s), the tier's counters, and the measured speedup over
    per-request host materialization of the same mix (the HM_SERVE=0
    cost). Scale with BENCH_READERS / BENCH_READS / BENCH_READ_DOCS
    (corpus size itself rides BENCH_DOCS).

    The speedup is doc-size-sensitive: host materialization is O(doc)
    per read while a served read is ~constant (batcher round trip +
    one shared dispatch), so tiny-doc corpora (BENCH_OPS <~ 256) can
    read below 1x — the tier's regime is the default 1k-op docs and
    up, where same-box runs measure ~13x."""
    import random as _rnd
    import threading as _th

    from hypermerge_tpu import telemetry
    from hypermerge_tpu.repo import Repo
    from hypermerge_tpu.serve.tier import host_value
    from hypermerge_tpu.utils.ids import validate_doc_url

    readers = int(os.environ.get("BENCH_READERS", "8"))
    n_reads = int(os.environ.get("BENCH_READS", "4000"))
    n_sub = int(os.environ.get("BENCH_READ_DOCS", "2048"))
    host_reads = max(64, n_reads // 16)
    repo = Repo(path=tmp)
    try:
        if repo.back.serve is None:
            raise RuntimeError("serving tier off (HM_SERVE=0)")
        sub = urls[: min(len(urls), n_sub)]
        repo.open_many(sub)
        repo.back.fetch_bulk_summaries()
        hot = sub[:32]
        rng = _rnd.Random(0xEAD5)
        mix = [
            hot[rng.randrange(len(hot))]
            if rng.random() < 0.9
            else sub[rng.randrange(len(sub))]
            for _ in range(n_reads)
        ]
        query = {"kind": "len", "path": []}
        for u in hot:  # steady state: hot set resident before timing
            repo.read(u, query)
        hist = repo.back.serve._hist
        h0 = hist.value()
        snap0 = telemetry.snapshot()

        # -- timed: concurrent readers over the served tier ------------
        errs = []

        def reader(n):
            try:
                for i in range(n, n_reads, readers):
                    if repo.read(mix[i], query) is None:
                        raise AssertionError(f"None read for {mix[i]}")
            except Exception as e:  # pragma: no cover - failure surface
                errs.append(e)

        threads = [
            _th.Thread(target=reader, args=(n,)) for n in range(readers)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errs:
            raise errs[0]
        h1 = hist.value()
        snap1 = telemetry.snapshot()
        qps = n_reads / dt
        p50 = _hist_quantile(hist.buckets, h0, h1, 0.50)
        p99 = _hist_quantile(hist.buckets, h0, h1, 0.99)
        fallbacks = snap1["serve.fallbacks"] - snap0.get(
            "serve.fallbacks", 0
        )

        # -- baseline: per-request host materialization, same mix, same
        # thread count (what every one of these reads cost pre-tier) --
        docs = {
            u: repo.back.docs[validate_doc_url(u)] for u in set(mix)
        }
        herrs = []

        def host_reader(n):
            try:
                for i in range(n, host_reads, readers):
                    if host_value(docs[mix[i]], query) is None:
                        raise AssertionError("None host read")
            except Exception as e:  # pragma: no cover
                herrs.append(e)

        threads = [
            _th.Thread(target=host_reader, args=(n,))
            for n in range(readers)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        host_dt = time.perf_counter() - t0
        if herrs:
            raise herrs[0]
        host_qps = host_reads / host_dt
        stats = {
            "docs": len(sub),
            "readers": readers,
            "reads": n_reads,
            "hot_docs": len(hot),
            "fallbacks_steady": int(fallbacks),
            "batches": int(
                snap1["serve.batches"] - snap0.get("serve.batches", 0)
            ),
            "installs": int(
                snap1["serve.installs"] - snap0.get("serve.installs", 0)
            ),
            "hits": int(
                snap1["serve.hits"] - snap0.get("serve.hits", 0)
            ),
            "resident_bytes": snap1.get("serve.resident_bytes", 0),
        }
        return qps, p50, p99, host_qps, stats
    finally:
        repo.close()


def _hist_quantile(bounds, before, after, q):
    """Quantile (ms) from the delta of two Histogram.value() snapshots:
    the upper bound of the bucket where the cumulative count crosses
    q (the +Inf tail reports the largest finite bound)."""
    counts = [
        b - a for a, b in zip(before["buckets"], after["buckets"])
    ]
    n = sum(counts)
    if n <= 0:
        return None
    target = q * n
    seen = 0
    for i, c in enumerate(counts):
        seen += c
        if seen >= target:
            bound = bounds[min(i, len(bounds) - 1)]
            return round(bound * 1e3, 3)
    return round(bounds[-1] * 1e3, 3)


_SERVICE_CHILD = r"""
import bisect, json, random, sys, threading, time

sock, idx = sys.argv[1], int(sys.argv[2])

from hypermerge_tpu.net.ipc import connect_frontend
from hypermerge_tpu.serve.overload import Overload

front, close = connect_frontend(sock)
setup = json.loads(sys.stdin.readline())
read_urls = setup["read_urls"]
own_url = setup["write_urls"][idx]
BOUNDS = setup["bounds"]  # seconds, ascending; +1 overflow slot
query = {"kind": "len", "path": []}

# zipf-ish popularity over the read corpus, identical ordering in
# every client — the aggregate mix concentrates on a shared hot set
# with a long cold tail (the brownout ladder's install-deferral prey)
w = [1.0 / (k + 1) ** 1.2 for k in range(len(read_urls))]
cum, s = [], 0.0
for x in w:
    s += x
    cum.append(s)

h = front.open(own_url)

def val(timeout=0.05):
    try:
        return h.value(timeout=timeout)
    except TimeoutError:
        return None

deadline = time.time() + 60
while time.time() < deadline:
    if val() is not None:
        break
    time.sleep(0.02)
else:
    raise SystemExit("write doc never materialized")

wseq = [0]    # next write sequence (keys are c{idx}.{seq})
wacked = [0]  # contiguous acked prefix: keys 0..wacked-1 observed

def hist_new():
    return [0] * (len(BOUNDS) + 1)

def hist_add(hist, dt):
    hist[bisect.bisect_left(BOUNDS, dt)] += 1

print("ready", flush=True)

for line in sys.stdin:
    cmd = json.loads(line)
    if cmd.get("op") == "quit":
        break
    threads, secs = int(cmd["threads"]), float(cmd["secs"])
    do_write = bool(cmd.get("writes"))
    stop = time.time() + secs
    out = {
        "reads": 0, "shed": 0, "errors": 0, "opens": 0,
        "rhist": hist_new(), "whist": hist_new(),
        "writes": 0, "write_timeouts": 0,
    }
    lock = threading.Lock()

    def reader(seed):
        rng = random.Random((idx << 10) ^ seed)
        n = shed = errs = opens = 0
        hist = hist_new()
        k = 0
        while time.time() < stop:
            u = read_urls[bisect.bisect_left(cum, rng.random() * s)]
            k += 1
            t0 = time.perf_counter()
            try:
                if k % 64 == 0:
                    # the open/watch lane of the mix: (re)open the doc
                    # and read the handle's materialized view
                    if front.open(u).value(timeout=60.0) is None:
                        errs += 1
                    else:
                        opens += 1
                    continue
                v = front.read(u, query, timeout=60.0)
                if v is None:
                    errs += 1
                else:
                    n += 1
                    hist_add(hist, time.perf_counter() - t0)
            except Overload as e:
                # the typed refusal: a well-behaved client backs off
                # for retry_after (capped so the storm stays a storm)
                shed += 1
                time.sleep(min(max(e.retry_after_s, 1e-3), 0.05))
            except Exception:
                errs += 1
        with lock:
            out["reads"] += n
            out["shed"] += shed
            out["errors"] += errs
            out["opens"] += opens
            for i, c in enumerate(hist):
                out["rhist"][i] += c

    def writer():
        # ack-paced durable writes to this tenant's own doc: the next
        # edit is released only when the previous one's patch echo is
        # visible in the handle — under SHED the WAL's stretched
        # gather window paces this loop down instead of refusing it
        n = tmo = 0
        hist = hist_new()
        while time.time() < stop:
            seq = wseq[0]
            key = "c%d.%d" % (idx, seq)
            t0 = time.perf_counter()
            front.change(
                own_url,
                lambda d, _k=key, _s=seq: d["edits"].__setitem__(
                    _k, _s
                ),
            )
            wseq[0] += 1
            lim = time.time() + 30
            acked = False
            while time.time() < lim:
                v = val(timeout=0.02)
                if v is not None and key in v.get("edits", {}):
                    acked = True
                    break
                time.sleep(0.002)
            if acked:
                n += 1
                hist_add(hist, time.perf_counter() - t0)
                if seq == wacked[0]:  # contiguous prefix only
                    wacked[0] = seq + 1
            else:
                tmo += 1
                break  # ack pipeline stalled: stop this phase's writer
        with lock:
            out["writes"] += n
            out["write_timeouts"] += tmo
            for i, c in enumerate(hist):
                out["whist"][i] += c

    t0 = time.perf_counter()
    ts = [
        threading.Thread(target=reader, args=(k,))
        for k in range(threads)
    ]
    if do_write:
        ts.append(threading.Thread(target=writer))
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    out["secs"] = time.perf_counter() - t0
    out["acked"] = wacked[0]
    print(json.dumps(out), flush=True)

close()
"""


def _svc_quantile(bounds, counts, q):
    """Quantile (ms) over a merged client-side histogram: `counts` is
    len(bounds)+1 (overflow last); the overflow tail reports one step
    past the last edge so a saturated histogram still moves."""
    n = sum(counts)
    if n <= 0:
        return None
    seen = 0
    for i, c in enumerate(counts):
        seen += c
        if seen >= q * n:
            bound = (
                bounds[i] if i < len(bounds) else bounds[-1] * 2
            )
            return round(bound * 1e3, 3)
    return round(bounds[-1] * 2 * 1e3, 3)


def _config_service():
    """THE top-level repo number (ISSUE 20): every plane at once,
    under overload, behind the one front door. A hub daemon
    (net/ipc.py --hub, serve tier on, service plane on, durable acks
    over the group-commit WAL, DHT member) serves a zipf-distributed
    open/read/write/watch mix from BENCH_SERVICE_CLIENTS frontend
    PROCESSES — one IPC connection each, so the hub's per-connection
    tenant tagging makes every client a quota tenant — while an
    in-process DHT peer replicates a slice of the corpus (gossip +
    anti-entropy competing with hot reads, exactly the traffic the
    brownout ladder deprioritizes).

    The driver ramps closed-loop reader threads per client
    (1, 2, 4, ... BENCH_SERVICE_MAX_THREADS) until aggregate read
    throughput plateaus or the daemon starts shedding — that round's
    peak is the SATURATION point — then holds a 2x-saturation storm
    for BENCH_SERVICE_HOLD_S with durable writers running, then drops
    the load and probes until client-observed p99 is back under the
    SLO with zero shed (recovery_to_slo_s). Gates (the `gates` block,
    all must hold):

      reads_never_error   — across ramp+storm+recovery, every read
        either returns a value, is answered from the host memo path
        (indistinguishable from a value, by design), or is refused
        with the TYPED Overload reply. Zero untyped errors.
      acked_lost_zero     — every write a client observed acked is
        present in the final doc state (writes are backpressured via
        WAL ack-pacing under SHED, never dropped).
      recovery_within_gate — p99 back under HM_SERVICE_P99_SLO_MS
        within BENCH_SERVICE_RECOVERY_GATE_S of the storm ending.
      shed_order_ok       — refusals only ever happened AFTER the
        ladder climbed through BROWNOUT (transitions >= 2: the
        documented shed order, cold installs brown out before hot
        reads are refused).
      attributed          — no silent refusals: the daemon's
        service.shed_reads equals both the per-tenant refused sum in
        the service report AND the clients' own Overload count.

    Runs in the config_writers daemon posture (HM_WORKERS rides the
    caller's env: 0 = in-process plane on the CI box, N = sharded);
    scale with BENCH_SERVICE_CLIENTS/DOCS/HOLD_S/SLO_MS."""
    import tempfile as _tempfile

    from hypermerge_tpu.net.discovery import DhtNode, DhtSwarm
    from hypermerge_tpu.repo import Repo

    n_clients = int(os.environ.get("BENCH_SERVICE_CLIENTS", "4"))
    n_docs = int(os.environ.get("BENCH_SERVICE_DOCS", "48"))
    ramp_s = float(os.environ.get("BENCH_SERVICE_RAMP_S", "1.0"))
    hold_s = float(os.environ.get("BENCH_SERVICE_HOLD_S", "3.0"))
    slo_ms = float(os.environ.get("BENCH_SERVICE_SLO_MS", "25"))
    gate_s = float(
        os.environ.get("BENCH_SERVICE_RECOVERY_GATE_S", "10")
    )
    max_threads = int(
        os.environ.get("BENCH_SERVICE_MAX_THREADS", "16")
    )
    # client-side latency buckets (seconds): merged across clients
    # for the p50/p99 SLO gating — sub-ms floor, 2.5s overflow edge
    bounds = [
        0.0005, 0.001, 0.002, 0.005, 0.01, 0.02, 0.05,
        0.1, 0.25, 0.5, 1.0, 2.5,
    ]

    tmp = _tempfile.mkdtemp(prefix="hm-service-")
    sock = os.path.join(tmp, "daemon.sock")
    env = _writer_daemon_env()
    env["HM_SERVICE"] = "1"
    env["HM_SERVICE_P99_SLO_MS"] = str(slo_ms)
    env.setdefault("HM_SERVICE_TICK_MS", "25")
    # per-tenant quota low enough that SHED visibly bites on a small
    # box (each tenant still gets a real trickle: no starvation)
    env.setdefault("HM_QUOTA_READS_S", "64")
    env.setdefault("HM_QUOTA_BURST", "16")
    env.setdefault("HM_DHT_ANNOUNCE_S", "0.5")
    env.setdefault("HM_DHT_LOOKUP_S", "0.5")

    boot = DhtNode()
    daemon = subprocess.Popen(
        [
            sys.executable, "-m", "hypermerge_tpu.net.ipc",
            os.path.join(tmp, "repo"), sock, "--hub", "--dht",
            "--dht-bootstrap", f"127.0.0.1:{boot.address[1]}",
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
    )
    clients = []
    peer = sw = close = None
    try:
        line = daemon.stdout.readline()
        if "ready" not in line:
            raise RuntimeError(f"daemon failed to start: {line!r}")
        from hypermerge_tpu.net.ipc import connect_frontend

        front, close = connect_frontend(sock)
        read_urls = [
            front.create({"k": i, "pad": "x" * 64})
            for i in range(n_docs)
        ]
        write_urls = [
            front.create({"edits": {}}) for _ in range(n_clients)
        ]
        # round-trip on the ordered channel: every doc is registered
        # in the daemon before any client opens or reads one
        got = []
        front.materialize(write_urls[-1], 1, got.append)
        deadline = time.time() + 60
        while not got and time.time() < deadline:
            time.sleep(0.02)
        if not got:
            raise RuntimeError("doc registration never acked")

        # the DHT peer: replicates a slice of the corpus through
        # announce/lookup discovery — live anti-entropy + gossip
        # traffic on the daemon during the storm
        peer = Repo(memory=True)
        sw = DhtSwarm(bootstrap=[boot.address])
        peer.set_swarm(sw)
        for u in read_urls[: min(4, n_docs)]:
            peer.open(u)

        setup = json.dumps({
            "read_urls": read_urls,
            "write_urls": write_urls,
            "bounds": bounds,
        })
        clients = [
            subprocess.Popen(
                [sys.executable, "-c", _SERVICE_CHILD, sock, str(i)],
                env=env,
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
            for i in range(n_clients)
        ]
        for c in clients:
            c.stdin.write(setup + "\n")
            c.stdin.flush()
        for c in clients:
            if c.stdout.readline().strip() != "ready":
                raise RuntimeError(
                    f"client failed: {c.stderr.read()[-500:]}"
                )

        def phase(threads, secs, writes):
            cmd = json.dumps({
                "op": "phase", "threads": threads, "secs": secs,
                "writes": 1 if writes else 0,
            })
            for c in clients:
                c.stdin.write(cmd + "\n")
                c.stdin.flush()
            outs = [json.loads(c.stdout.readline()) for c in clients]
            agg = {
                k: sum(o[k] for o in outs)
                for k in ("reads", "shed", "errors", "opens",
                          "writes", "write_timeouts")
            }
            agg["rhist"] = [
                sum(o["rhist"][i] for o in outs)
                for i in range(len(bounds) + 1)
            ]
            agg["whist"] = [
                sum(o["whist"][i] for o in outs)
                for i in range(len(bounds) + 1)
            ]
            agg["secs"] = max(o["secs"] for o in outs)
            agg["acked"] = [o["acked"] for o in outs]
            agg["qps"] = round(agg["reads"] / agg["secs"], 1)
            return agg

        # -- warmup: install the hot set so the steady baseline and
        # the ramp measure serving, not first-touch installs ---------
        ramp, errors, whist = [], 0, [0] * (len(bounds) + 1)
        writes_total = timeouts = shed_total = 0
        w0 = phase(1, 1.0, writes=False)
        errors += w0["errors"]
        shed_total += w0["shed"]
        time.sleep(0.25)  # let the install/replication queues drain

        # the steady-state reference: one reader/client over the warm
        # hot set, no writers — the SLO the recovery gate returns to
        r0 = phase(1, ramp_s, writes=False)
        errors += r0["errors"]
        shed_total += r0["shed"]
        steady = {
            "qps": r0["qps"],
            "read_p50_ms": _svc_quantile(bounds, r0["rhist"], 0.50),
            "read_p99_ms": _svc_quantile(bounds, r0["rhist"], 0.99),
        }

        # -- ramp: closed-loop threads/client double each round until
        # the daemon starts shedding or the thread budget runs out (a
        # throughput plateau alone is too noisy a stop on a small box;
        # the extra rounds cost ~1s each and the peak is the honest
        # saturation point) -----------------------------------------
        t = 1
        while t <= max_threads:
            r = phase(t, ramp_s, writes=True)
            errors += r["errors"]
            writes_total += r["writes"]
            timeouts += r["write_timeouts"]
            whist = [a + b for a, b in zip(whist, r["whist"])]
            ramp.append({
                "threads": t, "qps": r["qps"], "shed": r["shed"],
                "p99_ms": _svc_quantile(bounds, r["rhist"], 0.99),
            })
            if r["shed"] > 0:
                break
            t *= 2
        peak = max(ramp, key=lambda x: x["qps"])
        saturation_qps = peak["qps"]
        sat_threads = peak["threads"]

        # -- the storm: 2x-saturation offered load, writers on ------
        storm_threads = min(2 * sat_threads, 2 * max_threads)
        r = phase(storm_threads, hold_s, writes=True)
        errors += r["errors"]
        writes_total += r["writes"]
        timeouts += r["write_timeouts"]
        whist = [a + b for a, b in zip(whist, r["whist"])]
        storm = {
            "threads_per_client": storm_threads,
            "qps": r["qps"],
            "reads_ok": r["reads"],
            "reads_shed": r["shed"],
            "opens": r["opens"],
            "read_p99_ms": _svc_quantile(bounds, r["rhist"], 0.99),
            "writes_acked": r["writes"],
        }
        shed_total += sum(x["shed"] for x in ramp) + r["shed"]

        # -- recovery: drop to one thread/client, probe until p99 is
        # back under the SLO with zero shed --------------------------
        t_end = time.perf_counter()
        recovery_s = None
        while time.perf_counter() - t_end < gate_s + 5:
            p = phase(1, 0.4, writes=False)
            errors += p["errors"]
            shed_total += p["shed"]
            p99 = _svc_quantile(bounds, p["rhist"], 0.99)
            if (
                p["shed"] == 0
                and p99 is not None
                and p99 <= slo_ms
            ):
                recovery_s = round(time.perf_counter() - t_end, 2)
                break

        # -- drain the clients, then verify the acked ledger --------
        acked = []
        for c in clients:
            c.stdin.write(json.dumps({"op": "quit"}) + "\n")
            c.stdin.flush()
        for i, c in enumerate(clients):
            c.wait(timeout=30)
        # the coordinator's own handles receive every hub-routed
        # patch; poll until each doc shows the client's acked count
        acked_counts = r["acked"]
        acked_lost = 0
        for i, url in enumerate(write_urls):
            want = acked_counts[i]
            h = front.open(url)
            deadline = time.time() + 60
            edits = {}
            while time.time() < deadline:
                try:
                    v = h.value(timeout=0.5)
                except TimeoutError:
                    v = None
                edits = (v or {}).get("edits", {})
                if len(edits) >= want:
                    break
                time.sleep(0.05)
            acked_lost += sum(
                1 for s_ in range(want) if f"c{i}.{s_}" not in edits
            )
            acked.append(want)

        # -- attribution: the daemon's service report must account
        # for every refusal the clients saw --------------------------
        tele = []
        front.telemetry(tele.append)
        deadline = time.time() + 30
        while not tele and time.time() < deadline:
            time.sleep(0.02)
        payload = tele[0] if tele else {}
        svc = payload.get("service") or {}
        counters = payload.get("counters") or {}
        tenants = svc.get("tenants") or {}
        refused_sum = sum(
            row.get("refused", 0) for row in tenants.values()
        )
        shed_reads = int(svc.get("shed_reads", 0))
        transitions = int(svc.get("transitions", 0))

        gates = {
            "reads_never_error": errors == 0,
            "acked_lost_zero": acked_lost == 0 and sum(acked) > 0,
            "recovery_within_gate": (
                recovery_s is not None and recovery_s <= gate_s
            ),
            "shed_order_ok": shed_reads == 0 or transitions >= 2,
            "attributed": (
                refused_sum == shed_reads
                and shed_total == shed_reads
            ),
        }
        return {
            "clients": n_clients,
            "docs": n_docs,
            "slo_ms": slo_ms,
            "steady": steady,
            "ramp": ramp,
            "saturation_qps": saturation_qps,
            "sat_threads_per_client": sat_threads,
            "storm": storm,
            "recovery_to_slo_s": recovery_s,
            "recovery_gate_s": gate_s,
            "writes_acked": writes_total,
            "write_timeouts": timeouts,
            "write_p50_ms": _svc_quantile(bounds, whist, 0.50),
            "write_p99_ms": _svc_quantile(bounds, whist, 0.99),
            "acked_lost": acked_lost,
            "reads_errors": errors,
            "reads_shed": shed_total,
            "service": {
                "state": svc.get("state_name"),
                "transitions": transitions,
                "shed_reads": shed_reads,
                "brownout_reads": int(svc.get("brownout_reads", 0)),
                "deferred_installs": int(
                    svc.get("deferred_installs", 0)
                ),
                "tenants": tenants,
            },
            "paced_commits": int(
                counters.get("storage.wal.paced_commits", 0)
            ),
            "overload_shed": int(
                counters.get("serve.overload_shed", 0)
            ),
            "gates": gates,
            "gated_ok": all(gates.values()),
        }
    finally:
        for c in clients:
            c.kill()
        if close is not None:
            close()
        if peer is not None:
            peer.close()
        if sw is not None:
            sw.destroy()
        boot.close()
        daemon.terminate()
        try:
            daemon.wait(timeout=10)
        except subprocess.TimeoutExpired:
            daemon.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def _config5_union(n_docs=100_000, n_actors=64, seed=0, dirty=1000):
    """100k-doc clock union served from the device-RESIDENT ClockStore
    mirror (ops/clock_mirror.py; BASELINE config 5). Setup uploads the
    matrix once (untimed — a live deployment's mirror accretes with
    writes); the timed region is the realistic hot query: `dirty` fresh
    clock writes land (one batched scatter-max) and the union runs as a
    max-reduce over resident HBM + a [actors] fetch. Contrast r4, which
    re-packed and re-uploaded all 25MB per query (915ms)."""
    import numpy as np

    from hypermerge_tpu.ops.clock_mirror import DeviceClockMirror

    rng = np.random.default_rng(seed)
    clocks = rng.integers(
        1, 1000, size=(n_docs, n_actors), dtype=np.int32
    )
    mirror = DeviceClockMirror(
        capacity_docs=n_docs, capacity_actors=n_actors
    )
    actors = [f"a{j}" for j in range(n_actors)]
    mirror.seed_bulk(
        [f"d{i}" for i in range(n_docs)], actors, clocks
    )
    # warm BOTH query programs (with and without pending writes) at the
    # dirty-bucket shape the timed pass uses, and settle the upload
    mirror.union()
    for i in range(dirty):
        mirror.update(f"d{i}", {actors[i % n_actors]: 1})
    mirror.union()

    t0 = time.perf_counter()
    for i in range(dirty):
        mirror.update(f"d{i}", {actors[i % n_actors]: 2000 + i})
    merged = mirror.union()
    dt = time.perf_counter() - t0
    assert len(merged) == n_actors
    assert merged[actors[(dirty - 1) % n_actors]] >= 2000
    return dt * 1e3  # ms


def _config3_multiactor(n_docs=1024, n_ops=512):
    """BASELINE config 3: 1k synthetic docs x 3 concurrent actors x
    ~500 ops (LWW map + RGA list mix), batched through the device
    kernel. Unlike the single-writer corpus (configs 4), this drives
    the GENERAL sorted-composite pack path and the multi-actor
    tie-break lanes. Timed: warm materialize + liveness/clock fetch to
    host (the render barrier). Correctness for this shape is pinned by
    tests/test_device_materialize.py fuzz vs OpSet."""
    import numpy as np

    from hypermerge_tpu.ops.materialize import materialize_batch
    from hypermerge_tpu.ops.synth import synth_changes

    histories = [
        synth_changes(
            n_ops, n_actors=3, ops_per_change=8, text_frac=0.5, seed=s
        )
        for s in range(n_docs)
    ]

    def full_pass():
        dec = materialize_batch(histories)
        np.asarray(dec.elem_live)
        np.asarray(dec.clock)
        return dec

    full_pass()  # compile + warm
    t0 = time.perf_counter()
    dec = full_pass()
    dt = time.perf_counter() - t0
    assert dec.clock_dict(0), "empty clock"
    return dt, n_docs * n_ops / dt


def _device_rtt_ms():
    """The device link's dispatch+fetch round-trip floor, measured on a
    64-int array (payload-independent). It floors any single-dispatch
    metric (config5's union IS one round trip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jnp.zeros(64, jnp.int32)
    f = jax.jit(lambda a: a + 1)
    np.asarray(f(x))  # compile + settle
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(f(x))
        dt = (time.perf_counter() - t0) * 1e3
        best = dt if best is None else min(best, dt)
    return best


def _config6_text_trace(n_ops=None):
    """automerge-perf trace shape (BASELINE.md): ONE text doc, ONE
    author, one op per change — 259,778 ops, the published workload the
    reference's engine (automerge 0.14) takes MINUTES on (~0.4-0.9k
    ops/s, multi-GB heap). Timed region: a warm device materialize of
    the full trace + char-joined text extraction to a host string.
    Correctness at this scale is pinned by tests/test_text_scale.py
    (device == numpy twin == OpSet). BENCH_TRACE_OPS shrinks the trace
    (XLA:CPU compiles the 256k bucket in >10 minutes — published-shape
    numbers need the TPU backend)."""
    if n_ops is None:
        n_ops = int(os.environ.get("BENCH_TRACE_OPS", "259778"))
    import numpy as np

    from hypermerge_tpu.crdt.change import Action
    from hypermerge_tpu.ops.materialize import (
        materialize_batch,
        text_join,
    )
    from hypermerge_tpu.ops.synth import synth_changes

    changes = synth_changes(
        n_ops, n_actors=1, ops_per_change=1, text_frac=1.0, seed=3
    )

    def full_pass():
        dec = materialize_batch([changes])
        n = int(dec.batch.n_ops[0])
        rows = np.nonzero(
            dec.cols["action"][0][:n] == int(Action.MAKE_TEXT)
        )[0]
        return text_join(dec, 0, int(rows[0]))

    full_pass()  # compile + warm every program in the 256k bucket

    t0 = time.perf_counter()
    text = full_pass()
    dt = time.perf_counter() - t0
    assert len(text) > 1000, len(text)
    return dt, n_ops / dt


def main() -> None:
    n_docs = int(os.environ.get("BENCH_DOCS", "10240"))
    n_ops = int(os.environ.get("BENCH_OPS", "1024"))
    host_docs = int(os.environ.get("BENCH_HOST_DOCS", "8"))

    import jax

    from hypermerge_tpu.crdt.opset import OpSet
    from hypermerge_tpu.ops.corpus import make_corpus
    from hypermerge_tpu.ops.synth import synth_changes

    print(f"# device: {jax.devices()[0]}", file=sys.stderr)
    total_ops = n_docs * n_ops

    # -- speculative compile warmup (ops/warmup.py): a daemon thread
    # overlaps the XLA compile of the slab executables with the corpus
    # write + host baseline below. This mirrors what
    # any serving deployment does at startup; on a box whose persistent
    # compile cache is already warm it is a no-op. cold_first_process
    # then measures the product path, not the compiler.
    warm_thread = None
    if jax.default_backend() != "cpu":
        from hypermerge_tpu.ops.warmup import warmup_bulk

        warm_thread = warmup_bulk(n_docs, n_ops)

    # -- corpus on disk (untimed setup; BENCH_DIR reuses a prior one) --
    bench_dir = os.environ.get("BENCH_DIR")
    tmp = bench_dir or tempfile.mkdtemp(prefix="hm_bench")
    manifest = os.path.join(tmp, "corpus.json")
    if bench_dir and os.path.exists(manifest):
        with open(manifest) as fh:
            meta = json.load(fh)
        assert meta["docs"] == n_docs and meta["ops"] == n_ops, meta
        urls = meta["urls"]
        print(f"# corpus: reusing {tmp}", file=sys.stderr)
    else:
        t0 = time.perf_counter()
        urls = make_corpus(tmp, n_docs, n_ops, threads=16)
        with open(manifest, "w") as fh:
            json.dump({"docs": n_docs, "ops": n_ops, "urls": urls}, fh)
        print(
            f"# corpus: {n_docs} docs x {n_ops} ops written in "
            f"{time.perf_counter()-t0:.1f}s -> {tmp}",
            file=sys.stderr,
        )

    # -- host baseline: incremental OpSet replay (best of 2 — the box
    # load that wobbles the device numbers wobbles this too) ----------
    host_dt = None
    for _ in range(2):
        t0 = time.perf_counter()
        for i in range(host_docs):
            OpSet().apply_changes(
                synth_changes(n_ops, n_actors=1, ops_per_change=16, seed=i)
            )
        d = time.perf_counter() - t0
        host_dt = d if host_dt is None else min(host_dt, d)
    host_rate = host_docs * n_ops / host_dt
    print(
        f"# host baseline: {host_docs} docs x {n_ops} ops in "
        f"{host_dt:.2f}s -> {host_rate:,.0f} ops/s",
        file=sys.stderr,
    )

    # -- cold pass 1: fresh process. Join the warmup before timing: on a
    # fresh box it finished during the corpus write (join is instant);
    # with BENCH_DIR reuse there was no cover, and an in-flight warmup
    # compile/execute would otherwise contaminate the timed region. ----
    if warm_thread is not None:
        # bounded: a stalled compile must fail loudly in the
        # timed pass (which blocks inside jit anyway), not hang here
        warm_thread.join(timeout=180)
        if warm_thread.is_alive():
            print("# warmup still compiling after 180s", file=sys.stderr)
    dt1, stats1 = _open_and_materialize(tmp, urls)
    rate1 = total_ops / dt1
    print(
        f"# cold_first_process: {dt1:.2f}s -> {rate1:,.0f} ops/s "
        f"(stats {stats1})",
        file=sys.stderr,
    )

    # -- steady-state passes: fresh backend each, compile cached.
    # best-of-3: the bench host shares its CPU cores, so single-pass
    # numbers swing with unrelated machine load.
    dts = []
    stats_by_dt = {}
    for _ in range(3):
        d, s = _open_and_materialize(tmp, urls)
        dts.append(d)
        stats_by_dt[d] = s
    dt2 = min(dts)
    stats2 = stats_by_dt[dt2]  # stage breakdown of the BEST pass
    rate2 = total_ops / dt2
    print(
        f"# steady_state (best of {len(dts)}: "
        f"{', '.join(f'{d:.1f}s' for d in dts)}): "
        f"{dt2:.2f}s -> {rate2:,.0f} ops/s (stats {stats2})",
        file=sys.stderr,
    )
    assert stats2.get("fallback", 0) == 0, stats2

    # -- stage breakdown + multi-chip projection (VERDICT r5 item 1) --
    # Serial mode (HM_PIPELINE=0): stage keys are wall times that SUM
    # to the cold open, host stages don't divide across chips, so the
    # projection is host + other + device/8.
    # Pipeline mode (default): stage keys are per-stage BUSY times and
    # the stages OVERLAP — the wall clock is `wall_critical_path`
    # (~max(stage), not sum), and the 8-chip projection is the critical
    # path with only the device leg divided: other + max(host stages,
    # device/8). The t_*_busy aliases + wall_critical_path go into the
    # JSON so the driver sees both views.
    pipelined = bool(stats2.get("pipeline", 0))
    host_keys = ("t_sql", "t_io", "t_spec", "t_pack", "t_narrow")
    # fetch accounting: serial mode pays it at the barrier (t_fetch);
    # pipeline mode's fetch WORK is t_fetch_busy and the barrier's
    # t_fetch is residual waiting on that same work — counting both
    # would double-charge the stage
    dev_keys = (
        ("t_upload", "t_dispatch", "t_fetch_busy")
        if pipelined
        else ("t_upload", "t_dispatch", "t_fetch")
    )
    host_s = sum(stats2.get(k, 0.0) for k in host_keys)
    dev_s = sum(stats2.get(k, 0.0) for k in dev_keys)
    wall_cp = stats2.get("wall_critical_path", dt2)
    if pipelined:
        # busy times overlap inside wall_cp, so dt2 - busy would clamp
        # to 0 precisely when the pipeline works; the serial non-stage
        # time (repo ctor, handle build, barrier assembly) is the wall
        # outside the load's critical path
        other_s = max(0.0, dt2 - wall_cp)
    else:
        other_s = max(0.0, dt2 - host_s - dev_s)
    n_proj = 8
    if pipelined:
        # stages overlap: the host-side floor is the single slowest
        # pipelined host stage, reached when every other stage hides
        # behind it. t_sql stays OUTSIDE the max — it runs before the
        # workers start and after they join, so it can never overlap.
        sql_s = stats2.get("t_sql", 0.0)
        host_max = max(
            stats2.get(k, 0.0) for k in host_keys if k != "t_sql"
        )
        proj8 = other_s + sql_s + max(host_max, dev_s / n_proj)
    else:
        proj8 = host_s + other_s + dev_s / n_proj
    stages = {
        k: stats2.get(k, 0.0)
        for k in host_keys + ("t_upload", "t_dispatch", "t_fetch")
    }
    stages["other"] = round(other_s, 3)
    for k, v in stats2.items():
        if k.endswith("_busy"):
            stages[k] = v
    stages["wall_critical_path"] = round(wall_cp, 3)
    stages["pipeline"] = 1 if pipelined else 0
    busy_total = host_s + dev_s
    print(
        f"# stages ({'pipelined busy' if pipelined else 'serial wall'}): "
        f"host {host_s:.2f}s "
        f"({', '.join(f'{k[2:]}={stats2.get(k, 0.0):.2f}' for k in host_keys)}) "
        f"+ device {dev_s:.2f}s "
        f"({', '.join(f'{k[2:]}={stats2.get(k, 0.0):.2f}' for k in dev_keys)}) "
        f"+ other {other_s:.2f}s",
        file=sys.stderr,
    )
    if pipelined:
        overlap = busy_total / wall_cp if wall_cp > 0 else 1.0
        print(
            f"# overlap: wall critical path {wall_cp:.2f}s vs "
            f"{busy_total:.2f}s total stage busy time "
            f"({overlap:.2f}x concurrency)",
            file=sys.stderr,
        )
    print(
        f"# reference projection (superseded by the MEASURED "
        f"config_mesh multichip_8_s below): {n_proj}-chip "
        f"({'overlapped critical path' if pipelined else 'host serial'}, "
        f"device/{n_proj}) = {proj8:.2f}s -> {total_ops/proj8:,.0f} ops/s",
        file=sys.stderr,
    )

    # aux configs are fail-soft: a failure must not cost the driver the
    # primary metric line
    def _soft(name, fn):
        try:
            return fn()
        except Exception as e:  # pragma: no cover - defensive
            print(f"# {name} FAILED: {e}", file=sys.stderr)
            return None

    # -- measured multichip (the projection retirement): the same
    # corpus, cold-opened over a real device mesh --------------------
    cfgmesh = _soft("config_mesh", lambda: _config_mesh(tmp))
    if cfgmesh is not None:
        mc_s, mc_mode, mc_dev, _mc_topo, mc_stats = cfgmesh
        print(
            f"# config_mesh MEASURED multichip cold open: {mc_s:.2f}s "
            f"-> {total_ops / mc_s:,.0f} ops/s on {mc_dev} devices "
            f"({mc_mode}; slabs/chip {mc_stats.get('slabs_per_chip')}, "
            f"dispatch busy/chip {mc_stats.get('t_dispatch_chips')}, "
            f"fetch busy/chip {mc_stats.get('t_fetch_chips')})",
            file=sys.stderr,
        )

    cfg1 = _soft("config1", _config1_change_latency)
    if cfg1 is not None:
        print(f"# config1 change latency: {cfg1:.0f}us", file=sys.stderr)
    cfg2 = _soft("config2", _config2_convergence)
    if cfg2 is not None:
        print(
            f"# config2 2-repo convergence: {cfg2[0]:.2f}s "
            f"({cfg2[1]:,.0f} edits/s replicated+applied)",
            file=sys.stderr,
        )
        if cfg2[2]:
            print(f"# config2 live-apply: {cfg2[2]}", file=sys.stderr)
    cfgch = _soft("config_churn", _config_churn)
    if cfgch is not None:
        print(
            f"# config_churn convergence under kill/heal: "
            f"{cfgch[0]:.2f}s ({cfgch[1]:,.0f} edits/s; "
            f"churn {cfgch[2]})",
            file=sys.stderr,
        )
    cfgsw = _soft("config_swarm", _config_swarm)
    if cfgsw is not None:
        print(
            f"# config_swarm DHT fleet (no explicit connect, seeded "
            f"kill/heal churn): converged in {cfgsw[0]:.2f}s "
            f"({cfgsw[1]['peers']} peers, frame amp "
            f"max {cfgsw[1]['frame_amp_max']}x vs fanout "
            f"{cfgsw[1]['fanout']}, lookup hops "
            f"{cfgsw[1]['lookup_hops_mean']}; {cfgsw[1]})",
            file=sys.stderr,
        )
    cfgfl = _soft("config_fleet1000", _config_fleet1000)
    if cfgfl is not None:
        print(
            f"# config_fleet1000 scaling: {cfgfl[1]['real_peers']}-peer "
            f"async fleet at {cfgfl[1]['threads_per_daemon']} "
            f"threads/daemon; frames/peer/period "
            f"{cfgfl[1]['frames_per_peer_period_100']} @100 vs "
            f"{cfgfl[1]['frames_per_peer_period_1000']} @1000 "
            f"(ratio {cfgfl[1]['frames_flat_ratio']}x, gate <= 2x); "
            f"cold-join p99 {cfgfl[1]['coldjoin_p99_s']}s simulated "
            f"({cfgfl[1]})",
            file=sys.stderr,
        )
    cfgcr = _soft("config_crash", _config_crash)
    if cfgcr is not None:
        print(
            f"# config_crash kill -9 recovery: reopen+readable in "
            f"{cfgcr[0]:.0f}ms, acked_lost={cfgcr[1]['acked_lost']} "
            f"({cfgcr[1]})",
            file=sys.stderr,
        )
    cfg6l = _soft("config6_live", _config6_live_burst)
    if cfg6l is not None:
        st6 = cfg6l[2]
        print(
            f"# config6-live single-doc burst: first edit "
            f"{cfg6l[0]:.0f}ms, burst {cfg6l[1]:,.0f} edits/s "
            f"(live stats {st6})",
            file=sys.stderr,
        )
        print(
            "# config6-live adoption stages (ms): "
            + ", ".join(
                f"{k[8:]}={st6.get(k, 0.0) * 1e3:.1f}"
                for k in (
                    "t_adopt_pack", "t_adopt_kernel", "t_adopt_decode",
                    "t_adopt_reach", "t_adopt_lock_free",
                    "t_adopt_lock_held",
                )
            ),
            file=sys.stderr,
        )
    cfg6d = _soft("config6_demote", _config6_demote_readopt)
    if cfg6d is not None:
        print(
            f"# config6-demote lifecycle: re-adopt edit median "
            f"{cfg6d[0]:.1f}ms (demoted {cfg6d[1].get('demoted', 0)}, "
            f"readopted {cfg6d[1].get('readopted', 0)})",
            file=sys.stderr,
        )
    cfgld = _soft("config_lockdebt", _config_lockdebt)
    if cfgld is not None:
        print(
            f"# config_lockdebt write-plane blocking debt "
            f"(instrumented): live.engine held across blocking calls "
            f"{cfgld['fsync_group'].get('live_engine', 0.0):.1f}ms at "
            f"HM_FSYNC=1, "
            f"{cfgld['fsync_per_append'].get('live_engine', 0.0):.1f}"
            f"ms at HM_FSYNC=2; per class {cfgld}",
            file=sys.stderr,
        )
    cfgwr = _soft("config_writers", _config_writers)
    if cfgwr is not None:
        eps = cfgwr["edits_per_s"]
        print(
            f"# config_writers many-writer plane (IPC procs, disjoint "
            f"docs, HM_FSYNC=1): "
            + ", ".join(f"{k}w {v:,.0f} edits/s" for k, v in eps.items())
            + f" -> {cfgwr['scaling']:.1f}x scaling"
            + (
                f" (8->32 {cfgwr['scaling_8_32']:.1f}x)"
                if "scaling_8_32" in cfgwr
                else ""
            ),
            file=sys.stderr,
        )
    cfghd = _soft("config_writers_hotdoc", _config_writers_hotdoc)
    if cfghd is not None:
        print(
            f"# config_writers_hotdoc {cfghd['n_writers']} writers x "
            f"ONE shared doc (per-connection actors): "
            f"{cfghd['edits_per_s']:,.0f} edits/s, bit-identical "
            f"convergence {cfghd['converged']}",
            file=sys.stderr,
        )
    cfg3 = _soft("config3", _config3_multiactor)
    if cfg3 is not None:
        print(
            f"# config3 1k docs x 3 actors x 512 ops (general pack "
            f"path): {cfg3[0]:.2f}s -> {cfg3[1]:,.0f} ops/s",
            file=sys.stderr,
        )
    cfgco = _soft(
        "config_coldopen", lambda: _config_coldopen(n_docs, n_ops)
    )
    if cfgco is not None:
        print(
            f"# config_coldopen pack-plane gate "
            f"({cfgco['docs']} docs x {cfgco['ops_per_doc']} ops, "
            f"{cfgco['cores']} cores): pooled {cfgco['config_coldopen_s']}s "
            f"(serial {cfgco['config_coldopen_serial_s']}s), "
            f"{cfgco['pack_workers']} workers, lanes "
            f"{cfgco['t_pack_busy_per_worker']} over "
            f"{cfgco['t_pack_wall']}s wall -> "
            f"{cfgco['coldopen_pack_speedup']}x pack speedup, "
            f"pack_bound={cfgco['coldopen_pack_bound']} "
            f"(io {cfgco['t_io_busy']}s, dispatch "
            f"{cfgco['t_dispatch_busy']}s)",
            file=sys.stderr,
        )

    cfgrd = _soft("config_read", lambda: _config_read(tmp, urls))
    if cfgrd is not None:
        print(
            f"# config_read serving tier: {cfgrd[0]:,.0f} reads/s "
            f"(p50 {cfgrd[1]}ms p99 {cfgrd[2]}ms) vs host "
            f"per-request {cfgrd[3]:,.0f} reads/s -> "
            f"{cfgrd[0] / max(cfgrd[3], 1e-9):.1f}x "
            f"(fallbacks {cfgrd[4]['fallbacks_steady']}, "
            f"batches {cfgrd[4]['batches']})",
            file=sys.stderr,
        )
    cfgsvc = _soft("config_service", _config_service)
    if cfgsvc is not None:
        print(
            f"# config_service front door under overload: saturation "
            f"{cfgsvc['saturation_qps']:,.0f} reads/s "
            f"({cfgsvc['clients']} tenants), 2x-saturation storm "
            f"{cfgsvc['storm']['qps']:,.0f} ok reads/s + "
            f"{cfgsvc['storm']['reads_shed']} typed refusals "
            f"(errors {cfgsvc['reads_errors']}), "
            f"{cfgsvc['writes_acked']} durable writes acked "
            f"(lost {cfgsvc['acked_lost']}, paced commits "
            f"{cfgsvc['paced_commits']}), recovery to "
            f"{cfgsvc['slo_ms']:.0f}ms SLO in "
            f"{cfgsvc['recovery_to_slo_s']}s; gates "
            f"{'ALL PASS' if cfgsvc['gated_ok'] else cfgsvc['gates']}",
            file=sys.stderr,
        )
    rtt = _soft("device_rtt", _device_rtt_ms)
    if rtt is not None:
        print(
            f"# device link round-trip floor: {rtt:.0f}ms",
            file=sys.stderr,
        )
    cfg5 = _soft("config5", _config5_union)
    if cfg5 is not None:
        print(
            f"# config5 100k-doc union (device-resident mirror, 1k "
            f"dirty): {cfg5:.1f}ms"
            + (
                f" (= ONE dispatch; link RTT floor {rtt:.0f}ms)"
                if rtt is not None
                else ""
            ),
            file=sys.stderr,
        )
    cfg6 = _soft("config6", _config6_text_trace)
    if cfg6 is not None:
        print(
            f"# config6 automerge-perf text trace (259,778 ops, 1 doc): "
            f"{cfg6[0]:.2f}s -> {cfg6[1]:,.0f} ops/s "
            f"(reference engine: ~0.4-0.9k ops/s)",
            file=sys.stderr,
        )

    if not bench_dir:
        shutil.rmtree(tmp, ignore_errors=True)

    print(
        json.dumps(
            {
                "metric": "cold_open_materialize_ops_per_sec_per_chip",
                "value": round(rate2),
                "unit": "ops/s",
                "vs_baseline": round(rate2 / host_rate, 2),
                "configs": {
                    "cold_open_s_10k_docs": round(dt2, 2),
                    "cold_first_process_s": round(dt1, 2),
                    "config1_change_latency_us": (
                        round(cfg1) if cfg1 is not None else None
                    ),
                    "config2_convergence_s": (
                        round(cfg2[0], 2) if cfg2 is not None else None
                    ),
                    "config2_edits_per_s": (
                        round(cfg2[1]) if cfg2 is not None else None
                    ),
                    "config2_live": (
                        cfg2[2] if cfg2 is not None else None
                    ),
                    "config_churn_s": (
                        round(cfgch[0], 2) if cfgch is not None else None
                    ),
                    "config_churn_edits_per_s": (
                        round(cfgch[1]) if cfgch is not None else None
                    ),
                    "config_churn": (
                        cfgch[2] if cfgch is not None else None
                    ),
                    # DHT fleet: N daemons, discovery-only topology,
                    # seeded churn; frame amplification must stay
                    # O(HM_GOSSIP_FANOUT) regardless of peer count
                    "config_swarm_s": (
                        round(cfgsw[0], 2) if cfgsw is not None else None
                    ),
                    "config_swarm": (
                        cfgsw[1] if cfgsw is not None else None
                    ),
                    # 100->1000 peer scaling: async-transport thread
                    # census (real mini-fleet) + seeded steady-state
                    # period model; frames/peer/period must stay flat
                    "config_fleet1000_s": (
                        cfgfl[0] if cfgfl is not None else None
                    ),
                    "config_fleet1000": (
                        cfgfl[1] if cfgfl is not None else None
                    ),
                    "config_crash_t_recover_ms": (
                        round(cfgcr[0], 1) if cfgcr is not None else None
                    ),
                    "config_crash": (
                        cfgcr[1] if cfgcr is not None else None
                    ),
                    "config6_live_first_edit_ms": (
                        round(cfg6l[0], 1) if cfg6l is not None else None
                    ),
                    "config6_live_burst_edits_per_s": (
                        round(cfg6l[1]) if cfg6l is not None else None
                    ),
                    "config6_live": (
                        cfg6l[2] if cfg6l is not None else None
                    ),
                    "config6_live_adopt_decode_ms": (
                        round(
                            cfg6l[2].get("t_adopt_decode", 0.0) * 1e3, 1
                        )
                        if cfg6l is not None
                        else None
                    ),
                    "config6_demote_readopt_ms": (
                        round(cfg6d[0], 1) if cfg6d is not None else None
                    ),
                    "config6_demote": (
                        cfg6d[1] if cfg6d is not None else None
                    ),
                    # per-lock-class blocking debt (ms) from the
                    # instrumented durable burst; the `live_engine`
                    # entry gates the ROADMAP write-plane split
                    "lock_held_blocking_ms": cfgld,
                    # many-writer plane: N IPC writer processes on
                    # disjoint docs vs ONE hub daemon at HM_FSYNC=1
                    "config_writers_edits_per_s": (
                        cfgwr["edits_per_s"] if cfgwr is not None
                        else None
                    ),
                    "config_writers_scaling": (
                        cfgwr["scaling"] if cfgwr is not None else None
                    ),
                    # group-commit gate: >= 2.5x from 8 to 32 writers
                    "config_writers_scaling_8_32": (
                        cfgwr.get("scaling_8_32")
                        if cfgwr is not None else None
                    ),
                    # 8 writers x ONE shared doc (per-connection
                    # actors); converged == bit-identical final views
                    "config_writers_hotdoc_edits_per_s": (
                        cfghd["edits_per_s"] if cfghd is not None
                        else None
                    ),
                    "config_writers_hotdoc_converged": (
                        cfghd["converged"] if cfghd is not None
                        else None
                    ),
                    "config3_multiactor_ops_per_s": (
                        round(cfg3[1]) if cfg3 is not None else None
                    ),
                    "config5_union_100k_ms": (
                        round(cfg5, 1) if cfg5 is not None else None
                    ),
                    # pack-plane scaling gate (ISSUE 19): 10x corpus,
                    # serial vs pooled pack; the bool is the "cold
                    # opens bounded by slab IO" regression gate
                    "config_coldopen": cfgco,
                    "config_coldopen_s": (
                        cfgco["config_coldopen_s"]
                        if cfgco is not None else None
                    ),
                    "pack_workers": (
                        cfgco["pack_workers"]
                        if cfgco is not None else None
                    ),
                    "t_pack_busy_per_worker": (
                        cfgco["t_pack_busy_per_worker"]
                        if cfgco is not None else None
                    ),
                    "coldopen_pack_speedup": (
                        cfgco["coldopen_pack_speedup"]
                        if cfgco is not None else None
                    ),
                    "coldopen_pack_bound": (
                        cfgco["coldopen_pack_bound"]
                        if cfgco is not None else None
                    ),
                    "config_read_qps": (
                        round(cfgrd[0]) if cfgrd is not None else None
                    ),
                    "config_read_p50_ms": (
                        cfgrd[1] if cfgrd is not None else None
                    ),
                    "config_read_p99_ms": (
                        cfgrd[2] if cfgrd is not None else None
                    ),
                    "config_read_host_qps": (
                        round(cfgrd[3]) if cfgrd is not None else None
                    ),
                    "config_read_speedup": (
                        round(cfgrd[0] / max(cfgrd[3], 1e-9), 1)
                        if cfgrd is not None
                        else None
                    ),
                    "config_read": (
                        cfgrd[4] if cfgrd is not None else None
                    ),
                    "config6_text_trace_ops_per_s": (
                        round(cfg6[1]) if cfg6 is not None else None
                    ),
                    # ISSUE 20: the unified traffic bench — every
                    # plane at once behind the one front door, gated
                    # on shed order / acked_lost=0 / recovery-to-SLO
                    "config_service": cfgsvc,
                    "config_service_qps": (
                        round(cfgsvc["saturation_qps"])
                        if cfgsvc is not None else None
                    ),
                    "config_service_p50_ms": (
                        cfgsvc["steady"]["read_p50_ms"]
                        if cfgsvc is not None else None
                    ),
                    "config_service_p99_ms": (
                        cfgsvc["steady"]["read_p99_ms"]
                        if cfgsvc is not None else None
                    ),
                    "config_service_recovery_s": (
                        cfgsvc["recovery_to_slo_s"]
                        if cfgsvc is not None else None
                    ),
                    "config_service_gated_ok": (
                        cfgsvc["gated_ok"]
                        if cfgsvc is not None else None
                    ),
                    "device_link_rtt_ms": (
                        round(rtt, 1) if rtt is not None else None
                    ),
                    "docs": n_docs,
                    "ops_per_doc": n_ops,
                    "stages": stages,
                    "host_serial_s": round(host_s + other_s, 2),
                    "device_s": round(dev_s, 2),
                    "pipeline": 1 if pipelined else 0,
                    "wall_critical_path_s": round(wall_cp, 2),
                    # MEASURED multi-chip cold open (config_mesh): a
                    # real overlapped run over the mesh scheduler —
                    # this retires the projection formula below
                    "multichip_8_s": (
                        cfgmesh[0] if cfgmesh is not None else None
                    ),
                    "multichip_mode": (
                        cfgmesh[1] if cfgmesh is not None else None
                    ),
                    "multichip_devices": (
                        cfgmesh[2] if cfgmesh is not None else None
                    ),
                    "multichip_topology": (
                        cfgmesh[3] if cfgmesh is not None else None
                    ),
                    "multichip_stages": (
                        {
                            k: v
                            for k, v in cfgmesh[4].items()
                            if k
                            in (
                                "slabs_per_chip",
                                "t_dispatch_chips",
                                "t_fetch_chips",
                                "rr_slabs",
                                "rr_devices",
                                "wall_critical_path",
                                "t_io_busy",
                                "t_pack_busy",
                                "t_dispatch_busy",
                                "t_fetch_busy",
                            )
                        }
                        if cfgmesh is not None
                        else None
                    ),
                    # REFERENCE ONLY — the old single-chip-stage
                    # divide-by-N estimate, kept for continuity with
                    # BENCH_r05 and earlier; multichip_8_s above is
                    # the measured number
                    "projection_8chip_reference_s": round(proj8, 2),
                },
                # round-13 observability: the process-wide registry
                # snapshot for THIS bench run (every subsystem's
                # counters in one block) + trace state. A NEW key —
                # every pre-existing key above is untouched.
                "telemetry": _telemetry_block(),
            }
        )
    )


def _telemetry_block():
    from hypermerge_tpu import telemetry

    return {
        "counters": telemetry.snapshot(),
        "tracing": telemetry.tracing_enabled(),
        "trace_spans": telemetry.event_count(),
        "trace_file": telemetry.trace_path(),
    }


if __name__ == "__main__":
    main()
