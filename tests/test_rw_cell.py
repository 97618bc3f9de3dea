"""The store read AND written (ISSUE 37): the program under concurrent
`Repo.read` / `Repo.change` held to `benchmark/reference/rw_plain.py`
(which imports nothing of the program), a written doc re-installed one
length rung up, the window between a local change's clock move and its
feed append, and the reference's own window rule on hand-worked inputs.
CPU, small sizes, seeded; counts and answers only, no clock is asserted.
"""

import json
import os
import random
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.corpora import single_writer_templates as swt  # noqa: E402
from benchmark.reference import rw_plain  # noqa: E402
from benchmark.reference.rw_plain import Read, Update  # noqa: E402
from hypermerge_tpu import telemetry  # noqa: E402
from hypermerge_tpu.repo import Repo  # noqa: E402
from hypermerge_tpu.serve.batcher import ReadRequest  # noqa: E402
from hypermerge_tpu.utils.ids import validate_doc_url  # noqa: E402

SEED = 2147483707  # over 2**31: the driver's seeds are large


def group(count, ops):
    return {"count": count, "ops": ops, "distinct": 3,
            "ops_per_change": 16, "seq_frac": 0.85, "n_keys": 10,
            "seq_key": "t", "seq_type": "text", "refs": "random"}


def write_corpus(path, groups):
    job = swt.CorpusJob(
        str(path / "repo"), {"sign": True, "groups": groups}, SEED, 2
    )
    try:
        return job, job.start().finish()
    except BaseException:
        job.abort()
        raise


def corpus_changes(job, doc):
    d = job.plan[doc]
    tpl = job.templates[d["group"]][d["template"]]
    return json.loads(json.dumps(tpl).replace(
        tpl[0]["actor"], job.pairs[doc].public_key))


def serve(name):
    return telemetry.snapshot().get("serve." + name, 0)


def read_now(repo, url, query):
    """(answer, seconds it was answered at) of one read."""
    got = []
    done = threading.Event()

    def cb(v):
        got.append((v, time.perf_counter()))
        done.set()

    repo.read(url, query, cb)
    assert done.wait(30)
    return got[0]


def pin_ladder(monkeypatch):
    """The overload ladder's p99 is a clock: on a loaded CPU it leaves
    HEALTHY, defers installs and hands reads to the host twin. Pinned,
    `serve.fallbacks` counts the tier's own logic and no clock."""
    monkeypatch.setenv("HM_SERVICE_FORCE", "healthy")


def forget_other_ladders():
    """The driver's wait for a HEALTHY ladder (`ycsb_read_loop.
    _await_healthy`) reads the PROCESS's `service.state`: the sum over
    every ladder in the registry, not this cell's own. A worker runs
    other files' tests before this one, and a ladder one of them left
    above HEALTHY (a controller a test ticked up and never closed; on
    the parent a closed repo's too, which kept its gauge) holds that
    sum over 0 for good, whatever this test pins: the wait then timed
    out beside busy workers and never alone."""
    from hypermerge_tpu import telemetry

    for m in telemetry.REGISTRY.series():
        if m.name == "service.state":
            m.set(0)


# -- the program against the reference ---------------------------------------


def test_interleaved_reads_and_changes_against_reference(
    tmp_path, monkeypatch
):
    """4 threads, a seeded interleaving of reads and one-op changes on
    6 docs: every answer inside its window, nothing lost, doubled or
    out of order on disk, and a fresh repo reads the summaries back."""
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "0")
    pin_ladder(monkeypatch)
    job, urls = write_corpus(tmp_path, [group(6, 100)])
    repo = Repo(path=job.path)
    updates = {d: [] for d in range(len(urls))}
    reads = {d: [] for d in range(len(urls))}
    lock = threading.Lock()
    errors = []
    f0 = serve("fallbacks")
    try:
        repo.open_many(urls)
        repo.back.fetch_bulk_summaries()
        queries = [
            {"kind": "text", "path": ["t"]}, {"kind": "len", "path": ["t"]},
            {"kind": "lookup", "path": ["k3"]},
            {"kind": "index", "path": ["t"], "index": 5},
        ]

        def client(tid):
            rng = random.Random(SEED + tid)
            try:
                for j in range(70):
                    doc = min(int(rng.random() ** 2 * len(urls)),
                              len(urls) - 1)
                    if rng.random() < 0.5:
                        serial = tid * 1000 + j
                        if rng.random() < 0.85:
                            op = {"kind": "ins", "v": chr(97 + j % 26)}
                            u = rng.random()

                            def fn(d, op=op, u=u):
                                t = d["t"]
                                t.insert(int(u * (len(t) + 1)), op["v"])
                        else:
                            op = {"kind": "set", "k": f"k{j % 10}",
                                  "v": 10**6 + serial}

                            def fn(d, op=op):
                                d[op["k"]] = op["v"]
                        up = Update(serial, op, time.perf_counter())
                        with lock:
                            updates[doc].append(up)
                        repo.change(urls[doc], fn, f"u{serial}")
                        up.acked = time.perf_counter()
                    else:
                        q = queries[int(rng.random() * len(queries))]
                        t0 = time.perf_counter()
                        v, t1 = read_now(repo, urls[doc], q)
                        with lock:
                            reads[doc].append(Read(q, t0, t1, v))
            except Exception as e:  # pragma: no cover - shown below
                errors.append(repr(e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        repo.close()
    assert not errors
    assert serve("fallbacks") == f0
    keys = [p.public_key for p in job.pairs]
    feeds = rw_plain.local_feeds(job.path + "/feeds", keys)
    assert set(feeds) <= set(keys)
    summaries = {}
    for doc, key in enumerate(keys):
        got = rw_plain.check_doc(
            corpus_changes(job, doc), feeds.get(key, []),
            updates[doc], reads[doc])
        assert {k: got[k] for k in rw_plain.COUNTS} == dict.fromkeys(
            rw_plain.COUNTS, 0), (doc, got["examples"])
        assert got["updates_on_disk"] == len(updates[doc])
        summaries[doc] = got["summary"]
    assert sum(len(v) for v in updates.values()) > 100
    assert sum(len(v) for v in reads.values()) > 100
    fresh = Repo(path=job.path)
    try:
        fresh.open_many(urls)
        summ = fresh.back.fetch_bulk_summaries()
        for doc, url in enumerate(urls):
            assert summ.doc(validate_doc_url(url)) == summaries[doc]
    finally:
        fresh.close()


def test_first_write_promotes_a_full_bucket_one_rung(tmp_path, monkeypatch):
    """A doc of exactly a bucket's rows, written once: re-installed one
    rung up, its answers right, the old entry's device bytes given
    back (the gauge moves by exactly the difference)."""
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "0")
    pin_ladder(monkeypatch)
    job, urls = write_corpus(tmp_path, [group(2, 256)])
    repo = Repo(path=job.path)
    try:
        repo.open_many(urls)
        repo.back.fetch_bulk_summaries()
        url, doc_id = urls[0], validate_doc_url(urls[0])
        text = {"kind": "text", "path": ["t"]}
        for u in urls:
            repo.read(u, {"kind": "len", "path": ["t"]})
        before = repo.read(url, text)
        entry = repo.back.serve._cache._entries[doc_id]
        assert (entry.n, entry.bucket) == (256, 256)
        b0 = serve("resident_device_bytes")
        c0 = {k: serve(k) for k in ("cold_reads", "reinstalls",
                                    "rung_promotions", "fallbacks",
                                    "install_host_kernel_docs")}
        repo.change(url, lambda d: d["t"].insert(0, "Z"), "u1")
        assert repo.read(url, text) == "Z" + before
        entry = repo.back.serve._cache._entries[doc_id]
        assert (entry.n, entry.bucket) == (257, 1024)
        assert serve("resident_device_bytes") - b0 == 6 * 4 * (1024 - 256)
        assert {k: serve(k) - v for k, v in c0.items()} == {
            "cold_reads": 1, "reinstalls": 1, "rung_promotions": 1,
            "fallbacks": 0, "install_host_kernel_docs": 0}
        # a second write finds a row free: the entry follows it in
        # place, nothing is installed
        a0 = serve("advances")
        repo.change(url, lambda d: d.__setitem__("k1", 7), "u2")
        assert repo.read(url, {"kind": "lookup", "path": ["k1"]}) == 7
        assert repo.back.serve._cache._entries[doc_id] is entry
        assert (entry.n, entry.bucket) == (258, 1024)
        assert serve("advances") - a0 == 1
        assert serve("rung_promotions") - c0["rung_promotions"] == 1
        assert serve("reinstalls") - c0["reinstalls"] == 1
        assert serve("resident_device_bytes") - b0 == 6 * 4 * (1024 - 256)
    finally:
        repo.close()


@pytest.mark.parametrize("followed", [False, True])
def test_read_between_clock_move_and_append_waits_for_the_writer(
    tmp_path, monkeypatch, followed
):
    """A local change moves the doc's clock, then appends its block. A
    read flushed in between must not be answered by the host twin. Where
    the write released the doc's entry, the flush waits for the writer
    to leave the doc's emission domain and installs; where the entry
    noted the change (`followed`) it is applied and the read answered,
    writer or no writer. Each round holds a writer inside that window
    and flushes a read there."""
    from hypermerge_tpu.backend.actor import Actor
    from hypermerge_tpu.serve.tier import ServeTier

    pin_ladder(monkeypatch)
    if not followed:  # every write releases the entry, as a remote's does
        hook = ServeTier.note_clock_moved
        monkeypatch.setattr(
            ServeTier, "note_clock_moved",
            lambda self, doc_id, event=None: hook(self, doc_id))
    job, urls = write_corpus(tmp_path, [group(2, 48)])
    repo = Repo(path=job.path)
    inside = threading.Event()
    go = threading.Event()
    real = Actor.write_change

    def held(self, change):
        inside.set()
        assert go.wait(30)
        return real(self, change)

    monkeypatch.setattr(Actor, "write_change", held)
    rounds = 400
    try:
        repo.open_many(urls)
        repo.back.fetch_bulk_summaries()
        url, doc_id = urls[0], validate_doc_url(urls[0])
        n0 = repo.read(url, {"kind": "len", "path": ["t"]})
        f0, w0 = serve("fallbacks"), serve("cold_reads")
        for i in range(rounds):
            inside.clear()
            go.clear()
            writer = threading.Thread(target=repo.change, args=(
                url, lambda d: d["t"].insert(0, "w"), f"u{i}"))
            writer.start()
            assert inside.wait(30)
            # the clock has moved, the block is not appended: flush now
            got = []
            req = ReadRequest(doc_id, {"kind": "len", "path": ["t"]},
                              lambda p: got.append(p["value"]))
            req.t0 = time.perf_counter()
            flush = threading.Thread(
                target=repo.back.serve._flush, args=([req],))
            flush.start()
            time.sleep(0.0005)
            go.set()
            flush.join(30)
            writer.join(30)
            assert got == [n0 + i + 1]
            assert serve("fallbacks") == f0, (
                i, repo.back.serve.residency_report())
        # followed: cold only where the bucket was full (48 rows at
        # rung 64, then at 256: a promotion each)
        assert serve("cold_reads") - w0 == (2 if followed else rounds)
    finally:
        go.set()
        repo.close()


def test_rehearsal_cell_follows_writes_in_place(tmp_path, monkeypatch):
    """`rw.ycsb-a` at its rehearsal size through the harness's own
    cell and driver (ISSUE 38): every check of the driver at 0, writes
    followed in place (`serve.advances` > 0), no lane from the host
    kernel, fewer re-installs than writes that met an entry; the new
    metric file reads the counters' ratio, and nothing from a program
    without them (the parent)."""
    import argparse
    from unittest import mock

    from benchmark import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    args = argparse.Namespace(
        workload="rw.ycsb-a", seed=SEED, seconds=2.0, trace=0,
        rehearse=True, control=False, mix=None,
    )
    host0 = serve("install_host_kernel_docs")  # the process's, so far
    with mock.patch.dict(os.environ):
        pin_ladder(monkeypatch)  # a CPU's p99 is no pressure
        forget_other_ladders()  # nor is another test's ladder
        cell = harness.Cell(args, bench, time.perf_counter())
        cell.work = str(tmp_path / "run")
        harness.apply_env(cell)
        driver = harness.load_module("drivers", cell.mix["driver"])
        os.makedirs(cell.work)
        early = driver.before_jax(cell)
        cell.cache_watch = harness.CacheWatch()
        state = driver.setup(cell, early)
        try:
            before = cell.counters()
            win = driver.window(cell, state, float(args.seconds))
            after = cell.counters()
            checks = driver.verify(cell, state, win)
        finally:
            driver.teardown(cell, state)
    assert win.failed == 0 and win.attempted > 0
    # (the driver's `lanes_from_host_kernel` reads the process's total:
    # other tests' installs are in it here)
    assert [c.name for c in checks if not c.ok] in (
        [], ["lanes_from_host_kernel"] if host0 else [])
    assert serve("install_host_kernel_docs") == host0
    moved = {k: after[k] - before.get(k, 0) for k in after
             if k.startswith("serve.")}
    assert moved["serve.advances"] > 0
    assert moved["serve.advance_dispatches"] > 0
    assert moved["serve.install_host_kernel_docs"] == 0
    assert moved["serve.fallbacks"] == moved["serve.flush_errors"] == 0
    met = moved["serve.advance_notes"] + moved["serve.advance_refusals"]
    assert moved["serve.reinstalls"] < met
    assert moved["serve.invalidations"] == moved["serve.advance_refusals"]
    # the metric: data only, over a reader the benchmark had
    name = "serve.advance_refusal_share"
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as fh:
        spec = json.load(fh)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    # appended by PR 38; what later PRs append follows it
    assert bench["per_layer"].index(entry) == 81
    assert spec["reader"] == "counter_ratio"
    assert entry["workloads"] == spec["cells"] == ["rw.ycsb-a"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key]
    cell.bench = dict(bench, per_layer=[entry])
    obs = dict(win.obs, counters_before=before, counters_after=after)
    got = harness.layer_metrics(cell, obs)
    assert got[name]["value"] == (
        moved["serve.advance_refusals"] / met)
    older = {k: v for k, v in after.items() if "advance" not in k}
    assert harness.layer_metrics(
        cell, dict(obs, counters_before=older, counters_after=older)) == {}


# -- the frontend's queue, with the backend's messages held ------------------


class Held:
    """A repo whose frontend -> backend messages are held back and
    handed over one at a time: the order they were SENT in is the order
    the backend handles them in, whichever thread drains its queue."""

    def __init__(self, repo, monkeypatch):
        self.repo = repo
        self.sent = []
        monkeypatch.setattr(repo.front.to_backend, "push", self.sent.append)

    def kinds(self):
        return [m["query"]["type"] if m["type"] == "Query" else m["type"]
                for m in self.sent]

    def deliver(self, n=1):
        for _ in range(n):
            self.repo.back.receive(self.sent.pop(0))


def test_a_read_follows_the_changes_made_before_it(monkeypatch):
    """`change` returns while its request waits for the echo of the one
    before: a read asked for after it is sent after it, not past it."""
    repo = Repo(memory=True)
    try:
        url = repo.create({"n": 0})
        held = Held(repo, monkeypatch)
        got = []
        repo.change(url, lambda d: d.__setitem__("n", 1))
        repo.change(url, lambda d: d.__setitem__("n", 2))  # waits: echo
        repo.read(url, {"kind": "lookup", "path": ["n"]}, got.append)
        assert held.kinds() == ["Request"]
        held.deliver()  # the echo of n=1: the next change goes out
        assert held.kinds() == ["Request"] and got == []
        held.deliver()  # the echo of n=2: now the read
        assert held.kinds() == ["Read"]
        held.deliver()
        assert repo.back.serve.flush_now() and got == [2]
        # nothing waits: a read goes straight out
        repo.read(url, {"kind": "lookup", "path": ["n"]}, got.append)
        assert held.kinds() == ["Read"]
    finally:
        repo.close()


def test_first_write_of_a_bulk_opened_doc_waits_for_its_actor(
    tmp_path, monkeypatch
):
    """A bulk-opened doc has no actor until it is written. Its Ready
    may be handled (by another thread's drain) before the NeedsActorId
    the change sent: the change then stays queued for the actor and is
    not run as a change of no actor."""
    job, urls = write_corpus(tmp_path, [group(2, 48)])
    repo = Repo(path=job.path)
    try:
        repo.open_many(urls)
        repo.back.fetch_bulk_summaries()
        held = Held(repo, monkeypatch)
        repo.change(urls[0], lambda d: d["t"].insert(0, "w"), "u1")
        assert held.kinds() == ["Open", "NeedsActorId"]
        held.deliver()  # Ready, with no actor
        assert held.kinds() == ["NeedsActorId"]
        held.deliver()  # ActorId: the change runs, under that actor
        assert held.kinds() == ["Request"]
        actor = held.sent[0]["request"]["actor"]
        assert isinstance(actor, str) and len(actor) > 20
        n0 = job.plan[0]["n_ops"]
        held.deliver()
        monkeypatch.undo()
        assert repo.read(urls[0], {"kind": "text", "path": ["t"]})[0] == "w"
        assert n0 == 48
    finally:
        repo.close()


# -- the reference's window rule on hand-worked inputs -----------------------

A = "aaa"
L = "lll"
CORPUS = [{"actor": A, "seq": 1, "startOp": 1, "deps": {}, "ops": [
    {"a": 2, "o": "0@_root", "k": "t"},
    {"a": 4, "o": f"1@{A}", "r": "0@_head", "i": True, "v": "x"}]}]
TEXT = {"kind": "text", "path": ["t"]}


def block(seq, serial, char, ref):
    """The local feed's seq-th change: `char` inserted after `ref`."""
    return {"actor": L, "seq": seq, "startOp": 2 + seq, "deps": {A: 1},
            "message": f"u{serial}", "ops": [
                {"a": 4, "o": f"1@{A}", "r": ref, "i": True, "v": char}]}


def ins(serial, char, sent, acked):
    return Update(serial, {"kind": "ins", "v": char}, sent, acked)


# u1 inserts "a" after x (sent 1, acked 2); u2 inserts "b" after a
# (sent 5, acked 6): the text is "x", "xa", "xab" at prefixes 0, 1, 2
FEED = [block(1, 1, "a", f"2@{A}"), block(2, 2, "b", f"3@{L}")]
UPDATES = [ins(1, "a", 1.0, 2.0), ins(2, "b", 5.0, 6.0)]
ZERO = dict.fromkeys(rw_plain.COUNTS, 0)


@pytest.mark.parametrize("name,feed,updates,reads,want", [
    ("sound", FEED, UPDATES, [
        Read(TEXT, 0.0, 0.5, "x"),      # before anything was sent
        Read(TEXT, 1.5, 1.8, "x"),      # u1 sent, not acked: either
        Read(TEXT, 1.5, 1.8, "xa"),
        Read(TEXT, 3.0, 4.0, "xa"),     # u1 acked, u2 not sent
        Read(TEXT, 5.5, 7.0, "xab"),    # u2 in flight when sent
        Read(TEXT, 6.5, 7.0, "xab"),
    ], {}),
    ("a stale answer", FEED, UPDATES,
     [Read(TEXT, 3.0, 4.0, "x")], {"answers_outside_their_window": 1}),
    ("a future answer", FEED, UPDATES,
     [Read(TEXT, 3.0, 4.0, "xab")], {"answers_outside_their_window": 1}),
    ("an answer no prefix gives", FEED, UPDATES,
     [Read(TEXT, 6.5, 7.0, "xba")], {"answers_outside_their_window": 1}),
    ("a lost update", FEED[:1], UPDATES,
     [Read(TEXT, 6.5, 7.0, "xa")], {"acked_lost": 1}),
    ("an update that raised may be missing", FEED[:1],
     [UPDATES[0], ins(2, "b", 5.0, None)],
     [Read(TEXT, 6.5, 7.0, "xa")], {}),
    ("a doubled update", FEED + [block(3, 2, "b", f"3@{L}")], UPDATES, [],
     {"updates_twice_or_unknown": 1}),
    ("a block nobody sent", FEED + [block(3, 9, "c", f"3@{L}")], UPDATES,
     [], {"updates_twice_or_unknown": 1}),
    ("other content than sent", [block(1, 1, "q", f"2@{A}")],
     UPDATES[:1], [], {"updates_twice_or_unknown": 1, "acked_lost": 1}),
    ("acknowledged first, on disk second",
     [block(1, 2, "b", f"2@{A}"), block(2, 1, "a", f"2@{A}")], UPDATES, [],
     {"updates_out_of_order": 1}),
])
def test_window_rule_on_hand_worked_inputs(name, feed, updates, reads, want):
    got = rw_plain.check_doc(CORPUS, feed, updates, reads)
    assert {k: got[k] for k in rw_plain.COUNTS} == dict(ZERO, **want), name


def test_reference_reads_the_program_s_change_frames():
    """`wire_change` decodes the binary frame a one-op change is stored
    as from its documented layout, to what the program encoded."""
    from hypermerge_tpu.crdt import codec

    change = {"actor": L, "deps": {A: 64}, "message": "u\"7\\",
              "ops": [{"a": 4, "o": f"1@{A}", "r": f"9@{A}", "i": True,
                       "v": "é"},
                      {"a": 4, "o": "0@_root", "k": "k3", "v": 1000017,
                       "p": [f"5@{A}", f"7@{L}"]}],
              "seq": 3, "startOp": 1027, "time": 0}
    frame = codec.encode_change(change)
    assert frame[:2] == b"\xc5\x01"
    assert rw_plain.wire_change(frame) == change
    assert rw_plain.wire_change(json.dumps(change).encode()) == change
