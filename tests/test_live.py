"""Live apply engine (backend/live.py) — the HM_LIVE=1/0 twin contract.

The live path routes incremental changes on lazy (bulk-loaded) docs
through per-tick batched kernel dispatches; HM_LIVE=0 is the host-OpSet
correctness twin. Pinned here:

- no host replay: the deferred loader is NEVER invoked for live
  local/remote changes (the acceptance bar for the batched live path);
- fuzz twin: a randomized multi-actor workload (concurrent maps,
  lists, text, counters, deletes, nested objects, cross-site merges)
  delivered in BOTH orders produces bit-identical local patch echoes,
  clocks, snapshot patches, and frontend state across HM_LIVE=1/0;
- LiveColumns: appending a change stream incrementally decodes to the
  same state as packing the full history.
"""

import os
import random
import shutil
import tempfile

import pytest

from helpers import Site, plainify, random_mutation, sync, wait_until
from lockdep_fixture import lockdep_suite
from racedep_fixture import racedep_suite
from hypermerge_tpu.models import Text
from hypermerge_tpu.repo import Repo
from hypermerge_tpu.utils.ids import validate_doc_url

_lockdep_suite = lockdep_suite()
# the live twin suite doubles as the guard-map verifier: every
# declared shared field races through here fully instrumented
# (tests/racedep_fixture.py), asserted clean at teardown
_racedep_suite = racedep_suite()


@pytest.fixture
def live_env(monkeypatch):
    monkeypatch.setenv("HM_LIVE", "1")


def _seed_dir(tmp, n_changes=6, seed=7):
    """A stored single-writer doc on disk + its history snapshot."""
    repo = Repo(path=tmp)
    url = repo.create({"edits": [], "t": Text("hi")})
    r = random.Random(seed)
    for _ in range(n_changes):
        repo.change(url, lambda d: d["edits"].append(r.randint(0, 99)))
    repo.change(url, lambda d: d["t"].insert(2, "!"))
    doc_id = validate_doc_url(url)
    stored = list(repo.back.docs[doc_id].opset.history)
    repo.close()
    return url, doc_id, stored


def test_live_path_never_invokes_lazy_loader(tmp_path, live_env):
    """Acceptance: no full host replay on the first live change to a
    bulk-loaded doc — local AND remote."""
    from hypermerge_tpu.crdt.change import Action, Change, Op, ROOT

    url, doc_id, stored = _seed_dir(str(tmp_path))
    repo = Repo(path=str(tmp_path))
    repo.back.load_documents_bulk([doc_id])
    doc = repo.back.docs[doc_id]
    assert doc.opset is None and doc._lazy_loader is not None

    calls = []
    orig = doc._lazy_loader

    def spy():
        calls.append(1)
        return orig()

    doc._lazy_loader = spy

    # local change: resolves through the engine, no replay
    repo.change(url, lambda d: d.__setitem__("new", 1))
    assert repo.doc(url)["new"] == 1
    assert doc.opset is None and not calls

    # remote change from another actor: ticks through the engine
    peer = Site("peerpeerpeer0001")
    peer.receive(stored + [c for c in _local_changes(repo, doc_id)])
    ch, _ = peer.change(lambda d: d.__setitem__("remote", 2))
    doc.apply_remote_changes([ch])
    wait_until(lambda: repo.doc(url).get("remote") == 2)
    assert doc.opset is None and not calls

    # explicit history APIs still replay (and don't corrupt live state)
    hist = doc.materialize_at(doc.history_len)
    assert plainify(hist)["new"] == 1
    assert calls, "time travel should use the host replay"
    assert doc.opset is None
    repo.close()


def _local_changes(repo, doc_id):
    """The doc's applied changes as Change objects (from the feeds)."""
    out = []
    for actor_id, end in repo.back.docs[doc_id].clock.items():
        actor = repo.back._get_or_create_actor(actor_id)
        out.extend(actor.changes_in_window(0, end))
    return out


def _gen_remote_script(stored, seed, n_rounds=10):
    """Deterministic multi-actor change batches extending `stored`:
    two peers mutate concurrently and merge periodically."""
    r = random.Random(seed)
    peers = [Site(f"peer{i:1d}0000000000001") for i in range(2)]
    for p in peers:
        p.receive(stored)
    script = []  # [(peer_idx, [Change, ...])]
    for rnd in range(n_rounds):
        idx = r.randrange(2)
        site = peers[idx]
        batch = []
        for _ in range(r.randint(1, 3)):
            before = len(site.opset.history)
            random_mutation(site, r)
            batch.extend(site.opset.history[before:])
        if batch:
            script.append((idx, batch))
        if rnd % 3 == 2:
            sync(*peers)
    return script


def _run_workload(base_dir, live, order_flip, seed=13):
    """Replay the same remote script + local edits against a copy of
    the seeded repo under HM_LIVE=`live`; returns the observable
    outcome (local patch echoes, clock, snapshot, frontend state)."""
    os.environ["HM_LIVE"] = live
    work = tempfile.mkdtemp()
    shutil.rmtree(work)
    shutil.copytree(base_dir, work)
    try:
        repo = Repo(path=work)
        with open(os.path.join(base_dir, "_meta")) as fh:
            url, doc_id = fh.read().split()
        local_patches = []
        orig_push = repo.back.to_frontend.push

        def record(msg):
            if msg.get("type") == "Patch" and msg["patch"].get("actor"):
                local_patches.append(msg["patch"])
            orig_push(msg)

        repo.back.to_frontend.push = record
        h = repo.open(url)
        assert h.value(timeout=20) is not None
        doc = repo.back.docs[doc_id]
        stored = _local_changes(repo, doc_id)
        script = _gen_remote_script(stored, seed)
        if order_flip:
            # deliver each peer's stream order-preserved, but peer 1's
            # batches first — later batches park on unmet deps until
            # the other peer's stream arrives (both paths must park
            # identically)
            script = [b for b in script if b[0] == 1] + [
                b for b in script if b[0] == 0
            ]
        # an OpSet oracle tracks exactly which changes are applicable
        # after each delivery (parking semantics included), so the two
        # modes pause at identical states before each local edit
        from hypermerge_tpu.crdt.opset import OpSet

        oracle = OpSet()
        oracle.apply_changes(stored)
        peer_actors = set()
        for k, (_idx, batch) in enumerate(script):
            oracle.apply_changes(list(batch))
            peer_actors.update(c.actor for c in batch)
            doc.apply_remote_changes(list(batch))
            wait_until(
                lambda: all(
                    doc.clock.get(a, 0) == oracle.clock.get(a, 0)
                    for a in peer_actors
                )
            )
            # interleaved local edits (state-shape-independent)
            repo.change(url, lambda d, k=k: d.__setitem__(f"k{k}", k))
            repo.change(
                url, lambda d, k=k: d["edits"].append(1000 + k)
            )
        if repo.back.live is not None:
            repo.back.live.flush_now()
        import json

        outcome = {
            "snap": doc.snapshot_patch().to_json(),
            "clock": dict(doc.clock),
            "hist": doc.history_len,
            "state": plainify(h.value()),
            "local_patches": local_patches,
        }
        # the writable actor is minted fresh per reopen (its key is not
        # in the doc url): normalize it BEFORE the sorted dump, so key
        # ordering can't differ between runs
        actor_id = doc.actor_id
        repo.close()

        def scrub(v):
            if isinstance(v, str):
                return v.replace(actor_id, "<LOCAL-ACTOR>")
            if isinstance(v, dict):
                return {scrub(k): scrub(x) for k, x in v.items()}
            if isinstance(v, (list, tuple)):
                return [scrub(x) for x in v]
            return v

        return json.dumps(scrub(outcome), sort_keys=True, default=str)
    finally:
        shutil.rmtree(work, ignore_errors=True)


@pytest.mark.parametrize("order_flip", [False, True], ids=["fwd", "rev"])
def test_live_twin_fuzz_bit_identical(tmp_path, order_flip):
    """HM_LIVE=1 and HM_LIVE=0 produce bit-identical local patch
    echoes, clocks, snapshot patches, and frontend state on a
    randomized multi-actor workload, in both delivery orders."""
    base = str(tmp_path / "seed")
    os.makedirs(base)
    old = os.environ.get("HM_LIVE")
    try:
        os.environ["HM_LIVE"] = "0"
        url, doc_id, _stored = _seed_dir(base)
        with open(os.path.join(base, "_meta"), "w") as fh:
            fh.write(f"{url} {doc_id}")
        host = _run_workload(base, "0", order_flip)
        live = _run_workload(base, "1", order_flip)
    finally:
        if old is None:
            os.environ.pop("HM_LIVE", None)
        else:
            os.environ["HM_LIVE"] = old
    # ONE normalized comparison covers clocks, history length, frontend
    # state, the snapshot patch, and every local patch echo
    # patch-for-patch (the live engine's local resolution mirrors
    # OpSet.apply_local_request; `time` never appears in patches)
    assert live == host


def test_live_columns_append_matches_full_pack():
    """Appending a causal change stream to LiveColumns decodes to the
    same state as adopting the fully packed history (the no-repack
    invariant of the live cache) — and both match the OpSet snapshot."""
    from hypermerge_tpu.backend.live import (
        _decode_state,
        _diff_states,
        _DocState,
    )
    from hypermerge_tpu.ops.columnar import (
        LiveColumns,
        causal_sort,
        pack_docs,
    )

    for seed in range(4):
        r = random.Random(seed * 991)
        sites = [Site(f"s{i}000000000001") for i in range(3)]
        for _ in range(25):
            random_mutation(r.choice(sites), r)
            if r.random() < 0.3:
                sync(*sites)
        sync(*sites)
        changes = causal_sort(
            [c for s in sites for c in s.opset.history]
        )

        incremental = LiveColumns()
        incremental.append_changes(changes)
        batch = pack_docs([changes])
        adopted = LiveColumns.from_batch(batch, 0)

        def state_of(lv):
            return _decode_state(lv, _run_host(lv))

        s_inc = state_of(incremental)
        s_full = state_of(adopted)
        d_inc = [d.to_json() for d in _diff_states(_DocState(), s_inc)]
        d_full = [
            d.to_json() for d in _diff_states(_DocState(), s_full)
        ]
        assert d_inc == d_full
        # ...and both agree with the host OpSet snapshot
        opset = sites[0].opset
        want = [d.to_json() for d in opset.snapshot_patch().diffs]
        assert d_inc == want


def test_diff_states_streams_detached_object_updates():
    """Kernel-tick deltas must include mutations to objects the
    frontend still holds but that are currently DETACHED (a concurrent
    winner displaced their link). The host path streams those diffs
    (FrontendDoc retains detached objects and applies them), so a
    later re-attach links a CURRENT copy — dropping them would leave
    the live frontend stale and diverge from the HM_LIVE=0 twin."""
    from hypermerge_tpu.backend.live import (
        _diff_states,
        _DocState,
        _Obj,
        _Val,
    )
    from hypermerge_tpu.crdt.change import ROOT, OpId

    x = OpId(1, "actorA")

    def mk_state(x_val):
        st = _DocState()
        st.objs[x] = _Obj("map")
        st.objs[x].fields["inner"] = {
            OpId(2, "actorA"): _Val(x_val, False, None)
        }
        # root key 'a' holds the SET that displaced X's link
        st.objs[ROOT].fields["a"] = {
            OpId(3, "actorB"): _Val(5, False, None)
        }
        return st

    old = mk_state("old")
    new = mk_state("new")
    old.reachable = {ROOT, x}  # frontend got X before the detach
    diffs = _diff_states(old, new)
    assert any(
        d.action == "set" and d.obj == str(x) and d.value == "new"
        for d in diffs
    ), [d.to_json() for d in diffs]
    assert x in new.reachable  # successive ticks keep streaming it


def _run_host(lv):
    import numpy as np

    from hypermerge_tpu.ops.host_kernel import _host_doc_kernel

    n = lv.n
    A = max(1, len(lv.actors.items))
    K = max(1, len(lv.keys.items))
    c = lv.cols
    return _host_doc_kernel(
        c["action"][:n], lv.slots(), c["ctr"][:n],
        np.zeros(n, np.int32), c["obj"][:n], c["key"][:n],
        c["ref"][:n], c["insert"][:n], c["value"][:n],
        lv.psrc[: lv.n_preds], lv.ptgt[: lv.n_preds],
        np.arange(A, dtype=np.int32), A, K,
    )


def test_adopt_refused_missing_actor_creates_no_feed(tmp_path, live_env):
    """A refused adoption (the serving clock names an actor we hold no
    feed for) must NOT materialize an empty actor feed on disk — the
    old _get_or_create_actor lookup registered + announced a phantom
    feed (feed_info row, feeds/ directory entry) as a side effect of
    merely refusing."""
    import os as _os

    url, doc_id, _ = _seed_dir(str(tmp_path))
    repo = Repo(path=str(tmp_path))
    repo.back.load_documents_bulk([doc_id])
    doc = repo.back.docs[doc_id]
    assert doc.opset is None and doc._lazy_loader is not None
    bogus = "zzbogusactorzzzzzzzzzzzzzzzzzzzz"
    with doc._lock:
        doc._lazy_clock[bogus] = 3  # feed we can never serve
    # first live change: adoption must refuse (missing feed) and the
    # host path must still apply the change correctly
    repo.change(url, lambda d: d.__setitem__("after", 1))
    assert repo.doc(url)["after"] == 1
    assert repo.back.live.stats["refused"] == 1
    assert doc.opset is not None  # host fallback took over
    # no phantom feed materialized anywhere
    assert bogus not in repo.back.actors
    assert repo.back.feeds.get_feed(bogus) is None
    feed_path = _os.path.join(
        str(tmp_path), "feeds", bogus[:2], bogus
    )
    assert not _os.path.exists(feed_path)
    repo.close()


def test_adoption_reachability_lanes_twin():
    """The adoption path's lane-driven reachability (winner-link forest
    from map_winner/elem_winner) is bit-identical to both the state
    walk and the full snapshot diff walk, on randomized multi-actor
    docs (nested objects, deletes, counters, text)."""
    from hypermerge_tpu.backend.live import (
        _compute_reachable,
        _decode_state,
        _diff_states,
        _DocState,
        _reachable_from_lanes,
    )
    from hypermerge_tpu.ops.columnar import (
        LiveColumns,
        causal_sort,
        pack_docs,
    )

    for seed in range(6):
        r = random.Random(seed * 7919)
        sites = [Site(f"r{i}000000000001") for i in range(3)]
        for _ in range(30):
            random_mutation(r.choice(sites), r)
            if r.random() < 0.3:
                sync(*sites)
        sync(*sites)
        changes = causal_sort(
            [c for s in sites for c in s.opset.history]
        )
        batch = pack_docs([changes])
        lv = LiveColumns.from_batch(batch, 0)
        lanes = _run_host(lv)
        st = _decode_state(lv, lanes)
        from_lanes = _reachable_from_lanes(lv, lanes)
        st_walk = _decode_state(lv, lanes)
        _compute_reachable(st_walk)
        st_diff = _decode_state(lv, lanes)
        _diff_states(_DocState(), st_diff)  # sets reachable
        assert from_lanes == st_walk.reachable == st_diff.reachable, (
            seed,
            sorted(map(str, from_lanes ^ st_diff.reachable)),
        )
        assert st.inc == st_diff.inc


def test_other_docs_tick_during_adoption(tmp_path, live_env):
    """The engine lock is NOT held across an adoption build: while one
    doc's pack+kernel+decode is in flight (a replication thread), a
    different hot doc's remote changes admit AND its tick emits.
    Deterministic — the build blocks until the other doc's edit lands,
    so a regression (build back under the engine lock) stalls the
    admission/tick and fails the wait, instead of flaking on timing."""
    import threading as _th

    repo = Repo(path=str(tmp_path))
    url_a = repo.create({"n": 0})
    url_b = repo.create({"n": 0})
    for k in range(8):
        repo.change(url_a, lambda d, k=k: d.__setitem__("n", k))
        repo.change(url_b, lambda d, k=k: d.__setitem__("n", k))
    ids = [validate_doc_url(u) for u in (url_a, url_b)]
    stored = {i: _local_changes(repo, ids[i]) for i in range(2)}
    repo.close()

    repo2 = Repo(path=str(tmp_path))
    repo2.back.load_documents_bulk(ids)
    eng = repo2.back.live
    doc_a, doc_b = (repo2.back.docs[i] for i in ids)
    peers = []
    for i in range(2):
        p = Site(f"stall{i:1d}000000001")
        p.receive(stored[i])
        peers.append(p)
    # adopt A up front (one remote edit + tick)
    ch_a0, _ = peers[0].change(lambda d: d.__setitem__("r", 0))
    doc_a.apply_remote_changes([ch_a0])
    eng.flush_now()
    wait_until(lambda: repo2.doc(url_a).get("r") == 0)

    started = _th.Event()
    observed = _th.Event()
    orig = eng._adopt_build

    def gated_build(doc):
        out = orig(doc)
        started.set()
        assert observed.wait(20), "ticks stalled during adoption build"
        return out

    eng._adopt_build = gated_build
    ch_b, _ = peers[1].change(lambda d: d.__setitem__("r", 1))
    t = _th.Thread(
        target=lambda: doc_b.apply_remote_changes([ch_b])
    )  # a replication thread adopting doc B
    t.start()
    assert started.wait(20)
    # B's adoption build is mid-flight: A's remote change must still
    # admit (serving clock advances) and its tick must emit
    ch_a1, _ = peers[0].change(lambda d: d.__setitem__("during", 3))
    doc_a.apply_remote_changes([ch_a1])
    wait_until(lambda: repo2.doc(url_a).get("during") == 3)
    observed.set()
    t.join(20)
    assert not t.is_alive()
    eng.flush_now()
    wait_until(lambda: repo2.doc(url_b).get("r") == 1)
    assert eng.stats["adopted"] == 2
    assert eng.stats["refused"] == 0
    repo2.close()


def test_emission_reentry_never_waits_on_adoption_gate(
    tmp_path, live_env
):
    """A thread that already holds the engine (emission) lock — a
    frontend callback re-entering the repo mid-emission — must NOT
    wait on another thread's in-flight adoption gate: the builder
    needs that lock to install, so waiting with it held would wedge
    every emission. The guard answers host-path (None/False)
    immediately instead."""
    import threading as _th

    repo = Repo(path=str(tmp_path))
    url = repo.create({"n": 0})
    for k in range(6):
        repo.change(url, lambda d, k=k: d.__setitem__("n", k))
    doc_id = validate_doc_url(url)
    stored = _local_changes(repo, doc_id)
    repo.close()

    repo2 = Repo(path=str(tmp_path))
    repo2.back.load_documents_bulk([doc_id])
    eng = repo2.back.live
    doc = repo2.back.docs[doc_id]
    peer = Site("reent00000000001")
    peer.receive(stored)
    ch, _ = peer.change(lambda d: d.__setitem__("r", 1))

    started = _th.Event()
    release = _th.Event()
    orig = eng._adopt_build

    def gated_build(d):
        out = orig(d)
        started.set()
        assert release.wait(20)
        return out

    eng._adopt_build = gated_build
    builder = _th.Thread(
        target=lambda: doc.apply_remote_changes([ch])
    )
    builder.start()
    assert started.wait(20)
    # simulate the re-entry: this thread holds the emission lock and
    # submits for the doc whose adoption is mid-build elsewhere
    results = []

    def under_lock():
        with eng._lock:
            results.append(eng.submit_remote(doc, [ch]))

    probe = _th.Thread(target=under_lock)
    probe.start()
    probe.join(5)
    deadlocked = probe.is_alive()
    release.set()  # let the builder finish either way
    builder.join(20)
    probe.join(5)
    assert not deadlocked, (
        "emission-lock holder blocked on the adoption gate"
    )
    assert results == [False]  # host path, answered immediately
    eng.flush_now()
    wait_until(lambda: repo2.doc(url).get("r") == 1)
    repo2.close()


def test_live_reopen_serves_fresh_snapshot(tmp_path, live_env):
    """A handle reopened on a live-adopted doc gets the CURRENT state
    (the engine's snapshot twin), not the stale bulk-load decode."""
    url, doc_id, _ = _seed_dir(str(tmp_path))
    repo = Repo(path=str(tmp_path))
    h1 = repo.open(url)
    assert h1.value(timeout=20) is not None
    repo.change(url, lambda d: d.__setitem__("fresh", True))
    h1.close()
    repo.back.close_doc(doc_id)  # drop doc + live state entirely
    h2 = repo.open(url)
    wait_until(lambda: (h2.value(timeout=5) or {}).get("fresh"))
    repo.close()


def test_live_tick_batches_multiple_docs(tmp_path, live_env):
    """A burst across several lazy docs coalesces into shared ticks
    (the O(ticks) dispatch claim, visible in the engine stats)."""
    repo = Repo(path=str(tmp_path))
    urls = [repo.create({"i": i, "edits": []}) for i in range(6)]
    ids = [validate_doc_url(u) for u in urls]
    stored = {
        i: _local_changes(repo, ids[i]) for i in range(len(urls))
    }
    repo.close()

    repo2 = Repo(path=str(tmp_path))
    repo2.back.load_documents_bulk(ids)
    peers = []
    for i, did in enumerate(ids):
        p = Site(f"burst{i:1d}000000001")
        p.receive(stored[i])
        peers.append(p)
    # one coalesced burst: every doc gets a remote change in the same
    # tick window
    for i, did in enumerate(ids):
        ch, _ = peers[i].change(lambda d, i=i: d.__setitem__("r", i))
        repo2.back.docs[did].apply_remote_changes([ch])
    repo2.back.live.flush_now()
    for i, u in enumerate(urls):
        wait_until(lambda i=i, u=u: repo2.doc(u).get("r") == i)
    stats = repo2.back.live.stats
    assert stats["adopted"] == len(urls)
    assert stats["tick_changes"] >= len(urls)
    assert stats["ticks"] <= stats["tick_changes"], stats
    for did in ids:
        assert repo2.back.docs[did].opset is None
    repo2.close()


# ---------------------------------------------------------------------------
# the liveness vector (ISSUE 45): a sequence object's `alive` bytes
# beside `order`, and every live index a count over them


def _naive_live_index(obj, elem):
    """The live index as the engine computed it before ISSUE 45 (and
    as `OpSet._live_index` still does): a walk of `order`. The oracle
    the vector's count is held to."""
    idx = 0
    for e in obj.order:
        if e == elem:
            return idx
        if obj.fields.get(e):
            idx += 1
    return idx


def _live_vectors(repo, doc):
    """`_assert_vectors` over a live doc's state, under its emission
    domain (the state's declared guard)."""
    with doc.emission:
        with repo.back.live._lock:
            ld = repo.back.live._docs[doc.id]
        return _assert_vectors(ld.state)


def _assert_vectors(state):
    """Every sequence object's vector is its elems' liveness, byte for
    byte, and `live()` is the walk's answer. Returns the sequences."""
    seqs = [o for o in state.objs.values() if o.is_sequence]
    for obj in seqs:
        assert len(obj.alive) == len(obj.order)
        assert bytes(obj.alive) == bytes(
            1 if obj.fields[e] else 0 for e in obj.order
        )
        assert obj.live() == [e for e in obj.order if obj.fields.get(e)]
    return seqs


def _drop(seq, i):
    """Delete position i of a list or a text proxy."""
    if hasattr(seq, "delete"):
        seq.delete(i)
    else:
        del seq[i]


def _seq_mutation(site, r):
    """One random change, sequence ops mostly: inserts at random
    positions of a list and a text, deletes, element sets, nested
    lists / texts made mid-stream and written into."""

    def fn(d):
        lst, txt = d["l"], d["t"]
        choice = r.random()
        if choice < 0.25:
            lst.insert(r.randint(0, len(lst)), r.randint(0, 99))
        elif choice < 0.45:
            txt.insert(r.randint(0, len(txt)), r.choice("xyz"))
        elif choice < 0.6:
            seq = r.choice((lst, txt))
            if len(seq) > 0:
                _drop(seq, r.randint(0, len(seq) - 1))
        elif choice < 0.75:
            if len(lst) > 0:
                lst[r.randint(0, len(lst) - 1)] = r.randint(100, 199)
        elif choice < 0.85:
            made = r.choice(([r.randint(0, 9)], Text("ab")))
            lst.insert(r.randint(0, len(lst)), made)
        else:
            nested = [x for x in lst if hasattr(x, "insert")]
            if nested:
                seq = r.choice(nested)
                if len(seq) > 0 and r.random() < 0.4:
                    _drop(seq, r.randint(0, len(seq) - 1))
                else:
                    seq.insert(r.randint(0, len(seq)), "n")
            else:
                d["k"] = r.randint(0, 9)

    site.change(fn)


def _seq_script(stored, seed, n_rounds=12):
    """Change batches from two peers that extend `stored`, in an order
    that is causal: random sequence edits, and in rounds 3 and 7 a
    CONCURRENT set and delete of one list element, once delivered set
    first (the delete then finds its pred gone: the elem stays) and
    once delete first (the set then revives a tombstone)."""
    r = random.Random(seed)
    peers = [Site(f"peer{i:1d}0000000000001") for i in range(2)]
    for p in peers:
        p.receive(stored)
    script = []

    def step(idx, fn=None):
        site = peers[idx]
        before = len(site.opset.history)
        if fn is None:
            _seq_mutation(site, r)
        else:
            site.change(fn)
        batch = site.opset.history[before:]
        if batch:
            script.append((idx, list(batch)))

    for rnd in range(n_rounds):
        if rnd in (3, 7):
            sync(*peers)
            scalars = [
                i for i, x in enumerate(peers[0].doc["l"])
                if not isinstance(x, (list, Text))
            ]
            i = r.choice(scalars)
            fns = [
                lambda d, i=i: d["l"].__setitem__(i, 555),
                lambda d, i=i: d["l"].__delitem__(i),
            ]
            if rnd == 7:
                fns.reverse()  # the delete is delivered first
            step(0, fns[0])
            step(1, fns[1])
            continue
        idx = r.randrange(2)
        for _ in range(r.randint(1, 3)):
            step(idx)
        if rnd % 3 == 2:
            sync(*peers)
    return script


def _seq_seed_dir(tmp):
    """A stored doc on disk whose history holds a list with a
    tombstone and a nested list, and a text."""
    repo = Repo(path=tmp)
    url = repo.create({"l": [1, 2, 3, 4, 5, 6], "t": Text("hello")})
    repo.change(url, lambda d: d["l"].__delitem__(2))
    repo.change(url, lambda d: d["l"].insert(1, [7, 8]))
    repo.change(url, lambda d: d["t"].insert(2, "!"))
    doc_id = validate_doc_url(url)
    stored = list(repo.back.docs[doc_id].opset.history)
    repo.close()
    return url, doc_id, stored


@pytest.mark.parametrize("seed", [11, 2147483659, 77])
@pytest.mark.parametrize("way", ["adopt", "tick", "kernel", "local"])
def test_liveness_vector_is_the_elems_liveness(
    tmp_path, monkeypatch, way, seed
):
    """The four ways a decoded state comes to be or changes keep every
    sequence object's `alive` equal to its elems' liveness, and every
    index the engine emits equal to the naive walk's: `adopt` (the
    adoption's decode of a packed history), `tick` (`_tick_doc_locked`
    one op at a time), `kernel` (`_decode_install_locked`: the kernel
    path's decode and state diff), `local` (local changes with list
    intents between the remote batches). The patches equal the host
    OpSet's: diff for diff where ops are applied one at a time, by the
    frontend's state where ticks are decoded and diffed."""
    from hypermerge_tpu.backend import live as live_mod
    from hypermerge_tpu.crdt.opset import OpSet

    url, doc_id, stored = _seq_seed_dir(str(tmp_path))
    script = _seq_script(stored, seed)

    if way == "adopt":
        from hypermerge_tpu.ops.columnar import (
            LiveColumns,
            causal_sort,
            pack_docs,
        )

        changes = causal_sort(
            stored + [c for _i, batch in script for c in batch]
        )
        lv = LiveColumns.from_batch(pack_docs([changes]), 0)
        state = live_mod._decode_state(
            lv, live_mod.LiveApplyEngine._host_lanes(lv)
        )
        seqs = _assert_vectors(state)
        assert len(seqs) >= 3  # the list, the text, a nested one
        assert any(0 in o.alive for o in seqs)  # tombstones decoded
        opset = OpSet()
        opset.apply_changes(changes)
        got = live_mod._diff_states(live_mod._DocState(), state)
        assert [d.to_json() for d in got] == [
            d.to_json() for d in opset.snapshot_patch().diffs
        ]
        # ...and the state-walk reachability reads the same vector
        walked = live_mod._decode_state(
            lv, live_mod.LiveApplyEngine._host_lanes(lv)
        )
        live_mod._compute_reachable(walked)
        assert walked.reachable == state.reachable
        return

    monkeypatch.setenv("HM_LIVE", "1")
    if way == "kernel":
        # every tick of more than 8 ops goes to the kernel group
        monkeypatch.setenv("HM_LIVE_INC_BUDGET", "0")
        merged, script, batch = script, [], []
        for _i, b in merged:
            batch.extend(b)
            if sum(len(c.ops) for c in batch) > 8:
                script.append((0, batch))
                batch = []
        if batch:
            script.append((0, batch))

    # every op through _apply_seq_state: the vector after it, and each
    # index it emitted against the naive walk (errors are collected:
    # an assert on the tick thread would only be logged)
    faults, kinds = [], set()
    real = live_mod.LiveApplyEngine._apply_seq_state

    def checked(self, state, obj, opid, op, val, diffs):
        n = len(diffs)
        real(self, state, obj, opid, op, val, diffs)
        try:
            _assert_vectors(state)
            elem = opid if op.insert else op.ref
            for d in diffs[n:]:
                kinds.add(
                    "revive" if d.action == "insert" and not op.insert
                    else d.action
                )
                assert d.index == _naive_live_index(obj, elem), d
        except AssertionError as e:
            faults.append(e)

    monkeypatch.setattr(
        live_mod.LiveApplyEngine, "_apply_seq_state", checked
    )

    repo = Repo(path=str(tmp_path))
    try:
        got_diffs = []
        orig_push = repo.back.to_frontend.push

        def record(msg):
            if msg.get("type") == "Patch":
                got_diffs.extend(msg["patch"]["diffs"])
            orig_push(msg)

        repo.back.to_frontend.push = record
        h = repo.open(url)
        assert h.value(timeout=20) is not None
        doc = repo.back.docs[doc_id]
        eng = repo.back.live
        oracle = OpSet()
        oracle.apply_changes(_local_changes(repo, doc_id))
        want_diffs = []
        r = random.Random(seed + 1)

        def local_edit(d):
            seq = d[r.choice("lt")]
            kind = r.random()
            if kind < 0.5 or len(seq) == 0:
                seq.insert(r.randint(0, len(seq)), "L")
            elif kind < 0.75 or hasattr(seq, "delete"):
                _drop(seq, r.randint(0, len(seq) - 1))
            else:
                seq[r.randint(0, len(seq) - 1)] = "S"

        for _idx, batch in script:
            want_diffs.extend(
                d.to_json() for d in oracle.apply_changes(batch).diffs
            )
            doc.apply_remote_changes(list(batch))
            wait_until(lambda: dict(doc.clock) == dict(oracle.clock))
            eng.flush_now()
            _live_vectors(repo, doc)
            if way == "local":
                repo.change(url, local_edit)
                me = doc.actor_id
                mine = repo.back._get_or_create_actor(
                    me
                ).changes_in_window(
                    oracle.clock.get(me, 0), doc.clock[me]
                )
                want_diffs.extend(
                    d.to_json()
                    for d in oracle.apply_changes(mine).diffs
                )
                _live_vectors(repo, doc)
        assert not faults, faults[:3]
        assert doc.opset is None  # live-managed to the end
        stats = eng.stats
        if way == "kernel":
            assert stats["kernel_runs"] > 0
        else:
            # one op at a time all the way: the same diffs, in order
            assert stats["kernel_runs"] == 0
            assert got_diffs == want_diffs
            assert {"insert", "remove", "set", "revive"} <= kinds
            assert stats["seq_ops"] > 0
        wait_until(
            lambda: plainify(h.value())
            == plainify(oracle.materialize())
        )
        seqs = _live_vectors(repo, doc)
        assert any(0 in o.alive for o in seqs)
    finally:
        repo.close()


def test_caught_up_tick_counts_its_ops_and_walks_no_elems(
    tmp_path, monkeypatch
):
    """The catch-up cell's document shape: a held three-writer doc of
    1,152 ops takes its missing 24 changes (384 ops) in ONE tick, one
    op at a time: `live.inc_ops` moves by 384 and `live.seq_ops` by
    the ops on its text, and the tick asks no object for `live()` (the
    one O(elems) Python pass a sequence op could still reach)."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.corpora import multi_writer_rounds_behind as mwrb
    from hypermerge_tpu.backend import live as live_mod
    from hypermerge_tpu.crdt.change import ROOT, Change

    monkeypatch.setenv("HM_LIVE", "1")
    corpus = {
        "writer": "multi_writer_rounds_behind", "sign": True,
        "ops": 1536, "ops_per_change": 16, "blocks_held": 24,
        "seq_frac": 0.85, "del_frac": 0.1, "n_keys": 10,
        "seq_key": "t", "distinct": 1,
        "classes": [{"writers": 3, "count": 1}],
    }
    job = mwrb.CorpusJob(str(tmp_path / "a"), corpus, 2147483659, 1)
    try:
        urls = job.start().finish()
    except BaseException:
        job.abort()
        raise
    tail = [Change.from_json(c) for c in job.doc_changes(0, {})[72:]]
    assert sum(len(c.ops) for c in tail) == 384
    seq_ops = sum(op.obj != ROOT for c in tail for op in c.ops)
    assert seq_ops > 250  # seq_frac 0.85 of 384

    calls = []
    real_live = live_mod._Obj.live
    monkeypatch.setattr(
        live_mod._Obj, "live",
        lambda self: calls.append(1) or real_live(self),
    )
    repo = Repo(path=job.behind_path)
    try:
        repo.open_many(urls)
        repo.back.fetch_bulk_summaries()
        doc = repo.back.docs[validate_doc_url(urls[0])]
        eng = repo.back.live
        before = eng.stats
        doc.apply_remote_changes(tail)
        wait_until(lambda: sum(doc.clock.values()) == 96, timeout=60)
        eng.flush_now()
        after = eng.stats
        moved = {k: after[k] - before[k] for k in after}
        assert moved["adopt_held"] == moved["adopted"] == 1
        assert moved["tick_changes"] == moved["inc_changes"] == 24
        assert moved["inc_ops"] == 384
        assert moved["seq_ops"] == seq_ops
        assert moved["kernel_runs"] == 0
        assert calls == []
        text = [o for o in _live_vectors(repo, doc) if o.type == "text"]
        assert len(text) == 1 and len(text[0].order) > 1000
    finally:
        repo.close()
