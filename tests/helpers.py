"""Shared test fixtures: a wired collaborator Site + doc normalization.

Site couples FrontendDoc + OpSet the way the repo runtime does (request ->
backend -> patch echo) — the in-process analogue of the reference's
frontend/backend wiring in tests (reference tests/repo.test.ts:27-45)."""

from hypermerge_tpu.crdt.frontend_state import FrontendDoc
from hypermerge_tpu.crdt.opset import OpSet
from hypermerge_tpu.models import Counter, Table, Text


class Site:
    def __init__(self, actor: str):
        self.actor = actor
        self.front = FrontendDoc()
        self.opset = OpSet()
        self.seq = 1

    def change(self, fn, message=""):
        req, preview = self.front.change(fn, self.actor, self.seq, message)
        if req is None:
            return None, preview
        self.seq += 1
        change, patch = self.opset.apply_local_request(req)
        self.front.apply_patch(patch)
        return change, preview

    def receive(self, changes):
        patch = self.opset.apply_changes(changes)
        self.front.apply_patch(patch)

    @property
    def doc(self):
        return self.front.materialize()

    def assert_consistent(self):
        assert plainify(self.opset.materialize()) == plainify(self.doc)


def plainify(v):
    if isinstance(v, Text):
        return ("__text__", str(v))
    if isinstance(v, Table):
        return ("__table__", {k: plainify(v.by_id(k)) for k in v.ids})
    if isinstance(v, Counter):
        return ("__counter__", int(v))
    if isinstance(v, dict):
        return {k: plainify(x) for k, x in v.items()}
    if isinstance(v, list):
        return [plainify(x) for x in v]
    return v


def opset_replay_state(changes):
    """(summary, plain value) of a host OpSet replay of `changes`: the
    reference a bulk open's `BulkSummaries.doc(id)` and doc value are
    held to. The summary counts live sequence elements and visible map
    entries over every object, beside the clock."""
    opset = OpSet()
    opset.apply_changes(changes)
    elems = entries = 0
    for obj in opset.objects.values():
        live = sum(1 for visible in obj.fields.values() if visible)
        if obj.is_sequence:
            elems += live
        else:
            entries += live
    summary = {
        "elems": elems, "map_entries": entries, "clock": dict(opset.clock),
    }
    return summary, plainify(opset.materialize())


def wait_until(fn, timeout=10.0, interval=0.005):
    """Poll until fn() is truthy (live replication tails are batched
    and asynchronous — net/replication.py flush windows), returning the
    value; raise on timeout."""
    import time

    deadline = time.monotonic() + timeout
    while True:
        v = fn()
        if v:
            return v
        if time.monotonic() > deadline:
            raise AssertionError(f"wait_until timed out: {fn}")
        time.sleep(interval)


def sync(*sites):
    for a in sites:
        for b in sites:
            if a is not b:
                a.receive(list(b.opset.history))


def random_mutation(site: Site, r) -> None:
    """One random change covering every op family (maps, lists, text,
    counters, deletes, nested objects)."""

    def fn(d):
        choice = r.random()
        if choice < 0.3:
            d[r.choice("abc")] = r.randint(0, 99)
        elif choice < 0.45:
            if "l" not in d:
                d["l"] = []
            lst = d["l"]
            lst.insert(r.randint(0, len(lst)), r.randint(0, 9))
        elif choice < 0.55:
            if "l" in d and len(d["l"]) > 0:
                del d["l"][r.randint(0, len(d["l"]) - 1)]
        elif choice < 0.7:
            if "t" not in d:
                d["t"] = Text("")
            d["t"].insert(r.randint(0, len(d["t"])), r.choice("xyz"))
        elif choice < 0.8:
            if "n" not in d or not isinstance(d.get("n"), Counter):
                d["n"] = Counter(0)
            else:
                d.increment("n", r.randint(1, 3))
        elif choice < 0.9:
            k = r.choice("abc")
            if k in d:
                del d[k]
        else:
            d[r.choice("mn")] = {"v": [r.randint(0, 9)]}

    site.change(fn)
