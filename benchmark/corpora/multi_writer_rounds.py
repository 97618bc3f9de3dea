"""Corpus writer `multi_writer_rounds`: shared documents on disk, each
edited by 1-32 writers into feeds of their own, from the seed.

A doc's history is rounds of concurrent changes (config `collab-10k`):
change 0 is the root actor's (writer 0, whose key is the doc id), makes
the text at ROOT and stands alone; changes 1.. go round-robin over the
doc's W writers (change c is writer c mod W's) in rounds of W. A change
depends on every change of the round before it and on none of its own,
and its `startOp` is 1 + the largest counter among its deps: the
changes of one round carry EQUAL counters, and only the actor breaks
the tie. Every writer works on the state as it stood when the round
began plus what its own change has done so far:

- the first free op of every change inserts after the round's ANCHOR
  (one element drawn once a round; HEAD in rounds 0 and 1), so a round
  makes W siblings under one element whose op ids tie on the counter;
- every other op is, with probability `seq_frac`, a sequence op: a DEL
  (`del_frac` of them) of an element visible at the round's start (two
  writers may delete the same one, one may insert after what another
  deletes), else an insert after a uniformly drawn element that was
  visible at the round's start or that this change made;
- otherwise an integer SET of one of `n_keys` ROOT keys, whose `pred`
  is every SET of that key the writer sees (several after a concurrent
  round).

A template is one such history under placeholder actor names; a doc is
a template under the doc's own keys, drawn per doc from the seed. How a
doc's keys sort decides the order of its concurrent siblings and the
winner of its concurrent SETs, so two docs of one template hold
different states: the plain reference replays doc i's own changes
under doc i's own keys (`doc_reference`), never a renamed replay of
another doc. A multi-writer template is redrawn until some round holds
two concurrent SETs of one key (at full size the first draw has one).

Bytes go to disk through the program's storage API, as
`single_writer_templates` writes them and with its `CorpusJob`
interface: per feed a block log packed by `storage/block.py`, its
`.len` index and `.sig` chain, written by a pool of JAX-free worker
processes; one `cols.slab` with a v3 image a feed (rendered once a
template and writer, `_FeedTemplate`) and the sqlite cursor / clock /
feed rows of every (doc, actor), by the caller.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import re
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark.corpora.single_writer_templates import (
    _HDR,  # storage/feed.py block framing
    _LEN,  # storage/feed.py FileFeedStorage._LEN
    INFINITY_SEQ,
)

_MAKE_TEXT, _SET, _DEL = 2, 4, 5
_HEAD = "0@_head"
_ROOT = "0@_root"
_NAME = re.compile(rb"writer(\d\d)")  # a template's placeholder actors
_REDRAWS = 256


def _name(w: int) -> str:
    return f"writer{w:02d}"


def _draw(n_ops, W, rng, ops_per_change, seq_frac, del_frac, n_keys,
          seq_key) -> Tuple[List[dict], bool]:
    """One history of W writers -> (wire-form changes in the order
    they were made, whether two changes of one round SET the same
    key)."""
    if n_ops % ops_per_change:
        raise ValueError("corpus: ops must be a multiple of ops_per_change")
    n_changes = n_ops // ops_per_change
    text = f"1@{_name(0)}"
    live: List[str] = []  # elements visible at the round's start
    vis: Dict[int, List[str]] = {}  # key -> SET ids visible at its start
    seqs = [0] * W
    prev: Dict[str, int] = {}  # the round before: actor -> seq
    changes: List[dict] = []
    concurrent_sets = False
    c = rnd = 0
    while c < n_changes:
        members = [c] if rnd == 0 else list(range(c, min(c + W, n_changes)))
        start_op = 1 + ops_per_change * rnd
        anchor = (
            _HEAD if rnd <= 1 or not live
            else live[int(rng.integers(len(live)))]
        )
        made: List[str] = []  # this round's elements, change by change
        deleted: set = set()
        set_ops: List[Tuple[int, str, List[str], int]] = []
        now: Dict[str, int] = {}
        for cc in members:
            w = cc % W
            actor = _name(w)
            seqs[w] += 1
            now[actor] = seqs[w]
            own: List[str] = []  # elements this change made
            gone: set = set()  # elements this change deleted
            cur: Dict[int, List[str]] = {}  # keys this change has SET
            ops: List[dict] = []
            for i in range(ops_per_change):
                oid = f"{start_op + i}@{actor}"
                if cc == 0 and i == 0:
                    ops.append({"a": _MAKE_TEXT, "o": _ROOT, "k": seq_key})
                    continue
                if i == (1 if cc == 0 else 0):
                    kind, ref = "ins", anchor
                elif rng.random() < (seq_frac if n_keys else 1.0):
                    kind, ref = "ins", None
                    if rng.random() < del_frac:
                        cands = [e for e in live if e not in gone]
                        if cands:
                            kind = "del"
                            ref = cands[int(rng.integers(len(cands)))]
                else:
                    kind = "set"
                if kind == "del":
                    gone.add(ref)
                    ops.append({"a": _DEL, "o": text, "r": ref, "p": [ref]})
                elif kind == "ins":
                    if ref is None:
                        n = len(live) + len(own)
                        j = int(rng.integers(n)) if n else -1
                        ref = (
                            _HEAD if j < 0
                            else live[j] if j < len(live)
                            else own[j - len(live)]
                        )
                        if ref in gone:  # not one this change deleted
                            left = [e for e in live + own if e not in gone]
                            ref = (
                                left[int(rng.integers(len(left)))]
                                if left else _HEAD
                            )
                    own.append(oid)
                    ops.append({
                        "a": _SET, "o": text, "r": ref, "i": True,
                        "v": chr(97 + int(rng.integers(26))),
                    })
                else:
                    k = int(rng.integers(n_keys))
                    pred = list(cur.get(k, vis.get(k, ())))
                    cur[k] = [oid]
                    set_ops.append((k, oid, pred, cc))
                    op = {"a": _SET, "o": _ROOT, "k": f"k{k}",
                          "v": int(rng.integers(1000))}
                    if pred:
                        op["p"] = pred
                    ops.append(op)
            made.extend(e for e in own if e not in gone)
            deleted |= gone
            deps = dict(prev)
            if cc:
                deps.setdefault(_name(0), 1)  # every change follows change 0
            deps.pop(actor, None)
            changes.append({
                "actor": actor, "seq": seqs[w], "startOp": start_op,
                "deps": deps, "time": 0, "message": "", "ops": ops,
            })
        # the round is over: every writer now sees all of it
        live = [e for e in live if e not in deleted] + made
        named = {p for _k, _o, pred, _c in set_ops for p in pred}
        by_key: Dict[int, set] = {}
        for k, oid, _pred, cc in set_ops:
            vis.setdefault(k, []).append(oid)
            by_key.setdefault(k, set()).add(cc)
        for k in by_key:
            vis[k] = [o for o in vis[k] if o not in named]
        concurrent_sets |= any(len(s) > 1 for s in by_key.values())
        prev = now
        c += len(members)
        rnd += 1
    return changes, concurrent_sets


def template_changes(
    n_ops: int,
    writers: int,
    seed: int,
    ops_per_change: int = 16,
    seq_frac: float = 0.85,
    del_frac: float = 0.10,
    n_keys: int = 10,
    seq_key: str = "t",
) -> List[dict]:
    """One history of `writers` writers (placeholder actors
    `writer00`..) as wire-form changes. A multi-writer history is
    redrawn until two concurrent changes SET one key."""
    for attempt in range(_REDRAWS):
        changes, concurrent_sets = _draw(
            n_ops, writers, np.random.default_rng([seed, attempt]),
            ops_per_change, seq_frac, del_frac, n_keys, seq_key,
        )
        if concurrent_sets or writers == 1 or not n_keys:
            return changes
    raise RuntimeError(
        f"corpus: no concurrent SETs in {_REDRAWS} draws of {n_ops} ops "
        f"by {writers} writers"
    )


_DRAW_KEYS = ("ops_per_change", "seq_frac", "del_frac", "n_keys", "seq_key")


def _distinct(corpus: dict, cls: dict) -> int:
    return max(1, min(int(corpus["distinct"]), int(cls["count"])))


def doc_plan(corpus: dict, seed: int) -> List[dict]:
    """Every doc of the corpus, in the order it is opened: {cls,
    template, writers, n_ops, key_seeds}. The classes are interleaved
    by a seeded shuffle, so every slab of an open holds every class."""
    labels = [
        c for c, cls in enumerate(corpus["classes"])
        for _ in range(int(cls["count"]))
    ]
    random.Random(f"{seed}:order").shuffle(labels)
    seen = [0] * len(corpus["classes"])
    plan = []
    for i, c in enumerate(labels):
        cls = corpus["classes"][c]
        plan.append({
            "cls": c,
            "template": seen[c] % _distinct(corpus, cls),
            "writers": int(cls["writers"]),
            "n_ops": int(corpus["ops"]),
            "key_seeds": [
                hashlib.blake2b(
                    f"{seed}:{i}:{w}".encode(), digest_size=32
                ).digest()
                for w in range(int(cls["writers"]))
            ],
        })
        seen[c] += 1
    return plan


def class_templates(corpus: dict, seed: int) -> List[List[List[dict]]]:
    """[class][template] -> wire-form changes."""
    kw = {k: corpus[k] for k in _DRAW_KEYS if k in corpus}
    return [
        [
            template_changes(
                int(corpus["ops"]), int(cls["writers"]),
                (seed * 1000003 + c * 4099 + t) % 2**63, **kw,
            )
            for t in range(_distinct(corpus, cls))
        ]
        for c, cls in enumerate(corpus["classes"])
    ]


def rename(raw: bytes, names: List[bytes]) -> bytes:
    """A template's bytes (JSON blocks, a tables blob) under a doc's
    own actor names, in one pass."""
    return _NAME.sub(lambda m: names[int(m.group(1))], raw)


class _FeedTemplate:
    """One writer's feed of a template, rendered once: its blocks'
    JSON and its sidecar (one v3 image, storage/colcache.py) under the
    placeholder names. What `ops/corpus._Template` renders for a
    single-writer history, for a feed whose ops name other actors too:
    the image's planes, preds and row ends hold no actor name and are
    shared by every doc of the template; the tables blob names the
    feed's writer first and then the other actors its ops refer to, and
    is re-framed around the shared body under each doc's keys."""

    def __init__(self, changes: List[dict], writer: str) -> None:
        from hypermerge_tpu.crdt.change import Change
        from hypermerge_tpu.storage.colcache import (
            FeedColumnCache,
            MemoryColumnStorage,
            planes_from_rows,
            v3_body_bytes,
        )
        from hypermerge_tpu.utils.json_buffer import bufferify

        parsed = [Change.from_json(c) for c in changes]
        self.n_changes = len(parsed)
        self.raw_blocks = [bufferify(c.to_json()) for c in parsed]
        if self.raw_blocks != [bufferify(c) for c in changes]:
            raise RuntimeError(
                "corpus: wire form changed in the program's Change round trip"
            )
        cc = FeedColumnCache(MemoryColumnStorage(), writer=writer)
        for c in parsed:
            cc.append_change(c)
        fc = cc.columns()
        planes = (
            fc.planes if fc.planes is not None
            else planes_from_rows(fc.ensure_rows())
        )
        row_ends = np.asarray(cc._commits_arr[:, 0], np.int64)
        flags = np.asarray(cc._commits_arr[:, 3], np.uint8)
        self._body = v3_body_bytes(planes, fc.preds, row_ends, flags)
        self._shape = (fc.n_rows, len(row_ends), len(fc.preds))
        self._tables = cc._tables_blob()

    def image(self, names: List[bytes]) -> bytes:
        """The feed's sidecar under a doc's own actor names."""
        from hypermerge_tpu.storage.colcache import v3_frame

        return v3_frame(
            self._body, *self._shape, rename(self._tables, names)
        )


# ---------------------------------------------------------------------------
# pool workers (JAX-free: the process that holds the chip is elsewhere)

_W: Dict[str, Any] = {}


def _worker_init(feeds_root: str, raw_blocks, sign: bool) -> None:
    _W.update(feeds_root=feeds_root, raw=raw_blocks, sign=sign)


def _write_feeds(jobs) -> int:
    """jobs: [(class, template, [(public key, secret key) a writer])]
    -> the block log, `.len` index and `.sig` chain of every feed of
    each doc. Returns the feeds written."""
    from hypermerge_tpu.storage import block as blockmod
    from hypermerge_tpu.storage.integrity import sign_chain
    from hypermerge_tpu.utils import keys as keymod

    root = _W["feeds_root"]
    done = 0
    for c, t, pairs in jobs:
        names = [pk.encode("ascii") for pk, _sk in pairs]
        for (pk, sk), raws in zip(pairs, _W["raw"][c][t]):
            d = os.path.join(root, pk[:2])
            os.makedirs(d, exist_ok=True)
            blocks = [blockmod.pack_raw(rename(r, names)) for r in raws]
            parts: List[bytes] = []
            for b in blocks:
                parts.append(_HDR.pack(len(b)))
                parts.append(b)
            log_bytes = b"".join(parts)
            with open(os.path.join(d, pk), "wb") as fh:
                fh.write(log_bytes)
            with open(os.path.join(d, pk + ".len"), "wb") as fh:
                fh.write(_LEN.pack(len(blocks), len(log_bytes)))
            if _W["sign"]:
                with open(os.path.join(d, pk + ".sig"), "wb") as fh:
                    fh.write(sign_chain(blocks, keymod.decode(sk)))
            done += 1
    return done


class CorpusJob:
    """A corpus being written: `start()` returns at once with the feed
    writers running in their pool; `finish()` joins them, and returns
    the doc urls. The caller may start JAX in between."""

    def __init__(self, path: str, corpus: dict, seed: int,
                 workers: int) -> None:
        self.path = path
        self.corpus = corpus
        self.seed = seed
        self.workers = max(1, workers)
        self.templates = class_templates(corpus, seed)
        self.plan = doc_plan(corpus, seed)
        self.n_feeds = sum(d["writers"] for d in self.plan)
        self._pool = None
        self._pending = []

    def start(self) -> "CorpusJob":
        import multiprocessing

        from hypermerge_tpu.storage.slab import KIND_IMAGE, CorpusSlab
        from hypermerge_tpu.utils import keys as keymod

        feeds_root = os.path.join(self.path, "feeds")
        os.makedirs(feeds_root, exist_ok=True)
        # one rendered feed a template and writer: [class][template][w]
        rendered = [
            [
                [
                    _FeedTemplate(
                        [ch for ch in tpl if ch["actor"] == _name(w)],
                        _name(w),
                    )
                    for w in range(int(cls["writers"]))
                ]
                for tpl in self.templates[c]
            ]
            for c, cls in enumerate(self.corpus["classes"])
        ]
        raw = [[[r.raw_blocks for r in feeds] for feeds in grp]
               for grp in rendered]
        self.pairs = [
            [keymod.create(s) for s in d["key_seeds"]] for d in self.plan
        ]
        jobs = [
            (d["cls"], d["template"],
             [(p.public_key, p.secret_key) for p in pairs])
            for d, pairs in zip(self.plan, self.pairs)
        ]
        n = self.workers
        # interleave, so every worker gets the same mix of classes
        shares = [jobs[i::n * 4] for i in range(n * 4)]
        ctx = multiprocessing.get_context("spawn")
        self._pool = ctx.Pool(
            n, initializer=_worker_init,
            initargs=(feeds_root, raw, bool(self.corpus.get("sign", True))),
        )
        self._pending = [
            self._pool.apply_async(_write_feeds, (s,)) for s in shares if s
        ]
        # meanwhile, here: the slab of sidecar images and the rows
        slab = CorpusSlab(os.path.join(feeds_root, "cols.slab"))
        try:
            for d, pairs in zip(self.plan, self.pairs):
                names = [p.public_key.encode("ascii") for p in pairs]
                for p, r in zip(pairs, rendered[d["cls"]][d["template"]]):
                    slab.append(KIND_IMAGE, p.public_key, r.image(names))
        finally:
            slab.close()
        self._write_rows(rendered)
        return self

    def _write_rows(self, rendered) -> None:
        from hypermerge_tpu.storage.sql import SqlDatabase
        from hypermerge_tpu.utils import keys as keymod

        db = SqlDatabase(os.path.join(self.path, "repo.db"))
        repo_pair = keymod.create(hashlib.blake2b(
            f"{self.seed}:repo".encode(), digest_size=32
        ).digest())
        db.execute(
            "INSERT OR REPLACE INTO keys (name, public_key, secret_key) "
            "VALUES (?,?,?)",
            ("self.repo", repo_pair.public_key, repo_pair.secret_key),
        )
        rid = repo_pair.public_key
        # (doc, actor, changes of that actor) for every feed
        feeds = [
            (pairs[0].public_key, p.public_key, r.n_changes)
            for d, pairs in zip(self.plan, self.pairs)
            for p, r in zip(pairs, rendered[d["cls"]][d["template"]])
        ]
        with db.bulk():
            db.executemany(
                "INSERT OR REPLACE INTO cursors "
                "(repo_id, doc_id, actor_id, seq) VALUES (?,?,?,?)",
                [(rid, doc, pk, INFINITY_SEQ) for doc, pk, _n in feeds],
            )
            db.executemany(
                "INSERT OR REPLACE INTO clocks "
                "(repo_id, doc_id, actor_id, seq) VALUES (?,?,?,?)",
                [(rid, doc, pk, n) for doc, pk, n in feeds],
            )
            db.executemany(
                "INSERT OR REPLACE INTO feeds "
                "(public_id, discovery_id, is_writable) VALUES (?,?,0)",
                [(pk, keymod.discovery_id(pk)) for _doc, pk, _n in feeds],
            )
        db.close()

    def finish(self) -> List[str]:
        from hypermerge_tpu.utils.ids import to_doc_url

        try:
            done = sum(r.get(900) for r in self._pending)
        finally:
            self._pool.terminate()
            self._pool.join()
            self._pool = None
        if done != self.n_feeds:
            raise RuntimeError(
                f"corpus: {done} of {self.n_feeds} feeds written"
            )
        return [to_doc_url(pairs[0].public_key) for pairs in self.pairs]

    def abort(self) -> None:
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None

    def doc_changes(self, i: int, cache: dict) -> List[dict]:
        """Doc i's own changes: its template under its own keys."""
        d = self.plan[i]
        t = (d["cls"], d["template"])
        if t not in cache:
            cache[t] = json.dumps(self.templates[t[0]][t[1]]).encode()
        names = [p.public_key.encode("ascii") for p in self.pairs[i]]
        return json.loads(rename(cache[t], names))

    def doc_reference(self, i: int, cache: dict) -> Dict[str, Any]:
        """The plain reference's replay of doc i's own changes under
        doc i's own keys (`cache` keeps each template's JSON)."""
        from benchmark.reference import crdt_plain

        return crdt_plain.replay(self.doc_changes(i, cache))
