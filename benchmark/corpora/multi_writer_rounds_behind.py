"""Corpus writer `multi_writer_rounds_behind`: the store of
`multi_writer_rounds` written twice from one seed, whole for the peer
that stayed up and CUT for a peer that was offline (config
`catchup-1k3a`).

The whole store goes to `path` exactly as `multi_writer_rounds` writes
it (its templates, plan, keys, blocks and rows: this file imports them
and draws nothing of its own). The cut store goes to `path + "-behind"`
and holds the same documents `blocks_held` blocks deep in every feed:
the first `blocks_held x writers` changes of each document by change
index. Under the law of rounds (change c is writer c mod W's and
depends on the round before it) any prefix by change index is causally
closed, and it is the first `blocks_held` blocks of every writer's
feed. It is written as a peer that replicated that far and stopped
cleanly leaves it:

- per feed the first `blocks_held` blocks of the whole log, byte for
  byte, its `.len`, and the first `blocks_held` records of the whole
  `.sig` chain (one record a block: the chain ends at the block held);
- one `cols.slab` with a v3 image a feed, rendered over the prefix;
- the sqlite cursor / clock / feed rows at the prefix, under a repo key
  of the cut store's own (two peers, two identities);
- `feeds/heads.snap` sealed over every feed, through the program's own
  `HeadSnapshot`, as a clean close seals it.

`tails(i)` and `doc_changes(i, cache)` / `prefix_changes(i, cache)` give
the checks what the peer lacks and what it held.
"""

from __future__ import annotations

import hashlib
import os
from typing import Dict, List

from benchmark.corpora import multi_writer_rounds as mwr
from benchmark.corpora.multi_writer_rounds import (
    _HDR,
    _LEN,
    INFINITY_SEQ,
    _FeedTemplate,
    _name,
    rename,
)

_REC = 104  # storage/integrity.py: one (length, root, signature) record

_W: Dict[str, object] = {}


def _worker_init(roots, raw_blocks, sign: bool, held: int) -> None:
    _W.update(roots=roots, raw=raw_blocks, sign=sign, held=held)


def _write_feeds(jobs) -> int:
    """jobs as `multi_writer_rounds._write_feeds` takes them -> every
    feed of each doc twice: its whole log, `.len` and `.sig` chain under
    the first root, the first `held` blocks of the same bytes under the
    second. Returns the feeds written (counted once)."""
    from hypermerge_tpu.storage import block as blockmod
    from hypermerge_tpu.storage.integrity import sign_chain
    from hypermerge_tpu.utils import keys as keymod

    whole_root, cut_root = _W["roots"]
    held = _W["held"]
    done = 0
    for c, t, pairs in jobs:
        names = [pk.encode("ascii") for pk, _sk in pairs]
        for (pk, sk), raws in zip(pairs, _W["raw"][c][t]):
            blocks = [blockmod.pack_raw(rename(r, names)) for r in raws]
            chain = (
                sign_chain(blocks, keymod.decode(sk)) if _W["sign"] else None
            )
            for root, n in ((whole_root, len(blocks)), (cut_root, held)):
                if not n:
                    continue  # nothing held: a peer that never had it
                d = os.path.join(root, pk[:2])
                os.makedirs(d, exist_ok=True)
                log_bytes = b"".join(
                    p for b in blocks[:n] for p in (_HDR.pack(len(b)), b)
                )
                with open(os.path.join(d, pk), "wb") as fh:
                    fh.write(log_bytes)
                with open(os.path.join(d, pk + ".len"), "wb") as fh:
                    fh.write(_LEN.pack(n, len(log_bytes)))
                if chain is not None:
                    with open(os.path.join(d, pk + ".sig"), "wb") as fh:
                        fh.write(chain[:n * _REC])
            done += 1
    return done


class _Sealed:
    """What `HeadSnapshot.seal` reads of a storage: its file's path and
    the head a clean close knows."""

    def __init__(self, path: str, count: int, end: int) -> None:
        self.path, self._count, self._end = path, count, end


class CorpusJob(mwr.CorpusJob):
    """`multi_writer_rounds.CorpusJob` (the whole store at `path`) plus
    the cut store at `behind_path`; `finish()` returns the urls, which
    both stores share."""

    def __init__(self, path: str, corpus: dict, seed: int,
                 workers: int) -> None:
        super().__init__(path, corpus, seed, workers)
        self.behind_path = path + "-behind"
        self.held = int(corpus["blocks_held"])
        for cls in corpus["classes"]:
            w = int(cls["writers"])
            n = int(corpus["ops"]) // int(corpus["ops_per_change"])
            if n % w or not 0 <= self.held <= n // w:
                raise ValueError(
                    f"corpus: {n} changes by {w} writers cannot be held "
                    f"{self.held} blocks a feed"
                )

    def start(self) -> "CorpusJob":
        import multiprocessing

        from hypermerge_tpu.storage.slab import KIND_IMAGE, CorpusSlab
        from hypermerge_tpu.utils import keys as keymod

        roots = tuple(
            os.path.join(p, "feeds") for p in (self.path, self.behind_path)
        )
        for r in roots:
            os.makedirs(r, exist_ok=True)
        held = self.held
        # one rendered feed a template and writer, whole and cut:
        # [class][template][w] -> (whole, cut)
        def render(tpl, w):
            own = [ch for ch in tpl if ch["actor"] == _name(w)]
            return (_FeedTemplate(own, _name(w)),
                    _FeedTemplate(own[:held], _name(w)) if held else None)

        rendered = [
            [[render(tpl, w) for w in range(int(cls["writers"]))]
             for tpl in self.templates[c]]
            for c, cls in enumerate(self.corpus["classes"])
        ]
        raw = [[[r[0].raw_blocks for r in feeds] for feeds in grp]
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
        shares = [jobs[i::n * 4] for i in range(n * 4)]
        ctx = multiprocessing.get_context("spawn")
        self._pool = ctx.Pool(
            n, initializer=_worker_init,
            initargs=(roots, raw, bool(self.corpus.get("sign", True)), held),
        )
        self._pending = [
            self._pool.apply_async(_write_feeds, (s,)) for s in shares if s
        ]
        # meanwhile, here: both slabs of sidecar images and both sets of
        # rows (the cut store under a repo key of its own)
        for side, root in enumerate(roots):
            slab = CorpusSlab(os.path.join(root, "cols.slab"))
            try:
                for d, pairs in zip(self.plan, self.pairs):
                    names = [p.public_key.encode("ascii") for p in pairs]
                    for p, r in zip(
                        pairs, rendered[d["cls"]][d["template"]]
                    ):
                        if r[side] is not None:
                            slab.append(
                                KIND_IMAGE, p.public_key,
                                r[side].image(names),
                            )
            finally:
                slab.close()
        self._write_rows(
            [[[r[0] for r in feeds] for feeds in grp] for grp in rendered]
        )
        self._write_behind_rows()
        return self

    def _write_behind_rows(self) -> None:
        from hypermerge_tpu.storage.sql import SqlDatabase
        from hypermerge_tpu.utils import keys as keymod

        db = SqlDatabase(os.path.join(self.behind_path, "repo.db"))
        repo_pair = keymod.create(hashlib.blake2b(
            f"{self.seed}:repo-behind".encode(), digest_size=32
        ).digest())
        db.execute(
            "INSERT OR REPLACE INTO keys (name, public_key, secret_key) "
            "VALUES (?,?,?)",
            ("self.repo", repo_pair.public_key, repo_pair.secret_key),
        )
        rid = repo_pair.public_key
        feeds = [
            (pairs[0].public_key, p.public_key)
            for pairs in self.pairs for p in pairs
        ]
        if not self.held:
            feeds = []  # a store that holds nothing knows of no doc
        with db.bulk():
            db.executemany(
                "INSERT OR REPLACE INTO cursors "
                "(repo_id, doc_id, actor_id, seq) VALUES (?,?,?,?)",
                [(rid, doc, pk, INFINITY_SEQ) for doc, pk in feeds],
            )
            db.executemany(
                "INSERT OR REPLACE INTO clocks "
                "(repo_id, doc_id, actor_id, seq) VALUES (?,?,?,?)",
                [(rid, doc, pk, self.held) for doc, pk in feeds],
            )
            db.executemany(
                "INSERT OR REPLACE INTO feeds "
                "(public_id, discovery_id, is_writable) VALUES (?,?,0)",
                [(pk, keymod.discovery_id(pk)) for _doc, pk in feeds],
            )
        db.close()

    def finish(self) -> List[str]:
        from hypermerge_tpu.storage.feed import HeadSnapshot

        urls = super().finish()
        # the clean stop's last act: the head of every feed held, sealed
        # (what `.len` says, through the program's own snapshot)
        root = os.path.join(self.behind_path, "feeds")
        heads = HeadSnapshot(root)
        for pairs in self.pairs if self.held else ():
            for p in pairs:
                path = os.path.join(root, p.public_key[:2], p.public_key)
                with open(path + ".len", "rb") as fh:
                    count, end = _LEN.unpack(fh.read(_LEN.size))
                heads.tell(_Sealed(path, count, end))
        if not heads.seal():
            raise RuntimeError("corpus: the head snapshot was not sealed")
        return urls

    def prefix_changes(self, i: int, cache: dict) -> List[dict]:
        """What the cut store holds of doc i: its first
        `blocks_held x writers` changes, in the order they were made."""
        return self.doc_changes(i, cache)[:self.held * self.plan[i]["writers"]]

    def tails(self, i: int, cache: dict) -> List[List[dict]]:
        """What the cut store lacks of doc i, a list a writer: each
        feed's changes past the block held, in feed order."""
        changes = self.doc_changes(i, cache)
        w = self.plan[i]["writers"]
        pks = [p.public_key for p in self.pairs[i]]
        return [
            [c for c in changes[self.held * w:] if c["actor"] == pk]
            for pk in pks
        ]
