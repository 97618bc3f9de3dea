"""Corpus writer `single_writer_longtail`: single-writer docs whose
lengths follow a long tail, in a seeded store order.

Octave k holds the docs of [octave0 * 2**k, octave0 * 2**(k+1)) ops;
a config's `corpus.octaves` gives each octave's doc count (each
doubling of length, half as many docs, down to a handful of the
longest). In an octave min(`distinct`, count) lengths are drawn
log-uniformly (stratified) from the seed, each with a template history of its own,
and the octave's docs cycle over them under seeded keys. Store order
(the order of the urls, of the sidecars in `cols.slab` and of the
sqlite rows) is a seeded permutation of all docs, so a loader meets the
lengths at random.

A history is `single_writer_templates`' (op 1 makes the text at
ROOT[seq_key]; `seq_frac` of the later ops insert into it, the others
SET one of `n_keys` ROOT integers, superseding the key's last SET)
except where an insert lands: with probability `run_frac` after the
writer's previous insert (a typing run, what an editing trace is made
of), else after a uniformly drawn earlier element (a cursor jump).

The law becomes that writer's `groups` (one group a drawn length, one
template a group) and its plan, permuted; feeds, rows and the renaming
reference are that writer's, by import (`_write_feeds`, `_write_rows`,
`doc_plan`, `crdt_plain.rename_actor`). What differs is where the
templates are rendered: 2.5M template ops (the flagship has 65k) take
minutes of one core in `ops/corpus._Template`, so each pool worker
renders a group's template from the seed, writes the group's feeds and
hands the rendered sidecar body back; the caller writes `cols.slab` and
the rows in store order at `finish()`, and replays a template's changes
only for a doc the verifier asks about.
"""

from __future__ import annotations

import hashlib
import os
from typing import Any, Dict, List

import numpy as np

from benchmark.corpora import single_writer_templates as swt

_A = swt._TEMPLATE_ACTOR


def longtail_changes(
    n_ops: int,
    seed: int,
    ops_per_change: int = 16,
    seq_frac: float = 0.85,
    n_keys: int = 10,
    seq_key: str = "t",
    run_frac: float = 0.9,
) -> List[dict]:
    """One single-writer history as wire-form changes (see the module's
    docstring for where an insert lands)."""
    rng = np.random.default_rng(seed)
    is_seq = (rng.random(n_ops) < (seq_frac if n_keys else 1.0)).tolist()
    runs = (rng.random(n_ops) < run_frac).tolist()
    picks = rng.random(n_ops).tolist()
    chars = [chr(97 + c) for c in rng.integers(0, 26, n_ops).tolist()]
    keys = rng.integers(0, max(n_keys, 1), n_ops).tolist()
    vals = rng.integers(0, 1000, n_ops).tolist()
    seq_obj = f"1@{_A}"
    ops: List[dict] = [{"a": swt._MAKE_TEXT, "o": "0@_root", "k": seq_key}]
    elems: List[int] = []
    last_set: Dict[int, int] = {}
    for r in range(1, n_ops):
        ctr = r + 1
        if is_seq[r]:
            if not elems:
                ref = "0@_head"
            elif runs[r]:
                ref = f"{elems[-1]}@{_A}"
            else:
                ref = f"{elems[int(picks[r] * len(elems))]}@{_A}"
            elems.append(ctr)
            ops.append({
                "a": swt._SET, "o": seq_obj, "r": ref, "i": True,
                "v": chars[r],
            })
        else:
            k = keys[r]
            op = {"a": swt._SET, "o": "0@_root", "k": f"k{k}", "v": vals[r]}
            if k in last_set:
                op["p"] = [f"{last_set[k]}@{_A}"]
            last_set[k] = ctr
            ops.append(op)
    return [
        {
            "actor": _A, "seq": i // ops_per_change + 1, "startOp": i + 1,
            "deps": {}, "time": 0, "message": "",
            "ops": ops[i:i + ops_per_change],
        }
        for i in range(0, n_ops, ops_per_change)
    ]


def octave_lengths(corpus: dict, seed: int) -> List[List[int]]:
    """[octave] -> the op counts drawn for it: m = min(distinct,
    count) of them, log-uniform over the octave, from the seed, and
    stratified (the i-th is drawn from the i-th m-th of the octave), so
    that the store's total ops, what `ops_per_s` counts, vary by under
    1% from seed to seed and not by the 3% that four free draws of
    131,072-262,143 ops make."""
    rng = np.random.default_rng((seed * 1000003 + 7919) % 2**63)
    lo = int(corpus["octave0"])
    out = []
    for count in corpus["octaves"]:
        m = min(int(corpus["distinct"]), int(count))
        draws = (np.arange(m) + rng.random(m)) / m
        out.append([
            min(2 * lo - 1, int(lo * 2.0 ** float(u))) for u in draws
        ])
        lo *= 2
    return out


def groups_of(corpus: dict, seed: int) -> List[dict]:
    """`single_writer_templates` groups, one a drawn length: doc j of
    an octave takes the octave's length j % (lengths drawn)."""
    groups = []
    for k, (count, lengths) in enumerate(
        zip(corpus["octaves"], octave_lengths(corpus, seed))
    ):
        m = len(lengths)
        for i, n_ops in enumerate(lengths):
            groups.append({
                "octave": k, "ops": n_ops, "distinct": 1,
                "count": len(range(i, int(count), m)),
            })
    return groups


def octave_of(corpus: dict, n_ops: int) -> int:
    return (n_ops // int(corpus["octave0"])).bit_length() - 1


def _kw(corpus: dict) -> dict:
    return {
        k: corpus[k] for k in (
            "ops_per_change", "seq_frac", "n_keys", "seq_key", "run_frac",
        ) if k in corpus
    }


def _template_seed(seed: int, group: int) -> int:
    return (seed * 1000003 + group * 4099) % 2**63


def _render_and_write(job):
    """Pool worker: (group, ops, template seed, generator kwargs,
    [(public key, secret key)]) -> (group, feeds written, the rendered
    template without its blocks)."""
    from hypermerge_tpu.crdt.change import Change
    from hypermerge_tpu.ops.corpus import _Template
    from hypermerge_tpu.utils.json_buffer import bufferify

    g, n_ops, tseed, kw, docs = job
    changes = longtail_changes(n_ops, tseed, **kw)
    tpl = _Template([Change.from_json(c) for c in changes])
    if tpl.raw_blocks != [bufferify(c) for c in changes]:
        raise RuntimeError(
            "corpus: wire form changed in the program's Change round trip"
        )
    swt._W["raw"] = {g: [tpl.raw_blocks]}
    done = swt._write_feeds([(g, 0, pk, sk) for pk, sk in docs])
    swt._W["raw"] = tpl.raw_blocks = None
    return g, done, tpl


class CorpusJob(swt.CorpusJob):
    """`single_writer_templates.CorpusJob` over the law's groups, its
    plan in the seeded store order, its templates rendered in the
    pool."""

    def __init__(self, path: str, corpus: dict, seed: int,
                 workers: int) -> None:
        self.path = path
        self.seed = seed
        self.workers = max(1, workers)
        self.corpus = dict(corpus, groups=groups_of(corpus, seed))
        plan = swt.doc_plan(self.corpus, seed)
        order = np.random.default_rng(
            int.from_bytes(hashlib.blake2b(
                f"{seed}:store-order".encode(), digest_size=8
            ).digest(), "big")
        ).permutation(len(plan))
        self.plan = [plan[i] for i in order.tolist()]
        self._pool = None
        self._pending = []

    def start(self) -> "CorpusJob":
        import multiprocessing

        from hypermerge_tpu.utils import keys as keymod

        feeds_root = os.path.join(self.path, "feeds")
        os.makedirs(feeds_root, exist_ok=True)
        self.pairs = [keymod.create(d["key_seed"]) for d in self.plan]
        docs: Dict[int, List[tuple]] = {}
        for d, p in zip(self.plan, self.pairs):
            docs.setdefault(d["group"], []).append(
                (p.public_key, p.secret_key)
            )
        kw = _kw(self.corpus)
        jobs = [
            (g, int(grp["ops"]), _template_seed(self.seed, g), kw, docs[g])
            for g, grp in enumerate(self.corpus["groups"])
        ]
        # the longest templates first: one of 200k ops is a quarter of
        # a minute of one worker, and nothing can split it
        jobs.sort(key=lambda j: -j[1])
        ctx = multiprocessing.get_context("spawn")
        self._pool = ctx.Pool(
            self.workers, initializer=swt._worker_init,
            initargs=(feeds_root, None, bool(self.corpus.get("sign", True))),
        )
        self._pending = [
            self._pool.apply_async(_render_and_write, (j,)) for j in jobs
        ]
        return self

    def finish(self) -> List[str]:
        from hypermerge_tpu.storage.slab import KIND_IMAGE, CorpusSlab
        from hypermerge_tpu.utils.ids import to_doc_url

        try:
            results = [r.get(900) for r in self._pending]
        finally:
            self.abort()
        if sum(n for _g, n, _t in results) != len(self.plan):
            raise RuntimeError("corpus: not every feed was written")
        rendered = [[t] for _g, _n, t in sorted(results, key=lambda r: r[0])]
        slab = CorpusSlab(os.path.join(self.path, "feeds", "cols.slab"))
        try:
            for d, p in zip(self.plan, self.pairs):
                slab.append(
                    KIND_IMAGE, p.public_key,
                    rendered[d["group"]][0].checkpoint_bytes(p.public_key),
                )
        finally:
            slab.close()
        self._write_rows(rendered)
        return [to_doc_url(p.public_key) for p in self.pairs]

    def doc_reference(self, i: int, cache: dict) -> Dict[str, Any]:
        """The plain reference's replay of doc i: of its template's
        changes, made again from the seed (once a template, kept in
        `cache`), under the doc's own writer key."""
        from benchmark.reference import crdt_plain

        g = self.plan[i]["group"]
        if g not in cache:
            cache[g] = crdt_plain.replay(longtail_changes(
                int(self.corpus["groups"][g]["ops"]),
                _template_seed(self.seed, g), **_kw(self.corpus),
            ))
        return crdt_plain.rename_actor(
            cache[g], _A, self.pairs[i].public_key
        )
