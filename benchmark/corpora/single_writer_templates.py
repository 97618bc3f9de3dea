"""Corpus writer `single_writer_templates`: single-writer docs on disk,
from the seed, written in parallel.

Content generation is a copy of `hypermerge_tpu/ops/synth.py
synth_columns` made right for a benchmark (ROADMAP R0): the templates
come from `--seed`, `distinct` is a stated parameter, and keyed SETs
aim at the ROOT MAP (the program's generator aims them at the text
object, where the host OpSet does not show them). It yields changes in
wire form (the JSON dict a feed block holds), which is also what the
plain reference replays.

Bytes go to disk through the program's storage API, as a deployment's
writer would leave them: block logs packed by `storage/block.py`, the
signature chain by `storage/integrity.sign_chain`, one `cols.slab` of
v3 checkpoints rendered by `ops/corpus._Template`, and the sqlite rows
a repo persists. Signing and compressing 10k feeds is a minute or two
of one core (134 s, PERF.md PR 21), so the feeds are written by a pool
of JAX-free worker processes; the slab and the rows, which are cheap,
by the caller.

A config's `corpus.groups` is a list of {count, ops, distinct, ...}:
docs of one group share an op count and cycle over `distinct`
templates. Doc keys are derived from the seed too, so a seed fixes
every byte the open reads.
"""

from __future__ import annotations

import hashlib
import os
import struct
from typing import Any, Dict, List

import numpy as np

_TEMPLATE_ACTOR = "actor00"  # replaced per doc by the doc's writer key
_HDR = struct.Struct("<I")  # storage/feed.py block framing
_LEN = struct.Struct("<QQ")  # storage/feed.py FileFeedStorage._LEN
INFINITY_SEQ = 2**53 - 1  # crdt/clock.py INFINITY_SEQ
_SET, _MAKE_LIST, _MAKE_TEXT = 4, 1, 2


def template_changes(
    n_ops: int,
    seed: int,
    ops_per_change: int = 16,
    seq_frac: float = 0.85,
    n_keys: int = 10,
    seq_key: str = "t",
    seq_type: str = "text",
    refs: str = "random",
) -> List[dict]:
    """One single-writer history as wire-form changes. Op 1 makes the
    sequence at ROOT[seq_key]; every later op is, with probability
    `seq_frac`, an insert into it (after a uniformly drawn earlier
    element, or after the last one when refs == "append") and otherwise
    an integer SET of one of `n_keys` ROOT keys, superseding that key's
    previous SET."""
    rng = np.random.default_rng(seed)
    a = _TEMPLATE_ACTOR
    is_seq = rng.random(n_ops) < (seq_frac if n_keys else 1.0)
    picks = rng.random(n_ops)
    chars = rng.integers(0, 26, n_ops)
    keys = rng.integers(0, max(n_keys, 1), n_ops)
    vals = rng.integers(0, 1000, n_ops)
    seq_obj = f"1@{a}"
    ops: List[dict] = [{
        "a": _MAKE_TEXT if seq_type == "text" else _MAKE_LIST,
        "o": "0@_root", "k": seq_key,
    }]
    elems: List[int] = []
    last_set: Dict[int, int] = {}
    for r in range(1, n_ops):
        ctr = r + 1
        if is_seq[r]:
            if not elems:
                ref = "0@_head"
            elif refs == "append":
                ref = f"{elems[-1]}@{a}"
            else:
                ref = f"{elems[int(picks[r] * len(elems))]}@{a}"
            elems.append(ctr)
            ops.append({
                "a": _SET, "o": seq_obj, "r": ref, "i": True,
                "v": chr(97 + int(chars[r])) if seq_type == "text"
                else int(vals[r]),
            })
        else:
            k = int(keys[r])
            op = {"a": _SET, "o": "0@_root", "k": f"k{k}", "v": int(vals[r])}
            if k in last_set:
                op["p"] = [f"{last_set[k]}@{a}"]
            last_set[k] = ctr
            ops.append(op)
    return [
        {
            "actor": a, "seq": i // ops_per_change + 1, "startOp": i + 1,
            "deps": {}, "time": 0, "message": "",
            "ops": ops[i:i + ops_per_change],
        }
        for i in range(0, n_ops, ops_per_change)
    ]


def doc_plan(corpus: dict, seed: int) -> List[dict]:
    """Every doc of the corpus, in order: {group, template, n_ops,
    key_seed}. Pure arithmetic on the config and the seed."""
    plan = []
    for g, grp in enumerate(corpus["groups"]):
        distinct = min(int(grp["distinct"]), int(grp["count"]))
        for j in range(int(grp["count"])):
            plan.append({
                "group": g,
                "template": j % distinct,
                "n_ops": int(grp["ops"]),
                "key_seed": hashlib.blake2b(
                    f"{seed}:{g}:{j}".encode(), digest_size=32
                ).digest(),
            })
    return plan


def group_templates(corpus: dict, seed: int) -> List[List[List[dict]]]:
    """[group][template] -> wire-form changes."""
    out = []
    for g, grp in enumerate(corpus["groups"]):
        kw = {
            k: grp[k] for k in (
                "ops_per_change", "seq_frac", "n_keys", "seq_key",
                "seq_type", "refs",
            ) if k in grp
        }
        distinct = min(int(grp["distinct"]), int(grp["count"]))
        out.append([
            template_changes(
                int(grp["ops"]), (seed * 1000003 + g * 4099 + t) % 2**63,
                **kw,
            )
            for t in range(distinct)
        ])
    return out


# ---------------------------------------------------------------------------
# pool workers (JAX-free: the process that holds the chip is elsewhere)

_W: Dict[str, Any] = {}


def _worker_init(feeds_root: str, raw_blocks, sign: bool) -> None:
    _W.update(feeds_root=feeds_root, raw=raw_blocks, sign=sign)


def _write_feeds(jobs) -> int:
    """jobs: [(group, template, public key, secret key)] -> the block
    log, its `.len` index and the `.sig` chain of each doc's feed."""
    from hypermerge_tpu.storage import block as blockmod
    from hypermerge_tpu.storage.integrity import sign_chain
    from hypermerge_tpu.utils import keys as keymod

    tab = _TEMPLATE_ACTOR.encode("ascii")
    root = _W["feeds_root"]
    for g, t, pk, sk in jobs:
        d = os.path.join(root, pk[:2])
        os.makedirs(d, exist_ok=True)
        pkb = pk.encode("ascii")
        blocks = [
            blockmod.pack_raw(raw.replace(tab, pkb))
            for raw in _W["raw"][g][t]
        ]
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
    return len(jobs)


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
        self.templates = group_templates(corpus, seed)
        self.plan = doc_plan(corpus, seed)
        self._pool = None
        self._pending = []

    def start(self) -> "CorpusJob":
        import multiprocessing

        from hypermerge_tpu.ops.corpus import _Template
        from hypermerge_tpu.crdt.change import Change
        from hypermerge_tpu.storage.slab import KIND_IMAGE, CorpusSlab
        from hypermerge_tpu.utils import keys as keymod
        from hypermerge_tpu.utils.json_buffer import bufferify

        feeds_root = os.path.join(self.path, "feeds")
        os.makedirs(feeds_root, exist_ok=True)
        rendered = [
            [_Template([Change.from_json(c) for c in tpl]) for tpl in grp]
            for grp in self.templates
        ]
        raw = [[t.raw_blocks for t in grp] for grp in rendered]
        if not all(
            r == [bufferify(c) for c in tpl]
            for rg, tg in zip(raw, self.templates)
            for r, tpl in zip(rg, tg)
        ):
            raise RuntimeError(
                "corpus: wire form changed in the program's Change round trip"
            )
        self.pairs = [keymod.create(d["key_seed"]) for d in self.plan]
        jobs = [
            (d["group"], d["template"], p.public_key, p.secret_key)
            for d, p in zip(self.plan, self.pairs)
        ]
        n = self.workers
        # interleave, so every worker gets the same mix of doc sizes
        shares = [jobs[i::n * 4] for i in range(n * 4)]
        ctx = multiprocessing.get_context("spawn")
        self._pool = ctx.Pool(
            n, initializer=_worker_init,
            initargs=(feeds_root, raw, bool(self.corpus.get("sign", True))),
        )
        self._pending = [
            self._pool.apply_async(_write_feeds, (s,)) for s in shares if s
        ]
        # meanwhile, here: the slab of sidecar checkpoints and the rows
        slab = CorpusSlab(os.path.join(feeds_root, "cols.slab"))
        try:
            for d, p in zip(self.plan, self.pairs):
                tpl = rendered[d["group"]][d["template"]]
                slab.append(
                    KIND_IMAGE, p.public_key,
                    tpl.checkpoint_bytes(p.public_key),
                )
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
        pks = [p.public_key for p in self.pairs]
        with db.bulk():
            db.executemany(
                "INSERT OR REPLACE INTO cursors "
                "(repo_id, doc_id, actor_id, seq) VALUES (?,?,?,?)",
                [(rid, pk, pk, INFINITY_SEQ) for pk in pks],
            )
            db.executemany(
                "INSERT OR REPLACE INTO clocks "
                "(repo_id, doc_id, actor_id, seq) VALUES (?,?,?,?)",
                [
                    (rid, pk, pk,
                     rendered[d["group"]][d["template"]].n_changes)
                    for d, pk in zip(self.plan, pks)
                ],
            )
            db.executemany(
                "INSERT OR REPLACE INTO feeds "
                "(public_id, discovery_id, is_writable) VALUES (?,?,0)",
                [(pk, keymod.discovery_id(pk)) for pk in pks],
            )
        db.close()

    def finish(self) -> List[str]:
        from hypermerge_tpu.utils.ids import to_doc_url

        try:
            done = sum(r.get(600) for r in self._pending)
        finally:
            self._pool.terminate()
            self._pool.join()
            self._pool = None
        if done != len(self.plan):
            raise RuntimeError(
                f"corpus: {done} of {len(self.plan)} feeds written"
            )
        return [to_doc_url(p.public_key) for p in self.pairs]

    def abort(self) -> None:
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None

    def doc_reference(self, i: int, cache: dict) -> Dict[str, Any]:
        """The plain reference's replay of doc i. Docs stamped from one
        template differ in their writer's key only, so the replay is
        made once a template (kept in `cache`) and renamed."""
        from benchmark.reference import crdt_plain

        d = self.plan[i]
        t = (d["group"], d["template"])
        if t not in cache:
            cache[t] = crdt_plain.replay(self.templates[t[0]][t[1]])
        return crdt_plain.rename_actor(
            cache[t], _TEMPLATE_ACTOR, self.pairs[i].public_key
        )
