"""The plain reference for a peer that comes back online behind: what
each document must be while the peer has only its own disk, what it
must be once it has caught up, what its subscription must have been
delivered on the way, and what the peer's directory must hold after
its close. Independent of the program: nothing of `hypermerge_tpu` is
imported and nothing the program computed is read but the bytes of its
block logs, `.len` records and signature chains, and the clock rows of
its sqlite file (python's own `sqlite3`).

A document that is `held` changes deep is the replay
(`crdt_plain.replay`) of its first `held` changes by change index; once
caught up it is the replay of ALL of them, whatever order the feeds'
tails arrived in (`caught_up`: the held prefix, then the tails one
feed at a time in any order, is the same set of changes, and the
replay orders them causally itself).

On disk a caught-up feed equals the source's: its block log and its
`.len` byte for byte (the blocks held before the catch-up included),
and its `.sig` chain ends in the source's newest record. The chains
cannot be equal whole: the source signed one record a block, the peer
stores the one record that covered each extension it verified, so
every record the peer holds must be one of the source's.
"""

from __future__ import annotations

import os
import sqlite3
import struct
from typing import Dict, Iterable, List, Optional, Sequence

from benchmark.reference import clone_plain

_REC = struct.Struct("<Q32s64s")

expect = clone_plain.expect  # {"value", "clock", "len"} of a replay


def held(changes: List[dict], n_held: int, seq_key: str = "t"):
    """What the peer shows from its own disk: the replay of the first
    `n_held` changes by change index."""
    return expect(changes[:n_held], seq_key)


def caught_up(prefix: List[dict], tails: Sequence[List[dict]],
              order: Sequence[int], seq_key: str = "t"):
    """The held prefix, then the feeds' tails in the arrival order
    `order` (indexes into `tails`)."""
    arrived = list(prefix)
    for f in order:
        arrived.extend(tails[f])
    return expect(arrived, seq_key)


def delivery_fault(indexes: Sequence[int], n_held: int,
                   n_all: int) -> Optional[str]:
    """The indexes (counts of changes applied) a document's
    subscription was delivered, in order: the state held first, then
    every missing change, none twice -> None, or what is wrong."""
    if not indexes:
        return "nothing delivered"
    if indexes[0] != n_held:
        return f"first delivery at {indexes[0]}, held {n_held}"
    if any(b <= a for a, b in zip(indexes, indexes[1:])):
        return "an index was delivered twice or went back"
    if indexes[-1] != n_all:
        return f"last delivery at {indexes[-1]} of {n_all}"
    return None


def _records(path: str) -> List[bytes]:
    if not os.path.exists(path):
        return []
    with open(path, "rb") as fh:
        raw = fh.read()
    n = len(raw) // _REC.size
    return [raw[i * _REC.size:(i + 1) * _REC.size] for i in range(n)]


def _bytes(path: str) -> Optional[bytes]:
    try:
        with open(path, "rb") as fh:
            return fh.read()
    except OSError:
        return None


def compare_stores(source_root: str, peer_root: str,
                   keys: Iterable[str]) -> Dict[str, int]:
    """Feed for feed, the peer's disk against the source's: `short`
    (fewer blocks, or none), `differ` (the block log, the `.len` record
    or the newest signature record is not the source's byte for byte,
    or the chain holds a record the source's lacks), `unsigned` (blocks
    past the peer's newest signature record), with the `feeds`,
    `blocks` and `bytes` compared."""
    out = dict.fromkeys(
        ("feeds", "blocks", "bytes", "short", "differ", "unsigned"), 0)
    for key in keys:
        src = clone_plain.feed_path(source_root, key)
        path = clone_plain.feed_path(peer_root, key)
        want = clone_plain.feed_blocks(src)
        got = clone_plain.feed_blocks(path)
        out["feeds"] += 1
        out["blocks"] += len(got)
        out["bytes"] += sum(map(len, got))
        theirs, ours = _records(src + ".sig"), _records(path + ".sig")
        if len(got) < len(want) or not want:
            out["short"] += 1
        elif (
            _bytes(path) != _bytes(src)
            or _bytes(path + ".len") != _bytes(src + ".len")
            or not ours or ours[-1] != theirs[-1]
            or not set(ours) <= set(theirs)
        ):
            out["differ"] += 1
        if clone_plain.signed_length(path + ".sig") != len(got):
            out["unsigned"] += 1
    return out


def disk_clocks(repo_dir: str) -> Dict[str, Dict[str, int]]:
    """{doc id: {actor: seq}} as the peer's own sqlite rows hold it
    after its close (the repo's own id is the `self.repo` key's; rows
    of seq 0 are left out)."""
    db = sqlite3.connect(os.path.join(repo_dir, "repo.db"))
    try:
        (rid,) = db.execute(
            "SELECT public_key FROM keys WHERE name='self.repo'"
        ).fetchone()
        out: Dict[str, Dict[str, int]] = {}
        for doc, actor, seq in db.execute(
            "SELECT doc_id, actor_id, seq FROM clocks WHERE repo_id=?",
            (rid,),
        ):
            if seq:
                out.setdefault(doc, {})[actor] = int(seq)
        return out
    finally:
        db.close()

