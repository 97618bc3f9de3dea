"""The plain reference for a store cloned from a peer: what a cloned
document must be, what a read of it must answer, and what the clone
must hold on disk. Independent of the program: nothing of
`hypermerge_tpu` is imported and nothing the program computed is read
but the bytes of its block logs and signature chains.

A cloned document is the replay (`crdt_plain.replay`: causal order,
observed-remove, winner by (counter, actor), the insertion tree) of
EVERY change the corpus writer put on the source's disk for it, under
the document's own writer keys, whatever order its feeds arrived in.
A `len` read of its text answers the number of visible elements.

On disk a feed is `feeds/<first two characters of its key>/<key>`: the
block log, a uint32 length before each block (`feed_blocks`), beside
`<key>.sig`, the chain of 104-byte records (uint64 length, 32-byte
merkle root, 64-byte ed25519 signature). A clone holds a feed whole
when its log equals the source's byte for byte, and holds nothing it
could not have verified when its newest signature record covers
exactly the blocks it stores.
"""

from __future__ import annotations

import os
import struct
from typing import Any, Dict, Iterable, List, Optional

from benchmark.reference import crdt_plain, rw_plain

_REC = struct.Struct("<Q32s64s")


def expect(changes: List[dict], seq_key: str = "t") -> Dict[str, Any]:
    """-> {"value": the plain tree, "clock": {actor: seq}, "len": what
    {"kind": "len", "path": [seq_key]} answers} after every change."""
    got = crdt_plain.replay(changes)
    seq = got["value"].get(seq_key)
    if isinstance(seq, dict) and "__text__" in seq:
        n: Optional[int] = len(seq["__text__"])
    elif isinstance(seq, list):
        n = len(seq)
    else:
        n = None
    return {"value": got["value"], "clock": got["clock"], "len": n}


def feed_blocks(path: str) -> List[bytes]:
    """The blocks of one feed's log, in order (`rw_plain.feed_blocks`:
    a torn tail ends the list). A feed never written: []."""
    return rw_plain.feed_blocks(path) if os.path.exists(path) else []


def signed_length(path: str) -> int:
    """The block count the newest whole record of a `.sig` chain
    covers; 0 without one."""
    if not os.path.exists(path):
        return 0
    size = os.path.getsize(path) // _REC.size * _REC.size
    if not size:
        return 0
    with open(path, "rb") as fh:
        fh.seek(size - _REC.size)
        length, _root, _sig = _REC.unpack(fh.read(_REC.size))
    return int(length)


def feed_path(feeds_root: str, key: str) -> str:
    return os.path.join(feeds_root, key[:2], key)


def compare_stores(source_root: str, clone_root: str,
                   keys: Iterable[str]) -> Dict[str, int]:
    """Feed for feed, the clone's disk against the source's: `short`
    (fewer blocks than the source, or none), `differ` (as many blocks,
    other bytes), `unsigned` (blocks past the clone's newest signature
    record), with the `feeds`, `blocks` and `bytes` compared."""
    out = dict.fromkeys(
        ("feeds", "blocks", "bytes", "short", "differ", "unsigned"), 0)
    for key in keys:
        want = feed_blocks(feed_path(source_root, key))
        path = feed_path(clone_root, key)
        got = feed_blocks(path)
        out["feeds"] += 1
        out["blocks"] += len(got)
        out["bytes"] += sum(map(len, got))
        if len(got) < len(want) or not want:
            out["short"] += 1
        elif got != want:
            out["differ"] += 1
        if signed_length(path + ".sig") != len(got):
            out["unsigned"] += 1
    return out
