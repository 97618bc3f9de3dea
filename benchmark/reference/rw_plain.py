"""The plain reference for a store that is read AND written: what the
driver's updates left on disk, and every answer a read got, held to the
replay of the doc's changes. Independent of the program: nothing of
`hypermerge_tpu` is imported and nothing the program computed is read
but the bytes of its block logs.

A written doc has two feeds: the corpus writer's (its changes are the
template's, known from the seed) and the local one the repo minted at
the doc's first write. The local feed's block log is read from disk
after the repo is closed (`feed_blocks`: the framing the corpus writers
write, a uint32 length before each block) and each block decoded to
its wire change (`wire_change`: the binary change frame a one-op change
is stored as, decoded here from its documented layout; raw or zlib JSON
otherwise). A local feed names its doc in its first change's `deps`
(the corpus writer's key). An update is found on disk by its `message`
(`u<serial>`).

One local feed is a total order, so a doc's states are the prefixes
corpus + first k updates, k = 0..m. `check_doc` advances the corpus
replay (`read_plain.replay_objs`) one change at a time and holds:

- `answers_outside_their_window`: a read's answer has to equal the
  query's evaluation at an admissible prefix: not before the last
  update acknowledged (to any client) before the read was sent, not
  after the last update sent before the read was answered;
- `acked_lost`: an update whose call returned and that no block holds;
- `updates_twice_or_unknown`: a block the driver did not send (unknown
  or foreign serial, other content than sent, more than one op, a seq
  out of line), or a serial in two blocks;
- `updates_out_of_order`: an update acknowledged before another to the
  same doc was sent that stands after it in the feed;

and gives the doc's summary (live elements, map entries, clock) after
its last change, which the driver compares with what a fresh process
reads back (`reopen_summary_mismatches`).
"""

from __future__ import annotations

import bisect
import json
import os
import struct
import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from benchmark.reference import read_plain
from benchmark.reference.crdt_plain import _apply

_HDR = struct.Struct("<I")
_FRAME = b"\xc5\x01"
COUNTS = ("answers_outside_their_window", "acked_lost",
          "updates_twice_or_unknown", "updates_out_of_order")


# -- the bytes on disk -------------------------------------------------------


def feed_blocks(path: str) -> List[bytes]:
    """The blocks of one feed's log, in order. A torn tail (a length
    without its bytes) ends the list, as it does for the program."""
    with open(path, "rb") as fh:
        raw = fh.read()
    out, pos = [], 0
    while pos + _HDR.size <= len(raw):
        (size,) = _HDR.unpack_from(raw, pos)
        if pos + _HDR.size + size > len(raw):
            break
        out.append(raw[pos + _HDR.size:pos + _HDR.size + size])
        pos += _HDR.size + size
    return out


def wire_change(block: bytes) -> dict:
    """One block as the wire change `crdt_plain` eats."""
    if block[:2] == _FRAME:
        return _frame_change(block)
    if block[:2] == b"ZL":
        return json.loads(zlib.decompress(block[2:]))
    if block[:1] in (b"{", b"["):
        return json.loads(block)
    raise ValueError("reference: a block format this reader does not know")


class _Cursor:
    def __init__(self, buf: bytes, pos: int) -> None:
        self.buf, self.pos = buf, pos

    def varint(self) -> int:
        v = shift = 0
        while True:
            b = self.buf[self.pos]
            self.pos += 1
            v |= (b & 0x7F) << shift
            if not b & 0x80:
                return v
            shift += 7

    def text(self) -> str:
        """A token holding a JSON string's escaped inside."""
        return json.loads(b'"' + self._token() + b'"')

    def value(self) -> Any:
        return json.loads(self._token())

    def _token(self) -> bytes:
        n = self.varint()
        t = self.buf[self.pos:self.pos + n]
        if len(t) != n:
            raise ValueError("reference: truncated change frame")
        self.pos += n
        return t


def _frame_change(frame: bytes) -> dict:
    """The binary change frame (magic c5 01; actor; deps; message; ops:
    action, flags 1=key 2=ref 4=insert 8=value 16=datatype 32=pred, obj
    and the fields the flags name; seq, startOp, time), every string a
    JSON string's inside, every value a JSON token."""
    c = _Cursor(frame, 2)
    out: Dict[str, Any] = {"actor": c.text()}
    out["deps"] = {c.text(): c.varint() for _ in range(c.varint())}
    out["message"] = c.text()
    ops = []
    for _ in range(c.varint()):
        op: Dict[str, Any] = {"a": c.varint()}
        flags = frame[c.pos]
        c.pos += 1
        op["o"] = c.text()
        if flags & 1:
            op["k"] = c.text()
        if flags & 2:
            op["r"] = c.text()
        if flags & 4:
            op["i"] = True
        if flags & 8:
            op["v"] = c.value()
        if flags & 16:
            op["d"] = c.text()
        if flags & 32:
            op["p"] = [c.text() for _ in range(c.varint())]
        ops.append(op)
    out["ops"] = ops
    out["seq"], out["startOp"], out["time"] = (
        c.varint(), c.varint(), c.varint())
    if c.pos != len(frame):
        raise ValueError("reference: trailing bytes in a change frame")
    return out


def local_feeds(feeds_root: str, corpus_keys) -> Dict[str, List[dict]]:
    """{the corpus writer's key of a doc: the wire changes of the local
    feed(s) minted for it, in feed order} over every block log under
    `feeds_root` that is not a corpus writer's. A local feed names its
    doc by its first change's deps; one that names none is kept under
    the key None (every block of it is one the driver did not send)."""
    corpus = set(corpus_keys)
    out: Dict[Any, List[dict]] = {}
    for sub in sorted(os.listdir(feeds_root)):
        d = os.path.join(feeds_root, sub)
        if len(sub) != 2 or not os.path.isdir(d):
            continue
        for name in sorted(os.listdir(d)):
            if "." in name or name in corpus or not name.startswith(sub):
                continue
            changes = [wire_change(b)
                       for b in feed_blocks(os.path.join(d, name))]
            if not changes:
                continue
            owner = next(
                (a for a in changes[0].get("deps", {}) if a in corpus), None)
            out.setdefault(owner, []).extend(changes)
    return out


# -- what the driver did -----------------------------------------------------


@dataclass
class Update:
    """One update the driver sent: `op` is what it wrote ({"kind":
    "ins", "v": char} or {"kind": "set", "k": key, "v": value});
    `acked` stays None when the call raised."""

    serial: int
    op: Dict[str, Any]
    sent: float
    acked: Optional[float] = None


@dataclass
class Read:
    query: Dict[str, Any]
    sent: float
    answered: float
    answer: Any


def _same(op_sent: Dict[str, Any], change: dict) -> bool:
    """Is the block's change the one-op update the driver sent?"""
    ops = change.get("ops") or []
    if len(ops) != 1:
        return False
    op = ops[0]
    if op_sent["kind"] == "ins":
        return bool(op.get("i")) and op.get("v") == op_sent["v"] \
            and "k" not in op
    return not op.get("i") and op.get("k") == op_sent["k"] \
        and op.get("v") == op_sent["v"]


def _serial(change: dict) -> Optional[int]:
    m = change.get("message") or ""
    return int(m[1:]) if m[:1] == "u" and m[1:].isdigit() else None


class _LastBefore:
    """Over (time, place) pairs: the largest place among those whose
    time is before `t` (0 where there is none)."""

    def __init__(self, pairs) -> None:
        pairs = sorted(pairs)
        self.times = [t for t, _p in pairs]
        self.best, most = [], 0
        for _t, p in pairs:
            most = max(most, p)
            self.best.append(most)

    def __call__(self, t: float) -> int:
        at = bisect.bisect_left(self.times, t)
        return self.best[at - 1] if at else 0


def check_doc(corpus: List[dict], feed: List[dict],
              updates: List[Update], reads: List[Read]) -> Dict[str, Any]:
    """The four counts of COUNTS for one doc, and its `summary` after
    the last change. `corpus`: the corpus writer's changes under the
    doc's own key; `feed`: the local feed's changes as they stand on
    disk; `updates`, `reads`: what the driver sent to this doc."""
    by_serial = {u.serial: u for u in updates}
    pos: Dict[int, int] = {}  # serial -> 1-based place in the feed
    unknown = 0
    order: List[dict] = []  # the changes that are the driver's, in order
    for at, c in enumerate(feed):
        s = _serial(c)
        u = by_serial.get(s)
        if (u is None or s in pos or c.get("seq") != at + 1
                or not _same(u.op, c)):
            unknown += 1
            continue
        order.append(c)
        pos[s] = len(order)
    lost = sum(1 for u in updates
               if u.acked is not None and u.serial not in pos)
    # an update acknowledged before another was sent stands before it
    placed = sorted((pos[u.serial], u) for u in updates if u.serial in pos)
    out_of_order = 0
    earliest_ack_after = float("inf")
    for _p, u in reversed(placed):
        if earliest_ack_after < u.sent:
            out_of_order += 1
        if u.acked is not None:
            earliest_ack_after = min(earliest_ack_after, u.acked)

    # the admissible prefixes of every read, then one pass over them
    last_acked = _LastBefore(
        (u.acked, p) for p, u in placed if u.acked is not None)
    last_sent = _LastBefore((u.sent, p) for p, u in placed)
    wait: Dict[int, List[Tuple[int, int, Read]]] = {}  # by first prefix
    examples: List[Dict[str, Any]] = []
    for r in reads:
        lo = last_acked(r.sent)
        wait.setdefault(lo, []).append(
            (lo, max(lo, last_sent(r.answered)), r))
    objs = read_plain.replay_objs(corpus)
    clock = {}
    for c in corpus:
        clock[c["actor"]] = max(clock.get(c["actor"], 0), c["seq"])
    active: List[Tuple[int, int, Read]] = []
    outside = 0
    for k in range(len(order) + 1):
        if k:
            c = order[k - 1]
            for i, op in enumerate(c["ops"]):
                _apply(objs, (c["startOp"] + i, c["actor"]), op)
            clock[c["actor"]] = c["seq"]
        active.extend(wait.pop(k, ()))
        if not active:
            continue
        seen: Dict[str, Any] = {}
        still = []
        for lo, hi, r in active:
            key = json.dumps(r.query, sort_keys=True)
            if key not in seen:
                seen[key] = read_plain.evaluate(objs, r.query)
            if seen[key] == r.answer:
                continue  # admissible: this prefix gives the answer
            if k < hi:
                still.append((lo, hi, r))
            else:
                outside += 1
                if len(examples) < 3:
                    examples.append({
                        "query": r.query, "prefixes": [lo, hi],
                        "got": str(r.answer)[-48:],
                        "at_last": str(seen[key])[-48:],
                        "updates_on_disk": len(order),
                    })
        active = still
    outside += len(active) + sum(len(v) for v in wait.values())
    elems = entries = 0
    for o in objs.values():
        live = sum(1 for vis in o.fields.values() if vis)
        if o.is_sequence:
            elems += live
        else:
            entries += live
    return {
        "answers_outside_their_window": outside,
        "acked_lost": lost,
        "updates_twice_or_unknown": unknown,
        "updates_out_of_order": out_of_order,
        "summary": {"elems": elems, "map_entries": entries, "clock": clock},
        "updates_on_disk": len(order),
        "examples": examples,
    }
