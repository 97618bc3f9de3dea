"""The plain reference: CRDT replay of a doc's changes, independent of
the program.

Input is the wire form of changes (the JSON dict every feed block
holds: actor, seq, startOp, deps, ops[a,o,k,r,i,v,d,p]); nothing of
`hypermerge_tpu` is imported and nothing the program computed is read.
The algorithm is the textbook one, chosen to differ from the program's
host OpSet (a flat skip-scan) and from its device kernel (sort +
pointer doubling):

- causal order: a change applies when its seq is the next of its actor
  and its deps are met;
- a map key / list element holds the set of value ops no applied op
  names in `pred` (observed-remove); the displayed winner is the
  largest op id, ids ordered by (counter, actor);
- list order: the tree of "inserted after", children in descending id
  order, walked depth first;
- a counter is its SET plus the INCs that name it while it is visible.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Tuple

MAKE_MAP, MAKE_LIST, MAKE_TEXT, MAKE_TABLE, SET, DEL, INC = range(7)
ROOT = (0, "_root")
HEAD = (0, "_head")


def _id(s: str) -> Tuple[int, str]:
    ctr, _, actor = s.partition("@")
    return (int(ctr), actor)


class _Obj:
    __slots__ = ("kind", "fields", "children", "incs")

    def __init__(self, kind: int) -> None:
        self.kind = kind
        # map: key -> {op id: op}; sequence: element id -> {op id: op}
        self.fields: Dict[Any, Dict[Tuple[int, str], dict]] = {}
        # sequence only: ref id -> ids inserted after it
        self.children: Dict[Tuple[int, str], List[Tuple[int, str]]] = {}
        self.incs: Dict[Tuple[int, str], float] = {}

    @property
    def is_sequence(self) -> bool:
        return self.kind in (MAKE_LIST, MAKE_TEXT)


def causal_order(changes: List[dict]) -> List[dict]:
    """The changes in an order every replica may apply them in;
    duplicates dropped. Raises if a dependency never arrives."""
    clock: Dict[str, int] = {}
    out: List[dict] = []
    pending = list(changes)
    while pending:
        still = []
        for c in pending:
            have = clock.get(c["actor"], 0)
            if c["seq"] <= have:
                continue
            if c["seq"] == have + 1 and all(
                clock.get(a, 0) >= s for a, s in c["deps"].items()
            ):
                clock[c["actor"]] = c["seq"]
                out.append(c)
            else:
                still.append(c)
        if len(still) == len(pending):
            raise ValueError("reference replay: missing dependencies")
        pending = still
    return out


def replay(changes: List[dict]) -> Dict[str, Any]:
    """-> {"value": plain tree, "clock": {actor: seq}, "elems": live
    list/text elements over all sequences, "map_entries": map keys
    that hold a visible value over all maps}."""
    objs: Dict[Tuple[int, str], _Obj] = {ROOT: _Obj(MAKE_MAP)}
    clock: Dict[str, int] = {}
    for c in causal_order(changes):
        clock[c["actor"]] = c["seq"]
        for i, op in enumerate(c["ops"]):
            _apply(objs, (c["startOp"] + i, c["actor"]), op)
    elems = entries = 0
    for o in objs.values():
        live = sum(1 for vis in o.fields.values() if vis)
        if o.is_sequence:
            elems += live
        else:
            entries += live
    return {
        "value": _plain(objs, ROOT),
        "clock": clock,
        "elems": elems,
        "map_entries": entries,
    }


def _apply(objs, opid, op: dict) -> None:
    obj = objs.get(_id(op["o"]))
    if obj is None:
        return
    a = op["a"]
    if a <= MAKE_TABLE and opid not in objs:
        if a == MAKE_TABLE:
            raise ValueError("reference replay: tables are not modelled")
        objs[opid] = _Obj(a)
    if obj.is_sequence:
        if op.get("i"):
            ref = _id(op["r"])
            obj.children.setdefault(ref, []).append(opid)
            obj.fields[opid] = {opid: op}
            return
        slot = obj.fields.get(_id(op["r"])) if "r" in op else None
    else:
        if "k" not in op:
            return
        slot = obj.fields.setdefault(op["k"], {})
    if slot is None:
        return
    preds = [_id(p) for p in op.get("p", ())]
    if a == INC:
        for p in preds:
            if p in slot:
                obj.incs[p] = obj.incs.get(p, 0) + (op.get("v") or 0)
        return
    for p in preds:
        if slot.pop(p, None) is not None:
            obj.incs.pop(p, None)
    if a == SET or a <= MAKE_TABLE:
        slot[opid] = op


def _order(obj: _Obj) -> List[Tuple[int, str]]:
    """Depth-first walk of the insert tree, siblings in descending id."""
    out: List[Tuple[int, str]] = []
    stack = sorted(obj.children.get(HEAD, ()))
    while stack:
        e = stack.pop()  # the largest id of the level first
        out.append(e)
        kids = obj.children.get(e)
        if kids:
            stack.extend(sorted(kids))
    return out


def _plain(objs, obj_id) -> Any:
    obj = objs[obj_id]
    if obj.is_sequence:
        vals = []
        for e in _order(obj):
            vis = obj.fields.get(e)
            if vis:
                w = max(vis)
                vals.append(_value(objs, obj, w, vis[w]))
        if obj.kind == MAKE_TEXT:
            return {"__text__": "".join(str(v) for v in vals)}
        return vals
    out = {}
    for key, vis in obj.fields.items():
        if vis:
            w = max(vis)
            out[key] = _value(objs, obj, w, vis[w])
    return out


def _value(objs, obj: _Obj, opid, op: dict) -> Any:
    if op["a"] <= MAKE_TABLE:
        return _plain(objs, opid)
    if op.get("d") == "counter":
        return {"__counter__": int((op.get("v") or 0) + obj.incs.get(opid, 0))}
    return op.get("v")


def rename_actor(replayed: Dict[str, Any], old: str, new: str):
    """A replay result with one actor renamed: docs stamped from one
    template differ in their writer's key only, and values hold no
    actor ids, so one replay serves every doc of the template."""
    return dict(
        replayed,
        clock={(new if a == old else a): s
               for a, s in replayed["clock"].items()},
    )


if __name__ == "__main__":  # replay a file of JSON changes, print the tree
    import json

    with open(sys.argv[1]) as fh:
        print(json.dumps(replay(json.load(fh))))
