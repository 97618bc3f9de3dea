"""The plain reference for reads: one query evaluated on the replay of
a doc's changes, independent of the program.

`replay_objs` is `crdt_plain`'s replay kept as objects (a text's
elements stay apart, which its plain tree joins), `evaluate` walks a
query's `path` through them. Nothing of `hypermerge_tpu` is imported
and nothing the program computed is read. The query forms are the read
tier's (README "Serving reads"):

    {"kind": "text",   "path": [...]}             the joined text
    {"kind": "lookup", "path": [..., key]}        the value at a map key
    {"kind": "len",    "path": [...]}             elements / map entries
    {"kind": "index",  "path": [...], "index": i} the i-th live element

`path` holds map keys (str) and sequence positions (int) from the root.
A container answers as a type marker ({"_type": "text"}), a counter as
its total, a path that does not resolve as None.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.reference.crdt_plain import (
    MAKE_LIST, MAKE_MAP, MAKE_TABLE, MAKE_TEXT, ROOT, _apply, _Obj, _order,
    causal_order,
)

_TYPE = {MAKE_MAP: "map", MAKE_LIST: "list", MAKE_TEXT: "text"}
OpId = Tuple[int, str]


def replay_objs(changes: List[dict]) -> Dict[OpId, _Obj]:
    """Every object of the doc after its changes, by the id of the op
    that made it (the root map under `ROOT`)."""
    objs: Dict[OpId, _Obj] = {ROOT: _Obj(MAKE_MAP)}
    for c in causal_order(changes):
        for i, op in enumerate(c["ops"]):
            _apply(objs, (c["startOp"] + i, c["actor"]), op)
    return objs


def _elements(obj: _Obj) -> List[Tuple[OpId, dict]]:
    """A sequence's live elements in list order: (winning op id, op)."""
    out = []
    for e in _order(obj):
        vis = obj.fields.get(e)
        if vis:
            w = max(vis)
            out.append((w, vis[w]))
    return out


def _step(objs, obj: _Obj, step) -> Optional[Tuple[_Obj, OpId, dict]]:
    """(container, winning op id, op) one path step into `obj`."""
    if isinstance(step, str) and not obj.is_sequence:
        vis = obj.fields.get(step)
        if not vis:
            return None
        w = max(vis)
        return obj, w, vis[w]
    if isinstance(step, int) and not isinstance(step, bool) \
            and obj.is_sequence:
        elems = _elements(obj)
        if 0 <= step < len(elems):
            return (obj,) + elems[step]
    return None


def _walk(objs, steps) -> Optional[_Obj]:
    obj = objs[ROOT]
    for s in steps:
        hit = _step(objs, obj, s)
        if hit is None or hit[2]["a"] > MAKE_TABLE:
            return None  # broken path, or a scalar mid-path
        obj = objs[hit[1]]
    return obj


def _leaf(objs, hit: Tuple[_Obj, OpId, dict]) -> Any:
    obj, opid, op = hit
    if op["a"] <= MAKE_TABLE:
        return {"_type": _TYPE[op["a"]]}
    if op.get("d") == "counter":
        return int((op.get("v") or 0) + obj.incs.get(opid, 0))
    return op.get("v")


def evaluate(objs: Dict[OpId, _Obj], query: dict) -> Any:
    kind = query.get("kind")
    path = list(query.get("path") or [])
    if kind == "lookup":
        if not path or not isinstance(path[-1], str):
            return None
        obj = _walk(objs, path[:-1])
        hit = None if obj is None else _step(objs, obj, path[-1])
        return None if hit is None else _leaf(objs, hit)
    obj = _walk(objs, path)
    if obj is None:
        return None
    if kind == "text":
        if obj.kind != MAKE_TEXT:
            return None
        return "".join(str(_leaf(objs, (obj, w, op)))
                       for w, op in _elements(obj))
    if kind == "len":
        if obj.is_sequence:
            return len(_elements(obj))
        return sum(1 for vis in obj.fields.values() if vis)
    if kind == "index":
        if not obj.is_sequence:
            return None
        hit = _step(objs, obj, query.get("index"))
        return None if hit is None else _leaf(objs, hit)
    return None
