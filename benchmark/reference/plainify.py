"""The program's materialized values as plain JSON-safe data, in the
plain reference's notation (copy of chip_smoke.py `plain`). A
conversion of the program's OUTPUT types; no part of the reference."""

from __future__ import annotations


def plain(v):
    from hypermerge_tpu.models import Counter, Table, Text

    if isinstance(v, Text):
        return {"__text__": str(v)}
    if isinstance(v, Counter):
        return {"__counter__": int(v)}
    if isinstance(v, Table):
        return {"__table__": {k: plain(v.by_id(k)) for k in v.ids}}
    if isinstance(v, dict):
        return {k: plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [plain(x) for x in v]
    return v
