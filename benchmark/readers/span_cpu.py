"""Reader `span_cpu`: the second clock of the program's spans. Since
PR 34 a span carries, beside its wall seconds, the CPU microseconds of
its thread inside it as the stat `cpu_us` (hypermerge_tpu/telemetry/
trace.py: `time.thread_time_ns` at both ends; absent on a span that
ended on another thread than it began on). Built on `span_tree.load`
and `span_tree.Tree`; the spans are those of the traced open
(`Tree.named`) or, with `"window": true`, every such span of the
traced seconds (the read cell: no open to hang them on).

`params.measure` picks the number:

- `offcpu`: seconds the thread stood without the CPU inside the spans
  named `names`: the sum of (dur - cpu_us / 1e6), minus the same
  quantity of the spans named in `except` that lie inside one of them
  on its thread (so a span's "Python" off-CPU leaves out the native or
  blocking calls under it).
- `cpu_pct`: 100 x the sum of cpu over the sum of dur.
- `minus`: seconds of `names` less the seconds of `less` (no `less`:
  a total).
- `max`: the longest of `names`.

`per` names spans to divide by the count of (seconds a flush). Spans
without `cpu_us` count for neither clock of `offcpu` and `cpu_pct`; a
trace in which none of the named spans carries it (the program before
PR 34) reads None, not 0. No span of the name at all reads None too.
params: {"measure": "offcpu", "names": ["serve.batch"],
"except": ["serve.dispatch.fetch"], "per": ["serve.batch"],
"window": true}."""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional

from benchmark.readers import span_tree
from benchmark.readers.span_tree import Span

CPU = "cpu_us"


def cpu_s(s: Span) -> Optional[float]:
    """The span's CPU seconds, None where it carries none."""
    us = s.args.get(CPU)
    return None if us is None else float(us) / 1e6


def _inside(spans: List[Span], outer: List[Span]) -> List[Span]:
    """Those of `spans` that lie inside one of `outer` on its thread."""
    by_line: Dict[Any, List[Span]] = {}
    for o in sorted(outer, key=lambda s: s.t0):
        by_line.setdefault(o.line, []).append(o)
    starts = {ln: [o.t0 for o in os_] for ln, os_ in by_line.items()}
    out = []
    for s in spans:
        row = by_line.get(s.line)
        if not row:
            continue
        i = bisect.bisect_right(starts[s.line], s.t0) - 1
        if i >= 0 and s.t1 <= row[i].t1:
            out.append(s)
    return out


def _clocked(spans: List[Span]) -> List[Span]:
    return [s for s in spans if CPU in s.args]


def _off(spans: List[Span]) -> float:
    return sum(s.dur - cpu_s(s) for s in spans)


def offcpu(hit: List[Span], less: List[Span]) -> Optional[float]:
    hit = _clocked(hit)
    if not hit:
        return None
    return _off(hit) - _off(_clocked(_inside(less, hit)))


def cpu_pct(hit: List[Span]) -> Optional[float]:
    hit = _clocked(hit)
    wall = sum(s.dur for s in hit)
    return 100.0 * sum(cpu_s(s) for s in hit) / wall if wall > 0 else None


def measure(params, find) -> Optional[float]:
    """`find(names)` gives the spans of those names in the scope."""
    hit = find(params["names"])
    kind = params["measure"]
    if not hit:
        return None
    if kind == "offcpu":
        value = offcpu(hit, find(params.get("except", ())))
    elif kind == "cpu_pct":
        value = cpu_pct(hit)
    elif kind == "minus":
        value = (sum(s.dur for s in hit)
                 - sum(s.dur for s in find(params.get("less", ()))))
    elif kind == "max":
        value = max(s.dur for s in hit)
    else:
        raise SystemExit(f"benchmark: span_cpu has no measure {kind!r}")
    if value is not None and "per" in params:
        n = len(find(params["per"]))
        return value / n if n else None
    return value


def read(params, obs):
    if params.get("window"):
        path = span_tree.newest_trace()
        if path is None or not obs.get("trace"):
            return None
        spans, _busy = span_tree.load(path)
        return measure(
            params, lambda names: [s for s in spans if s.name in names])
    got = span_tree.tree_of(obs)
    if got is None:
        return None
    tree, _busy = got
    return measure(params, lambda names: tree.named(tuple(names)))
