"""Reader `span_tree`: the program's own spans of one cold open, read
from the traced run's `.xplane.pb`, where they sit on the device's
clock beside the device ops (hypermerge_tpu/telemetry/trace.py enters
every span as a `jax.profiler.TraceAnnotation` while a profiler session
runs; its keywords come back as the event's stats).

The tree of one open is rebuilt from three things the spans carry:
`open` (the request id every span of one open shares), `slab`, and
nesting: a span's parent is the innermost span of the same open that
contains it on its own thread; failing that the span its `parent=<name>`
names (same slab where both carry one); failing that the root,
`repo.open_many`.

`params.measure` picks the number:

- `total`: seconds of the spans named in `names` (`slab` restricts).
- `self`: their self seconds: duration minus what the spans nested in
  them on their own thread cover. `before_root` names spans that run
  just before the root on its thread (`repo.init`) and count too.
- `first_dispatch`: root start to the end of `pipeline.enqueue{slab=0}`.
- `chain_wait`: seconds slab work sat between stages: for each slab, the
  start of its io to the end of its fetch, minus the union of its busy
  spans (io, spec, pack, dispatch, fetch); summed over the slabs.
- `head_idle`: root start to the first device operation after it.
- `idle_attributed_pct`: of the seconds device 0 is idle between the
  root's start and the open's last span, the share covered by a span
  below the root (`idle_table` names each gap; the reader logs it).

A trace without program spans (the program before PR 24) gives None.
The trace is the newest under benchmark/.cache/trace-*/ (README: it
stays there until the cell's next traced run).
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# `<subsystem>.<what>`: the subsystems of hypermerge_tpu/telemetry
PROGRAM = re.compile(
    r"^(repo|frontend|pipeline|storage|host|mesh|live|net|serve|service"
    r"|lock)\.[\w.]+$"
)
ROOT = "repo.open_many"
WAIT = "pipeline.wait"
BUSY = ("pipeline.io", "pipeline.spec", "pipeline.pack",
        "pipeline.dispatch", "pipeline.fetch")
DEVICE0 = re.compile(r"^/device:TPU:0$")
_DEVICE = re.compile(r"^/device:")

Interval = Tuple[float, float]


class Span:
    """One program span: seconds on the trace's clock, the thread
    (trace line) it ran on, its tags; `parent` / `depth` once placed
    in a tree."""

    __slots__ = ("name", "t0", "t1", "line", "args", "parent", "depth")

    def __init__(self, name: str, t0: float, t1: float, line: Any,
                 args: Dict[str, Any]) -> None:
        self.name, self.t0, self.t1 = name, t0, t1
        self.line, self.args = line, args
        self.parent: Optional["Span"] = None
        self.depth = 0

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    @property
    def slab(self) -> Optional[int]:
        return self.args.get("slab")

    def __repr__(self) -> str:
        return f"<{self.name} {self.t0:.4f}+{self.dur:.4f} {self.args}>"


def newest_trace() -> Optional[str]:
    found = glob.glob(os.path.join(
        HERE, ".cache", "trace-*", "plugins", "profile", "*", "*.xplane.pb"
    ))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def load(path: str) -> Tuple[List[Span], List[Interval]]:
    """(program spans of the host planes, merged busy intervals of
    device 0), in seconds."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    busy: List[Interval] = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if _DEVICE.match(plane.name):
            if not DEVICE0.match(plane.name):
                continue
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops") or lines.get("XLA Modules")
            if ops is not None:
                busy = trace_reduce.union([
                    (e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                    for e in ops.events if e.duration_ns > 0
                ])
            continue
        for l, line in enumerate(plane.lines):
            for e in line.events:
                if PROGRAM.match(e.name):
                    spans.append(Span(
                        e.name, e.start_ns / 1e9,
                        (e.start_ns + e.duration_ns) / 1e9, (p, l),
                        {k: v for k, v in e.stats},
                    ))
    return spans, busy


def from_chrome(events: List[Dict[str, Any]]) -> List[Span]:
    """The same records from an HM_TRACE ring file (Chrome trace-event
    dicts): for scripts/profile_trace.py, no device beside them."""
    return [
        Span(e["name"], e["ts"] / 1e6, (e["ts"] + e.get("dur", 0)) / 1e6,
             e.get("tid"), dict(e.get("args") or {}))
        for e in events
        if e.get("ph") == "X" and PROGRAM.match(e.get("name", ""))
    ]


def _covers(a: Span, b: Span) -> bool:
    return a is not b and a.t0 <= b.t0 and b.t1 <= a.t1


class Tree:
    """The spans of one open, placed under its root."""

    def __init__(self, spans: List[Span], open_id: Any = None) -> None:
        roots = [s for s in spans if s.name == ROOT and "open" in s.args
                 and open_id in (None, s.args["open"])]
        if not roots:
            raise LookupError("no repo.open_many span")
        self.root = max(roots, key=lambda s: s.t0)  # the latest open
        self.open = self.root.args["open"]
        self.all = spans
        self.members = sorted(
            (s for s in spans if s.args.get("open") == self.open),
            key=lambda s: (s.t0, -s.t1),
        )
        self.end = max(s.t1 for s in self.members)
        # sorted by (start, longest first): a parent comes before its
        # children, so each span looks only at those placed before it
        for i, s in enumerate(self.members):
            s.parent = self._parent_of(s, self.members[:i])
            s.depth = 0 if s.parent is None else s.parent.depth + 1

    def _parent_of(self, s: Span, earlier: List[Span]) -> Optional[Span]:
        if s is self.root:
            return None
        inside = [m for m in earlier if m.line == s.line and _covers(m, s)]
        if inside:
            return min(inside, key=lambda m: m.dur)
        named = [m for m in earlier if m.name == s.args.get("parent")]
        same = [m for m in named if m.slab == s.slab]
        for group in (same, named):
            if group:
                return max(group, key=lambda m: m.t0)
        return self.root if self.root in earlier else None

    # -- measures --------------------------------------------------------

    def named(self, names, slab=None) -> List[Span]:
        """Spans of the open with one of `names`; a span that carries
        no `open` (host.gc on a thread outside any context) counts
        where it falls inside the open's extent."""
        out = []
        for s in self.all:
            if s.name not in names or (slab is not None and s.slab != slab):
                continue
            mine = s.args.get("open", None)
            if mine == self.open or (
                mine is None and s.t0 >= self.root.t0 and s.t1 <= self.end
            ):
                out.append(s)
        return out

    def total(self, names, slab=None) -> float:
        return sum(s.dur for s in self.named(names, slab))

    def self_s(self, s: Span) -> float:
        """Duration minus what program spans nested in it on its own
        thread cover."""
        kids = [(max(k.t0, s.t0), min(k.t1, s.t1)) for k in self.all
                if k.line == s.line and _covers(s, k)]
        return s.dur - sum(b - a for a, b in trace_reduce.union(kids))

    def before_root(self, name: str) -> Optional[Span]:
        prior = [s for s in self.all if s.name == name
                 and s.line == self.root.line and s.t1 <= self.root.t0]
        return max(prior, key=lambda s: s.t1) if prior else None

    def slabs(self) -> List[int]:
        return sorted({s.slab for s in self.members
                       if s.name in BUSY and s.slab is not None})

    def chain(self, slab: int) -> List[Span]:
        """A slab's busy spans in the order the work took them."""
        return sorted((s for s in self.members
                       if s.name in BUSY and s.slab == slab),
                      key=lambda s: s.t0)

    def chain_wait(self, slab: int) -> float:
        chain = self.chain(slab)
        if not chain:
            return 0.0
        busy = trace_reduce.union([(s.t0, s.t1) for s in chain])
        return (max(s.t1 for s in chain) - chain[0].t0
                - sum(b - a for a, b in busy))

    def first_dispatch(self) -> Optional[float]:
        hit = self.named(("pipeline.enqueue",), slab=0)
        return min(s.t1 for s in hit) - self.root.t0 if hit else None

    # -- the device's idle seconds, by what the host was doing -----------

    def idle_gaps(self, busy: List[Interval]) -> List[Interval]:
        """Where device 0 ran nothing, from the root's start to the
        open's last span."""
        lo, hi = self.root.t0, self.end
        edge = [lo]
        for a, b in busy:
            if b <= lo or a >= hi:
                continue
            edge += [max(a, lo), min(b, hi)]
        edge.append(hi)
        return [(edge[i], edge[i + 1]) for i in range(0, len(edge), 2)
                if edge[i + 1] > edge[i]]

    def _owner(self, t: float) -> Optional[Span]:
        """The span that names instant `t` of an idle gap. Each thread
        is in its innermost span of the open that covers `t`; of those,
        slab work that feeds the device (io to enqueue) comes before
        fetch, which is behind it, before work that carries no slab
        (register, a container's own time), before waiting. Then the
        lowest slab: the device runs slabs in order, so it waits for
        the earliest one not yet enqueued."""
        active: Dict[Any, Span] = {}
        for s in self.members:
            if s is not self.root and s.t0 <= t < s.t1:
                cur = active.get(s.line)
                if cur is None or (s.t0, -s.t1) >= (cur.t0, -cur.t1):
                    active[s.line] = s

        def key(s: Span):
            rank = (3 if s.name == WAIT else 1 if s.name == "pipeline.fetch"
                    else 2 if s.slab is None else 0)
            return (rank, s.slab if s.slab is not None else 0, -s.t0)

        return min(active.values(), key=key) if active else None

    def idle_table(self, busy: List[Interval]
                   ) -> List[Tuple[float, float, str, Optional[int]]]:
        """[(gap start from the root, seconds, span name, slab)]: each
        idle gap cut where the owning span changes."""
        cuts = sorted({t for s in self.members for t in (s.t0, s.t1)})
        out: List[Tuple[float, float, str, Optional[int]]] = []
        for a, b in self.idle_gaps(busy):
            i, j = bisect.bisect_right(cuts, a), bisect.bisect_left(cuts, b)
            edges = [a] + cuts[i:j] + [b]
            for x, y in zip(edges, edges[1:]):
                if y <= x:
                    continue
                own = self._owner((x + y) / 2)
                name = own.name if own else ROOT + " (self)"
                slab = own.slab if own else None
                if out and out[-1][2:] == (name, slab) and abs(
                    out[-1][0] + out[-1][1] - (x - self.root.t0)
                ) < 1e-9:
                    out[-1] = (out[-1][0], out[-1][1] + y - x, name, slab)
                else:
                    out.append((x - self.root.t0, y - x, name, slab))
        return out

    def head_idle(self, busy: List[Interval]) -> Optional[float]:
        after = [a for a, b in busy if b > self.root.t0]
        return max(0.0, min(after) - self.root.t0) if after else None


def tree_of(obs) -> Optional[Tuple[Tree, List[Interval]]]:
    path = newest_trace()
    if path is None:
        return None
    spans, busy = load(path)
    try:
        return Tree(spans), busy
    except LookupError:
        return None


def read(params, obs):
    got = tree_of(obs)
    if got is None:
        return None
    tree, busy = got
    measure = params["measure"]
    if measure == "total":
        hit = tree.named(tuple(params["names"]), params.get("slab"))
        return sum(s.dur for s in hit) if hit else None
    if measure == "self":
        hit = tree.named(tuple(params["names"]))
        hit += [s for s in map(tree.before_root,
                               params.get("before_root", ())) if s]
        return sum(tree.self_s(s) for s in hit) if hit else None
    if measure == "first_dispatch":
        return tree.first_dispatch()
    if measure == "chain_wait":
        slabs = tree.slabs()
        return sum(tree.chain_wait(k) for k in slabs) if slabs else None
    if measure == "head_idle":
        return tree.head_idle(busy) if busy else None
    if measure == "idle_attributed_pct":
        if not busy:
            return None
        table = tree.idle_table(busy)
        idle = sum(r[1] for r in table)
        from benchmark.harness import log

        for at, secs, name, slab in table:
            if secs >= 0.05:
                log(f"idle gap at {at:.3f}s: {secs:.3f}s  {name}"
                    + ("" if slab is None else f"{{slab={slab}}}"))
        if idle <= 0:
            return None
        named = sum(r[1] for r in table if not r[2].startswith(ROOT))
        return 100.0 * named / idle
    raise SystemExit(f"benchmark: span_tree has no measure {measure!r}")
