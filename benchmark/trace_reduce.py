"""From a profiler trace (.xplane.pb) to the numbers the per-layer
metrics read: device busy seconds (the union of the intervals in which
an operation ran), the window, seconds per XLA program, the operations
that took most time, and the longest idle gaps named by the benchmark's
own host spans (`bench.<layer>.<what>`, harness.span).

Kept with the benchmark so that every PR reduces a trace in the same
way; `selftest/run.py` checks it on a small recorded trace and on a
hand-made one. Reads the file with nothing but JAX
(`jax.profiler.ProfileData`).

A trace is handled as plain data: [(plane name, [(line name,
[(event name, start ns, duration ns)])])].
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

Event = Tuple[str, float, float]
Planes = List[Tuple[str, List[Tuple[str, List[Event]]]]]

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OP_LINES = ("XLA Ops",)
_MODULE_LINES = ("XLA Modules",)
_SPAN_PREFIX = "bench."
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def load(path: str) -> Planes:
    from jax.profiler import ProfileData

    out: Planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events
            ]))
        out.append((plane.name, lines))
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_name(event_name: str) -> str:
    """A device operation's event name is its whole HLO instruction:
    keep the result name and the opcode (`%fusion.27 fusion`)."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name[:80]
    m = re.search(r"\b([a-z][a-z0-9_-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


def program_name(event_name: str) -> str:
    """`jit_materialize_full_lean_device(1234567)` -> without the id."""
    return _ID_SUFFIX.sub("", event_name)


def reduce_planes(planes: Planes, window_s: Optional[float] = None
                  ) -> Dict[str, Any]:
    devices = [(n, ls) for n, ls in planes if _DEVICE.match(n)]
    spans: List[Event] = []
    lo, hi = float("inf"), float("-inf")
    for name, lines in planes:
        for lname, events in lines:
            for e in events:
                if e[2] > 0 or e[0].startswith(_SPAN_PREFIX):
                    lo, hi = min(lo, e[1]), max(hi, e[1] + e[2])
                if not _DEVICE.match(name) and e[0].startswith(_SPAN_PREFIX):
                    spans.append(e)
    extent_s = max(0.0, hi - lo) / 1e9 if hi > lo else 0.0
    busy_each: List[float] = []
    programs: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for d, (name, lines) in enumerate(devices):
        by_line = dict(lines)
        op_events = next(
            (by_line[l] for l in _OP_LINES + _MODULE_LINES if by_line.get(l)),
            [],
        )
        merged = union([(s, s + dur) for _n, s, dur in op_events if dur > 0])
        busy_each.append(sum(b - a for a, b in merged) / 1e9)
        for l in _MODULE_LINES:
            for n, _s, dur in by_line.get(l, ()):
                programs[program_name(n)] = (
                    programs.get(program_name(n), 0.0) + dur / 1e9
                )
        for l in _OP_LINES:
            for n, _s, dur in by_line.get(l, ()):
                n = op_name(n)
                ops[n] = ops.get(n, 0.0) + dur / 1e9
        if d == 0 and merged:  # gaps of the first chip, lead-in included
            edge = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps = [
                (edge[i], edge[i + 1]) for i in range(0, len(edge), 2)
                if edge[i + 1] > edge[i]
            ]
    n_dev = max(1, len(devices))
    busy_s = sum(busy_each) / n_dev
    # the programs and operations are summed over the chips: per chip
    programs = {k: v / n_dev for k, v in programs.items()}
    ops = {k: v / n_dev for k, v in ops.items()}
    window = float(window_s) if window_s else extent_s
    if busy_s > window:
        raise ValueError(
            f"trace: device busy {busy_s} s in a window of {window} s"
        )
    named = []
    gaps = [g for g in gaps if g[1] - g[0] >= 1e3]  # under 1 us: not a gap
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        named.append([_gap_name(a, b, spans), (b - a) / 1e9])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    if not top:
        top = sorted(programs.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": len(devices),
        "busy_s": busy_s,
        "busy_s_per_device": busy_each,
        "window_s": window,
        "extent_s": extent_s,
        "programs": programs,
        "top_ops": [[n, s] for n, s in top],
        "top_programs": [
            [n, s] for n, s in
            sorted(programs.items(), key=lambda kv: -kv[1])[:10]
        ],
        "idle_gaps": named,
        "host_spans": len(spans),
    }


def _gap_name(a: float, b: float, spans: List[Event]) -> str:
    """The benchmark's host span that covers most of the gap; the
    innermost (shortest) of those that cover it equally."""
    best, cover, length = "unattributed", 0.0, float("inf")
    for n, s, dur in spans:
        c = min(b, s + dur) - max(a, s)
        if c > cover * 1.001 or (c > 0 and c >= cover * 0.999 and dur < length):
            best, cover, length = n, c, dur
    return best


def reduce_file(path: str, window_s: Optional[float] = None) -> Dict[str, Any]:
    return reduce_planes(load(path), window_s)
