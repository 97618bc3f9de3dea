"""Replay a trace of the program's spans into the busy-vs-wall stage
timeline.

Takes either file the span seam (hypermerge_tpu/telemetry/trace.py)
feeds: the Chrome trace-event JSON a run wrote under HM_TRACE=<path>,
or the `.xplane.pb` of a `jax.profiler` session that ran while the
program did (there the spans sit on the device's clock, beside the
device ops). Prints busy seconds per span name vs the overlapped wall
clock, so a trace from ANY run (bench, daemon, test) answers "where
did the time go" without re-running it under a profiler.

Usage:
    python scripts/profile_trace.py /tmp/t.json [--by name|cat|slab]
        [--top N] [--threads]

--by cat groups by subsystem (live/pipeline/net/storage/mesh/serve)
instead of span name; --threads adds a per-thread busy breakdown. The
serving tier's spans show up as `serve.read` (per-request latency,
admission to completion) and `serve.batch` (one coalesced kernel
flush) — their count ratio IS the read-batching factor.

--by slab follows each cold open in the trace (the span tree of
benchmark/readers/span_tree.py: `open` / `slab` ids and nesting): the
head of the open stage by stage, each chunk of docs on the io thread
(io -> spec -> form and the slabs it completed; slabs are formed by
length, so a chunk feeds several and a slab holds docs of several),
each slab's shape (`[D x N]`, its real rows and padded cells a row,
from the tags of its `pipeline.pack` span: a ragged open reads at a
glance) and walk pack -> dispatch -> fetch across its threads, each
span with its wall seconds, the CPU seconds of its thread inside it
(`cpu`: the span's `cpu_us` stat, `tdur` in a ring file) and the rest
(`off` = wall - CPU: the thread stood without the CPU: a blocking
call, a page, or the GIL; `-` on a span that carries no CPU value),
with the seconds the work waited between stages (a trace from before the
`chunk` tag: io -> spec -> pack -> dispatch -> fetch, a chunk being a
slab), and, from an `.xplane.pb` with a device in it,
each idle gap of device 0 over 50 ms by the span that covers it.

Under HM_PACK_WORKERS>1 the pack plane fans out: each pool worker
emits its own `pipeline.pack` spans from an `hm-pipe-pack-<i>` thread,
so `--threads` draws one busy lane per pack worker (their sum past the
`pipeline.pack` row's share of the wall is the pool's realized
speedup).

Instrumented runs (HM_LOCKDEP=1 / HM_RACEDEP=1) add two instants in
the `lock` category: `lock.held_blocking` (a blocking primitive ran
while a no-block emission lock was held — each one is a stall of every
doc's patch pushes) and `lock.racedep_violation` (the lockset detector
observed a guard-manifest violation). Their counts surface in the
instants total; grep the trace JSON for the names to locate them on
the timeline.
"""

import argparse
import json
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_events(path):
    """Chrome trace-event dicts, and the busy intervals of device 0
    (seconds; empty for a ring file). An `.xplane.pb` is read through
    the benchmark's span-tree reader (needs jax)."""
    if path.endswith(".pb"):
        from benchmark.readers import span_tree

        spans, busy = span_tree.load(path)
        lines = {}
        return [
            {
                "ph": "X", "name": s.name, "cat": s.name.split(".")[0],
                "ts": s.t0 * 1e6, "dur": s.dur * 1e6,
                "tid": lines.setdefault(s.line, len(lines) + 1),
                "args": s.args,
            }
            for s in spans
        ], busy
    with open(path) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    return [e for e in events if isinstance(e, dict)], []


def _cpu_cols(s) -> str:
    """"  cpu 0.412s  off 1.688s" from the span's CPU stat."""
    from benchmark.readers.span_cpu import cpu_s

    cpu = cpu_s(s)
    if cpu is None:
        return "  cpu        -  off        -"
    return f"  cpu {cpu:8.3f}s  off {s.dur - cpu:8.3f}s"


def slab_view(events, busy, out=sys.stdout) -> bool:
    """Every cold open in the trace, followed by request and slab."""
    from benchmark.readers import span_tree

    # a ring file carries a span's CPU microseconds as the event's
    # `tdur`, an `.xplane.pb` as the stat `cpu_us`: one name from here
    events = [
        dict(e, args=dict(e.get("args") or {}, cpu_us=e["tdur"]))
        if "tdur" in e else e for e in events
    ]
    spans = span_tree.from_chrome(events)
    opens = sorted({s.args["open"] for s in spans
                    if s.name == span_tree.ROOT and "open" in s.args})
    for open_id in opens:
        _open_view(span_tree.Tree(spans, open_id), busy, span_tree, out)
    return bool(opens)


def _slab_shape(tree, k) -> str:
    """"; [D x N], R real rows, C.CC cells a row" from the tags of the
    slab's `pipeline.pack` span ("" in a trace from before them)."""
    for s in tree.named(("pipeline.pack",), k):
        a = s.args
        if "D" in a and "N" in a:
            d, n, rows = int(a["D"]), int(a["N"]), int(a.get("rows", 0))
            real = (f", {rows} real rows, {d * n / rows:.2f} cells a row"
                    if rows else "")
            return f"; [{d} x {n}]{real}"
    return ""


def _open_view(tree, busy, span_tree, out) -> None:
    root = tree.root
    w = out.write
    w(f"open {tree.open}: {root.dur:.3f}s in repo.open_many, "
      f"{tree.end - root.t0:.3f}s to its last span, "
      f"{len(tree.members)} spans\n")
    w("head (spans of the open that carry no slab, by start):\n")
    for s in tree.members:
        if (s.slab is None and "chunk" not in s.args
                and s.name != span_tree.WAIT):
            w(f"  {s.t0 - root.t0:9.3f}s {'  ' * s.depth}{s.name:<28}"
              f" {s.dur:8.3f}s{_cpu_cols(s)}"
              f"  self {tree.self_s(s):.3f}s\n")

    def walk(chain):
        last = None
        for s in chain:
            gap = "" if last is None else f"  (+{s.t0 - last:.3f}s)"
            note = (f"  -> {s.args['slabs']} slabs"
                    if s.name == "pipeline.form" else "")
            w(f"  {s.t0 - root.t0:9.3f}s {s.name:<20} {s.dur:8.3f}s"
              f"{_cpu_cols(s)}  thread {s.line}{gap}{note}\n")
            kids(s, 1)
            last = s.t1

    def kids(s, depth):
        for kid in tree.members:
            if (kid.parent is s and kid.line == s.line
                    and kid.name != span_tree.WAIT):
                name = "  " * depth + kid.name
                w(f"  {kid.t0 - root.t0:9.3f}s {name:<32} "
                  f"{kid.dur:8.3f}s{_cpu_cols(kid)}\n")
                kids(kid, depth + 1)

    # a trace since slabs are formed by length tags the io thread's
    # stages with their `chunk` of docs; a chunk feeds the slabs of
    # several rungs, so it is followed apart from them
    stages = ("pipeline.io", "pipeline.spec", "pipeline.form")
    by_chunk = defaultdict(list)
    for s in tree.members:
        if s.name in stages and "chunk" in s.args:
            by_chunk[s.args["chunk"]].append(s)
    for c, chain in sorted(by_chunk.items()):
        w(f"chunk {c}: {sum(s.dur for s in chain):.3f}s on the io thread\n")
        walk(chain)
    for k in tree.slabs():
        chain = [s for s in tree.chain(k)
                 if not (by_chunk and s.name in stages)]
        if not chain:  # an id only a chunk bears: more chunks than slabs
            continue
        busy_s = sum(b - a for a, b in span_tree.trace_reduce.union(
            [(s.t0, s.t1) for s in chain]))
        waited = max(s.t1 for s in chain) - chain[0].t0 - busy_s
        w(f"slab {k}: waited {waited:.3f}s between stages"
          f"{_slab_shape(tree, k)}\n")
        walk(chain)
    waits = defaultdict(float)
    for s in tree.members:
        if s.name == span_tree.WAIT:
            waits[(s.args.get("q"), s.args.get("side"))] += s.dur
    if waits:
        w("pipeline.wait, thread-seconds by queue and side: " + ", ".join(
            f"{q}/{side} {v:.3f}" for (q, side), v in sorted(waits.items())
        ) + "\n")
    gc = tree.named(("host.gc",))
    if gc:
        w(f"host.gc: {len(gc)} full collections, "
          f"{sum(s.dur for s in gc):.3f}s, at "
          + ", ".join(f"{s.t0 - root.t0:.2f}s" for s in gc) + "\n")
    if busy:
        table = tree.idle_table(busy)
        idle = sum(r[1] for r in table)
        w(f"device 0 idle {idle:.3f}s of {tree.end - root.t0:.3f}s; "
          "gaps over 50 ms by covering span:\n")
        for at, secs, name, slab in table:
            if secs >= 0.05:
                tag = "" if slab is None else f"{{slab={slab}}}"
                w(f"  {at:9.3f}s {secs:8.3f}s  {name}{tag}\n")


def timeline(events, by="name"):
    """(rows, wall_s, t0_us): rows are (key, count, busy_s) sorted by
    busy desc, over the complete ("X") events."""
    spans = [e for e in events if e.get("ph") == "X"]
    if not spans:
        return [], 0.0, 0.0
    t0 = min(e["ts"] for e in spans)
    t1 = max(e["ts"] + e.get("dur", 0) for e in spans)
    wall = (t1 - t0) / 1e6
    busy = defaultdict(lambda: [0, 0.0])
    for e in spans:
        key = e.get("cat", "hm") if by == "cat" else e["name"]
        cell = busy[key]
        cell[0] += 1
        cell[1] += e.get("dur", 0) / 1e6
    rows = sorted(
        ((k, c, s) for k, (c, s) in busy.items()),
        key=lambda r: -r[2],
    )
    return rows, wall, t0


def thread_busy(events, tid_names):
    busy = defaultdict(float)
    for e in events:
        if e.get("ph") == "X":
            busy[e.get("tid")] += e.get("dur", 0) / 1e6
    return sorted(
        ((tid_names.get(t, f"tid {t}"), s) for t, s in busy.items()),
        key=lambda r: -r[1],
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "trace", help="Chrome trace JSON (HM_TRACE output) or .xplane.pb"
    )
    ap.add_argument(
        "--by", choices=("name", "cat", "slab"), default="name"
    )
    ap.add_argument("--top", type=int, default=24)
    ap.add_argument(
        "--threads", action="store_true",
        help="also print per-thread busy totals",
    )
    args = ap.parse_args()

    events, busy = load_events(args.trace)
    if args.by == "slab":
        if not slab_view(events, busy):
            print("no repo.open_many span in trace", file=sys.stderr)
            sys.exit(1)
        return
    rows, wall, _t0 = timeline(events, by=args.by)
    if not rows:
        print("no complete spans in trace", file=sys.stderr)
        sys.exit(1)
    n_instant = sum(1 for e in events if e.get("ph") == "i")
    print(
        f"trace: {sum(c for _k, c, _s in rows)} spans"
        + (f" + {n_instant} instants" if n_instant else "")
        + f", wall {wall:.3f}s"
    )
    print(f"stage timeline [busy (overlapped)] by {args.by}:")
    busy_total = 0.0
    for key, count, busy_s in rows[: args.top]:
        busy_total += busy_s
        bar = "#" * max(1, int(40 * busy_s / max(wall, 1e-9)))
        print(f"  {key:<26} {busy_s:9.3f}s x{count:<6} |{bar}")
    dropped = rows[args.top:]
    if dropped:
        rest = sum(s for _k, _c, s in dropped)
        busy_total += rest
        print(f"  (+{len(dropped)} more stages, {rest:.3f}s)")
    print(
        f"  wall {wall:.3f}s, busy total {busy_total:.3f}s -> "
        f"{busy_total / max(wall, 1e-9):.2f}x concurrency"
    )
    if args.threads:
        names = {
            e["tid"]: e["args"]["name"]
            for e in events
            if e.get("ph") == "M" and e.get("name") == "thread_name"
        }
        print("per-thread busy:")
        for name, s in thread_busy(events, names):
            print(f"  {name:<26} {s:9.3f}s")


if __name__ == "__main__":
    main()
