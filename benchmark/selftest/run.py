#!/usr/bin/env python3
"""Checks of the benchmark's own yardstick; not collected by tier-1.

    python benchmark/selftest/run.py            # seconds, no chip
    python benchmark/selftest/run.py --controls # + every cell, sound and broken

1. the trace reduction on a hand-made trace whose answers are worked
   out below by hand, and on `small.xplane.pb` (recorded on a TPU v5e by
   PR 23: three sorts, a 20 ms sleep, two matmuls under named spans),
   where every number is recomputed by a second, brute-force method;
2. the byte counts of counts/ against hand-worked shapes;
3. the plain reference against a hand-worked history;
4. with --controls: every cell at rehearsal size on the CPU, once sound
   (`correct` true), once with the guarantee broken that the mix's
   `control` names, and once with an answer altered where the program
   produces it (`correct` has to come out false both times). This skips
   nothing of a run but the look for a chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

FAILED = []


def check(cond, what, detail=None) -> None:
    print(("ok   " if cond else "FAIL ") + what
          + ("" if cond or detail is None else f": {detail!r}"))
    if not cond:
        FAILED.append(what)


def close(a, b, rel=1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def test_handmade_trace() -> None:
    from benchmark import trace_reduce

    ms = 1e6  # ns
    planes = [
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_a(1)", 10 * ms, 30 * ms),
                             ("jit_b(22)", 60 * ms, 20 * ms),
                             ("jit_a(1)", 90 * ms, 10 * ms)]),
            # two ops overlap (25..30): the union counts it once
            ("XLA Ops", [("%x = f32[] add(f32[] %p)", 10 * ms, 20 * ms),
                         ("%y = f32[] sort(f32[] %q)", 25 * ms, 15 * ms),
                         ("%x = f32[] add(f32[] %p)", 60 * ms, 20 * ms),
                         ("%z = f32[] copy(f32[] %r)", 90 * ms, 10 * ms)]),
        ]),
        ("/host:CPU", [
            ("python", [("bench.loader.open", 0 * ms, 58 * ms),
                        ("bench.facade.close", 80 * ms, 20 * ms),
                        ("not ours", 0, 100 * ms)]),
        ]),
    ]
    r = trace_reduce.reduce_planes(planes)
    # busy: [10,40] + [60,80] + [90,100] = 60 ms of a 100 ms extent
    check(close(r["busy_s"], 0.060), "hand trace: busy union 60 ms", r["busy_s"])
    check(close(r["window_s"], 0.100), "hand trace: window 100 ms", r["window_s"])
    check(close(100 * (1 - r["busy_s"] / r["window_s"]), 40.0),
          "hand trace: idle share 40%")
    check(close(r["programs"]["jit_a"], 0.040) and close(r["programs"]["jit_b"], 0.020),
          "hand trace: per-program seconds, ids stripped", r["programs"])
    check(r["top_ops"][0][0] == "%x add" and close(r["top_ops"][0][1], 0.040),
          "hand trace: top op is %x add at 40 ms", r["top_ops"])
    # gaps: [0,10] open, [40,60] open (18 of 20 ms), [80,90] close
    gaps = {(n, round(s, 6)) for n, s in r["idle_gaps"]}
    check(gaps == {("bench.loader.open", 0.01), ("bench.loader.open", 0.02),
                   ("bench.facade.close", 0.01)},
          "hand trace: idle gaps named by our spans", r["idle_gaps"])
    r2 = trace_reduce.reduce_planes(planes, window_s=0.2)
    check(close(r2["window_s"], 0.2), "hand trace: a given window is kept")
    try:  # 60 ms busy cannot lie in a 50 ms window: an error, no clamp
        trace_reduce.reduce_planes(planes, window_s=0.05)
        check(False, "hand trace: busy over the window is an error")
    except ValueError:
        check(True, "hand trace: busy over the window is an error")


def test_recorded_trace() -> None:
    from benchmark import trace_reduce

    path = os.path.join(HERE, "small.xplane.pb")
    planes = trace_reduce.load(path)
    r = trace_reduce.reduce_planes(planes)
    dev = dict(next(ls for n, ls in planes if n == "/device:TPU:0"))
    ops = [(s, s + d) for _n, s, d in dev["XLA Ops"] if d > 0]
    # brute force: sweep the sorted edges, count covered time
    edges = sorted({x for ab in ops for x in ab})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for s, e in ops))
    check(r["devices"] == 1, "recorded trace: one device plane")
    check(close(r["busy_s"], busy / 1e9, 1e-6),
          "recorded trace: busy union == brute-force sweep",
          (r["busy_s"], busy / 1e9))
    mods = sum(d for _n, _s, d in dev["XLA Modules"]) / 1e9
    check(close(sum(r["programs"].values()), mods),
          "recorded trace: program seconds sum to the module line")
    check(len(dev["XLA Modules"]) == 5, "recorded trace: 3 sorts + 2 matmuls",
          len(dev["XLA Modules"]))
    check(0 < r["busy_s"] <= mods * 1.001 <= r["window_s"],
          "recorded trace: busy <= programs <= window")
    idle = 100 * (1 - r["busy_s"] / r["window_s"])
    check(99.0 < idle < 100.0, "recorded trace: idle share 99-100%", idle)
    check(r["idle_gaps"][0][0] == "bench.selftest.sleep"
          and 0.019 < r["idle_gaps"][0][1] < 0.03,
          "recorded trace: the longest gap is the 20 ms sleep, by name",
          r["idle_gaps"][0])
    check(r["top_ops"][0][0].endswith(" sort"),
          "recorded trace: the sort is the top op", r["top_ops"][0])


def test_counts() -> None:
    from benchmark.counts import bulk_slab

    # [4096,1024]: 4,194,304 cells x 44 B in; wire 2+10 bits a cell out
    one = bulk_slab.bytes_moved([[4096, 1024]])
    check(one == 4194304 * 44 + 4194304 * 12 / 8 == 190840832.0,
          "counts: one [4096,1024] slab is 190,840,832 B", one)
    check(bulk_slab.wire_bits_per_cell(1024) == 12
          and bulk_slab.wire_bits_per_cell(2048) == 13
          and bulk_slab.wire_bits_per_cell(1025) == 13,
          "counts: wire bits a cell")
    flag = bulk_slab.bytes_moved([[4096, 1024], [4096, 1024], [2048, 1024]])
    check(flag == 2.5 * 190840832, "counts: the flagship open is 2.5 slabs",
          flag)
    peaks = json.load(open(os.path.join(ROOT, "benchmark", "peaks.json")))
    t = flag / peaks["TPU v5 lite"]["hbm_bytes_per_s"]
    check(close(t, 477102080 / 819e9), "counts: 0.58 ms at 819 GB/s", t)


def test_reference() -> None:
    from benchmark.reference import crdt_plain as ref

    a, b = "aaa", "bbb"
    ch = [
        {"actor": a, "seq": 1, "startOp": 1, "deps": {}, "ops": [
            {"a": 2, "o": "0@_root", "k": "t"},
            {"a": 4, "o": f"1@{a}", "r": "0@_head", "i": True, "v": "x"},
            {"a": 4, "o": f"1@{a}", "r": f"2@{a}", "i": True, "v": "y"},
            {"a": 4, "o": "0@_root", "k": "n", "v": 1}]},
        # b, concurrent with a's seq 2: inserts after x, sets n, deletes y
        {"actor": b, "seq": 1, "startOp": 5, "deps": {a: 1}, "ops": [
            {"a": 4, "o": f"1@{a}", "r": f"2@{a}", "i": True, "v": "B"},
            {"a": 4, "o": "0@_root", "k": "n", "v": 2, "p": [f"4@{a}"]},
            {"a": 5, "o": f"1@{a}", "r": f"3@{a}", "p": [f"3@{a}"]}]},
        {"actor": a, "seq": 2, "startOp": 5, "deps": {}, "ops": [
            {"a": 4, "o": f"1@{a}", "r": f"2@{a}", "i": True, "v": "A"},
            {"a": 4, "o": "0@_root", "k": "n", "v": 3, "p": [f"4@{a}"]}]},
    ]
    got = ref.replay(list(reversed(ch)))  # order of arrival must not matter
    # after x: siblings B (5@bbb) > A (5@aaa) > y (3@aaa, deleted)
    check(got["value"] == {"t": {"__text__": "xBA"}, "n": 2},
          "reference: RGA order, observed-remove, winner by (ctr, actor)",
          got["value"])
    check(got["clock"] == {a: 2, b: 1} and got["elems"] == 3
          and got["map_entries"] == 2, "reference: clock and counts", got)


def test_controls() -> None:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        for control in (False, True):
            cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                   "--workload", w["name"], "--seed", "2147483659",
                   "--seconds", "3", "--trace", "0", "--rehearse"]
            out = subprocess.run(
                cmd + (["--control"] if control else []),
                capture_output=True, text=True, timeout=600,
            )
            try:
                line = json.loads(out.stdout.strip().splitlines()[-1])
            except (IndexError, ValueError):
                line = {"correct": None}
            bad = [c["name"] for c in line.get("checks", ())
                   if c["value"] > c["limit"]]
            check(line["correct"] is (not control),
                  f"{w['name']}: {'control is NOT correct' if control else 'sound run is correct'}"
                  + (f" (fails {bad})" if control else ""),
                  out.stderr[-400:] if line["correct"] is None else bad)
            check("metrics" in line and line["metrics"] == {},
                  f"{w['name']}: a rehearsal prints no metric")


def broken_child() -> int:
    """A rehearsal run of the first cell with the timed path broken
    underneath: every summary the program's barrier decodes reports one
    live element too many."""
    from benchmark import harness
    from benchmark import run as bench_run

    apply_env = harness.apply_env

    def apply_env_then_break(cell) -> None:
        apply_env(cell)  # the program is imported only after this
        from hypermerge_tpu.ops.materialize import BulkSummaries

        doc = BulkSummaries.doc

        def off_by_one(self, doc_id):
            out = dict(doc(self, doc_id))
            out["elems"] += 1
            return out

        BulkSummaries.doc = off_by_one

    harness.apply_env = apply_env_then_break
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return bench_run.main([
        "--workload", bench["workloads"][0]["name"], "--seed", "2147483693",
        "--seconds", "3", "--trace", "0", "--rehearse"])


def test_broken_path() -> None:
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--broken-child"],
        capture_output=True, text=True, timeout=600,
    )
    try:
        line = json.loads(out.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        line = {"correct": None}
    bad = [c["name"] for c in line.get("checks", ())
           if c["value"] > c["limit"]]
    check(line["correct"] is False and bad == ["summary_mismatches"],
          "altered summaries: the run is NOT correct (fails "
          "summary_mismatches alone)",
          out.stderr[-400:] if line["correct"] is None else bad)


def main() -> int:
    if "--broken-child" in sys.argv[1:]:
        return broken_child()
    test_handmade_trace()
    test_recorded_trace()
    test_counts()
    test_reference()
    if "--controls" in sys.argv[1:]:
        test_controls()
        test_broken_path()
    print(f"{len(FAILED)} failed" if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
