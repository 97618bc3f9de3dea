#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result (correct, attempted, failed,
metrics, device[, breakdown]); everything else goes to stderr. A run
that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result. `--rehearse` runs the same code at the
tiny sizes of the files' `rehearsal` blocks on the CPU and prints
counts only, never a device metric.

This file imports no JAX: it finds the cell's config and mix by name
and hands over to the mix's driver. The cell of PR 23 runs in this one
process, which then holds the chip; a later driver that starts
hub-daemon children can keep this process off JAX.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    # for the builder of a cell, never used by the driver's check:
    ap.add_argument("--mix", default=None,
                    help="JSON laid over the mix file (a rate sweep)")
    ap.add_argument("--control", action="store_true",
                    help="break the timed path as the mix's `control` "
                    "says: `correct` has to come out false")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "hypermerge_tpu")):
        print("benchmark: the hypermerge_tpu package is not beside "
              "benchmark/; nothing to measure", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    from benchmark import harness

    return harness.run_cell(args, bench, T_START)


if __name__ == "__main__":
    sys.exit(main())
