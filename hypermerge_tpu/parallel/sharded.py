"""The multi-chip dispatch and the program table.

- `SlabRoundRobin`: whole slabs round-robin (or least-loaded,
  HM_RR_LEAST_LOADED) across the visible devices with bounded
  per-device in-flight queues, so chips run independent programs while
  the host packs ahead (the bulk loader, backend/bulk_loader.py).
  Tracks per-chip dispatch busy time.
- the program table (`_PROGRAMS`): every serve, advance and pack
  program (serve/kernels.py) is built ONCE per key — repeated calls
  reuse the jitted executable with zero retracing (`trace_counts`
  exposes per-key trace tallies for the regression tests and the
  benchmark's drivers).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from ..ops import compile_cache
from ..ops.columnar import ColumnarBatch
from .. import telemetry

# mesh telemetry (process registry): program dispatches, retraces
# (mirrors trace_counts, which stays the per-key regression-test
# truth), and host->device transfer bytes — the "is the mesh being
# fed" view tools/top.py renders next to pipeline queue depths.
_M_DISPATCHES = telemetry.counter("mesh.dispatches")
_M_TRACES = telemetry.counter("mesh.traces")
_M_H2D = telemetry.counter("mesh.h2d_bytes")


# ---------------------------------------------------------------------------
# program table — ONE jitted program per key
#
# jit caches per FUNCTION OBJECT, and a closure built inside a call is
# a new function: it would retrace every time. The table keeps each
# program behind a key; the jit object lives as long as the process
# and its own shape-cache does the rest.

_PROGRAMS: Dict[Tuple, Any] = {}
trace_counts: Dict[Tuple, int] = {}


def _program(key: Tuple, build: Callable[[], Any]) -> Any:
    fn = _PROGRAMS.get(key)
    if fn is None:
        # the one seam every mesh, serve and pack program is built
        # through: place the compile cache before its first compile
        compile_cache.ensure()
        fn = build()
        _PROGRAMS[key] = fn
    return fn


def _traced(key: Tuple, fn: Callable) -> Callable:
    """Wrap a to-be-jitted python callable so each TRACE (not each call)
    bumps trace_counts[key] — the retrace regression tests assert the
    count stays at 1 across repeated same-shape calls."""

    def wrapper(*args):
        trace_counts[key] = trace_counts.get(key, 0) + 1
        _M_TRACES.add(1)
        return fn(*args)

    return wrapper


def clear_program_cache() -> None:
    """Test hook: drop every cached mesh program and trace tally."""
    _PROGRAMS.clear()
    trace_counts.clear()


# per-device in-flight slab bound of the round-robin scheduler
RR_DEPTH = 2


class SlabRoundRobin:
    """Stream WHOLE slabs across visible devices with bounded
    per-device in-flight queues — the streaming pipeline's multi-chip
    dispatch (backend/bulk_loader.py BulkLoader._dispatch).

    Each slab stays whole on one chip and successive slabs go to
    successive chips. Chips run independent programs, so while chip k
    computes slab N the host packs slab N+1 for chip k+1. Same kernels
    (materialize_full_device / the lean twin) and the same (A_loc, K)
    buckets as one device, so results are bit-identical to it.

    Placement: strict round-robin by default; HM_RR_LEAST_LOADED=1 (or
    least_loaded=True) picks the device with the SHORTEST in-flight
    queue instead — a chip wedged on a slow slab is skipped while idle
    chips take new work — with the round-robin cursor as the FIFO
    tiebreak so equal loads still cycle.

    Backpressure: at most `depth` unfetched
    slabs per device; dispatching onto a saturated device blocks on its
    OLDEST outstanding summary, which bounds host staging and device
    memory to depth x n_devices slabs.

    Accounting: `t_dispatch_chip[i]` accumulates per-chip dispatch busy
    seconds and `slabs_per_chip[i]` the slab count; `last_device` is the
    index the most recent dispatch landed on (the bulk loader's per-chip
    stats and the fetch stage's chip attribution read these)."""

    def __init__(
        self, devices=None, depth: int = RR_DEPTH, least_loaded: bool = None
    ) -> None:
        self.devices = list(
            devices if devices is not None else jax.devices()
        )
        self.depth = depth
        self.least_loaded = (
            least_loaded
            if least_loaded is not None
            else os.environ.get("HM_RR_LEAST_LOADED", "0") == "1"
        )
        self._next = 0
        self._inflight = {i: [] for i in range(len(self.devices))}
        self.t_dispatch_chip = [0.0] * len(self.devices)
        self.slabs_per_chip = [0] * len(self.devices)
        self.last_device: Optional[int] = None

    def device_index(self, device) -> Optional[int]:
        """Index of a jax device within this scheduler (None when it is
        not one of ours) — the fetch stage attributes per-chip busy time
        by the wire buffer's device."""
        try:
            return self.devices.index(device)
        except ValueError:
            return None

    def _pick_device(self) -> int:
        """Next device index. Round-robin: the cursor, regardless of
        load (the dispatch below blocks if it is saturated). Least
        loaded: the shortest in-flight queue, scanning from the cursor
        so ties break FIFO — a saturated device is SKIPPED while any
        other has room."""
        n = len(self.devices)
        if not self.least_loaded:
            i = self._next
            self._next = (self._next + 1) % n
            return i
        best = None
        best_len = None
        for k in range(n):
            i = (self._next + k) % n
            qlen = len(self._inflight[i])
            if best_len is None or qlen < best_len:
                best, best_len = i, qlen
                if qlen == 0:
                    break
        self._next = (best + 1) % n
        return best

    def dispatch(self, batch: ColumnarBatch, lean: bool = False):
        """(MaterializeOut, summary wire) on the chosen device; blocks
        only when that device already holds `depth` unfetched slabs.
        The kernel entry is run_batch_full with a pinned device — the
        same code path as the single-device twin, so the two cannot
        diverge."""
        from ..ops.crdt_kernels import run_batch_full

        i = self._pick_device()
        q = self._inflight[i]
        while len(q) >= self.depth:
            q.pop(0).block_until_ready()
        with telemetry.timed("mesh.dispatch", "mesh", chip=i) as sp:
            out, summary = run_batch_full(
                batch, lean=lean, device=self.devices[i]
            )
        _M_DISPATCHES.add(1)
        _M_H2D.add(
            sum(a.nbytes for a in batch.cols.values())
            + batch.psrc.nbytes
            + batch.ptgt.nbytes
        )
        self.t_dispatch_chip[i] += sp.dur
        self.slabs_per_chip[i] += 1
        self.last_device = i
        q.append(summary)
        return out, summary

    def drain(self) -> None:
        """Block until every outstanding dispatch has completed."""
        for q in self._inflight.values():
            while q:
                q.pop(0).block_until_ready()

    def release(self) -> None:
        """Drop the backpressure refs without blocking — called when a
        bulk load finishes dispatching. The consumers (pending summary
        entries / the fetch worker) hold their own refs; keeping these
        would pin depth x n_devices device buffers for the lifetime of
        the cached scheduler."""
        for q in self._inflight.values():
            q.clear()

