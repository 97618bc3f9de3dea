"""DeviceClockMirror — the ClockStore's device-resident query twin.

The reference answers bulk clock queries by scanning sqlite rows per
call (reference src/ClockStore.ts:63-72 getMultiple + Clock.ts folds).
The TPU-first shape keeps the whole [docs, actors] clock matrix
RESIDENT in device HBM and applies writes as small batched scatter-max
updates, so the hot bulk queries — union across all docs, domination
against a cursor, top-k covered docs — are single dispatches that read
nothing from the host beyond the query vector:

- writes buffer host-side (dict of (row, col) -> seq, monotonic max)
  and flush lazily as ONE scatter-max right before the next query —
  interactive writes never pay a device round trip;
- capacity grows by pow2 doubling on either axis (device-side pad);
  jit buckets stay stable per capacity;
- seqs clamp to INT32_INF like the rest of the clock kernels.

`ClockStore.attach_mirror` keeps a mirror consistent with every sqlite
write (update/update_many/set/delete_doc), which the consistency test
pins against the raw rows (tests/test_clock_mirror.py).
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Optional, Tuple

from ..analysis.lockdep import make_rlock

import numpy as np

INT32_INF = 2**31 - 1


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _lazy_jits():
    """Module-level jitted programs, built on first use (importing jax
    at module import would drag device init into cold paths)."""
    global _scatter_max, _scatter_max_union
    import jax.numpy as jnp

    from . import compile_cache

    @compile_cache.jit
    def _scatter_max(m, r, c, v):
        return m.at[r, c].max(v)

    @compile_cache.jit
    def _scatter_max_union(m, r, c, v):
        m2 = m.at[r, c].max(v)
        return m2, jnp.max(m2, axis=0)

    return _scatter_max, _scatter_max_union


_scatter_max = None
_scatter_max_union = None


def _jits():
    if _scatter_max is None:
        _lazy_jits()
    return _scatter_max, _scatter_max_union


class DeviceClockMirror:
    def __init__(
        self, capacity_docs: int = 1024, capacity_actors: int = 64
    ) -> None:
        self._lock = make_rlock("ops.clock_mirror")
        self.doc_index: Dict[str, int] = {}
        self.actor_index: Dict[str, int] = {}
        self._actors: List[str] = []
        self._docs: List[str] = []
        self._cap_d = _pow2(max(1, capacity_docs))
        self._cap_a = _pow2(max(1, capacity_actors))
        # device state is LAZY: writes only buffer host-side, so a repo
        # can attach a mirror unconditionally without paying device init
        # (or any dispatch) until the first bulk query
        self._matrix = None
        self._pending: Dict[Tuple[int, int], int] = {}

    @property
    def _jnp(self):
        # every device touch of the mirror goes through here: eager
        # jnp ops compile too, so the compile cache is placed first
        import jax.numpy as jnp

        from . import compile_cache

        compile_cache.ensure()
        return jnp

    def _mat(self):
        if self._matrix is None:
            self._matrix = self._jnp.zeros(
                (self._cap_d, self._cap_a), self._jnp.int32
            )
        return self._matrix

    # -- host-side indexing --------------------------------------------

    def _doc_row(self, doc_id: str) -> int:
        row = self.doc_index.get(doc_id)
        if row is None:
            row = len(self._docs)
            self.doc_index[doc_id] = row
            self._docs.append(doc_id)
            if row >= self._cap_d:
                self._grow(docs=True)
        return row

    def _actor_col(self, actor_id: str) -> int:
        col = self.actor_index.get(actor_id)
        if col is None:
            col = len(self._actors)
            self.actor_index[actor_id] = col
            self._actors.append(actor_id)
            if col >= self._cap_a:
                self._grow(docs=False)
        return col

    def _grow(self, docs: bool) -> None:
        if docs:
            self._cap_d *= 2
        else:
            self._cap_a *= 2
        if self._matrix is not None:
            pad = (
                (0, self._cap_d - self._matrix.shape[0]),
                (0, self._cap_a - self._matrix.shape[1]),
            )
            self._matrix = self._jnp.pad(self._matrix, pad)

    # -- writes ---------------------------------------------------------

    def seed_bulk(self, doc_ids, actor_ids, matrix) -> None:
        """Bulk initialization from a dense [docs, actors] array: one
        device upload, capacity-padded. Only valid on an empty mirror
        (attach-time seeding, benchmarks)."""
        with self._lock:
            if self.doc_index or self.actor_index or self._pending:
                raise RuntimeError("seed_bulk on a non-empty mirror")
            self._docs = list(doc_ids)
            self._actors = list(actor_ids)
            self.doc_index = {d: i for i, d in enumerate(self._docs)}
            self.actor_index = {a: i for i, a in enumerate(self._actors)}
            self._cap_d = max(self._cap_d, _pow2(max(1, len(self._docs))))
            self._cap_a = max(
                self._cap_a, _pow2(max(1, len(self._actors)))
            )
            arr = np.asarray(matrix)
            assert arr.shape == (len(self._docs), len(self._actors))
            padded = np.zeros((self._cap_d, self._cap_a), np.int32)
            padded[: arr.shape[0], : arr.shape[1]] = np.minimum(
                arr, INT32_INF
            )
            self._matrix = self._jnp.asarray(padded)

    def update(self, doc_id: str, clock: Dict[str, int]) -> None:
        """Monotonic merge (max) — buffered; flushed at next query."""
        with self._lock:
            row = self._doc_row(doc_id)
            for actor, seq in clock.items():
                key = (row, self._actor_col(actor))
                s = min(int(seq), INT32_INF)
                if s > self._pending.get(key, 0):
                    self._pending[key] = s

    def update_many(self, clocks: Dict[str, Dict[str, int]]) -> None:
        for doc_id, clock in clocks.items():
            self.update(doc_id, clock)

    def set(self, doc_id: str, clock: Dict[str, int]) -> None:
        """Hard overwrite of one doc's row (ClockStore.set)."""
        jnp = self._jnp
        with self._lock:
            self._flush_locked()
            row = self._doc_row(doc_id)
            # resolve columns first: _actor_col may grow the matrix
            pairs = [
                (self._actor_col(a), min(int(s), INT32_INF))
                for a, s in clock.items()
            ]
            vec = np.zeros(self._cap_a, np.int32)
            for col, s in pairs:
                vec[col] = s
            self._matrix = self._mat().at[row].set(jnp.asarray(vec))

    def delete_doc(self, doc_id: str) -> None:
        with self._lock:
            row = self.doc_index.get(doc_id)
            if row is None:
                return
            self._flush_locked()
            self._matrix = self._mat().at[row].set(0)
            # row index stays allocated (zeros = neutral for max/union;
            # dominated() masks unallocated/deleted rows by doc list)
            del self.doc_index[doc_id]
            self._docs[row] = None

    # -- flush ----------------------------------------------------------

    def _pending_arrays(self):
        """Pending writes as (rows, cols, vals) padded to a pow2 bucket
        (stable jit shapes); the pad is a scatter-max of 0 at (0, 0) —
        a no-op against the non-negative matrix."""
        items = self._pending
        self._pending = {}
        n = len(items)
        cap = _pow2(max(1, n))
        rows = np.zeros(cap, np.int32)
        cols = np.zeros(cap, np.int32)
        vals = np.zeros(cap, np.int32)
        rows[:n] = np.fromiter((k[0] for k in items), np.int32, count=n)
        cols[:n] = np.fromiter((k[1] for k in items), np.int32, count=n)
        vals[:n] = np.fromiter(items.values(), np.int32, count=n)
        return rows, cols, vals

    def _flush_locked(self) -> None:
        if not self._pending:
            return
        jnp = self._jnp
        rows, cols, vals = self._pending_arrays()
        scatter, _ = _jits()
        self._matrix = scatter(
            self._mat(), jnp.asarray(rows), jnp.asarray(cols),
            jnp.asarray(vals),
        )

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    # -- queries (single dispatches over the resident matrix) ----------

    def union(self) -> Dict[str, int]:
        """Union clock across ALL docs — one device dispatch, even with
        writes pending (the scatter-max flush and the max-reduce fuse
        into a single program)."""
        from . import clock_kernels as K

        with self._lock:
            if self._pending:
                jnp = self._jnp
                rows, cols, vals = self._pending_arrays()
                _, scatter_union = _jits()
                self._matrix, merged = scatter_union(
                    self._mat(), jnp.asarray(rows), jnp.asarray(cols),
                    jnp.asarray(vals),
                )
                merged = np.asarray(merged)
            else:
                merged = np.asarray(K.union_reduce(self._mat()))
            return {
                a: int(merged[c])
                for a, c in self.actor_index.items()
                if merged[c] > 0
            }

    def dominated(self, query: Dict[str, int]) -> List[str]:
        """Doc ids whose clock the query dominates (is >= everywhere)."""
        with self._lock:
            self._flush_locked()
            q = self._query_vec(query)
            ok = np.asarray(
                self._jnp.all(self._mat() <= q[None, :], axis=-1)
            )
            return [
                d for d, r in self.doc_index.items() if ok[r]
            ]

    def top_k_dominated(
        self, query: Dict[str, int], k: int
    ) -> List[str]:
        from . import clock_kernels as K

        with self._lock:
            self._flush_locked()
            q = self._query_vec(query)
            scores, idx = K.top_k_dominated(self._mat(), q, k)
            scores = np.asarray(scores)
            idx = np.asarray(idx)
            out = []
            for s, i in zip(scores, idx):
                if s < 0:
                    break
                d = self._docs[int(i)] if int(i) < len(self._docs) else None
                if d is not None:
                    out.append(d)
            return out

    def _query_vec(self, query: Dict[str, int]):
        jnp = self._jnp
        q = np.zeros(self._cap_a, np.int32)
        for actor, seq in query.items():
            col = self.actor_index.get(actor)
            if col is not None:
                q[col] = min(int(seq), INT32_INF)
        return jnp.asarray(q)

    # -- introspection ---------------------------------------------------

    def rows(self) -> Dict[str, Dict[str, int]]:
        """Full host decode (consistency tests; not a hot path)."""
        with self._lock:
            self._flush_locked()
            m = np.asarray(self._mat())
            return {
                d: {
                    a: int(m[r, c])
                    for a, c in self.actor_index.items()
                    if m[r, c] > 0
                }
                for d, r in self.doc_index.items()
            }
