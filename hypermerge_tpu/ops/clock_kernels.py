"""Batched vector-clock algebra as XLA programs.

TPU-first re-expression of reference src/Clock.ts + the ClockStore bulk
queries (reference src/ClockStore.ts:63-72 getMultiple): clocks live as dense
`[docs, actors]` int32 matrices; cmp/gte/union/intersection become elementwise
comparisons + small reductions that XLA fuses into a single kernel; the 100k-
doc clock-union/cursor query (BASELINE.json config 5) is one device dispatch
sharded over the `dp` mesh axis (see parallel/sharded.py).

All kernels are shape-polymorphic in the leading batch dims and jit-cached.
Seqs are int32; the cursor sentinel "infinity" (reference CursorStore
INFINITY_SEQ) maps to INT32_INF on device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import compile_cache

INT32_INF = np.int32(2**31 - 1)  # host scalar: importing touches no device

# cmp result codes — stable across host/device (crdt/clock.Ordering)
EQ, GT, LT, CONCUR = 0, 1, 2, 3


@compile_cache.jit
def gte(a: jax.Array, b: jax.Array) -> jax.Array:
    """a, b: [..., actors] -> [...] bool. a dominates b elementwise."""
    return jnp.all(a >= b, axis=-1)


@compile_cache.jit
def cmp(a: jax.Array, b: jax.Array) -> jax.Array:
    """[..., actors] x [..., actors] -> [...] int32 code (EQ/GT/LT/CONCUR)."""
    a_gte = jnp.all(a >= b, axis=-1)
    b_gte = jnp.all(b >= a, axis=-1)
    return jnp.where(
        a_gte & b_gte,
        EQ,
        jnp.where(a_gte, GT, jnp.where(b_gte, LT, CONCUR)),
    ).astype(jnp.int32)


@compile_cache.jit
def union(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.maximum(a, b)


@compile_cache.jit
def intersection(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.minimum(a, b)


@compile_cache.jit
def union_reduce(clocks: jax.Array) -> jax.Array:
    """[n, actors] -> [actors]: union of many clocks in one reduction —
    the ClockStore.getMultiple + Clock.union fold as a single max-reduce."""
    return jnp.max(clocks, axis=0)


@compile_cache.jit
def satisfied(clock: jax.Array, minimum: jax.Array) -> jax.Array:
    """minimumClock render gate (reference src/DocBackend.ts:90-113):
    clock [..., actors] >= minimum [..., actors] -> [...] bool."""
    return jnp.all(clock >= minimum, axis=-1)


@compile_cache.jit
def cursor_window(doc_seqs: jax.Array, cursor_seqs: jax.Array) -> jax.Array:
    """Change-window computation of RepoBackend.syncChanges (reference
    src/RepoBackend.ts:513-522): per (doc, actor), how many new changes the
    cursor admits beyond what the doc already holds.

    doc_seqs, cursor_seqs: [..., actors] -> [..., actors] int32 counts.
    """
    return jnp.maximum(jnp.minimum(cursor_seqs, INT32_INF) - doc_seqs, 0)


@compile_cache.jit(static_argnames=("k",))
def top_k_dominated(clocks: jax.Array, query: jax.Array, k: int):
    """Bulk query: indices of up to k docs whose clock is dominated by
    `query` — the device form of 'which docs are fully covered by this
    cursor'. clocks: [docs, actors]; query: [actors]."""
    ok = jnp.all(clocks <= query[None, :], axis=-1)
    # per-actor contributions capped so the int32 sum cannot wrap even with
    # INT32_INF sentinel entries (supports up to 2^10 actors safely)
    capped = jnp.minimum(clocks, 1 << 20)
    score = jnp.where(ok, jnp.sum(capped, axis=-1), -1)
    return jax.lax.top_k(score, k)


def pack_clocks(rows) -> jax.Array:
    """Host rows (crdt.clock.pack output) -> device array with int32 clamp."""
    arr = np.asarray(rows, dtype=np.int64)
    arr = np.minimum(arr, int(INT32_INF))
    return jnp.asarray(arr.astype(np.int32))
