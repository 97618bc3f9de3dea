"""Batched device query kernels for the read-serving tier.

One read of a resident doc never materializes anything host-side: the
structural queries — winner row of a (map, key) pair, live-entry
counts, element order of a text/list object — run as jitted programs
over the stacked summary lanes of EVERY read in the batch, so a
thousand concurrent reads cost one dispatch per shape bucket instead
of a thousand host summary parses. Each program answers what the one
before it does and one thing more, about the container a read names
as (object row, key): the key's winner is resolved on the device, so
no read waits for a row to come back before it asks about it.

The programs live in the PR-7 cached program table
(parallel/sharded._PROGRAMS): one trace per ("serve", kind, B, N) key
for the life of the process, pinned by the same trace_counts regression
mechanism the mesh programs use. Batch axes bucket to pow2 so a varying
read mix reuses a handful of executables.

One more program writes instead of reading: `advance` applies the
local changes a resident entry noted to the lanes the device holds, in
place (donated), so that a written doc is not packed, materialized and
uploaded again for the one row a local change adds (`_build_advance`
has the rule; serve/resident.py `ResidencyCache.note` decides what it
may be asked to apply).

Lane layout (serve/resident.py uploads one stacked [LANES, N] int32
array per resident doc — a single host->device transfer per install):
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from .. import telemetry

# stacked-lane row indices (ResidentDoc.dev is [LANES, N] int32)
L_LIVE = 0     # elem_live: INS rows whose element has a visible value
L_RANK = 1     # RGA order key (higher = earlier)
L_OBJ = 2      # container MAKE row (-1 = root map)
L_INSERT = 3   # 1 on element-creating ops
L_KEY = 4      # key-table index (-1 = none)
L_MAPWIN = 5   # winning visible op of its (obj, key)
N_LANES = 6

_INT32_MAX = 2**31 - 1

# qobj value that matches no container: real obj rows are >= -1 (root)
NO_OBJ = -7


# a dispatch's batch axis: a power of four up to MAX_BATCH, so that a
# kind and a row bucket have five programs whatever the flushes hold (a
# flush of more reads goes in dispatches of MAX_BATCH: a program takes
# each resident array as an argument of its own, and one of thousands
# of arguments compiles for half a minute)
BATCH_BUCKETS = (1, 4, 16, 64, 256)
MAX_BATCH = BATCH_BUCKETS[-1]


# an advance: the local changes a resident entry noted, applied to its
# lanes in place. One descriptor an op, DELTA_WIDTH int32s: (kind, the
# op's new row, its object's row (-1: the root map), the element row
# an insert follows (-1: the head) or a DEL removes, the key index of a
# SET); one program a row bucket applies ADVANCE_OPS of them a call
# (unrolled; a short run is padded with D_NOOP, a long one takes calls)
D_NOOP, D_INSERT, D_SET, D_DEL = 0, 1, 2, 3
DELTA_WIDTH = 5
ADVANCE_OPS = 8


def batch_bucket(n: int) -> int:
    for b in BATCH_BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"serve batch of {n} over {MAX_BATCH}")


def _jnp():
    import jax.numpy as jnp

    return jnp


def _program(kind: str, B: int, N: int, build, donate=()):
    """A jitted serve program from the shared mesh program table —
    ("serve", kind, B, N) keys sit next to the mesh keys, and
    sharded.trace_counts pins the one-trace contract for both."""
    from ..parallel import sharded

    key = ("serve", kind, B, N)

    def named():
        # the XLA program's name: `jit_serve_seq_order_b32_n1024` in a
        # device trace carries the kind and the shape it ran at
        fn = sharded._traced(key, build())
        rows = N if isinstance(N, int) else "x".join(map(str, N))
        fn.__name__ = f"serve_{kind}_b{B}_n{rows}"
        return _jit(fn, donate)

    return sharded._program(key, named)


def _jit(fn, donate=()):
    import jax

    return jax.jit(fn, donate_argnums=donate)


def stack_entries(entries: Sequence) -> tuple:
    """The batch's resident lanes as a TUPLE of [LANES, N] device
    arrays, padded to its `batch_bucket`. The stack into [B, LANES, N]
    happens INSIDE the jitted program (a pytree argument), so it fuses
    into the one dispatch instead of paying a per-buffer concat on the
    way in.
    Padding repeats the first entry's array — zero new device
    allocations; pad lanes are masked out by the NO_OBJ query pad."""
    B = batch_bucket(len(entries))
    devs = [e.dev for e in entries]
    if len(devs) < B:
        devs.extend([devs[0]] * (B - len(devs)))
    return tuple(devs)


def _pad_q(vals: List[int], B: int, fill: int) -> np.ndarray:
    out = np.full(B, fill, np.int32)
    out[: len(vals)] = np.asarray(vals, np.int32)
    return out


def _container(stacked, qobj, qkey):
    """The container each read asks about, and (row, found) of the key
    it named: `qkey` < 0 (no key: a keyless read, a pad row) leaves
    the container `qobj` itself; otherwise it is the winner row of
    (`qobj`, `qkey`), and NO_OBJ where nothing won (the argmax of an
    empty mask is 0, a real row). A winner that is a scalar owns no
    rows: a count of 0 either way, and the host decides from `found`
    and the row's type."""
    jnp = _jnp()
    mask = (
        (stacked[:, L_MAPWIN] != 0)
        & (stacked[:, L_KEY] == qkey[:, None])
        & (stacked[:, L_OBJ] == qobj[:, None])
    )
    row = jnp.argmax(mask, axis=1).astype(jnp.int32)
    found = mask.any(axis=1)
    return jnp.where(qkey < 0, qobj, jnp.where(found, row, NO_OBJ)), row, found


def _count(stacked, obj):
    """(the live element rows of `obj`, how many, its live map keys)."""
    at_obj = stacked[:, L_OBJ] == obj[:, None]
    elems = (stacked[:, L_LIVE] != 0) & at_obj & (stacked[:, L_INSERT] == 1)
    n_map = ((stacked[:, L_MAPWIN] != 0) & at_obj).sum(axis=1)
    return elems, elems.sum(axis=1).astype("int32"), n_map.astype("int32")


def _build_map_lookup():
    def fn(arrs, qobj, qkey):
        return _container(_jnp().stack(arrs), qobj, qkey)[1:]

    return fn


def _build_counts():
    def fn(arrs, qobj, qkey):
        stacked = _jnp().stack(arrs)
        obj, row, found = _container(stacked, qobj, qkey)
        return (row, found, *_count(stacked, obj)[1:])

    return fn


def _build_seq_order():
    def fn(arrs, qobj, qkey):
        jnp = _jnp()
        stacked = jnp.stack(arrs)
        obj, row, found = _container(stacked, qobj, qkey)
        elems, n_elems, n_map = _count(stacked, obj)
        # descending rank, ties in row order — the decode_patch element
        # order (jnp.argsort is stable)
        key = jnp.where(elems, -stacked[:, L_RANK], _INT32_MAX)
        order = jnp.argsort(key, axis=1).astype(jnp.int32)
        return row, found, n_elems, n_map, order

    return fn


def _build_install_merge():
    def fn(lanes, live, rank, mapwin, take):
        """An install group's uploaded lanes with the kernel lanes of
        the docs `take` marks written over them (the others hold the
        summary memo's): the slab program's outputs never leave the
        device."""
        jnp = _jnp()
        n = live.shape[1]
        for lane, src in ((L_LIVE, live), (L_RANK, rank), (L_MAPWIN, mapwin)):
            merged = jnp.where(
                take[:, None], src.astype(jnp.int32), lanes[:, lane, :n]
            )
            lanes = lanes.at[:, lane, :n].set(merged)
        return lanes

    return fn


def install_merge(lanes, live, rank, mapwin, take: np.ndarray):
    """[D, LANES, N] device lanes <- the slab program's [D, n] outputs
    for the docs `take` ([D] bool) marks. One dispatch a page."""
    D, _l, N = lanes.shape
    fn = _program(
        "install_merge", D, (N, live.shape[1]), _build_install_merge
    )
    return fn(lanes, live, rank, mapwin, _jnp().asarray(take))


def _build_install_split():
    def fn(lanes):
        return tuple(lanes[d] for d in range(lanes.shape[0]))

    return fn


def install_split(lanes, n_docs: int) -> list:
    """The first `n_docs` docs of an install page's [D, LANES, N] array
    as [LANES, N] arrays of their own (an entry owns its device bytes,
    so the LRU frees them doc by doc). One dispatch a page."""
    D, _l, N = lanes.shape
    fn = _program("install_split", D, N, _build_install_split)
    return list(fn(lanes))[:n_docs]


def _build_advance():
    def fn(lanes, desc):
        """[LANES, N] lanes with the ops `desc` ([K, DELTA_WIDTH])
        describes applied in order: what the slab program would give
        for the doc with those ops appended, for an op that is the
        newest of its doc and whose preds are every visible value of
        its cell (a local change: backend/live.py `_apply_local_locked`).
        An inserted element is its parent's greatest child, so it
        follows its reference at once: it takes the reference's rank
        and the object's elements from there up move one up (ranks are
        distinct within an object, higher = earlier; at the head it
        takes the greatest + 1). A SET wins its (object, key) alone. A
        DEL leaves its element not live."""
        import jax

        jnp = _jnp()
        K, N = desc.shape[0], lanes.shape[1]
        at_row = jnp.arange(N, dtype=jnp.int32)

        def step(i, lanes):
            kind, row, obj, ref, key = (desc[i, j] for j in range(5))
            live, rank, lobj, ins, lkey, mapwin = (
                lanes[j] for j in (
                    L_LIVE, L_RANK, L_OBJ, L_INSERT, L_KEY, L_MAPWIN)
            )
            is_ins, is_set, is_del = (
                kind == D_INSERT, kind == D_SET, kind == D_DEL
            )
            new = (at_row == row) & (kind != D_NOOP)
            elems = (lobj == obj) & (ins == 1)
            slot = jnp.where(
                ref >= 0, rank[jnp.maximum(ref, 0)],
                jnp.max(jnp.where(elems, rank, 0)) + 1,
            )
            rank = jnp.where(is_ins & elems & (rank >= slot), rank + 1, rank)
            rank = jnp.where(new, jnp.where(is_ins, slot, 0), rank)
            live = jnp.where(is_del & (at_row == ref), 0, live)
            live = jnp.where(new, is_ins.astype(jnp.int32), live)
            mapwin = jnp.where(
                is_set & (lobj == obj) & (lkey == key), 0, mapwin
            )
            mapwin = jnp.where(new, is_set.astype(jnp.int32), mapwin)
            out = [None] * N_LANES
            out[L_LIVE], out[L_RANK], out[L_MAPWIN] = live, rank, mapwin
            out[L_OBJ] = jnp.where(new, obj, lobj)
            out[L_INSERT] = jnp.where(new, is_ins.astype(jnp.int32), ins)
            out[L_KEY] = jnp.where(new, jnp.where(is_set, key, -1), lkey)
            return jnp.stack(out)

        return jax.lax.fori_loop(0, K, step, lanes, unroll=True)

    return fn


def advance(dev, desc: np.ndarray):
    """`dev` ([LANES, N] device lanes, DONATED: the caller drops its
    reference) advanced by the ops `desc` ([ops, DELTA_WIDTH] int32)
    describes: one dispatch an ADVANCE_OPS of them and no fetch (the
    query programs read the result on the device). The program of a
    row bucket compiles at the bucket's first advance."""
    fn = _program(
        "advance", ADVANCE_OPS, dev.shape[1], _build_advance, donate=(0,)
    )
    for i in range(0, len(desc), ADVANCE_OPS):
        padded = np.zeros((ADVANCE_OPS, DELTA_WIDTH), np.int32)
        part = desc[i:i + ADVANCE_OPS]
        padded[: len(part)] = part
        dev = fn(dev, padded)
    return dev


# a query program's outputs, narrowest answer first: a kind returns
# its own and those of the kinds before it (map_lookup the first two,
# counts four, seq_order all), each about the container (`qobj`,
# `qkey`) names
ROW, FOUND, N_ELEMS, N_MAP, ORDER = range(5)
KINDS = ("map_lookup", "counts", "seq_order")


def _query(kind: str, build, entries: Sequence, qobjs, qkeys, skip) -> tuple:
    """One query dispatch over the group's resident lanes: the stack of
    the entries, the program call, the device->host fetch of its
    outputs as numpy arrays (None for those `skip` names: no read of
    the group uses them). The `serve.dispatch{kind,B,N}` span is the
    host's whole cost of the dispatch, its three children follow one
    another: `serve.dispatch.stack` (the program's look-up and its
    arguments: the entries' arrays, the padded query as device arrays),
    `serve.dispatch.call` (the program call, until it returns) and
    `serve.dispatch.fetch` (the wait for the program and the transfers
    of what it returned, started together)."""
    jnp = _jnp()
    B, N = batch_bucket(len(entries)), entries[0].dev.shape[1]
    with telemetry.span("serve.dispatch", "serve", kind=kind, B=B, N=N):
        with telemetry.span("serve.dispatch.stack", "serve"):
            fn = _program(kind, B, N, build)
            args = (
                stack_entries(entries),
                jnp.asarray(_pad_q(qobjs, B, NO_OBJ)),
                jnp.asarray(_pad_q(qkeys, B, -1)),
            )
        with telemetry.span("serve.dispatch.call", "serve"):
            out = fn(*args)
        with telemetry.span("serve.dispatch.fetch", "serve"):
            # every wanted output's copy is under way before the first
            # is waited for: one blocking wait a dispatch, where an
            # `np.asarray` pass alone is a round trip an output
            want = [None if i in skip else o for i, o in enumerate(out)]
            for o in want:
                if o is not None:
                    o.copy_to_host_async()
            return tuple(None if o is None else np.asarray(o) for o in want)


def map_lookup(
    entries: Sequence, qobjs: List[int], qkeys: List[int], skip=()
) -> tuple:
    """(ROW, FOUND): the winner value row per (doc, container, key),
    [B] rows + [B] found mask. One dispatch for the whole group."""
    return _query(
        "map_lookup", _build_map_lookup, entries, qobjs, qkeys, skip
    )


def counts(
    entries: Sequence, qobjs: List[int], qkeys: List[int], skip=()
) -> tuple:
    """`map_lookup`'s pair, then (N_ELEMS, N_MAP): [B] live element
    counts and [B] map entry counts of the container: `qobj` itself
    where `qkey` is -1, else the winner of (`qobj`, `qkey`)."""
    return _query("counts", _build_counts, entries, qobjs, qkeys, skip)


def seq_order(
    entries: Sequence, qobjs: List[int], qkeys: List[int], skip=()
) -> tuple:
    """`counts`' four, then ORDER: the container's element order (live
    INS rows, descending rank), [B, N] rows of which the first N_ELEMS
    count."""
    return _query(
        "seq_order", _build_seq_order, entries, qobjs, qkeys, skip
    )
