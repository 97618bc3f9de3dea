"""Batched CRDT materialization kernels — the hot loop on device.

Computes, for a whole batch of documents at once, everything Automerge's
`Backend.applyChanges` full-replay produces (the reference's cold-start hot
loop, SURVEY.md §3.3), as one fused XLA program over the columnar encoding
(ops/columnar.py):

1. supersession: pred edges scatter a `dead` mask (observed-remove)
2. counter totals: INC deltas segment-sum onto live counter ops
3. LWW map winners: lexsort by (group, lamport) + run boundaries
4. RGA element order: one forest over all list/text objects — sibling sort
   (parent asc, OpId desc), preorder-successor via pointer-doubling climb,
   Wyllie list-ranking for positions. All data-dependent chasing is
   log2(N) rounds of gathers — no scalar loops. Where a round reads its
   table from is chosen by the slab's shape (`rga_rounds_in_vmem`): on a
   TPU, up to `RGA_VMEM_MAX_ROWS` rows, one Pallas kernel holds a block
   of docs' tables in VMEM for all 2 x (log2 N + 1) rounds and gathers
   with the vector unit's lane gather; longer docs and every other
   backend run each round as one full-width XLA gather from HBM, which
   a TPU executes element by element (~10 ns each). Same rounds, same
   `rank`, either way.
5. element liveness + winner value op per element (scatter-max)
6. per-doc vector clock (scatter-max of seq per actor)

Everything is `vmap`ed over the leading doc axis and jit-cached per
(N, P, A, K) bucket. The doc axis is the `dp` sharding axis (parallel/).

Each numbered section, and the summary wire, sits in a `jax.named_scope`
(`PHASES`): metadata only — the scope reaches each HLO instruction's
`op_name`, not the program or its cache key — so a device trace's
seconds can be split by phase (`phase_of_ops`).
"""

from __future__ import annotations

import functools
import math
import re
from typing import Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from ..crdt.change import Action
from . import compile_cache
from .columnar import (
    PAD,
    ColumnarBatch,
    doc_actor_map_from_pairs,
    round_up_pow2,
)

_SET = int(Action.SET)
_DEL = int(Action.DEL)
_INC = int(Action.INC)
_MAKE_LIST = int(Action.MAKE_LIST)
_MAKE_TEXT = int(Action.MAKE_TEXT)

# the named scopes of the slab program, in program order
PHASES = (
    "supersession", "counters", "lww", "elem_values", "rga_order",
    "clock", "wire",
)


class MaterializeOut(NamedTuple):
    """Per-row outputs, shape [D, N] unless noted."""

    dead: jax.Array  # bool: superseded by some pred edge
    visible: jax.Array  # bool: value op (SET/MAKE) still visible
    map_winner: jax.Array  # bool: the winning visible op of its (obj, key)
    elem_winner: jax.Array  # bool: winning visible value op of its element
    elem_live: jax.Array  # bool (INS rows): element has a visible value
    rank: jax.Array  # int32: RGA order key (higher = earlier in list)
    inc_total: jax.Array  # int32: accumulated INC deltas per value op
    clock: jax.Array  # [D, A] int32 vector clock


def _ceil_log2(n: int) -> int:
    return max(1, math.ceil(math.log2(max(n, 2))))


def _doc_kernel(
    action, slot, ctr, seq, obj, key, ref, insert, value, psrc, ptgt,
    doc_actors, *, A: int, K: int,
):
    """One document. `slot` holds per-doc LOCAL actor slots (precomputed
    on host — ensure_slot); ascending `doc_actors` [A] maps slots back to
    batch-global actor ids. A = A_loc, the per-doc actor bucket — a small
    constant independent of how many docs (and therefore distinct actors)
    share the batch, so the jit cache key and the [A] clock output don't
    scale with slab size. Slot order == actor-string sort order, the OpId
    tie-break order within this doc.

    Returns the doc's lanes with `rank` left open, and the forest tables
    `(jump, nsib, first_child, in_forest)` of section 5: the sibling sort
    runs here, per doc under `vmap`; the climb's and the ranking's
    rounds over those tables (`_rga_chase`) run on the whole slab
    (`_rga_rank`), so that a TPU can keep a block of docs' tables in VMEM
    for all the rounds instead of reading and writing [D, N] in HBM once
    a round."""
    N = action.shape[0]
    idx = jnp.arange(N, dtype=jnp.int32)
    valid = action != PAD
    is_make = (action <= 3) & valid
    is_set = (action == _SET) & valid
    is_ins = (insert == 1) & valid

    # -- 1. supersession ------------------------------------------------
    with jax.named_scope("supersession"):
        tgt = jnp.where(ptgt >= 0, ptgt, N)
        dead = jnp.zeros(N + 1, dtype=bool).at[tgt].set(True)[:N]
        visible = (is_make | is_set) & ~dead

    # -- 2. counter increments -----------------------------------------
    with jax.named_scope("counters"):
        is_inc = (action == _INC) & valid
        inc_tgt = jnp.clip(ref, 0, N - 1)
        inc_ok = is_inc & (ref >= 0) & ~dead[inc_tgt]
        inc_total = (
            jnp.zeros(N + 1, dtype=jnp.int32)
            .at[jnp.where(inc_ok, inc_tgt, N)]
            .add(jnp.where(inc_ok, value, 0))[:N]
        )

    # -- 3. LWW map winners --------------------------------------------
    with jax.named_scope("lww"):
        # group id over (obj, key); 0 = not a map-located value op
        in_map = visible & (key >= 0)
        gid = jnp.where(in_map, (obj + 1) * (K + 1) + (key + 1), 0)
        order = jnp.lexsort((slot, ctr, gid))
        g_sorted = gid[order]
        run_end = jnp.concatenate(
            [g_sorted[1:] != g_sorted[:-1], jnp.ones((1,), dtype=bool)]
        )
        winner_sorted = run_end & (g_sorted > 0)
        map_winner = jnp.zeros(N, dtype=bool).at[order].set(winner_sorted)

    # -- 4. element values: winner per element -------------------------
    with jax.named_scope("elem_values"):
        # OpId composite; +1 so 0 means "no visible value"
        comp = ctr * jnp.int32(A) + slot + 1
        is_elem_update = visible & ~is_ins & (key < 0) & (ref >= 0)
        own_value = visible & is_ins
        contrib = is_elem_update | own_value
        elem_of = jnp.where(is_elem_update, ref, jnp.where(own_value, idx, N))
        best = (
            jnp.zeros(N + 1, dtype=jnp.int32)
            .at[elem_of]
            .max(jnp.where(contrib, comp, 0))[:N]
        )
        elem_live = is_ins & (best > 0)
        elem_winner = contrib & (
            comp == best[jnp.clip(elem_of, 0, N - 1)]
        )

    # -- 5. RGA forest order -------------------------------------------
    with jax.named_scope("rga_order"):
        is_seq_container = (
            (action == _MAKE_LIST) | (action == _MAKE_TEXT)
        ) & valid
        in_forest = is_ins | is_seq_container
        # parent: INS -> predecessor elem (HEAD -> the container row);
        # non-inserted containers are tree roots (-1)
        parent = jnp.where(
            is_ins, jnp.where(ref == -2, obj, ref), jnp.int32(-1)
        )
        # sibling sort: group by parent (asc), OpId descending within group
        pa = jnp.where(in_forest, parent + 1, N + 1)
        inv = jnp.int32(2**30) - comp
        order2 = jnp.lexsort((inv, pa))
        pa_s = pa[order2]
        run_start = jnp.concatenate(
            [jnp.ones((1,), dtype=bool), pa_s[1:] != pa_s[:-1]]
        )
        fc_table = (
            jnp.full(N + 2, -1, dtype=jnp.int32)
            .at[jnp.where(run_start, pa_s, N + 1)]
            .set(jnp.where(run_start, order2, -1).astype(jnp.int32))
        )
        first_child = fc_table[idx + 1]  # children of node i have pa == i+1
        nxt_in_sort = jnp.concatenate(
            [order2[1:], jnp.full((1,), -1, jnp.int32)]
        )
        same_parent = jnp.concatenate(
            [pa_s[1:] == pa_s[:-1], jnp.zeros((1,), dtype=bool)]
        )
        nsib = (
            jnp.full(N, -1, dtype=jnp.int32)
            .at[order2]
            .set(jnp.where(same_parent, nxt_in_sort, -1).astype(jnp.int32))
        )

        # the climb's table: a node with a next sibling stays, the last
        # sibling steps to its parent, a root (and every row outside
        # the forest) to the terminal N; the rounds over it and the
        # ranking are `_rga_chase`, hoisted to the slab (`_rga_rank`)
        has_sib = nsib != -1
        jump = jnp.where(
            has_sib, idx, jnp.where(parent >= 0, parent, N)
        ).astype(jnp.int32)
        jump = jnp.where(in_forest, jump, N)

    # -- 6. clock (local slots; [A_loc], decoded via doc_actors) -------
    with jax.named_scope("clock"):
        clock = (
            jnp.zeros(A, dtype=jnp.int32)
            .at[jnp.where(valid, slot, 0)]
            .max(jnp.where(valid, seq, 0))
        )

    out = MaterializeOut(
        dead=dead,
        visible=visible,
        map_winner=map_winner,
        elem_winner=elem_winner,
        elem_live=elem_live,
        rank=None,  # `_rga_rank` over the whole slab's forest tables
        inc_total=inc_total,
        clock=clock,
    )
    return out, (jump, nsib, first_child, in_forest)


# -- section 5's rounds: where the gather reads its table from ----------
# The climb and the ranking are 2 x (ceil(log2 N) + 1) rounds of
# `table[idx]` along a doc's rows. `_rga_chase` writes them once, over
# the four operations of an arm (`_ChaseArm`); `_rga_rank` picks the
# arm from the slab's static shape and the backend.

# Longest doc (rows) whose rounds run out of VMEM on a TPU; above it,
# and on every other backend, XLA's gather. Set from the chip's
# per-rung readings (PERF.md section 6, PR 31).
RGA_VMEM_MAX_ROWS = 65536
_LANES = 128  # a vreg's lanes: what one in-VMEM lane gather spans
_VMEM_BLOCK_CELLS = 131072  # cells of a doc block's table, 512 KB
_TAKE_UNROLL = 128  # source chunks a step of the lane gather's loop


class _ChaseArm(NamedTuple):
    """How the rounds hold and read a table whose terminal is index N.
    table(x, fill, dtype): rows x [..., N] as a table whose terminal
    slot holds `fill`; take(table, idx, fill): table[idx] along the last
    axis; rows(table): the N real rows, int32; rounds(n, step, state):
    `step` applied n times."""

    table: Callable
    take: Callable
    rows: Callable
    rounds: Callable


def _rga_chase(jump, nsib, first_child, in_forest, *, N: int, arm):
    """RGA rank of every row from the forest tables ([..., N]): the
    climb-to-sibling fixpoint by pointer doubling, the preorder
    successor, then Wyllie list-ranking (rank = #nodes from here to the
    end of the chain)."""
    i32 = jnp.int32
    rounds = _ceil_log2(N) + 1
    narrow = N < 2**15
    # int16 payload when it fits: XLA's gathers move half the bytes
    j = arm.rounds(
        rounds, lambda j: arm.take(j, j, N),
        arm.table(jump, N, jnp.int16 if narrow else i32),
    )
    fix = arm.rows(j)
    succ = jnp.where(
        first_child != -1, first_child,
        arm.take(arm.table(nsib, -1, i32), fix, -1),
    )
    succ = jnp.where(in_forest, succ, -1)
    nxt = jnp.where(succ == -1, N, succ).astype(i32)
    rank = jnp.where(in_forest, 1, 0).astype(i32)
    if narrow:
        # pack (rank, nxt) into one int32 lane: rank <= chain length <= N
        # < 2^15 and nxt <= N, so `nxt | rank<<16` fits: one gather per
        # round instead of two (the gathers, not the VPU work, bound
        # these loops where XLA's element-by-element gather runs them)
        def packed(p):
            q = arm.take(p, p & 0xFFFF, N)
            return (q & 0xFFFF) | ((p >> 16) + (q >> 16)) << 16

        p = arm.table(nxt, N, i32) | (arm.table(rank, 0, i32) << 16)
        return arm.rows(arm.rounds(rounds, packed, p) >> 16)

    def wide(state):
        r, nx = state
        return r + arm.take(r, nx, 0), arm.take(nx, nx, N)

    r, _nx = arm.rounds(
        rounds, wide, (arm.table(rank, 0, i32), arm.table(nxt, N, i32))
    )
    return arm.rows(r)


def _ext(x, fill, dtype):
    slot = jnp.full(x.shape[:-1] + (1,), fill, x.dtype)
    return jnp.concatenate([x, slot], axis=-1).astype(dtype)


def _unrolled(n, step, state):
    for _ in range(n):
        state = step(state)
    return state


# one doc under vmap: the table is N + 1 long, the terminal its last
# slot, and a round is one full-width gather from HBM in a program that
# spells every round out
_XLA_ARM = _ChaseArm(
    table=_ext,
    take=lambda table, idx, fill: table[idx.astype(jnp.int32)],
    rows=lambda table: table[..., :-1].astype(jnp.int32),
    rounds=_unrolled,
)


def _tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


def rga_rounds_in_vmem(n_rows: int) -> bool:
    """Whether a slab of `n_rows`-row docs runs its `rga_order` rounds
    out of VMEM (`_rga_rank_vmem`): a TPU backend, rows that tile into
    whole vregs, and no more of them than the lane gather's N^2 / 128
    work a doc a round still beats XLA's gather at."""
    return (
        _tpu_backend()
        and n_rows % _LANES == 0
        and n_rows <= RGA_VMEM_MAX_ROWS
    )


def _rga_rank(jump, nsib, first_child, in_forest):
    """[D, N] forest tables -> [D, N] rank, by the arm the slab's shape
    selects."""
    N = jump.shape[1]
    if rga_rounds_in_vmem(N):
        return _rga_rank_vmem(jump, nsib, first_child, in_forest)
    return jax.vmap(functools.partial(_rga_chase, N=N, arm=_XLA_ARM))(
        jump, nsib, first_child, in_forest
    )


# what Mosaic lowers to `tpu.dynamic_gather` along the lanes
_LANE_GATHER = jax.lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,),
)


def _vmem_take(tab_ref, idx_ref, out_ref, fill: int):
    """out[d, i] = tab[d, idx[d, i]] over [db, N] VMEM refs; an idx of
    N (the terminal, which has no slot here) reads `fill`. The vector
    unit gathers within one vreg of 128 lanes, so a gather over N lanes
    is composed: for each source chunk c, `dynamic_gather(tab[c],
    idx & 127)` kept where `idx >> 7 == c`. The source chunks are
    unrolled, `_TAKE_UNROLL` to a loop step: a gather + compare + select
    costs about 2.4 cycles a vreg, a loop step several times that
    (PERF.md section 6, PR 31). The body is traced once and of bare
    `lax` operations: a process traces every slab program it runs, on
    the thread that dispatches, and this is its longest trace."""
    db, N = tab_ref.shape
    chunks = N // _LANES
    unroll = min(_TAKE_UNROLL, chunks)

    def out_chunk(o, carry):
        at = pl.ds(pl.multiple_of(o * _LANES, _LANES), _LANES)
        idx = idx_ref[:, at]
        hi = idx >> 7
        lo = (idx & (_LANES - 1))[..., None]

        def src_chunk(c, acc):
            src = tab_ref[:, pl.ds(pl.multiple_of(c * _LANES, _LANES), _LANES)]
            got = jax.lax.gather(
                src, lo, _LANE_GATHER, slice_sizes=(1, 1),
                mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
            )
            return jax.lax.select(hi == c, got, acc)

        def src_group(g, acc):
            return jax.lax.fori_loop(
                0, unroll, lambda u, acc: src_chunk(g * unroll + u, acc),
                acc, unroll=True,
            )

        out_ref[:, at] = jax.lax.fori_loop(
            0, chunks // unroll, src_group,
            jnp.full((db, _LANES), fill, jnp.int32),
        )
        return carry

    jax.lax.fori_loop(0, chunks, out_chunk, 0)


def _rga_vmem_kernel(
    jump_ref, nsib_ref, fc_ref, forest_ref, rank_ref,
    tab_ref, idx_ref, out_ref,
):
    """One block of docs: its tables come into VMEM once, every round
    of `_rga_chase` gathers there, its rank leaves once."""
    N = jump_ref.shape[1]

    def take(table, idx, fill):
        tab_ref[...] = table
        idx_ref[...] = idx
        _vmem_take(tab_ref, idx_ref, out_ref, fill)
        return out_ref[...]

    # tables are N long, int32 (the lane gather's width), the terminal
    # is the index that matches no chunk, and the rounds are a loop (a
    # round's code is as long as its table: spelt out, a rung of 65,536
    # rows compiles for half a minute)
    arm = _ChaseArm(
        table=lambda x, fill, dtype: x,
        take=take,
        rows=lambda table: table,
        rounds=lambda n, step, state: jax.lax.fori_loop(
            0, n, lambda _, s: step(s), state
        ),
    )
    rank_ref[...] = _rga_chase(
        jump_ref[...], nsib_ref[...], fc_ref[...], forest_ref[...] != 0,
        N=N, arm=arm,
    )


def _rga_rank_vmem(jump, nsib, first_child, in_forest):
    """`_rga_chase` as one Pallas TPU kernel over blocks of docs (the
    interpreter off a TPU, which only a test asks for)."""
    D, N = jump.shape
    db = max(8, min(_VMEM_BLOCK_CELLS // N, round_up_pow2(D)))
    pad = (-D) % db
    args = [
        jnp.pad(a.astype(jnp.int32), ((0, pad), (0, 0)))
        for a in (jump, nsib, first_child, in_forest)
    ]
    block = pl.BlockSpec((db, N), lambda i: (i, 0))
    rank = pl.pallas_call(
        _rga_vmem_kernel,
        grid=((D + pad) // db,),
        in_specs=[block] * 4,
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((D + pad, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((db, N), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # five blocks in and out, double-buffered, three scratch
            # tables and the rounds' temporaries
            vmem_limit_bytes=max(32 << 20, 20 * db * N * 4),
        ),
        interpret=not _tpu_backend(),
        name="rga_chase",
    )(*args)
    return rank[:D]


def _widen(flags, slot, ctr, seq, obj, key, ref, value, psrc, ptgt):
    """Narrow wire dtypes -> int32 kernel lanes. The host packs columns
    as small as their ranges allow (uint8 flags = action|insert<<3,
    int8 slots, int16 rows/ids when they fit) because the host<->device
    link — not the MXU/VPU — bounds the bulk path: widening on device is
    fused VPU work, while every wire byte is wall-clock."""
    i32 = jnp.int32
    action = (flags & 7).astype(i32)
    insert = ((flags >> 3) & 1).astype(i32)
    return (
        action, slot.astype(i32), ctr.astype(i32), seq.astype(i32),
        obj.astype(i32), key.astype(i32), ref.astype(i32), insert,
        value.astype(i32), psrc.astype(i32), ptgt.astype(i32),
    )


def batched_kernel(A: int, K: int):
    """Batched (vmapped) kernel over narrow wire args — the function
    every slab program compiles, whichever device it is pinned to."""

    def fn(flags, slot, ctr, seq, obj, key, ref, value, psrc, ptgt,
           doc_actors):
        (action, slot_w, ctr_w, seq_w, obj_w, key_w, ref_w, insert,
         value_w, psrc_w, ptgt_w) = _widen(
            flags, slot, ctr, seq, obj, key, ref, value, psrc, ptgt
        )
        out, forest = jax.vmap(lambda *xs: _doc_kernel(*xs, A=A, K=K))(
            action, slot_w, ctr_w, seq_w, obj_w, key_w, ref_w, insert,
            value_w, psrc_w, ptgt_w, doc_actors,
        )
        with jax.named_scope("rga_order"):
            return out._replace(rank=_rga_rank(*forest))

    return fn


@compile_cache.jit(static_argnames=("A", "K"))
def materialize_device(
    flags, slot, ctr, seq, obj, key, ref, value, psrc, ptgt,
    doc_actors, A: int, K: int,
) -> MaterializeOut:
    """Batched kernel: all args [D, N] narrow wire dtypes (pred edges
    [D, P], actor map [D, A_loc])."""
    return batched_kernel(A, K)(
        flags, slot, ctr, seq, obj, key, ref, value, psrc, ptgt,
        doc_actors,
    )


# ---------------------------------------------------------------------------
# summary wire: ONE fused uint8 buffer per slab
#
# The materialization barrier's transfer used to be six leaves per slab
# (bit-packed masks, an int16 elem_order, two count vectors, the clock),
# and elem_order was ~85% of the bytes at 16 bits per entry for values
# that need ceil(log2 N).
# The wire packs everything into a single [D, W] uint8 buffer per slab:
# masks bit-packed, elem_order at exactly `order_bits` bits per entry,
# counts at int16 when N allows, and the clock section omitted entirely
# on lean runs (the bulk loader holds authoritative host clocks). For
# the 10k x 1k corpus this is ~1540 bytes/doc vs ~2330 — and one
# transfer to start asynchronously instead of six.


def summary_wire_spec(N: int, A: int, lean: bool) -> Dict[str, int]:
    """Byte layout of the [D, W] summary wire buffer."""
    mask_bytes = (N + 7) // 8
    order_bits = max(1, (N - 1).bit_length())
    if order_bits > 25:
        # _unpack_uint gathers at most 4 bytes per value: shift (<=7) +
        # order_bits must fit a 32-bit window, so entries wider than 25
        # bits would decode silently truncated. No real bucket is within
        # two orders of magnitude of 2^25 rows; reject loudly.
        raise ValueError(
            f"summary wire bucket too large: N={N} needs "
            f"{order_bits}-bit order entries, max 25 (N <= 2^25)"
        )
    order_bytes = (N * order_bits + 7) // 8
    count_bytes = 2 if N < 2**15 else 4
    clock_bytes = 0 if lean else 4 * A
    return {
        "mask_bytes": mask_bytes,
        "order_bits": order_bits,
        "order_bytes": order_bytes,
        "count_bytes": count_bytes,
        "clock_bytes": clock_bytes,
        "total": 2 * mask_bytes + order_bytes + 2 * count_bytes
        + clock_bytes,
    }


def _pack_bits(mask: jax.Array) -> jax.Array:
    """[D, N] bool/0-1 -> [D, ceil(N/8)] uint8, little bit order (numpy
    np.unpackbits(..., bitorder='little') inverts it exactly)."""
    D, N = mask.shape
    pad = (-N) % 8
    m = jnp.pad(mask.astype(jnp.uint8), ((0, 0), (0, pad))).reshape(
        D, -1, 8
    )
    weights = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], jnp.uint8)
    return (m * weights).sum(-1).astype(jnp.uint8)


def _pack_uint(vals: jax.Array, bits: int) -> jax.Array:
    """[D, N] ints in [0, 2^bits) -> [D, ceil(N*bits/8)] uint8: each
    value at exactly `bits` bits, little bit order throughout."""
    D, N = vals.shape
    shifts = jnp.arange(bits, dtype=jnp.int32)
    bitmat = (
        (vals.astype(jnp.int32)[..., None] >> shifts) & 1
    ).reshape(D, N * bits)
    return _pack_bits(bitmat)


def _le_bytes(x: jax.Array, nbytes: int) -> jax.Array:
    """[D, k] ints -> [D, k*nbytes] uint8, little-endian per element
    (portable across backends — no bitcast)."""
    xi = x.astype(jnp.int32)
    parts = [
        ((xi >> (8 * i)) & 0xFF).astype(jnp.uint8) for i in range(nbytes)
    ]
    return jnp.stack(parts, axis=-1).reshape(x.shape[0], -1)


def _summarize_wire(
    out: MaterializeOut, N: int, A: int, lean: bool
) -> jax.Array:
    with jax.named_scope("wire"):
        return _summarize_wire_scoped(out, N, A, lean)


def _summarize_wire_scoped(
    out: MaterializeOut, N: int, A: int, lean: bool
) -> jax.Array:
    spec = summary_wire_spec(N, A, lean)
    order_key = jnp.where(
        out.elem_live, -out.rank, jnp.iinfo(jnp.int32).max
    )
    elem_order = jnp.argsort(order_key, axis=1).astype(jnp.int32)
    cb = spec["count_bytes"]
    parts = [
        _pack_bits(out.map_winner),
        _pack_bits(out.elem_live),
        _pack_uint(elem_order, spec["order_bits"]),
        _le_bytes(out.elem_live.sum(axis=1, dtype=jnp.int32)[:, None], cb),
        _le_bytes(out.map_winner.sum(axis=1, dtype=jnp.int32)[:, None], cb),
    ]
    if not lean:
        parts.append(_le_bytes(out.clock, 4))
    return jnp.concatenate(parts, axis=1)


def _unpack_uint(packed: "Any", N: int, bits: int) -> "Any":
    """Host-side inverse of _pack_uint: [D, OB] uint8 -> [D, N] int64.
    Vectorized byte gathers — no np.unpackbits blowup (that would
    materialize `bits` bytes per value)."""
    import numpy as np

    D = packed.shape[0]
    idx = np.arange(N, dtype=np.int64) * bits
    lo = (idx >> 3).astype(np.int64)
    sh = (idx & 7).astype(np.int64)
    pk = np.concatenate([packed, np.zeros((D, 4), np.uint8)], axis=1)
    wide = bits > 17  # sh + bits can exceed the 3-byte window
    acct = np.int64 if wide else np.int32
    acc = pk[:, lo].astype(acct)
    acc |= pk[:, lo + 1].astype(acct) << 8
    acc |= pk[:, lo + 2].astype(acct) << 16
    if wide:
        acc |= pk[:, lo + 3].astype(acct) << 24
    return ((acc >> sh.astype(acct)) & ((1 << bits) - 1)).astype(np.int64)


def unpack_bits_le(packed, N: int):
    """Host-side inverse of _pack_bits: [D, ceil(N/8)] uint8 -> [D, N]
    bool. The single unpack twin for BOTH fetched wires and memo-served
    summary rows — bit order/padding changes happen here and in
    _pack_bits only."""
    import numpy as np

    return np.unpackbits(
        np.ascontiguousarray(packed), axis=1, bitorder="little"
    )[:, :N].astype(bool)


def parse_summary_wire(wire, N: int, A: int, lean: bool):
    """Host decode of one slab's fused summary buffer -> the columnar
    summary dict (same keys/values as ops.materialize.decode_columnar;
    the clock comes back zeros on lean wires — the caller overlays its
    authoritative host clocks)."""
    import numpy as np

    spec = summary_wire_spec(N, A, lean)
    wire = np.asarray(wire)
    D = wire.shape[0]
    assert wire.shape[1] == spec["total"], (wire.shape, spec)
    mb = spec["mask_bytes"]

    def bits(seg):
        return unpack_bits_le(seg, N)

    o = 2 * mb
    ob = spec["order_bytes"]
    elem_order = _unpack_uint(
        np.ascontiguousarray(wire[:, o : o + ob]), N, spec["order_bits"]
    )
    o += ob
    cb = spec["count_bytes"]
    cdt = "<i2" if cb == 2 else "<i4"
    n_live = (
        np.ascontiguousarray(wire[:, o : o + cb])
        .view(cdt)
        .ravel()
        .astype(np.int64)
    )
    o += cb
    n_map = (
        np.ascontiguousarray(wire[:, o : o + cb])
        .view(cdt)
        .ravel()
        .astype(np.int64)
    )
    o += cb
    if lean:
        clock = np.zeros((D, A), np.int32)
    else:
        clock = (
            np.ascontiguousarray(wire[:, o : o + 4 * A])
            .view("<i4")
            .reshape(D, A)
        )
    return {
        "map_winner": bits(wire[:, 0:mb]),
        "elem_live": bits(wire[:, mb : 2 * mb]),
        "elem_order": elem_order,
        "n_live_elems": n_live,
        "n_map_entries": n_map,
        "clock": clock,
    }


@compile_cache.jit(static_argnames=("A", "K"))
def materialize_summary_device(
    flags, slot, ctr, seq, obj, key, ref, value, psrc, ptgt,
    doc_actors, A: int, K: int,
) -> jax.Array:
    """Kernel + on-device summarization in ONE dispatch: the full per-row
    lanes (visible/rank/winner masks) never leave the device; the return
    is the fused summary wire buffer."""
    out = batched_kernel(A, K)(
        flags, slot, ctr, seq, obj, key, ref, value, psrc, ptgt,
        doc_actors,
    )
    return _summarize_wire(out, flags.shape[1], A, lean=False)


@compile_cache.jit(static_argnames=("A", "K"))
def materialize_full_device(
    flags, slot, ctr, seq, obj, key, ref, value, psrc, ptgt,
    doc_actors, A: int, K: int,
):
    """One dispatch -> (MaterializeOut, summary wire). The bulk loader
    uses this: the fused summary buffer transfers compactly for the
    materialization barrier, while the full lanes stay device-resident
    for lazy per-doc patch decode (DecodedBatch.doc_view)."""
    out = batched_kernel(A, K)(
        flags, slot, ctr, seq, obj, key, ref, value, psrc, ptgt,
        doc_actors,
    )
    return out, _summarize_wire(out, flags.shape[1], A, lean=False)


@compile_cache.jit(static_argnames=("A", "K"))
def materialize_full_lean_device(
    flags, slot, ctr, obj, key, ref, psrc, ptgt, doc_actors,
    A: int, K: int,
):
    """materialize_full_device minus the seq and value wires (~4 bytes/op
    on a link where every byte is wall-clock) AND minus the summary's
    clock section. Correct ONLY when the batch has no INC ops (value
    feeds counter accumulation) and the caller supplies clocks host-side
    (seq feeds only the clock lane — the bulk loader's clocks come from
    the sidecar metadata and are the more authoritative value anyway).
    inc_total and clock lanes come back as zeros."""
    zeros = jnp.zeros_like(ctr)
    out = batched_kernel(A, K)(
        flags, slot, ctr, zeros, obj, key, ref, zeros, psrc, ptgt,
        doc_actors,
    )
    return out, _summarize_wire(out, flags.shape[1], A, lean=True)


LIVE_MIN_ROWS = 64
LIVE_MIN_DOCS = 1


def live_bucket(n: int, floor: int) -> int:
    """Pow2 jit bucket with a floor: live tick batches pad their row /
    doc / actor-slot / key axes to these shapes so a stream of ticks
    reuses a handful of compiled programs instead of compiling one per
    exact shape (the same bucketing discipline as the bulk slab path)."""
    return max(floor, round_up_pow2(max(n, 1)))


@compile_cache.jit(static_argnames=("A", "K"))
def materialize_live_device(
    flags, slot, ctr, obj, key, ref, value, psrc, ptgt, A: int, K: int
) -> MaterializeOut:
    """The live tick entry: materialize_device minus the seq wire and
    the doc-actor map. The live engine holds authoritative clocks
    host-side (admission mirrors OpSet's causal gating), so the clock
    lane is never read — seq uploads nothing and the [D, A] clock
    output comes back zeros. `value` still rides the wire: live batches
    may carry INC ops."""
    zeros = jnp.zeros_like(ctr)
    da = jnp.zeros((flags.shape[0], A), jnp.int32)
    return batched_kernel(A, K)(
        flags, slot, ctr, zeros, obj, key, ref, value, psrc, ptgt, da
    )


def ensure_doc_actors(batch: ColumnarBatch):
    """batch.doc_actors, deriving it from the actor column when a legacy
    producer didn't supply one (cached back onto the batch)."""
    import numpy as np

    if batch.doc_actors is not None:
        return batch.doc_actors
    A = max(1, len(batch.actors))
    D = batch.n_docs
    valid = batch.cols["action"] != PAD
    dcol = np.repeat(np.arange(D, dtype=np.int64), batch.n_rows)
    acol = batch.cols["actor"].astype(np.int64).ravel()
    pairs = np.unique((dcol * A + acol)[valid.ravel()])
    batch.doc_actors = doc_actor_map_from_pairs(pairs, A, D)
    return batch.doc_actors


def actor_bucket(batch: ColumnarBatch) -> int:
    """A_loc: the pow2 bucket (floor 4) of the most actors any doc of
    the batch has."""
    return max(4, round_up_pow2(ensure_doc_actors(batch).shape[1]))


def bucket_doc_actors(batch: ColumnarBatch):
    """(doc_actors padded to the A_loc bucket, A_loc, K): the pow2 bucket
    shape (A_loc >= 4, K >= 16), so batches of different composition
    land in the same compiled program — a bulk load's slabs all reuse
    one executable."""
    import numpy as np

    da = ensure_doc_actors(batch)
    A = actor_bucket(batch)
    if da.shape[1] < A:
        da = np.concatenate(
            [da, np.full((da.shape[0], A - da.shape[1]), -1, np.int32)],
            axis=1,
        )
    K = max(16, round_up_pow2(max(1, len(batch.keys))))
    return da, A, K


def ensure_slot(batch: ColumnarBatch):
    """[D, N] per-doc LOCAL actor slot per row (int16), derived from the
    global actor column + doc_actors map and cached on the batch. One
    vectorized searchsorted — rows of doc_actors are ascending, so a
    doc-offset composite keeps the flat array sorted."""
    import numpy as np

    if batch.slot is not None:
        return batch.slot
    da = ensure_doc_actors(batch)
    D, A = da.shape
    stride = max(2, len(batch.actors) + 2)
    docs = np.arange(D, dtype=np.int64)[:, None]
    flat_da = np.where(
        da < 0, stride - 1, da.astype(np.int64)
    ) + docs * stride
    comp = batch.cols["actor"].astype(np.int64) + docs * stride
    slot = (
        np.searchsorted(flat_da.ravel(), comp.ravel())
        - (np.repeat(np.arange(D, dtype=np.int64), batch.n_rows) * A)
    )
    # PAD rows may name an actor outside the doc's set; clamp into [0, A)
    batch.slot = np.clip(slot, 0, A - 1).astype(np.int16).reshape(D, -1)
    return batch.slot


def _narrow(arr, lo: int, hi: int):
    """Smallest safe wire dtype for values known to lie in [lo, hi]."""
    import numpy as np

    if lo >= -(2**15) and hi < 2**15:
        return np.ascontiguousarray(arr, dtype=np.int16)
    return np.ascontiguousarray(arr, dtype=np.int32)


def host_args(batch: ColumnarBatch, lean: bool = False):
    """(numpy wire args, A_loc, K): the narrow columns every kernel entry
    transfers. uint8 flags = action|insert<<3; int8 slot; int16 where the
    value range fits (N-indexed columns whenever N < 32k — the common
    case), int32 otherwise. Dtypes are a function of the (N, P) bucket
    and value ranges, so slabs of one bulk load share one executable.
    `lean` leaves the seq/value slots as None — their narrowing passes
    (two [D, N] copies + range scans) are skipped, not just their
    uploads."""
    import numpy as np

    da, A, K = bucket_doc_actors(batch)
    slot = ensure_slot(batch)
    c = batch.cols
    _check_ranges(batch, A, K)
    N = batch.n_rows
    flags = (
        np.asarray(c["action"], np.uint8)
        | (np.asarray(c["insert"], np.uint8) << 3)
    )
    cmax = int(c["ctr"].max(initial=0))
    if lean:
        seq_w = value_w = None
    else:
        vmax = int(c["value"].max(initial=0))
        vmin = int(c["value"].min(initial=0))
        smax = int(c["seq"].max(initial=0))
        seq_w = _narrow(c["seq"], 0, smax)
        value_w = _narrow(c["value"], vmin, vmax)
    args = (
        flags,
        np.ascontiguousarray(
            slot, dtype=np.int8 if A <= 127 else np.int16
        ),
        _narrow(c["ctr"], 0, cmax),
        seq_w,
        _narrow(c["obj"], -1, N - 1),
        _narrow(c["key"], -1, max(0, len(batch.keys) - 1)),
        _narrow(c["ref"], -3, N - 1),
        value_w,
        _narrow(batch.psrc, -1, N - 1),
        _narrow(batch.ptgt, -1, N - 1),
        np.ascontiguousarray(da, np.int32),
    )
    return args, A, K


def _device_args(batch: ColumnarBatch, lean: bool = False, device=None):
    """(device args, A_loc, K) for the jitted kernels. `lean` skips the
    seq/value builds and uploads (their slots are None). `device` pins
    the upload to a specific device (the slab round-robin scheduler);
    None uses the default placement. Narrowing and upload are spans; a
    bulk load's dispatch stage reads their seconds from its own span's
    `kids` (t_narrow / t_upload)."""
    with telemetry.timed("pipeline.narrow", "pipeline"):
        np_args, A, K = host_args(batch, lean=lean)
    with telemetry.timed("pipeline.upload", "pipeline"):
        if device is None:
            args = tuple(
                None if a is None else jnp.asarray(a) for a in np_args
            )
        else:
            args = tuple(
                None if a is None else jax.device_put(a, device)
                for a in np_args
            )
    return args, A, K


def run_batch_summary(batch: ColumnarBatch) -> jax.Array:
    """Host entry for the bulk path: pack numpy -> fused kernel+summary
    wire buffer (decode with parse_summary_wire)."""
    args, A, K = _device_args(batch)
    return materialize_summary_device(*args, A=A, K=K)


def run_batch(batch: ColumnarBatch) -> MaterializeOut:
    """Convenience host entry: pack numpy -> device -> outputs."""
    args, A, K = _device_args(batch)
    return materialize_device(*args, A=A, K=K)


def run_batch_full(
    batch: ColumnarBatch, lean: bool = False, device=None
):
    """Host entry -> (MaterializeOut, fused summary wire buffer) in one
    dispatch (decode the wire with parse_summary_wire).

    `lean=True` (callers that hold authoritative host clocks and verified
    the batch carries no INC ops) skips the seq/value wires entirely.
    `device` pins args (and therefore execution) to one device — the
    slab round-robin scheduler's per-chip dispatch."""
    args, A, K = _device_args(batch, lean=lean, device=device)
    fn, args = _full_entry(args, lean)
    avals = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)
    statics = {"A": A, "K": K}
    _dispatched.setdefault(
        (batch.n_docs, batch.n_rows, bool(lean)), {}
    )[(fn.__name__, avals, A, K)] = (fn, avals, statics)
    with telemetry.timed(
        "pipeline.enqueue", "pipeline", D=batch.n_docs, N=batch.n_rows,
        A=A, K=K, P=batch.psrc.shape[1],
    ):
        return fn(*args, A=A, K=K)


def batch_is_lean(batch: ColumnarBatch) -> bool:
    """No INC op in the batch: with host clocks in hand the lean entry
    serves it (no seq and value wires, no clock section)."""
    import numpy as np

    return not bool(np.any(batch.cols["action"] == _INC))


def _full_entry(args, lean: bool):
    """(jitted entry, its positional args) of a full-kernel dispatch:
    the lean entry takes no seq and value wires."""
    if not lean:
        return materialize_full_device, args
    flags, slot, ctr, _seq, obj, key, ref, _value, psrc, ptgt, da = args
    return materialize_full_lean_device, (
        flags, slot, ctr, obj, key, ref, psrc, ptgt, da
    )


# -- device seconds by phase --------------------------------------------
# A device trace names an operation by its HLO instruction (`%fusion.27`)
# and carries no scope, so the map from instruction to `PHASES` comes
# from the program: the optimized HLO of the same executable (a hit of
# the compile caches, so the names are those of the trace) keeps each
# instruction's `op_name`, and a fusion's scope is that of the
# instructions fused into it.

_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?(%?[\w.\-]+)\s+=")
_HLO_CALLS = re.compile(r"\b(?:calls|to_apply)=(%?[\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PHASE_IN_OP_NAME = re.compile(
    r"(?<![\w.])(" + "|".join(PHASES) + r")(?![\w.])"
)


def phases_of_hlo(hlo_text: str) -> Dict[str, str]:
    """{instruction of the entry computation: phase} from optimized HLO
    text. The phases of an instruction are those its own `op_name`
    names plus, for a fusion (or any caller), those of the instructions
    inside the computation it calls: one gives that phase, two or more
    "mixed", none "unscoped"."""
    inside: Dict[str, set] = {}  # computation -> phases its lines name
    callees: Dict[str, set] = {}  # computation -> computations it calls
    entry: Dict[str, tuple] = {}  # entry instruction -> (own, callees)
    comp, in_entry = None, False
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            comp, in_entry = m.group(1), line.startswith("ENTRY")
            inside[comp], callees[comp] = set(), set()
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        named = _HLO_OP_NAME.search(line)
        own = (
            set(_PHASE_IN_OP_NAME.findall(named.group(1))) if named
            else set()
        )
        called = set(_HLO_CALLS.findall(line))
        inside[comp] |= own
        callees[comp] |= called
        if in_entry:
            entry[m.group(1)] = (own, called)

    def phases_in(name: str, seen: frozenset) -> set:
        if name in seen or name not in inside:
            return set()
        out = set(inside[name])
        for c in callees[name]:
            out |= phases_in(c, seen | {name})
        return out

    out: Dict[str, str] = {}
    for name, (own, called) in entry.items():
        seen = set(own)
        for c in called:
            seen |= phases_in(c, frozenset())
        out[name] = (
            "unscoped" if not seen
            else next(iter(seen)) if len(seen) == 1 else "mixed"
        )
    return out


def phase_of_ops(n_docs: int, n_rows: int, lean: bool) -> Dict[str, str]:
    """{HLO instruction: phase | "mixed" | "unscoped"} of the slab
    programs this process dispatched for a [n_docs, n_rows] slab, or {}
    if it dispatched none of that shape.

    Two slabs of one [n_docs, n_rows] are different programs when they
    differ in the actor bucket `A`, the key bucket `K`, the pred bucket
    or a wire dtype. Each is lowered; an instruction name that all of
    them give one phase keeps it, and one they disagree on is "mixed"
    (the caller knows a slab by its shape alone, and must not book a
    program's seconds under another program's phase).

    Compiles the same function for the same argument shapes (those
    `run_batch_full` remembered) and reads the optimized HLO: the same
    program as the one that ran, so the same instruction names. Not
    the very executable, though: the persistent cache's key leaves
    metadata out, so the executable that ran may date from before its
    scopes were named. So this compile goes through a function object
    and a jit of its own (JAX's in-process caches would hand back the
    executable that ran) with the metadata in the persistent cache's
    key: cached too, but never stale."""
    out: Dict[str, str] = {}
    programs = _dispatched.get((n_docs, n_rows, bool(lean)), {})
    for fn, avals, statics in programs.values():
        for name, phase in _phases_of_program(fn, avals, statics).items():
            out[name] = phase if out.get(name, phase) == phase else "mixed"
    return out


def _phases_of_program(fn, avals, statics) -> Dict[str, str]:
    @functools.wraps(fn.__wrapped__)
    def same_program(*args, **kwargs):
        return fn.__wrapped__(*args, **kwargs)

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        compiled = (
            jax.jit(same_program, static_argnames=tuple(statics))
            .lower(*avals, **statics)
            .compile()
        )
    finally:
        jax.config.update(flag, before)
    return phases_of_hlo(compiled.as_text())


# (n_docs, n_rows, lean) -> {full static signature: (jitted entry, arg
# shapes, statics)} of the full-kernel programs this process
# dispatched: what phase_of_ops lowers
_dispatched: Dict[tuple, Dict[tuple, tuple]] = {}


def _check_ranges(batch: ColumnarBatch, A: int, K: int) -> None:
    N = batch.n_rows
    max_ctr = int(batch.cols["ctr"].max(initial=0))
    if max_ctr * A + A >= 2**30:
        raise ValueError(
            f"lamport x actor-slot composite overflow: ctr={max_ctr} A={A}"
        )
    if (N + 1) * (K + 1) + K >= 2**31:
        raise ValueError(f"obj x key group id overflow: N={N} K={K}")
