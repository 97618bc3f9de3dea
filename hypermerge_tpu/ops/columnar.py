"""Columnar op-log encoding — changes as padded int32 tensors.

The bulk half of the dual-path design (SURVEY.md §7.1, BASELINE.json):
a document's change history becomes fixed-shape int32 columns that the
device kernels (ops/crdt_kernels.py) consume; `vmap` batches documents on a
leading axis; `pjit` shards that axis over the mesh (parallel/).

Row = one op, in a causal linear order (sorted by (start_op ctr, actor) —
valid because a change depending on another always has a larger start_op).

Columns (all int32, shape [N] per doc, padded with PAD rows):
  action  Action code (change.Action; PAD=7)
  actor   index into the batch actor table
  ctr     lamport counter (op id = (ctr, actor))
  seq     change seq the op belongs to (for device clock derivation)
  obj     row index of the container's MAKE op; -1 = root map
  key     index into the batch key-string table; -1 = none (list ops)
  ref     row index: INS -> predecessor elem row (-2 = HEAD);
          SET/DEL on elem -> elem row; INC -> target value-op row; else -3
  insert  1 if the op creates a new list/text element
  vkind   value encoding kind (VK_*)
  value   inline small int / bool / index into a side table
  dt      datatype code: 0 none, 1 counter, 2 timestamp

Supersession (pred) edges are their own arrays [P]: psrc (superseding row),
ptgt (superseded row), padded with (-1, -1). INC ops contribute NO pred
edges — their target rides the ref column (an INC must not kill its
counter).

Side tables (batch-global, host-side): actors, key strings, value strings,
floats (float64 — no precision loss through the device path), bigints.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from operator import attrgetter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from ..crdt.change import HEAD, ROOT, Action, Change, OpId

PAD = int(Action.PAD)

# value kinds
VK_NONE = 0
VK_INT = 1  # inline int32
VK_FLOAT = 2  # index into floats table
VK_STR = 3  # index into strings table
VK_BOOL = 4  # inline 0/1
VK_BIGINT = 5  # index into bigints table
# MAKE_* rows carry no value (the op id is the object id)

_INT32_MIN, _INT32_MAX = -(2**31), 2**31 - 1

COLUMNS = (
    "action",
    "actor",
    "ctr",
    "seq",
    "obj",
    "key",
    "ref",
    "insert",
    "vkind",
    "value",
    "dt",
)


class _Interner:
    def __init__(self) -> None:
        self.items: List[Any] = []
        self._index: Dict[Any, int] = {}

    def __call__(self, item: Any) -> int:
        idx = self._index.get(item)
        if idx is None:
            idx = len(self.items)
            self.items.append(item)
            self._index[item] = idx
        return idx

    def __len__(self) -> int:
        return len(self.items)


@dataclass
class ColumnarBatch:
    """[D, N] padded op columns + [D, P] pred edges + side tables.

    `doc_actors` is the per-doc local actor map: [D, A_loc] int32
    indices into `actors`, ascending (== actor-string sort order, the
    device tie-break), padded with -1. The device kernels only ever see
    A_loc (max actors per doc, a small constant) — never the batch-wide
    actor count — so the jit bucket and the [D, A_loc] clock output stay
    independent of how many documents share a slab."""

    cols: Dict[str, np.ndarray]
    psrc: np.ndarray
    ptgt: np.ndarray
    n_ops: np.ndarray  # [D] real (unpadded) op counts
    actors: List[str]
    keys: List[str]
    strings: List[str]
    floats: List[float]
    bigints: List[int]
    op_actor_ids: List[List[str]] = field(default_factory=list)
    doc_actors: Optional[np.ndarray] = None  # [D, A_loc] int32, -1 pad
    slot: Optional[np.ndarray] = None  # [D, N] int16 local actor slots
    # which pack_docs_columns path made the batch: "prefix" | "general"
    packed_by: str = ""
    # general path: feeds whose rows the native gather / its numpy twin read
    gather_feeds: Tuple[int, int] = (0, 0)
    # the gate: feeds whose verdict hm_prefix_gate / the numpy twin gave
    gate_feeds: Tuple[int, int] = (0, 0)

    @property
    def shape(self) -> Tuple[int, int]:
        return self.cols["action"].shape  # (D, N)

    @property
    def n_docs(self) -> int:
        return self.shape[0]

    @property
    def n_rows(self) -> int:
        return self.shape[1]


def widen_preds(batch: ColumnarBatch, n_pred: int) -> ColumnarBatch:
    """`batch` with a pred axis of at least `n_pred` columns (the new
    ones empty, -1): a caller that buckets the pred axis by another
    rule than the pow2 over the widest doc. A batch that wide already
    is handed back untouched."""
    D, P = batch.psrc.shape
    if P < n_pred:
        for name in ("psrc", "ptgt"):
            narrow = getattr(batch, name)
            wide = np.full((D, n_pred), -1, narrow.dtype)
            wide[:, :P] = narrow
            setattr(batch, name, wide)
    return batch


def causal_sort(changes: Sequence[Change]) -> List[Change]:
    """Deduplicate by (actor, seq) and sort into a causal linear order.

    (start_op, actor) is a valid linear extension: if X depends on Y then
    X.start_op > Y.max_op >= Y.start_op (lamport assignment in
    OpSet.apply_local_request)."""
    seen = {}
    for c in changes:
        seen.setdefault((c.actor, c.seq), c)
    return sorted(seen.values(), key=lambda c: (c.start_op, c.actor))


def pack_docs(
    docs_changes: Sequence[Sequence[Change]],
    n_rows: Optional[int] = None,
    n_pred: Optional[int] = None,
) -> ColumnarBatch:
    """Pack many documents' histories into one padded batch."""
    actor_ids = _Interner()
    key_ids = _Interner()
    str_ids = _Interner()
    float_ids = _Interner()
    big_ids = _Interner()

    per_doc: List[Tuple[Dict[str, List[int]], List[Tuple[int, int]]]] = []
    for changes in docs_changes:
        per_doc.append(
            _pack_one(
                causal_sort(changes), actor_ids, key_ids, str_ids, float_ids,
                big_ids,
            )
        )

    # Device kernels tie-break concurrent ops by actor *index* (the
    # composite ctr*A + actor); the host OpSet tie-breaks by actor *string*
    # (OpId ordering). Remap indices so index order == string sort order.
    sorted_actors = sorted(actor_ids.items)
    lut = np.zeros(max(len(actor_ids.items), 1), dtype=np.int32)
    for old, name in enumerate(actor_ids.items):
        lut[old] = sorted_actors.index(name)
    for doc_cols, _ in per_doc:
        doc_cols["actor"] = [int(lut[a]) for a in doc_cols["actor"]]
    actor_ids.items = sorted_actors

    max_ops = max((len(d[0]["action"]) for d in per_doc), default=0)
    max_preds = max((len(d[1]) for d in per_doc), default=0)
    N = n_rows if n_rows is not None else _round_up(max(max_ops, 1))
    P = n_pred if n_pred is not None else _round_up(max(max_preds, 1))
    if max_ops > N or max_preds > P:
        raise ValueError(
            f"doc exceeds bucket: ops {max_ops}>{N} or preds {max_preds}>{P}"
        )

    D = len(per_doc)
    cols = {name: np.full((D, N), 0, dtype=np.int32) for name in COLUMNS}
    cols["action"][:] = PAD
    cols["obj"][:] = -1
    cols["key"][:] = -1
    cols["ref"][:] = -3
    psrc = np.full((D, P), -1, dtype=np.int32)
    ptgt = np.full((D, P), -1, dtype=np.int32)
    n_ops = np.zeros((D,), dtype=np.int32)

    doc_actor_sets: List[List[int]] = []
    for d, (doc_cols, preds) in enumerate(per_doc):
        n = len(doc_cols["action"])
        n_ops[d] = n
        for name in COLUMNS:
            cols[name][d, :n] = doc_cols[name]
        for k, (s, t) in enumerate(preds):
            psrc[d, k] = s
            ptgt[d, k] = t
        doc_actor_sets.append(sorted(set(doc_cols["actor"])))

    return ColumnarBatch(
        cols=cols,
        psrc=psrc,
        ptgt=ptgt,
        n_ops=n_ops,
        actors=list(actor_ids.items),
        keys=list(key_ids.items),
        strings=list(str_ids.items),
        floats=list(float_ids.items),
        bigints=list(big_ids.items),
        doc_actors=pack_doc_actor_map(doc_actor_sets),
    )


def pack_doc_actor_map(doc_actor_sets: Sequence[Sequence[int]]) -> np.ndarray:
    """[D, A_loc] int32 local actor map from per-doc ascending actor-index
    lists; -1 pads. A_loc = max actors in any one doc (min 1)."""
    D = len(doc_actor_sets)
    a_loc = max((len(s) for s in doc_actor_sets), default=1)
    out = np.full((D, max(a_loc, 1)), -1, np.int32)
    for d, s in enumerate(doc_actor_sets):
        out[d, : len(s)] = s
    return out


def round_up_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


_round_up = round_up_pow2


def doc_actor_map_from_pairs(
    pairs: np.ndarray, A: int, Dp: int
) -> np.ndarray:
    """[Dp, A_loc] local actor map from sorted unique (doc*A + actor)
    composites; ascending within a doc (== actor-string sort order when
    actor indices index a sorted actor table), -1 pads."""
    pair_doc = pairs // A
    pair_counts = np.bincount(pair_doc, minlength=Dp).astype(np.int64)
    A_loc = int(pair_counts.max(initial=1))
    pair_starts = np.zeros(Dp + 1, np.int64)
    np.cumsum(pair_counts, out=pair_starts[1:])
    out = np.full(Dp * max(A_loc, 1), -1, np.int32)
    slot = np.arange(len(pairs), dtype=np.int64) - pair_starts[pair_doc]
    out[pair_doc * A_loc + slot] = (pairs % A).astype(np.int32)
    return out.reshape(Dp, max(A_loc, 1))


def _encode_op_row(
    op,
    opid: OpId,
    change: Change,
    row_of: Dict[OpId, int],
    actor_ids: _Interner,
    key_ids: _Interner,
    str_ids: _Interner,
    float_ids: _Interner,
    big_ids: _Interner,
) -> Optional[Tuple[Dict[str, int], List[int]]]:
    """Resolve + encode ONE op as ({column: value}, pred target rows).
    None when the op drops (unknown container/element/INC target — the
    OpSet tolerance). THE row encoding: `_pack_one` (bulk reference
    pack) and `LiveColumns._append_one` (live engine appends) both
    write exactly these values, so the two paths cannot drift."""
    if op.obj == ROOT:
        obj_row = -1
    else:
        obj_row = row_of.get(op.obj, -4)
        if obj_row == -4:
            return None  # container unknown (tolerate, like OpSet)
    if op.action == Action.INC:
        target = op.pred[0] if op.pred else None
        ref_row = row_of.get(target, -3) if target else -3
        if ref_row == -3:
            return None
    elif op.ref is None:
        ref_row = -3
    elif op.ref == HEAD:
        ref_row = -2
    else:
        ref_row = row_of.get(op.ref, -4)
        if ref_row == -4:
            return None  # unknown element
    vkind, value = _encode_value(op, str_ids, float_ids, big_ids)
    vals = {
        "action": int(op.action),
        "actor": actor_ids(change.actor),
        "ctr": opid.ctr,
        "seq": change.seq,
        "obj": obj_row,
        "key": key_ids(op.key) if op.key is not None else -1,
        "ref": ref_row,
        "insert": 1 if op.insert else 0,
        "vkind": vkind,
        "value": value,
        "dt": (
            1 if op.datatype == "counter"
            else 2 if op.datatype == "timestamp" else 0
        ),
    }
    pred_tgts: List[int] = []
    if op.action != Action.INC:
        for p in op.pred:
            tgt = row_of.get(p)
            if tgt is not None:
                pred_tgts.append(tgt)
    return vals, pred_tgts


def _pack_one(
    changes: List[Change],
    actor_ids: _Interner,
    key_ids: _Interner,
    str_ids: _Interner,
    float_ids: _Interner,
    big_ids: _Interner,
) -> Tuple[Dict[str, List[int]], List[Tuple[int, int]]]:
    cols: Dict[str, List[int]] = {name: [] for name in COLUMNS}
    preds: List[Tuple[int, int]] = []
    row_of: Dict[OpId, int] = {}
    row = 0
    for change in changes:
        for i, op in enumerate(change.ops):
            opid = change.op_id(i)
            enc = _encode_op_row(
                op, opid, change, row_of,
                actor_ids, key_ids, str_ids, float_ids, big_ids,
            )
            if enc is None:
                continue
            vals, pred_tgts = enc
            for name in COLUMNS:
                cols[name].append(vals[name])
            for tgt in pred_tgts:
                preds.append((row, tgt))
            row_of[opid] = row
            row += 1
    return cols, preds


def _encode_value(op, str_ids, float_ids, big_ids) -> Tuple[int, int]:
    v = op.value
    if op.action.makes_object or v is None:
        return VK_NONE, 0
    if isinstance(v, bool):
        return VK_BOOL, 1 if v else 0
    if isinstance(v, int):
        if _INT32_MIN <= v <= _INT32_MAX:
            return VK_INT, v
        return VK_BIGINT, big_ids(v)
    if isinstance(v, float):
        return VK_FLOAT, float_ids(v)
    if isinstance(v, str):
        return VK_STR, str_ids(v)
    # fallthrough: non-scalar payloads shouldn't occur (containers are MAKE
    # ops); encode their repr so nothing crashes
    return VK_STR, str_ids(repr(v))


# ---------------------------------------------------------------------------
# vectorized bulk packing from columnar feed caches (storage/colcache.py)
#
# The per-op Python loop above (`pack_docs`) is the correctness reference;
# this path packs the same batch from FeedColumns sidecars with numpy only:
# window slicing by searchsorted, one flat causal argsort across all docs,
# and OpId -> row resolution via a sorted composite-key lookup. This is
# what makes the 10k-doc cold start feed->device path real (BASELINE
# config 4): zero per-op host work.


def _prefix_single_ok(fc) -> bool:
    """True if a feed qualifies for the no-sort prefix pack: every op's
    container/element/pred references stay inside the feed (single-writer
    history), and ctr is strictly increasing (commit order == causal
    order). Cached on the FeedColumns object.

    The cache is an idempotent latch, safe under concurrent pack
    workers (HM_PACK_WORKERS>1, guard manifest entry for FeedColumns):
    racing callers compute the same bool from immutable planes and the
    attribute rebind is GIL-atomic, so the worst case is duplicate
    compute, never a torn or wrong value."""
    ok = getattr(fc, "_prefix_single_ok", None)
    if ok is None:
        n = fc.n_rows
        ctr = fc.plane("ctr")
        ok = bool(
            np.all(fc.plane("obj_a") <= 0)  # obj actor: ROOT or writer
            and np.all(fc.plane("ref_a") <= 0)  # writer or sentinel
            # dense lamport counters: row i is op ctr i+1, so references
            # resolve as ctr-1 with no search
            and np.array_equal(
                ctr, np.arange(1, n + 1, dtype=ctr.dtype)
            )
            and (len(fc.preds) == 0 or np.all(fc.preds[:, 2] == 0))
        )
        fc._prefix_single_ok = ok
    return ok


_DT_CODE = {
    np.dtype(np.int8): 0,
    np.dtype(np.int16): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.uint8): 3,
}

# source plane order of the native pack entry (hm_native.cpp hm_pack_prefix)
_PACK_SRC_PLANES = (
    "action", "ctr", "seq", "obj_ctr", "obj_a", "key",
    "ref_ctr", "ref_a", "insert", "vkind", "value", "dt",
)

_pack_src_idx_cache: Optional[np.ndarray] = None


def _pack_src_idx() -> np.ndarray:
    """Indices of the native pack's source planes within the sidecar's
    PLANE_NAMES order (what FeedColumns.plane_meta offsets follow).

    Thread-safety (pack pool, HM_PACK_WORKERS>1): compute-local, then
    ONE assignment publishes — concurrent first callers may each build
    the (identical, immutable) array, but no caller can ever observe a
    half-built cache; the module-global rebind is GIL-atomic."""
    global _pack_src_idx_cache
    got = _pack_src_idx_cache
    if got is None:
        from ..storage.colcache import PLANE_NAMES

        got = np.asarray(
            [PLANE_NAMES.index(n) for n in _PACK_SRC_PLANES], np.int64
        )
        _pack_src_idx_cache = got
    return got


def _ptr(a: np.ndarray) -> int:
    """The address of an array's first element, for the native ABI."""
    return a.__array_interface__["data"][0]


def _native_pack_lib():
    if os.environ.get("HM_NATIVE_PACK", "1") == "0":
        return None
    from .. import native

    return native.pack_lib()


def _pack_wire_dtypes(i16ok, row_dt, kdt, vmin, vmax):
    return {
        "action": np.uint8,
        "insert": np.uint8,
        "vkind": np.uint8,
        "dt": np.uint8,
        "actor": np.int32,  # batch-global ids (host/decode only)
        "ctr": row_dt,
        "seq": row_dt,
        "obj": row_dt,
        "key": kdt,
        "ref": row_dt,
        "value": (
            np.int16
            if i16ok and -(2**15) <= vmin and vmax < 2**15
            else np.int32
        ),
    }


def _native_pack_prefix(
    lib, fcs, fc_idx_a, ends, writer_g, flat_lut,
    D, Dp, N, i16ok, row_dt, kdt,
) -> Dict[str, np.ndarray]:
    """Emit the padded [Dp, N] column planes through the C++ batch entry
    point: per-feed narrow plane pointers in, preallocated output buffers
    filled in place (real rows AND pad cells — no np.full prepass, no [M]
    intermediates). Returns {} when a plane can't be described to the
    native ABI (caller falls back to the numpy twin)."""
    with _stage("prefix.tables"):
        F = len(fcs)
        srcs = np.empty((F, len(_PACK_SRC_PLANES)), np.int64)
        sdts = np.empty((F, len(_PACK_SRC_PLANES)), np.uint8)
        keep_alive = []  # converted planes must outlive the call
        src_idx = _pack_src_idx()
        for i, fc in enumerate(fcs):
            meta = fc.plane_meta
            if meta is not None:
                # every plane is a slice of one checkpoint buffer: all 12
                # pointers derive from the base address in two gathers
                base_addr, offs, dts = meta[0], meta[1], meta[2]
                srcs[i] = base_addr + offs[src_idx]
                sdts[i] = dts[src_idx]
                keep_alive.append(meta)
                continue
            planes = fc.planes
            for j, name in enumerate(_PACK_SRC_PLANES):
                p = planes[name]
                code = _DT_CODE.get(p.dtype)
                if code is None or not p.flags["C_CONTIGUOUS"]:
                    p = np.ascontiguousarray(p, np.int32)
                    keep_alive.append(p)
                    code = 2
                srcs[i, j] = p.__array_interface__["data"][0]
                sdts[i, j] = code

        # a corrupt sidecar whose row_ends overrun its planes must not reach
        # the C loops (the numpy twin fails loudly on the length mismatch)
        feed_rows = np.asarray([fc.n_rows for fc in fcs], np.int64)
        if np.any(ends > feed_rows[fc_idx_a]):
            return {}

        klut, koffs = flat_lut("k")
        slut, soffs = flat_lut("s")
        flut, foffs = flat_lut("f")
        blut, boffs = flat_lut("b")
        lut_lens = np.asarray(
            [len(klut), len(slut), len(flut), len(blut)], np.int64
        )
        writer_g = np.ascontiguousarray(writer_g, np.int64)
        ends = np.ascontiguousarray(ends, np.int64)
        fc_idx_a = np.ascontiguousarray(fc_idx_a, np.int64)

    with _stage("prefix.native", native=1):
        ptr = _ptr
        mm = np.zeros(2, np.int64)
        rc = lib.hm_pack_value_minmax(
            D, ptr(fc_idx_a), ptr(ends), ptr(srcs), ptr(sdts),
            ptr(slut), ptr(soffs), ptr(flut), ptr(foffs), ptr(blut),
            ptr(boffs), ptr(lut_lens), ptr(mm),
        )
        if rc != 0:
            return {}
        dtypes = _pack_wire_dtypes(i16ok, row_dt, kdt, int(mm[0]), int(mm[1]))

        cols: Dict[str, np.ndarray] = {}
        out_ptrs = np.empty(len(COLUMNS), np.int64)
        out_dts = np.empty(len(COLUMNS), np.uint8)
        for ci, name in enumerate(COLUMNS):
            arr = np.empty(Dp * N, dtypes[name])
            cols[name] = arr
            out_ptrs[ci] = arr.__array_interface__["data"][0]
            out_dts[ci] = _DT_CODE[arr.dtype]
        rc = lib.hm_pack_prefix(
            D, Dp, N, ptr(fc_idx_a), ptr(ends), ptr(srcs), ptr(sdts),
            ptr(klut), ptr(koffs), ptr(slut), ptr(soffs), ptr(flut),
            ptr(foffs), ptr(blut), ptr(boffs), ptr(lut_lens),
            ptr(writer_g), ptr(out_ptrs), ptr(out_dts),
        )
        del keep_alive
        if rc != 0:
            return {}
    return {
        name: cols[name].reshape(Dp, N) for name in COLUMNS
    }


# the gate's source planes (hm_native.cpp hm_prefix_gate)
_GATE_PLANES = ("obj_a", "ref_a", "ctr")


def _image_planes(metas):
    """([F, planes] plane pointers, dtype codes) of feeds whose planes
    are slices of one image each, from their plane_meta tables
    (PLANE_NAMES order)."""
    n = len(metas)
    base = np.fromiter((m[0] for m in metas), np.int64, n)
    offs = np.concatenate([m[1] for m in metas]).reshape(n, -1)
    code = np.concatenate([m[2] for m in metas]).reshape(n, -1)
    return base[:, None] + offs, code


def _gate_sources(fcs):
    """The feeds of `fcs` that hm_prefix_gate can read where they lie,
    and their table: (feeds, [F, 3] plane pointers, dtype codes, row
    counts, pred pointers, pred counts). A feed is described from what
    it is, as _native_pack_prefix does: the gate's rows of its
    plane_meta table where every plane is a slice of one image, the
    plane arrays' own pointers otherwise; a rows-backed feed, or one
    whose planes or preds the ABI cannot take, is left to the twin."""
    fcs = list(fcs)
    srcs = np.empty((len(fcs), 3), np.int64)
    sdts = np.empty((len(fcs), 3), np.uint8)

    def own_planes(i, fc) -> bool:
        if fc.planes is None:
            return False
        n = fc.n_rows
        for j, name in enumerate(_GATE_PLANES):
            p = fc.planes[name]
            code = _DT_CODE.get(p.dtype)
            if code is None or p.ndim != 1 or len(p) != n or (
                not p.flags["C_CONTIGUOUS"]
            ):
                return False
            srcs[i, j] = _ptr(p)
            sdts[i, j] = code
        return True

    keep, imaged = [], []
    for i, fc in enumerate(fcs):
        preds = fc.preds
        if preds.dtype != np.int32 or preds.ndim != 2 or (
            preds.shape[1] != 3 or not preds.flags["C_CONTIGUOUS"]
        ):
            continue
        if fc.plane_meta is not None:
            imaged.append(i)
        elif not own_planes(i, fc):
            continue
        keep.append(i)
    if imaged:
        from ..storage.colcache import PLANE_NAMES

        at = [PLANE_NAMES.index(name) for name in _GATE_PLANES]
        ptrs, code = _image_planes([fcs[i].plane_meta for i in imaged])
        srcs[imaged] = ptrs[:, at]
        sdts[imaged] = code[:, at]
    took = [fcs[i] for i in keep]
    F = len(took)
    n_rows = np.fromiter((fc.n_rows for fc in took), np.int64, F)
    n_preds = np.fromiter((len(fc.preds) for fc in took), np.int64, F)
    pred_ptrs = np.fromiter(
        (_ptr(fc.preds) if len(fc.preds) else 0 for fc in took), np.int64, F
    )
    return took, srcs[keep], sdts[keep], n_rows, pred_ptrs, n_preds


def _prefix_single_slab(doc_specs) -> Tuple[bool, int, int]:
    """The gate of the prefix path, per slab and all or nothing: every
    doc is one single-writer feed read from its start (one doc of two
    writers sends its whole slab through the general path). Returns
    (the slab's verdict, feeds the native call judged, feeds the numpy
    twin judged); a feed that carries its latch already counts as
    neither.

    The structural part comes first, over all docs, and reads no plane
    (it collects the latches the feeds carry on its way). The verdicts
    still missing (`_prefix_single_ok`'s, latched on the FeedColumns as
    that function latches them) then come from ONE call of
    hm_prefix_gate with the GIL dropped for every feed it can read
    where it lies, and from the twin for the rest (a rows-backed feed,
    no library, HM_NATIVE_PACK=0). Both stop at the first feed that
    fails: the slab leaves for the general path either way."""
    todo: Dict[int, Any] = {}
    failed = False
    for spec in doc_specs:
        if len(spec) != 1:
            return False, 0, 0
        fc, s, _e = spec[0]
        if s != 0:
            return False, 0, 0
        ok = getattr(fc, "_prefix_single_ok", None)
        if ok is None:
            todo[id(fc)] = fc
        elif not ok:
            failed = True
    if failed or not todo:
        return not failed, 0, 0
    rest = todo.values()
    n_native = 0
    lib = _native_pack_lib()
    if lib is not None:
        took, srcs, sdts, n_rows, pred_ptrs, n_preds = _gate_sources(rest)
        out = np.empty(len(took), np.uint8)
        ptr = _ptr
        if took and lib.hm_prefix_gate(
            len(took), ptr(srcs), ptr(sdts), ptr(n_rows), ptr(pred_ptrs),
            ptr(n_preds), ptr(out),
        ) == 0:
            # the latch, set with the GIL held after the call returned:
            # the same idempotent rebind _prefix_single_ok makes. The
            # call stops at the first feed that fails (the feeds after
            # it read 2), so the judged feeds are the first n_native
            n_native = int(np.count_nonzero(out != 2))
            for fc, ok in zip(took, out[:n_native].tolist()):
                fc._prefix_single_ok = bool(ok)
            if not out[n_native - 1]:
                return False, n_native, 0
            if n_native == len(todo):
                return True, n_native, 0
            for fc in took:
                del todo[id(fc)]
    n_twin = 0
    for fc in rest:
        n_twin += 1
        if not _prefix_single_ok(fc):
            return False, n_native, n_twin
    return True, n_native, n_twin


def _pack_prefix_single(
    doc_specs, n_rows, n_pred, n_docs
) -> ColumnarBatch:
    """Fast pack for the dominant cold-open shape: one single-writer feed
    per doc, whole-prefix windows. Rows are already in causal order (ctr
    ascending) and every reference resolves within the prefix (causal
    lamport property: a referenced op always has a smaller ctr), so this
    path needs ZERO sorts and no drop fixpoint — the general path's two
    M-sized argsorts and composite-key resolution collapse into one
    searchsorted over an already-sorted key.

    The padded-plane emit itself has two bit-identical twins: the C++
    batch entry point (native/src/hm_native.cpp hm_pack_prefix — one
    fused pass per column straight from the feeds' narrow planes into
    preallocated output buffers), and the numpy scatter below (the
    reference, and the fallback when the native layer is absent,
    HM_NATIVE_PACK=0, or a feed is not plane-backed)."""
    D = len(doc_specs)
    Dp = max(n_docs, D) if n_docs is not None else D

    with _stage("prefix.tables"):
        fcs: List[Any] = []
        fc_idx: List[int] = []
        fc_of: Dict[int, int] = {}
        ends = np.zeros(D, np.int64)  # prefix row counts
        for d, spec in enumerate(doc_specs):
            fc, _s, e = spec[0]
            i = fc_of.get(id(fc))
            if i is None:
                i = fc_of[id(fc)] = len(fcs)
                fcs.append(fc)
            fc_idx.append(i)
            ends[d] = fc.window(0, e)[1]

        # -- global tables (same interning as the general path). Feeds
        # instantiated from shared templates carry IDENTICAL local tables,
        # so the per-item interning loop memoizes on the table tuple — the
        # global id sequence is unchanged (a memo hit means every item was
        # already interned, in the same order).
        actor_int = _Interner()
        key_int = _Interner()
        str_int = _Interner()
        float_int = _Interner()
        big_int = _Interner()
        luts = {"k": [], "s": [], "f": [], "b": []}
        writers: List[int] = []
        lut_memo: Dict[Any, np.ndarray] = {}

        def lut_of(kind, interner, items):
            key = (kind, tuple(items))
            got = lut_memo.get(key)
            if got is None:
                got = np.asarray([interner(x) for x in items], np.int64)
                lut_memo[key] = got
            return got

        writer_memo: Dict[Any, int] = {}
        for fc in fcs:
            akey = tuple(fc.actors)
            w = writer_memo.get(akey)
            if w is None:
                for x in fc.actors:
                    actor_int(x)
                w = actor_int(fc.actors[0]) if fc.actors else 0
                writer_memo[akey] = w
            writers.append(w)
            luts["k"].append(lut_of("k", key_int, fc.keys))
            luts["s"].append(lut_of("s", str_int, fc.strings))
            luts["f"].append(lut_of("f", float_int, fc.floats))
            luts["b"].append(lut_of("b", big_int, fc.bigints))
        sorted_actors = sorted(actor_int.items)
        rank_of = {name: i for i, name in enumerate(sorted_actors)}
        arank = np.asarray(
            [rank_of[a] for a in actor_int.items], np.int64
        )
        writer_g = (
            arank[np.asarray(writers, np.int64)]
            if writers
            else np.zeros(0, np.int64)
        )

        M = int(ends.sum())
    if M == 0:
        N = n_rows if n_rows is not None else 1
        P = n_pred if n_pred is not None else 1
        return _empty_batch(
            Dp, N, P, sorted_actors, key_int, str_int, float_int, big_int
        )

    fc_idx_a = np.asarray(fc_idx, np.int64)

    from ..storage.colcache import OBJ_ROOT, REF_HEAD, REF_NONE

    with _stage("prefix.preds"):
        # -- preds ------------------------------------------------------
        pr_docs_l: List[int] = []
        pr_cnt_l: List[int] = []
        pr_rows: List[np.ndarray] = []
        for d in range(D):
            fc = fcs[fc_idx[d]]
            n_pr = len(fc.preds)
            if not n_pr:
                continue
            e = int(ends[d])
            phi = (
                n_pr  # whole-prefix window: every pred src is inside it
                if e >= fc.n_rows
                else int(np.searchsorted(fc.preds[:, 0], e, side="left"))
            )
            if phi:
                pr_rows.append(fc.preds[:phi])
                pr_docs_l.append(d)
                pr_cnt_l.append(phi)
        if pr_rows:
            PR = np.concatenate(pr_rows, axis=0)
            pr_doc = np.repeat(
                np.asarray(pr_docs_l, np.int64), np.asarray(pr_cnt_l, np.int64)
            )
            p_src_row = PR[:, 0].astype(np.int64)  # feed row == doc row
            p_tgt_row = PR[:, 1].astype(np.int64) - 1  # dense ctr -> row
            pred_counts = np.bincount(pr_doc, minlength=Dp).astype(np.int64)
            pred_starts = np.zeros(Dp + 1, np.int64)
            np.cumsum(pred_counts, out=pred_starts[1:])
            p_pos = (
                np.arange(len(pr_doc), dtype=np.int64) - pred_starts[pr_doc]
            )
        else:
            pred_counts = np.zeros(Dp, np.int64)
            p_src_row = p_tgt_row = p_pos = pr_doc = np.zeros(0, np.int64)

        # -- bucket shapes ----------------------------------------------
        max_ops = int(ends.max(initial=0))
        max_preds = int(pred_counts.max(initial=0))
        N = n_rows if n_rows is not None else _round_up(max(max_ops, 1))
        P = n_pred if n_pred is not None else _round_up(max(max_preds, 1))
        if max_ops > N or max_preds > P:
            raise ValueError(
                f"doc exceeds bucket: ops {max_ops}>{N} "
                f"or preds {max_preds}>{P}"
            )

    # wire dtypes are a function of the bucket + value ranges so native
    # and numpy twins allocate identically (host_args passes the planes
    # through copy-free): everything row-indexed fits int16 when N < 32k
    # — the common case — and flags planes fit uint8
    i16ok = N < 2**15
    row_dt = np.int16 if i16ok else np.int32
    kdt = np.int16 if len(key_int.items) < 2**15 else np.int32

    def flat_lut(kind):
        offs = np.zeros(len(fcs) + 1, np.int64)
        for i, l in enumerate(luts[kind]):
            offs[i + 1] = offs[i] + len(l)
        flat = (
            np.concatenate(luts[kind])
            if any(len(l) for l in luts[kind])
            else np.zeros(1, np.int64)
        )
        return flat, offs

    use_planes = all(fc.planes is not None for fc in fcs)
    native_lib = _native_pack_lib() if use_planes else None
    cols: Dict[str, np.ndarray] = {}

    if native_lib is not None:
        cols = _native_pack_prefix(
            native_lib, fcs, fc_idx_a, ends, writer_g, flat_lut,
            D, Dp, N, i16ok, row_dt, kdt,
        )

    if not cols:  # numpy twin (fallback, and the fuzz reference)
        with _stage("prefix.native", native=0):
            doc_col = np.repeat(np.arange(D, dtype=np.int64), ends)
            doc_starts = np.zeros(D + 1, np.int64)
            np.cumsum(ends, out=doc_starts[1:])
            pos = (
                np.arange(M, dtype=np.int64) - doc_starts[doc_col]
            ).astype(np.int32)
            flat_idx = doc_col * N + pos

            # column sources: v3 plane-backed feeds serve each column as a
            # contiguous narrow array (concat promotes mixed widths); v2
            # feeds fall back to strided slices of the dense row matrix.
            if use_planes:
                def col(name):
                    return np.concatenate(
                        [
                            fcs[fc_idx[d]].plane(name)[: ends[d]]
                            for d in range(D)
                        ]
                    )
            else:
                R = np.concatenate(
                    [
                        fcs[fc_idx[d]].ensure_rows()[: ends[d]]
                        for d in range(D)
                    ],
                    axis=0,
                )
                from ..storage.colcache import PLANE_NAMES

                def col(name):
                    return R[:, PLANE_NAMES.index(name)]

            # -- derived columns, computed in (near-)wire dtypes ------------
            obj_a = col("obj_a")
            obj_row = np.where(
                obj_a == 0, col("obj_ctr").astype(row_dt) - 1, row_dt(OBJ_ROOT)
            )
            del obj_a
            ref_a = col("ref_a")
            ref_row = np.where(
                ref_a == 0,
                col("ref_ctr").astype(row_dt) - 1,
                np.where(
                    ref_a == -2, row_dt(REF_HEAD), row_dt(REF_NONE)
                ).astype(row_dt),
            )
            del ref_a

            # -- key/value global remap -------------------------------------
            klut, koffs = flat_lut("k")
            key_l = col("key").astype(np.int64)
            off_doc = np.repeat(koffs[fc_idx_a], ends)
            safe = np.minimum(np.maximum(off_doc + key_l, 0), len(klut) - 1)
            key_g = np.where(key_l >= 0, klut[safe].astype(kdt), kdt(-1))
            del safe, off_doc, key_l
            vkind = col("vkind")
            value_g = col("value").astype(np.int64)
            from ..storage.colcache import VK_BIGINT, VK_FLOAT, VK_STR

            for code, kind in (
                (VK_STR, "s"), (VK_FLOAT, "f"), (VK_BIGINT, "b")
            ):
                m = vkind == code
                if m.any():
                    lut, offs = flat_lut(kind)
                    oc = np.repeat(offs[fc_idx_a], ends)
                    value_g[m] = lut[oc[m] + value_g[m]]

            # -- scatter into padded [Dp, N] --------------------------------
            defaults = {"action": PAD, "obj": -1, "key": -1, "ref": -3}
            sources = {
                "action": col("action"),
                "actor": np.repeat(writer_g[fc_idx_a], ends),
                "ctr": col("ctr"), "seq": col("seq"), "obj": obj_row,
                "key": key_g, "ref": ref_row, "insert": col("insert"),
                "vkind": vkind, "value": value_g, "dt": col("dt"),
            }
            vmin = int(value_g.min(initial=0))
            vmax = int(value_g.max(initial=0))
            dtypes = _pack_wire_dtypes(i16ok, row_dt, kdt, vmin, vmax)
            for name in COLUMNS:
                flat = np.full(Dp * N, defaults.get(name, 0), dtypes[name])
                flat[flat_idx] = sources[name]
                cols[name] = flat.reshape(Dp, N)
    with _stage("prefix.emit"):
        pdt = np.int16 if i16ok else np.int32
        psrc = np.full(Dp * P, -1, pdt)
        ptgt = np.full(Dp * P, -1, pdt)
        if len(p_src_row):
            pidx = pr_doc * P + p_pos
            psrc[pidx] = p_src_row
            ptgt[pidx] = p_tgt_row

        doc_actors = np.full((Dp, 1), -1, np.int32)
        doc_actors[:D, 0] = writer_g.astype(np.int32)[fc_idx_a]
        n_ops = np.zeros(Dp, np.int32)
        n_ops[:D] = ends
        batch = ColumnarBatch(
            cols=cols,
            psrc=psrc.reshape(Dp, P),
            ptgt=ptgt.reshape(Dp, P),
            n_ops=n_ops,
            actors=list(sorted_actors),
            keys=list(key_int.items),
            strings=list(str_int.items),
            floats=list(float_int.items),
            bigints=list(big_int.items),
            doc_actors=doc_actors,
        )
        batch.slot = np.zeros((Dp, N), np.int8)  # single writer: slot 0
    return batch


# docs packed by each path, over the process's life (tools/top.py), the
# general pack's feeds by who gathered their rows, and the gate's feeds
# by who judged them (a feed judged once keeps its latch)
_M_PACK_PREFIX = telemetry.counter("pipeline.pack_prefix_docs")
_M_PACK_GENERAL = telemetry.counter("pipeline.pack_general_docs")
_M_GATHER_NATIVE = telemetry.counter("pipeline.pack_gather_native_feeds")
_M_GATHER_TWIN = telemetry.counter("pipeline.pack_gather_twin_feeds")
_M_GATE_NATIVE = telemetry.counter("pipeline.pack_gate_native_feeds")
_M_GATE_TWIN = telemetry.counter("pipeline.pack_gate_twin_feeds")


def _stage(name: str, **tags: Any):
    """A stage of a pack path: a span below `pipeline.pack.general`
    (`tables`, `gather`, ...) or, with the path's name in front, below
    `pipeline.pack.prefix` (`prefix.tables`, ...). A path's stages
    follow one another and never nest, so their seconds add up to the
    path's span."""
    return telemetry.span("pipeline.pack." + name, "pipeline", **tags)


def pack_docs_columns(
    doc_specs: Sequence[Sequence[Tuple[Any, int, float]]],
    n_rows: Optional[int] = None,
    n_pred: Optional[int] = None,
    n_docs: Optional[int] = None,
) -> ColumnarBatch:
    """Pack documents from columnar feed windows.

    doc_specs[d] = [(FeedColumns, start_seq, end_seq), ...] — one entry
    per actor feed in the doc's cursor; the window is (start_seq,
    end_seq] like Actor.changes_in_window. Produces a ColumnarBatch
    equivalent (same device-kernel results and decoded patches) to
    `pack_docs` over the same histories.

    `n_docs` pads the doc axis with empty (all-PAD) documents — slab
    loaders bucket the batch shape so every slab reuses one compiled
    kernel executable.

    Single-writer whole-prefix loads (the dominant cold-open shape)
    dispatch to a no-sort fast path; anything else takes the general
    sorted-composite path below.
    """
    n = len(doc_specs)
    with _stage("gate", docs=n) as gate:
        prefix, n_native, n_twin = _prefix_single_slab(doc_specs)
        gate.note(prefix=int(prefix), native=n_native)
    _M_GATE_NATIVE.add(n_native)
    _M_GATE_TWIN.add(n_twin)
    if prefix:
        _M_PACK_PREFIX.add(n)
        with telemetry.span("pipeline.pack.prefix", "pipeline", docs=n):
            batch = _pack_prefix_single(
                doc_specs, n_rows, n_pred, n_docs
            )
        batch.packed_by = "prefix"
    else:
        _M_PACK_GENERAL.add(n)
        with telemetry.span(
            "pipeline.pack.general", "pipeline", docs=n
        ) as sp:
            batch = _pack_general(doc_specs, n_rows, n_pred, n_docs, sp)
        batch.packed_by = "general"
    batch.gate_feeds = (n_native, n_twin)
    return batch


# what the general pack's gather hands the stages after it: the
# sidecar's planes but `flags` (PLANE_NAMES order: the source plane order
# of hm_native.cpp hm_pack_gather), the writer and doc of every row, and
# the pred rows. A column that enters a composite key or indexes another
# is int64; the rest are only emitted, as the int32 they are emitted in.
_GATHER_PLANES = (
    "action", "ctr", "seq", "start_op", "obj_ctr", "obj_a", "key",
    "ref_ctr", "ref_a", "insert", "vkind", "value", "dt",
)
_GATHER_COLS = _GATHER_PLANES + (
    "actor", "doc", "pr_src", "pr_tgt_ctr", "pr_tgt_a", "pr_doc",
)
_GATHER_I32 = frozenset(
    ("action", "seq", "key", "insert", "vkind", "value", "dt")
)
_DT_ITEMSIZE = np.asarray(
    [dt.itemsize for dt in sorted(_DT_CODE, key=_DT_CODE.get)], np.int64
)


def _starts(counts: np.ndarray) -> np.ndarray:
    """[len + 1] exclusive running sum of `counts`."""
    at = np.zeros(len(counts) + 1, np.int64)
    np.cumsum(counts, out=at[1:])
    return at


def _ranges(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """concatenate([arange(s, s + c) for s, c in ...]) without the loop."""
    at = _starts(counts)
    return np.repeat(starts - at[:-1], counts) + np.arange(
        int(at[-1]), dtype=np.int64
    )


def _gather_windows(doc_specs, fcs, fc_of):
    """Every window of a slab as one [W, 6] int64 table (feed, doc, lo,
    rows, pred lo, preds), the slab's pred rows feed after feed (what
    the pred ranges index), and every feed's row count. A window that
    row_ends puts outside its feed's rows (a corrupt sidecar) raises
    here, before any loop reads a plane.

    [lo, lo + rows) is FeedColumns.window for all windows at once:
    changes (start_seq, end_seq] clamped to ok_prefix_len, through
    row_ends; the pred window is the feed's preds whose src row lies in
    it (src is nondecreasing within a feed). What Python does per feed
    is what walking doc_specs forces; a feed listed twice in one doc
    gives one window."""
    w_fc: List[int] = []
    w_doc: List[int] = []
    w_s: List[int] = []
    w_e: List[float] = []
    for d, spec in enumerate(doc_specs):
        seen = set()
        for fc, s, e in spec:
            fci = fc_of[id(fc)]
            if fci in seen:
                continue
            seen.add(fci)
            w_fc.append(fci)
            w_doc.append(d)
            w_s.append(s)
            w_e.append(e)
    F = len(fcs)
    if not F:
        none = np.zeros(0, np.int64)
        return none.reshape(0, 6), np.zeros((0, 3), np.int32), none
    # the feeds' fields in one C-level pass each (no bytecode per feed:
    # the loading thread's Python competes for the GIL meanwhile)
    n_changes, ok_prefix, ends, preds = zip(
        *map(attrgetter("n_changes", "ok_prefix_len", "row_ends", "preds"),
             fcs)
    )
    wf = np.asarray(w_fc, np.int64)
    n_changes = np.asarray(n_changes, np.int64)[wf]
    e = np.minimum(
        np.asarray(w_e, np.float64), np.asarray(ok_prefix, np.float64)[wf]
    ).astype(np.int64)
    s_at = np.minimum(np.asarray(w_s, np.int64), n_changes)
    e_at = np.clip(e, 0, n_changes)
    ends_at = _starts(np.fromiter(map(len, ends), np.int64, F))
    if np.any(
        (s_at < 0) | (np.maximum(s_at, e_at) >= np.diff(ends_at)[wf])
    ):
        raise IndexError("window outside its feed's row_ends")
    ends_all = np.concatenate(ends).astype(np.int64, copy=False)
    lo = ends_all[ends_at[wf] + s_at]
    hi = np.maximum(np.where(e > 0, ends_all[ends_at[wf] + e_at], 0), lo)
    feed_rows = np.fromiter(map(attrgetter("n_rows"), fcs), np.int64, F)
    if np.any((lo < 0) | (hi > feed_rows[wf])):
        raise ValueError("row_ends overruns the feed's planes")

    n_preds = np.fromiter(map(len, preds), np.int64, F)
    if n_preds.any():
        preds_all = np.ascontiguousarray(np.concatenate(preds), np.int32)
        # one search for all windows: (feed, src) is sorted as a whole
        key = np.repeat(np.arange(F, dtype=np.int64) << 32, n_preds)
        key += preds_all[:, 0]
        plo = np.searchsorted(key, (wf << 32) + lo)
        phi = np.searchsorted(key, (wf << 32) + hi)
    else:
        preds_all = np.zeros((0, 3), np.int32)
        plo = phi = np.zeros(len(wf), np.int64)
    win = np.stack(
        [wf, np.asarray(w_doc, np.int64), lo, hi - lo, plo, phi - plo],
        axis=1,
    )
    return win, preds_all, feed_rows


def _gather_sources(fcs):
    """([F, 13] pointers, dtype codes, byte strides) of the feeds'
    source planes for hm_pack_gather, each feed described from
    what it is: the rows of its plane_meta table where every plane is a
    slice of one image, the plane arrays' own pointers otherwise, and
    for a rows-backed feed thirteen int32 columns of its row matrix.
    None when a plane cannot be described to the native ABI."""
    F, NP = len(fcs), len(_GATHER_PLANES)
    srcs = np.empty((F, NP), np.int64)
    sdts = np.empty((F, NP), np.uint8)
    strides = np.empty((F, NP), np.int64)
    imaged = [i for i, fc in enumerate(fcs) if fc.plane_meta is not None]
    if imaged:
        ptrs, code = _image_planes([fcs[i].plane_meta for i in imaged])
        code = code[:, :NP]
        srcs[imaged] = ptrs[:, :NP]
        sdts[imaged] = code
        strides[imaged] = _DT_ITEMSIZE[code]
    col4 = 4 * np.arange(NP, dtype=np.int64)
    for i, fc in enumerate(fcs):
        if fc.plane_meta is not None:
            continue
        if fc.planes is not None:
            n = fc.n_rows
            for j, name in enumerate(_GATHER_PLANES):
                p = fc.planes[name]
                code = _DT_CODE.get(p.dtype)
                if code is None or p.ndim != 1 or len(p) != n:
                    return None
                srcs[i, j] = p.__array_interface__["data"][0]
                sdts[i, j] = code
                strides[i, j] = p.strides[0]
            continue
        rows = fc.rows
        if rows is None or rows.dtype != np.int32 or rows.ndim != 2 or (
            rows.shape[1] < NP or (len(rows) and rows.strides[1] != 4)
        ):
            return None
        srcs[i] = rows.__array_interface__["data"][0] + col4
        sdts[i] = _DT_CODE[rows.dtype]
        strides[i] = rows.strides[0]
    return srcs, sdts, strides


def _native_gather(lib, fcs, win, preds_all, tabs, writer_g):
    """The gather through the C++ entry point (hm_native.cpp
    hm_pack_gather, GIL dropped): each source plane read in the dtype
    it is stored in, each column written once. {} when a plane cannot
    be described to the native ABI (the caller takes the numpy twin)."""
    described = _gather_sources(fcs)
    if described is None:
        return {}
    srcs, sdts, strides = described
    ptr = _ptr
    tab = np.asarray(
        [(ptr(lut), ptr(offs), len(lut)) for lut, offs in tabs], np.int64
    )
    writer_g = np.ascontiguousarray(writer_g, np.int64)
    n_rows, n_preds = int(win[:, 3].sum()), int(win[:, 5].sum())
    g = {
        name: np.empty(
            n_preds if name.startswith("pr_") else n_rows,
            np.int32 if name in _GATHER_I32 else np.int64,
        )
        for name in _GATHER_COLS
    }
    outs = np.asarray([ptr(g[name]) for name in _GATHER_COLS], np.int64)
    rc = lib.hm_pack_gather(
        len(win), ptr(win), ptr(srcs), ptr(sdts), ptr(strides), ptr(tab),
        ptr(writer_g), ptr(preds_all), ptr(outs),
    )
    return g if rc == 0 else {}


def _gather_twin(fcs, feed_rows, win, preds_all, tabs, writer_g):
    """The gather in numpy, column by column over the feeds' planes:
    the reference of hm_pack_gather, and what runs without the native
    library, under HM_NATIVE_PACK=0, or for a slab the native ABI
    cannot take."""
    (alut, aoffs), (klut, koffs), *value_tabs = tabs
    wf, wd, lo, cnt, plo, pcnt = win.T
    # the windows' rows within the feeds' planes laid end to end
    src = _ranges(_starts(feed_rows)[wf] + lo, cnt)

    def col(name):
        flat = np.concatenate([fc.plane(name) for fc in fcs])
        return flat[src].astype(np.int64)

    def lut_where(cond, lut, idx, alt):
        # np.where evaluates both branches: rows where cond is False
        # carry a sentinel local index (e.g. -1), and a feed whose table
        # is empty but sits at the end of the flat LUT would index one
        # past the end — clamp before gathering, select after.
        safe = np.minimum(np.maximum(idx, 0), len(lut) - 1)
        return np.where(cond, lut[safe], alt)

    g = {name: col(name) for name in _GATHER_PLANES}
    aoff_col = np.repeat(aoffs[wf], cnt)
    for name in ("obj_a", "ref_a"):  # sentinels (< 0) pass
        a_l = g[name]
        g[name] = lut_where(a_l >= 0, alut, aoff_col + a_l, a_l)
    key_l = g["key"]
    g["key"] = lut_where(
        key_l >= 0, klut, np.repeat(koffs[wf], cnt) + key_l, -1
    )
    value = g["value"]
    for code, (lut, offs) in zip((VK_STR, VK_FLOAT, VK_BIGINT), value_tabs):
        m = g["vkind"] == code
        if m.any():
            value[m] = lut[np.repeat(offs[wf], cnt)[m] + value[m]]
    for name in _GATHER_I32:
        g[name] = g[name].astype(np.int32)
    g["actor"] = np.repeat(writer_g[wf], cnt)
    g["doc"] = np.repeat(wd, cnt)

    # preds: src rebased from the feed's rows to the flat row index
    pr = preds_all[_ranges(plo, pcnt)].astype(np.int64)
    g["pr_src"] = pr[:, 0] + np.repeat(_starts(cnt)[:-1] - lo, pcnt)
    g["pr_tgt_ctr"] = pr[:, 1]
    g["pr_tgt_a"] = alut[np.repeat(aoffs[wf], pcnt) + pr[:, 2]]
    g["pr_doc"] = np.repeat(wd, pcnt)
    return g


def _pack_general(
    doc_specs, n_rows, n_pred, n_docs, span
) -> ColumnarBatch:
    """The general pack: any number of feeds a doc, any windows. Rows
    are gathered straight from the feeds' planes in one pass (a native
    entry and its numpy twin), references and pred targets resolved by
    a composite (doc, counter, actor) key over one M-sized argsort, ops
    whose container or referenced element is outside the window dropped
    to a fixpoint, and the rows laid out in causal order by a second
    argsort. `span` (pipeline.pack.general) takes the slab's feeds and
    rows as tags once they are known."""
    from ..storage.colcache import OBJ_ROOT, REF_HEAD, REF_NONE

    D = len(doc_specs)
    Dp = max(n_docs, D) if n_docs is not None else D

    # -- global tables + per-feed LUTs ---------------------------------
    with _stage("tables"):
        fcs: List[Any] = []
        fc_of: Dict[int, int] = {}
        for spec in doc_specs:
            for fc, _s, _e in spec:
                if id(fc) not in fc_of:
                    fc_of[id(fc)] = len(fcs)
                    fcs.append(fc)

        actor_int = _Interner()
        key_int = _Interner()
        str_int = _Interner()
        float_int = _Interner()
        big_int = _Interner()
        luts = {"a": [], "k": [], "s": [], "f": [], "b": []}
        for fc in fcs:
            luts["a"].append(
                np.asarray([actor_int(x) for x in fc.actors], np.int64)
            )
            luts["k"].append(
                np.asarray([key_int(x) for x in fc.keys], np.int64)
            )
            luts["s"].append(
                np.asarray([str_int(x) for x in fc.strings], np.int64)
            )
            luts["f"].append(
                np.asarray([float_int(x) for x in fc.floats], np.int64)
            )
            luts["b"].append(
                np.asarray([big_int(x) for x in fc.bigints], np.int64)
            )

        # actor index order must equal actor string sort order (device
        # tie-break parity — same remap as pack_docs)
        sorted_actors = sorted(actor_int.items)
        rank_of = {name: i for i, name in enumerate(sorted_actors)}
        arank = np.asarray(
            [rank_of[a] for a in actor_int.items], np.int64
        )
        luts["a"] = [
            arank[l] if len(l) else l for l in luts["a"]
        ]

        def _flat_lut(kind: str) -> Tuple[np.ndarray, np.ndarray]:
            offs = np.zeros(len(fcs) + 1, np.int64)
            for i, l in enumerate(luts[kind]):
                offs[i + 1] = offs[i] + len(l)
            flat = (
                np.concatenate(luts[kind])
                if any(len(l) for l in luts[kind])
                else np.zeros(1, np.int64)
            )
            return flat, offs

        alut, aoffs = _flat_lut("a")
        klut, koffs = _flat_lut("k")
        slut, soffs = _flat_lut("s")
        flut, foffs = _flat_lut("f")
        blut, boffs = _flat_lut("b")

    # -- gather window slices ------------------------------------------
    with _stage("gather") as gather_span:
        win, preds_all, feed_rows = _gather_windows(doc_specs, fcs, fc_of)
        M = int(win[:, 3].sum())
        A = max(1, len(sorted_actors))
        if M == 0:
            N = n_rows if n_rows is not None else 1
            P = n_pred if n_pred is not None else 1
            return _empty_batch(
                Dp, N, P, sorted_actors, key_int, str_int, float_int, big_int
            )
        tabs = (
            (alut, aoffs), (klut, koffs), (slut, soffs), (flut, foffs),
            (blut, boffs),
        )
        # writer (op actor) = feed-local actor 0
        if not np.all(np.diff(aoffs)):
            raise IndexError("a feed's actor table lacks its writer")
        writer_g = alut[aoffs[:-1]]
        native_lib = _native_pack_lib()
        g = (
            _native_gather(native_lib, fcs, win, preds_all, tabs, writer_g)
            if native_lib is not None
            else {}
        )
        n_native = len(fcs) if g else 0
        if not g:  # numpy twin (fallback, and the fuzz reference)
            g = _gather_twin(
                fcs, feed_rows, win, preds_all, tabs, writer_g
            )
        _M_GATHER_NATIVE.add(n_native)
        _M_GATHER_TWIN.add(len(fcs) - n_native)
        gather_span.note(native=n_native)
        (
            action, ctr, seqc, start_op, obj_ctr, obj_a_g, key_g, ref_ctr,
            ref_a_g, insert, vkind, value_g, dt, actor_g, doc_col,
            pr_src, pr_tgt_ctr, pr_tgt_a, pr_doc,
        ) = (g[name] for name in _GATHER_COLS)

    span.note(feeds=len(fcs), rows=M)

    # -- composite key bit budget --------------------------------------
    ab = max(1, int(A - 1).bit_length())
    max_ctr = int(
        max(ctr.max(initial=0), obj_ctr.max(initial=0),
            ref_ctr.max(initial=0),
            int(pr_tgt_ctr.max(initial=0)))
    )
    cb = max(1, max_ctr.bit_length())
    db = max(1, int(Dp - 1).bit_length())
    if db + cb + ab > 62:
        raise ValueError(
            f"composite key overflow: docs={Dp} ctr={max_ctr} actors={A}"
        )

    def _rowkey(doc, c, a):
        return (doc << (cb + ab)) | (c << ab) | a

    need_obj = obj_a_g >= 0  # sentinels pass the actor LUT unchanged
    need_ref = ref_a_g >= 0

    def _resolve(rk_sorted, order_rk, q_doc, q_ctr, q_a):
        q = _rowkey(q_doc, q_ctr, np.maximum(q_a, 0))
        pos = np.searchsorted(rk_sorted, q)
        pos_c = np.minimum(pos, len(rk_sorted) - 1)
        hit = rk_sorted[pos_c] == q
        return order_rk[pos_c], hit

    with _stage("sort"):
        rk = _rowkey(doc_col, ctr, actor_g)
        order_rk = np.argsort(rk)
        rk_sorted = rk[order_rk]
    # validity fixpoint: an op drops if its container or referenced
    # element is absent from the packed window (matches _pack_one's
    # incremental row_of misses, including the cascade)
    with _stage("resolve"):
        obj_tgt, obj_hit = _resolve(
            rk_sorted, order_rk, doc_col, obj_ctr, obj_a_g
        )
        ref_tgt, ref_hit = _resolve(
            rk_sorted, order_rk, doc_col, ref_ctr, ref_a_g
        )
        valid = np.ones(M, bool)
        while True:
            bad = (
                (need_obj & (~obj_hit | ~valid[obj_tgt]))
                | (need_ref & (~ref_hit | ~valid[ref_tgt]))
            ) & valid
            if not bad.any():
                break
            valid[bad] = False

    if not valid.all():
        with _stage("resolve"):
            keep = valid
            (
                action, ctr, seqc, start_op, obj_ctr, obj_a_g, key_g,
                ref_ctr, ref_a_g, insert, vkind, value_g, dt, actor_g,
                doc_col, need_obj, need_ref,
            ) = (
                x[keep]
                for x in (
                    action, ctr, seqc, start_op, obj_ctr, obj_a_g, key_g,
                    ref_ctr, ref_a_g, insert, vkind, value_g, dt, actor_g,
                    doc_col, need_obj, need_ref,
                )
            )
            # remap pred srcs through the compaction
            new_idx = np.cumsum(valid) - 1
            if len(pr_src):
                pk = valid[pr_src]
                pr_src = new_idx[pr_src[pk]]
                pr_tgt_ctr = pr_tgt_ctr[pk]
                pr_tgt_a = pr_tgt_a[pk]
                pr_doc = pr_doc[pk]
            M = len(action)
            if M == 0:
                N = n_rows if n_rows is not None else 1
                P = n_pred if n_pred is not None else 1
                return _empty_batch(
                    Dp, N, P, sorted_actors, key_int, str_int, float_int,
                    big_int,
                )
        with _stage("sort"):
            rk = _rowkey(doc_col, ctr, actor_g)
            order_rk = np.argsort(rk)
            rk_sorted = rk[order_rk]
        with _stage("resolve"):
            obj_tgt, obj_hit = _resolve(
                rk_sorted, order_rk, doc_col, obj_ctr, obj_a_g
            )
            ref_tgt, ref_hit = _resolve(
                rk_sorted, order_rk, doc_col, ref_ctr, ref_a_g
            )

    # -- causal order + within-doc positions ---------------------------
    with _stage("sort"):
        sort_key = _rowkey(doc_col, start_op, actor_g)
        perm = np.argsort(sort_key, kind="stable")
        inv = np.empty(M, np.int64)
        inv[perm] = np.arange(M, dtype=np.int64)
        doc_counts = np.bincount(doc_col, minlength=Dp).astype(np.int64)
        doc_starts = np.zeros(Dp + 1, np.int64)
        np.cumsum(doc_counts, out=doc_starts[1:])
        pos = inv - doc_starts[doc_col]

    with _stage("resolve"):
        obj_row = np.where(need_obj, pos[obj_tgt], OBJ_ROOT)
        ref_row = np.where(
            need_ref,
            pos[ref_tgt],
            np.where(
                ref_a_l_compact(ref_a_g) == REF_HEAD, REF_HEAD, REF_NONE
            ),
        )

        # -- pred edges -> per-doc rows --------------------------------
        if len(pr_src):
            tgt_row, tgt_hit = _resolve(
                rk_sorted, order_rk, pr_doc, pr_tgt_ctr, pr_tgt_a
            )
            pk = tgt_hit
            pr_doc = pr_doc[pk]
            p_src_row = pos[pr_src[pk]]
            p_tgt_row = pos[tgt_row[pk]]
            pred_counts = np.bincount(pr_doc, minlength=Dp).astype(np.int64)
            pred_starts = np.zeros(Dp + 1, np.int64)
            np.cumsum(pred_counts, out=pred_starts[1:])
            # pr_doc is nondecreasing (windows gathered doc-by-doc; the
            # validity compaction preserves order)
            p_pos = (
                np.arange(len(pr_doc), dtype=np.int64) - pred_starts[pr_doc]
            )
        else:
            pred_counts = np.zeros(Dp, np.int64)
            p_src_row = p_tgt_row = p_pos = pr_doc = np.zeros(0, np.int64)

    # -- scatter into padded [D, N] ------------------------------------
    with _stage("emit"):
        max_ops = int(doc_counts.max(initial=0))
        max_preds = int(pred_counts.max(initial=0))
        N = n_rows if n_rows is not None else _round_up(max(max_ops, 1))
        P = n_pred if n_pred is not None else _round_up(max(max_preds, 1))
        if max_ops > N or max_preds > P:
            raise ValueError(
                f"doc exceeds bucket: ops {max_ops}>{N} or "
                f"preds {max_preds}>{P}"
            )

        flat_idx = doc_col * N + pos
        cols: Dict[str, np.ndarray] = {}
        defaults = {
            "action": PAD, "obj": -1, "key": -1, "ref": -3,
        }
        sources = {
            "action": action, "actor": actor_g, "ctr": ctr, "seq": seqc,
            "obj": obj_row, "key": key_g, "ref": ref_row, "insert": insert,
            "vkind": vkind, "value": value_g, "dt": dt,
        }
        for name in COLUMNS:
            flat = np.full(Dp * N, defaults.get(name, 0), np.int32)
            flat[flat_idx] = sources[name].astype(np.int32)
            cols[name] = flat.reshape(Dp, N)
        psrc = np.full(Dp * P, -1, np.int32)
        ptgt = np.full(Dp * P, -1, np.int32)
        if len(p_src_row):
            pidx = pr_doc * P + p_pos
            psrc[pidx] = p_src_row.astype(np.int32)
            ptgt[pidx] = p_tgt_row.astype(np.int32)

        # per-doc local actor map (ascending == string sort order: actor_g
        # indexes sorted_actors)
        doc_actors = doc_actor_map_from_pairs(
            np.unique(doc_col * np.int64(A) + actor_g), A, Dp
        )

        return ColumnarBatch(
            cols=cols,
            psrc=psrc.reshape(Dp, P),
            ptgt=ptgt.reshape(Dp, P),
            n_ops=doc_counts.astype(np.int32),
            actors=list(sorted_actors),
            keys=list(key_int.items),
            strings=list(str_int.items),
            floats=list(float_int.items),
            bigints=list(big_int.items),
            doc_actors=doc_actors,
            gather_feeds=(n_native, len(fcs) - n_native),
        )


def ref_a_l_compact(ref_a_g: np.ndarray) -> np.ndarray:
    """Sentinels (-2 HEAD / -3 none) pass through the global remap
    unchanged; this just names that fact at the use site."""
    return ref_a_g


def _empty_batch(
    D: int, N: int, P: int, actors, key_int, str_int, float_int, big_int
) -> ColumnarBatch:
    cols = {name: np.zeros((D, N), np.int32) for name in COLUMNS}
    cols["action"][:] = PAD
    cols["obj"][:] = -1
    cols["key"][:] = -1
    cols["ref"][:] = -3
    return ColumnarBatch(
        cols=cols,
        psrc=np.full((D, P), -1, np.int32),
        ptgt=np.full((D, P), -1, np.int32),
        n_ops=np.zeros(D, np.int32),
        actors=list(actors),
        keys=list(key_int.items),
        strings=list(str_int.items),
        floats=list(float_int.items),
        bigints=list(big_int.items),
        doc_actors=np.full((D, 1), -1, np.int32),
    )


# ---------------------------------------------------------------------------
# appendable per-doc packed columns (the live apply engine's cache)


class LiveColumns:
    """ONE document's packed op history, appendable in place.

    The live apply engine (backend/live.py) keeps each hot doc's packed
    columns host-pinned: incoming changes append rows at the tail (no
    feed IO, no repack of the prefix), and each tick stacks dirty docs'
    columns into a padded [D, N] batch for the jitted kernels.

    Row encoding is `_pack_one`'s, with persistent state: `row_of`
    resolves obj/ref/pred references across appends, the interners are
    per-DOC (the kernels never read table *contents*, only group by
    index — so no batch-global remap is ever needed), and unresolvable
    ops drop exactly as `_pack_one` drops them (the OpSet tolerance).

    Row order is arrival order, NOT the causal linear order `pack_docs`
    emits. The kernels are row-order-independent (winners come from
    lexsorts over (group, lamport) keys, RGA order from explicit parent
    pointers), so appending at the tail is always sound; only consumers
    that assume causally-sorted rows (none on the live path) may not
    read these columns.

    Actor column values are intern indices; `slots()` maps them through
    the string-sort rank LUT the kernels tie-break by (recomputed only
    when a new actor joins).
    """

    _INIT_CAP = 64

    def __init__(self) -> None:
        self.n = 0
        self.n_preds = 0
        self.cols: Dict[str, np.ndarray] = {
            name: np.full(
                self._INIT_CAP, _COL_DEFAULTS.get(name, 0), np.int32
            )
            for name in COLUMNS
        }
        self.psrc = np.full(self._INIT_CAP, -1, np.int32)
        self.ptgt = np.full(self._INIT_CAP, -1, np.int32)
        self.actors = _Interner()
        self.keys = _Interner()
        self.strings = _Interner()
        self.floats = _Interner()
        self.bigints = _Interner()
        self.row_of: Dict[OpId, int] = {}
        self.opids: List[OpId] = []  # row -> OpId (append-only, so the
        # per-tick decoders reuse it instead of rebuilding O(n) objects)
        self._rank_lut: Optional[np.ndarray] = None

    @classmethod
    def from_batch(cls, batch: ColumnarBatch, d: int = 0) -> "LiveColumns":
        """Adopt one doc's rows out of a packed batch (bulk-loaded docs
        enter the live engine through this — their history is already
        packed, so adoption is a column copy plus the row_of index)."""
        lv = cls()
        n = int(batch.n_ops[d])
        lv._reserve_rows(n)
        for name in COLUMNS:
            lv.cols[name][:n] = batch.cols[name][d, :n]
        lv.n = n
        keep = np.asarray(batch.psrc[d]) >= 0
        srcs = np.asarray(batch.psrc[d])[keep].astype(np.int32)
        tgts = np.asarray(batch.ptgt[d])[keep].astype(np.int32)
        lv._reserve_preds(len(srcs))
        lv.psrc[: len(srcs)] = srcs
        lv.ptgt[: len(tgts)] = tgts
        lv.n_preds = len(srcs)
        for a in batch.actors:
            lv.actors(a)
        for k in batch.keys:
            lv.keys(k)
        for s in batch.strings:
            lv.strings(s)
        for f in batch.floats:
            lv.floats(f)
        for b in batch.bigints:
            lv.bigints(b)
        ctr = batch.cols["ctr"][d, :n].tolist()
        acts = batch.cols["actor"][d, :n]
        actors = batch.actors
        if n and int(acts.min()) == int(acts.max()):
            # single-writer doc (the dominant bulk shape): one actor
            # lookup for the whole column
            writer = actors[int(acts[0])]
            lv.opids = [OpId(c, writer) for c in ctr]
        else:
            names = [actors[a] for a in acts.tolist()]
            lv.opids = list(map(OpId, ctr, names))
        lv.row_of = dict(zip(lv.opids, range(n)))
        return lv

    # -- appends --------------------------------------------------------

    def append_changes(self, changes: Sequence[Change]) -> None:
        """Append already-admitted changes (caller enforces causal
        order + dedup — the live engine's admission mirror of OpSet)."""
        for change in changes:
            self._append_one(change)

    def _append_one(self, change: Change) -> None:
        row_of = self.row_of
        for i, op in enumerate(change.ops):
            opid = change.op_id(i)
            n_actors = len(self.actors.items)
            enc = _encode_op_row(
                op, opid, change, row_of,
                self.actors, self.keys, self.strings, self.floats,
                self.bigints,
            )
            if enc is None:
                continue
            if len(self.actors.items) != n_actors:
                self._rank_lut = None  # new actor: ranks shift
            vals, pred_tgts = enc
            row = self.n
            self._reserve_rows(row + 1)
            c = self.cols
            for name in COLUMNS:
                c[name][row] = vals[name]
            for tgt in pred_tgts:
                k = self.n_preds
                self._reserve_preds(k + 1)
                self.psrc[k] = row
                self.ptgt[k] = tgt
                self.n_preds = k + 1
            row_of[opid] = row
            self.opids.append(opid)
            self.n = row + 1

    def _reserve_rows(self, n: int) -> None:
        cap = len(self.cols["action"])
        if n <= cap:
            return
        new_cap = round_up_pow2(n)
        for name in COLUMNS:
            grown = np.full(
                new_cap, _COL_DEFAULTS.get(name, 0), np.int32
            )
            grown[: self.n] = self.cols[name][: self.n]
            self.cols[name] = grown

    def _reserve_preds(self, n: int) -> None:
        cap = len(self.psrc)
        if n <= cap:
            return
        new_cap = round_up_pow2(n)
        for attr in ("psrc", "ptgt"):
            grown = np.full(new_cap, -1, np.int32)
            grown[: self.n_preds] = getattr(self, attr)[: self.n_preds]
            setattr(self, attr, grown)

    # -- kernel views ---------------------------------------------------

    @property
    def actor_rank(self) -> np.ndarray:
        """LUT: actor intern index -> string-sort rank (the kernel's
        tie-break order)."""
        if self._rank_lut is None or len(self._rank_lut) != max(
            1, len(self.actors.items)
        ):
            order = sorted(
                range(len(self.actors.items)),
                key=lambda i: self.actors.items[i],
            )
            lut = np.zeros(max(1, len(self.actors.items)), np.int32)
            for rank, idx in enumerate(order):
                lut[idx] = rank
            self._rank_lut = lut
        return self._rank_lut

    def slots(self) -> np.ndarray:
        """[n] int32 actor slots in string-sort rank order."""
        return self.actor_rank[self.cols["actor"][: self.n]]

    def opid(self, row: int) -> OpId:
        return OpId(
            int(self.cols["ctr"][row]),
            self.actors.items[int(self.cols["actor"][row])],
        )

    def decode_row_value(self, row: int) -> Any:
        return decode_live_value(
            int(self.cols["vkind"][row]),
            int(self.cols["value"][row]),
            self,
        )

    def decode_values(self, rows: np.ndarray) -> List[Any]:
        """Decoded Python values for the given row indices — the batch
        twin of `decode_row_value` (`decode_value_rows` over this doc's
        columns and side tables). The live decode's value hot path."""
        return decode_value_rows(
            self.cols["vkind"][rows], self.cols["value"][rows],
            self.strings.items, self.floats.items, self.bigints.items,
        )

    @property
    def nbytes(self) -> int:
        """Resident host bytes of this doc's live cache: the packed
        numpy planes plus an estimate of the opids/row_of index
        structures (~one OpId tuple + two dict/list slots per row).
        What the live engine's byte-bounded LRU charges a hot doc."""
        b = self.psrc.nbytes + self.ptgt.nbytes
        for a in self.cols.values():
            b += a.nbytes
        return b + len(self.opids) * 144


_COL_DEFAULTS = {"action": PAD, "obj": -1, "key": -1, "ref": -3}


def decode_live_value(vkind: int, value: int, lv: "LiveColumns") -> Any:
    if vkind == VK_NONE:
        return None
    if vkind == VK_INT:
        return int(value)
    if vkind == VK_BOOL:
        return bool(value)
    if vkind == VK_FLOAT:
        return lv.floats.items[value]
    if vkind == VK_STR:
        return lv.strings.items[value]
    if vkind == VK_BIGINT:
        return lv.bigints.items[value]
    raise ValueError(f"bad vkind {vkind}")


def decode_value_rows(
    vkind: np.ndarray, value: np.ndarray, strings: Sequence[str],
    floats: Sequence[float], bigints: Sequence[int],
) -> List[Any]:
    """Decoded Python values of a run of rows, from their value kinds
    and codes and the side tables the codes index — the batch twin of
    `decode_value` / `decode_live_value`, vectorized by value kind (one
    nonzero + one tight fixup pass per kind present instead of a
    per-row Python call). Shared by the live engine
    (`LiveColumns.decode_values`) and the read tier's text join
    (serve/tier.py)."""
    out: List[Any] = value.tolist()
    if not out:
        return out
    # VK_INT rows are already right (tolist yields Python ints);
    # patch the other kinds in place
    m = vkind == VK_NONE
    if m.any():
        for i in np.nonzero(m)[0].tolist():
            out[i] = None
    m = vkind == VK_BOOL
    if m.any():
        for i in np.nonzero(m)[0].tolist():
            out[i] = bool(out[i])
    for code, table in (
        (VK_FLOAT, floats), (VK_STR, strings), (VK_BIGINT, bigints),
    ):
        m = vkind == code
        if m.any():
            for i in np.nonzero(m)[0].tolist():
                out[i] = table[out[i]]
    return out


def decode_value(
    vkind: int, value: int, dt: int, batch: ColumnarBatch
) -> Any:
    if vkind == VK_NONE:
        return None
    if vkind == VK_INT:
        return int(value)
    if vkind == VK_BOOL:
        return bool(value)
    if vkind == VK_FLOAT:
        return batch.floats[value]
    if vkind == VK_STR:
        return batch.strings[value]
    if vkind == VK_BIGINT:
        return batch.bigints[value]
    raise ValueError(f"bad vkind {vkind}")
