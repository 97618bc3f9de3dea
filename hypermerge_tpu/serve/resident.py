"""The HBM residency cache: per-doc summary columns pinned on device.

A resident doc is the device half of a read: six structural lanes
(serve/kernels.py layout) stacked into ONE [LANES, N] int32 array — a
single upload per install — keyed by the serving clock the columns
were built at. The host half stays host: the value/str/float side
tables, the per-row value columns, and the element->winner-value map.
A `lookup` or `index` read decodes one row of them; a `text` read
takes every live element's row at once (serve/tier.py `_join_text`),
which is why they are kept as arrays and never as Python objects.

Install follows the PR-4 adoption idiom: the build (sidecar pack +
summary kernel + upload) runs with NO lock held; the install takes the
cache lock for dict bookkeeping only and re-checks the serving clock.
A doc whose clock moved mid-build still serves THIS batch from the
built arrays (they are correct as of read admission) but is not
cached — and a stale entry can never serve a later read, because every
read re-compares the entry clock against the doc's current serving
clock (clock-driven invalidation). Docs whose state the sidecars
cannot rebuild (_serveable_spec None — dirty/unbacked feeds) are never
installed at all: they stay on the host path rather than risk a stale
resurrection.

A LOCAL change does not have to cost an entry its place: where the
change's ops are the newest of their doc and supersede every visible
value of their cell (backend/live.py `_apply_local_locked`, the host
OpSet's `apply_local_request`), the lanes after it follow from the
lanes before it in closed form. `ResidencyCache.note` (the write
path's hook, bookkeeping under the cache lock) records such a change on
the entry, resolved to the entry's own rows; the flush thread applies
what was noted before it serves (`follow` for the host half,
serve/kernels.py `advance` for the lanes). Everything else takes
`mark_stale`.

Eviction is a byte-bounded LRU under HM_SERVE_MAX_BYTES; device OOM
during an install sheds LRU entries and retries once before degrading
to the host path (serve/tier.py owns those counters).
"""

from __future__ import annotations

import os
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis.lockdep import make_rlock
from ..crdt.change import HEAD, ROOT, Action, Change, Op, OpId
from . import kernels
from .kernels import (
    D_DEL, D_INSERT, D_SET, N_LANES, L_INSERT, L_KEY, L_LIVE, L_MAPWIN,
    L_OBJ, L_RANK,
)

# under the loader's lowest rung (256 rows) every tiny doc shares the
# 64-row executables and pays for 64 rows of device lanes
SERVE_MIN_ROWS = 64


def serve_max_bytes() -> int:
    """HM_SERVE_MAX_BYTES — read per enforcement pass so tests and
    operators can adjust the budget live."""
    return int(os.environ.get("HM_SERVE_MAX_BYTES", "268435456"))


class _Tables:
    """The batch side tables decode_value needs, without pinning the
    whole ColumnarBatch (its [D, N] column dict) in the entry. One per
    install page: the page's docs share it and its key index. `chars`
    is the strings table as an object array: a text read takes all
    its characters from it at once (serve/tier.py `_join_text`). A
    page's tables are never written: an entry that follows a local
    change takes a copy of its own first (`own`) and interns the
    change's values there. Read and, once owned, written by the flush
    thread alone."""

    __slots__ = (
        "strings", "floats", "bigints", "chars", "owned", "_str_index",
    )

    def __init__(self, batch) -> None:
        self.strings = batch.strings
        self.floats = batch.floats
        self.bigints = batch.bigints
        self.chars = np.empty(len(batch.strings), object)
        self.chars[:] = batch.strings
        self.owned = False
        self._str_index: Optional[Dict[str, int]] = None

    def own(self) -> "_Tables":
        """These tables if an entry owns them, else a copy it may
        append to."""
        if self.owned:
            return self
        t = _Tables(self)
        t.strings, t.floats, t.bigints = (
            list(self.strings), list(self.floats), list(self.bigints)
        )
        t.owned = True
        return t

    def intern_string(self, s: str) -> int:
        """REQUIRES `owned`."""
        if self._str_index is None:
            self._str_index = {v: i for i, v in enumerate(self.strings)}
        i = self._str_index.get(s)
        if i is None:
            i = self._str_index[s] = len(self.strings)
            self.strings.append(s)
            self.chars = np.append(self.chars, np.asarray([s], object))
        return i

    def intern_float(self, v: float) -> int:
        """REQUIRES `owned`."""
        self.floats.append(v)
        return len(self.floats) - 1

    def intern_bigint(self, v: int) -> int:
        """REQUIRES `owned`."""
        self.bigints.append(v)
        return len(self.bigints) - 1


class ResidentDoc:
    """One doc's device lanes + host decode half, valid at `clock`.
    The device lanes (`dev`) and the tables are the flush thread's
    alone; the host half's columns, `actors`, `n` and `clock` are
    written by it under the cache lock (`ResidencyCache.follow`), where
    the write path's hook reads them (`ResidencyCache.note`)."""

    __slots__ = (
        "doc_id", "clock", "n", "bucket", "dev", "action", "vkind",
        "value", "dt", "ctr", "actor", "actors", "inc_total", "elem_val",
        "tables", "key_index", "nbytes", "last_use", "stale",
    )

    def __init__(
        self, doc_id: str, clock: Dict[str, int], n: int, bucket: int,
        dev: Any, host_cols: Dict[str, np.ndarray],
        elem_val: np.ndarray, tables: _Tables,
        key_index: Dict[str, int], actors: Dict[str, int],
    ) -> None:
        self.doc_id = doc_id
        self.clock = clock
        self.n = n
        self.bucket = bucket
        self.dev = dev  # jnp [N_LANES, bucket] int32, device-resident
        self.action = host_cols["action"]
        self.vkind = host_cols["vkind"]
        self.value = host_cols["value"]
        self.dt = host_cols["dt"]
        # the rows' op ids, (ctr[r], the actor `actors` maps to
        # actor[r]): what resolves the object and element a local
        # change names to THIS entry's rows (the live engine numbers
        # its rows otherwise). `actors` is the page's, shared and never
        # written: an entry that meets a new actor takes a grown copy
        self.ctr = host_cols["ctr"]
        self.actor = host_cols["actor"]
        self.actors = actors
        self.inc_total = host_cols["inc_total"]
        self.elem_val = elem_val  # [n] element row -> winner value row
        self.tables = tables
        self.key_index = key_index
        self.nbytes = self._bytes()
        self.last_use = 0
        self.stale = False

    _HOST = (
        "action", "vkind", "value", "dt", "ctr", "actor", "inc_total",
        "elem_val",
    )

    def _bytes(self) -> int:
        # a doc with no INC and no element SET points at its group's
        # shared zeros / identity rows (base set): not its bytes
        return int(getattr(self.dev, "nbytes", 0)) + sum(
            int(a.nbytes)
            for a in (getattr(self, k) for k in self._HOST)
            if a.base is None
        ) + 512

    def row_of(self, opid: OpId) -> int:
        """The row of the op `opid`, or -4."""
        a = self.actors.get(opid.actor)
        if a is None:
            return -4
        hit = np.nonzero((self.ctr == opid.ctr) & (self.actor == a))[0]
        return int(hit[0]) if len(hit) else -4

    def followed(
        self, deltas: Sequence[tuple], clock: Dict[str, int]
    ) -> Dict[str, Any]:
        """The host half of an advance, beside the entry (which is not
        written: `ResidencyCache.follow` swaps the result in under its
        lock): one appended row a delta (the rows `kernels.advance`
        writes on the device), `n` and `clock`. The change's values go
        into tables the entry owns (its first follow copies its
        page's). Flush thread only."""
        from ..ops.columnar import _encode_value

        t = self.tables.own()
        actors = self.actors
        rows: Dict[str, List[int]] = {
            k: [] for k in ("action", "vkind", "value", "dt", "ctr", "actor")
        }
        for _kind, _row, _obj, _ref, _key, op, opid in deltas:
            vkind, value = _encode_value(
                op, t.intern_string, t.intern_float, t.intern_bigint
            )
            rows["action"].append(int(op.action))
            rows["vkind"].append(vkind)
            rows["value"].append(value)
            rows["dt"].append(
                1 if op.datatype == "counter"
                else 2 if op.datatype == "timestamp" else 0
            )
            rows["ctr"].append(opid.ctr)
            if opid.actor not in actors:
                actors = dict(actors)
                actors[opid.actor] = len(actors)
            rows["actor"].append(actors[opid.actor])
        k = len(deltas)
        new: Dict[str, Any] = {
            "tables": t, "actors": actors, "n": self.n + k,
            "clock": dict(clock),
        }
        if k:
            for name, vals in rows.items():
                new[name] = _extended(getattr(self, name), vals)
            new["inc_total"] = _extended(self.inc_total, [0] * k)
            new["elem_val"] = _extended(
                self.elem_val, range(self.n, self.n + k)
            )
        return new

    def obj_type(self, row: int) -> Optional[str]:
        """'map'/'list'/'text'/'table' for a MAKE row, 'map' for the
        root (-1), None for value rows."""
        from ..ops.materialize import _OBJ_TYPES

        if row < 0:
            return "map"
        return _OBJ_TYPES.get(int(self.action[row]))


def _extended(old: np.ndarray, vals) -> np.ndarray:
    """`old` with `vals` after it, in `old`'s dtype where they fit (the
    host half keeps the pack's narrow columns), else int32."""
    new = np.asarray(list(vals), np.int64)
    info = np.iinfo(old.dtype)
    fits = info.min <= int(new.min()) and int(new.max()) <= info.max
    return np.concatenate(
        [old, new.astype(old.dtype if fits else np.int32)]
    )


def _to_device(stacked: np.ndarray):
    """An install page's one host->device transfer — a module seam so
    the OOM tests can make the device refuse without faking a whole
    backend."""
    import jax.numpy as jnp

    return jnp.asarray(stacked)


def rung_of(spec) -> int:
    """The length rung (backend/pipeline.py ROW_RUNGS, the ladder bulk
    slabs are formed by, with SERVE_MIN_ROWS under it) over the op rows
    of one doc's feed windows: the row bucket of its resident lanes,
    whichever docs it is installed beside, so the query programs see
    six row shapes up to 65,536."""
    from ..backend.pipeline import SlabFormer

    rows = 0
    for fc, start, end in spec:
        lo, hi = fc.window(start, end)
        rows += hi - lo
    if rows <= SERVE_MIN_ROWS:
        return SERVE_MIN_ROWS
    return SlabFormer.rung(rows)


# docs of an install page: a group is built a page at a time, each
# padded to one of these, so that the programs of an install (the
# split, the merge, the slab program of a rung) have two doc shapes
# whatever number of cold docs a flush happened to hold
PAGE_DOCS = (16, 256)


def build_group(
    backend, items: List[Tuple[str, Dict[str, int], Any]], bucket: int,
    count: Callable[[str, int], None],
) -> List[ResidentDoc]:
    """Build the resident entries of one install group: the docs
    `items` = [(doc_id, clock, feed spec)] of one length rung
    (`bucket` rows), a page of at most PAGE_DOCS[-1] docs at a time.
    A page is one `pack_slab` (the loader's pack, its rows at the
    rung), the three kernel
    lanes of all its docs at once (`_memo_lanes` where the bulk
    loader's summary memo holds the doc's clock, else ONE run of the
    slab program), the host decode halves as rows of the page's
    columns, one upload, and the page's [D, LANES, bucket] array cut
    into the entries' own [LANES, bucket] arrays on the device. The
    one-doc install is the group of one. Runs with NO lock held.

    `count(name, n)` feeds the tier's counters (memo_hits,
    install_device_docs, install_host_kernel_docs). Raises whatever the
    pack, the kernel or the upload raises (the tier's OOM
    evict-and-retry wraps this call)."""
    from .. import telemetry

    entries: List[ResidentDoc] = []
    tier = getattr(backend, "serve", None)
    with telemetry.span(
        "serve.install", "serve", docs=len(items), rung=bucket,
        # docs of the group that were resident at an older clock (a
        # write made them stale), and the feeds the pack reads
        stale=0 if tier is None else sum(
            1 for doc_id, _c, _s in items
            if tier._cache.prior_bucket(doc_id) is not None
        ),
        feeds=sum(len(spec) for _d, _c, spec in items),
    ) as sp:
        memo = 0
        for at in range(0, len(items), PAGE_DOCS[-1]):
            page = items[at:at + PAGE_DOCS[-1]]
            n_docs = next(d for d in PAGE_DOCS if len(page) <= d)
            built, n_memo = _build_page(
                backend, page, bucket, n_docs, count
            )
            entries.extend(built)
            memo += n_memo
        count("memo_hits", memo)
        sp.note(memo=memo)
    return entries


def _build_page(backend, items, bucket: int, n_docs: int, count):
    """(entries, docs whose lanes the memo held) of one page of a
    group, packed at `n_docs` docs."""
    from .. import telemetry
    from ..backend.bulk_loader import pack_slab

    n_real = len(items)
    with telemetry.span("serve.install.pack", "serve"):
        # rows at the rung, not at the pow2 over the page's longest
        # doc: the slab program of an install follows from (page, rung)
        # as the query programs do, and is warm once it ran once
        batch = pack_slab(
            [spec for _d, _c, spec in items], n_docs, n_rows=bucket
        )
    cols = batch.cols
    D, N = batch.shape
    n_ops = np.asarray(batch.n_ops[:n_real], np.int64)
    action = cols["action"]
    # rows whose lanes the memo does not carry (its wire has no INC
    # totals and no element-override SETs)
    has_inc = (action[:n_real] == int(Action.INC)).any(axis=1)
    has_eset = (
        (cols["insert"][:n_real] == 0) & (cols["key"][:n_real] < 0)
        & (cols["ref"][:n_real] >= 0)
        & (action[:n_real] == int(Action.SET))
    ).any(axis=1)
    lanes = np.zeros((D, N_LANES, bucket), np.int32)
    pad = np.arange(N)[None, :] >= batch.n_ops[:, None]
    lanes[:, L_OBJ, :N] = np.where(pad, -3, cols["obj"])
    lanes[:, L_OBJ, N:] = -3  # pad rows match no container (root: -1)
    lanes[:, L_INSERT, :N] = cols["insert"]
    lanes[:, L_KEY, :N] = cols["key"]
    lanes[:, L_KEY, N:] = -1
    with telemetry.span("serve.install.lanes", "serve") as lsp:
        from_memo = _memo_lanes(
            backend, items, n_ops, has_inc | has_eset, lanes, N
        )
        n_memo = int(from_memo.sum())
        out = on_device = None
        if n_memo < n_real:
            out, on_device = _kernel_lanes(batch)
            count(
                "install_device_docs" if on_device
                else "install_host_kernel_docs", n_real - n_memo,
            )
        lsp.note(device=int(bool(on_device)), memo=n_memo)
    if out is not None and not on_device:
        for lane, src in (
            (L_LIVE, out.elem_live), (L_RANK, out.rank),
            (L_MAPWIN, out.map_winner),
        ):
            src = np.asarray(src)[:n_real].astype(np.int32)
            lanes[:n_real, lane, :N] = np.where(
                from_memo[:, None], lanes[:n_real, lane, :N], src
            )
    with telemetry.span(
        "serve.install.upload", "serve", bytes=int(lanes.nbytes)
    ):
        dev = _to_device(lanes)  # ONE upload per page
        if on_device:
            take = np.zeros(D, bool)
            take[:n_real] = ~from_memo
            dev = kernels.install_merge(
                dev, out.elem_live, out.rank, out.map_winner, take
            )
        devs = kernels.install_split(dev, n_real)
    with telemetry.span("serve.install.host_half", "serve"):
        # only a doc with INC totals or element SETs needs the
        # kernel's other lanes on the host; the rest share two rows
        full = np.nonzero(~from_memo & (has_inc | has_eset))[0]
        inc_total = visible = elem_winner = None
        if len(full):
            inc_total = np.asarray(out.inc_total)
            visible = np.asarray(out.visible)
            elem_winner = np.asarray(out.elem_winner)
        full_set = set(full.tolist())
        zeros = np.zeros(N, np.int32)
        ident = np.arange(N, dtype=np.int32)
        tables = _Tables(batch)
        key_index = {k: i for i, k in enumerate(batch.keys)}
        actors = {a: i for i, a in enumerate(batch.actors)}
        actor_dt = np.int16 if len(batch.actors) < 2 ** 15 else np.int32
        entries = []
        for d, (doc_id, clock, _spec) in enumerate(items):
            n = int(n_ops[d])
            host_cols = {
                k: cols[k][d, :n].copy()
                for k in ("action", "vkind", "value", "dt", "ctr")
            }
            host_cols["actor"] = cols["actor"][d, :n].astype(actor_dt)
            if d in full_set:
                c = {k: np.asarray(cols[k][d, :n], np.int32)
                     for k in ("insert", "key", "ref")}
                host_cols["inc_total"] = np.asarray(
                    inc_total[d, :n], np.int32
                ).copy()
                elem_val = _elem_val_map(
                    c, visible[d, :n], elem_winner[d, :n]
                )
            else:
                host_cols["inc_total"] = zeros[:n]
                elem_val = ident[:n]
            entries.append(ResidentDoc(
                doc_id, dict(clock), n, bucket, devs[d], host_cols,
                elem_val, tables, key_index, actors,
            ))
    return entries, n_memo


def _kernel_lanes(batch):
    """(MaterializeOut-shaped lanes of the whole group, on_device): one
    run of the slab program a cold open runs (`materialize_full*`). On
    an accelerator always; a CPU process answers a group under the
    loader's own gate (HM_DEVICE_MIN_CELLS) with the numpy twin, as its
    bulk loads do."""
    from ..backend.bulk_loader import device_min_cells
    from ..ops import compile_cache

    cells = batch.n_docs * batch.n_rows
    if compile_cache.ensure() == "cpu" and cells < device_min_cells():
        from ..ops.host_kernel import run_batch_host

        return run_batch_host(batch), False
    from ..ops.crdt_kernels import batch_is_lean, run_batch_full

    out, _wire = run_batch_full(batch, lean=batch_is_lean(batch))
    return out, True


def _elem_val_map(
    c: Dict[str, np.ndarray], visible: np.ndarray, elem_winner: np.ndarray
) -> np.ndarray:
    """[n] element row -> its winning value row (the decode_patch
    elem_val rule, vectorized): a visible winning SET on the element
    overrides; otherwise the INS row's own value stands."""
    n = len(visible)
    ev = np.arange(n, dtype=np.int32)
    rows = np.nonzero(
        visible
        & (c["insert"] == 0)
        & (c["key"] < 0)
        & (c["ref"] >= 0)
        & elem_winner
    )[0]
    ev[c["ref"][rows]] = rows
    return ev


def _memo_lanes(backend, items, n_ops, needs_kernel, lanes, N):
    """Fill the kernel lanes (live, rank, map winner) of every doc the
    backend's per-doc summary memo (the bulk loader's host half) holds
    at exactly its serving clock: those docs skip the kernel run — the
    serving tier and the bulk path share ONE freshness rule (clock
    equality). Only sound when no row needs the lanes the memo does not
    carry (`needs_kernel`: INC totals, element-override SETs). Returns
    the [docs] mask of the docs served; rows of one memo width decode
    together."""
    from ..ops.crdt_kernels import unpack_bits_le

    served = np.zeros(len(items), bool)
    by_width: Dict[int, List[Tuple[int, Dict]]] = {}
    for d, (doc_id, clock, _spec) in enumerate(items):
        m = backend.summary_memo_row(doc_id)
        if (
            m is None or needs_kernel[d] or m["clock"] != clock
            or m["N"] < n_ops[d]
        ):
            continue
        by_width.setdefault(m["N"], []).append((d, m))
    for M, rows in by_width.items():
        idx = np.asarray([d for d, _m in rows])
        w = min(M, N)
        for lane, key in ((L_MAPWIN, "mw_bits"), (L_LIVE, "el_bits")):
            bits = unpack_bits_le(np.stack([m[key] for _d, m in rows]), M)
            lanes[idx, lane, :w] = bits[:, :w]
        # pseudo-rank from the memo'd element order: rank[order[i]] =
        # M - i reproduces the order under the seq_order kernel's argsort
        order = np.stack([m["order"] for _d, m in rows]).astype(np.int64)
        pos = np.empty_like(order)
        np.put_along_axis(pos, order, np.arange(M)[None, :], axis=1)
        lanes[idx, L_RANK, :w] = (M - pos[:, :w]).astype(np.int32)
        served[idx] = True
    return served


# the local changes an entry may have noted before a read applies them
MAX_NOTED = 64
# why a write that met an entry released it (`ResidencyCache.note`'s
# answers, and "remote": no local change at all: a remote patch, a tick)
REFUSALS = (
    "remote", "absent", "clock", "cap", "full", "order", "ref", "shape",
    "key",
)


class _Noted:
    """The local changes a resident entry has yet to follow: one delta
    an op, (kind, new row, object row, element row, key index, op, op
    id) with the rows the ENTRY's, in the order they were made; `clock`
    is the doc's clock after the last of them."""

    __slots__ = ("deltas", "clock", "rows", "top")

    def __init__(self, entry: ResidentDoc) -> None:
        self.deltas: List[tuple] = []
        self.clock = entry.clock
        self.rows: Dict[OpId, int] = {}  # the noted ops' rows-to-be
        self.top = int(entry.ctr.max()) if entry.n else 0  # highest ctr


def _delta_of(e: ResidentDoc, noted: _Noted, op: Op, opid: OpId, row: int):
    """The delta of one op of a local change, or (a str) why the entry
    cannot follow it. The three shapes whose lanes are closed-form for
    an op that is the newest of its doc and supersedes every visible
    value of its cell (serve/kernels.py `advance`): one element
    inserted, one map key SET, one element deleted."""

    def row_of(target: OpId) -> int:
        r = noted.rows.get(target)
        return e.row_of(target) if r is None else r

    obj = -1 if op.obj == ROOT else row_of(op.obj)
    if obj < -1:
        return "ref"
    if op.insert:
        if op.action != Action.SET or op.key is not None or op.ref is None:
            return "shape"  # an object made in a list
        ref = -1 if op.ref == HEAD else row_of(op.ref)
        return "ref" if ref < -1 else (D_INSERT, row, obj, ref, -1, op, opid)
    if op.action == Action.SET and op.key is not None and op.ref is None:
        key = e.key_index.get(op.key)
        if key is None:
            return "key"  # the page's key table is shared: no new key
        return (D_SET, row, obj, -1, key, op, opid)
    if op.action == Action.DEL and op.key is None and op.ref not in (
        None, HEAD
    ):
        ref = row_of(op.ref)
        return "ref" if ref < 0 else (D_DEL, row, obj, ref, -1, op, opid)
    return "shape"  # MAKE, INC, a SET on an element, a DEL of a key


class ResidencyCache:
    """doc_id -> ResidentDoc under a byte-bounded LRU. The lock guards
    table bookkeeping only — builds and uploads always run outside it
    (see module docstring)."""

    # ids remembered as "evicted" for the residency report — bounded
    # (FIFO) so a long-lived daemon cycling a huge corpus does not
    # grow the Telemetry payload with the whole doc universe
    EVICTED_REMEMBERED = 1024

    def __init__(self) -> None:
        self._lock = make_rlock("serve.cache")
        self._entries: "OrderedDict[str, ResidentDoc]" = OrderedDict()
        self._evicted: "OrderedDict[str, None]" = OrderedDict()
        # doc_id -> row bucket of the entry a write invalidated, until
        # the doc is installed again (or dropped): what tells a
        # re-install, and a promotion to the next rung, from a first one
        self._invalidated: Dict[str, int] = {}
        # doc_id -> the local changes its entry noted and no read has
        # applied yet (`note` / `follow`); gone with the entry
        self._noted: Dict[str, _Noted] = {}
        self._bytes = 0
        self._use = 0

    def get_fresh(
        self, doc_id: str, clock: Dict[str, int]
    ) -> Tuple[Optional[ResidentDoc], Optional[tuple]]:
        """The serving invalidation check, in ONE look: the doc's live
        entry (None: it has none, or a write marked it stale) and the
        deltas it noted and has yet to apply, if its clock after them
        EQUALS `clock`, the doc's current serving clock: it serves
        then, once the caller has applied them (`follow`: whenever
        `entry.clock != clock`). Deltas None: the entry is at another
        clock: a writer has moved the doc's clock and not yet noted its
        change (it is inside the doc's emission domain), or the entry
        lost a build race."""
        with self._lock:
            e = self._entries.get(doc_id)
            if e is None or e.stale:
                return None, None
            noted = self._noted.get(doc_id)
            if (e.clock if noted is None else noted.clock) != clock:
                return e, None
            self._use += 1
            e.last_use = self._use
            self._entries.move_to_end(doc_id)
            return e, () if noted is None else tuple(noted.deltas)

    def note(
        self, doc_id: str, change: Change, clock: Dict[str, int]
    ) -> Optional[str]:
        """A local change moved the doc's clock to `clock`: note it on
        the doc's entry, for the next read to apply, if the entry can
        follow it: it is fresh at the clock the change was made at, has
        a row free for every op, and every op is of a shape `_delta_of`
        takes. None when noted, else the reason it was not: the caller
        then releases the entry (`mark_stale`), and with it what it
        had noted. Dict and list bookkeeping and one compare over the
        entry's op ids an op."""
        with self._lock:
            e = self._entries.get(doc_id)
            if e is None or e.stale:
                return "absent"
            noted = self._noted.get(doc_id) or _Noted(e)
            was = dict(noted.clock)
            if was.get(change.actor, 0) != change.seq - 1:
                return "clock"
            was[change.actor] = change.seq
            if was != clock:
                return "clock"  # the entry missed a change before it
            n_ops = len(change.ops)
            if len(noted.deltas) + n_ops > MAX_NOTED:
                return "cap"
            row = e.n + len(noted.deltas)
            if row + n_ops > e.bucket:
                return "full"
            if n_ops and change.start_op <= noted.top:
                return "order"  # not the newest op of its doc
            deltas = []  # noted whole or not at all: a read may look
            for i, op in enumerate(change.ops):
                opid = change.op_id(i)
                d = _delta_of(e, noted, op, opid, row + i)
                if isinstance(d, str):
                    return d
                deltas.append(d)
                noted.rows[opid] = row + i
            noted.deltas.extend(deltas)
            noted.clock = was
            noted.top = max(noted.top, change.start_op + n_ops - 1)
            self._noted[doc_id] = noted
            return None

    def follow(
        self, entry: ResidentDoc, deltas: Sequence[tuple],
        clock: Dict[str, int],
    ) -> bool:
        """Apply `deltas`, what `get_fresh` said `entry` had noted up to
        `clock`, to its host half: the rows, tables and clock are made
        beside the entry, outside the lock (`followed`), and swapped in
        under it, where what a writer noted meanwhile stays noted.
        False, and nothing written, if the entry left the cache since
        `get_fresh` (a write released it, and what it had noted with
        it). Called by the flush thread alone, between dispatches,
        which then advances the lanes (`kernels.advance`)."""
        new = entry.followed(deltas, clock)
        with self._lock:
            noted = self._noted.get(entry.doc_id)
            if self._entries.get(entry.doc_id) is not entry or noted is None:
                return False
            del noted.deltas[: len(deltas)]
            if noted.clock == clock:
                del self._noted[entry.doc_id]
            for k, v in new.items():
                setattr(entry, k, v)
            grown = entry._bytes() - entry.nbytes
            entry.nbytes += grown
            self._bytes += grown
            return True

    def install(self, entry: ResidentDoc) -> List[ResidentDoc]:
        """Install a built entry (replacing any older clock's entry)
        and evict LRU down to the byte budget. Returns the evicted
        entries (the tier counts them)."""
        cap = serve_max_bytes()
        with self._lock:
            evicted = []
            old = self._entries.pop(entry.doc_id, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._noted.pop(entry.doc_id, None)
            self._invalidated.pop(entry.doc_id, None)
            self._use += 1
            entry.last_use = self._use
            self._entries[entry.doc_id] = entry
            self._bytes += entry.nbytes
            self._evicted.pop(entry.doc_id, None)
            while self._bytes > cap and len(self._entries) > 1:
                did, lru = next(iter(self._entries.items()))
                del self._entries[did]
                self._bytes -= lru.nbytes
                self._note_evicted(did)
                evicted.append(lru)
            return evicted

    def _note_evicted(self, doc_id: str) -> None:
        """Remember (bounded) that this id was resident once.
        REQUIRES serve.cache (analysis/guards.py)."""
        self._noted.pop(doc_id, None)
        self._evicted[doc_id] = None
        self._evicted.move_to_end(doc_id)
        while len(self._evicted) > self.EVICTED_REMEMBERED:
            self._evicted.popitem(last=False)

    def evict_lru(self, want_bytes: int) -> List[ResidentDoc]:
        """Shed LRU entries until `want_bytes` are freed (memory
        pressure during an install: the OOM retry path)."""
        with self._lock:
            evicted: List[ResidentDoc] = []
            freed = 0
            while self._entries and freed < want_bytes:
                did, lru = next(iter(self._entries.items()))
                del self._entries[did]
                self._bytes -= lru.nbytes
                self._note_evicted(did)
                freed += lru.nbytes
                evicted.append(lru)
            return evicted

    def mark_stale(self, doc_id: str) -> bool:
        """A write moved the doc's clock: the entry (if any) can never
        serve again (clocks never revert to the build clock), so its
        device arrays are RELEASED immediately instead of pinning the
        byte budget as dead weight until LRU pressure finds them.
        In-flight batches that already resolved the entry keep their
        reference and finish serving — those reads were admitted
        before the write's patch was delivered. True when a resident
        entry was actually invalidated."""
        with self._lock:
            e = self._entries.pop(doc_id, None)
            if e is None:
                return False
            e.stale = True
            self._bytes -= e.nbytes
            self._noted.pop(doc_id, None)
            self._invalidated[doc_id] = e.bucket
            return True

    def prior_bucket(self, doc_id: str) -> Optional[int]:
        """The row bucket this doc was resident at before (the entry a
        write invalidated, or one still held at an older clock); None
        for a doc not resident since it was opened or evicted."""
        with self._lock:
            e = self._entries.get(doc_id)
            if e is not None:
                return e.bucket
            return self._invalidated.get(doc_id)

    def drop(self, doc_id: str) -> None:
        with self._lock:
            e = self._entries.pop(doc_id, None)
            if e is not None:
                self._bytes -= e.nbytes
            self._noted.pop(doc_id, None)
            self._evicted.pop(doc_id, None)
            self._invalidated.pop(doc_id, None)

    @property
    def resident_bytes(self) -> int:
        # atomic_read_ok (analysis/guards.py): monitoring snapshot
        return self._bytes

    @property
    def device_bytes(self) -> int:
        """Bytes of the resident entries' device arrays."""
        with self._lock:
            return sum(
                int(getattr(e.dev, "nbytes", 0))
                for e in self._entries.values()
            )

    @property
    def resident_docs(self) -> int:
        with self._lock:
            return len(self._entries)

    def report(self) -> Dict[str, Any]:
        """Per-doc residency for tools/ls.py (via the Telemetry
        query): resident entries with their device bytes, plus the ids
        eviction pushed out since they were last resident."""
        with self._lock:
            return {
                "resident": {
                    did: {
                        "bytes": e.nbytes,
                        "stale": e.stale,
                        "rows": e.n,
                    }
                    for did, e in self._entries.items()
                },
                "evicted": sorted(self._evicted),
                "bytes": self._bytes,
            }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._evicted.clear()
            self._invalidated.clear()
            self._noted.clear()
            self._bytes = 0
