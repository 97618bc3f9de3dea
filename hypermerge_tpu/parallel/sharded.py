"""Sharded batched programs: the multi-chip execution path.

The reference processes documents one at a time on one Node thread
(SURVEY.md §2.3); here the same workloads run as SPMD programs over a
(dp, sp) Mesh:

- `sharded_materialize`: the full batched CRDT replay (ops/crdt_kernels)
  with every [D, N] column sharded on dp. Per-doc compute has no cross-doc
  data flow, so XLA compiles this with zero collectives — linear scaling
  over chips.
- `sharded_clock_union` / `sharded_dominated`: GLOBAL-actor-indexed
  [D, A] clock matrices (ClockStore rows — BASELINE config 5 bulk
  queries) sharded (dp, sp); the cross-shard doc-axis reduction is an
  EXPLICIT `shard_map` collective (`lax.pmax`/`lax.pmin` over the mesh
  axes — over ICI on hardware). NOT for kernel clock outputs:
  MaterializeOut.clock is slot-LOCAL ([D, A_loc], a different actor per
  slot per doc) — decode those with `local_clock_union`.
- `step`: one full "merge step" — materialize + clock union as ONE
  `shard_map` collective program (the per-shard kernel, the per-shard
  scatter-max, and the cross-shard pmax all in one executable) — what
  the driver's multichip entry exercises end-to-end.
- `SlabRoundRobin`: the streaming-pipeline alternative to sharded
  dispatch — whole slabs round-robin (or least-loaded, HM_RR_LEAST_LOADED)
  across devices with bounded per-device in-flight queues, so chips run
  independent programs while the host packs ahead (the bulk loader,
  backend/bulk_loader.py). Tracks per-chip dispatch busy time.
- `MeshBulkScheduler`: SlabRoundRobin's streaming married to the mesh —
  whole slabs stay pinned per chip, and the CROSS-DOC reductions over
  everything resident (clock union across every chip's slabs, the bulk
  summary gather) run as one `shard_map` collective program over the
  mesh instead of a host-side merge of per-device fetches. On real ICI
  the gather is a Pallas `make_async_remote_copy` program
  (`remote_copy_capable`); host-platform CPU meshes lower the same
  program through `lax` collectives, so CPU CI pins the numerics.

Every mesh program is built ONCE per (mesh, shape-bucket) key in a
module program table (`_PROGRAMS`) — repeated calls reuse the jitted
executable with zero retracing (`trace_counts` exposes per-key trace
tallies for the regression tests).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import compile_cache
from ..ops.columnar import ColumnarBatch
from ..ops.crdt_kernels import MaterializeOut, batched_kernel
from .. import telemetry
from .mesh import doc_actor_sharding, doc_sharding, pad_to_multiple

# mesh telemetry (process registry): program dispatches, retraces
# (mirrors trace_counts, which stays the per-key regression-test
# truth), and host<->device transfer bytes — the "is the mesh being
# fed" view tools/top.py renders next to pipeline queue depths.
_M_DISPATCHES = telemetry.counter("mesh.dispatches")
_M_TRACES = telemetry.counter("mesh.traces")
_M_H2D = telemetry.counter("mesh.h2d_bytes")
_M_D2H = telemetry.counter("mesh.d2h_bytes")

# narrow wire-arg order, matching ops.crdt_kernels.host_args; pad-doc
# rows must decode to action=PAD (flags=7), insert=0
_N_ARGS = 11  # flags, slot, ctr, seq, obj, key, ref, value, psrc, ptgt, da
_PAD_VALUES = (7, 0, 0, 0, -1, -1, -3, 0, -1, -1, -1)


# ---------------------------------------------------------------------------
# program table — ONE jitted program per (mesh, kind, shape bucket)
#
# The first cut of this module built a fresh `jax.jit` closure inside
# every call (`local_clock_union`, `sharded_full`'s inner `fn`), so every
# union/materialize paid a full retrace: jit caches per FUNCTION OBJECT,
# and a new closure is a new function. The table below hoists every mesh
# program behind a key; the jit object lives as long as the process and
# its own shape-cache does the rest.

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


# a uint8 array tiles (32 rows, 128 lanes) on TPU: the gather's blocks
# are padded to it so a block is a whole number of tiles
_GATHER_TILE = (32, 128)


def remote_copy_capable(mesh: Optional[Mesh] = None) -> bool:
    """True when the mesh's devices can run the Pallas
    `make_async_remote_copy` ICI gather (real TPU chips). Host-platform
    CPU meshes — the CI twin — always lower the lax-collective variant
    instead. HM_ICI_PALLAS=0 forces the lax path on hardware too (A/B
    and escape hatch)."""
    if os.environ.get("HM_ICI_PALLAS", "1") == "0":
        return False
    devs = list(mesh.devices.flat) if mesh is not None else jax.devices()
    return bool(devs) and devs[0].platform == "tpu"


def _pallas_gather(n_devices: int, rows: int, width: int, dtype):
    """Pallas all-gather over the "dp" mesh axis, HBM to HBM: after a
    barrier (every peer has entered the kernel, so its output buffer
    exists) each chip DMAs its [rows, width] block straight into its
    slot of every peer's replicated [n*rows, width] output
    (`make_async_remote_copy`) and into its own (`make_async_copy`) —
    no VMEM staging, so the block size is bounded by HBM only. The
    slot slices are DMA windows into a tiled buffer: `rows` and `width`
    must be multiples of the uint8 tile (`_GATHER_TILE`; the caller
    pads). Sends and receives are symmetric (n-1 equal-sized blocks
    each way), so waiting on copy k settles one send and one receive.
    Built only when `remote_copy_capable` — the lax.all_gather twin is
    the numerics reference on CPU CI. `_gather_program` selects this
    path only when sp == 1, so dp IS the flattened device axis."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mesh_id = pltpu.DeviceIdType.MESH

    def kernel(local_ref, out_ref, own_sem, send_sem, recv_sem):
        my_id = jax.lax.axis_index("dp")
        peers = [
            jax.lax.rem(my_id + k, n_devices)
            for k in range(1, n_devices)
        ]
        barrier = pltpu.get_barrier_semaphore()
        for peer in peers:
            pltpu.semaphore_signal(
                barrier, inc=1, device_id={"dp": peer},
                device_id_type=mesh_id,
            )
        pltpu.semaphore_wait(barrier, n_devices - 1)
        slot = out_ref.at[pl.ds(my_id * rows, rows)]
        own = pltpu.make_async_copy(local_ref, slot, own_sem)
        own.start()
        sends = []
        for k, peer in enumerate(peers):
            rdma = pltpu.make_async_remote_copy(
                src_ref=local_ref,
                dst_ref=slot,
                send_sem=send_sem.at[k],
                recv_sem=recv_sem.at[k],
                device_id={"dp": peer},
                device_id_type=mesh_id,
            )
            rdma.start()
            sends.append(rdma)
        own.wait()
        for rdma in sends:
            rdma.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((n_devices - 1,)),
            pltpu.SemaphoreType.DMA((n_devices - 1,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_devices * rows, width), dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            collective_id=0, has_side_effects=True
        ),
    )


def shard_batch(batch: ColumnarBatch, mesh: Mesh):
    """Pad the doc axis to the dp size and device_put with dp sharding.

    Returns (args, A_loc, K, D_pad) — the same narrow wire args (and the
    same A_loc/K bucketing) as the single-device path, so both compile to
    the same per-shard program; only the sharding differs."""
    import numpy as np

    from ..ops.crdt_kernels import host_args

    dp = mesh.shape["dp"]
    D = batch.n_docs
    D_pad = pad_to_multiple(max(D, dp), dp)
    sh = doc_sharding(mesh)
    # the same child spans as crdt_kernels._device_args: a bulk load's
    # dispatch stage reads t_narrow / t_upload from them
    with telemetry.timed("pipeline.narrow", "pipeline"):
        np_args, A, K = host_args(batch)

    def put(arr, pad_value):
        if D_pad != arr.shape[0]:
            pad = np.full(
                (D_pad - arr.shape[0], *arr.shape[1:]), pad_value, arr.dtype
            )
            arr = np.concatenate([arr, pad], axis=0)
        return jax.device_put(arr, sh)

    with telemetry.timed("pipeline.upload", "pipeline"):
        args = tuple(put(a, pv) for a, pv in zip(np_args, _PAD_VALUES))
    return args, A, K, D_pad


def _docwise(mesh: Mesh, fn):
    """`fn` over [D, ...] arrays whose docs are independent, as a
    shard_map over dp: each chip runs it on its own docs. Not left to
    the partitioner, which cannot split the Pallas kernel a slab's
    rga_order rounds may be (crdt_kernels._rga_rank_vmem)."""
    return shard_map(
        fn, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        check_vma=False,
    )


def _materialize_program(mesh: Mesh, A: int, K: int):
    key = ("materialize", mesh, A, K)

    def build():
        sh = doc_sharding(mesh)
        return jax.jit(
            _traced(key, _docwise(mesh, batched_kernel(A, K))),
            in_shardings=(sh,) * _N_ARGS,
            out_shardings=MaterializeOut(
                *([sh] * len(MaterializeOut._fields))
            ),
        )

    return _program(key, build)


def _materialize_on_mesh(batch: ColumnarBatch, mesh: Mesh):
    """(out, doc_actors): the sharded batched replay plus the dp-sharded
    actor map it ran with (step reuses the map for the clock union)."""
    args, A, K, _ = shard_batch(batch, mesh)
    fn = _materialize_program(mesh, A, K)
    with mesh:
        out = fn(*args)
    return out, args[-1]


def sharded_materialize(
    batch: ColumnarBatch, mesh: Mesh
) -> MaterializeOut:
    """Batched replay sharded over dp; returns device-sharded outputs."""
    return _materialize_on_mesh(batch, mesh)[0]


def _full_program(mesh: Mesh, A: int, K: int, N: int, lean: bool):
    key = ("full", mesh, A, K, N, lean)

    def build():
        from ..ops.crdt_kernels import _summarize_wire

        sh = doc_sharding(mesh)
        kern = batched_kernel(A, K)

        def fn(*xs):
            out = kern(*xs)
            return out, _summarize_wire(out, N, A, lean)

        return jax.jit(
            _traced(key, _docwise(mesh, fn)),
            in_shardings=(sh,) * _N_ARGS,
            out_shardings=(
                MaterializeOut(*([sh] * len(MaterializeOut._fields))),
                sh,
            ),
        )

    return _program(key, build)


def sharded_full(batch: ColumnarBatch, mesh: Mesh, lean: bool = False):
    """(MaterializeOut, summary wire) sharded over dp — the multi-chip
    twin of ops.crdt_kernels.run_batch_full (the product bulk loader
    sends whole slabs round-robin instead, SlabRoundRobin below): full
    lanes stay device-resident per shard for lazy patch decode, the
    fused summary buffer transfers for the materialization barrier (one
    dp-sharded [D, W] uint8 leaf). `lean` drops the wire's clock section
    — callers holding authoritative host clocks only. Per-doc compute
    has no cross-doc data flow, so XLA compiles this with zero
    collectives — linear scaling over dp."""
    args, A, K, _ = shard_batch(batch, mesh)
    jfn = _full_program(mesh, A, K, batch.n_rows, lean)
    _M_DISPATCHES.add(1)
    with mesh, telemetry.timed("pipeline.enqueue", "pipeline"), \
            telemetry.span("mesh.sharded_full", "mesh"):
        return jfn(*args)


def _pad_axes(arr, mesh: Mesh):
    """Pad [D, A] to (dp, sp) multiples with zeros (neutral for max and
    for <= domination checks)."""
    import numpy as np

    arr = np.asarray(arr)
    D, A = arr.shape
    Dp = pad_to_multiple(max(D, mesh.shape["dp"]), mesh.shape["dp"])
    Ap = pad_to_multiple(max(A, mesh.shape["sp"]), mesh.shape["sp"])
    if (Dp, Ap) != (D, A):
        out = np.zeros((Dp, Ap), arr.dtype)
        out[:D, :A] = arr
        arr = out
    return arr, D, A


def _union_program(mesh: Mesh):
    """[D, A] (dp, sp)-sharded -> [A] sp-sharded union: per-shard doc
    max, then an explicit pmax collective across the dp axis."""
    key = ("union", mesh)

    def build():
        def f(c):
            return jax.lax.pmax(jnp.max(c, axis=0), "dp")

        return jax.jit(
            shard_map(
                _traced(key, f),
                mesh=mesh,
                in_specs=P("dp", "sp"),
                out_specs=P("sp"),
                check_vma=False,
            )
        )

    return _program(key, build)


def sharded_clock_union(clocks, mesh: Mesh):
    """[D, A] -> [A] union across a (dp, sp)-sharded clock matrix whose
    columns are GLOBAL actor indices (ClockStore rows); the dp-axis
    max-reduce is an explicit shard_map `lax.pmax` — an ICI collective
    on hardware. Kernel clock outputs are slot-local — use
    `local_clock_union` for those."""
    arr, _D, A = _pad_axes(clocks, mesh)
    arr = jax.device_put(arr, doc_actor_sharding(mesh))
    fn = _union_program(mesh)
    with mesh:
        return fn(arr)[:A]


def _dominated_program(mesh: Mesh):
    """[D, A], [A] -> [D] bool: per-shard <= check, then an explicit
    pmin collective ANDs the verdicts across the sp axis."""
    key = ("dominated", mesh)

    def build():
        def f(c, q):
            part = jnp.all(c <= q[None, :], axis=-1)
            return jax.lax.pmin(part.astype(jnp.int32), "sp") > 0

        return jax.jit(
            shard_map(
                _traced(key, f),
                mesh=mesh,
                in_specs=(P("dp", "sp"), P("sp")),
                out_specs=P("dp"),
                check_vma=False,
            )
        )

    return _program(key, build)


def sharded_dominated(clocks, query, mesh: Mesh):
    """[D, A], [A] -> [D] bool: which docs' clocks the query dominates.
    The actor-axis `all` reduction crosses sp shards (shard_map pmin)."""
    import numpy as np

    arr, D, A = _pad_axes(clocks, mesh)
    q = np.zeros((arr.shape[1],), arr.dtype)
    q[:A] = np.asarray(query)
    arr = jax.device_put(arr, doc_actor_sharding(mesh))
    q = jax.device_put(q, NamedSharding(mesh, P("sp")))
    fn = _dominated_program(mesh)
    with mesh:
        return fn(arr, q)[:D]


def _scatter_union(clock, doc_actors, n_actors: int):
    """Per-shard scatter-max of slot-local clocks into global actor
    rows: [d, A_loc] x [d, A_loc] -> [n_actors]."""
    return (
        jnp.zeros(n_actors + 1, jnp.int32)
        .at[jnp.where(doc_actors >= 0, doc_actors, n_actors).ravel()]
        .max(jnp.where(doc_actors >= 0, clock, 0).ravel())[:n_actors]
    )


def _local_union_program(mesh: Mesh, n_actors: int):
    key = ("local_union", mesh, n_actors)

    def build():
        def f(c, da):
            u = _scatter_union(c, da, n_actors)
            return jax.lax.pmax(jax.lax.pmax(u, "dp"), "sp")

        return jax.jit(
            shard_map(
                _traced(key, f),
                mesh=mesh,
                in_specs=(P("dp"), P("dp")),
                out_specs=P(),
                check_vma=False,
            )
        )

    return _program(key, build)


def local_clock_union(clock, doc_actors, n_actors: int, mesh: Mesh):
    """[D, A_loc] local-slot clocks + [D, A_loc] actor maps -> [n_actors]
    global union. Each shard scatter-maxes its docs, then one explicit
    pmax collective (shard_map) replicates the union over the mesh —
    max-allreduce over ICI on hardware. The program is cached per
    (mesh, n_actors): repeated calls never retrace."""
    fn = _local_union_program(mesh, n_actors)
    with mesh:
        return fn(clock, doc_actors)


def _step_program(mesh: Mesh, A: int, K: int, n_actors: int):
    """ONE collective program for the full merge step: the per-shard
    kernel, the per-shard scatter-max clock union, and the cross-shard
    pmax — materialize + union in a single executable over the mesh."""
    key = ("step", mesh, A, K, n_actors)

    def build():
        kern = batched_kernel(A, K)

        def f(*args):
            out = kern(*args)
            u = _scatter_union(out.clock, args[-1], n_actors)
            u = jax.lax.pmax(jax.lax.pmax(u, "dp"), "sp")
            return out, u

        return jax.jit(
            shard_map(
                _traced(key, f),
                mesh=mesh,
                in_specs=(P("dp"),) * _N_ARGS,
                out_specs=(
                    MaterializeOut(
                        *([P("dp")] * len(MaterializeOut._fields))
                    ),
                    P(),
                ),
                check_vma=False,
            )
        )

    return _program(key, build)


def step(batch: ColumnarBatch, mesh: Mesh):
    """One full merge step: materialize everything + union every clock,
    as ONE shard_map collective program over the mesh. This is the
    framework's 'training step' analogue — the complete device-side
    work of a bulk sync cycle."""
    args, A, K, _ = shard_batch(batch, mesh)
    n_actors = max(1, len(batch.actors))
    fn = _step_program(mesh, A, K, n_actors)
    _M_DISPATCHES.add(1)
    with mesh, telemetry.span("mesh.step", "mesh"):
        return fn(*args)


def _gather_program(mesh: Mesh, dtype):
    """[rows, W] sharded over the flattened mesh axis -> replicated
    [rows, W]: the bulk summary gather as one collective program. On
    meshes whose chips pass `remote_copy_capable` the inner gather is
    the Pallas `make_async_remote_copy` program (sp == 1 topology);
    everywhere else (CPU CI, sp > 1) it is `lax.all_gather` — identical
    numerics, different transport. A Pallas failure is an error, never
    a quiet switch to the lax twin."""
    n = mesh.devices.size
    use_pallas = (
        n > 1 and remote_copy_capable(mesh) and mesh.shape["sp"] == 1
    )
    key = ("gather", mesh, jnp.dtype(dtype).name, use_pallas)

    def build():
        def lax_gather(x):
            g = jax.lax.all_gather(x, "sp", axis=0, tiled=True)
            return jax.lax.all_gather(g, "dp", axis=0, tiled=True)

        def pallas_gather(x):
            return _pallas_gather(
                n, x.shape[0], x.shape[1], x.dtype
            )(x)

        f = pallas_gather if use_pallas else lax_gather
        return jax.jit(
            shard_map(
                _traced(key, f),
                mesh=mesh,
                in_specs=P(("dp", "sp")),
                out_specs=P(),
                check_vma=False,
            )
        )

    return _program(key, build)


def _combine_partials_program(mesh: Mesh):
    """[n_chips, A] (one row per chip, sharded over the flattened mesh
    axis) -> replicated [A] max: the cross-chip clock-union combine."""
    key = ("combine", mesh)

    def build():
        def f(x):
            u = jnp.max(x, axis=0)
            return jax.lax.pmax(jax.lax.pmax(u, "dp"), "sp")

        return jax.jit(
            shard_map(
                _traced(key, f),
                mesh=mesh,
                in_specs=P(("dp", "sp")),
                out_specs=P(),
                check_vma=False,
            )
        )

    return _program(key, build)


# per-device in-flight slab bound of the round-robin scheduler
RR_DEPTH = 2


class SlabRoundRobin:
    """Stream WHOLE slabs across visible devices with bounded
    per-device in-flight queues — the streaming pipeline's multi-chip
    dispatch (backend/bulk_loader.py BulkLoader._dispatch).

    Where `sharded_full` splits one slab across the mesh (dp sharding:
    one program, every chip in lockstep, the host blocked feeding all
    chips at once), round-robin keeps each slab whole on one chip and
    streams successive slabs to successive chips. Chips run independent
    programs, so while chip k computes slab N the host packs slab N+1
    for chip k+1 — the 8-chip projection becomes an actual overlapped
    run instead of an 8x divide of a serial device stage. Same kernels
    (materialize_full_device / the lean twin), same (A_loc, K) buckets,
    so results are bit-identical to the single-device and sharded
    paths.

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


class MeshBulkScheduler(SlabRoundRobin):
    """SlabRoundRobin's streaming dispatch + shard_map collective
    cross-doc reductions: the mesh-native bulk sync scheduler.

    Dispatch is UNCHANGED from the round-robin parent (whole slabs
    pinned per chip, host packs slab N+1 while chip k computes slab N,
    identical kernels so summaries stay bit-identical) — but every
    dispatched slab's device-resident outputs are also tracked per
    chip, so the cross-doc reductions that used to be a host-side merge
    of per-device fetches become collective programs over the mesh:

    - `collective_clock_union(n_actors)`: each chip pre-reduces ITS
      resident slabs' slot-local clocks (one tiny scatter-max program
      per slab, executed where the data lives — no transfer), the
      per-chip partials assemble zero-copy into one mesh-sharded
      [n_chips, n_actors] array, and ONE shard_map pmax program
      replicates the global union — a single [n_actors] fetch instead
      of n_chips fetch-and-merge round trips.
    - `gather_summaries()`: every chip's resident summary wires stack
      on-chip, assemble into one mesh-sharded [rows, W] array, and ONE
      collective gather program (`lax.all_gather`, or the Pallas
      `make_async_remote_copy` program on capable ICI) replicates them —
      the host reads the whole load's summaries in ONE transfer, in
      dispatch order.

    Tracking is OPT-IN (`track_resident`): callers that will run the
    collective reductions (the bulk-sync merge layer, the measured
    bench, tests) pay the per-dispatch actor-map upload and keep
    wire/clock refs pinned until `reset_resident()`; the PRODUCT bulk
    loader constructs with tracking OFF — its barrier fetches per slab
    on the overlapped fetch workers, so tracking there would pin every
    slab's device wire for no consumer. Track + reduce state resets
    with `reset_resident()` (a new bulk load) — the backpressure/
    release contract is the parent's."""

    def __init__(
        self,
        mesh: Mesh,
        depth: int = RR_DEPTH,
        least_loaded: bool = None,
        track_resident: bool = True,
    ) -> None:
        super().__init__(
            list(mesh.devices.flat), depth, least_loaded=least_loaded
        )
        self.mesh = mesh
        self.track_resident = track_resident
        # per chip: (clock ref [D, A_loc], doc_actors ref [D, A_loc])
        self._resident_clocks: Dict[int, List] = {
            i: [] for i in range(len(self.devices))
        }
        # per chip: (dispatch sequence number, n_docs, wire ref [D, W])
        self._resident_wires: Dict[int, List] = {
            i: [] for i in range(len(self.devices))
        }
        self._seq = 0

    def reset_resident(self) -> None:
        """Forget tracked device refs (start of a new bulk load)."""
        for d in (self._resident_clocks, self._resident_wires):
            for q in d.values():
                q.clear()
        self._seq = 0

    def dispatch(self, batch: ColumnarBatch, lean: bool = False):
        from ..ops.crdt_kernels import bucket_doc_actors

        out, summary = super().dispatch(batch, lean=lean)
        if not self.track_resident:
            return out, summary
        i = self.last_device
        da, _A, _K = bucket_doc_actors(batch)
        da_ref = jax.device_put(da, self.devices[i])
        self._resident_clocks[i].append((out.clock, da_ref))
        self._resident_wires[i].append(
            (self._seq, batch.n_docs, summary)
        )
        self._seq += 1
        return out, summary

    # -- collective reductions over everything resident -----------------

    def _chip_partial(self, items, n_actors: int, device):
        """Max-fold one chip's resident (clock, da) refs into a [1,
        n_actors] partial ON that chip. Data is committed to the chip,
        so the cached scatter program executes there — no host hop."""
        key = ("chip_union", n_actors)

        def build():
            def f(c, da, acc):
                return jnp.maximum(acc, _scatter_union(c, da, n_actors))

            return jax.jit(_traced(key, f))

        fn = _program(key, build)
        acc = jax.device_put(
            jnp.zeros((n_actors,), jnp.int32), device
        )
        for clock, da in items:
            acc = fn(clock, da, acc)
        return acc.reshape(1, n_actors)

    def collective_clock_union(self, n_actors: int):
        """[n_actors] global union of every resident slab's clocks:
        per-chip pre-reduce, then ONE shard_map pmax collective across
        the mesh. Replaces fetching each chip's partial and merging on
        host."""
        import numpy as np

        n_actors = max(1, n_actors)
        partials = [
            self._chip_partial(
                self._resident_clocks[i], n_actors, self.devices[i]
            )
            for i in range(len(self.devices))
        ]
        sh = NamedSharding(self.mesh, P(("dp", "sp")))
        arr = jax.make_array_from_single_device_arrays(
            (len(self.devices), n_actors), sh, partials
        )
        fn = _combine_partials_program(self.mesh)
        with self.mesh:
            return np.asarray(fn(arr))

    def gather_summaries(self):
        """Every resident summary wire, host-side, in DISPATCH order:
        [(seq, n_docs, np wire rows)] via ONE collective gather program
        per wire width. Chips stack their wires locally (device-pinned
        concat + zero-pad to the max per-chip row count), the stacks
        assemble into one mesh-sharded array, and the gather collective
        replicates it — a single device->host transfer serves the whole
        load, replacing one fetch per slab per chip."""
        import numpy as np

        # group by wire width: one collective per distinct [.., W]
        by_w: Dict[int, Dict[int, List]] = {}
        for i, items in self._resident_wires.items():
            for seq, n_docs, wire in items:
                by_w.setdefault(wire.shape[1], {}).setdefault(
                    i, []
                ).append((seq, n_docs, wire))
        out = []
        for W, per_chip in sorted(by_w.items()):
            rows_per_chip = [
                sum(int(w.shape[0]) for _s, _n, w in per_chip.get(i, []))
                for i in range(len(self.devices))
            ]
            rows = pad_to_multiple(
                max(max(rows_per_chip), 1), _GATHER_TILE[0]
            )
            Wp = pad_to_multiple(W, _GATHER_TILE[1])
            stacks = []
            for i in range(len(self.devices)):
                items = per_chip.get(i, [])
                key = ("wire_stack", W, rows, len(items))

                def build(items=items, rows=rows, W=W, Wp=Wp):
                    def f(*wires):
                        used = sum(w.shape[0] for w in wires)
                        parts = list(wires) + [
                            jnp.zeros((rows - used, W), jnp.uint8)
                        ]
                        return jnp.pad(
                            jnp.concatenate(parts, axis=0),
                            ((0, 0), (0, Wp - W)),
                        )

                    return jax.jit(_traced(key, f))

                fn = _program(key, build)
                if items:
                    stacks.append(fn(*[w for _s, _n, w in items]))
                else:
                    stacks.append(
                        jax.device_put(
                            jnp.zeros((rows, Wp), jnp.uint8),
                            self.devices[i],
                        )
                    )
            sh = NamedSharding(self.mesh, P(("dp", "sp")))
            arr = jax.make_array_from_single_device_arrays(
                (len(self.devices) * rows, Wp), sh, stacks
            )
            gfn = _gather_program(self.mesh, jnp.uint8)
            with self.mesh:
                host = np.asarray(gfn(arr))[:, :W]
            _M_D2H.add(host.nbytes)
            for i in range(len(self.devices)):
                base = i * rows
                for seq, n_docs, wire in per_chip.get(i, []):
                    n = int(wire.shape[0])
                    out.append((seq, n_docs, host[base : base + n]))
                    base += n
        out.sort(key=lambda t: t[0])
        return out
