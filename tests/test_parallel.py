"""Sharded execution over the 8-device virtual CPU mesh + graft entries."""

import subprocess
import sys

import jax
import numpy as np
import pytest

from hypermerge_tpu.ops.crdt_kernels import run_batch
from hypermerge_tpu.ops.synth import synth_batch, synth_changes
from hypermerge_tpu.parallel.mesh import make_mesh
from hypermerge_tpu.parallel import sharded as sharded_mod
from hypermerge_tpu.parallel.sharded import (
    MeshBulkScheduler,
    SlabRoundRobin,
    local_clock_union,
    sharded_clock_union,
    sharded_dominated,
    sharded_full,
    sharded_materialize,
    step,
)

# mesh tests need the 8-device virtual CPU backend conftest.py sets up
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs >= 8 devices (virtual mesh)"
)


def test_mesh_shapes():
    mesh = make_mesh(8, sp=2)
    assert dict(mesh.shape) == {"dp": 4, "sp": 2}
    mesh1 = make_mesh(4)
    assert dict(mesh1.shape) == {"dp": 4, "sp": 1}
    with pytest.raises(ValueError):
        make_mesh(1000)


def test_sharded_materialize_matches_single_device():
    batch = synth_batch(n_docs=16, n_ops=128)
    single = run_batch(batch)
    mesh = make_mesh(8, sp=1)
    sharded = sharded_materialize(batch, mesh)
    for field in ("visible", "map_winner", "elem_live", "rank", "clock"):
        a = np.asarray(getattr(single, field))
        b = np.asarray(getattr(sharded, field))[: batch.n_docs]
        np.testing.assert_array_equal(a, b, err_msg=field)


def test_sharded_materialize_pads_ragged_doc_axis():
    batch = synth_batch(n_docs=13, n_ops=64)  # not divisible by dp
    mesh = make_mesh(8, sp=1)
    out = sharded_materialize(batch, mesh)
    assert out.rank.shape[0] == 16  # padded to dp multiple
    single = run_batch(batch)
    np.testing.assert_array_equal(
        np.asarray(single.rank), np.asarray(out.rank)[:13]
    )


def test_sharded_clock_union_and_dominated():
    mesh = make_mesh(8, sp=2)
    rng = np.random.default_rng(0)
    clocks = rng.integers(0, 100, (64, 16)).astype(np.int32)
    union = np.asarray(sharded_clock_union(clocks, mesh))
    np.testing.assert_array_equal(union, clocks.max(axis=0))

    query = clocks[7]
    dom = np.asarray(sharded_dominated(clocks, query, mesh))
    np.testing.assert_array_equal(dom, np.all(clocks <= query, axis=-1))


def test_full_step():
    batch = synth_batch(n_docs=8, n_ops=64)
    mesh = make_mesh(8, sp=2)
    out, union = step(batch, mesh)
    assert union.shape[-1] == len(batch.actors)


def test_synth_changes_replay_host():
    """The Change-object form of the synthetic workload is causally valid
    and replays fully on the host OpSet."""
    from hypermerge_tpu.crdt.opset import OpSet

    changes = synth_changes(200, seed=3)
    opset = OpSet()
    opset.apply_changes(changes)
    assert not opset._pending
    doc = opset.materialize()
    assert "t" in doc and len(str(doc["t"])) > 0


def test_synth_columns_equal_synth_changes_on_device():
    """Both generator forms produce the same materialized state."""
    from hypermerge_tpu.ops.columnar import pack_docs
    from hypermerge_tpu.ops.materialize import (
        DecodedBatch,
        materialize_docs,
    )
    from hypermerge_tpu.crdt.opset import OpSet
    from helpers import plainify

    changes = synth_changes(150, seed=5)
    opset = OpSet()
    opset.apply_changes(changes)
    dec = DecodedBatch(*_run(pack_docs([changes])))
    docs = materialize_docs(dec)
    assert plainify(docs[0]) == plainify(opset.materialize())


def _run(batch):
    return batch, run_batch(batch)


# -- mesh shapes the fuzz matrix pins: (dp, sp) ------------------------
_MESH_SHAPES = [(8, 1), (4, 2), (2, 2), (1, 1)]


def _mesh_for(dp, sp):
    return make_mesh(dp * sp, sp=sp)


def _host_local_union(clock, doc_actors, n_actors):
    """Numpy twin of the collective local clock union."""
    want = np.zeros(n_actors + 1, np.int64)
    c = np.asarray(clock)
    da = np.asarray(doc_actors)
    np.maximum.at(
        want,
        np.where(da >= 0, da, n_actors).ravel(),
        np.where(da >= 0, c, 0).ravel(),
    )
    return want[:n_actors].astype(np.int32)


def test_mesh_reductions_fuzz_bit_identical_across_shapes():
    """sharded_clock_union / sharded_dominated match the numpy twin on
    every mesh shape, including ragged (non-multiple) doc and actor
    counts that force padding on both axes."""
    rng = np.random.default_rng(7)
    for dp, sp in _MESH_SHAPES:
        mesh = _mesh_for(dp, sp)
        for D, A in [(13, 5), (32, 16), (7, 11), (1, 1), (64, 3)]:
            clocks = rng.integers(0, 1000, (D, A)).astype(np.int32)
            union = np.asarray(sharded_clock_union(clocks, mesh))
            np.testing.assert_array_equal(
                union, clocks.max(axis=0), err_msg=f"{dp}x{sp} {D}x{A}"
            )
            query = clocks[rng.integers(0, D)]
            dom = np.asarray(sharded_dominated(clocks, query, mesh))
            np.testing.assert_array_equal(
                dom,
                np.all(clocks <= query, axis=-1),
                err_msg=f"{dp}x{sp} {D}x{A}",
            )


def test_step_fuzz_bit_identical_to_single_device_across_shapes():
    """The one-program collective merge step (materialize + clock
    union) matches the single-device twin on every mesh shape, ragged
    doc counts included."""
    from hypermerge_tpu.ops.crdt_kernels import bucket_doc_actors

    for seed, (dp, sp) in enumerate(_MESH_SHAPES):
        mesh = _mesh_for(dp, sp)
        for n_docs in (13, 8):
            batch = synth_batch(n_docs=n_docs, n_ops=96, seed=seed)
            single = run_batch(batch)
            da, _A, _K = bucket_doc_actors(batch)
            n_actors = len(batch.actors)
            out, union = step(batch, mesh)
            for field in (
                "visible", "map_winner", "elem_live", "rank", "clock",
            ):
                np.testing.assert_array_equal(
                    np.asarray(getattr(single, field)),
                    np.asarray(getattr(out, field))[:n_docs],
                    err_msg=f"{dp}x{sp} D={n_docs} {field}",
                )
            np.testing.assert_array_equal(
                np.asarray(union),
                _host_local_union(single.clock, da, n_actors),
                err_msg=f"{dp}x{sp} D={n_docs} union",
            )


def test_mesh_programs_cached_no_retrace():
    """Repeated same-shape calls reuse ONE traced program: the program
    table (not a fresh jit closure per call) serves local_clock_union,
    sharded_full, and step — the r5 per-call retrace regression."""
    mesh = make_mesh(8, sp=1)
    batch = synth_batch(n_docs=16, n_ops=64, seed=1)
    n_actors = max(1, len(batch.actors))

    out, da = sharded_mod._materialize_on_mesh(batch, mesh)
    local_clock_union(out.clock, da, n_actors, mesh)
    sharded_full(batch, mesh, lean=False)
    step(batch, mesh)
    sharded_clock_union(
        np.ones((16, 8), np.int32), mesh
    )
    snapshot = dict(sharded_mod.trace_counts)
    assert snapshot, "trace counter never engaged"

    for _ in range(3):
        out, da = sharded_mod._materialize_on_mesh(batch, mesh)
        local_clock_union(out.clock, da, n_actors, mesh)
        sharded_full(batch, mesh, lean=False)
        step(batch, mesh)
        sharded_clock_union(np.ones((16, 8), np.int32), mesh)
    assert dict(sharded_mod.trace_counts) == snapshot, (
        "a mesh program retraced on a repeated same-shape call",
        snapshot,
        sharded_mod.trace_counts,
    )


class _Saturator:
    """Sentinel in-flight entry: popping it (blocking on a saturated
    device) is the failure the least-loaded test pins against."""

    def block_until_ready(self):
        raise AssertionError(
            "dispatch blocked on the saturated device instead of "
            "skipping to an idle one"
        )


def test_least_loaded_skips_saturated_device():
    """HM_RR_LEAST_LOADED: a device at its in-flight depth is skipped
    while any other device has room (FIFO tiebreak otherwise)."""
    from hypermerge_tpu.ops.columnar import pack_docs

    devices = jax.devices()
    rr = SlabRoundRobin(devices, depth=2, least_loaded=True)
    # saturate device 0 (the round-robin cursor's first pick)
    rr._inflight[0] = [_Saturator(), _Saturator()]
    batch = pack_docs(
        [synth_changes(48, n_actors=1, ops_per_change=8, seed=0)]
    )
    _out, wire = rr.dispatch(batch, lean=False)
    assert rr.last_device == 1  # skipped 0, FIFO tiebreak picked 1
    assert next(iter(wire.devices())) == devices[1]
    assert len(rr._inflight[0]) == 2  # untouched
    # strict round-robin twin WOULD have blocked (and popped) device 0
    rr_strict = SlabRoundRobin(devices, depth=2, least_loaded=False)
    rr_strict._inflight[0] = [_Saturator(), _Saturator()]
    with pytest.raises(AssertionError, match="saturated"):
        rr_strict.dispatch(batch, lean=False)


def test_least_loaded_env_gate(monkeypatch):
    monkeypatch.setenv("HM_RR_LEAST_LOADED", "1")
    assert SlabRoundRobin(jax.devices()).least_loaded
    monkeypatch.setenv("HM_RR_LEAST_LOADED", "0")
    assert not SlabRoundRobin(jax.devices()).least_loaded


def test_mesh_scheduler_collective_union_and_gather():
    """MeshBulkScheduler: streaming whole-slab dispatch stays
    bit-identical to per-slab fetch, while the cross-doc reductions
    (clock union, summary gather) run as collective programs whose
    results equal the host-side merge they replace."""
    from hypermerge_tpu.ops.columnar import pack_docs
    from hypermerge_tpu.ops.crdt_kernels import bucket_doc_actors
    from hypermerge_tpu.ops.materialize import fetch_summary

    mesh = make_mesh(8, sp=2)
    sch = MeshBulkScheduler(mesh, depth=2)
    batches = [
        pack_docs(
            [synth_changes(48, n_actors=2, ops_per_change=8, seed=s)]
        )
        for s in range(5)
    ]
    outs = []
    for b in batches:
        out, wire = sch.dispatch(b, lean=False)
        outs.append((b, out, wire))
    n_actors = max(len(b.actors) for b in batches)
    want = np.zeros(n_actors, np.int32)
    for b, out, _w in outs:
        da, _A, _K = bucket_doc_actors(b)
        want = np.maximum(
            want, _host_local_union(out.clock, da, n_actors)
        )
    np.testing.assert_array_equal(
        sch.collective_clock_union(n_actors), want
    )
    gathered = sch.gather_summaries()
    assert [g[0] for g in gathered] == list(range(len(batches)))
    for (_seq, _n, host_wire), (b, _out, wire) in zip(gathered, outs):
        np.testing.assert_array_equal(host_wire, np.asarray(wire))
        a = fetch_summary(host_wire, b, lean=False)
        bsl = fetch_summary(wire, b, lean=False)
        for k in a:
            np.testing.assert_array_equal(a[k], bsl[k], err_msg=k)
    # per-chip accounting: every dispatched slab is attributed
    assert sum(sch.slabs_per_chip) == len(batches)
    sch.drain()
    sch.release()
    sch.reset_resident()
    assert sch.gather_summaries() == []


def test_remote_copy_capability_gate(monkeypatch):
    """CPU host-platform meshes never select the Pallas ICI path; the
    env escape hatch forces it off everywhere."""
    from hypermerge_tpu.parallel.sharded import remote_copy_capable

    mesh = make_mesh(8, sp=1)
    assert remote_copy_capable(mesh) is False  # cpu devices
    assert remote_copy_capable() is False
    monkeypatch.setenv("HM_ICI_PALLAS", "0")
    assert remote_copy_capable(mesh) is False


def test_graft_entry_single_chip():
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    assert out.rank.shape[0] == 8


def test_graft_dryrun_multichip(monkeypatch):
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    # small corpus in CI; the driver runs the slab-scale default
    monkeypatch.setenv("HM_DRYRUN_DOCS", "64")
    monkeypatch.setenv("HM_DRYRUN_OPS", "96")
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "1")  # force device slabs
    g.dryrun_multichip(8)
    g.dryrun_multichip(4)


def test_graft_multichip_refuses_devices_it_does_not_have():
    """Asked for more devices than the backend shows, the hook raises:
    it never rebuilds on virtual CPU devices and prints "ok" for a mesh
    the chips did not run."""
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    with pytest.raises(RuntimeError, match="only 8 cpu device"):
        g.measured_multichip(16)
    assert jax.default_backend() == "cpu" and len(jax.devices()) == 8
