"""The slab round-robin over the 8-device virtual CPU backend, the
program table, the device summary + graft entries."""

import sys

import jax
import numpy as np
import pytest

from hypermerge_tpu.ops import crdt_kernels as ck
from hypermerge_tpu.ops.materialize import fetch_summary
from hypermerge_tpu.ops.synth import synth_batch, synth_changes
from hypermerge_tpu.parallel import sharded as sharded_mod
from hypermerge_tpu.parallel.sharded import SlabRoundRobin

# the round-robin tests need the 8-device virtual CPU backend
# conftest.py sets up
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs >= 8 (virtual) devices"
)


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
    return batch, ck.run_batch(batch)


# five slabs of unequal [D, N]: not a multiple of 2, 4 or 8 devices
_SLAB_SHAPES = [(7, 96), (3, 48), (12, 96), (5, 200), (9, 48)]


def _slabs():
    return [
        synth_batch(n_docs=d, n_ops=n, seed=11 * i)
        for i, (d, n) in enumerate(_SLAB_SHAPES)
    ]


def _jit_cache_sizes():
    return (
        ck.materialize_full_device._cache_size(),
        ck.materialize_full_lean_device._cache_size(),
    )


@pytest.mark.parametrize("lean", [False, True], ids=["full", "lean"])
@pytest.mark.parametrize("k", [2, 4, 8])
def test_round_robin_bit_identical_to_one_device(k, lean):
    """Several devices give bit for bit what one device gives, on
    ragged slab counts and shapes: every slab's decoded summary equals
    run_batch_full's on the default device, array by array."""
    slabs = _slabs()
    if lean:
        assert all(ck.batch_is_lean(b) for b in slabs)
    rr = SlabRoundRobin(jax.devices()[:k])
    wires = [rr.dispatch(b, lean=lean)[1] for b in slabs]
    assert sum(rr.slabs_per_chip) == len(slabs)
    assert max(rr.slabs_per_chip) - min(rr.slabs_per_chip) <= 1
    for i, (b, wire) in enumerate(zip(slabs, wires)):
        assert next(iter(wire.devices())) == rr.devices[i % k]
        got = fetch_summary(wire, b, lean)
        _out, one = ck.run_batch_full(b, lean=lean)
        want = fetch_summary(one, b, lean)
        assert got.keys() == want.keys()
        for key in want:
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)


def test_round_robin_second_pass_traces_nothing():
    """The same shapes over the same devices a second time compile and
    trace nothing: the jit caches keep their sizes and the program
    table's tallies stand."""
    devices = jax.devices()[:4]
    for lean in (False, True):
        rr = SlabRoundRobin(devices)
        for b in _slabs() + _slabs()[:3]:  # every shape on every device
            rr.dispatch(b, lean=lean)
        rr.drain()
    sizes = _jit_cache_sizes()
    counts = dict(sharded_mod.trace_counts)
    for lean in (False, True):
        rr = SlabRoundRobin(devices)
        for b in _slabs() + _slabs()[:3]:
            rr.dispatch(b, lean=lean)
        rr.drain()
    assert _jit_cache_sizes() == sizes
    assert dict(sharded_mod.trace_counts) == counts


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


@pytest.mark.parametrize("case", ["release", "device_index"])
def test_round_robin_release_and_device_index(case):
    devices = jax.devices()
    rr = SlabRoundRobin(devices[:2])
    if case == "device_index":
        assert rr.device_index(devices[1]) == 1
        assert rr.device_index(devices[2]) is None
        return
    # release() empties the in-flight queues without blocking on what
    # is in them, and a summary the caller holds still decodes
    batch = _slabs()[0]
    _out, wire = rr.dispatch(batch)
    rr._inflight[1] = [_Saturator()]
    rr.release()
    assert all(not q for q in rr._inflight.values())
    assert fetch_summary(wire, batch)["n_map_entries"].shape == (7,)


@pytest.mark.parametrize("case", ["HM_MESH=0", "one visible device", "eight"])
def test_loader_takes_round_robin_only_with_devices_to_share(
    case, monkeypatch
):
    """BulkLoader._rr: None under HM_MESH=0 and with one device to be
    seen, else the round-robin over jax.devices() in order."""
    from hypermerge_tpu.backend.bulk_loader import BulkLoader

    devices = jax.devices()
    if case == "HM_MESH=0":
        monkeypatch.setenv("HM_MESH", "0")
    else:
        monkeypatch.delenv("HM_MESH", raising=False)
    if case == "one visible device":
        monkeypatch.setattr(jax, "devices", lambda: devices[:1])
    rr = BulkLoader(backend=None)._rr
    if case != "eight":
        assert rr is None
        return
    assert type(rr) is SlabRoundRobin
    assert rr.devices == devices and rr.depth == sharded_mod.RR_DEPTH


def test_device_topology_keys():
    """What `tools/meta.py --devices` prints and the Telemetry reply
    carries as `device`."""
    from hypermerge_tpu.parallel.mesh import device_topology

    assert device_topology() == {
        "n_devices": 8,
        "platform": "cpu",
        "device_kind": jax.devices()[0].device_kind,
        "default_backend": "cpu",
        "process_count": 1,
    }


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
