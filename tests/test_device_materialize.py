"""Device batched materialization == host OpSet, for arbitrary histories.

This is the core correctness contract of the framework (SURVEY.md §7.3.6:
determinism across backends — both paths must produce identical state from
the same feeds)."""

import random

import numpy as np
import pytest

from hypermerge_tpu.crdt.frontend_state import FrontendDoc
from hypermerge_tpu.crdt.opset import OpSet
from hypermerge_tpu.models import Counter, Text
from hypermerge_tpu.ops import columnar
from hypermerge_tpu.ops.materialize import (
    decode_columnar,
    decode_patch,
    materialize_batch,
    materialize_docs,
    text_join,
)

from helpers import Site, plainify, random_mutation, sync


def device_docs(*histories):
    dec = materialize_batch([list(h) for h in histories])
    return dec, materialize_docs(dec)


def test_single_doc_map():
    s = Site("alice")
    s.change(lambda d: d.__setitem__("x", 1))
    s.change(lambda d: d.__setitem__("y", "hello"))
    s.change(lambda d: d.__delitem__("x"))
    dec, docs = device_docs(s.opset.history)
    assert plainify(docs[0]) == plainify(s.opset.materialize())
    assert dec.clock_dict(0) == s.opset.clock


def test_nested_and_lists():
    s = Site("alice")
    s.change(
        lambda d: d.__setitem__(
            "cfg", {"deep": {"list": [1, 2, 3]}, "t": Text("hey")}
        )
    )
    s.change(lambda d: d["cfg"]["deep"]["list"].insert(1, 99))
    s.change(lambda d: d["cfg"]["deep"]["list"].__delitem__(0))
    s.change(lambda d: d["cfg"]["t"].insert(3, "!"))
    _, docs = device_docs(s.opset.history)
    assert plainify(docs[0]) == plainify(s.opset.materialize())


def test_concurrent_conflicts_match_host():
    a, b = Site("alice"), Site("bob")
    a.change(lambda d: d.__setitem__("x", 0))
    b.receive(a.opset.history)
    a.change(lambda d: d.__setitem__("x", "A"))
    b.change(lambda d: d.__setitem__("x", "B"))
    sync(a, b)
    dec, docs = device_docs(a.opset.history)
    assert plainify(docs[0]) == plainify(a.opset.materialize())
    # conflicts survive the device path identically to the host snapshot
    host_patch = a.opset.snapshot_patch()
    dev_patch = decode_patch(dec, 0)
    host_x = [d for d in host_patch.diffs if d.key == "x"][0]
    dev_x = [d for d in dev_patch.diffs if d.key == "x"][0]
    assert host_x.value == dev_x.value
    assert [c.op_id for c in host_x.conflicts] == [
        c.op_id for c in dev_x.conflicts
    ]


def test_counters_and_incs():
    a, b = Site("alice"), Site("bob")
    a.change(lambda d: d.__setitem__("n", Counter(10)))
    b.receive(a.opset.history)
    a.change(lambda d: d.increment("n", 5))
    b.change(lambda d: d.increment("n", 7))
    sync(a, b)
    _, docs = device_docs(a.opset.history)
    assert plainify(docs[0]) == plainify(a.opset.materialize())
    assert int(docs[0]["n"]) == 22


def test_rga_concurrent_inserts_match_host():
    a, b = Site("alice"), Site("bob")
    a.change(lambda d: d.__setitem__("l", ["x"]))
    b.receive(a.opset.history)
    for i in range(4):
        a.change(lambda d: d["l"].insert(1, f"a{i}"))
        b.change(lambda d: d["l"].insert(1, f"b{i}"))
    sync(a, b)
    assert plainify(a.doc) == plainify(b.doc)
    _, docs = device_docs(a.opset.history)
    assert plainify(docs[0]) == plainify(a.opset.materialize())


def test_batch_many_docs():
    sites = []
    for i in range(7):
        s = Site(f"actor{i}")
        s.change(lambda d: d.__setitem__("id", i))
        s.change(lambda d: d.__setitem__("l", list(range(i))))
        sites.append(s)
    dec, docs = device_docs(*[s.opset.history for s in sites])
    for s, doc in zip(sites, docs):
        assert plainify(doc) == plainify(s.opset.materialize())
    cols = decode_columnar(dec)
    assert cols["clock"].shape[0] == 7


def test_device_summary_equals_host_decode():
    # summarize_columnar (fused on-device summary, bit-packed transfer)
    # must agree exactly with decode_columnar (host numpy reference)
    from hypermerge_tpu.ops.materialize import summarize_columnar

    rng = random.Random(7)
    sites = [Site(f"s{i}") for i in range(5)]
    for _ in range(60):
        random_mutation(rng.choice(sites), rng)
    for i in range(len(sites) - 1):
        sync(sites[i], sites[i + 1])
    histories = [list(s.opset.history) for s in sites]
    batch = columnar.pack_docs(histories)
    dec = materialize_batch(histories)
    host = decode_columnar(dec)
    dev = summarize_columnar(batch)
    for k in host:
        np.testing.assert_array_equal(
            np.asarray(host[k]), np.asarray(dev[k]), err_msg=k
        )


def test_text_join_fast_path():
    s = Site("alice")
    s.change(lambda d: d.__setitem__("t", Text("hello")))
    s.change(lambda d: d["t"].insert(5, " world"))
    s.change(lambda d: d["t"].delete(0, 1))
    dec, _ = device_docs(s.opset.history)
    # find the text object's row: the MAKE_TEXT op
    act = dec.cols["action"][0]
    row = int(np.nonzero(act == 2)[0][0])
    assert text_join(dec, 0, row) == "ello world"
    assert str(s.opset.materialize()["t"]) == "ello world"


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_fuzz_device_equals_host(seed):
    r = random.Random(seed)
    actors = ["alice", "bob", "carol"]
    sites = [Site(a) for a in actors]
    for _ in range(5):
        for s in sites:
            for _ in range(r.randint(1, 3)):
                random_mutation(s, r)
        if r.random() < 0.6:
            donor, receiver = r.sample(sites, 2)
            receiver.receive(list(donor.opset.history))
    sync(*sites)
    assert plainify(sites[0].doc) == plainify(sites[1].doc)
    _, docs = device_docs(sites[0].opset.history)
    assert plainify(docs[0]) == plainify(sites[0].opset.materialize())


def test_causal_sort_is_valid_linear_extension():
    a, b = Site("alice"), Site("bob")
    a.change(lambda d: d.__setitem__("x", 1))
    b.receive(a.opset.history)
    b.change(lambda d: d.__setitem__("y", 2))
    a.receive(b.opset.history)
    a.change(lambda d: d.__setitem__("z", 3))
    shuffled = list(a.opset.history)
    random.Random(0).shuffle(shuffled)
    ordered = columnar.causal_sort(shuffled)
    seen_clock = {}
    for c in ordered:
        for dep_actor, dep_seq in c.deps.items():
            assert seen_clock.get(dep_actor, 0) >= dep_seq
        assert seen_clock.get(c.actor, 0) == c.seq - 1
        seen_clock[c.actor] = c.seq


def test_pack_roundtrip_values():
    s = Site("alice")
    s.change(
        lambda d: (
            d.__setitem__("i", 42),
            d.__setitem__("big", 2**40),
            d.__setitem__("f", 3.14159),
            d.__setitem__("b", True),
            d.__setitem__("none_later", 1),
            d.__setitem__("s", "string"),
        )
    )
    s.change(lambda d: d.__setitem__("none_later", None))
    _, docs = device_docs(s.opset.history)
    assert plainify(docs[0]) == plainify(s.opset.materialize())
    assert docs[0]["big"] == 2**40
    assert docs[0]["f"] == 3.14159
    assert docs[0]["none_later"] is None


def test_host_kernel_matches_device():
    """ops/host_kernel.py is a bit-exact numpy twin of the device kernel
    (the interactive single-doc open path must agree with bulk slabs)."""
    from hypermerge_tpu.ops.crdt_kernels import run_batch
    from hypermerge_tpu.ops.host_kernel import run_batch_host
    from hypermerge_tpu.ops.columnar import pack_docs
    from hypermerge_tpu.ops.synth import synth_batch, synth_changes

    histories = [synth_changes(257, seed=s) for s in range(3)]
    for batch in (pack_docs(histories), synth_batch(5, 192)):
        dev = run_batch(batch)
        host = run_batch_host(batch)
        for f in host._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(dev, f)), getattr(host, f), err_msg=f
            )


# -- rga_order's two gather arms (PR 31) --------------------------------
# The rounds of `_rga_chase` read their table through XLA's gather or,
# on a TPU at short rows, out of VMEM (`_rga_rank_vmem`; here through
# the Pallas interpreter): same rounds, `rank` equal to the bit.

_SET, _DEL, _MAKE_TEXT = 4, 5, 3  # crdt.change.Action


def _arm_doc(law: str, n_rows: int, rng):
    """One doc's kernel columns [n_rows] under `law`, and its pred
    edges."""
    from hypermerge_tpu.ops.columnar import PAD
    from hypermerge_tpu.ops.synth import synth_columns

    n = n_rows
    c = {
        "action": np.full(n, PAD, np.int32),
        "slot": np.zeros(n, np.int32),
        "ctr": np.zeros(n, np.int32),
        "seq": np.zeros(n, np.int32),
        "obj": np.full(n, -1, np.int32),
        "key": np.full(n, -1, np.int32),
        "ref": np.full(n, -3, np.int32),
        "insert": np.zeros(n, np.int32),
        "value": np.zeros(n, np.int32),
    }
    psrc, ptgt = [], []
    if law == "pad":
        return c, psrc, ptgt
    if law == "forest":  # the flagship's: inserts after a uniform pick
        n_ops = n - int(rng.integers(0, 9))
        s, ps, pt = synth_columns(
            n_ops, n_actors=3, seed=int(rng.integers(1 << 30))
        )
        for name in c:
            src = "actor" if name == "slot" else name
            c[name][:n_ops] = s[src]
        return c, list(ps), list(pt)
    rows = np.arange(n)
    c["action"][:] = _SET
    c["action"][0] = _MAKE_TEXT
    c["key"][0] = 0
    c["obj"][1:] = 0
    c["insert"][1:] = 1
    c["ctr"][:] = rows + 1
    c["seq"][:] = rows + 1
    if law == "chain":  # the longtail's typing run: a chain of N - 1
        c["ref"][1:] = rows[:-1]
        c["ref"][1] = -2
        return c, psrc, ptgt
    assert law == "collab"
    # rounds of 32 writers on EQUAL counters, all under the round's
    # anchor; every fourth op deletes an element of an earlier round
    c["ref"][1] = -2
    for lo in range(2, n, 32):
        hi = min(lo + 32, n)
        k = hi - lo
        anchor = int(rng.integers(1, lo))
        while c["insert"][anchor] != 1:
            anchor -= 1
        c["slot"][lo:hi] = rng.permutation(32)[:k]
        c["ctr"][lo:hi] = lo + 1
        c["ref"][lo:hi] = anchor
        for r in range(lo + 3, hi, 4):
            victim = int(rng.integers(1, lo))
            if c["insert"][victim] != 1:
                continue
            c["action"][r], c["insert"][r] = _DEL, 0
            c["ref"][r] = victim
            psrc.append(r)
            ptgt.append(victim)
    return c, psrc, ptgt


def _arm_args(law: str, n_docs: int, n_rows: int, seed: int):
    """Narrow wire args of a [n_docs, n_rows] slab whose docs follow
    `law`, and its actor bucket."""
    rng = np.random.default_rng(seed)
    docs = [_arm_doc(law, n_rows, rng) for _ in range(n_docs)]
    P = max(8, max(len(ps) for _c, ps, _pt in docs))
    psrc = np.full((n_docs, P), -1, np.int32)
    ptgt = np.full((n_docs, P), -1, np.int32)
    for d, (_c, ps, pt) in enumerate(docs):
        psrc[d, : len(ps)], ptgt[d, : len(pt)] = ps, pt
    col = {k: np.stack([c[k] for c, _ps, _pt in docs]) for k in docs[0][0]}
    A = 32 if law == "collab" else 4
    flags = (col["action"] | (col["insert"] << 3)).astype(np.uint8)
    da = np.tile(np.arange(A, dtype=np.int32), (n_docs, 1))
    return (
        flags, col["slot"].astype(np.int8), col["ctr"], col["seq"],
        col["obj"], col["key"], col["ref"], col["value"], psrc, ptgt, da,
    ), A


_ARM_CASES = [
    # (law, docs): D of 8 fills doc blocks whole, 11 does not, 1 is a
    # live tick's
    ("forest", 8), ("chain", 8), ("collab", 8), ("pad", 8),
    ("forest", 11), ("chain", 1),
]


@pytest.mark.parametrize("n_rows", [256, 1024, 4096])
@pytest.mark.parametrize("law,n_docs", _ARM_CASES)
def test_rga_vmem_arm_equals_xla_arm(law, n_docs, n_rows, monkeypatch):
    import jax

    from hypermerge_tpu.ops import crdt_kernels as ck

    args, A = _arm_args(law, n_docs, n_rows, seed=n_rows + n_docs)
    assert not ck.rga_rounds_in_vmem(n_rows)  # tier-1 runs on the CPU
    want = jax.jit(ck.batched_kernel(A, 16))(*args)
    monkeypatch.setattr(ck, "rga_rounds_in_vmem", lambda n: True)
    got = jax.jit(ck.batched_kernel(A, 16))(*args)
    rank = np.asarray(want.rank)
    if law == "chain":  # the chain is walked to its end
        assert rank[0, 1] == n_rows - 1 and rank[0, n_rows - 1] == 1
    if law == "pad":
        assert not rank.any()
    for f in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
            err_msg=f,
        )


def test_rga_arm_selector(monkeypatch):
    """The VMEM arm is for a TPU backend, whole vregs of rows and no
    more rows than the threshold; everything else keeps XLA's gather."""
    from hypermerge_tpu.ops import crdt_kernels as ck

    top = ck.RGA_VMEM_MAX_ROWS
    assert not any(ck.rga_rounds_in_vmem(n) for n in (256, 1024, top))
    monkeypatch.setattr(ck, "_tpu_backend", lambda: True)
    assert all(ck.rga_rounds_in_vmem(n) for n in (128, 256, 1024, top))
    assert not any(ck.rga_rounds_in_vmem(n) for n in (64, 192, 2 * top))


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described v5e: the TPU's compiler without a TPU."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_vmem_arm_compiles_for_v5e_and_is_booked_under_rga_order(
    v5e_chip, monkeypatch
):
    """The lean slab program of a [16, 256] slab, compiled for a v5e
    with the VMEM arm selected: Mosaic takes the kernel (the lane
    gather, the scratch tables), and `phases_of_hlo` books its custom
    call under `rga_order`, so `kernel.phase.rga_order_s` keeps reading
    the rounds."""
    import jax
    import jax.numpy as jnp

    from hypermerge_tpu.ops import crdt_kernels as ck

    monkeypatch.setattr(ck, "_tpu_backend", lambda: True)
    D, N, P = 16, 256, 64

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    i16 = jnp.int16
    avals = (
        aval((D, N), jnp.uint8), aval((D, N), jnp.int8), aval((D, N), i16),
        aval((D, N), i16), aval((D, N), i16), aval((D, N), i16),
        aval((D, P), i16), aval((D, P), i16), aval((D, 4), jnp.int32),
    )
    # a function of this test's own: jit's trace cache goes by the
    # function, and the program's own jit of the lean kernel must not
    # find, at this shape, the trace made here for a TPU (its Pallas
    # call not interpreted) when a later test installs such a slab
    def fn(*args, A, K):
        return ck.materialize_full_lean_device.__wrapped__(*args, A=A, K=K)

    hlo = (
        jax.jit(fn, static_argnames=("A", "K"))
        .lower(*avals, A=4, K=16).compile().as_text()
    )
    phases = ck.phases_of_hlo(hlo)
    calls = [
        line.split("=")[0].strip() for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert len(calls) == 1 and "rga_chase" in calls[0]
    assert phases[calls[0]] == "rga_order"


@pytest.mark.parametrize("n_rows", [256, 1024, 4096, 16384, 65536])
def test_vmem_arm_compiles_at_a_slab_of_every_rung(
    v5e_chip, monkeypatch, n_rows
):
    """The rounds' kernel alone, at the real width of a full slab of
    each rung the selector gives it (`bulk_loader.SLAB_CELLS` cells),
    compiled for a v5e: what the interpreter cannot show (tiling, the
    VMEM a block of docs' tables and the rounds' temporaries take)."""
    import jax
    import jax.numpy as jnp

    from hypermerge_tpu.backend.bulk_loader import SLAB_CELLS
    from hypermerge_tpu.ops import crdt_kernels as ck

    monkeypatch.setattr(ck, "_tpu_backend", lambda: True)
    assert ck.rga_rounds_in_vmem(n_rows)
    shape = (SLAB_CELLS // n_rows, n_rows)
    table = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=v5e_chip)
    forest = jax.ShapeDtypeStruct(shape, jnp.bool_, sharding=v5e_chip)
    hlo = (
        jax.jit(ck._rga_rank_vmem)
        .lower(table, table, table, forest).compile().as_text()
    )
    assert 'custom_call_target="tpu_custom_call"' in hlo


@pytest.mark.parametrize("n_rows", [1024, 4096, 16384])
def test_serve_advance_compiles_for_v5e_with_its_lanes_donated(
    v5e_chip, n_rows
):
    """The read tier's `advance` program (ISSUE 38), one a row bucket,
    at the rungs the docs of the yardstick store live at ([6, 4096]
    once written) and the one above, compiled for a v5e: the output
    takes the donated lanes' buffer (`input_output_alias`: 8 x n_rows
    int32 on the chip, six lanes in a tile of eight rows), so an
    advance allocates nothing."""
    import jax
    import jax.numpy as jnp

    from hypermerge_tpu.serve import kernels

    lanes = jax.ShapeDtypeStruct(
        (kernels.N_LANES, n_rows), jnp.int32, sharding=v5e_chip
    )
    desc = jax.ShapeDtypeStruct(
        (kernels.ADVANCE_OPS, kernels.DELTA_WIDTH), jnp.int32,
        sharding=v5e_chip,
    )
    compiled = (
        jax.jit(kernels._build_advance(), donate_argnums=(0,))
        .lower(lanes, desc).compile()
    )
    assert "input_output_alias" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes >= 6 * n_rows * 4


@pytest.mark.parametrize("kind,batch,n_rows", [
    ("seq_order", 16, 1024), ("seq_order", 4, 1024),
    ("counts", 256, 4096), ("counts", 16, 1024),
    ("map_lookup", 16, 1024),
])
def test_keyed_query_programs_compile_for_v5e(v5e_chip, kind, batch, n_rows):
    """The read tier's query programs (ISSUE 46: the container named by
    (object row, key) and resolved on the device) at the shapes the
    benchmark's cells dispatch them at (`reads.resident`'s mixed flush:
    seq_order at B 16 and 4 over [6, 1024] lanes; the sync cells' 256
    `len` reads: one keyed counts over [6, 4096]), compiled for a v5e:
    each returns the outputs of the kinds before it and its own."""
    import jax
    import jax.numpy as jnp

    from hypermerge_tpu.serve import kernels

    lanes = jax.ShapeDtypeStruct(
        (kernels.N_LANES, n_rows), jnp.int32, sharding=v5e_chip
    )
    q = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=v5e_chip)
    build = getattr(kernels, "_build_" + kind)
    compiled = jax.jit(build()).lower((lanes,) * batch, q, q).compile()
    shapes = [(o.shape, str(o.dtype)) for o in compiled.out_info]
    want = [((batch,), "int32"), ((batch,), "bool"),
            ((batch,), "int32"), ((batch,), "int32"),
            ((batch, n_rows), "int32")]
    assert shapes == want[:{"map_lookup": 2, "counts": 4, "seq_order": 5}[kind]]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
