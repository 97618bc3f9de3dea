"""On-device pack kernel: the cold open's last host compute as a jitted
prefix-scatter (HM_DEVICE_PACK=1).

Rung 2 of the parallel pack plane (rung 1 is the HM_PACK_WORKERS thread
pool in backend/pipeline.py). Instead of scattering the padded [Dp, N]
column planes on host — hm_pack_prefix in C++, or the numpy twin — the
host only CONCATENATES the raw narrow feed planes into [M] int32
vectors (memcpy-bound, so the host pack stage is O(IO)), uploads them,
and ONE jitted program derives every wire column (obj/ref row
resolution, key/value global LUT remaps, writer broadcast) and scatters
it into the padded planes on device. Programs live in the PR-7 shared
program table under ("pack", Mp, Dp, N, row_dt, kdt, lut-lens) keys;
every axis buckets to pow2, so a corpus sweep reuses a handful of
executables and sharded.trace_counts pins the one-trace contract.

Placement rides the mesh: the bulk loader passes the chip strict
round-robin will dispatch the slab to (SlabRoundRobin.pack_device_for),
so the packed columns are born on the chip that materializes them.

Bit-identity contract: the planes returned are byte-equal to the host
twins' _pack_wire_dtypes output (the fuzz matrix in
tests/test_native_pack.py pins numpy == native == device). Pad rows
scatter into a scratch slot (index Dp*N of a Dp*N+1 flat buffer, sliced
off) and carry value 0 / vkind VK_NONE, so the device value min/max
over the padded [Mp] vector matches the host twins' min(initial=0) /
max(initial=0) and the value plane's int16-vs-int32 wire decision is
identical. LUT gathers clamp to the padded table like the numpy twin
clamps to the real one — out-of-range lanes are discarded by the same
where() masks, so the clamp bound never reaches the output.

Anything the kernel can't serve — no jax, no device, a tracing failure
— returns {} and the caller (ops/columnar._pack_prefix_single)
falls through native -> numpy, so HM_DEVICE_PACK=1 on a host-only box
degrades to exactly today's path; fallbacks are a counter, never an
error (telemetry pack.device_fallbacks).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import numpy as np

from .. import telemetry
from ..utils.debug import log

_M_PACKS = telemetry.counter("pack.device_packs")
_M_FALLBACKS = telemetry.counter("pack.device_fallbacks")

# source plane order the kernel consumes (matches the native entry's
# _PACK_SRC_PLANES so the marshalling loop is the same shape)
_SRC_PLANES = (
    "action", "ctr", "seq", "obj_ctr", "obj_a", "key",
    "ref_ctr", "ref_a", "insert", "vkind", "value", "dt",
)


def device_pack_enabled() -> bool:
    """HM_DEVICE_PACK=1 opts the fast pack path onto the device kernel.
    Default off: the host native pack is faster below the transfer
    break-even and is always available."""
    return os.environ.get("HM_DEVICE_PACK", "0") == "1"


def _round_up_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _build_pack(Mp, Dp, N, row16, key16, PAD, OBJ_ROOT, REF_HEAD,
                REF_NONE, VK_STR, VK_FLOAT, VK_BIGINT, COLUMNS):
    """The traced pack program for one shape bucket. Every operand is a
    trace constant except the [Mp] planes and pow2-padded LUTs."""
    import jax.numpy as jnp

    rdt = jnp.int16 if row16 else jnp.int32
    kt = jnp.int16 if key16 else jnp.int32
    L = Dp * N + 1  # +1 scratch slot: pad rows land there, sliced off
    defaults = {"action": PAD, "obj": -1, "key": -1, "ref": REF_NONE}
    out_dt = {
        "action": jnp.uint8, "insert": jnp.uint8, "vkind": jnp.uint8,
        "dt": jnp.uint8, "actor": jnp.int32, "ctr": rdt, "seq": rdt,
        "obj": rdt, "key": kt, "ref": rdt, "value": jnp.int32,
    }

    def fn(action, ctr, seq, obj_ctr, obj_a, key, ref_ctr, ref_a,
           insert, vkind, value, dt, flat_idx, actor_rows,
           koff, soff, foff, boff, klut, slut, flut, blut):
        # -- derived columns, in wire dtypes (cast-then-subtract so the
        # int16 arithmetic matches the numpy twin bit for bit) ---------
        obj_row = jnp.where(
            obj_a == 0, obj_ctr.astype(rdt) - 1, rdt(OBJ_ROOT)
        )
        ref_row = jnp.where(
            ref_a == 0,
            ref_ctr.astype(rdt) - 1,
            jnp.where(ref_a == -2, rdt(REF_HEAD), rdt(REF_NONE)),
        )
        kidx = jnp.clip(koff + key, 0, klut.shape[0] - 1)
        key_g = jnp.where(key >= 0, klut[kidx].astype(kt), kt(-1))
        value_g = value
        for code, lut, off in (
            (VK_STR, slut, soff),
            (VK_FLOAT, flut, foff),
            (VK_BIGINT, blut, boff),
        ):
            idx = jnp.clip(off + value, 0, lut.shape[0] - 1)
            value_g = jnp.where(vkind == code, lut[idx], value_g)
        # pad rows carry value 0 / vkind VK_NONE, so folding 0 in makes
        # the reduction equal the host twins' min(initial=0) even when
        # M == Mp (no pad rows at all)
        vmin = jnp.minimum(value_g.min(), 0).astype(jnp.int32)
        vmax = jnp.maximum(value_g.max(), 0).astype(jnp.int32)

        sources = {
            "action": action, "actor": actor_rows, "ctr": ctr,
            "seq": seq, "obj": obj_row, "key": key_g, "ref": ref_row,
            "insert": insert, "vkind": vkind, "value": value_g,
            "dt": dt,
        }
        outs = []
        for name in COLUMNS:
            dtv = out_dt[name]
            flat = jnp.full(L, defaults.get(name, 0), dtv)
            flat = flat.at[flat_idx].set(sources[name].astype(dtv))
            outs.append(flat[: L - 1].reshape(Dp, N))
        return tuple(outs) + (vmin, vmax)

    return fn


def _pack_program(Mp, Dp, N, row16, key16, lut_lens):
    import jax

    from ..parallel import sharded
    from ..storage.colcache import (
        OBJ_ROOT, REF_HEAD, REF_NONE, VK_BIGINT, VK_FLOAT, VK_STR,
    )
    from .columnar import COLUMNS, PAD

    key = ("pack", Mp, Dp, N, row16, key16) + lut_lens
    return sharded._program(
        key,
        lambda: jax.jit(
            sharded._traced(
                key,
                _build_pack(
                    Mp, Dp, N, row16, key16, PAD, OBJ_ROOT, REF_HEAD,
                    REF_NONE, VK_STR, VK_FLOAT, VK_BIGINT, COLUMNS,
                ),
            )
        ),
    )


def _m_vec(a, Mp, fill=0) -> np.ndarray:
    """[M] -> [Mp] int32, pow2-padded with `fill`."""
    out = np.full(Mp, fill, np.int32)
    out[: len(a)] = a
    return out


def _lut_vec(a) -> np.ndarray:
    """Flat LUT -> pow2-padded int32 (global interner ids fit int32)."""
    n = _round_up_pow2(max(len(a), 1))
    out = np.zeros(n, np.int32)
    out[: len(a)] = a
    return out


def device_pack_prefix(
    fcs, fc_idx, fc_idx_a, ends, writer_g, flat_lut,
    D, Dp, N, i16ok, row_dt, kdt, device=None,
) -> Dict[str, np.ndarray]:
    """Device twin of columnar._native_pack_prefix: same operands, same
    {} -> fall-through contract, planes byte-identical to the host
    twins. The host side is pure marshalling — narrow-plane concats and
    int32 casts into [Mp] vectors — and the scatter/remap compute rides
    the jitted program (on `device` when the mesh scheduler predicted
    the slab's chip, the default device otherwise)."""
    if not device_pack_enabled():
        return {}
    try:
        import jax
    except Exception:
        return {}
    from ..storage.colcache import PLANE_NAMES

    try:
        # -- marshal [M] source vectors (the only host compute) --------
        use_planes = all(fc.planes is not None for fc in fcs)
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

            def col(name):
                return R[:, PLANE_NAMES.index(name)]

        # the same corrupt-sidecar guard the native entry applies
        feed_rows = np.asarray([fc.n_rows for fc in fcs], np.int64)
        if np.any(ends > feed_rows[fc_idx_a]):
            return {}

        M = int(ends.sum())
        Mp = _round_up_pow2(max(M, 1))
        doc_col = np.repeat(np.arange(D, dtype=np.int64), ends)
        doc_starts = np.zeros(D + 1, np.int64)
        np.cumsum(ends, out=doc_starts[1:])
        pos = np.arange(M, dtype=np.int64) - doc_starts[doc_col]
        # pad rows scatter into the program's scratch slot Dp*N
        flat_idx = _m_vec(doc_col * N + pos, Mp, fill=Dp * N)

        planes = [_m_vec(col(n), Mp) for n in _SRC_PLANES]
        actor_rows = _m_vec(np.repeat(writer_g[fc_idx_a], ends), Mp)
        klut, koffs = flat_lut("k")
        slut, soffs = flat_lut("s")
        flut, foffs = flat_lut("f")
        blut, boffs = flat_lut("b")
        offs_rows = [
            _m_vec(np.repeat(o[fc_idx_a], ends), Mp)
            for o in (koffs, soffs, foffs, boffs)
        ]
        luts = [_lut_vec(t) for t in (klut, slut, flut, blut)]

        fn = _pack_program(
            Mp, Dp, N, bool(i16ok), kdt == np.int16,
            tuple(t.shape[0] for t in luts),
        )
        args = planes + [flat_idx, actor_rows] + offs_rows + luts
        if device is not None:
            args = [jax.device_put(a, device) for a in args]
        out = fn(*args)

        # -- back to host wire planes (value dtype decided by minmax) --
        from .columnar import COLUMNS, _pack_wire_dtypes

        vmin, vmax = int(out[-2]), int(out[-1])
        dtypes = _pack_wire_dtypes(i16ok, row_dt, kdt, vmin, vmax)
        cols: Dict[str, np.ndarray] = {}
        for ci, name in enumerate(COLUMNS):
            arr = np.asarray(out[ci])
            if arr.dtype != np.dtype(dtypes[name]):
                arr = arr.astype(dtypes[name])
            cols[name] = arr
        _M_PACKS.add(1)
        return cols
    except Exception as e:  # degrade, never fail the load
        _M_FALLBACKS.add(1)
        log("ops:pack", f"device pack fell back to host: {e}")
        return {}
