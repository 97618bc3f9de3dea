"""Reader `trace_scope_time`: device seconds of the matched programs'
operations that belong to one named scope of the program (a phase of
the slab kernel, hypermerge_tpu/ops/crdt_kernels.py `PHASES`), divided
by obs[`per`] where given.
params: {"match": ["materialize_full"], "scope": "rga_order",
"shapes": "bulk_slabs", "per": "traced_opens"}.

A device trace names an operation by its HLO instruction and carries
no scope ("XLA Ops" events of a v5e trace hold the instruction text,
an offset and a duration). So the map from instruction to scope is the
program's: `crdt_kernels.phase_of_ops(n_docs, n_rows, lean)` compiles
the same program for a slab's shape (so the same instruction names as
the executable of the trace; cached, with the scopes in the key) and
reads each instruction's `op_name`, looking inside fused computations:
one scope inside gives that scope, two or more `mixed`, none
`unscoped`; an operation of the trace that the compiled program does
not name is logged and booked `unscoped`. The k-th matched program of the
trace ran the k-th slab of obs[`shapes`] (slabs are dispatched in
order; the list repeats for each traced open).

Nothing is dropped: scope `mixed` is the sum of `mixed`, `unscoped`
and the part of each program's time that no operation event covers, so
the scopes add up to the programs' time (`kernel.bulk_s_per_open`).
The three parts are logged apart. A program without `phase_of_ops`
(before PR 24) gives None, as does a shape it never dispatched.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

from benchmark.harness import log
from benchmark.readers import span_tree


def _instruction(event_name: str) -> str:
    """`%fusion.27 = s32[...] fusion(...)` -> `fusion.27`."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def scope_seconds(modules, ops, shapes, match, phase_of_ops
                  ) -> Optional[Dict[str, float]]:
    """{scope: seconds} over the matched program events. `modules` and
    `ops` are [(name, start, duration)] of one device, any one unit."""
    hit = sorted((m for m in modules if any(k in m[0] for k in match)),
                 key=lambda m: m[1])
    if not hit or not shapes:
        return None
    ops = sorted(ops, key=lambda o: o[1])
    out: Dict[str, float] = {}
    for k, (name, start, dur) in enumerate(hit):
        n_docs, n_rows = shapes[k % len(shapes)]
        phases = phase_of_ops(int(n_docs), int(n_rows), "lean" in name)
        if not phases:
            log(f"trace_scope_time: no program for slab {n_docs}x{n_rows}")
            return None
        phases = {k2.lstrip("%"): v for k2, v in phases.items()}
        covered = unknown = 0.0
        for op_name, s, d in ops:
            if s < start or s >= start + dur:
                continue
            scope = phases.get(_instruction(op_name))
            if scope is None:  # not an instruction of that program
                scope, unknown = "unscoped", unknown + d
            out[scope] = out.get(scope, 0.0) + d
            covered += d
        out["uncovered"] = out.get("uncovered", 0.0) + max(0.0, dur - covered)
        if unknown:
            log(f"trace_scope_time: {unknown:.4f}s of {name} ran in "
                "operations the compiled program does not name")
    return out


@functools.lru_cache(maxsize=2)
def _from_trace(path: str, shapes: Tuple[Tuple[int, int], ...],
                match: Tuple[str, ...]) -> Optional[Dict[str, float]]:
    from hypermerge_tpu.ops import crdt_kernels

    phase_of_ops = getattr(crdt_kernels, "phase_of_ops", None)
    if phase_of_ops is None:
        return None
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not span_tree.DEVICE0.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Modules" not in lines or "XLA Ops" not in lines:
            return None
        events = {
            n: [(e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                for e in lines[n].events]
            for n in ("XLA Modules", "XLA Ops")
        }
        got = scope_seconds(events["XLA Modules"], events["XLA Ops"],
                            shapes, match, functools.lru_cache(None)(
                                phase_of_ops))
        if got is not None:
            log("kernel seconds by scope: " + ", ".join(
                f"{k} {v:.4f}" for k, v in sorted(
                    got.items(), key=lambda kv: -kv[1])))
        return got
    return None


def read(params, obs):
    path = span_tree.newest_trace()
    shapes = obs.get(params["shapes"])
    if path is None or not shapes:
        return None
    got = _from_trace(path, tuple((int(d), int(n)) for d, n in shapes),
                      tuple(params["match"]))
    if got is None:
        return None
    scope = params["scope"]
    secs = got.get(scope, 0.0)
    if scope == "mixed":
        secs += got.get("unscoped", 0.0) + got.get("uncovered", 0.0)
    per = obs.get(params["per"], 1) if params.get("per") else 1
    return secs / max(1, per)
