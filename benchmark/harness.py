"""What every cell shares: finding files by name, the device check, the
compile-cache watch, the traced sub-window, the per-layer readers and
the result line. A driver (benchmark/drivers/<name>.py) supplies

    setup(cell)                 -> state       (counted as set-up)
    window(cell, state, secs)   -> Window      (the measured window)
    verify(cell, state, window) -> [Check]     (after the window)
    teardown(cell, state)

and nothing else of a cell lives in code: its sizes are the config
file's, its load the mix file's, its per-layer metrics the files under
layer_metrics/.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(*a) -> None:
    print("[bench]", *a, file=sys.stderr, flush=True)


def load_json(*parts: str) -> Any:
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, by name. `name` comes from a data
    file, so it is checked before it reaches the import system."""
    if not name.replace("_", "").isalnum():
        raise SystemExit(f"benchmark: bad {kind} name {name!r}")
    if not os.path.isfile(os.path.join(HERE, kind, name + ".py")):
        raise SystemExit(f"benchmark: no {kind}/{name}.py")
    return importlib.import_module(f"benchmark.{kind}.{name}")


@dataclass
class Check:
    """One number compared after the window, beside its limit. Exact
    comparisons count mismatches and have the limit 0."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Window:
    metrics: Dict[str, float]  # end-to-end values by name
    attempted: int
    failed: int
    obs: Dict[str, Any] = field(default_factory=dict)  # for the readers


class CacheWatch:
    """Compile requests of this process, and how many the persistent
    cache answered (jax.monitoring events; copy of chip_smoke.py's).
    `mark()` splits the count at the start of the window."""

    def __init__(self) -> None:
        import jax

        self.requests = 0
        self.hits = 0
        self._mark = (0, 0)
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> None:
        self._mark = (self.requests, self.hits)

    def report(self) -> Dict[str, Dict[str, int]]:
        r0, h0 = self._mark
        return {
            "setup": {"requests": r0, "hits": h0, "misses": r0 - h0},
            "window": {
                "requests": self.requests - r0,
                "hits": self.hits - h0,
                "misses": (self.requests - r0) - (self.hits - h0),
            },
        }


class Tracer:
    """The traced sub-window of a `--trace 1` run. Drivers call
    `start()` / `stop()`; with `--trace 0` both do nothing."""

    def __init__(self, on: bool, out_dir: str) -> None:
        self.on = on
        self.dir = out_dir
        self.started = False
        self.path: Optional[str] = None
        self._lock = threading.Lock()

    def start(self) -> None:
        with self._lock:
            if not self.on or self.started or self.path:
                return
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # our annotations only
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.started = True

    def stop(self) -> None:
        with self._lock:
            if not self.started:
                return
            import glob

            import jax

            jax.profiler.stop_trace()
            self.started = False
            found = sorted(glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"
            )))
            self.path = found[-1] if found else None

    def close(self) -> None:
        self.stop()


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's own trace, around a call into one
    layer (`bench.<layer>.<what>`); the reduction names idle gaps by
    these. Costs nothing measurable when no trace runs."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class Cell:
    """One run of one cell: its files, its seed, its scratch space."""

    def __init__(self, args, bench: dict, t_start: float) -> None:
        self.args = args
        self.t_start = t_start
        self.name = args.workload
        entry = next(
            (w for w in bench["workloads"] if w["name"] == self.name), None
        )
        if entry is None:
            raise SystemExit(f"benchmark: no workload {self.name!r}")
        self.chips = int(entry["chips"])
        cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
        with open(os.path.join(ROOT, cfg["file"])) as fh:
            self.config = json.load(fh)
        self.mix = load_json("traffic", entry["traffic"] + ".json")
        self.bench = bench
        self.seed = int(args.seed)
        self.rehearse = bool(args.rehearse)
        self.control = bool(getattr(args, "control", False))
        if getattr(args, "mix", None):
            self.mix = _overlay(self.mix, json.loads(args.mix))
        if self.rehearse:  # tiny sizes, same code: counts only
            self.config = _overlay(self.config, self.config.get("rehearsal"))
            self.mix = _overlay(self.mix, self.mix.get("rehearsal"))
        # scratch inside the checkout, at a fixed path per cell: wiped
        # at both ends of a run, never part of the compile cache's key
        self.work = os.path.join(HERE, ".cache", "run-" + self.name)
        self.tracer = Tracer(
            bool(args.trace), os.path.join(HERE, ".cache", "trace-" + self.name)
        )
        self.cache_watch: Optional[CacheWatch] = None
        self.notes: Dict[str, Any] = {}  # set-up breakdown etc. (stderr)

    def corpus_job(self):
        """Start writing the config's corpus (its writer is found by
        name); the caller finishes it once JAX is up."""
        from hypermerge_tpu import native

        # loads the native layer, building it first in a checkout that
        # has only what git holds: once, here, before any pool worker
        # or thread could race to build it too
        if native.caps() == 0:
            raise SystemExit("benchmark: the native layer did not build")
        spec = self.config["corpus"]
        writer = load_module("corpora", spec["writer"])
        workers = min(int(spec.get("workers", 12)),
                      max(1, (os.cpu_count() or 2) - 1))
        return writer.CorpusJob(
            os.path.join(self.work, "repo"), spec, self.seed, workers
        ).start()

    def counters(self) -> Dict[str, float]:
        from hypermerge_tpu import telemetry

        return {
            k: v for k, v in telemetry.snapshot().items()
            if isinstance(v, (int, float))
        }


def _overlay(base: dict, over: Optional[dict]) -> dict:
    if not over:
        return base
    out = dict(base)
    for k, v in over.items():
        out[k] = (
            _overlay(out[k], v)
            if isinstance(v, dict) and isinstance(out.get(k), dict) else v
        )
    return out


def apply_env(cell: Cell) -> None:
    """The deployment's environment (config `env`) and, rehearsing, the
    overrides that let tiny docs take the device paths. Set before the
    program is imported; a real run sets nothing the config does not
    state."""
    env = dict(cell.config.get("env") or {})
    if cell.control:  # the fault of the control run, where it is an option
        env.update((cell.mix.get("control") or {}).get("env") or {})
    for k, v in env.items():
        os.environ[k] = str(v)


def device_or_exit(cell: Cell) -> Dict[str, Any]:
    """JAX's devices, or exit non-zero with no result: a run without
    the accelerator (or with fewer chips than the cell asks) measures
    nothing this benchmark reports."""
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    log("device:", info)
    want = "cpu" if cell.rehearse else "tpu"
    if d.platform != want or jax.default_backend() != want:
        log(f"FAILED: platform {d.platform!r}, this run needs {want!r}")
        raise SystemExit(3)
    if len(devs) < cell.chips:
        log(f"FAILED: {len(devs)} devices, the cell needs {cell.chips}")
        raise SystemExit(3)
    return info


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


class _GcPauses:
    """The interpreter's collections inside the window, by generation:
    a stall the trace cannot name (logged, not a metric)."""

    def __init__(self) -> None:
        self.n = [0, 0, 0]
        self.total = [0.0, 0.0, 0.0]
        self.worst = 0.0
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            dt = time.perf_counter() - self._t
            g = info["generation"]
            self.n[g] += 1
            self.total[g] += dt
            self.worst = max(self.worst, dt)

    def stop(self) -> Dict[str, Any]:
        gc.callbacks.remove(self._cb)
        return {"collections": self.n,
                "seconds": [round(t, 3) for t in self.total],
                "worst_s": round(self.worst, 3)}


def layer_metrics(cell: Cell, obs: Dict[str, Any]) -> Dict[str, Dict]:
    """Each per-layer metric this cell lists, through its own file and
    its reader. A reader that finds nothing returns None and the
    metric is left out of the line."""
    out: Dict[str, Dict] = {}
    for m in cell.bench["per_layer"]:
        if "workloads" in m and cell.name not in m["workloads"]:
            continue
        spec = load_json("layer_metrics", m["name"] + ".json")
        reader = load_module("readers", spec["reader"])
        value = reader.read(spec.get("params") or {}, obs)
        if value is None:
            log(f"layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(args, bench: dict, t_start: float) -> int:
    cell = Cell(args, bench, t_start)
    apply_env(cell)
    driver = load_module("drivers", cell.mix["driver"])
    shutil.rmtree(cell.work, ignore_errors=True)
    os.makedirs(cell.work, exist_ok=True)
    state = None
    try:
        state_early = driver.before_jax(cell) if hasattr(
            driver, "before_jax") else None
        try:
            device = device_or_exit(cell)
            cell.cache_watch = CacheWatch()
            state = driver.setup(cell, state_early)
        except BaseException:
            if state_early is not None and hasattr(state_early, "abort"):
                state_early.abort()
            raise
        # set-up ends as a long-lived server's start does: with the
        # garbage of start-up collected, so that no full collection of
        # the whole store's objects is owed inside the window
        gc.collect()
        pauses = _GcPauses()
        cell.cache_watch.mark()
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.2f}s: {cell.notes}")
        before = cell.counters()
        win: Window = driver.window(cell, state, float(args.seconds))
        cell.tracer.close()
        after = cell.counters()
        compiles = cell.cache_watch.report()  # before verify compiles
        log(f"window: compile {compiles['window']}, gc {pauses.stop()}")
        peak = memory_peak_bytes()
        t0 = time.perf_counter()
        checks: List[Check] = driver.verify(cell, state, win)
        log(f"verify {time.perf_counter() - t0:.2f}s")
    finally:
        cell.tracer.close()
        if state is not None:
            driver.teardown(cell, state)
        shutil.rmtree(cell.work, ignore_errors=True)

    for c in checks:
        log(f"check {c.name}: {c.value} (limit {c.limit})"
            + ("" if c.ok else "  <-- FAILED"))
    correct = bool(checks) and all(c.ok for c in checks)
    line: Dict[str, Any] = {
        "correct": correct,
        "attempted": int(win.attempted),
        "failed": int(win.failed),
        "metrics": {},
        "device": dict(device, memory_peak_bytes=peak),
        "checks": [
            {"name": c.name, "value": c.value, "limit": c.limit}
            for c in checks
        ],
        "workload": cell.name,
        "seed": cell.seed,
        "control": cell.control,
        "setup": cell.notes,
    }
    if cell.rehearse:
        # a rehearsal proves paths and counts; its clock readings are
        # the CPU's and are not printed under any metric's name
        line["rehearsal"] = True
        line["counts"] = {
            "attempted": win.attempted, "failed": win.failed,
            "compile": compiles,
        }
        print(json.dumps(line), flush=True)
        return 0 if correct else 1
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    if not args.trace:
        values = dict(win.metrics, setup_s=setup_s)
        for m in bench["end_to_end"]:
            if "workloads" in m and cell.name not in m["workloads"]:
                continue
            line["metrics"][m["name"]] = {
                "value": values[m["name"]], "unit": units[m["name"]],
            }
    else:
        from benchmark import trace_reduce

        if cell.tracer.path is None:
            log("FAILED: the traced run wrote no trace")
            return 4
        reduced = trace_reduce.reduce_file(cell.tracer.path)
        if reduced["busy_s"] <= 0:
            log("FAILED: no operation ran on the device in the trace")
            return 4
        obs = dict(win.obs)
        obs.update(
            trace=reduced, counters_before=before, counters_after=after,
            compile=compiles, device_kind=device["kind"],
            peaks=load_json("peaks.json"), setup_s=setup_s,
        )
        line["metrics"] = layer_metrics(cell, obs)
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["window_s"]
        line["breakdown"] = {
            "device_ops": reduced["top_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
        line["end_to_end_traced"] = dict(win.metrics, setup_s=setup_s)
    print(json.dumps(line), flush=True)
    return 0
