"""The one seam through which the program records a span.

A span is live when either of two sinks is on, and goes to both:

- the **ring**: begin/end windows with tags in a fixed-capacity ring
  (HM_TRACE_RING events, default 65536) — a long-running daemon keeps
  the LAST N events, never unbounded memory. Export renders Chrome
  trace-event JSON (Perfetto / chrome://tracing) via telemetry.export.
  For operators without a chip trace.
- the **profiler**: while a ``jax.profiler`` session runs
  (``TraceAnnotation.is_enabled()``), the span is also entered as a
  ``jax.profiler.TraceAnnotation(name, **ids)``, so it lands in the
  ``.xplane.pb`` beside the device ops, on the device's clock. This
  module never imports jax: it looks the class up only once ``jax``
  is already in ``sys.modules``.

Cause and request: a span entered with ``with`` becomes the context of
its thread, and spans begun under it inherit its ``open`` (the request
id of one cold open, ``open_id()``) and ``slab`` ids; same-thread
nesting is the parent. A span whose cause ran on another thread says
so itself (``parent=<name>``). A span entered with ``with`` also
collects the seconds of the spans that ran under it on its thread, by
name (``kids``), so a stage can feed a stat from its child spans' own
clock readings.

Two clocks: while a sink is live a span reads its thread's CPU clock
(``time.thread_time_ns``: CLOCK_THREAD_CPUTIME_ID, native code that
runs on the thread with the GIL dropped included) beside the wall
clock at each end. The CPU seconds go with the span: ``cpu`` on the
handle beside ``dur``, the integer stat ``cpu_us`` in the profiler
sink (set at the end, as ``note()`` sets tags), the Chrome field
``tdur`` in the exported ring. A span has NO CPU value, never a wrong
one, when it ended on another thread than it began on (``serve.read``:
begun by the caller, ended by the flusher), when it was made with both
sinks off, or where the platform has no ``thread_time_ns``. Wall minus
CPU is the time the thread stood without the CPU: for a span whose
body is Python and numpy that is waiting for the GIL (or for a page to
come in); for a span around a blocking call (a fetch from the device,
a queue, a file) it is that call's wait, and says nothing of the GIL.
The CPU clock's resolution is the platform's: nanoseconds on Linux
proper, 10 ms ticks under gVisor (the chip machine's sandbox kernel),
where a short span reads 0 or 10,000 us and only a sum over many
spans, or a span of seconds, is a measurement.

With both sinks off ``span()`` checks two flags and returns a shared
no-op singleton — no object allocation, no timestamp read. ``timed()``
is for a stage whose seconds also feed a stat: it reads the wall clock
once at each end whether or not a sink is on, and the CPU clock only
while one is. Enable the ring with:

- ``HM_TRACE=<path>`` in the environment (read at import): tracing on
  for the process lifetime, the trace file written at exit (atexit)
  and on explicit ``flush()``.
- ``enable(path=None)`` at runtime (tests, tools). ``path=None`` keeps
  the ring in memory only (``events()`` reads it).

Recording is lock-free on the hot path: a global monotone sequence
(itertools.count — atomic in CPython) claims a slot, and the slot
assignment is a single list-item store. Wraparound overwrites the
oldest slot; ``events()`` reorders by sequence.
"""

from __future__ import annotations

import gc
import itertools
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .registry import REGISTRY

# event tuples: (seq implicit via slot, ph, name, cat, ts_us, dur_us,
# tid, args, cpu_us) — converted to Chrome dicts at export time
# (export.py); cpu_us is None where the span has no CPU value
EventT = Tuple[str, str, str, float, float, int, Optional[Dict],
               Optional[float]]

# the calling thread's CPU clock, where the platform has one
_thread_ns = getattr(time, "thread_time_ns", None)


def _ring_capacity() -> int:
    try:
        return max(16, int(os.environ.get("HM_TRACE_RING", "65536")))
    except ValueError:
        return 65536


class _Ring:
    __slots__ = ("cap", "_buf", "_seq")

    def __init__(self, cap: int) -> None:
        self.cap = cap
        self._buf: List[Optional[Tuple[int, EventT]]] = [None] * cap
        self._seq = itertools.count()

    def add(self, ev: EventT) -> None:
        i = next(self._seq)  # atomic claim
        self._buf[i % self.cap] = (i, ev)

    def events(self) -> List[EventT]:
        got = [s for s in list(self._buf) if s is not None]
        got.sort(key=lambda s: s[0])
        return [ev for _i, ev in got]

    def __len__(self) -> int:
        return sum(1 for s in self._buf if s is not None)


class _Tracer:
    def __init__(self) -> None:
        self.on = False
        self.path: Optional[str] = None
        self.ring = _Ring(_ring_capacity())
        self.t0 = time.perf_counter()
        self.tid_names: Dict[int, str] = {}
        self._tid_seen = threading.local()
        self._atexit = False


_T = _Tracer()


def enabled() -> bool:
    return _T.on


def enable(path: Optional[str] = None, capacity: Optional[int] = None):
    """Turn tracing on (idempotent). ``path`` is where ``flush()`` and
    the atexit hook write the Chrome trace; None keeps the ring
    memory-only."""
    if capacity is not None:
        _T.ring = _Ring(max(16, capacity))
    if path:
        _T.path = path
        if not _T._atexit:
            import atexit

            atexit.register(_atexit_flush)
            _T._atexit = True
    _T.on = True


def disable() -> None:
    _T.on = False


def reset() -> None:
    """Drop recorded events (tests); keeps the enabled flag/path."""
    _T.ring = _Ring(_T.ring.cap)
    _T.tid_names.clear()
    # threads must RE-register their names (the per-thread seen flag
    # would otherwise leave post-reset exports without thread labels)
    _T._tid_seen = threading.local()


def _note_thread() -> int:
    tid = threading.get_ident()
    seen = getattr(_T._tid_seen, "done", False)
    if not seen:
        _T.tid_names[tid] = threading.current_thread().name
        _T._tid_seen.done = True
    return tid


# ids a span hands down to the spans begun under it on its thread
_ID_KEYS = ("open", "slab")
_CTX = threading.local()  # .top: innermost span entered with `with`
_OPEN_SEQ = itertools.count(1)
_TA = None  # jax.profiler.TraceAnnotation, once jax is imported


def _profiling() -> bool:
    """Is a jax.profiler session recording? A static check on the
    annotation class; False while nothing has imported jax."""
    global _TA
    ta = _TA
    if ta is None:
        jax = sys.modules.get("jax")
        # mid-import `jax` has no `profiler` yet: look again next time
        ta = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
        if ta is None:
            return False
        _TA = ta
    return ta.is_enabled()


def open_id() -> int:
    """The request id of the cold open this thread works on (from the
    enclosing span that carries ``open=``), else the next of the
    per-process sequence."""
    top = getattr(_CTX, "top", None)
    if top is not None and top.args and "open" in top.args:
        return top.args["open"]
    return next(_OPEN_SEQ)


class SpanHandle:
    """An open span: ``end()`` records it and returns its seconds. Use
    via ``span()`` / ``timed()`` as a context manager, or ``begin()`` /
    ``end()`` across seams where the window opens and closes on
    different code paths. After the end ``dur`` holds the seconds,
    ``cpu`` the seconds its thread had the CPU in them (None where the
    span has no CPU value: module docstring) and ``kids`` the seconds,
    by name, of the spans that ran under it on its thread (those
    entered with ``with``)."""

    __slots__ = ("name", "cat", "args", "t0", "dur", "cpu", "kids",
                 "_ring", "_ann", "_up", "_c0", "_tid")

    def __init__(self, name: str, cat: str, args: Dict, ring: bool,
                 prof: bool):
        top = getattr(_CTX, "top", None)
        if top is not None and top.args:
            for k in _ID_KEYS:
                if k in top.args and k not in args:
                    args[k] = top.args[k]
        self.name = name
        self.cat = cat
        self.args = args or None
        self.dur = 0.0
        self.cpu: Optional[float] = None
        self.kids: Dict[str, float] = {}
        self._ring = ring
        self._up: Optional["SpanHandle"] = None
        # the annotation's clock starts where it is made
        self._ann = _TA(name, **args) if prof else None
        self.t0 = time.perf_counter()
        # the CPU clock is its thread's own: read only for a sink (and
        # inside the wall window, so cpu <= dur), and only comparable
        # on the thread that read it
        if (ring or prof) and _thread_ns is not None:
            self._tid = threading.get_ident()
            self._c0: Optional[int] = _thread_ns()
        else:
            self._c0 = None

    def note(self, **more: Any) -> None:
        """Tags known only once the work is done (`docs=`, `ops=`)."""
        self.args = {**(self.args or {}), **more}
        if self._ann is not None:
            self._ann.set_metadata(**more)

    def end(self, **more: Any) -> float:
        cpu_us = None
        if self._c0 is not None and threading.get_ident() == self._tid:
            cpu_ns = _thread_ns() - self._c0
            self.cpu = cpu_ns / 1e9
            cpu_us = cpu_ns / 1e3
        self.dur = time.perf_counter() - self.t0
        if more:
            self.note(**more)
        ann = self._ann
        if ann is not None:
            self._ann = None
            if cpu_us is not None:
                ann.set_metadata(cpu_us=int(cpu_us))
            ann.__exit__(None, None, None)
        if self._ring and _T.on:
            _T.ring.add((
                "X",
                self.name,
                self.cat,
                (self.t0 - _T.t0) * 1e6,
                self.dur * 1e6,
                _note_thread(),
                self.args,
                cpu_us,
            ))
        return self.dur

    # context-manager protocol: the span is its thread's context
    def __enter__(self) -> "SpanHandle":
        self._up = getattr(_CTX, "top", None)
        _CTX.top = self
        return self

    def __exit__(self, *exc) -> None:
        up = _CTX.top = self._up
        self._up = None
        dur = self.end()
        if up is not None:  # hand up own seconds and the descendants'
            kids = up.kids
            kids[self.name] = kids.get(self.name, 0.0) + dur
            for name, secs in self.kids.items():
                kids[name] = kids.get(name, 0.0) + secs


class _NoopSpan:
    """The shared disabled span: no allocation, no clock read."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def note(self, **more: Any) -> None:
        pass

    def end(self, **more: Any) -> float:
        return 0.0


NOOP = _NoopSpan()


def span(name: str, cat: str = "", **args: Any):
    """A context manager timing one section into the live sinks. With
    ring and profiler both off it is the shared no-op singleton."""
    ring, prof = _T.on, _profiling()
    if not (ring or prof):
        return NOOP
    return SpanHandle(name, cat, args, ring, prof)


# Open a span to be closed by ``handle.end()`` later (possibly on
# another code path); it does not become its thread's context.
begin = span


def timed(name: str, cat: str = "", **args: Any) -> SpanHandle:
    """A span whose seconds the caller needs too (a stage that feeds a
    stat or a counter): always a real handle, the wall clock read once
    at each end (the CPU clock only while a sink is on), recorded only
    into the sinks that are on."""
    return SpanHandle(name, cat, args, _T.on, _profiling())


def instant(name: str, cat: str = "", **args: Any) -> None:
    """A point event (demotions, resync closures, faults)."""
    if _profiling():
        _TA(name, **args).__exit__(None, None, None)
    if not _T.on:
        return
    _T.ring.add((
        "i",
        name,
        cat,
        (time.perf_counter() - _T.t0) * 1e6,
        0.0,
        _note_thread(),
        args or None,
        None,
    ))


# -- the interpreter's full collections ---------------------------------
# A generation-2 collection stops every thread for as long as it walks
# the heap (0.2-0.8 s with a 10k-doc store open): a stall no stage owns.
# One hook times them as `host.gc` spans and counts them; generations 0
# and 1 (thousands a second, microseconds each) return at once.

_GC_SPAN: List[Optional[SpanHandle]] = [None]
# monotone totals kept as gauges: the hook can run inside any
# allocation, under any lock, so it takes none (Gauge.set is one
# assignment; a Counter's first add on a thread locks)
_GC_FULL = REGISTRY.gauge("host.gc_full")
_GC_FULL_S = REGISTRY.gauge("host.gc_full_s")


def _gc_hook(phase: str, info: Dict[str, int]) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        _GC_SPAN[0] = timed("host.gc", "host", gen=2)
        return
    sp, _GC_SPAN[0] = _GC_SPAN[0], None
    if sp is None:
        return
    dt = sp.end(collected=info.get("collected", 0))
    # collections never overlap, so read-then-set loses nothing
    _GC_FULL.set(_GC_FULL.value() + 1)
    _GC_FULL_S.set(_GC_FULL_S.value() + dt)


def install_gc_hook() -> None:
    """Idempotent; the telemetry package calls it once at import."""
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)


def events() -> List[EventT]:
    """Recorded events, oldest first (ring order)."""
    return _T.ring.events()


def event_count() -> int:
    return len(_T.ring)


def trace_path() -> Optional[str]:
    return _T.path


def flush(path: Optional[str] = None) -> Optional[str]:
    """Write the ring as Chrome trace JSON to ``path`` (default: the
    enable()/HM_TRACE path). Returns the path written, or None when
    there is nowhere to write."""
    out = path or _T.path
    if out is None:
        return None
    from .export import write_chrome_trace

    write_chrome_trace(out, events(), dict(_T.tid_names))
    return out


def _atexit_flush() -> None:
    try:
        flush()
    except Exception:
        pass  # never fail interpreter shutdown over a trace file


def _maybe_enable_from_env() -> None:
    v = os.environ.get("HM_TRACE", "")
    if v and v != "0":
        # HM_TRACE=<path>: run-long trace file. A bare "1" enables the
        # in-memory ring without a file.
        enable(None if v == "1" else v)


_maybe_enable_from_env()
