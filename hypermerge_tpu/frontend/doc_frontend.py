"""DocFrontend — per-doc materialized state and change entry point.

Parity: reference src/DocFrontend.ts:23-192 — mode state machine
(pending -> read -> write), change fns queued until an actor id exists,
patches applied to the materialized state, new states fanned out to every
handle. The «blank -> preview -> final» sequence subscribers observe
matches the reference's change flow (src/DocFrontend.ts:135-150).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional

from .. import telemetry
from ..analysis.lockdep import make_rlock
from ..crdt.frontend_state import FrontendDoc
from ..crdt.patch import Patch
from ..utils.debug import log
from ..utils.ids import to_doc_url
from .handle import Handle

# patches no local change made, applied to a frontend's document
_M_REMOTE_PATCHES = telemetry.counter("frontend.remote_patches")


class DocFrontend:
    def __init__(self, repo_frontend, doc_id: str,
                 actor_id: Optional[str] = None) -> None:
        self._repo = repo_frontend
        self.doc_id = doc_id
        self.url = to_doc_url(doc_id)
        self.actor_id = actor_id
        self.mode = "pending" if actor_id is None else "write"
        self.front = FrontendDoc()
        self.seq = 1
        self.history = 0
        self._handles: List[Handle] = []
        self._change_queue: List[tuple] = []
        self._lock = make_rlock("front.doc")
        # lazy-ready (bulk open): the backend has this doc materialized
        # but the Ready (with its snapshot patch) is fetched only when a
        # reader actually wants the value — a 10k-doc open_many must not
        # decode 10k snapshots eagerly
        self._lazy_ready = False
        self._ready_requested = False
        self._interested = False  # a reader poked before BulkReady landed
        # seq of the local change whose backend echo is outstanding.
        # Committed state only advances via echo patches, so change fns
        # must run one-per-echo: in-process the echo returns before
        # change() does (unchanged behavior); cross-process (net/ipc.py)
        # later fns queue here instead of running against stale state.
        self._inflight: Optional[int] = None
        # the thread that is between taking an item off the queue and
        # having sent it (`_run_queue`): what another thread brings
        # then queues behind it
        self._runner: Optional[int] = None

    # ------------------------------------------------------------------

    def mark_lazy_ready(self) -> None:
        """BulkReady: the backend can serve Ready on demand; fetch now
        only if a reader already wants it (a poke recorded interest, a
        subscriber attached, or a value() is blocking)."""
        with self._lock:
            self._lazy_ready = True
            want = self._interested or any(
                h.value_fn is not None for h in self._handles
            )
        if want:
            self.request_ready()

    def request_ready(self) -> None:
        with self._lock:
            if self._ready_requested or self.mode != "pending":
                return
            self._ready_requested = True
        from .. import msgs

        self._repo.to_backend.push(msgs.open_msg(self.doc_id))

    def poke(self) -> None:
        """A reader wants the value: resolve a pending lazy-ready doc.
        Interest is recorded even before BulkReady lands (backend
        messages may drain on another thread), so mark_lazy_ready can
        honor it then."""
        with self._lock:
            if self.mode != "pending":
                return
            self._interested = True
            if not self._lazy_ready:
                return
        self.request_ready()

    def handle(self) -> Handle:
        h = Handle(self)
        with self._lock:
            self._handles.append(h)
            if self.mode != "pending":
                h.push(self.front.materialize(), self.history)
        return h

    def release_handle(self, h: Handle) -> None:
        with self._lock:
            if h in self._handles:
                self._handles.remove(h)

    def change(self, fn: Callable[[Any], None], message: str = "") -> None:
        # a lazy-ready doc must materialize before the change fn runs,
        # else the fn would build ops against a blank document
        self.poke()
        with self._lock:
            needs_actor = self.mode == "pending" or self.actor_id is None
            # behind whatever waits already: changes reach the backend
            # in the order `change` was called in
            wait = needs_actor or bool(self._change_queue) or (
                self._runner not in (None, threading.get_ident()))
            if wait:
                self._change_queue.append((fn, message))
        if needs_actor:
            # OUTSIDE self._lock: pushing to the backend queue can make
            # THIS thread the drainer of whatever is buffered there —
            # including another change's Request, which takes the
            # engine lock — while a tick holding the engine lock is
            # pushing a patch back into this doc's on_patch
            # (front.doc <-> live.engine AB/BA; caught by the first
            # HM_LOCKDEP=1 run over this tree). Queue callbacks for one
            # queue never run concurrently, so the append above is
            # already safely ordered.
            self._repo.needs_actor(self.doc_id)
        if wait:
            self._run_queue()
        else:
            self._run_change(fn, message)

    def in_turn(self, send: Callable[[], None]) -> None:
        """Send a read of this doc in the order it was asked for: after
        every change that waits in the queue (no actor yet, an echo
        outstanding) or is on its way out of it, whose `change` has
        returned to its caller but whose request the backend has not
        been sent. `send()` runs now when nothing waits, else on the
        thread that runs the queue, once what is ahead of it has been
        sent. One frontend's queue to the backend is first in, first
        out, so a read never overtakes a change made before it."""
        with self._lock:
            # (asked from inside this thread's own run of the queue, a
            # watch callback: what it follows is already on its way)
            wait = self._runner != threading.get_ident() and (
                bool(self._change_queue) or self._runner is not None)
            if wait:
                self._change_queue.append((None, send))
        if wait:
            self._run_queue()
        else:
            send()

    def _run_queue(self) -> None:
        """Run what waits in the queue, in order, until something stops
        it: an echo outstanding (its arrival resumes the queue), no
        Ready yet, a change at the head and no actor. One thread at a
        time (`_runner`): what arrives meanwhile queues behind and is
        run by that thread before it leaves."""
        while True:
            with self._lock:
                q = self._change_queue
                if (
                    self._runner is not None or not q
                    or self._inflight is not None
                    or self.mode == "pending"
                    or (q[0][0] is not None and self.actor_id is None)
                ):
                    return
                fn, message = q.pop(0)
                self._runner = threading.get_ident()
            try:
                if fn is None:
                    message()  # a read whose turn has come
                else:
                    self._run_change(fn, message, from_queue=True)
            finally:
                with self._lock:
                    self._runner = None

    def _run_change(
        self, fn: Callable, message: str, from_queue: bool = False
    ) -> None:
        # `frontend.change` is the whole local change as the caller
        # waits on it (in-process the request is applied before
        # send_request returns, unless another thread is handling the
        # backend's queue just then); its child is intent resolution:
        # the change fn run over a scratch mirror of the doc, O(doc
        # length)
        with telemetry.span("frontend.change", "frontend"):
            with self._lock:
                if self._inflight is not None:
                    # an echo is outstanding: the committed state this
                    # fn would read is stale — run it when the echo
                    # lands (a queued one keeps its place at the head)
                    if from_queue:
                        self._change_queue.insert(0, (fn, message))
                    else:
                        self._change_queue.append((fn, message))
                    return
                with telemetry.span(
                    "frontend.change.resolve", "frontend"
                ) as sp:
                    request, preview = self.front.change(
                        fn, self.actor_id, self.seq, message
                    )
                    sp.note(
                        ops=len(request.intents) if request else 0
                    )
                if request is None:
                    return
                self.seq += 1
                self._inflight = request.seq
            self._fan_out(preview)  # «change preview»
            self._repo.send_request(self.doc_id, request)

    def send_doc_message(self, contents: Any) -> None:
        self._repo.send_doc_message(self.doc_id, contents)

    # ------------------------------------------------------------------
    # backend messages

    def on_ready(
        self,
        actor_id: Optional[str],
        patch_json: Optional[Dict],
        history: int,
    ) -> None:
        with self._lock:
            if self.mode != "pending":
                # Ready only initializes a pending doc (reference
                # DocFrontend.init, src/DocFrontend.ts:121-133). A doc
                # already reading/writing is AHEAD of this snapshot —
                # cross-process, the backend's Ready for a just-created
                # doc arrives after local optimistic changes, and
                # applying its blank snapshot would clobber them (the
                # backend's state reaches us through Patch echoes).
                return
            if patch_json is not None:
                self.front.apply_patch(Patch.from_json(patch_json))
            if actor_id is not None:
                self.actor_id = actor_id
                self.seq = self.front.clock.get(actor_id, 0) + 1
            self.history = history
            self.mode = "write" if self.actor_id else "read"
        self._fan_out(self.front.materialize())
        # (a change queued before this Ready on a doc that has no actor
        # yet, a bulk-opened doc's first write from one thread while
        # another drains the backend's queue, stays queued: its
        # NeedsActorId is on its way and on_actor_id runs it; run here
        # it would be a change of no actor)
        self._run_queue()

    def on_actor_id(self, actor_id: str) -> None:
        with self._lock:
            if self.mode == "write" and actor_id == self.actor_id:
                # duplicate notification (a NeedsActorId raced the Ready
                # that already enabled writes): resetting seq from the
                # clock here would corrupt the counter while a change's
                # echo is still in flight — the next request would reuse
                # its seq, be rejected by the backend, and strand the
                # in-flight queue forever
                return
            self.actor_id = actor_id
            if self.mode == "pending":
                # Ready (with the snapshot patch) hasn't landed: flipping
                # to write now would run queued change fns against a
                # blank doc. on_ready runs them once state exists —
                # matching the reference, where setActorId only enables
                # writes on an initialized doc (src/DocFrontend.ts:110-119).
                return
            self.seq = self.front.clock.get(actor_id, 0) + 1
            self.mode = "write"
        self._run_queue()

    def on_patch(self, patch_json: Dict, history: int) -> None:
        sp = telemetry.NOOP
        try:
            with self._lock:
                if self.mode == "pending":
                    # A patch can only precede this doc's Ready in
                    # the queue when the backend announced between
                    # emitting the patch and pushing the Ready — and
                    # that Ready snapshot (computed under the
                    # live-engine lock, AFTER every earlier emission)
                    # already contains the patch's effects. Applying
                    # it to the blank doc would corrupt the baseline
                    # and silently poison every later patch.
                    return
                patch = Patch.from_json(patch_json)
                if patch.actor is None:
                    _M_REMOTE_PATCHES.add(1)
                    # no local change's echo: what a peer's changes
                    # cost here, the handles' new value included
                    sp = telemetry.begin(
                        "frontend.remote_patch", "repo",
                        diffs=len(patch.diffs),
                    )
                self.front.apply_patch(patch)
                self.history = history
                if (
                    self._inflight is not None
                    and patch.actor == self.actor_id
                    and patch.seq == self._inflight
                ):
                    self._inflight = None
                empty = patch.is_empty
            if not empty:
                # «change final» echo
                self._fan_out(self.front.materialize())
        finally:
            sp.end()
        # the echo resumes the queue: the next change runs (one per
        # echo: committed state only advances by echoes), and the reads
        # that waited behind the one just echoed. A no-op change fn
        # makes no request and leaves _inflight unset: the queue runs on
        self._run_queue()

    def on_message(self, contents: Any) -> None:
        with self._lock:
            handles = list(self._handles)
        for h in handles:
            h.push_message(contents)

    def on_progress(self, progress: Dict) -> None:
        with self._lock:
            handles = list(self._handles)
        for h in handles:
            h.push_progress(progress)

    # ------------------------------------------------------------------

    def _fan_out(self, state: Any) -> None:
        with self._lock:
            handles = list(self._handles)
            history = self.history
        for h in handles:
            h.push(state, history)

    @property
    def clock(self):
        return dict(self.front.clock)
