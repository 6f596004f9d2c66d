"""Per-request token streaming over the serving engine.

``ServingEngine.submit()`` returns a :class:`ResponseStream`: an
iterable that yields token ids the moment the pool's decode step emits
them, then ends; the terminal :class:`StreamStatus` record (finish
reason, token counts, timings) is available as ``stream.status`` /
``stream.result()`` afterwards.

The backing queue is BOUNDED at the request's own declared budget
(``max_new_tokens`` + the terminal marker): no request can buffer more
output than it was admitted for, so a slow consumer costs memory
proportional to what admission control already approved — never an
unbounded pile-up — and the engine's producer side can always
``put_nowait`` without risking a deadlock against its own step loop.

Iteration adapts to the engine's drive mode: under the background
step-loop thread it blocks on the queue (tokens arrive from the owning
thread); in synchronous ``pump()`` mode it drives ``engine.pump(1)``
itself between reads, so ``for tok in engine.submit(...)`` works
single-threaded and deterministically — the form every tier-1 test
uses.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Optional

from . import faults, trace

__all__ = ["RequestState", "ResponseStream", "StreamStatus"]


class RequestState:
    """Request lifecycle: QUEUED → PREFILLING → DECODING → terminal.

    ``PREEMPTED`` is a NON-terminal detour off DECODING: the scheduler
    evicted the request mid-decode (its K/V spilled to the host tier)
    and will resume it — the stream stays open, tokens already
    delivered stand, and the request returns to DECODING at resume.

    ``HANDED_OFF`` is terminal FOR THE TIER, not for the request: a
    prefill-role engine exported the request's K/V over the transfer
    contract and a decode-role engine now owns it (docs §5n).  The
    disaggregated front never surfaces it — its bridged stream keeps
    flowing across the hand-off — but tier-local observers (the
    journal, per-tier metrics) see the prefill tier's involvement end
    here."""

    QUEUED = "QUEUED"
    PREFILLING = "PREFILLING"
    DECODING = "DECODING"
    PREEMPTED = "PREEMPTED"
    DONE = "DONE"
    CANCELLED = "CANCELLED"
    EXPIRED = "EXPIRED"
    FAILED = "FAILED"
    HANDED_OFF = "HANDED_OFF"
    TERMINAL = frozenset({DONE, CANCELLED, EXPIRED, FAILED, HANDED_OFF})


# the terminal record delivered once per request: finish_reason is the
# decode layer's eos/length for DONE, else the scheduler's
# cancelled/deadline/error; ttft_s is None when the request never
# produced a token (expired in the queue, cancelled pre-admission).
# ttft_s and total_s run from ADMISSION (``submit()`` holding the engine
# lock): the wait for that lock is ``lock_wait_s``, to be added for what
# the caller saw; ``queue_wait_s`` is admission to the first slot-take
# (None when the request never took one).  Both are None on the
# statuses the fleet and the disaggregated front build themselves.
# ``commit_steps`` is set only by a pool that generates by diffusion over
# blocks: per token of ``tokens``, the denoising step of its block that
# committed it.
StreamStatus = collections.namedtuple(
    "StreamStatus",
    ["request_id", "state", "finish_reason", "tokens", "prompt_tokens",
     "new_tokens", "ttft_s", "total_s", "error", "lock_wait_s",
     "queue_wait_s", "commit_steps"],
    defaults=(None, None, None))

_TERMINAL = object()


class ResponseStream:
    """Iterable of one request's generated token ids + terminal status.

    Engine-side producers call ``_put_token``/``_finalize``; consumers
    iterate (or call :meth:`result`).  Thread-safe: the queue and the
    done-event are the only shared state."""

    def __init__(self, engine, request_id, max_new_tokens: int):
        self._engine = engine
        self.request_id = request_id
        # tokens <= max_new_tokens plus exactly one terminal marker, so
        # the producer can never block or overflow even if the consumer
        # never reads a single token
        self._q: queue.Queue = queue.Queue(maxsize=int(max_new_tokens) + 1)
        self._done = threading.Event()
        self._status: Optional[StreamStatus] = None
        # under a tracer only (serving/trace.py): when each token was
        # put, as (the token's place in the stream, the tracer's clock),
        # for the front to say what a line waited before it was flushed
        # (``http.stream``).  One writer, the thread that puts (the tick
        # thread, appending), and one reader, the thread that consumes
        # the stream (an HTTP handler, popping from the left): a deque
        # takes both without a lock.  None until a token is put under a
        # tracer: no tracer, no deque
        self._stamps: Optional[collections.deque] = None

    # -- engine side -----------------------------------------------------
    def _put_token(self, tok: int) -> None:
        # `stream.deliver` is the injection seam for delivery failures;
        # the engine delivers BEFORE committing a token, so a fault here
        # means recovery regenerates exactly this token (no loss, no
        # duplicate — see ServingEngine._on_tokens)
        faults.fire("stream.deliver")
        tr = trace.active()
        if tr is not None:
            if self._stamps is None:
                self._stamps = collections.deque()
            # the queue counts its puts (``unfinished_tasks``: nothing
            # here calls ``task_done``), so the token about to be put is
            # the stream's ``unfinished_tasks``-th, counted from 0
            self._stamps.append((self._q.unfinished_tasks, tr.now()))
        self._q.put_nowait(tok)

    def _finalize(self, status: StreamStatus) -> None:
        self._status = status
        self._q.put_nowait(_TERMINAL)
        self._done.set()

    # -- consumer side ---------------------------------------------------
    def _take_stamp(self, n: int) -> Optional[float]:
        """When the stream's ``n``-th token (from 0) was put, on the
        tracer's clock; None for a token put with no tracer installed.
        For the one consumer, once a token, in order."""
        stamps = self._stamps
        if stamps is None:
            return None
        while stamps and stamps[0][0] < n:
            stamps.popleft()        # a token its consumer did not ask about
        if stamps and stamps[0][0] == n:
            return stamps.popleft()[1]
        return None

    @property
    def status(self) -> Optional[StreamStatus]:
        """The terminal record, or None while the request is live."""
        return self._status

    @property
    def state(self) -> str:
        s = self._status
        if s is not None:
            return s.state
        return self._engine.request_state(self.request_id)

    def done(self) -> bool:
        return self._done.is_set()

    def __iter__(self):
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                if self._done.is_set():
                    return
                if self._engine.is_running():
                    item = self._q.get()  # the step-loop thread feeds us
                else:
                    # synchronous mode: WE are the engine's legs
                    if not self._engine.pump(1) and not self._done.is_set():
                        return  # engine drained under us (shutdown race)
                    continue
            if item is _TERMINAL:
                return
            yield item

    def result(self, timeout_s: Optional[float] = None
               ) -> Optional[StreamStatus]:
        """Wait for the terminal record (pumping the engine inline when
        it has no background thread); None on timeout — honored in both
        drive modes, so a bounded caller never rides out a long
        generation it did not ask to wait for."""
        deadline = None if timeout_s is None \
            else time.monotonic() + timeout_s
        if not self._done.is_set() and not self._engine.is_running():
            while not self._done.is_set() and \
                    (deadline is None or time.monotonic() < deadline):
                if not self._engine.pump(1):
                    break
        self._done.wait(
            None if deadline is None
            else max(0.0, deadline - time.monotonic()))
        return self._status
