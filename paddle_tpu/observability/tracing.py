"""Structured trace spans — ring-buffered, thread-safe, exportable as
Chrome-trace / Perfetto JSON.

The reference's platform/profiler records RecordEvent begin/end pairs
into per-thread event lists and ParseEvents folds them into a table.
Under XLA the op-level story moved to the fused-step profiler
(fluid/profiler.py); what was MISSING is the request-level story: when
did request 17 get submitted, admitted, prefilled, and when did each of
its tokens come out?  That timeline is what TTFT and inter-token
latency are made of, and no whole-step table can reconstruct it.

``Tracer`` keeps a bounded ring of event dicts (append under one lock —
O(1); measured on the host of a TPU v5e, PERF.md section 6, PR 40: in a
loop 4.5 us a span with both sinks on, 1.9 us an instant, 1.6 us a
disabled span, and 6.2-6.7 us for each read of the thread's clock, which
on that sandboxed kernel is a system call (0.3-0.4 us on a plain Linux
host).  End to end, in the busiest serving cell, a step of 15 ms and 14
spans: the spans it gained cost 0.7 % of the step, the thread's clock 1.7
%, read 6 times a step or 28; a training step of 264 ms has 4 spans):

* ``span(name, **args)`` — context manager emitting a Chrome "X"
  (complete) event with microsecond ``ts``/``dur`` and ``tdur``, the
  thread's CPU time inside the span (``time.thread_time()``; Chrome's
  own name for an X event's thread-clock duration), so ``dur - tdur`` is
  how long the thread stood off the CPU: waiting for the interpreter,
  asleep, or blocked in a transfer.  Exact where the kernel counts a
  thread's time as it runs.  Where it counts in ticks (that sandboxed
  kernel: 10 ms) one span's ``tdur`` is a sample, 0 or a whole tick and
  so over ``dur`` as often as under, and only sums over many spans read
  true; there the clock is not read again within a twentieth of its
  tick (``_thread_clock_tick``, found once by reading until it moves):
  at most one time in twenty a tick is charged to the span after the
  one it fell in.  The same ``with``
  enters a ``jax.profiler.TraceAnnotation(name)``, so the span also
  lands on the host plane of whatever profiler session is open, on the
  profiler's clock, beside the device's own lines (inert without a
  session);
* ``instant(name, **args)`` — zero-duration "i" event (lifecycle marks:
  submitted / admitted / token / retired), now or ``at`` a recorded mark;
* ``complete(name, start, end, **args)`` — an X event from timestamps
  recorded elsewhere (the scheduler builds the whole-request span from
  the Request's own submitted/finished marks; no ``tdur``: it has no
  thread of its own).

Ids are *seeded*: a process-local monotonic counter (a span takes its
id when it opens), so two runs that do the same work emit the same id
sequence — the span-timeline tests key on that determinism.  Every
event carries ``parent``, the id of the span open on its thread when it
started (absent at top level), and inherits that span's ``rid`` and
``step`` args unless it sets its own: the spans of one serve step and
the events of one request join on those.  ``chrome_trace()`` emits the
``{"traceEvents": [...]}`` JSON both chrome://tracing and Perfetto
load directly.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..utils.sync import RANK_TRACER, OrderedLock

__all__ = ["Tracer", "tracer", "span", "instant"]

# args a span hands down to the events opened under it on its thread
_CARRIED = ("rid", "step")


@functools.cache
def _thread_clock_tick() -> float:
    """Seconds between two values of ``time.thread_time()`` on this host,
    found once by reading until it moves: the cost of a read where the
    kernel counts a thread's time as it runs (under a microsecond), the
    tick where it counts in ticks (a sandboxed kernel: 10 ms)."""
    c0 = time.thread_time()
    while True:
        c = time.thread_time()
        if c != c0:
            return c - c0


class _OpenSpans(threading.local):
    """Per thread: the open spans, innermost last, as (id, carried args);
    and the thread's CPU clock as last read, with the wall-clock mark of
    that read."""

    def __init__(self):
        self.stack: List[tuple] = []
        self.cpu = 0.0
        self.cpu_read_at = float("-inf")


class Tracer:
    """Bounded in-memory trace sink.  ``capacity`` bounds the ring (old
    events drop, counted in ``dropped``); ``enabled=False`` turns every
    emit into a cheap no-op (the bench's "bare" leg).

    The default holds half a minute of the busiest server there is: 128
    lanes at a 24 ms step emit 6 000 events a second (an instant a
    token), and a reader that wants a window whole refuses a ring that
    has dropped anything.  Full, it is about 150 MB (580 B an event)."""

    def __init__(self, capacity: int = 262144, enabled: bool = True):
        # innermost-but-one rank: emits happen under the scheduler and
        # router locks (the scheduler's spans and admission instants;
        # the per-token and retirement instants come from its delivery
        # thread, under no lock of the scheduler's)
        self._lock = OrderedLock("obs.tracer", RANK_TRACER)
        self._events: deque = deque(maxlen=int(capacity))
        self._ids = itertools.count(1)
        self.enabled = bool(enabled)
        self.dropped = 0
        self._pid = os.getpid()
        self._open = _OpenSpans()
        # a clock is not read again within a twentieth of its own tick:
        # never, where it runs fine; where it ticks in 10 ms and a read
        # is a system call of 6 us, a serve step's 28 reads become 5-7
        self._cpu_reread_s = _thread_clock_tick() / 20.0

    # -- emit ----------------------------------------------------------------
    def _emit(self, ev: Dict[str, object]) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)

    def _base(self, name: str, cat: str, ph: str, ts: float,
              args: Dict[str, object]) -> Dict[str, object]:
        """One event, under the span open on this thread: ``args`` gains
        what that span carries and the event its ``parent``."""
        ev = {"name": name, "cat": cat or "default", "ph": ph,
              "ts": ts * 1e6, "pid": self._pid,
              "tid": threading.get_ident(), "id": next(self._ids)}
        stack = self._open.stack
        if stack:
            ev["parent"], carried = stack[-1]
            for k, v in carried.items():
                args.setdefault(k, v)
        if args:
            ev["args"] = args
        return ev

    def _thread_cpu(self, now: float) -> float:
        """This thread's CPU clock at ``now``, a perf_counter mark just
        taken: as last read, if that was within ``_cpu_reread_s``."""
        mine = self._open
        if now - mine.cpu_read_at >= self._cpu_reread_s:
            mine.cpu, mine.cpu_read_at = time.thread_time(), now
        return mine.cpu

    def instant(self, name: str, cat: str = "",
                at: Optional[float] = None, **args) -> None:
        """``at``: a perf_counter mark recorded elsewhere (the scheduler
        stamps a step's tokens in its loop and emits their instants from
        the delivery thread); now where there is none."""
        if not self.enabled:
            return
        ev = self._base(name, cat, "i",
                        time.perf_counter() if at is None else at, args)
        ev["s"] = "t"               # thread-scoped instant
        self._emit(ev)

    def complete(self, name: str, start: float, end: float,
                 cat: str = "", **args) -> None:
        """An "X" event from externally recorded perf_counter marks."""
        if not self.enabled:
            return
        ev = self._base(name, cat, "X", start, args)
        ev["dur"] = max(0.0, (end - start) * 1e6)
        self._emit(ev)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "", **args):
        """Time a block as one complete event, in the ring and in any
        open profiler session.  Yields a mutable dict merged into the
        event's args at exit — fill in results computed inside the block
        (token ids, counts)."""
        if not self.enabled:
            yield {}
            return
        t0 = time.perf_counter()
        ev = self._base(name, cat, "X", t0, args)
        stack = self._open.stack
        stack.append((ev["id"], {k: args[k] for k in _CARRIED
                                 if k in args}))
        extra: Dict[str, object] = {}
        # the thread's clock is read inside the wall clock's two reads,
        # so what it counts lies within ``dur``
        c0 = self._thread_cpu(t0)
        try:
            with TraceAnnotation(name):
                yield extra
        finally:
            cpu = self._thread_cpu(time.perf_counter()) - c0
            stack.pop()
            ev["dur"] = (time.perf_counter() - t0) * 1e6
            ev["tdur"] = cpu * 1e6
            if extra:
                ev["args"] = {**args, **extra}
            self._emit(ev)

    # -- control -------------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    # -- export --------------------------------------------------------------
    def events(self, name: Optional[str] = None,
               cat: Optional[str] = None) -> List[Dict[str, object]]:
        """Snapshot of the ring (optionally filtered), oldest first."""
        with self._lock:
            evs = list(self._events)
        if name is not None:
            evs = [e for e in evs if e["name"] == name]
        if cat is not None:
            evs = [e for e in evs if e["cat"] == cat]
        return evs

    def chrome_trace(self) -> Dict[str, object]:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def export(self, path: str) -> int:
        """Write the Chrome-trace JSON; returns the event count."""
        trace = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])


_tracer = Tracer()


def tracer() -> Tracer:
    """The process-global tracer every instrumented surface shares."""
    return _tracer


def span(name: str, cat: str = "", **args):
    """Module-level shorthand for ``tracer().span(...)``."""
    return _tracer.span(name, cat=cat, **args)


def instant(name: str, cat: str = "", **args) -> None:
    _tracer.instant(name, cat=cat, **args)
