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
O(1); measured on the host of a TPU v5e, PERF.md section 6: 4.4-5.0 us a
span with both sinks on, 1.9 us an instant, 1.5 us a disabled span, which
is 0.03 % of a serving step of 12 spans and 0.006 % of a training step
of 4):

* ``span(name, **args)`` — context manager emitting a Chrome "X"
  (complete) event with microsecond ``ts``/``dur``.  The same ``with``
  enters a ``jax.profiler.TraceAnnotation(name)``, so the span also
  lands on the host plane of whatever profiler session is open, on the
  profiler's clock, beside the device's own lines (inert without a
  session);
* ``instant(name, **args)`` — zero-duration "i" event (lifecycle marks:
  submitted / admitted / token / retired), now or ``at`` a recorded mark;
* ``complete(name, start, end, **args)`` — an X event from timestamps
  recorded elsewhere (the scheduler builds the whole-request span from
  the Request's own submitted/finished marks).

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


class _OpenSpans(threading.local):
    """Per thread: the open spans, innermost last, as (id, carried args)."""

    def __init__(self):
        self.stack: List[tuple] = []


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
        try:
            with TraceAnnotation(name):
                yield extra
        finally:
            stack.pop()
            ev["dur"] = (time.perf_counter() - t0) * 1e6
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
