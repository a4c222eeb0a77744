"""The benchmark's own spans: kept in memory on the host clock, and, in a
traced run, also written into the profiler's trace (so that they share the
device trace's clock and idle gaps can be named by what the host was
doing).  The program is not edited: these spans sit around the calls into
it."""

from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

PREFIX = "pb:"


class Spans:
    def __init__(self, annotate: bool = False):
        self.records: List[Tuple[str, float, float]] = []
        self._annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self._annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(PREFIX + name)
            ann.__enter__()
        t0 = time.monotonic()
        try:
            yield
        finally:
            t1 = time.monotonic()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def total(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.records if n == name)
