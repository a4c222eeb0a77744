"""What the per-layer readers of ``source: program_span`` share: the
program's own trace ring (``paddle_tpu.observability.tracer()``), cut to
the measured window.

The ring stamps its events with ``time.perf_counter`` (microseconds in
``ts``/``dur``), the harness its window with ``time.monotonic``: one clock,
``CLOCK_MONOTONIC``, on Linux (a test pins it).  A reader gets nothing,
and its metric is left out of the line, where the program has no tracer or
no such span (the parent of the PR that added the spans), where the ring
overflowed (``dropped``: a median of what happened to be left would
mislead), or where no such span started in the window.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from . import stats


def window(layer: Dict) -> Optional[Tuple[float, float]]:
    """The measured window in seconds of the host's monotonic clock."""
    if "t_open" in layer:                           # a serving cell
        return float(layer["t_open"]), float(layer["t_close"])
    records = getattr(layer.get("spans"), "records", ())
    for name, t0, t1 in records:                    # a training cell
        if name == "window":
            return float(t0), float(t1)
    return None


def events(layer: Dict, kind: str, *names: str) -> Optional[List[Dict]]:
    """The ring's events of these names that started in the window of a
    ``kind`` cell, oldest first; None where there is nothing to trust."""
    if layer.get("kind") != kind:
        return None
    try:
        from paddle_tpu.observability import tracer
    except ImportError:
        return None
    ring = tracer()
    span = window(layer)
    if span is None or getattr(ring, "dropped", 0) > 0:
        return None
    lo, hi = 1e6 * span[0], 1e6 * span[1]
    return sorted((e for name in names for e in ring.events(name=name)
                   if lo <= e["ts"] < hi), key=lambda e: e["ts"])


def arg(event: Dict, key: str):
    """An event's argument, None where it has none."""
    return (event.get("args") or {}).get(key)


def median_ms(durations_us: Iterable[float]) -> Optional[float]:
    values = [d / 1e3 for d in durations_us]
    return stats.percentile(values, 50) if values else None


def median_span_ms(layer: Dict, kind: str, name: str) -> Optional[float]:
    """Median duration of the spans called ``name``."""
    found = events(layer, kind, name)
    return median_ms(e["dur"] for e in found) if found else None


def summed_by(found: List[Dict], key) -> Dict[object, float]:
    """Durations (microseconds) summed over the events that share
    ``key(event)``; events for which it is None are left out."""
    out: Dict[object, float] = {}
    for e in found:
        k = key(e)
        if k is not None:
            out[k] = out.get(k, 0.0) + e["dur"]
    return out
