"""What the readers of the step loop's books share (ISSUE 40): a serve
round as the spans its own thread opened in it, from the program's ring.

The step loop opens one ``scheduler/round`` over everything it does
between two calls of ``step_once``, so every span of that thread in a
round reaches the round by ``parent``.  A round's time is then a sum with
nothing outside it: its leaves' durations plus the self times of the spans
above them.  A span's ``tdur`` is its thread's CPU time; ``dur - tdur`` is
how long the thread stood off the CPU inside it (the interpreter's queue,
a sleep, a blocking transfer), exactly where the thread's clock is fine
and as a sample where it ticks (``mean_per_round_ms``).  Rounds that
dispatched nothing (no ``scheduler/deliver`` under them) are left out, as
``sched_host_ms.serve`` leaves them out.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

from . import ring

ROUND = "scheduler/round"
Round = Tuple[Dict, List[Dict]]


def loop_span_ms(layer: Dict, name: str) -> Optional[float]:
    """Median over the window's dispatched steps of the ``name`` spans the
    step loop's thread opened in each, summed per ``step``.  The thread is
    the one that opened the step's ``scheduler/deliver``, so this reads on
    a program without ``scheduler/round`` too."""
    found = ring.events(layer, "serve", name, "scheduler/deliver")
    if not found:
        return None
    loop = {ring.arg(e, "step"): e["tid"] for e in found
            if e["name"] == "scheduler/deliver"}

    def step_of(e):
        step = ring.arg(e, "step")
        on_loop = e["name"] == name and loop.get(step) == e["tid"]
        return step if on_loop else None

    return ring.median_ms(ring.summed_by(found, step_of).values())


def dispatched_rounds(layer: Dict) -> Optional[List[Round]]:
    """(the round's span, every span under it) for each round that began
    in the window and dispatched; None where the program opens no
    ``scheduler/round`` (or the ring cannot be trusted)."""
    rounds = ring.events(layer, "serve", ROUND)
    if not rounds:
        return None
    from paddle_tpu.observability import tracer

    tids = {r["tid"] for r in rounds}
    # a round's last spans may start after the window closed: look at the
    # whole ring (events() has already refused an overflowed one)
    spans = {e["id"]: e for e in tracer().events()
             if e["ph"] == "X" and e["tid"] in tids}
    under: Dict[int, List[Dict]] = {r["id"]: [] for r in rounds}
    for e in spans.values():
        top = e
        while top["name"] != ROUND and top.get("parent") in spans:
            top = spans[top["parent"]]
        if top is not e and top["id"] in under:
            under[top["id"]].append(e)
    return [(r, under[r["id"]]) for r in rounds
            if any(e["name"] == "scheduler/deliver" for e in under[r["id"]])]


def per_round(layer: Dict,
              of: Callable[[Dict, List[Dict]], Optional[float]]
              ) -> Optional[List[float]]:
    """``of(round, spans under it)`` (microseconds) for each dispatched
    round; None where there is no round, or any round yields None."""
    rounds = dispatched_rounds(layer)
    if not rounds:
        return None
    values = [of(r, inner) for r, inner in rounds]
    return None if None in values else values


def median_per_round_ms(layer: Dict, of) -> Optional[float]:
    values = per_round(layer, of)
    return None if values is None else ring.median_ms(values)


def mean_per_round_ms(layer: Dict, of) -> Optional[float]:
    """For what is read off a clock that ticks coarsely: a sandboxed
    kernel counts a thread's CPU time in ticks of 10 ms (the chip's host
    does), so one round's ``tdur`` is 0 or 10 ms, a sample, and only the
    window's sum says anything."""
    values = per_round(layer, of)
    return None if values is None else sum(values) / len(values) / 1e3


def off_cpu(e: Dict) -> Optional[float]:
    """Microseconds the span's thread stood off the CPU inside it; None on
    a program whose spans carry no thread time."""
    return e["dur"] - e["tdur"] if "tdur" in e else None


def less_fetches(r: Dict, inner: List[Dict],
                 of: Callable[[Dict], Optional[float]]) -> Optional[float]:
    """``of(round)`` less ``of`` each ``engine/fetch`` under it: the round
    without its waits for the device."""
    values = [of(r)] + [of(e) for e in inner if e["name"] == "engine/fetch"]
    return None if None in values else values[0] - sum(values[1:])


def unspanned(r: Dict, inner: List[Dict]) -> float:
    """Summed self time of every span of the round that has children, the
    round included: what no leaf span covers."""
    durs = {e["id"]: e["dur"] for e in inner}
    durs[r["id"]] = r["dur"]
    covered: Dict[int, float] = {}
    for e in inner:
        covered[e["parent"]] = covered.get(e["parent"], 0.0) + e["dur"]
    return sum(durs[p] - c for p, c in covered.items())
