"""From a profiler trace to numbers: device busy and idle time, the time of
Mosaic custom calls and of collectives, the gaps between launched modules,
and the ``breakdown`` of the result line.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes (with
nothing but JAX) into a plain structure::

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

``reduce`` works on that structure alone, so the tests check it on a small
recorded trace kept as JSON (``dump``/``load_json``), cut from a chip run.

How a TPU trace is laid out (looked at by hand on the v5e, PR 23): one
plane per chip, ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
per executed HLO instruction, NAMED BY THE INSTRUCTION'S WHOLE TEXT
(``%fusion.941 = f32[16384,1024]{...} fusion(... %custom-call.211), ...``)
and carrying no category stat, so the opcode is parsed out of the name;
``XLA Modules`` holds one event per launched executable, ``Steps`` the
step markers, ``Async XLA Ops`` the copies that overlap compute (not
counted as busy).  Host threads are lines of ``/host:CPU``; the
benchmark's own spans appear there under ``pb:<name>``
(``perfbench.spans``), beside the runtime's (``PjitFunction(step)``,
``np.asarray(jax.Array)``, ...).
"""

from __future__ import annotations

import gzip
import json
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "pb:window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
# HLO instructions that only contain others: their time is their children's
CONTAINERS = ("while", "call", "conditional")


def load_xplane(path: str) -> Dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def dump(trace: Dict, path: str, t0_ns: Optional[float] = None,
         t1_ns: Optional[float] = None) -> None:
    """Write ``trace`` (cut to events starting in [t0, t1)) as gzipped JSON."""
    def keep(e):
        return (t0_ns is None or e[1] >= t0_ns) and \
            (t1_ns is None or e[1] < t1_ns)

    cut = {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [e for e in ln["events"] if keep(e)]}
            for ln in p["lines"]]} for p in trace["planes"]]}
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(cut, f, separators=(",", ":"))


def load_json(path: str) -> Dict:
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


# -- interval arithmetic ------------------------------------------------------

def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Total length (in the intervals' unit) of the union."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def self_times(events: List[List]) -> List[Tuple[str, float]]:
    """(name, own nanoseconds) per event of ONE line, where an event's own
    time is its duration less its nested children's."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []                 # stack of [name, end, own]
    for name, start, dur in (e[:3] for e in order):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


_OPCODE = re.compile(r"(?<![A-Za-z0-9_.%])([a-z][a-z\-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event: the first lower-case word
    before a parenthesis after the ``=`` of the instruction's text (shapes
    and layouts hold only upper-case ``T(...)``/``S(...)``); for a bare
    name (``fusion.3``) the name without its number."""
    if " = " in name:
        m = _OPCODE.search(name.split(" = ", 1)[1])
        if m:
            return m.group(1)
    return re.sub(r"[.\d]+$", "", name.lstrip("%"))


def short_name(name: str) -> str:
    """``fusion.941 = f32[16384,1024] fusion`` from the instruction text."""
    if " = " not in name:
        return name[:120]
    head, rest = name.split(" = ", 1)
    shape = re.sub(r"\{[^{}]*\}", "", rest.split(" " + opcode(name) + "(")[0])
    return f"{head.lstrip('%')} = {shape} {opcode(name)}"[:120]


MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_SHAPE = re.compile(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]")


def is_mosaic_call(name: str) -> bool:
    """A Pallas (Mosaic) kernel, by its exact kind: the compiler's own
    zero-time ``ConcatBitcast`` custom calls are not kernels."""
    return opcode(name) == "custom-call" and MOSAIC_TARGET in name


def call_signature(name: str) -> Tuple[Tuple, Tuple]:
    """(results, operands) of a custom-call event, each a tuple of
    ``(dtype, dims)``, parsed from the instruction's text."""
    head, _, tail = name.partition(" custom-call(")
    tail = tail.split("custom_call_target", 1)[0]

    def shapes(text):
        return tuple((d, tuple(int(x) for x in dims.split(",") if x))
                     for d, dims in _SHAPE.findall(text))

    return shapes(head.split(" = ", 1)[-1]), shapes(tail)


def is_collective(name: str) -> bool:
    return opcode(name).startswith(COLLECTIVES)


def _clip(events, lo, hi):
    out = []
    for e in events:
        a, b = max(e[1], lo), min(e[1] + e[2], hi)
        if b > a:
            out.append([e[0], a, b - a])
    return out


def _find_window(trace: Dict) -> Optional[Tuple[float, float]]:
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            for e in ln["events"]:
                if e[0] == WINDOW_SPAN:
                    return e[1], e[1] + e[2]
    return None


def _host_events(trace: Dict, lo: float, hi: float) -> List[List]:
    out = []
    for p in trace["planes"]:
        if not p["name"].startswith("/host:CPU"):
            continue
        for ln in p["lines"]:
            for e in ln["events"]:
                if e[2] > 0 and e[1] < hi and e[1] + e[2] > lo \
                        and e[0] != WINDOW_SPAN:
                    out.append(e)
    return out


def _name_gap(gap: Tuple[float, float], host: List[List]) -> str:
    """What the host was doing in an idle gap: the SHORTEST host span that
    covers at least half of it (the innermost), the benchmark's own
    ``pb:`` spans first."""
    a, b = gap
    need = 0.5 * (b - a)
    best = None
    for e in host:
        cover = min(b, e[1] + e[2]) - max(a, e[1])
        if cover < need:
            continue
        rank = (0 if e[0].startswith("pb:") else 1, e[2])
        if best is None or rank < best[0]:
            best = (rank, e[0])
    return best[1] if best else "(no host span)"


def reduce(trace: Dict, n_devices: int = 1, top: int = 10) -> Dict:
    """The trace's numbers over the ``pb:window`` span (or, without one,
    over the device events' own extent).  Seconds throughout."""
    devices = sorted((int(DEVICE_PLANE.match(p["name"]).group(1)), p)
                     for p in trace["planes"] if DEVICE_PLANE.match(p["name"]))
    devices = devices[:n_devices]
    if not devices:
        return {}
    window = _find_window(trace)
    if window is None:
        starts = [e[1] for _, p in devices for ln in p["lines"]
                  for e in ln["events"]]
        ends = [e[1] + e[2] for _, p in devices for ln in p["lines"]
                for e in ln["events"]]
        if not starts:
            return {}
        window = (min(starts), max(ends))
    lo, hi = window
    per_dev = []
    for idx, plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = _clip(lines.get(OPS_LINE, []), lo, hi)
        modules = _clip(lines.get(MODULES_LINE, []), lo, hi)
        busy_src = ops or modules
        intervals = [(e[1], e[1] + e[2]) for e in busy_src]
        own = self_times(ops)
        by_name: Dict[str, float] = {}
        mosaic_ns = coll_ns = 0.0
        mosaic_n = 0
        kernels: Dict[Tuple, List[float]] = {}
        for name, ns in own:
            if opcode(name) in CONTAINERS:
                continue
            by_name[short_name(name)] = by_name.get(short_name(name), 0.0) + ns
            if is_mosaic_call(name):
                mosaic_ns += ns
                mosaic_n += 1
                group = kernels.setdefault(call_signature(name), [0, 0.0])
                group[0] += 1
                group[1] += ns
            elif is_collective(name):
                coll_ns += ns
        mod_iv = sorted((e[1], e[1] + e[2]) for e in modules)
        launch = [b[0] - a[1] for a, b in zip(mod_iv, mod_iv[1:])
                  if b[0] > a[1]]
        per_dev.append({
            "device": idx, "busy_s": union_seconds(intervals) / 1e9,
            "mosaic_s": mosaic_ns / 1e9, "mosaic_calls": mosaic_n,
            "kernels": [{"results": sig[0], "operands": sig[1], "calls": n,
                         "seconds": ns / 1e9}
                        for sig, (n, ns) in kernels.items()],
            "collective_s": coll_ns / 1e9, "modules": len(modules),
            "launch_gaps_s": [g / 1e9 for g in launch],
            "ops": by_name, "intervals": intervals})
    first = per_dev[0]
    host = _host_events(trace, lo, hi)
    by_host: Dict[str, float] = {}
    idle = sorted(gaps(first["intervals"], lo, hi),
                  key=lambda g: g[0] - g[1])
    host = [e for e in host if e[2] >= 20e3 or e[0].startswith("pb:")]
    for g in idle[:300]:                # the longest gaps carry the time
        name = _name_gap(g, host)
        by_host[name] = by_host.get(name, 0.0) + (g[1] - g[0]) / 1e9
    n = len(per_dev)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "mosaic_s": sum(d["mosaic_s"] for d in per_dev) / n,
        "mosaic_calls": first["mosaic_calls"],
        "kernels": first["kernels"],
        "collective_s": first["collective_s"],
        "modules": first["modules"],
        "launch_gaps_s": first["launch_gaps_s"],
        "per_device_busy_s": [d["busy_s"] for d in per_dev],
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            first["ops"].items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            by_host.items(), key=lambda kv: -kv[1])[:top]],
    }
