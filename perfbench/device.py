"""What the run is on: the device block of the result line, the peaks
table, the compile cache's place, the device's memory peak."""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """The run has no accelerator, or fewer chips than the cell needs."""


def place_compile_cache() -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else the checkout's fixed ``.jax_cache`` (the program's own
    rule, ``paddle_tpu/__init__.py`` — stated here so that the benchmark
    does not depend on it silently).  Must run before JAX compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def describe(chips_needed: int, rehearse_cpu: bool) -> Dict[str, object]:
    """The ``device`` block as JAX reports it; raises NoChip where the
    cell cannot be measured here."""
    import jax

    devs = jax.devices()
    block = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if rehearse_cpu:
        if block["platform"] == "tpu":
            raise NoChip("--rehearse-cpu is for hosts without a chip")
        return block
    if block["platform"] != "tpu":
        raise NoChip(f"no TPU (platform {block['platform']!r}); nothing "
                     f"was built.  --rehearse-cpu walks the cell tiny.")
    if len(devs) < chips_needed:
        raise NoChip(f"the cell needs {chips_needed} chips, JAX sees "
                     f"{len(devs)}")
    return block


def peaks(kind: str, path: Optional[str] = None) -> Dict[str, float]:
    """Published peaks of ``kind``.  A device that is not in the table is
    an error, never a default."""
    with open(path or os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise KeyError(f"device_kind {kind!r} is not in perfbench/peaks.json "
                       f"(known: {[k for k in table if not k.startswith('_')]})")
    return table[kind]


def memory_stats(n_devices: int):
    """Every counter the runtime keeps, per device (for the info line)."""
    import jax

    return [dict(d.memory_stats() or {}) for d in jax.devices()[:n_devices]]


def memory_peak_bytes(stats) -> int:
    """Peak bytes on the fullest device, from ``memory_stats`` as taken
    when the window closed.  This runtime counts an executable's
    temporaries apart from the live arrays, as a reservation: on the v5e a
    training step held 3.38 GB of arrays (``bytes_in_use``) and 12.61 GB
    reserved, the compiler's own 12.8 GB of temporaries, while
    ``peak_bytes_in_use`` alone read 4.5 (PR 23, PR 24).  So the peak is
    the arrays live as the window closed plus the largest reservation, or
    the arrays' own peak if larger."""
    peak = 0
    for s in stats or []:
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)),
                   int(s.get("bytes_in_use", 0))
                   + int(s.get("peak_bytes_reserved", 0)))
    return peak
