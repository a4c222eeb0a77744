"""One run of one cell.

``python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``

Loads, warms the cell's own shapes (set-up), measures for ``--seconds``,
checks what the timed path produced against the plain reference, prints
each number compared beside its limit, and prints as the LAST line of
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``.

Without a TPU (or with fewer chips than the cell needs) it exits non-zero
before building anything and prints no result.  ``--rehearse-cpu`` walks
the same code at a tiny size on a host without a chip: its output says
``platform: cpu`` and carries no metric at all.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time
from typing import Dict, Optional

T_PROCESS_START = time.monotonic()

from . import manifest as mf                                    # noqa: E402
from .cells import load_cell                                    # noqa: E402
from .device import (NoChip, ROOT, describe,                    # noqa: E402
                     memory_peak_bytes, memory_stats,
                     peaks as device_peaks, place_compile_cache)
from .spans import Spans                                        # noqa: E402

OUT_DIR = os.path.join(ROOT, ".perfbench")

CONTROL_PRECISION: Optional[str] = None     # set by the controls only


class Context:
    """What a cell's runner gets: the files' contents, the run's
    arguments, the spans, and the hooks that mark set-up's end and switch
    the profiler on and off."""

    def __init__(self, cell: Dict, cfg: Dict, mix: Dict, args, peaks,
                 n_devices: int = 1):
        from . import serve_cell, train_cell
        from .reference import transformer as ref

        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.seed, self.trace = int(args.seed), bool(args.trace)
        self.seconds = float(args.seconds)
        self.peaks = peaks
        self.spans = Spans(annotate=self.trace)
        self.setup_s: Optional[float] = None
        self.trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        self._tracing = False
        self.n_devices = n_devices
        self.memory = None                  # the counters as the window closed
        # what the tests swap to break the timed path underneath
        self.make_step = train_cell.TrainStep
        self.make_served = serve_cell.Served
        self.served_gaps = ref.served_logit_gaps
        # the controls (tests, and the readings a limit is set from): the
        # reference once more in this lower precision, in the program's
        # place; a benchmark run leaves it None
        self.control_precision: Optional[str] = CONTROL_PRECISION

    def window_seconds(self) -> float:
        """A traced run measures a short window of its own."""
        if self.trace:
            return min(self.seconds, float(self.mix.get("trace_seconds", 5)))
        return self.seconds

    def work_dir(self) -> str:
        path = os.path.join(OUT_DIR, "work", self.cell["name"])
        os.makedirs(path, exist_ok=True)
        return path

    @staticmethod
    def settle() -> None:
        """Collect set-up's garbage before traffic starts.  Building and
        warming leave the interpreter a large heap with a full collection
        pending; left alone it lands somewhere inside the window and stalls
        the host for some tenths of a second to over a second (seen in 3 of
        12 training runs, PR 24), which is set-up's cost, not the window's."""
        import gc

        gc.collect()

    def setup_done(self, now: float) -> None:
        self.setup_s = now - T_PROCESS_START

    def start_trace(self) -> None:
        if not self.trace:
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # TraceMe spans only: the
        options.host_tracer_level = 2       # Python tracer slows the host
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True

    def window_closed(self) -> None:
        """Note the device's memory counters, while they are still the
        program's alone (the reference runs later), and end the trace."""
        if self.memory is None:
            self.memory = memory_stats(self.n_devices)
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False

    def reduced_trace(self) -> Dict:
        from . import trace_reduce

        found = glob.glob(os.path.join(self.trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not found:
            return {}
        return trace_reduce.reduce(trace_reduce.load_xplane(found[0]),
                                   n_devices=self.n_devices)


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the cell tiny on a host without a chip; "
                         "proves control flow, measures nothing")
    args = ap.parse_args(argv)

    manifest = mf.load()
    cell, cfg, mix = load_cell(manifest, args.workload, args.rehearse_cpu)

    cache_dir = place_compile_cache()
    try:
        device = describe(int(cell["chips"]), args.rehearse_cpu)
        peaks = None if args.rehearse_cpu else device_peaks(device["kind"])
    except (NoChip, KeyError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 1
    n_dev = int(cell["chips"])
    say(cell=cell["name"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device, compile_cache=cache_dir,
        rehearsal=bool(args.rehearse_cpu))

    import paddle_tpu  # noqa: F401  (the system under test)

    from . import serve_cell, train_cell

    ctx = Context(cell, cfg, mix, args, peaks, n_dev)
    runner = {"train": train_cell.run, "serve": serve_cell.run}[mix["kind"]]
    out = runner(ctx)

    correct = True
    for c in out["checks"]:
        c["ok"] = bool(c["value"] <= c["limit"])
        correct = correct and c["ok"]
        say(compared=c["name"], value=c["value"], limit=c["limit"], ok=c["ok"])
    say(info=out["info"], spans={n: round(ctx.spans.total(n), 4) for n in
                                 sorted({r[0] for r in ctx.spans.records})})

    say(memory_at_window_close=ctx.memory, memory_at_exit=memory_stats(n_dev))
    device["memory_peak_bytes"] = memory_peak_bytes(ctx.memory)
    e2e = dict(out["e2e"], setup_s=ctx.setup_s)
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {}, "device": device}
    if args.rehearse_cpu:
        # a CPU walk: no number under a device metric's name
        say(rehearsal_only=sorted(e2e))
    elif not args.trace:
        for m in mf.metrics_for(manifest, cell["name"], "end_to_end"):
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    if args.trace:
        reduced = ctx.reduced_trace()
        layer = dict(out["layer"], trace=reduced, cfg=cfg, mix=mix,
                     peaks=peaks, e2e=e2e, spans=ctx.spans)
        for m in mf.metrics_for(manifest, cell["name"], "per_layer"):
            value = mf.load_reader(m["name"])(layer)
            if value is None:
                continue
            if args.rehearse_cpu:
                say(rehearsal_reader=m["name"], read=True)
            else:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        if reduced and not args.rehearse_cpu:
            # what the roofline and time shares were read from, for the
            # hand arithmetic PERF.md shows
            say(trace={k: reduced[k] for k in (
                "window_s", "busy_s", "mosaic_s", "mosaic_calls", "modules",
                "kernels")})
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
