"""Shared by the perfbench tests: run one cell's CPU rehearsal in this
process and parse what it printed."""

import json

from perfbench import run


def rehearse(capsys, workload, seed=3, seconds=1.0, trace=0, patch=None,
             control=None):
    """-> (exit code, result line as dict, every JSON line printed)."""
    old_ctx, old_control = run.Context, run.CONTROL_PRECISION
    if patch is not None:
        class Patched(old_ctx):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                patch(self)
        run.Context = Patched
    run.CONTROL_PRECISION = control
    try:
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace),
                       "--rehearse-cpu"])
    finally:
        run.Context, run.CONTROL_PRECISION = old_ctx, old_control
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return rc, (lines[-1] if lines else None), lines


def compared(lines):
    return {ln["compared"]: ln for ln in lines if "compared" in ln}
