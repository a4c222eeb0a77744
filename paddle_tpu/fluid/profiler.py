"""Profiler — analog of python/paddle/v2/fluid/profiler.py (profiler
context manager :76, cuda_profiler :33) over platform/profiler.h's
RecordEvent machinery.

Re-architected for XLA: per-op RecordEvent timing is meaningless when ops
fuse into one executable, so the op-level table is produced by costed
HLO analysis + whole-step wall times, and deep profiling delegates to JAX's
trace profiler (jax.profiler.start_trace -> xprof/perfetto, the TPU
equivalent of nvprof)."""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, List, Optional

from ..utils.sync import RANK_PROFILER, OrderedLock

__all__ = ["profiler", "cuda_profiler", "tpu_trace", "reset_profiler", "op_cost_table",
           "record_event", "get_profile_table"]

# _events is appended from whatever thread runs the dispatch — the
# serving scheduler's daemon thread, the guardrail watchdog's worker,
# run_pipeline's caller — so every touch goes through _events_lock
# (ISSUE 8 satellite: the bare defaultdict lost events under
# concurrent append and could resize mid-iteration in
# get_profile_table)
_events: Dict[str, List[float]] = defaultdict(list)
_events_lock = OrderedLock("fluid.profiler", RANK_PROFILER)
_enabled = False

from ..observability.tracing import tracer as _obs_tracer  # noqa: E402


@contextlib.contextmanager
def record_event(name: str):
    """RAII timing block — analog of platform::RecordEvent (profiler.h:25).
    The executor wraps each compiled-step invocation in one of these.

    Every event is ALSO an observability tracing span (same name,
    cat="profiler": ring event plus profiler annotation), so
    ``get_profile_table``, the Chrome-trace export and a ``jax.profiler``
    trace describe the same timeline — the table aggregates, the traces
    keep per-occurrence timing."""
    t0 = time.perf_counter()
    try:
        with _obs_tracer().span(name, cat="profiler"):
            yield
    finally:
        if _enabled:
            with _events_lock:
                _events[name].append(time.perf_counter() - t0)


def reset_profiler():
    with _events_lock:
        _events.clear()


def get_profile_table(sorted_key: Optional[str] = "total"):
    """Event table like the reference's ParseEvents output
    (platform/profiler.cc): name, calls, total, min, max, ave."""
    with _events_lock:
        snapshot = {name: list(times) for name, times in _events.items()}
    rows = []
    for name, times in snapshot.items():
        rows.append({
            "name": name, "calls": len(times),
            "total": sum(times), "min": min(times), "max": max(times),
            "ave": sum(times) / len(times),
        })
    if sorted_key:
        rows.sort(key=lambda r: -r.get(sorted_key, 0))
    return rows


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str = "total",
             print_table: bool = True):
    """Mirror of fluid.profiler.profiler(state, sorted_key): enables event
    collection for the block and prints the table at exit."""
    global _enabled
    old, _enabled = _enabled, True
    reset_profiler()
    try:
        yield
    finally:
        _enabled = old
        if print_table:
            rows = get_profile_table(sorted_key)
            if rows:
                w = max(len(r["name"]) for r in rows)
                print(f"{'Event':<{w}}  Calls  Total(s)   Min(s)    Max(s)"
                      f"    Ave(s)")
                for r in rows:
                    print(f"{r['name']:<{w}}  {r['calls']:>5}  "
                          f"{r['total']:8.4f}  {r['min']:8.4f}  "
                          f"{r['max']:8.4f}  {r['ave']:8.4f}")


@contextlib.contextmanager
def tpu_trace(log_dir: str = "/tmp/paddle_tpu_trace"):
    """Deep device profile via the JAX trace profiler (xprof) — the TPU
    analog of the reference's cuda_profiler/nvprof path."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """Reference-API alias (fluid/profiler.py:33); routes to tpu_trace."""
    with tpu_trace() as d:
        yield d


def op_cost_table(program=None, feed=None, scope=None, mode="train",
                  top: int = 20, print_table: bool = True):
    """Per-op costed-HLO breakdown — the tool VERDICT r1 weak#8 asked
    for: where does the step's compute go?

    Each desc op is emitted in isolation on abstract inputs (shapes
    propagated through the block with jax.eval_shape) and lowered for
    HLO cost analysis; the table reports flops and bytes per op sorted
    by flops.  Estimates are pre-fusion (XLA later fuses elementwise
    into the matmuls), so treat them as attribution, not wall time —
    whole-step wall time comes from the profiler events.
    """
    import jax
    import numpy as np

    from .executor import HOST_OPS, global_scope, _as_feed_value
    from .framework import default_main_program
    from .lowering import MARKER_OPS, _gather_inputs, _scatter_outputs
    from .core.registry import (EmitCtx, base_op_type, get_op_info, has_op,
                                is_grad_op_type)
    from .lowering import _emit_generic_grad

    program = program or default_main_program()
    scope = scope or global_scope()
    feed = {k: _as_feed_value(v) for k, v in (feed or {}).items()}
    block = program.desc.global_block()

    def aval_of(v):
        from .core.lod import SeqArray

        if isinstance(v, SeqArray):
            return SeqArray(jax.ShapeDtypeStruct(v.data.shape,
                                                 v.data.dtype),
                            jax.ShapeDtypeStruct(v.lengths.shape,
                                                 v.lengths.dtype))
        a = np.asarray(v) if not hasattr(v, "shape") else v
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    env = {n: aval_of(v) for n, v in feed.items()}
    rows = []
    key_aval = jax.eval_shape(lambda: jax.random.key(0))
    # op-signature cost cache: identical layers repeat the same op with the
    # same shapes/attrs (a 6-layer transformer re-lowers each op type ~6-18
    # times); without this the table takes minutes on big programs
    sig_cache: dict = {}

    def sig_of_op(op, flat):
        try:
            avals = tuple(
                (tuple(getattr(a, "shape", ())), str(getattr(a, "dtype", "")))
                for a in flat)
            return (op.type, repr(sorted(op.attrs.items())), avals)
        except Exception:
            return None

    def fallback_outputs(op):
        # when an op can't be emitted in isolation, still register avals
        # for its outputs (block var descs, else a scalar placeholder) so
        # downstream ops keep the table going instead of aborting with a
        # misleading "run startup first" error
        for names in op.outputs.values():
            for n in names:
                if not n or n in env:
                    continue
                v = scope.find_var(n)   # live value (param/state) is exact
                if v is not None:
                    env[n] = aval_of(v)
                    continue
                vd = block.vars.get(n)
                if vd is not None and vd.shape is not None:
                    # dynamic dims take the leading dim of the fed avals
                    # (the real batch) so downstream shape-strict ops and
                    # flop counts stay consistent; _DUMMY_BATCH otherwise
                    from .framework import _DUMMY_BATCH

                    batch = next((a.shape[0] for a in env.values()
                                  if getattr(a, "shape", ()) and
                                  a.shape[0] > 0), _DUMMY_BATCH)
                    shape = [batch if d in (-1, None) else d
                             for d in vd.shape]
                    env[n] = jax.ShapeDtypeStruct(
                        tuple(shape), np.dtype(vd.dtype or "float32"))
                else:
                    env[n] = jax.ShapeDtypeStruct((), np.float32)

    for idx, op in enumerate(block.ops):
        if op.type in MARKER_OPS or op.type in HOST_OPS:
            continue
        # pull unmet inputs from the scope (params/state) — OUTSIDE the
        # try: an uninitialized scope must raise the actionable error, not
        # degrade into an all-zero table. Inputs produced by an op whose
        # emission failed are already in env via fallback_outputs.
        for names in op.inputs.values():
            for n in names:
                if n and n not in env:
                    v = scope.find_var(n)
                    if v is None:
                        raise RuntimeError(
                            f"op_cost_table: {op.type} input {n!r} "
                            f"absent (run startup first)")
                    env[n] = aval_of(v)
        try:
            ins = _gather_inputs(op, env)
            flat, treedef = jax.tree.flatten(ins)

            def one_op(flat_vals, rng):
                ins2 = jax.tree.unflatten(treedef, flat_vals)
                ctx = EmitCtx(op, rng=rng, mode=mode)
                if has_op(op.type):
                    return get_op_info(op.type).emit(ctx, ins2)
                if is_grad_op_type(op.type) and has_op(base_op_type(op.type)):
                    return _emit_generic_grad(ctx, op, ins2)
                raise KeyError(op.type)

            outs = jax.eval_shape(one_op, flat, key_aval)
            _scatter_outputs(op, outs, env)
            sig = sig_of_op(op, flat)
            if sig is not None and sig in sig_cache:
                ca = sig_cache[sig]
            else:
                lowered = jax.jit(one_op).lower(flat, key_aval)
                ca = lowered.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else None
                if not ca or not ca.get("flops"):
                    # CPU PJRT only exposes cost analysis post-compile; a
                    # silently all-zero table defeats the tool's purpose
                    ca = lowered.compile().cost_analysis()
                    if isinstance(ca, (list, tuple)):
                        ca = ca[0] if ca else None
                ca = dict(ca or {})
                if sig is not None:
                    sig_cache[sig] = ca
        except Exception:
            # control-flow ops (need a live block lowerer), unregistered
            # types, emit failures — count as zero, keep the table going
            ca = {}
            fallback_outputs(op)
        rows.append({
            "op": f"#{idx} {op.type}",
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
        })

    total_flops = sum(r["flops"] for r in rows) or 1.0
    rows.sort(key=lambda r: -r["flops"])
    if print_table:
        print(f"{'op':<40}{'GFLOPs':>12}{'MB':>10}{'% flops':>9}")
        for r in rows[:top]:
            print(f"{r['op']:<40}{r['flops']/1e9:>12.3f}"
                  f"{r['bytes']/1e6:>10.1f}"
                  f"{100*r['flops']/total_flops:>8.1f}%")
        rest = rows[top:]
        if rest:
            print(f"{'... ' + str(len(rest)) + ' more ops':<40}"
                  f"{sum(r['flops'] for r in rest)/1e9:>12.3f}")
    return rows
