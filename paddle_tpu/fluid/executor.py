"""Scope and Executor.

Analog of the reference's Scope (paddle/framework/scope.h:38), C++ Executor
(paddle/framework/executor.cc:77,230) and its Python wrapper
(python/paddle/v2/fluid/executor.py:149,204) — re-architected for XLA:

* ``Executor.run`` does NOT walk ops per step.  It compiles the whole block
  into one jitted step function (see lowering.py) keyed by (program version,
  feed signature, fetch list, state signature) and replays the executable —
  the reference pays per-op dispatch + Python->C++ crossing per run
  (executor.py:204 clones the program per call!); we pay once per signature.
* Feed = jitted-arg transfer (device_put under the hood), fetch = executable
  results; the reference's feed/fetch ops and FeedFetchList
  (feed_fetch_method.cc) become markers.
* Persistables live in the Scope as device arrays and are threaded
  functionally; XLA buffer donation turns parameter updates into in-place
  HBM writes (the analog of ParamOut aliasing in sgd_op.cc).
* ``save``/``load`` ops (operators/save_op.cc, load_op.cc) are executed
  host-side, streaming tensors to disk in a sidecar-JSON + raw-bytes format.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from .core.lod import SeqArray
from .framework import Program, Variable, default_main_program
from .lowering import HOST_OPS, build_step_fn

__all__ = ["Scope", "global_scope", "scope_guard", "Executor",
           "TPUPlace", "CPUPlace"]


class TPUPlace:
    """Device tag — analog of platform::CUDAPlace (paddle/platform/place.h),
    pointing at a TPU chip."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


class CPUPlace:
    def __init__(self):
        self.device_id = 0

    def __repr__(self):
        return "CPUPlace()"


class Scope:
    """name -> value map with parent chaining (scope.h:38).  Values are JAX
    arrays, SeqArrays, or host objects."""

    def __init__(self, parent: Optional["Scope"] = None):
        self.vars: Dict[str, Any] = {}
        self.parent = parent
        self._rng_seed: Optional[int] = None
        self._rng_step: int = 0

    def var(self, name: str) -> str:
        self.vars.setdefault(name, None)
        return name

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        return None

    def has_var(self, name: str) -> bool:
        s: Optional[Scope] = self
        while s is not None:
            if name in s.vars:
                return True
            s = s.parent
        return False

    def set_var(self, name: str, value) -> None:
        self.vars[name] = value

    def new_scope(self) -> "Scope":
        return Scope(parent=self)

    def next_rng_bits(self, seed: Optional[int]) -> np.ndarray:
        """int32[2] (seed, step) — the step RNG key is derived from these
        inside the compiled computation (see lowering.build_step_fn)."""
        if self._rng_seed is None or (seed is not None and seed != self._rng_seed):
            self._rng_seed = (seed if seed is not None
                              else (time.time_ns() & 0x7FFFFFFF))
        self._rng_step += 1
        return np.array([self._rng_seed, self._rng_step], dtype=np.int32)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


import contextlib


@contextlib.contextmanager
def scope_guard(scope: Scope):
    global _global_scope
    old, _global_scope = _global_scope, scope
    try:
        yield
    finally:
        _global_scope = old


def _as_feed_value(v):
    """Normalise one feed entry to a device-ready value (int64/f64 narrowed to
    JAX defaults).  Device-resident arrays pass through untouched — feeding a
    jax.Array skips the per-step H2D transfer (device-side input pipelines)."""
    from .core.lod import NestedSeqArray

    if isinstance(v, SeqArray):
        return SeqArray(_as_feed_value(v.data), np.asarray(v.lengths, np.int32))
    if isinstance(v, NestedSeqArray):
        return NestedSeqArray(_as_feed_value(v.data),
                              np.asarray(v.outer_lengths, np.int32),
                              np.asarray(v.inner_lengths, np.int32))
    if isinstance(v, jax.Array):
        return v
    a = np.asarray(v)
    if a.dtype == np.int64:
        a = a.astype(np.int32)
    elif a.dtype == np.float64:
        a = a.astype(np.float32)
    return a


def _sig_of(v):
    # shape/dtype only — must NOT materialise device arrays (np.asarray on a
    # device value is a D2H transfer; doing that per state var per step would
    # ship every parameter to the host each iteration)
    from .core.lod import NestedSeqArray

    if isinstance(v, SeqArray):
        return ("seq",) + tuple(v.data.shape) + (str(v.data.dtype),)
    if isinstance(v, NestedSeqArray):
        return ("nested",) + tuple(v.data.shape) + (str(v.data.dtype),)
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        return tuple(v.shape) + (str(v.dtype),)
    a = np.asarray(v)
    return tuple(a.shape) + (str(a.dtype),)


class Executor:
    """Compiling executor.  API mirrors fluid.Executor (executor.py:149):
    ``run(program, feed, fetch_list, scope)`` -> list of numpy arrays."""

    # bound on distinct (program, signature) executables kept alive; LRU
    # eviction — the reference keeps no executable cache at all (it re-walks
    # the block per step), so any bound here is strictly better
    CACHE_CAPACITY = 64

    def __init__(self, place: Union[TPUPlace, CPUPlace, None] = None):
        self.place = place or TPUPlace(0)
        from collections import OrderedDict

        self._cache: "OrderedDict[tuple, Any]" = OrderedDict()
        # structural classification cache: (program fp, feed names, fetch
        # names) -> (traced_ops, pre_host, post_host, state_in, state_out).
        # Re-deriving this walks every op in the block (~thousands after
        # backward) — measurable per-step Python overhead in the hot loop
        # (the reference re-walks the block per step; we don't have to)
        self._cls_cache: "OrderedDict[tuple, Any]" = OrderedDict()
        # hit/miss/eviction counters for both caches — the observability
        # half of log_recompiles (cache_stats() accessor below)
        self._stats = {
            "executable": {"hits": 0, "misses": 0, "evictions": 0},
            "structure": {"hits": 0, "misses": 0, "evictions": 0},
            # pre-flight analysis (validate=...): "runs" = full analyses
            # performed, "cached" = dispatches that skipped re-analysis
            # because the (fingerprint, level) was already validated
            "validate": {"runs": 0, "cached": 0},
        }
        # the same counters keyed by analysis level (ISSUE 11 satellite):
        # a level="cost" run after a "structural" one is a fresh run, and
        # the per-level split makes that visible instead of folding every
        # level into one runs/cached pair
        self._validate_by_level: Dict[str, Dict[str, int]] = {}
        # (program fingerprint, level) pairs already analyzed clean —
        # the analyzer runs once per program STRUCTURE, not per step
        self._validated: set = set()
        # guardrail counters (health_stats()) + per-(program, scope)
        # guard contexts: the device-side last-good snapshot and the
        # consecutive-bad-step escalation counter.  Keyed by program
        # fingerprint with the owning scope held weakly — a snapshot of
        # program A's params must never be republished into program B's
        # scope (or A's vars into a fresh scope).
        self._health = {"guarded_steps": 0, "nonfinite_steps": 0,
                        "skips": 0, "rollbacks": 0, "escalations": 0,
                        "watchdog_fires": 0, "retries": 0}
        self._guard_ctxs: "OrderedDict[tuple, dict]" = OrderedDict()
        # (prog fp, fetch names, policy.check) -> sentinel check names
        self._guard_names: Dict[tuple, tuple] = {}
        # the counter dicts above stay the hot-path source of truth;
        # the registry reads them at SCRAPE time (bound method held
        # weakly — a GC'd executor stops contributing)
        from ..observability.metrics import registry as _obs_registry

        _obs_registry().register_collector(self._collect_metrics)

    def _collect_metrics(self):
        """Scrape-time view of cache_stats()/health_stats() as labeled
        series; samples from every live executor SUM into one process
        rollup (see observability.metrics)."""
        from ..observability.metrics import Sample

        for cache in ("executable", "structure"):
            st = self._stats[cache]
            for ev in ("hits", "misses", "evictions"):
                yield Sample(
                    "paddle_executor_cache_events_total", "counter",
                    (("cache", cache), ("event", ev)), float(st[ev]),
                    "Compiled-step / structure-classification cache events")
        for cache, size in (("executable", len(self._cache)),
                            ("structure", len(self._cls_cache)),
                            ("validated", len(self._validated))):
            yield Sample("paddle_executor_cache_size", "gauge",
                         (("cache", cache),), float(size),
                         "Live entries per executor-side cache")
        for ev in ("runs", "cached"):
            yield Sample("paddle_executor_validate_total", "counter",
                         (("event", ev),),
                         float(self._stats["validate"][ev]),
                         "Static-analysis pre-flight runs vs fingerprint "
                         "cache hits")
        for ev, v in self._health.items():
            yield Sample("paddle_guardrail_events_total", "counter",
                         (("event", ev),), float(v),
                         "Guardrail sentinel/recovery counters "
                         "(health_stats)")

    def health_stats(self) -> Dict[str, int]:
        """Guardrail counters (see resilience/guardrails.py):
        guarded_steps (dispatches run under a GuardPolicy),
        nonfinite_steps (health flag came back False), skips /
        rollbacks / escalations (recovery actions taken),
        watchdog_fires (dispatch deadline expiries), retries
        (transient-fault re-dispatches).  Deltas over a training window
        are the divergence telemetry the reference never had."""
        return dict(self._health)

    def cache_stats(self) -> Dict[str, Dict[str, int]]:
        """Counters for the executable cache (compiled step signatures)
        and the structure cache (feed/state/fetch classification):
        {'executable': {hits, misses, evictions, size}, 'structure':
        {...}}.  A hot training loop should converge to pure hits; a
        climbing miss count is the recompile churn `log_recompiles`
        prints about (unbucketed sequence lengths, drifting feed
        signatures, cache capacity thrash)."""
        out = {k: dict(v) for k, v in self._stats.items()}
        out["executable"]["size"] = len(self._cache)
        out["structure"]["size"] = len(self._cls_cache)
        out["validate"]["size"] = len(self._validated)
        out["validate"]["by_level"] = {
            lv: dict(c) for lv, c in self._validate_by_level.items()}
        return out

    # -- static-analysis pre-flight -----------------------------------------
    @staticmethod
    def _validate_level(validate: Optional[str]) -> str:
        """Resolve the effective pre-flight level: explicit arg wins, else
        the PADDLE_TPU_VALIDATE env flag, else off.  Any analysis LEVELS
        key is accepted — "cost" pre-flights the static cost family."""
        level = (validate if validate is not None
                 else os.environ.get("PADDLE_TPU_VALIDATE", "off"))
        from .analysis import LEVELS

        if level != "off" and level not in LEVELS:
            raise ValueError(
                f"validate must be 'off' or one of {sorted(LEVELS)}, "
                f"got {level!r}")
        return level

    def _preflight(self, program: Program, prog_fp: str, level: str,
                   fetch_names: Sequence[str]) -> None:
        """Run the static analyzer once per (program fingerprint, level);
        raise ProgramValidationError on error-severity findings.  The
        fingerprint cache makes validate="full" effectively free on the
        steps after the first (the <5% overhead contract).  Counters key
        on the LEVEL too: a "cost" run after a "structural" one of the
        same program is a fresh analysis, not a cache hit."""
        key = (prog_fp, level)
        by_level = self._validate_by_level.setdefault(
            level, {"runs": 0, "cached": 0})
        if key in self._validated:
            self._stats["validate"]["cached"] += 1
            by_level["cached"] += 1
            return
        self._stats["validate"]["runs"] += 1
        by_level["runs"] += 1
        from .analysis import ProgramValidationError, analyze_program

        diag = analyze_program(program, level=level, fetch=fetch_names)
        if diag.has_errors:
            raise ProgramValidationError(diag,
                                         context=f"validate={level!r}")
        self._validated.add(key)

    @staticmethod
    def _program_key(program: Program) -> str:
        """Content-addressed cache key: a sha256 fingerprint of the desc,
        recomputed only when the program's mutation version changes.  Keying
        on id(program) would alias a GC'd program whose id was reused."""
        cached = getattr(program, "_fp_cache", None)
        if cached is not None and cached[0] == program.version:
            return cached[1]
        fp = program.desc.fingerprint()
        program._fp_cache = (program.version, fp)
        return fp

    # -- host-side IO ops ---------------------------------------------------
    def _run_host_op(self, op, scope: Scope) -> None:
        from . import io as fluid_io

        if op.type in ("save", "save_combine"):
            names = op.input("X")
            path = op.attr("file_path")
            if op.type == "save":
                fluid_io.save_tensor(scope.find_var(names[0]), path)
            else:
                fluid_io.save_tensors({n: scope.find_var(n) for n in names}, path)
        elif op.type in ("load", "load_combine"):
            names = op.output("Out")
            path = op.attr("file_path")
            if op.type == "load":
                scope.set_var(names[0], fluid_io.load_tensor(path))
            else:
                loaded = fluid_io.load_tensors(path)
                for n in names:
                    scope.set_var(n, loaded[n])

    # -- main entry ---------------------------------------------------------
    @staticmethod
    def _classify_structure(traced_ops, feed_names, fetch_names, block):
        """Feed/state/fetch dataflow classification — structural, value
        free, cacheable per (program, feed names, fetch names):
        -> (state_in, state_out)."""
        written: set = set()
        state_in: List[str] = []
        seen_state: set = set()
        for op in traced_ops:
            for n in op.input_names():
                if n and n not in written and n not in feed_names \
                        and n not in seen_state:
                    seen_state.add(n)
                    state_in.append(n)
            for n in op.output_names():
                if n:
                    written.add(n)
        persistable = {n for n, vd in block.vars.items() if vd.persistable}
        state_out = [n for n in written
                     if n in persistable or n.startswith("@STATE@")]
        for n in fetch_names:
            if n not in written and n not in feed_names \
                    and n not in seen_state:
                seen_state.add(n)
                state_in.append(n)
        return state_in, state_out

    @staticmethod
    def _fetch_state(state_in, traced_ops, fetch_names, scope):
        """Pull the classified state vars from the scope (per step)."""
        state_vals = {}
        for n in state_in:
            v = scope.find_var(n)
            if v is None:
                if n in fetch_names and not any(
                        n in op.input_names() for op in traced_ops):
                    raise RuntimeError(
                        f"Executor: fetch target {n!r} is not produced by "
                        f"the program and not present in the scope")
                raise RuntimeError(
                    f"Executor: variable {n!r} is read by the program but "
                    f"absent from the scope — did you run the startup "
                    f"program? (reference executor raises the same way)")
            state_vals[n] = v
        return state_vals

    @staticmethod
    def _check_nan_inf(named_values) -> None:
        """Post-step scan of every produced value — the analog of
        CheckTensorNANOrInf per op output (executor.cc:64,129); shared
        by run() and run_steps()."""
        for name, v in named_values:
            arr = np.asarray(v.data if isinstance(v, SeqArray) else v)
            if np.issubdtype(arr.dtype, np.floating) and \
                    not np.isfinite(arr).all():
                raise FloatingPointError(
                    f"Tensor {name!r} contains NaN/Inf "
                    f"(FLAGS check_nan_inf)")

    def _lookup_executable(self, key, what: str = "step"):
        """Executable-cache probe with hit/miss accounting and the
        log_recompiles miss narration; returns the cached entry tuple
        or None."""
        entry = self._cache.get(key)
        if entry is not None:
            self._cache.move_to_end(key)
            self._stats["executable"]["hits"] += 1
            return entry
        self._stats["executable"]["misses"] += 1
        from ..utils.flags import FLAGS

        if FLAGS["log_recompiles"] and self._cache:
            import sys

            st = self._stats["executable"]
            print(f"[paddle_tpu] compiling new {what} signature "
                  f"(cache size {len(self._cache)}, "
                  f"hits {st['hits']} misses {st['misses']} "
                  f"evictions {st['evictions']})", file=sys.stderr)
        return None

    @staticmethod
    def _jit_step(step, in_shardings=None):
        """The one place a step function becomes an executable: state
        (argument 1) is ALWAYS donated, so parameters, optimizer moments
        and KV pools update in place.  Persistence across processes is
        JAX's compilation cache (``paddle_tpu._place_compile_cache``)."""
        kwargs = {} if in_shardings is None else \
            {"in_shardings": in_shardings}
        return jax.jit(step, donate_argnums=(1,), **kwargs)

    def _store_executable(self, key, entry) -> None:
        """Insert + LRU-evict with eviction accounting/narration."""
        from ..utils.flags import FLAGS

        self._cache[key] = entry
        while len(self._cache) > self.CACHE_CAPACITY:
            self._cache.popitem(last=False)
            self._stats["executable"]["evictions"] += 1
            if FLAGS["log_recompiles"]:
                import sys

                print("[paddle_tpu] evicted a compiled step (cache over "
                      f"capacity {self.CACHE_CAPACITY})", file=sys.stderr)

    def _classified(self, prog_fp, feed, fetch_names, block):
        """Structure-cache lookup (or derivation) of the block's
        host-op split + feed/state/fetch classification — the per-step
        Python cost run()/run_steps() must NOT re-pay in the hot loop:
        -> (traced_ops, pre_host, post_host, state_in, state_out)."""
        cls_key = (prog_fp, tuple(sorted(feed)), tuple(fetch_names))
        cls = self._cls_cache.get(cls_key)
        if cls is not None:
            self._cls_cache.move_to_end(cls_key)
            self._stats["structure"]["hits"] += 1
            return cls
        self._stats["structure"]["misses"] += 1
        # host IO ops (save/load) execute in block order relative to
        # the compiled segment: a `load` prologue before, a `save`
        # epilogue after (the reference executor runs them inline; an
        # IO op sandwiched between compute ops would need segment
        # splitting — reject it).
        traced_ops = [op for op in block.ops if op.type not in HOST_OPS]
        pre_host, post_host = [], []
        seen_traced = False
        for op in block.ops:
            if op.type in HOST_OPS:
                (post_host if seen_traced else pre_host).append(op)
            else:
                seen_traced = True
        for op in post_host:
            idx = block.ops.index(op)
            if any(o.type not in HOST_OPS for o in block.ops[idx:]):
                raise NotImplementedError(
                    "save/load ops interleaved between compute ops are "
                    "not supported; put IO ops at the block boundary or "
                    "in their own program")
        # classify vars: feeds come from the feed dict; every other var
        # read before written (or fetched but never written) must come
        # from the scope as state.
        state_in, state_out = self._classify_structure(
            traced_ops, set(feed), fetch_names, block)
        cls = (traced_ops, pre_host, post_host, state_in, state_out)
        self._cls_cache[cls_key] = cls
        while len(self._cls_cache) > self.CACHE_CAPACITY:
            self._cls_cache.popitem(last=False)
            self._stats["structure"]["evictions"] += 1
        return cls

    # -- guardrails ----------------------------------------------------------
    def _guard_check_names(self, prog_fp: str, policy, program, traced_ops,
                           state_out, fetch_names) -> tuple:
        """Resolve the sentinel's check set for this (program, fetch,
        policy.check) — cached, since re-walking every parameter per
        step is exactly the hot-loop Python cost the classifier caches
        exist to avoid.  'loss' = the fetches (non-floats are skipped
        at trace time), 'grads' = each parameter's @GRAD the program
        writes, 'params' = the post-update parameters themselves.
        Parameters are identified on the FRAMEWORK block (the desc
        block's VarDescs don't record parameter-ness)."""
        key = (prog_fp, tuple(fetch_names), policy.check)
        cached = self._guard_names.get(key)
        if cached is not None:
            return cached
        from .core.registry import grad_var_name
        from .framework import Parameter

        names: List[str] = []
        want = set(policy.check)
        if "loss" in want:
            names.extend(fetch_names)
        params = [n for n, v in program.global_block().vars.items()
                  if isinstance(v, Parameter)]
        if "grads" in want:
            written = {n for op in traced_ops
                       for n in op.output_names() if n}
            names.extend(g for g in (grad_var_name(p) for p in params)
                         if g in written)
        if "params" in want:
            pset = set(params)
            names.extend(n for n in state_out if n in pset)
        out = tuple(dict.fromkeys(names))
        self._guard_names[key] = out
        return out

    def _guard_ctx_for(self, prog_fp: str, scope) -> dict:
        """The guard context (snapshot + escalation counter) for this
        (program, scope) pairing — rollback must republish values that
        came from THIS scope's run of THIS program, and alternating
        scopes (an ensemble sharing one executor) must each keep their
        own escalation counter.  The scope is held weakly and verified
        by identity (an id() reused after GC must not inherit a stale
        snapshot).  LRU-bounded like the executable caches: an evicted
        context drops its device-resident snapshot instead of pinning
        HBM for programs that will never run again."""
        import weakref

        key = (prog_fp, id(scope))
        ctx = self._guard_ctxs.get(key)
        if ctx is None or ctx["scope"]() is not scope:
            ctx = {"scope": weakref.ref(scope), "snapshot": None,
                   "since_snapshot": 0, "consecutive_bad": 0}
            self._guard_ctxs[key] = ctx
        else:
            self._guard_ctxs.move_to_end(key)
        while len(self._guard_ctxs) > self.CACHE_CAPACITY:
            self._guard_ctxs.popitem(last=False)
        while len(self._guard_names) > self.CACHE_CAPACITY:
            self._guard_names.pop(next(iter(self._guard_names)))
        return ctx

    def _run_guarded(self, compiled, feed, state_vals, rng_bits, policy,
                     scope, prog_fp):
        """One guarded dispatch: chaos points -> rollback snapshot
        upkeep -> watchdog/retry dispatch -> recovery accounting.
        Returns (fetches, new_state, healthy); raises NonFiniteError /
        NonFiniteEscalation with the (pre-step) state already written
        back to the scope.  A StepFault/StepTimeout escape republishes
        the last-good snapshot into the scope when one exists (rollback
        policy) — without a snapshot the scope keeps its pre-dispatch
        entries, which a real-hardware mid-execution hang may have
        consumed (pair step_timeout with on_nonfinite="rollback" when
        the scope must survive a wedged device)."""
        from ..observability.tracing import tracer as _obs_tracer
        from ..resilience import guardrails as gr
        from ..resilience.chaos import injector

        tr = _obs_tracer()
        inj = injector()
        if inj.enabled():
            feed = gr.poison_feed(feed, inj)
        gctx = self._guard_ctx_for(prog_fp, scope)
        if policy.on_nonfinite == "rollback" and (
                gctx["snapshot"] is None
                or gctx["since_snapshot"] >= policy.snapshot_every):
            # pre-step state is always last-good (bad steps publish the
            # gated pre-step values), so snapshotting before dispatch
            # is safe at any cadence
            gctx["snapshot"] = gr.device_snapshot(state_vals)
            gctx["since_snapshot"] = 0

        def dispatch(ctl):
            if inj.enabled():
                inj.maybe_fail("guard.fault")
                inj.maybe_hang("guard.hang")
            if not ctl.begin_consume():
                # the watchdog abandoned this attempt while it stalled
                # host-side; a retry may already be re-dispatching the
                # same donated buffers — do not touch the device (the
                # claim is atomic with the monitor's cancel)
                raise gr.StepFault("dispatch abandoned after watchdog "
                                   "timeout")
            try:
                fetches, new_state, flag = compiled(feed, state_vals,
                                                    rng_bits)
                # the health flag materialises here, INSIDE the watchdog
                # deadline — a hung dispatch blocks on this sync
                return fetches, new_state, bool(np.asarray(flag))
            except Exception:
                # a transient PJRT fault (preemption, transport drop) is
                # only re-dispatchable if the donated inputs survived —
                # is_deleted() is ground truth, so a failure that left
                # every state buffer live releases the consumption claim
                # and stays retryable
                if gr.state_buffers_live(state_vals):
                    ctl.unconsume()
                raise

        try:
            fetches, new_state, healthy = gr.dispatch_guarded(
                dispatch, policy, self._health)
        except gr.StepFault:
            # the failed/hung dispatch may have consumed the scope's
            # donated buffers (real hardware); with a rollback policy
            # we hold a never-donated last-good snapshot — republish it
            # so the scope keeps live arrays for whoever catches this
            if gctx["snapshot"] is not None:
                for n, v in gr.device_snapshot(gctx["snapshot"]).items():
                    scope.set_var(n, v)
                gctx["since_snapshot"] = 0
                tr.instant("guard/fault_rollback", cat="guard")
            raise
        self._health["guarded_steps"] += 1
        gctx["since_snapshot"] += 1
        if healthy:
            gctx["consecutive_bad"] = 0
            return fetches, new_state, True
        self._health["nonfinite_steps"] += 1
        gctx["consecutive_bad"] += 1
        # a write-only persistable (a metric the program writes but never
        # reads) has no pre-step twin for the gate to select, so its
        # non-finite value came through ungated — drop it: a bad step
        # must not publish ANYTHING to the scope (or the next checkpoint
        # would durably record the poison)
        new_state = {n: v for n, v in new_state.items() if n in state_vals}
        escalate = (policy.escalate_after > 0
                    and gctx["consecutive_bad"] >= policy.escalate_after)
        tr.instant("guard/nonfinite_step", cat="guard",
                   consecutive=gctx["consecutive_bad"])
        if escalate:
            tr.instant("guard/escalation", cat="guard")
            self._health["escalations"] += 1
            gctx["consecutive_bad"] = 0
            gctx["snapshot"] = None     # the restorer will change the scope
            for n, v in new_state.items():
                scope.set_var(n, v)     # gated = pre-step, still live
            raise gr.NonFiniteEscalation(
                f"{policy.escalate_after} consecutive non-finite steps "
                f"under on_nonfinite={policy.on_nonfinite!r}; escalate to "
                f"checkpoint restore")
        if policy.on_nonfinite == "raise":
            for n, v in new_state.items():
                scope.set_var(n, v)
            raise gr.NonFiniteError(
                "guarded step produced non-finite values (loss/grad/param "
                "sentinel); scope holds the pre-step state")
        if policy.on_nonfinite == "rollback":
            tr.instant("guard/rollback", cat="guard")
            self._health["rollbacks"] += 1
            # publish COPIES: the snapshot itself must survive the next
            # dispatch donating whatever sits in the scope
            new_state = dict(new_state)
            new_state.update(gr.device_snapshot(gctx["snapshot"]))
            gctx["since_snapshot"] = 0  # scope now equals the snapshot
        else:                           # "skip": gated state IS pre-step
            tr.instant("guard/skip", cat="guard")
            self._health["skips"] += 1
        return fetches, new_state, False

    def _prepare_step(self, program, feed, fetch_list, scope, mode):
        """Shared prologue for the out-of-band step consumers
        (cost_analysis / device_time_per_step): normalize the call,
        classify state against the scope, and build the pure step fn —
        the same (cached) classification run() performs, so the
        analyzed/timed step IS the executed step.  Like run(), this
        rejects programs with host IO ops interleaved between compute
        ops."""
        program = program or default_main_program()
        feed = {k: _as_feed_value(v) for k, v in (feed or {}).items()}
        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in (fetch_list or [])]
        scope = scope or global_scope()
        desc = program.desc
        block = desc.global_block()
        traced_ops, _, _, state_in, state_out = self._classified(
            self._program_key(program), feed, fetch_names, block)
        state_vals = self._fetch_state(state_in, traced_ops, fetch_names,
                                       scope)
        step = build_step_fn(desc, 0, list(feed), state_in, state_out,
                             fetch_names, mode)
        return feed, state_vals, step

    def cost_analysis(self, program: Optional[Program] = None,
                      feed: Optional[Dict[str, Any]] = None,
                      fetch_list: Optional[Sequence] = None,
                      scope: Optional[Scope] = None,
                      mode: str = "train") -> Dict[str, float]:
        """HLO cost analysis of one compiled step — {'flops', 'bytes
        accessed', ...} — WITHOUT executing it (jax lowering only).  The
        honest-MFU primitive VERDICT r1 weak#1 calls for: measured step
        time + these flops ⇒ delivered FLOP/s ÷ chip peak."""
        feed, state_vals, step = self._prepare_step(program, feed,
                                                    fetch_list, scope, mode)
        import numpy as _np

        # fixed rng bits: analysis must not advance the scope's rng counter
        lowered = self._jit_step(step).lower(
            feed, state_vals, _np.zeros(2, _np.int32))
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not ca:
            # some PJRT plugins only expose cost analysis post-compile
            ca = lowered.compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
        return dict(ca or {})

    def memory_analysis(self, program: Optional[Program] = None,
                        feed: Optional[Dict[str, Any]] = None,
                        fetch_list: Optional[Sequence] = None,
                        scope: Optional[Scope] = None,
                        mode: str = "train") -> Dict[str, float]:
        """XLA's buffer-assignment view of one compiled step — argument/
        output/temp/alias bytes — WITHOUT executing it.  ``peak_bytes``
        (arguments + outputs + temps) is the measured counterpart of the
        static planner's peak (fluid/analysis/cost.plan_program): the
        pair is what bench.py's ``cost_model`` section gates against
        each other.  Returns {} when the PJRT plugin exposes no memory
        stats."""
        feed, state_vals, step = self._prepare_step(program, feed,
                                                    fetch_list, scope, mode)
        import numpy as _np

        lowered = self._jit_step(step).lower(
            feed, state_vals, _np.zeros(2, _np.int32))
        try:
            ma = lowered.compile().memory_analysis()
        except Exception:
            ma = None
        if ma is None:
            return {}
        out = {
            "argument_bytes": float(ma.argument_size_in_bytes),
            "output_bytes": float(ma.output_size_in_bytes),
            "temp_bytes": float(ma.temp_size_in_bytes),
            "alias_bytes": float(ma.alias_size_in_bytes),
            "generated_code_bytes": float(ma.generated_code_size_in_bytes),
        }
        # aliased (donated) buffers appear in argument_size and serve as
        # outputs in place — arguments + outputs + temps double-counts
        # exactly the aliased bytes
        out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                             + out["temp_bytes"] - out["alias_bytes"])
        return out

    def compiled_hlo(self, program: Optional[Program] = None,
                     feed: Optional[Dict[str, Any]] = None,
                     fetch_list: Optional[Sequence] = None,
                     scope: Optional[Scope] = None,
                     mode: str = "train") -> str:
        """Optimized HLO text of the step ``run()`` dispatches for this
        call — lowered with run()'s exact input shardings under the
        active mesh (feeds batch-sharded, persistables per their desc
        annotations) and compiled, WITHOUT executing.  What the device
        was really given: which custom calls (a Mosaic kernel is a
        ``tpu_custom_call``), which collectives, which aliases."""
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel import mesh as _pmesh

        mesh = _pmesh.current_mesh()
        program = program or default_main_program()
        feed, state_vals, step = self._prepare_step(program, feed,
                                                    fetch_list, scope, mode)
        in_sh = None
        if mesh is not None:
            block = program.desc.global_block()
            feed_sh = {n: _pmesh.feed_sharding(mesh, v)
                       for n, v in feed.items()}
            state_sh = {
                n: _pmesh.state_sharding(
                    mesh, v,
                    block.vars[n].sharding if n in block.vars else None)
                for n, v in state_vals.items()}
            in_sh = (feed_sh, state_sh,
                     NamedSharding(mesh, PartitionSpec()))
            # run()'s re-layout rule: state whose current placement
            # disagrees with its annotation (e.g. loaded replicated)
            # moves first, or lowering rejects the arg/sharding mismatch
            for n, target in state_sh.items():
                v = state_vals[n]
                cur = getattr(v, "sharding", None)
                if cur is not None and not isinstance(v, SeqArray) \
                        and cur != target:
                    state_vals[n] = jax.device_put(v, target)
        # fixed rng bits: analysis must not advance the scope's rng counter
        lowered = self._jit_step(step, in_sh).lower(
            feed, state_vals, np.zeros(2, np.int32))
        return lowered.compile().as_text()

    # HLO element-type byte widths for collective payload accounting
    _HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
                  "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                  "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                  "f64": 8, "c64": 8, "c128": 16}

    def collective_analysis(self, program: Optional[Program] = None,
                            feed: Optional[Dict[str, Any]] = None,
                            fetch_list: Optional[Sequence] = None,
                            scope: Optional[Scope] = None,
                            mode: str = "infer") -> Dict[str, Any]:
        """MEASURED collective traffic of one SPMD step: the program is
        lowered under the active mesh with run()'s exact input shardings
        (feeds batch-sharded, persistables per their desc annotations),
        and the partitioner's optimized HLO is scanned for collective
        instructions — the ground truth the static estimator
        (analysis/comms.estimate_comms) predicts from descs alone.
        Returns {kind: {count, payload_bytes}} per collective kind plus
        ``total_payload_bytes`` (sum of per-shard operand bytes) and the
        mesh shape; {} without an active mesh (no partitioner, no
        collectives).  Lowering only — nothing executes."""
        from ..parallel import mesh as _pmesh

        mesh = _pmesh.current_mesh()
        if mesh is None:
            return {}
        per_kind, total = self.collectives_in_hlo(
            self.compiled_hlo(program, feed, fetch_list, scope, mode))
        return {
            "per_kind": per_kind,
            "total_payload_bytes": total,
            "mesh_axes": {str(a): int(s) for a, s in mesh.shape.items()},
        }

    @classmethod
    def collectives_in_hlo(cls, hlo: str):
        """({kind: {count, payload_bytes}}, total bytes) of the
        collective instructions in optimized HLO text, payload = bytes
        of each instruction's result shape(s)."""
        import re

        kinds = ("all-reduce", "all-gather", "reduce-scatter",
                 "all-to-all", "collective-permute")
        # result shape(s), then the op.  The shape text is "anything up
        # to the op name": TPU layouts carry tiling and memory-space
        # annotations (f32[8,128]{1,0:T(8,128)S(1)}), and XLA numbers
        # long tuples with /*index=N*/ comments — exactly the combined
        # gradient all-reduce of a dp step (stripped below)
        head = re.compile(
            r"=\s+(\S.*?)\s+(" + "|".join(kinds) + r")(?:-start)?\(")
        shape = re.compile(r"([a-z]\d*[a-z0-9]*)\[([0-9,]*)\]")
        per_kind: Dict[str, Dict[str, float]] = {}
        total = 0.0
        for line in hlo.splitlines():
            m = head.search(re.sub(r"/\*.*?\*/", "", line))
            if not m:
                continue
            result, kind = m.group(1), m.group(2)
            payload = 0.0
            for dt, dims in shape.findall(result):
                width = cls._HLO_BYTES.get(dt)
                if width is None:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                payload += n * width
            d = per_kind.setdefault(kind,
                                    {"count": 0, "payload_bytes": 0.0})
            d["count"] += 1
            d["payload_bytes"] += payload
            total += payload
        return per_kind, total

    def device_time_per_step(self, program: Optional[Program] = None,
                             feed: Optional[Dict[str, Any]] = None,
                             fetch_list: Optional[Sequence] = None,
                             scope: Optional[Scope] = None,
                             iters: int = 50, trials: int = 3,
                             mode: str = "train") -> float:
        """Seconds per step with ``iters`` steps CHAINED inside one jit
        (a lax.fori_loop carrying the state dict) — pure DEVICE time.
        Per-call ``run`` timing includes one host dispatch per step,
        which for a small step can dwarf the chip (the analog of
        wall-clocking each Session call instead of profiling the
        kernels).  The chained number is the profiler-grade ms/batch.
        The scope is NOT updated (the chained states are discarded)."""
        feed, state_vals, step = self._prepare_step(program, feed,
                                                    fetch_list, scope, mode)
        import jax.numpy as jnp

        def chained(feeds, state):
            # the carry threads BOTH the state and a scalar folded from
            # the fetches: without the fetch fold, a program that updates
            # no state (mode='infer') would reduce to an identity carry
            # and XLA would dead-code-eliminate the whole step
            def body(i, carry):
                st, acc = carry
                # fixed seed, per-iteration fold only: timing must not
                # advance the scope's rng counter (cost_analysis rule)
                fetches, ns = step(feeds, st,
                                   jnp.stack([jnp.int32(0),
                                              i.astype(jnp.int32)]))
                for f in fetches:
                    acc = acc + jnp.sum(jnp.asarray(f).astype(
                        jnp.float32)) * 1e-12
                # keys must stay type-stable across iterations: only
                # entries the next step reads (state_in) carry forward
                return ({n: ns.get(n, st[n]) for n in st}, acc)
            return jax.lax.fori_loop(0, iters, body,
                                     (state, jnp.float32(0.0)))

        fn = jax.jit(chained)

        def _sync(res):
            _, acc = res
            float(jnp.asarray(acc).astype(jnp.float32))  # D2H barrier

        _sync(fn(feed, dict(state_vals)))
        best = float("inf")
        for _ in range(max(1, trials)):
            t0 = time.perf_counter()
            _sync(fn(feed, dict(state_vals)))
            best = min(best, (time.perf_counter() - t0) / max(1, iters))
        return best

    def run(self, program: Optional[Program] = None,
            feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
            scope: Optional[Scope] = None, return_numpy: bool = True,
            mode: str = "train",
            validate: Optional[str] = None,
            guard=None) -> List[Any]:
        """``validate``: opt-in static-analysis pre-flight — "off" (default),
        "structural" (desc-only passes) or "full" (adds the abstract
        shape/dtype re-check).  Defaults to the PADDLE_TPU_VALIDATE env
        flag; analysis is cached by program fingerprint, so a hot loop
        pays it once.

        ``guard``: a ``resilience.GuardPolicy`` (or an ``on_nonfinite``
        string shorthand) enabling the training guardrails: the step is
        compiled with a fused finiteness sentinel over loss/grads/params
        (same dispatch — no extra device round-trip), non-finite steps
        are raised/skipped/rolled back per the policy with the scope
        never holding a corrupted update, and the dispatch runs under
        the policy's watchdog deadline + transient-fault retry.
        Counters: ``health_stats()``.  Guarded steps are
        bitwise-identical to unguarded ones on healthy batches."""
        from ..observability.tracing import tracer as _obs_tracer
        from ..utils.flags import FLAGS

        tr = _obs_tracer()
        # four spans a step, five on a miss: prepare, (compile,) the
        # launch (executor_step/<mode>, below), writeback and release
        with tr.span("executor/prepare", cat="executor", mode=mode):
            policy = None
            if guard is not None:
                from ..resilience.guardrails import GuardPolicy

                policy = (guard if isinstance(guard, GuardPolicy)
                          else GuardPolicy(on_nonfinite=str(guard)))
            program = program or default_main_program()
            feed = {k: _as_feed_value(v) for k, v in (feed or {}).items()}
            fetch_names = [f.name if isinstance(f, Variable) else str(f)
                           for f in (fetch_list or [])]
            scope = scope or global_scope()
            desc = program.desc
            block = desc.global_block()

            prog_fp = self._program_key(program)
            level = self._validate_level(validate)
            if level != "off":
                self._preflight(program, prog_fp, level, fetch_names)
            traced_ops, pre_host, post_host, state_in, state_out = \
                self._classified(prog_fp, feed, fetch_names, block)

            for op in pre_host:
                self._run_host_op(op, scope)
            if not traced_ops and not fetch_names:
                for op in post_host:
                    self._run_host_op(op, scope)
                return []

            state_vals = self._fetch_state(state_in, traced_ops, fetch_names,
                                           scope)

            from ..parallel import mesh as _pmesh

            mesh = _pmesh.current_mesh()
            # content key, not id(mesh): a GC'd Mesh's reused id must not
            # replay an executable jitted for different axes/devices (same
            # hazard the program fingerprint guards against)
            mesh_key = None if mesh is None else (
                tuple(mesh.shape.items()),
                tuple(d.id for d in mesh.devices.flat))
            guard_names = None
            if policy is not None:
                guard_names = self._guard_check_names(
                    prog_fp, policy, program, traced_ops, state_out,
                    fetch_names)
            key = (self._program_key(program), mode, mesh_key,
                   tuple((n, _sig_of(v)) for n, v in sorted(feed.items())),
                   tuple(fetch_names),
                   tuple((n, _sig_of(v))
                         for n, v in sorted(state_vals.items())),
                   None if guard_names is None else ("guard",) + guard_names)
            compiled, state_sh, feed_sh = self._lookup_executable(key) \
                or (None, None, None)
        if compiled is None:
            import hashlib

            # the digest answers "which step recompiled": equal keys,
            # equal digests, within one process
            digest = hashlib.sha1(repr(key).encode()).hexdigest()[:12]
            with tr.span("executor/compile", cat="executor", mode=mode,
                         key=digest) as compile_args:
                if policy is not None:
                    from ..resilience.guardrails import build_guarded_step_fn

                    step = build_guarded_step_fn(desc, 0, list(feed), state_in,
                                                 state_out, fetch_names, mode,
                                                 guard_names)
                else:
                    step = build_step_fn(desc, 0, list(feed), state_in,
                                         state_out, fetch_names, mode)
                in_sh = None
                if mesh is not None:
                    # SPMD: feeds batch-sharded over 'dp', persistables per
                    # their desc annotations; the partitioner emits the grad
                    # all-reduce the reference needed pserver/NCCL for.
                    feed_sh = {n: _pmesh.feed_sharding(mesh, v)
                               for n, v in feed.items()}
                    state_sh = {
                        n: _pmesh.state_sharding(
                            mesh, v, block.vars[n].sharding
                            if n in block.vars else None)
                        for n, v in state_vals.items()}
                    from jax.sharding import NamedSharding, PartitionSpec

                    in_sh = (feed_sh, state_sh,
                             NamedSharding(mesh, PartitionSpec()))
                else:
                    feed_sh = None
                compiled = self._jit_step(step, in_sh)
                # trace here, once (the dispatch below finds the trace
                # cached), so that what the lowering notes lies under
                # this span: which road each attention gradient took
                compiled.trace(feed, state_vals,
                               jax.ShapeDtypeStruct((2,), np.int32))
                routes = [a["route"] for what, a in step.noted
                          if what == "attn_grad"]
                compile_args["attn_grad_direct"] = routes.count("direct")
                compile_args["attn_grad_vjp"] = routes.count("vjp")
                self._store_executable(key, (compiled, state_sh
                                             if mesh is not None else None,
                                             feed_sh))

        if state_sh is not None:
            # re-lay out state whose current placement disagrees with its
            # annotation (e.g. arrays produced by a mesh-less startup run or
            # loaded from a checkpoint) — an explicit device_put, the analog
            # of the reference's DataTransform between kernels
            for n, target in state_sh.items():
                v = state_vals[n]
                cur = getattr(v, "sharding", None)
                if cur is not None and not isinstance(v, SeqArray) \
                        and cur != target:
                    state_vals[n] = jax.device_put(v, target)

        rng_bits = scope.next_rng_bits(program.random_seed)
        if mesh is not None and jax.process_count() > 1:
            # multi-host SPMD: jit rejects host numpy under non-trivial
            # shardings.  Feeds are GLOBAL batches (every process passes
            # the same array — single-process semantics preserved); each
            # process materialises only its addressable shards.  This is
            # where the reference's trainer sharded data across pserver
            # trainers; per-host input pipelines can still pass
            # pre-sharded jax.Arrays directly.
            def _globalize(v, sh, name, what):
                if isinstance(v, jax.Array) or sh is None:
                    return v
                if isinstance(v, SeqArray):
                    if isinstance(v.data, jax.Array) and \
                            isinstance(v.lengths, jax.Array):
                        return v
                    raise NotImplementedError(
                        f"multi-host SPMD: {what} {name!r} is a SeqArray "
                        f"with host-numpy contents; pass BOTH data and "
                        f"lengths as device arrays (jax.Array) — host "
                        f"numpy sequence values are single-process only")
                a = np.asarray(v)
                return jax.make_array_from_callback(
                    a.shape, sh, lambda idx: a[idx])

            feed = {n: _globalize(v, (feed_sh or {}).get(n), n, "feed")
                    for n, v in feed.items()}
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(mesh, PartitionSpec())
            state_vals = {n: _globalize(v, state_sh.get(n, repl), n,
                                        "state var")
                          for n, v in state_vals.items()}
            rng_bits = _globalize(np.asarray(rng_bits), repl, "__rng__",
                                  "rng")

        from .profiler import record_event

        with record_event(f"executor_step/{mode}"):
            if policy is not None:
                fetches, new_state, _healthy = self._run_guarded(
                    compiled, feed, state_vals, rng_bits, policy, scope,
                    prog_fp)
            else:
                fetches, new_state = compiled(feed, state_vals, rng_bits)
                if FLAGS["benchmark"]:
                    jax.block_until_ready(fetches)
        if FLAGS["check_nan_inf"] and (
                policy is None
                or set(policy.check) != {"loss", "grads", "params"}):
            # the full-check sentinel supersedes the host-side post-hoc
            # scan; a guard watching a NARROWER set must not silently
            # disable the explicitly-requested global scan (note the
            # scan raises on the non-finite fetches of a skipped step —
            # the flag's promise is "raise on any non-finite", and it
            # outranks a partial guard's recovery)
            self._check_nan_inf(list(new_state.items()) +
                                list(zip(fetch_names, fetches)))
        with tr.span("executor/writeback", cat="executor", mode=mode):
            for n, v in new_state.items():
                scope.set_var(n, v)
            for op in post_host:
                self._run_host_op(op, scope)

            if return_numpy:
                out = [_to_numpy(f) for f in fetches]
            else:
                out = list(fetches)
        with tr.span("executor/release", cat="executor", mode=mode):
            # what this frame alone still holds dies here, under a name,
            # and not unseen as the frame unwinds: the state that went
            # in, and the signature key over every variable of it
            del state_vals, new_state, feed, fetches, key
        return out

    # -- pipelined dispatch --------------------------------------------------
    def run_pipeline(self, program: Optional[Program] = None,
                     loader=None,
                     fetch_list: Optional[Sequence] = None,
                     scope: Optional[Scope] = None,
                     fetch_every: int = 8, return_numpy: bool = True,
                     mode: str = "train", on_fetch=None,
                     guard=None) -> List[Any]:
        """Drive a DataLoader (or any iterable of feed dicts) through
        compiled steps WITHOUT blocking on fetch each iteration.

        Each step is the exact same dispatch ``run()`` performs (same
        executable cache, same rng advancement, donated state buffers
        reused in place), so the results are bitwise identical to the
        synchronous loop — the difference is purely scheduling: fetches
        stay device-resident futures and only materialise every
        ``fetch_every`` steps, so the host races ahead dispatching and
        the loader's device-prefetch overlaps H2D with compute.  Up to
        ``fetch_every`` steps are in flight at once (the periodic drain
        is the backpressure that stops the host queueing unbounded
        work).

        Returns the per-step fetch lists, or — when ``on_fetch(outs)``
        is given — streams them to the callback and returns the step
        count (long epochs should stream; accumulating a million fetch
        lists is its own host stall).

        Caveat: fetching a STATE value (a persistable such as a
        parameter, or any var the program does not itself compute)
        forces per-step host materialisation — such a fetch aliases a
        buffer the next step donates, so deferring it is unsafe.  The
        loop then performs like the synchronous one; keep fetch lists
        to freshly computed values (losses, metrics) for overlap.

        ``guard`` (a resilience.GuardPolicy) threads through to each
        step's run(); note the health flag syncs per step, so a guarded
        pipeline trades the deferred-fetch overlap for the sentinel.
        """
        if loader is None:
            raise ValueError("run_pipeline needs a loader (DataLoader or "
                             "iterable of feed dicts)")
        if callable(loader) and not hasattr(loader, "__iter__"):
            loader = loader()    # zero-arg reader convention
        fetch_every = max(1, int(fetch_every))
        # a fetched STATE value shares its buffer with the scope entry
        # the NEXT step donates — holding such a fetch device-side
        # across steps would read a reused/deleted buffer on hardware
        # where donation is real.  State here means anything that is
        # not freshly WRITTEN by the program this step (persistables,
        # @STATE@ names, and scope-only fetch targets the program never
        # produces).  Those fetches materialise to host numpy
        # IMMEDIATELY (overriding return_numpy=False — a live device
        # alias is never safe to hand back); deferred fetch is only for
        # freshly computed values (losses, metrics).
        blk = (program or default_main_program()).desc.global_block()
        # written by the COMPILED step only: a var a host load op
        # produces is served from scope state (donated) like any other
        written = {n for op in blk.ops if op.type not in HOST_OPS
                   for n in op.output_names() if n}
        force_numpy = False
        for f in (fetch_list or []):
            n = f.name if isinstance(f, Variable) else str(f)
            if n.startswith("@STATE@") or n not in written or (
                    n in blk.vars and blk.vars[n].persistable):
                fetch_every = 1
                force_numpy = True
                break
        pending: List[Any] = []
        results: List[Any] = []
        n_steps = 0

        from ..observability.tracing import tracer as _obs_tracer

        tr = _obs_tracer()

        def _drain():
            if not pending:
                return
            with tr.span("executor/fetch_drain", cat="executor",
                         steps=len(pending)):
                for outs in pending:
                    if return_numpy or force_numpy:
                        outs = [_to_numpy(f) for f in outs]
                    else:
                        # still a sync point: without it the device-fetch
                        # path would let the host dispatch arbitrarily far
                        # ahead, voiding the documented in-flight bound
                        outs = list(outs)
                        jax.block_until_ready(outs)
                    if on_fetch is not None:
                        on_fetch(outs)
                    else:
                        results.append(outs)
                pending.clear()

        try:
            for feed in loader:
                outs = self.run(program, feed=feed, fetch_list=fetch_list,
                                scope=scope, return_numpy=False, mode=mode,
                                guard=guard)
                n_steps += 1
                pending.append(outs)
                if len(pending) >= fetch_every:
                    _drain()
        except BaseException:
            # deliver fetches of steps that DID execute even when the
            # loader raises mid-epoch (the scope already advanced
            # through them) — but never let that best-effort drain
            # mask the root-cause error
            try:
                _drain()
            except Exception:
                pass
            raise
        _drain()
        return n_steps if on_fetch is not None else results

    def run_steps(self, program: Optional[Program] = None,
                  feeds: Optional[Sequence[Dict[str, Any]]] = None,
                  fetch_list: Optional[Sequence] = None,
                  scope: Optional[Scope] = None,
                  return_numpy: bool = True,
                  mode: str = "train") -> List[List[Any]]:
        """Execute ``len(feeds)`` steps in ONE device dispatch.

        The real version of ``device_time_per_step``'s chained-steps
        trick: the per-step function is wrapped in a ``lax.scan`` over
        the stacked feed batches (carrying the state dict), so k
        optimizer steps cost one host dispatch instead of k — for
        small steps the difference between paying the dispatch latency
        per step and per k steps.  Unlike the timing helper this is
        a first-class execution mode: the scope's rng advances exactly
        as k ``run()`` calls would, the final state is written back, and
        every step's fetches are returned (list over steps of fetch
        lists, matching ``run``'s shape).

        All feeds must share one signature (bucket padded sequences).
        Under an SPMD mesh or multi-host the scan would need
        axis-shifted shardings; those fall back to per-step dispatch —
        same results, no fusion.
        """
        feeds = list(feeds or [])
        if not feeds:
            return []
        from ..parallel import mesh as _pmesh

        if _pmesh.current_mesh() is not None or jax.process_count() > 1:
            return [self.run(program, feed=f, fetch_list=fetch_list,
                             scope=scope, return_numpy=return_numpy,
                             mode=mode) for f in feeds]

        program = program or default_main_program()
        feeds = [{k: _as_feed_value(v) for k, v in f.items()}
                 for f in feeds]
        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in (fetch_list or [])]
        scope = scope or global_scope()
        desc = program.desc
        block = desc.global_block()
        k = len(feeds)

        prog_fp = self._program_key(program)
        level = self._validate_level(None)
        if level != "off":       # PADDLE_TPU_VALIDATE covers scans too
            self._preflight(program, prog_fp, level, fetch_names)
        traced_ops, pre_host, post_host, state_in, state_out = \
            self._classified(prog_fp, feeds[0], fetch_names, block)
        if pre_host or post_host:
            raise NotImplementedError(
                "run_steps cannot scan over host IO ops (save/load); "
                "run them in their own program")

        sig0 = tuple((n, _sig_of(v)) for n, v in sorted(feeds[0].items()))
        for i, f in enumerate(feeds[1:], 1):
            sig = tuple((n, _sig_of(v)) for n, v in sorted(f.items()))
            if sig != sig0:
                raise ValueError(
                    f"run_steps feed #{i} signature differs from feed #0 "
                    f"— every step in one dispatch must share a compiled "
                    f"shape (bucket sequence lengths / fix the batch "
                    f"size): {sig} != {sig0}")

        state_vals = self._fetch_state(state_in, traced_ops, fetch_names,
                                       scope)
        from ..utils.flags import FLAGS

        import jax.numpy as jnp
        from jax import tree_util as jtu

        stacked_feeds = jtu.tree_map(lambda *xs: jnp.stack(xs), *feeds)
        # the SAME rng stream k sequential run() calls would consume
        rng_stack = np.stack([scope.next_rng_bits(program.random_seed)
                              for _ in range(k)])

        key = (prog_fp, mode, ("scan", k), sig0, tuple(fetch_names),
               tuple((n, _sig_of(v)) for n, v in sorted(state_vals.items())))
        compiled, _, _ = self._lookup_executable(key, f"{k}-step scan") \
            or (None, None, None)
        if compiled is None:
            step = build_step_fn(desc, 0, list(feeds[0]), state_in,
                                 state_out, fetch_names, mode)

            def multi(stacked_feeds, state, rng_stack):
                def body(st, xs):
                    fd, bits = xs
                    fetches, ns = step(fd, st, bits)
                    # carry keys stay type-stable (state_in); outputs the
                    # next step never reads ride along in ys so the
                    # epilogue can still persist them
                    carry = {n: ns.get(n, st[n]) for n in st}
                    extra = {n: v for n, v in ns.items() if n not in st}
                    return carry, (fetches, extra)

                return jax.lax.scan(body, state, (stacked_feeds, rng_stack))

            multi.__name__ = f"{mode}_scan{k}"
            compiled = self._jit_step(multi)
            self._store_executable(key, (compiled, None, None))

        from .profiler import record_event

        with record_event(f"executor_scan{k}/{mode}"):
            final_state, (fetch_stack, extra_stack) = compiled(
                stacked_feeds, state_vals, rng_stack)
            if FLAGS["benchmark"]:
                jax.block_until_ready(fetch_stack)

        # write back EVERY carried entry, not just the classified
        # state_out: the whole state dict was donated, so any var not
        # re-stored (read-only LR, all params under mode='infer') would
        # be a deleted buffer in the scope on hardware where donation is
        # real (build_step_fn returns every entry for the same reason)
        new_state = dict(final_state)
        new_state.update({n: jtu.tree_map(lambda a: a[-1], v)
                          for n, v in extra_stack.items()})
        if FLAGS["check_nan_inf"]:
            self._check_nan_inf(list(new_state.items()) +
                                list(zip(fetch_names, fetch_stack)))
        for n, v in new_state.items():
            scope.set_var(n, v)

        out: List[List[Any]] = []
        for i in range(k):
            row = [jtu.tree_map(lambda a: a[i], f) for f in fetch_stack]
            out.append([_to_numpy(f) for f in row] if return_numpy
                       else row)
        return out

    def close(self):
        self._cache.clear()
        self._cls_cache.clear()
        self._validated.clear()
        self._guard_ctxs.clear()
        self._guard_names.clear()


def _is_cpu(place) -> bool:
    return isinstance(place, CPUPlace)


def _to_numpy(v):
    from .core.lod import NestedSeqArray

    if isinstance(v, SeqArray):
        return SeqArray(np.asarray(v.data), np.asarray(v.lengths))
    if isinstance(v, NestedSeqArray):
        # keep the level-2 structure: dropping to the dense block would
        # lose the per-hypothesis lengths beam_search_decode produces
        return NestedSeqArray(np.asarray(v.data),
                              np.asarray(v.outer_lengths),
                              np.asarray(v.inner_lengths))
    return np.asarray(v)
