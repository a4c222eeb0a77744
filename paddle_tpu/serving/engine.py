"""InferenceEngine: shape-bucketed compiled inference over a pruned program.

The reference's deployment path (`paddle/capi` /
`paddle_gradient_machine_create_for_inference`, inference/io.h) loads a
merged model once and then forwards arbitrary-shaped requests through
the interpreted GradientMachine.  Under XLA, arbitrary shapes are the
enemy: every distinct (batch, seq) signature compiles a fresh
executable.  The engine makes the shape set finite:

* requests are padded UP into a small set of batch buckets (and, for
  SeqArray feeds, time buckets), so mixed traffic reuses a handful of
  compiled executables — zero recompiles in steady state;
* outputs are sliced back to the true batch, so bucketing is invisible
  to the caller (tests assert output invariance);
* weights live in the scope as device-resident arrays (``warmup`` /
  first dispatch uploads them; the executor's donated state round-trip
  keeps them on device);
* ``cache_stats()`` exposes bucket hit/miss counters next to the
  executor's executable-cache counters — the observability contract the
  acceptance test asserts 0-recompile steady state with.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .. import fluid
from ..fluid.core.lod import NestedSeqArray, SeqArray
from ..fluid.framework import Variable

__all__ = ["InferenceEngine"]

DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def _pad_rows(a: np.ndarray, n: int) -> np.ndarray:
    """Pad the batch axis to ``n`` rows by replicating the last row —
    replicated real data can never produce NaN paths a zero row might."""
    if a.shape[0] == n:
        return a
    pad = np.repeat(a[-1:], n - a.shape[0], axis=0)
    return np.concatenate([a, pad], axis=0)


def _pad_time(a: np.ndarray, t: int) -> np.ndarray:
    if a.shape[1] == t:
        return a
    width = [(0, 0)] * a.ndim
    width[1] = (0, t - a.shape[1])
    return np.pad(a, width)


def _slice_rows(v, n: int):
    """Row-slice WITHOUT materialising to host: device arrays slice
    device-side, so the padded bucket rows never ride a D2H transfer."""
    if isinstance(v, SeqArray):
        return SeqArray(v.data[:n], v.lengths[:n])
    if isinstance(v, NestedSeqArray):
        return NestedSeqArray(v.data[:n], v.outer_lengths[:n],
                              v.inner_lengths[:n])
    return v[:n]


def _rows_to_numpy(v):
    if isinstance(v, SeqArray):
        return SeqArray(np.asarray(v.data), np.asarray(v.lengths))
    if isinstance(v, NestedSeqArray):
        return NestedSeqArray(np.asarray(v.data),
                              np.asarray(v.outer_lengths),
                              np.asarray(v.inner_lengths))
    return np.asarray(v)


class InferenceEngine:
    """Bucketed, executable-cached inference over one pruned program.

    Construct either from a ``save_inference_model`` directory
    (``InferenceEngine(dirname=...)``) or from an in-memory pruned
    program (``InferenceEngine(program=..., feed_names=...,
    fetch_vars=..., scope=...)`` — e.g. ``fluid.io.prune_program`` output
    sharing a trained scope).
    """

    def __init__(self, program=None, feed_names: Optional[Sequence] = None,
                 fetch_vars: Optional[Sequence] = None, *,
                 dirname: Optional[str] = None, scope=None, place=None,
                 executor=None,
                 batch_buckets: Sequence[int] = DEFAULT_BATCH_BUCKETS,
                 time_bucket: int = 8, mode: str = "infer",
                 quantize: str = "off"):
        if quantize not in ("off", "int8"):
            raise ValueError(f"quantize={quantize!r}: 'off' or 'int8'")
        owns_scope = scope is None
        self.scope = scope or fluid.Scope()
        self.exe = executor or fluid.Executor(place or fluid.TPUPlace(0))
        if dirname is not None:
            if program is not None:
                raise ValueError("pass program=... or dirname=..., not both")
            # when quantizing, the fp32 weights are only calibration input
            # on the host — _quantize_int8 re-places the int8 copies, so
            # uploading the full fp32 model first would be discarded work
            program, feed_names, fetch_vars = fluid.io.load_inference_model(
                dirname, self.exe, scope=self.scope,
                to_device=(quantize != "int8"))
        if program is None:
            raise ValueError("InferenceEngine needs a program or a dirname")
        self._quant_stats = None
        if quantize == "int8":
            program = self._quantize_int8(program, clone_scope=not owns_scope)
        self.quantize = quantize
        self.program = program
        self.feed_names = list(feed_names or [])
        self.fetch_list = [f if isinstance(f, Variable) else str(f)
                           for f in (fetch_vars or [])]
        self.mode = mode
        self.batch_buckets = tuple(sorted(set(int(b) for b in batch_buckets)))
        self.time_bucket = max(1, int(time_bucket))
        self._stats = {"bucket_hits": 0, "bucket_misses": 0}
        self._buckets: Dict[tuple, int] = {}
        # padding honesty counters (ISSUE 6 satellite): the rows/tokens
        # the caller actually asked for vs what the bucket dispatched —
        # before this, padded slots were invisible in cache_stats and the
        # dense-vs-paged HBM comparison under-counted the dense waste
        self._padding = {"true_rows": 0, "padded_rows": 0,
                         "true_tokens": 0, "padded_tokens": 0}
        self._warming = False
        # telemetry (ISSUE 8): the counter dicts above stay the source
        # of truth; a weak scrape-time collector exports them labeled
        from ..observability.metrics import registry as _obs_registry

        _obs_registry().register_collector(self._collect_metrics)

    def _collect_metrics(self):
        from ..observability.metrics import Sample

        for ev in ("bucket_hits", "bucket_misses"):
            yield Sample("paddle_engine_bucket_events_total", "counter",
                         (("event", ev.split("_", 1)[1]),),
                         float(self._stats[ev]),
                         "Shape-bucket reuse vs first-compile events")
        yield Sample("paddle_engine_buckets", "gauge", (),
                     float(len(self._buckets)),
                     "Distinct compiled shape buckets registered")
        for k, v in self._padding.items():
            kind, what = k.split("_", 1)    # true/padded x rows/tokens
            yield Sample(f"paddle_engine_padding_{what}_total",
                         "counter", (("kind", kind),), float(v),
                         "Requested vs dispatched rows/tokens (padding "
                         "honesty counters)")

    # -- post-training quantization (ISSUE 7) --------------------------------
    def _quantize_int8(self, program, clone_scope=True):
        """Clone the program and the persistable slice of the scope, then
        run the per-channel int8 PTQ rewrite over the PRIVATE copies —
        a trained scope shared with the caller keeps its fp32 weights
        (the transform replaces weight values in place, which must never
        leak back into training).  ``clone_scope=False`` skips the scope
        copy when the engine created the scope itself (dirname load with
        no caller scope): it is already private, and cloning would
        transiently double the host weight footprint for nothing."""
        from ..fluid.transforms.quantize import quantize_program

        program = program.clone(for_test=True)
        if clone_scope:
            private = fluid.Scope()
            for v in program.list_vars():
                if v.persistable:
                    val = self.scope.find_var(v.name)
                    if val is not None:
                        # host COPY, not a reference: the donor scope's
                        # device buffers get donated by its own executor
                        # dispatches, and a shared jax.Array would be
                        # left deleted under us
                        private.set_var(v.name, np.array(np.asarray(val)))
            self.scope = private
        self._quant_stats = quantize_program(program, self.scope)
        # the host copies above are host-resident (dirname loads skip the
        # device upload when quantizing): place the int8 weights + scale
        # sidecars so the first request doesn't pay the H2D upload the
        # to_device contract exists to prevent
        fluid.io.device_put_persistables(self.scope, program)
        return program

    # -- bucketing -----------------------------------------------------------
    def _batch_bucket(self, b: int) -> int:
        i = bisect.bisect_left(self.batch_buckets, b)
        if i < len(self.batch_buckets):
            return self.batch_buckets[i]
        # beyond the largest configured bucket: next multiple of it, so
        # giant batches still land on a finite shape set
        top = self.batch_buckets[-1]
        return ((b + top - 1) // top) * top

    def _time_pad(self, t: int) -> int:
        tb = self.time_bucket
        return ((t + tb - 1) // tb) * tb

    def _pad_feed(self, feed: Dict[str, Any]):
        """Pad every feed entry to (batch bucket, time bucket); returns
        (padded_feed, true_batch, signature_key)."""
        true_b = None
        for v in feed.values():
            b = (v.data.shape[0] if isinstance(v, (SeqArray, NestedSeqArray))
                 else np.asarray(v).shape[0])
            if true_b is None:
                true_b = b
            elif b != true_b:
                raise ValueError(
                    f"InferenceEngine: mixed feed batch sizes {true_b} vs "
                    f"{b}; all feeds must share the batch dimension")
        if true_b is None:
            raise ValueError("InferenceEngine: empty feed")
        nb = self._batch_bucket(true_b)
        padded = {}
        key: List[tuple] = [("batch", nb)]
        pad_tokens = [0, 0]      # [true, padded] across SeqArray feeds
        for name in sorted(feed):
            v = feed[name]
            if isinstance(v, SeqArray):
                data = np.asarray(v.data)
                lengths = np.asarray(v.lengths, np.int32)
                t = self._time_pad(data.shape[1])
                pad_tokens[0] += int(np.minimum(lengths, t).sum())
                pad_tokens[1] += nb * t
                data = _pad_rows(_pad_time(data, t), nb)
                lengths = _pad_rows(lengths, nb)
                padded[name] = SeqArray(data, lengths)
                key.append((name, "seq", data.shape, str(data.dtype)))
            elif isinstance(v, NestedSeqArray):
                # batch-pad all three components in step (np.asarray on a
                # NestedSeqArray would silently DROP the outer/inner
                # lengths); the nested time extents stay as given
                data = _pad_rows(np.asarray(v.data), nb)
                outer = _pad_rows(np.asarray(v.outer_lengths, np.int32), nb)
                inner = _pad_rows(np.asarray(v.inner_lengths, np.int32), nb)
                padded[name] = NestedSeqArray(data, outer, inner)
                key.append((name, "nested", data.shape, str(data.dtype)))
            else:
                a = np.asarray(v)
                a = _pad_rows(a, nb)
                padded[name] = a
                key.append((name, a.shape, str(a.dtype)))
        return padded, true_b, tuple(key), pad_tokens

    def bucket_key(self, feed: Dict[str, Any]) -> tuple:
        """The bucket signature this feed lands on (host-side padding
        math only, no dispatch) — lets callers enumerate the distinct
        buckets of a traffic sample for targeted warmup."""
        _, _, key, _ = self._pad_feed(feed)
        return key

    # -- execution -----------------------------------------------------------
    def infer(self, feed: Dict[str, Any],
              fetch_list: Optional[Sequence] = None,
              return_numpy: bool = True) -> List[Any]:
        """Run one request batch through the bucketed executable; outputs
        are sliced back to the true batch size."""
        padded, true_b, key, pad_tokens = self._pad_feed(feed)
        warming = self._warming
        if not warming:
            if key in self._buckets:
                self._stats["bucket_hits"] += 1
            else:
                self._stats["bucket_misses"] += 1
            nb = key[0][1]
            self._padding["true_rows"] += true_b
            self._padding["padded_rows"] += nb
            self._padding["true_tokens"] += pad_tokens[0]
            self._padding["padded_tokens"] += pad_tokens[1]
        # warm-up registers the key (count 0) without counting a request:
        # sum(buckets.values()) == bucket_hits + bucket_misses always
        self._buckets[key] = self._buckets.get(key, 0) + (0 if warming
                                                          else 1)
        with fluid.scope_guard(self.scope):
            outs = self.exe.run(self.program, feed=padded,
                                fetch_list=fetch_list or self.fetch_list,
                                return_numpy=False, mode=self.mode)
        outs = [_slice_rows(o, true_b) for o in outs]
        if not return_numpy:
            return outs
        return [_rows_to_numpy(o) for o in outs]

    def warmup(self, sample_feeds: Sequence[Dict[str, Any]]) -> None:
        """Compile the buckets the given sample feeds land on (and upload
        the weights device-side via the first dispatch) so serving traffic
        starts at steady state.  Warm-up dispatches register their bucket
        keys but count as neither hits nor misses."""
        self._warming = True
        try:
            for feed in sample_feeds:
                self.infer(feed)
        finally:
            self._warming = False

    def place_weights(self) -> int:
        """Explicitly device_put every host-resident scope value; returns
        the number uploaded.  The first dispatch does this implicitly —
        call it from setup when you want the upload off the request
        path.  Restricted to THIS program's persistables — a scope
        shared with training may hold unrelated host values."""
        return fluid.io.device_put_persistables(self.scope, self.program)

    # -- static cost surface (ISSUE 11) --------------------------------------
    def static_hbm_estimate(self, batch: Optional[int] = None):
        """Static peak-HBM plan of the served program at ``batch``
        (default: the largest configured batch bucket — the worst
        signature this engine will ever dispatch).  The gateway
        registry and the scheduler budget with this number."""
        from ..fluid.analysis.cost import plan_program

        b = int(batch) if batch is not None else max(self.batch_buckets)
        return plan_program(self.program, assume_batch=b)

    def bucket_set(self, max_time: Optional[int] = None):
        """Enumerate the closed set of compile signatures this engine
        can dispatch — the recompile-hazard lint's enumeration (ISSUE
        11).
        Ragged (SeqArray) feeds need ``max_time`` to close the time
        axis: the time buckets are the multiples of ``time_bucket`` up
        to it."""
        from ..fluid.analysis.dataflow import ProgramView
        from ..fluid.analysis.recompile import enumerate_buckets

        time_buckets = ()
        if max_time is not None:
            # top bucket rounds UP, matching _time_pad: a request of
            # max_time tokens must land on an enumerated signature
            time_buckets = tuple(range(self.time_bucket,
                                       self._time_pad(int(max_time)) + 1,
                                       self.time_bucket))
        return enumerate_buckets(ProgramView(self.program.desc),
                                 batch_buckets=self.batch_buckets,
                                 time_buckets=time_buckets)

    def cache_stats(self) -> Dict[str, Any]:
        """{'bucket_hits', 'bucket_misses', 'buckets': {key: count},
        'padding': true-vs-padded row/token counters, 'executable':
        executor executable-cache counters}.  In steady state
        bucket_misses and the executable miss count both stop moving —
        the 0-recompile serving contract.  The padding block is the
        honest cost of that contract: every padded row/token is compute
        and HBM spent on data nobody asked for (what the paged cache
        eliminates on the decode path)."""
        out: Dict[str, Any] = dict(self._stats)
        out["buckets"] = dict(self._buckets)
        pad = dict(self._padding)
        pad["padded_row_fraction"] = round(
            1.0 - pad["true_rows"] / pad["padded_rows"], 4) \
            if pad["padded_rows"] else 0.0
        pad["padded_token_fraction"] = round(
            1.0 - pad["true_tokens"] / pad["padded_tokens"], 4) \
            if pad["padded_tokens"] else 0.0
        out["padding"] = pad
        out["quant"] = dict(self._quant_stats.to_dict(),
                            mode=self.quantize) \
            if self._quant_stats is not None else {"mode": self.quantize}
        out["executable"] = self.exe.cache_stats()["executable"]
        return out
