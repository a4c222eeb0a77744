"""Training guardrails — in-dispatch NaN/divergence sentinels, device-side
rollback-and-skip recovery, and a hung-step watchdog.

The reference defends a training step in three disconnected places: a
host-side post-hoc scan of every op output (CheckTensorNANOrInf,
paddle/framework/executor.cc:64,129), per-var error clipping appended by
backward (python/paddle/v2/fluid/clip.py ErrorClipByValue), and the
pserver's rule that a bad update must never be published.  This module
fuses that self-defense INTO the compiled step and gives it a recovery
policy:

* **Fused finiteness sentinel** — ``build_guarded_step_fn`` wraps the
  ordinary step function so ``jnp.isfinite`` all-reductions over the
  checked values (loss fetches, parameter gradients, post-update
  parameters) compile into the SAME XLA dispatch; the step returns a
  scalar health flag alongside the fetches.  No extra device
  round-trip, no host-side re-scan of every tensor (the reference pays
  a D2H transfer per op output when FLAGS_check_nan_inf is on).

* **Gated state publish** — on an unhealthy step the wrapped function
  selects the PRE-step state for every carried entry
  (``jnp.where(healthy, new, old)``), so a non-finite gradient can
  never corrupt parameters: ``skip`` leaves params byte-identical to
  the pre-step values.  On a healthy step the select is the identity,
  so guarded and unguarded steps are bitwise-identical.

* **Device-side rollback** — ``GuardPolicy(on_nonfinite="rollback")``
  keeps a "last good" copy of the state dict on device every
  ``snapshot_every`` guarded steps (``device_snapshot`` copies the
  buffers BEFORE they are donated to the dispatch — no disk, no host
  round-trip on TPU) and republishes it when a step goes bad.  After
  ``escalate_after`` consecutive bad steps the executor raises
  :class:`NonFiniteEscalation`; ``ResilientTrainer`` answers it with
  ``CheckpointManager.restore()``.

* **Step watchdog** — ``dispatch_guarded`` runs the dispatch on a
  worker thread while the calling thread monitors a wall-clock
  deadline (``step_timeout``); a wedged device surfaces as a
  structured :class:`StepTimeout` instead of hanging the trainer
  forever.  Transient faults (injected chaos, PJRT/XLA UNAVAILABLE /
  RESOURCE_EXHAUSTED / ABORTED-class errors, and timeouts themselves)
  are retried through the policy's ``resilience.retry.RetryPolicy``
  before a :class:`StepFault` surfaces.

Entry point: ``Executor.run(..., guard=GuardPolicy(...))`` — counters
in ``Executor.health_stats()``.

Caveats (documented limits, not bugs): the deadline covers the first
dispatch's XLA compile too, so set ``step_timeout`` above worst-case
compile time or warm the executable up first; a retry re-dispatches
with the same feeds/state/rng, and is only attempted when the donated
state buffers are verifiably intact — chaos faults and pre-device
stalls never claimed them, and a device-call failure releases its
claim when ``jax.Array.is_deleted`` confirms every donated input
survived (``state_buffers_live``), so PJRT preemptions/transport drops
that fail cleanly retry while a fault that consumed the buffers — or a
hang still running inside the device call (``StepTimeout`` with
``retry_safe=False``) — surfaces structured, with the rollback
snapshot republished into the scope; variable-length (SeqArray) state
entries pass through ungated.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from ..utils.sync import RANK_GUARD, OrderedLock
from .retry import RetryPolicy

__all__ = ["GuardPolicy", "NonFiniteError", "NonFiniteEscalation",
           "StepFault", "StepTimeout", "classify_step_error",
           "build_guarded_step_fn", "device_snapshot", "poison_feed",
           "dispatch_guarded"]

_ON_NONFINITE = ("raise", "skip", "rollback")
_CHECKS = ("loss", "grads", "params")


class NonFiniteError(FloatingPointError):
    """A guarded step produced NaN/Inf and the policy is ``raise``.
    The scope still holds the PRE-step state (the gated publish ran
    before this raised) — unlike the reference's CheckTensorNANOrInf,
    which leaves the corrupted tensors behind."""


class NonFiniteEscalation(RuntimeError):
    """``escalate_after`` consecutive non-finite steps under a
    skip/rollback policy: device-side recovery is not converging.
    ``ResilientTrainer`` answers this with ``CheckpointManager.restore``."""


class StepFault(RuntimeError):
    """A step dispatch failed with a non-recoverable (or retry-exhausted)
    runtime error; the original exception is chained as ``__cause__``."""


class StepTimeout(StepFault, TimeoutError):
    """The watchdog's wall-clock deadline expired before the dispatch
    (and its health-flag sync) completed.  Subclasses TimeoutError so
    stock ``RetryPolicy`` transient classes cover it.

    ``retry_safe`` records whether the timed-out attempt had reached
    the device: once the jitted call started, the donated state buffers
    belong to the (still running) hung dispatch and re-dispatching them
    would race it — such a timeout classifies NON-transient and
    surfaces immediately.  A timeout before the device call (an
    injected chaos hang, a stall in host-side staging) is safely
    retryable."""

    def __init__(self, msg: str, retry_safe: bool = True):
        super().__init__(msg)
        self.retry_safe = retry_safe


class _DispatchControl:
    """Shared state between the watchdog (monitor thread) and one
    dispatch attempt (worker thread): ``cancelled`` is set when the
    deadline fires so an abandoned attempt must NOT proceed to consume
    the donated buffers a retry may be re-using; ``consumed`` is set by
    the attempt just before the device call, deciding StepTimeout's
    ``retry_safe``.  Both transitions go through one lock —
    ``begin_consume``/``cancel`` are atomic, so the monitor can never
    read consumed=False while the worker slips past the cancellation
    check into the device call."""

    __slots__ = ("cancelled", "consumed", "_lock")

    def __init__(self):
        self.cancelled = threading.Event()
        self.consumed = False
        self._lock = OrderedLock("guardrails.dispatch", RANK_GUARD)

    def begin_consume(self) -> bool:
        """Worker side: claim the donated buffers for the device call.
        Returns False when the watchdog already abandoned this attempt
        (the worker must not touch the device)."""
        with self._lock:
            if self.cancelled.is_set():
                return False
            self.consumed = True
            return True

    def unconsume(self) -> None:
        """Worker side: the device call failed but the donated inputs
        are verifiably still live (``state_buffers_live``) — release
        the claim so the failure stays retryable.  No-op once the
        watchdog cancelled (the monitor already read the flag)."""
        with self._lock:
            if not self.cancelled.is_set():
                self.consumed = False

    def cancel(self) -> bool:
        """Monitor side: abandon the attempt; returns True when the
        attempt never claimed the buffers (safe to retry)."""
        with self._lock:
            self.cancelled.set()
            return not self.consumed


class GuardPolicy:
    """Recovery policy for guarded execution.

    Parameters
    ----------
    on_nonfinite: ``"raise"`` (surface :class:`NonFiniteError`; state
        stays pre-step), ``"skip"`` (drop the update — params
        byte-identical to pre-step) or ``"rollback"`` (republish the
        device-side last-good snapshot, DELIBERATELY rewinding up to
        ``snapshot_every - 1`` healthy steps: rollback distrusts the
        recent trajectory — loss-scale blowups and optimizer-state
        poisoning precede the first non-finite value — where ``skip``
        trusts everything up to the bad batch).
    check: which value classes feed the fused sentinel — any subset of
        ``("loss", "grads", "params")``.  ``loss`` = the float fetches,
        ``grads`` = every parameter's ``@GRAD``, ``params`` = the
        post-update parameters.
    snapshot_every: rollback snapshot cadence in guarded steps (K).
    escalate_after: consecutive bad steps before
        :class:`NonFiniteEscalation` (M; 0 = never escalate).
    step_timeout: wall-clock seconds per dispatch before the watchdog
        fires ``StepTimeout`` (None or <= 0 = no watchdog; 0 is
        accepted as the conventional "off" so a config plumbing a
        numeric field through never arms an instant-fire deadline).
    retry: a ``RetryPolicy`` whose schedule/bounds govern re-dispatch
        of transient faults (classification is this module's
        ``classify_step_error``, not the policy's ``retryable`` set);
        None = no retries, transients surface structured.
    """

    def __init__(self, on_nonfinite: str = "raise",
                 check: Sequence[str] = _CHECKS,
                 snapshot_every: int = 10, escalate_after: int = 0,
                 step_timeout: Optional[float] = None,
                 retry: Optional[RetryPolicy] = None):
        if on_nonfinite not in _ON_NONFINITE:
            raise ValueError(f"on_nonfinite must be one of {_ON_NONFINITE}, "
                             f"got {on_nonfinite!r}")
        check = tuple(check)
        bad = [c for c in check if c not in _CHECKS]
        if bad or not check:
            raise ValueError(f"check must be a non-empty subset of "
                             f"{_CHECKS}, got {check!r}")
        self.on_nonfinite = on_nonfinite
        self.check = check
        self.snapshot_every = max(1, int(snapshot_every))
        self.escalate_after = max(0, int(escalate_after))
        if step_timeout is not None:
            step_timeout = float(step_timeout)
            if step_timeout <= 0:
                step_timeout = None
        self.step_timeout = step_timeout
        self.retry = retry
        # the guard-classified twin of `retry` is pure config — derive
        # it once, not per dispatch in the hot loop
        self._retry_effective = (None if retry is None
                                 else _effective_retry(retry))

    def __repr__(self):
        return (f"GuardPolicy(on_nonfinite={self.on_nonfinite!r}, "
                f"check={self.check}, snapshot_every={self.snapshot_every}, "
                f"escalate_after={self.escalate_after}, "
                f"step_timeout={self.step_timeout})")


# -- fused sentinel ----------------------------------------------------------

def _float_data(v):
    """The float array behind a value, or None for ints/bools (finiteness
    is vacuous there — matches CheckTensorNANOrInf only scanning floats)."""
    import jax.numpy as jnp

    from ..fluid.core.lod import SeqArray

    data = v.data if isinstance(v, SeqArray) else v
    if hasattr(data, "dtype") and jnp.issubdtype(data.dtype, jnp.floating):
        return data
    return None


def build_guarded_step_fn(desc, block_idx: int, feed_names: Sequence[str],
                          state_in: Sequence[str], state_out: Sequence[str],
                          fetch_names: Sequence[str], mode: str,
                          check_names: Sequence[str]):
    """The guarded variant of ``lowering.build_step_fn``:

        (feeds, state, rng_bits) -> (fetches, new_state, healthy)

    ``healthy`` is a scalar bool — the AND of ``jnp.isfinite(x).all()``
    over every float value named in ``check_names`` — computed inside
    the same traced function, so the sentinel compiles into the same
    XLA dispatch as the step itself.  Every carried state entry is
    published through ``jnp.where(healthy, new, old)``: a healthy step
    is bitwise-identical to the unguarded step (select-on-true is the
    identity), an unhealthy one leaves the scope exactly pre-step.
    """
    import jax.numpy as jnp

    from ..fluid.core.lod import SeqArray
    from ..fluid.lowering import build_step_fn

    fetch_names = tuple(fetch_names)
    check_names = tuple(check_names)
    # the sentinel reads checked values off the traced env by fetching
    # them through the base step — grads and post-update params are env
    # entries like any other, so no second lowering path is needed
    all_fetch = tuple(dict.fromkeys(fetch_names + check_names))
    idx = {n: i for i, n in enumerate(all_fetch)}
    base = build_step_fn(desc, block_idx, feed_names, state_in, state_out,
                         all_fetch, mode)

    def step(feeds: Dict[str, Any], state: Dict[str, Any], rng_bits):
        outs, new_state = base(feeds, state, rng_bits)
        healthy = jnp.bool_(True)
        for n in check_names:
            data = _float_data(outs[idx[n]])
            if data is not None:
                healthy = jnp.logical_and(healthy,
                                          jnp.all(jnp.isfinite(data)))
        gated = {}
        for n, v in new_state.items():
            old = state.get(n)
            if (old is None or isinstance(v, SeqArray)
                    or isinstance(old, SeqArray)):
                gated[n] = v            # no pre-step twin to select from
            else:
                gated[n] = jnp.where(healthy, v, old)
        return [outs[idx[n]] for n in fetch_names], gated, healthy

    step.__name__ = f"{mode}_step"      # as build_step_fn names its own
    step.noted = base.noted
    return step


# -- device-side snapshots ---------------------------------------------------

def device_snapshot(state: Dict[str, Any]) -> Dict[str, Any]:
    """Copy every state value into fresh buffers (device-resident for
    jax arrays — no disk, no host round-trip).  The copies are never
    passed to a dispatch, so buffer donation can't consume them; that
    is what makes the snapshot restorable after any number of donated
    steps."""
    import jax.numpy as jnp

    from ..fluid.core.lod import SeqArray

    def copy_one(v):
        if isinstance(v, SeqArray):
            return SeqArray(copy_one(v.data), np.asarray(v.lengths).copy())
        if hasattr(v, "dtype"):
            return jnp.array(v, copy=True)
        return v
    return {n: copy_one(v) for n, v in state.items()}


def state_buffers_live(state: Dict[str, Any]) -> bool:
    """True when none of the (donation-candidate) state arrays has
    actually been consumed — ``jax.Array.is_deleted`` is ground truth
    for whether a failed dispatch took the buffers with it.  On CPU
    donation is a no-op (never deleted -> always live); on TPU a fault
    mid-execution deletes the donated inputs and this returns False.
    Host values without the probe (numpy) count live."""
    from ..fluid.core.lod import SeqArray

    for v in state.values():
        for d in ((v.data, v.lengths) if isinstance(v, SeqArray) else (v,)):
            probe = getattr(d, "is_deleted", None)
            if probe is not None and probe():
                return False
    return True


# -- chaos poisoning ---------------------------------------------------------

def poison_feed(feed: Dict[str, Any], inj) -> Dict[str, Any]:
    """Apply the ``guard.nan`` / ``guard.inf_grad`` injection points:
    when one fires, the first element of the first float feed (sorted
    by name, for a deterministic target) is replaced by NaN/Inf — the
    seeded stand-in for a corrupt batch or an exploding gradient.
    Returns a new feed dict; the caller's arrays are never mutated."""
    from ..fluid.core.lod import SeqArray

    for point, bad in (("guard.nan", np.nan), ("guard.inf_grad", np.inf)):
        if not inj.should(point):
            continue
        for name in sorted(feed):
            v = feed[name]
            data = v.data if isinstance(v, SeqArray) else v
            arr = np.asarray(data)
            if not np.issubdtype(arr.dtype, np.floating) or arr.size == 0:
                continue
            arr = arr.copy()
            arr.flat[0] = bad
            feed = dict(feed)
            feed[name] = (SeqArray(arr, v.lengths)
                          if isinstance(v, SeqArray) else arr)
            break
    return feed


# -- watchdog + transient retry ----------------------------------------------

_TRANSIENT_MARKERS = ("UNAVAILABLE", "RESOURCE_EXHAUSTED", "ABORTED",
                      "DEADLINE_EXCEEDED", "CANCELLED", "INTERNAL: Failed to "
                      "connect")
# attribute stamped on an exception raised AFTER the attempt claimed the
# donated buffers: retrying would hand the same (now consumed) arrays to
# a second dispatch, so even transient-shaped errors classify fatal
_CONSUMED_ATTR = "_guardrail_buffers_consumed"


def _transient_shaped(exc: BaseException) -> bool:
    """The error CLASS looks transient (ignoring buffer consumption)."""
    if isinstance(exc, StepTimeout):
        return exc.retry_safe
    if isinstance(exc, (ConnectionError, TimeoutError)):
        return True
    if type(exc).__name__ == "XlaRuntimeError":
        msg = str(exc)
        return any(m in msg for m in _TRANSIENT_MARKERS)
    return False


def classify_step_error(exc: BaseException) -> bool:
    """True when a dispatch failure is worth re-dispatching: injected
    chaos (ChaosError is a ConnectionError), watchdog timeouts whose
    attempt never reached the device (``retry_safe``), plain transport
    errors, and PJRT/XLA runtime errors whose status text carries a
    transient absl status class.  Shape/compile/user errors — and ANY
    error raised after the attempt consumed the donated state buffers —
    classify fatal."""
    if getattr(exc, _CONSUMED_ATTR, False):
        return False
    return _transient_shaped(exc)


def _effective_retry(retry: RetryPolicy) -> RetryPolicy:
    """The caller's policy owns the schedule and bounds; the guard owns
    transiency classification (``classify_step_error`` covers PJRT/XLA
    errors no exception-class list can name)."""
    return RetryPolicy(max_attempts=retry.max_attempts,
                       deadline=retry.deadline,
                       base_delay=retry.base_delay,
                       max_delay=retry.max_delay,
                       retryable=(Exception,),
                       retry_if=classify_step_error,
                       seed=retry._seed, sleep=retry._sleep,
                       clock=retry._clock)


def _run_with_deadline(thunk, deadline: Optional[float], stats: Dict[str, int]):
    """Run ``thunk(ctl)`` under a wall-clock deadline: the dispatch
    executes on a worker thread while this (monitor) thread waits.  On
    expiry the attempt is cancelled (so an abandoned pre-device stall
    cannot later consume the donated buffers a retry re-uses) and a
    :class:`StepTimeout` surfaces immediately — a wedged PJRT call
    itself cannot be interrupted from Python; surfacing the hang is the
    watchdog's whole job."""
    ctl = _DispatchControl()

    def call():
        try:
            return thunk(ctl)
        except StepFault:
            raise
        except Exception as e:
            if ctl.consumed:
                # raised from inside (or after) the device call: the
                # donated buffers are gone — poison any retry decision
                setattr(e, _CONSUMED_ATTR, True)
            raise

    if deadline is None:
        return call()
    box: Dict[str, Any] = {}
    done = threading.Event()

    def work():
        try:
            box["value"] = call()
        except BaseException as e:      # noqa: B036 — relayed to caller
            box["error"] = e
        finally:
            done.set()

    worker = threading.Thread(target=work, daemon=True,
                              name="guardrail-dispatch")
    worker.start()
    if not done.wait(deadline):
        retry_safe = ctl.cancel()       # atomic with begin_consume
        stats["watchdog_fires"] += 1
        raise StepTimeout(
            f"step dispatch exceeded the {deadline:.3f}s watchdog deadline "
            f"(device hung, or the executable is still compiling — warm up "
            f"or raise GuardPolicy.step_timeout)",
            retry_safe=retry_safe)
    if "error" in box:
        raise box["error"]
    return box["value"]


def dispatch_guarded(thunk, policy: GuardPolicy,
                     stats: Dict[str, int]) -> Tuple:
    """Run one step dispatch under the policy's watchdog deadline,
    retrying transient faults through its RetryPolicy.  ``thunk`` is
    called as ``thunk(ctl)`` with a fresh :class:`_DispatchControl` per
    attempt — it must honor ``ctl.cancelled`` (abort without touching
    the device) and set ``ctl.consumed`` just before the jitted call.
    Counts ``watchdog_fires`` and ``retries`` into ``stats``; surfaces
    :class:`StepTimeout` / :class:`StepFault` when recovery runs out."""
    attempts = {"n": 0}

    def attempt():
        attempts["n"] += 1
        return _run_with_deadline(thunk, policy.step_timeout, stats)

    try:
        if policy._retry_effective is not None:
            return policy._retry_effective.call(attempt)
        return attempt()
    except (StepFault, NonFiniteError, NonFiniteEscalation):
        raise
    except Exception as exc:
        # structure (a) transient-shaped faults that ran out of retries
        # and (b) ANY error raised after the buffers were consumed — the
        # executor's StepFault handler republishes the rollback snapshot
        # precisely because such a scope may hold consumed arrays
        if _transient_shaped(exc) or getattr(exc, _CONSUMED_ATTR, False):
            raise StepFault(
                f"step fault not recovered "
                f"({type(exc).__name__}: {exc})") from exc
        raise
    finally:
        stats["retries"] += max(0, attempts["n"] - 1)
