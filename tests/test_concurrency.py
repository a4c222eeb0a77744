"""Concurrency sanitizer tests (ISSUE 13): the ordered-lock runtime
checker (rank inversions and lock-order cycles detected at acquire time
with both acquisition sites), the ``syncheck`` static lint (raw locks,
blocking I/O under locks, predicate-free condition waits), the
``paddle_sync_*`` accounting + blocked-thread statusz dump, and the
seeded-schedule race harness: scheduler + gateway + journals + release
controller driven through deterministic ``sync.preempt`` perturbation
schedules asserting zero lost/duplicated requests, clean journal
replay, exact metric counts, and ``PageAllocator.check_invariants``.

Regression notes for the syncheck satellite sweep over paddle_tpu/
(every real finding the lint surfaced, each fixed in this PR):

* ``resilience/chaos.py`` ``FaultInjector._log`` wrote (open + write)
  the chaos journal INSIDE its draw lock — every injection point in
  every thread serialized behind the disk.  Fixed: the lock now covers
  only the draw index; appends are lock-free single-line O_APPEND
  writes (``test_chaos_log_concurrent_lines_intact``).
* ``native/__init__.py`` ``_load`` ran the g++ subprocess + dlopen
  under the publish lock — the first analyzer call held every other
  one (even already-answered lookups) behind a multi-second compile.
  Fixed: the build serializes under a dedicated ``native.build`` lock
  (two concurrent ``make`` runs writing the .so in place could publish
  a corrupt artifact); the publish lock is held only for the
  flag/pointer swap.
* ``lifecycle/controller.py`` verdict polling audit: the probe waits
  and ``run()``'s ``time.sleep`` hold NO lock (confirmed clean), but
  ``status()`` — called from ObservabilityServer HTTP threads —
  iterated ``state.bad``/``state.directives`` while ``step()`` mutated
  them.  Fixed: ``lifecycle.controller`` lock around state commits +
  a locked snapshot in ``status()``
  (``test_controller_status_concurrent_with_step``).
* ``observability/tracing.py`` export audit: ``events()`` snapshots
  under the tracer lock and ``export()`` serializes OUTSIDE it —
  already clean; the lint run documents it stays that way.
* ``fluid/pipeline_io.py`` ``DataLoader.__iter__`` one-shot check was
  check-then-act: two concurrent iterators could both pass and
  silently split the epoch.  Fixed with the ``pipeline.loader`` lock
  (``test_dataloader_one_shot_single_owner``).

Two production bugs found BY the seeded harness itself (both fixed in
this PR, both previously unreachable by the deterministic suites):

* ``serving/scheduler.py``: a request whose ``admit_slot`` dispatch
  was in flight — outside the scheduler lock — when ``remove_model``
  tore its lane group down was silently orphaned (activated into a
  group the step loop no longer iterates; never stepped, never
  failed).  Deterministic regression:
  ``test_admission_racing_remove_model_requeues_zero_lost``.
* ``serving/gateway/gateway.py`` ``submit``: resolve→instance TOCTOU
  against a concurrent hot swap — the alias flipped and the old
  version unloaded between the two calls, so a client submitting
  against a model that IS being served got a spurious unknown-model
  error mid-swap.  Fixed with a single re-resolve; the seeded
  gateway sweeps (submit threads racing ``swap_model``) cover it.
"""

import json
import os
import re
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability.server import resolve_source
from paddle_tpu.resilience.chaos import FaultInjector, install
from paddle_tpu.serving import PagedTransformerGenerator, copy_weights
from paddle_tpu.serving.gateway import Gateway
from paddle_tpu.serving.gateway.journal import RequestJournal
from paddle_tpu.serving.scheduler import RequestCancelled
from paddle_tpu.lifecycle import ReleaseConfig, ReleaseController
from paddle_tpu.lifecycle.journal import ReleaseJournal
from paddle_tpu.tools import syncheck
from paddle_tpu.utils import sync
from paddle_tpu.utils.sync import (DeadlockCycleError, LockOrderError,
                                   OrderedCondition, OrderedLock,
                                   OrderedRLock)

_SITE = re.compile(r"test_concurrency\.py:\d+")


@pytest.fixture
def checking():
    """Fresh registry + checking ON for the test, OFF after — so the
    rest of the suite keeps the zero-overhead passthrough."""
    sync.registry().reset()
    sync.enable_checking()
    yield sync.registry()
    sync.disable_checking()
    sync.registry().reset()


@pytest.fixture(autouse=True)
def _inert_injector():
    prev = install(FaultInjector())
    yield
    install(prev)
    sync.disable_preemption()


class EchoModel:
    """Deterministic slot model: every lane repeats its prompt's first
    token — cross-lane contamination is immediately visible."""

    start_id, end_id = 0, 1
    src_len = 64

    def __init__(self):
        self.n = 0
        self.slot_val = {}

    def open_slots(self, n):
        self.n = n

    def admit_slot(self, slot, prompt, **_):
        self.slot_val[slot] = int(np.asarray(prompt).reshape(-1)[0])
        return len(np.asarray(prompt).reshape(-1))

    def clear_slot(self, slot):
        self.slot_val.pop(slot, None)

    def step_slots(self, tokens, pos, src_len):
        return np.array([self.slot_val.get(i, 7777)
                         for i in range(self.n)], np.int64)


# -- runtime checker: detection -----------------------------------------------

def test_rank_inversion_detected_with_both_sites(checking):
    lo = OrderedLock("t13.lo", 10)
    hi = OrderedLock("t13.hi", 20)
    with hi:                                   # site A
        with pytest.raises(LockOrderError) as ei:
            lo.acquire()                       # site B: rank 10 < 20
    msg = str(ei.value)
    assert "t13.lo" in msg and "t13.hi" in msg
    assert "rank inversion" in msg
    # BOTH acquisition sites (where hi was taken, where lo is being
    # taken) are reported as file:line
    assert len(_SITE.findall(msg)) >= 2, msg
    # the held lock is still usable; ascending order stays legal
    with lo:
        with hi:
            pass


def test_two_lock_cycle_detected_with_both_sites(checking):
    a = OrderedLock("t13.a", 30)
    b = OrderedLock("t13.b", 30)               # equal rank: legal nest
    with a:
        with b:                                # records edge a -> b
            pass
    with b:
        with pytest.raises(DeadlockCycleError) as ei:
            a.acquire()                        # b -> a closes the cycle
    msg = str(ei.value)
    assert "t13.b" in msg and "t13.a" in msg and "cycle" in msg
    # both acquisition sites: this thread's (holding b, acquiring a)
    # AND the first-recorded reverse edge's sites
    assert len(_SITE.findall(msg)) >= 2, msg
    assert checking.violations >= 1


def test_same_name_nesting_is_a_cycle(checking):
    s1 = OrderedLock("t13.same", 33)
    s2 = OrderedLock("t13.same", 33)
    with s1:
        with pytest.raises(DeadlockCycleError):
            s2.acquire()


def test_self_deadlock_on_nonreentrant_lock(checking):
    lk = OrderedLock("t13.self", 35)
    with lk:
        with pytest.raises(LockOrderError, match="self-deadlock"):
            lk.acquire()


def test_rlock_reentry_and_equal_rank_ok(checking):
    r = OrderedRLock("t13.re", 40)
    other = OrderedLock("t13.other", 40)
    with r:
        assert r.locked(), "owner must see its own RLock as held"
        with r:                                # re-entry: no edge
            with other:                        # equal rank, no cycle
                pass
    assert not r.locked()
    assert checking.violations == 0


def test_condition_wait_bookkeeping_and_wait_for(checking):
    cv = OrderedCondition(name="t13.cv", rank=50)
    box = []

    def producer():
        time.sleep(0.02)
        with cv:
            box.append(1)
            cv.notify_all()

    t = threading.Thread(target=producer)
    t.start()
    with cv:
        assert cv.wait_for(lambda: box, timeout=5)
    t.join(5)
    st = checking.status()
    assert st["locks"]["t13.cv"]["acquires"] >= 2
    # nothing left held or blocked after the dance
    assert not st["blocked"]


def test_blocked_thread_stack_dump(checking):
    lk = OrderedLock("t13.blocked", 45)
    lk.acquire()
    started = threading.Event()

    def contender():
        started.set()
        with lk:
            pass

    t = threading.Thread(target=contender, name="t13-contender")
    t.start()
    started.wait(5)
    try:
        deadline = time.time() + 5
        blocked = []
        while time.time() < deadline:
            blocked = checking.status()["blocked"]
            if blocked:
                break
            time.sleep(0.005)
        assert blocked, "contender never showed in the blocked dump"
        entry = blocked[0]
        assert entry["blocked_on"].startswith("t13.blocked")
        assert "contender" in "".join(entry.get("stack", [])), \
            "stack dump must show the blocked frame"
    finally:
        lk.release()
        t.join(5)
    # statusz duck-typing: SyncRegistry attaches via its status() method
    assert resolve_source(sync.registry())()["checking"] is True


def test_sync_metrics_series_exported(checking):
    lk = OrderedLock("t13.metrics", 47)
    for _ in range(5):
        with lk:
            pass
    text = obs_metrics.registry().render_prometheus()
    assert 'paddle_sync_acquires_total{lock="t13.metrics"} 5' in text
    assert "paddle_sync_hold_seconds_total" in text
    assert "paddle_sync_contended_total" in text
    assert "paddle_sync_order_violations_total" in text


def test_toggle_checking_midstream_drops_stale_held_entries():
    """REGRESSION (review): disabling checking while a lock is held —
    its release then goes through the passthrough — must not leave a
    stale held entry that makes a later re-enable raise a spurious
    self-deadlock on the next acquire."""
    sync.registry().reset()
    sync.enable_checking()
    lk = OrderedLock("t13.toggle", 37)
    lk.acquire()
    sync.disable_checking()          # drops held bookkeeping
    lk.release()                     # passthrough release
    sync.enable_checking()
    try:
        with lk:                     # must not raise LockOrderError
            pass
    finally:
        sync.disable_checking()
        sync.registry().reset()


def test_passthrough_records_nothing_when_disabled():
    sync.registry().reset()
    lk = OrderedLock("t13.off", 49)
    with lk:
        pass
    assert sync.registry().status()["locks"] == {}


def test_real_stack_clean_under_checking(checking, tmp_path):
    """Drive the real scheduler + gateway + journal with checking ON:
    the repo rank table must hold (no inversions, no cycles), and the
    observed lock-order graph must contain the canonical nestings."""
    gw = Gateway(n_slots=2, max_new_tokens=4,
                 journal_path=str(tmp_path / "rj.jsonl"))
    gw.load_model("m", "1", instance=EchoModel())
    gw.serve()
    try:
        reqs = [gw.submit("m", [50 + i]) for i in range(6)]
        for r in reqs:
            assert r.wait(30)
    finally:
        gw.shutdown(drain=True)
    assert gw.journal.pending() == []
    assert checking.violations == 0
    g = checking.graph()
    edges = {(e["from"], e["to"]) for e in g["edges"]}
    # the canonical nestings the migration preserves
    assert ("serving.scheduler", "metrics.child") in edges
    assert ("serving.scheduler", "gateway.registry") in edges
    # completion (the journal's done record) is the delivery thread's
    # since PR 36: it no longer nests in the scheduler's state lock
    assert ("serving.scheduler", "gateway.journal.cv") not in edges
    out = tmp_path / "graph.json"
    checking.export_graph(str(out))
    assert json.loads(out.read_text())["edges"]


# -- the static lint ----------------------------------------------------------

_FIXTURE = textwrap.dedent("""\
    import os
    import threading
    import time

    RAW = threading.Lock()

    class Bad:
        def __init__(self):
            self._lock = threading.Lock()

        def write_under_lock(self, f):
            with self._lock:
                time.sleep(0.1)
                os.fsync(f.fileno())

        def bare_wait(self, flag):
            with self._cv:
                if not flag:
                    self._cv.wait()
    """)


def test_syncheck_fixture_findings(tmp_path):
    p = tmp_path / "fixture.py"
    p.write_text(_FIXTURE)
    findings = syncheck.check_file(str(p))
    codes = sorted(f.code for f in findings)
    assert codes.count("raw-lock") == 2
    assert codes.count("io-under-lock") == 2      # sleep + fsync
    assert codes.count("wait-no-loop") == 1
    assert syncheck.main([str(p), "--quiet"]) == 1


def test_syncheck_cli_exit_codes(tmp_path):
    """Acceptance: exit 1 on the raw-lock + fsync-under-lock fixture,
    exit 0 over the real paddle_tpu tree (after the satellite fixes)."""
    p = tmp_path / "fixture.py"
    p.write_text(_FIXTURE)
    bad = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tools.syncheck", str(p)],
        capture_output=True, text=True)
    assert bad.returncode == 1
    assert "raw-lock" in bad.stdout and "io-under-lock" in bad.stdout
    import paddle_tpu

    pkg = os.path.dirname(os.path.abspath(paddle_tpu.__file__))
    assert syncheck.main([pkg, "--quiet"]) == 0, \
        "the real tree must be syncheck-clean"


def test_syncheck_suppression_and_nested_def(tmp_path):
    src = textwrap.dedent("""\
        import os, time

        class Ok:
            def sanctioned(self, f):
                with self._lock:  # syncheck: ok
                    os.fsync(f.fileno())

            def nested(self):
                with self._lock:
                    def helper():
                        time.sleep(1)   # not run under the lock
                    return helper

            def looped_wait(self, pred):
                with self._cv:
                    while not pred():
                        self._cv.wait()
        """)
    p = tmp_path / "clean.py"
    p.write_text(src)
    assert syncheck.check_file(str(p)) == []


def test_syncheck_json_output(tmp_path):
    p = tmp_path / "fixture.py"
    p.write_text(_FIXTURE)
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tools.syncheck", str(p),
         "--json"],
        capture_output=True, text=True)
    findings = json.loads(out.stdout)
    assert out.returncode == 1
    assert {f["code"] for f in findings} == {
        "raw-lock", "io-under-lock", "wait-no-loop"}


# -- sync.preempt determinism -------------------------------------------------

def test_preempt_schedule_is_seeded():
    a = FaultInjector(spec="sync.preempt=0.4", seed=11)
    b = FaultInjector(spec="sync.preempt=0.4", seed=11)
    c = FaultInjector(spec="sync.preempt=0.4", seed=12)
    fa = [a.maybe_preempt(max_sleep=0.0) for _ in range(64)]
    fb = [b.maybe_preempt(max_sleep=0.0) for _ in range(64)]
    fc = [c.maybe_preempt(max_sleep=0.0) for _ in range(64)]
    assert fa == fb, "same seed => same perturbation schedule"
    assert fa != fc, "different seed => different schedule"
    assert any(fa) and not all(fa)


def test_preempt_off_point_consumes_nothing():
    inj = FaultInjector(spec="master.http=0.5", seed=3)
    assert not inj.maybe_preempt()
    # the should() draw sequence is unperturbed by preempt probes
    assert [inj.should("master.http") for _ in range(4)] == \
        [FaultInjector.decision(3, "master.http", i) < 0.5
         for i in range(4)]


# -- satellite regression: chaos log off the draw lock ------------------------

def test_chaos_log_concurrent_lines_intact(tmp_path):
    log = tmp_path / "chaos.journal"
    inj = FaultInjector(spec="master.http=0.5", seed=9,
                        log_path=str(log))

    def hammer():
        for _ in range(50):
            inj.should("master.http")

    ts = [threading.Thread(target=hammer) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    lines = log.read_text().splitlines()
    assert len(lines) == 200
    pat = re.compile(r"^master\.http \d+ 0\.\d{9} [01]$")
    assert all(pat.match(ln) for ln in lines), \
        "concurrent appends interleaved mid-line"


# -- satellite regression: DataLoader one-shot race ---------------------------

def test_dataloader_one_shot_single_owner():
    from paddle_tpu.fluid.pipeline_io import DataLoader

    n = 40
    loader = DataLoader(iter([{"x": np.zeros(1)} for _ in range(n)]),
                        device_prefetch=False)
    barrier = threading.Barrier(2)
    results = [None, None]

    def consume(i):
        barrier.wait()
        try:
            results[i] = len(list(loader))
        except RuntimeError:
            results[i] = "exhausted"

    ts = [threading.Thread(target=consume, args=(i,)) for i in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    # exactly ONE thread owns the epoch; the other fails loudly —
    # never a silent split
    assert sorted(results, key=str) == [n, "exhausted"]


# -- journal ordering under seeded interleaving (satellite) -------------------

def _journal_indices(path):
    sub, done = {}, {}
    with open(path) as f:
        for i, line in enumerate(f):
            e = json.loads(line)
            (sub if e["op"] == "submit" else done)[e["jid"]] = i
    return sub, done


@pytest.mark.parametrize("seed", [1, 7])
def test_request_journal_done_never_precedes_submit(tmp_path, seed):
    """The async background writer must never reorder a ``done`` ahead
    of its ``submit`` in the file — asserted under seeded preemption at
    every lock boundary (ISSUE 13 satellite)."""
    inj = FaultInjector(spec="sync.preempt=0.3", seed=seed)
    sync.enable_preemption(inj)
    j = RequestJournal(str(tmp_path / "rq.jsonl"))

    def writer(base):
        for k in range(20):
            jid = j.new_jid()
            j.record_submit(jid, f"t{base}", "m", [base + k], 4)
            j.record_done(jid, ok=True)

    ts = [threading.Thread(target=writer, args=(100 * i,))
          for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(20)
    assert j.flush(10)
    sub, done = _journal_indices(j.path)
    assert set(sub) == set(done) and len(sub) == 60
    for jid, si in sub.items():
        assert si < done[jid], \
            f"done for {jid} reordered ahead of its submit"
    assert j.pending() == []


def test_release_journal_concurrent_appends_parse(tmp_path):
    inj = FaultInjector(spec="sync.preempt=0.3", seed=4)
    sync.enable_preemption(inj)
    j = ReleaseJournal(str(tmp_path / "rel.jsonl"), fsync=False)

    def writer(tag):
        for k in range(25):
            j.append("candidate", version=f"{tag}-{k}")

    ts = [threading.Thread(target=writer, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(20)
    entries = j.replay()
    assert len(entries) == 75, "an append was lost or merged"
    assert [e["_seq"] for e in entries] == sorted(
        e["_seq"] for e in entries)
    assert {e["version"] for e in entries} == {
        f"{i}-{k}" for i in range(3) for k in range(25)}


# -- the seeded-schedule race harness -----------------------------------------

def _event_delta(before, name="paddle_serving_requests_total"):
    after = _event_counts(name)
    return {k: after.get(k, 0.0) - before.get(k, 0.0)
            for k in set(after) | set(before)}


def _event_counts(name="paddle_serving_requests_total"):
    fam = obs_metrics.registry().get(name)
    out = {}
    if fam is None:
        return out
    for vals, child in fam.children():
        labels = dict(zip(fam.label_names, vals))
        ev = labels.get("event", "?")
        out[ev] = out.get(ev, 0.0) + child.value
    return out


def _drive_gateway_schedule(seed, tmp_path, model_factory=EchoModel,
                            n_per_tenant=6, n_slots=3, max_new=5,
                            check_invariants=False, cancel_some=True,
                            swap=True):
    """One seeded schedule: 3 client threads × n_per_tenant requests
    through a live gateway, a hot swap mid-traffic, a couple of
    cancellations — all with ``sync.preempt`` perturbing every lock
    boundary.  Asserts the ISSUE 13 contract: zero lost/duplicated
    requests, clean journal replay, exact metric counts."""
    inj = FaultInjector(spec="sync.preempt=0.25", seed=seed)
    prev = install(inj)
    sync.enable_preemption(inj)
    before = _event_counts()
    try:
        gw = Gateway(n_slots=n_slots, max_new_tokens=max_new,
                     journal_path=str(tmp_path / f"rq-{seed}.jsonl"),
                     check_invariants=check_invariants)
        gw.load_model("m", "1", instance=model_factory())
        gw.serve()
        reqs, rlock = [], threading.Lock()

        def client(tenant, base):
            for k in range(n_per_tenant):
                r = gw.submit("m", [base + k], tenant=tenant)
                with rlock:
                    reqs.append(r)
                if cancel_some and k == 2 and tenant == "t1":
                    r.cancel()

        ts = [threading.Thread(target=client,
                               args=(f"t{i}", 100 * (i + 1)))
              for i in range(3)]
        for t in ts:
            t.start()
        if swap:
            gw.swap_model("m", "2", instance=model_factory())
        for t in ts:
            t.join(60)
        for r in reqs:
            if not r.wait(60):
                import faulthandler

                st = gw.sched.stats()
                faulthandler.dump_traceback()
                raise AssertionError(
                    f"request rid={r.rid} model={r.model} "
                    f"group={r.group} slot={r.slot} "
                    f"cancelled={r.cancelled} never finished; "
                    f"sched={{steps: {st['steps']}, queued: "
                    f"{st['queued']}, in_flight: {st['in_flight']}}} "
                    f"models={st.get('models')} queued_rids="
                    f"{[q.rid for q in gw.sched.queued_requests()]} "
                    f"active={[(q.rid, q.group) for q in gw.sched.active_requests()]}")
        leftovers = gw.shutdown(drain=True)
        assert leftovers == []
        n = len(reqs)
        assert n == 3 * n_per_tenant
        cancelled = 0
        for r in reqs:
            if r.error is None:
                # no lost tokens, no duplicates, no cross-lane bleed
                assert r.tokens == [int(r.src[0])] * max_new, \
                    f"rid {r.rid}: {r.tokens} != echo of {r.src[0]}"
            else:
                assert isinstance(r.error, RequestCancelled), r.error
                cancelled += 1
        # clean journal replay: every submit has its done record
        assert gw.journal.pending() == []
        # exact metric counts for this window
        d = _event_delta(before)
        assert d.get("submitted", 0) == n
        assert d.get("finished", 0) == n - cancelled
        assert d.get("cancelled", 0) == cancelled
        assert d.get("failed", 0) == 0
        return gw
    finally:
        install(prev)
        sync.disable_preemption()


# fast subset: 3 seeded schedules (the full sweep is the slow marker)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_race_harness_gateway_fast(seed, tmp_path):
    _drive_gateway_schedule(seed, tmp_path)


def test_admission_racing_remove_model_requeues_zero_lost():
    """REGRESSION (found by the seeded race harness, this PR): a
    request whose ``admit_slot`` dispatch was in flight — outside the
    scheduler lock — when ``remove_model`` tore its lane group down
    was silently orphaned: activated into a group the step loop no
    longer iterates, never stepped, never failed.  The fix re-queues
    it at the head; across a hot swap it re-resolves to the new
    version — zero lost."""
    from paddle_tpu.serving import ContinuousBatchingScheduler

    entered, gate = threading.Event(), threading.Event()

    class BlockingAdmitEcho(EchoModel):
        def admit_slot(self, slot, prompt, **_):
            entered.set()
            gate.wait(10)          # hold the admission mid-flight
            return super().admit_slot(slot, prompt, **_)

    alias = {"m": "m@1"}
    sched = ContinuousBatchingScheduler(
        max_new_tokens=3, resolve=lambda a: alias.get(a, a))
    sched.add_model("m@1", BlockingAdmitEcho(), 2)
    sched.serve()
    try:
        r = sched.submit([42], model="m")
        assert entered.wait(10), "admission never started"
        # hot swap while the admission dispatch is mid-flight: the new
        # version registers, the alias flips, the old group drains
        # (it sees NO active lanes — the racing admission is not
        # visible yet) and is deleted
        sched.add_model("m@2", EchoModel(), 2)
        alias["m"] = "m@2"
        sched.remove_model("m@1", drain=True, timeout=5)
        gate.set()                 # the orphaned admission completes
        assert r.wait(10), "request lost across the racing swap"
        assert r.error is None
        assert r.group == "m@2", "must re-resolve to the new version"
        assert r.tokens == [42] * 3
    finally:
        gate.set()
        sched.shutdown(drain=True)


V, SRC, OUT, PS, CHUNK = 24, 8, 6, 4, 4
GEN_KW = dict(n_layer=2, n_head=2, d_key=4, d_value=4, d_model=16,
              d_inner_hid=32, max_length=64, src_len=SRC,
              max_out_len=OUT, page_size=PS, chunk_size=CHUNK,
              num_pages=64)


@pytest.fixture(scope="module")
def paged_pair():
    from paddle_tpu import fluid

    # same param_prefix, separate scopes: copy_weights maps by NAME
    a = PagedTransformerGenerator(V, V, param_prefix="ccg",
                                  place=fluid.CPUPlace(), **GEN_KW)
    a.init_params(seed=3)
    b = PagedTransformerGenerator(V, V, param_prefix="ccg",
                                  place=fluid.CPUPlace(), **GEN_KW)
    copy_weights(a.scope, b.scope, prefix="ccg")
    return a, b


def test_race_harness_paged_invariants(paged_pair, tmp_path):
    """One seeded schedule over the REAL paged generator with
    ``check_invariants=True`` (PageAllocator audited after every
    retirement) + an explicit post-drain invariant check: no page is
    leaked or double-freed under perturbation."""
    gen, _ = paged_pair
    inj = FaultInjector(spec="sync.preempt=0.2", seed=5)
    prev = install(inj)
    sync.enable_preemption(inj)
    try:
        gw = Gateway(n_slots=2, max_new_tokens=OUT,
                     journal_path=str(tmp_path / "pq.jsonl"),
                     check_invariants=True)
        gw.load_model("m", "1", instance=gen)
        gw.serve()
        rng = np.random.RandomState(0)
        reqs = []
        for i in range(8):
            prompt = rng.randint(2, V, rng.randint(3, SRC + 1))
            reqs.append(gw.submit("m", prompt))
            if i in (2, 5):
                reqs[-1].cancel()
        for r in reqs:
            assert r.wait(120)
        gw.shutdown(drain=True)
        gen.alloc.check_invariants()
        st = gen.alloc.stats()
        assert st["in_use"] == 0, f"leaked pages after drain: {st}"
        assert gw.journal.pending() == []
        gw.unload_model("m")
    finally:
        install(prev)
        sync.disable_preemption()


def test_race_harness_controller_canary(tmp_path):
    """The release controller's canary verdict under seeded preemption
    while a second thread hammers status() (the lifecycle.controller
    lock regression test): the candidate promotes from live series,
    zero lost requests, and the poller sees no exceptions."""
    inj = FaultInjector(spec="sync.preempt=0.2", seed=8)
    prev = install(inj)
    sync.enable_preemption(inj)
    try:
        gw = Gateway(n_slots=2, max_new_tokens=4)
        cfg = ReleaseConfig("m", n_slots=2, canary_fraction=0.5,
                            canary_requests=4, p95_floor_s=5.0, seed=3)
        rc = ReleaseController(
            gw, cfg, journal_path=str(tmp_path / "rc.jsonl"),
            eval_fn=lambda inst: 1.0)
        rc.offer("1", EchoModel())
        assert rc.step() == "promoted"
        rc.offer("2", EchoModel())
        assert rc.step() == "canary-started"
        poll_err, stop = [], threading.Event()

        def poller():
            while not stop.is_set():
                try:
                    rc.status()
                except Exception as e:   # noqa: BLE001 - the assert
                    poll_err.append(e)
                    return

        t = threading.Thread(target=poller)
        t.start()
        try:
            verdict, reqs = None, []
            for i in range(24):
                batch = [gw.submit("m", [20 + 4 * i + k], max_new=4)
                         for k in range(4)]
                reqs.extend(batch)
                gw.run_until_idle()
                verdict = rc.step()
                if verdict != "canary":
                    break
            assert verdict == "promoted"
        finally:
            stop.set()
            t.join(10)
        assert not poll_err, f"status() raced step(): {poll_err[0]}"
        assert gw.registry.resolve("m") == "m@2"
        assert all(r.error is None for r in reqs), "lost requests"
    finally:
        install(prev)
        sync.disable_preemption()


# full sweep: N seeded schedules, including the paged model — slow tier
@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(10, 17)))
def test_race_harness_sweep(seed, tmp_path):
    _drive_gateway_schedule(seed, tmp_path, n_per_tenant=10)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [21, 22, 23])
def test_race_harness_paged_sweep(paged_pair, tmp_path, seed):
    gen, gen2 = paged_pair
    inj = FaultInjector(spec="sync.preempt=0.25", seed=seed)
    prev = install(inj)
    sync.enable_preemption(inj)
    try:
        gw = Gateway(n_slots=2, max_new_tokens=OUT,
                     journal_path=str(tmp_path / f"ps-{seed}.jsonl"),
                     check_invariants=True)
        gw.load_model("m", "1", instance=gen)
        gw.serve()
        rng = np.random.RandomState(seed)
        reqs = []

        def client(base):
            r = np.random.RandomState(base)
            for _ in range(6):
                reqs.append(gw.submit(
                    "m", r.randint(2, V, r.randint(3, SRC + 1))))

        ts = [threading.Thread(target=client, args=(seed + i,))
              for i in range(2)]
        for t in ts:
            t.start()
        gw.swap_model("m", "2", instance=gen2)
        for t in ts:
            t.join(120)
        for r in list(reqs):
            assert r.wait(180)
        gw.shutdown(drain=True)
        for g in (gen, gen2):
            g.alloc.check_invariants()
            assert g.alloc.stats()["in_use"] == 0
        assert gw.journal.pending() == []
        assert all(r.error is None for r in reqs)
        gw.unload_model("m")
        _ = rng
    finally:
        install(prev)
        sync.disable_preemption()
