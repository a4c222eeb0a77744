"""Token delivery (ISSUE 36): a step's tokens and ends leave the step loop
in one hand-off and ONE consumer delivers them in order — the
``serving-delivery`` thread beside ``serve()``, the loop's driver without
one.  Each invariant of the hand-off has its test here, on a fake lane
model (the scheduler and the gateway are what is under test), and the
soak drives the whole front door over keep-alive HTTP connections.
"""

import functools
import gc
import http.client
import json
import threading
import time
import weakref

import numpy as np
import pytest

from paddle_tpu.observability import tracer
from paddle_tpu.serving import ContinuousBatchingScheduler
from paddle_tpu.serving.gateway import Gateway, GatewayServer, TokenStream
from paddle_tpu.serving.scheduler import RequestCancelled


def limited(seconds):
    """The test's own time limit: its body runs on a thread that is given
    ``seconds`` (a hand-off that loses an end shows as a hang)."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            box = {}

            def body():
                try:
                    fn(*args, **kwargs)
                except BaseException as e:      # re-raised on the caller
                    box["error"] = e

            t = threading.Thread(target=body, daemon=True)
            t.start()
            t.join(seconds)
            assert not t.is_alive(), \
                f"{fn.__name__} passed its time limit of {seconds} s"
            if "error" in box:
                raise box["error"]
        return run
    return wrap


class LaneModel:
    """Self-managed fake: every live lane emits ``burst`` tokens a step,
    counting up from 100 x its prompt's first token; never the end id."""

    start_id, end_id = 0, 1
    src_len = 64
    max_out_len = 4096

    def __init__(self, burst=1, step_s=0.0):
        self.burst, self.step_s = burst, step_s
        self.lanes = {}
        self.fail = None
        self.checked_on = []

    def open_slots(self, n):
        self.n = n

    def admit_slot(self, slot, prompt, **_):
        prompt = np.asarray(prompt).reshape(-1)
        self.lanes[slot] = 100 * int(prompt[0])
        return len(prompt)

    def clear_slot(self, slot):
        self.lanes.pop(slot, None)

    def lane_step(self):
        if self.fail is not None:
            raise self.fail
        if self.step_s:
            time.sleep(self.step_s)
        out = {}
        for slot in sorted(self.lanes):
            first = self.lanes[slot]
            self.lanes[slot] += self.burst
            toks = list(range(first, first + self.burst))
            out[slot] = toks if self.burst > 1 else toks[0]
        return out


class Listener:
    """A streaming callback that writes down what it is told, and
    whether the request already called itself done."""

    def __init__(self, gate=None, raises=False, pause_s=0.0):
        self.seen, self.done_flags = [], []
        self.gate, self.raises, self.pause_s = gate, raises, pause_s

    def __call__(self, req, tok):
        self.seen.append(tok)
        self.done_flags.append(req.done)
        if self.gate is not None:
            self.gate.wait()
        if self.pause_s:
            time.sleep(self.pause_s)
        if self.raises:
            raise RuntimeError("a broken listener")

    def whole(self, req):
        """Every token in order, the sentinel last and once, and the
        request never done before its sentinel was emitted."""
        return self.seen == req.tokens + [None] \
            and not any(self.done_flags)


def wait_for(cond, seconds=5.0):
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, "waited too long"
        time.sleep(0.001)


def delivery_threads():
    return [t for t in threading.enumerate() if t.name == "serving-delivery"]


# -- I1: order, and no end before a token --------------------------------------

@limited(10)
def test_blocked_listener_delays_the_other_stream_never_cuts_it():
    """The race PR 35 lost: a request retires in the step loop while its
    last tokens still wait for delivery.  Its stream's consumer must
    block, not find the request done and its queue empty."""
    sched = ContinuousBatchingScheduler(LaneModel(), n_slots=2).serve()
    gate = threading.Event()
    try:
        first = Listener(gate=gate)
        sched.submit([2], max_new_tokens=64, on_token=first)
        wait_for(lambda: first.seen)            # delivery stands here
        stream = TokenStream(timeout=8.0)
        req = sched.submit([3], max_new_tokens=6, on_token=stream._push)
        stream.request = req
        got, ended = [], threading.Event()

        def consume():
            for tok in stream:
                got.append(tok)
            ended.set()

        threading.Thread(target=consume, daemon=True).start()
        wait_for(lambda: req.finished is not None)      # retired
        assert len(req.tokens) == 6                     # I2
        time.sleep(0.05)
        assert not req.done and not ended.is_set() and len(got) < 6
        gate.set()
        assert ended.wait(5) and req.wait(5)
        assert got == req.tokens and len(got) == 6
        backlog = sched.stats()["delivery"]["backlog_max"]
        assert backlog >= 2, "the backlog is reported, not bounded"
    finally:
        gate.set()
        sched.shutdown(drain=True)


@pytest.mark.parametrize("path", ["cap", "cancel", "fail_group",
                                  "remove_model"])
@limited(10)
def test_every_retiring_path_delivers_tokens_before_the_sentinel(path):
    model = LaneModel(step_s=0.001)
    sched = ContinuousBatchingScheduler(max_new_tokens=4096)
    sched.add_model("m", model, 2)
    sched.serve()
    try:
        # delivery lags the loop (less where the drain's allowance is
        # what the test waits out)
        heard = Listener(pause_s=0.0002 if path == "remove_model"
                         else 0.002)
        cap = 12 if path == "cap" else 4096
        req = sched.submit([2], max_new_tokens=cap, model="m",
                           on_token=heard)
        wait_for(lambda: len(heard.seen) >= 3)
        if path == "cancel":
            req.cancel()
        elif path == "fail_group":
            model.fail = ValueError("the dispatch failed")
        elif path == "remove_model":
            sched.remove_model("m", drain=True, timeout=0.2)
            # I3: back only after the ends it handed off were delivered
            assert heard.seen[-1] is None and req.done
        assert req.wait(5)
        assert heard.whole(req), (heard.seen, req.tokens)
        assert len(req.tokens) >= 3
        want = {"cap": type(None), "cancel": RequestCancelled,
                "fail_group": ValueError, "remove_model": RuntimeError}
        assert isinstance(req.error, want[path])
    finally:
        sched.shutdown(drain=True)


# -- I3: drains wait for delivery, a plain stop halts at a record's edge -------

@limited(15)
def test_shutdown_drain_returns_with_nothing_undelivered():
    sched = ContinuousBatchingScheduler(LaneModel(), n_slots=4).serve()
    heard = [Listener(pause_s=0.0005) for _ in range(8)]
    reqs = [sched.submit([2 + i], max_new_tokens=20, on_token=h)
            for i, h in enumerate(heard)]
    wait_for(lambda: sched.stats()["queued"] == 0)
    sched.shutdown(drain=True, timeout=10)
    assert not delivery_threads()
    assert sched._outbox.empty()
    assert sched._delivered == sched.stats()["delivery"]["handoffs"]
    for req, h in zip(reqs, heard):
        assert req.done and len(req.tokens) == 20 and h.whole(req)
    assert sched.stats()["delivery"]["tokens"] == 160


@limited(15)
def test_plain_shutdown_stops_at_a_records_edge_and_the_rest_follows():
    sched = ContinuousBatchingScheduler(LaneModel(burst=3), n_slots=2)
    sched.serve()
    heard = Listener(pause_s=0.003)
    req = sched.submit([2], max_new_tokens=300, on_token=heard)
    wait_for(lambda: len(heard.seen) >= 6)
    sched.shutdown(drain=False)
    assert not delivery_threads() and sched._thread is None
    # whole records only: a burst of three is never cut in the middle
    assert len(heard.seen) % 3 == 0
    assert heard.seen == req.tokens[:len(heard.seen)]
    assert not req.done
    # the next consumer (here the loop's driver) takes up where it stopped
    sched.run_until_idle()
    assert req.done and heard.whole(req) and len(req.tokens) == 300


# -- I4: a listener harms only its own timing ----------------------------------

@limited(10)
def test_a_raising_or_blocking_listener_stops_neither_thread():
    sched = ContinuousBatchingScheduler(LaneModel(), n_slots=3).serve()
    gate = threading.Event()
    try:
        broken, good = Listener(raises=True), Listener()
        r1 = sched.submit([2], max_new_tokens=8, on_token=broken)
        r2 = sched.submit([3], max_new_tokens=8, on_token=good)
        assert r1.wait(5) and r2.wait(5)
        assert broken.whole(r1) and good.whole(r2)
        assert r1.error is None and len(delivery_threads()) == 1
        # the step loop never waits for delivery: behind a listener that
        # blocks, requests go on being stepped and retired
        stuck = Listener(gate=gate)
        r3 = sched.submit([4], max_new_tokens=4, on_token=stuck)
        wait_for(lambda: stuck.seen)
        steps = sched.stats()["steps"]
        r4 = sched.submit([5], max_new_tokens=30)
        wait_for(lambda: r4.finished is not None)
        assert sched.stats()["steps"] >= steps + 30 and not r4.done
        gate.set()
        assert r3.wait(5) and r4.wait(5) and stuck.whole(r3)
    finally:
        gate.set()
        sched.shutdown(drain=True)


@limited(15)
def test_the_loop_offers_the_interpreter_after_a_hand_off():
    """A token reaches its listener while the loop still holds the
    interpreter for the next launch, not only once the loop blocks."""
    held_until = []

    class Holding(LaneModel):
        def lane_step(self):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 0.004:     # feeds, prepare
                pass
            held_until.append(time.perf_counter())
            time.sleep(0.002)                           # the device's wait
            return super().lane_step()

    sched = ContinuousBatchingScheduler(Holding(), n_slots=2).serve()
    heard_at = []
    try:
        req = sched.submit([2], max_new_tokens=40,
                           on_token=lambda r, tok: heard_at.append(
                               time.perf_counter()))
        assert req.wait(10)
    finally:
        sched.shutdown(drain=True)
    # token k was stamped after step k's dispatch; step k + 1 then holds
    # the interpreter for 4 ms: the listener heard before that ended
    early = sum(heard < held for heard, held
                in zip(heard_at[:39], held_until[1:40]))
    assert early >= 28, (early, len(heard_at))


# -- I5: no loop thread, the same deliver function, before step_once returns ---

@limited(10)
def test_inline_driving_delivers_before_step_once_returns():
    sched = ContinuousBatchingScheduler(LaneModel(), n_slots=2)
    heard = Listener()
    req = sched.submit([2], max_new_tokens=3, on_token=heard)
    assert sched.step_once()
    assert heard.seen == [200] and req.tokens == [200]
    assert sched.stats()["delivery"] == {
        "handoffs": 1, "tokens": 1, "backlog_max": 0, "inline": 1}
    sched.run_until_idle()
    assert req.done and heard.whole(req)
    d = sched.stats()["delivery"]
    assert d["inline"] == d["handoffs"] == 3 and not delivery_threads()
    # a cancelled lane's end goes the same way
    other = Listener()
    r2 = sched.submit([3], max_new_tokens=50, on_token=other)
    sched.step_once()
    r2.cancel()
    sched.step_once()
    assert r2.done and other.whole(r2) and len(r2.tokens) == 1
    # shutdown(drain=True) drives the loop inline too
    r3 = sched.submit([4], max_new_tokens=5, on_token=(last := Listener()))
    sched.step_once()
    sched.shutdown(drain=True)
    assert r3.done and last.whole(r3) and len(r3.tokens) == 5


# -- speculative lanes, and the trace's stamps ---------------------------------

@limited(10)
@pytest.mark.parametrize("threaded", [False, True])
def test_a_lanes_list_keeps_its_order_and_instants_carry_the_steps_stamp(
        threaded):
    ring = tracer()
    ring.clear()
    sched = ContinuousBatchingScheduler(LaneModel(burst=3), n_slots=2)
    if threaded:
        sched.serve()
    heard = Listener()
    req = sched.submit([2], max_new_tokens=8, on_token=heard)
    if threaded:
        assert req.wait(5)
        sched.shutdown(drain=True)
    else:
        sched.run_until_idle()
    # three lists of three, the last cut at the cap in the step loop
    assert req.tokens == list(range(200, 208)) and heard.whole(req)
    marks = [e for e in ring.events(name="request/token")
             if e["args"]["rid"] == req.rid]
    assert [e["args"]["index"] for e in marks] == list(range(1, 9))
    delivered = {e["args"]["step"]: e for e in
                 ring.events(name="scheduler/deliver")}
    for e in marks:
        # the step loop's stamp (taken inside that step's
        # scheduler/deliver), not the delivering thread's clock
        span = delivered[e["args"]["step"]]
        assert span["ts"] <= e["ts"] <= span["ts"] + span["dur"]
    assert marks[0]["ts"] == marks[2]["ts"] == req.first_token * 1e6
    assert marks[-1]["ts"] == req.last_token * 1e6
    outs = [e for e in ring.events(name="scheduler/deliver_out")]
    assert [e["args"]["tokens"] for e in outs] == [3, 3, 2]
    assert [e["args"]["finished"] for e in outs] == [0, 0, 1]
    assert [e["args"]["step"] for e in outs] == sorted(delivered)
    retired = [e for e in ring.events(name="request/retired")
               if e["args"]["rid"] == req.rid]
    assert len(retired) == 1 and retired[0]["args"]["tokens"] == 8
    assert retired[0]["ts"] == req.finished * 1e6


# -- I6, and what a delivered record must not keep alive -----------------------

@limited(10)
def test_the_page_audit_runs_in_the_retire_bookkeeping_and_is_loud():
    class Audited(LaneModel):
        broken = False

        def check_invariants(self):
            self.checked_on.append(threading.current_thread().name)
            assert not self.broken, "a page leaked"

    inst = Audited()
    gw = Gateway(n_slots=2, max_new_tokens=8, check_invariants=True)
    gw.load_model("m", "1", instance=inst, warm=False)
    gw.serve()
    try:
        ok = gw.submit("m", [2], max_new=3)
        assert ok.wait(5) and ok.error is None
        assert inst.checked_on == ["serving-scheduler"]
        inst.broken = True
        bad = gw.submit("m", [3], max_new=3)
        assert bad.wait(5) and isinstance(bad.error, AssertionError)
    finally:
        gw.shutdown(drain=True)


@limited(10)
def test_a_delivered_record_pins_no_listener():
    class Held:
        pass

    sched = ContinuousBatchingScheduler(LaneModel(), n_slots=2).serve()
    try:
        held = Held()
        ref = weakref.ref(held)

        def listener(req, tok, held=held):
            pass

        req = sched.submit([2], max_new_tokens=4, on_token=listener)
        assert req.wait(5)
        del held, listener
        wait_for(lambda: gc.collect() is not None and ref() is None)
    finally:
        sched.shutdown(drain=True)


# -- the soak: every stream end over keep-alive connections, whole ------------

class GatedLanes(LaneModel):
    """Emits for a lane only once its request's stream has its sink, so
    every stream is attached before its first token."""

    def __init__(self, attached):
        super().__init__()
        self.attached, self.rids = attached, {}

    def tag_slot(self, slot, rid):
        self.rids[slot] = rid

    def clear_slot(self, slot):
        super().clear_slot(slot)
        self.rids.pop(slot, None)

    def lane_step(self):
        out = {}
        for slot in sorted(self.lanes):
            if self.rids.get(slot) in self.attached:
                out[slot] = self.lanes[slot]
                self.lanes[slot] += 1
        if not out:
            time.sleep(0.0005)
        return out


@pytest.mark.parametrize("gated", [False, True], ids=["free", "gated"])
@pytest.mark.parametrize("clients,each,lanes,most", [
    (32, 20, 16, 16),           # 640 ends; what found PR 35's short streams
    (192, 4, 128, 24),          # the cell's shape: 192 connections, 128 lanes
], ids=["32x20", "192x4"])
@limited(60)
def test_soak_every_stream_over_keep_alive_ends_whole(
        monkeypatch, clients, each, lanes, most, gated):
    """Through the real ``GatewayServer``: every response is read to its
    terminating chunk, is the scheduler's own ``req.tokens`` token for
    token, says ``tokens == max_new`` in its ``done`` line, and the next
    request goes out on the same connection at once.  A tree that lets
    the handler go before the response's last byte, or sets ``_done``
    before the sentinel, fails here (PERF.md section 6, PR 39)."""
    attached = set()
    if gated:
        real_attach = TokenStream.attach

        def attach(self, sink):
            real_attach(self, sink)
            attached.add(self.request.rid)

        monkeypatch.setattr(TokenStream, "attach", attach)
    gw = Gateway(n_slots=lanes, max_new_tokens=most)
    gw.load_model("m", "1", warm=False,
                  instance=GatedLanes(attached) if gated else LaneModel())
    srv = GatewayServer(gw, request_timeout=30.0)
    host, port = srv.start().split(":")
    faults, ends = [], []

    def client(k):
        rng = np.random.RandomState(k)
        conn = http.client.HTTPConnection(host, int(port), timeout=30)
        try:
            for i in range(each):
                want = int(rng.randint(4, most + 1))
                body = json.dumps({"model": "m", "prompt": [2 + k],
                                   "max_new": want, "stream": True})
                conn.request("POST", "/v1/generate", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                lines = [json.loads(ln) for ln in
                         resp.read().decode().splitlines()]
                toks = [ln["token"] for ln in lines if "token" in ln]
                done = lines[-1]
                first = 100 * (2 + k)
                if resp.status != 200 or not done.get("done") \
                        or "error" in done or done["tokens"] != want \
                        or toks != list(range(first, first + want)):
                    faults.append((k, i, want, resp.status, lines[-3:]))
                ends.append((done.get("rid"), toks))
        except Exception as e:                  # a fault, not a crash
            faults.append((k, repr(e)))
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(clients)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(50)
        assert not any(t.is_alive() for t in threads)
        assert not faults, faults[:5]
        assert len(ends) == clients * each
        own = {r.rid: r.tokens for r in gw.sched.finished_requests()}
        assert all(own[rid] == toks for rid, toks in ends)
        total = sum(len(toks) for _, toks in ends)
        stats = gw.sched.stats()
        assert stats["failed"] == 0 and stats["finished"] == len(ends)
        assert stats["delivery"]["tokens"] == total
        assert stats["delivery"]["inline"] == 0
        c = gw.stats()["streams"]
        assert c["opened"] == c["attached"] == c["done_lines"] == len(ends)
        assert c["handed_back"] == c["send_failed"] == c["non_200"] == 0
        assert c["chunks_direct"] + c["chunks_by_handler"] == total
        if gated:       # attached before its first token: all by the writer
            assert c["chunks_by_handler"] == 0
    finally:
        srv.stop(drain=True)
