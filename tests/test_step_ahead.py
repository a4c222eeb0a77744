"""The scheduler over an engine that runs one step ahead of its own fetch
(ISSUE 41: ``PagedLMGenerator.lane_step_ahead``), with the real engine at a
tiny size on the CPU: a request's tokens are what the synchronous call
gives it, whoever held its slot before; the last tokens arrive and the
loop goes idle with nothing in flight; a failure at the fetch fails the
group as one at the launch does; unloading and shutting down do not wait
for a step in flight."""

import json

import numpy as np
import pytest

from paddle_tpu.serving import ContinuousBatchingScheduler, PagedLMGenerator
from paddle_tpu.serving.scheduler import RequestCancelled
from perfbench import weights
from perfbench.families import mimo_v2_flash as fam
from test_delivery import limited, wait_for

LANES = 4


@pytest.fixture(scope="module")
def engine():
    with open("perfbench/configs/mimo-v2-flash-ep32.json",
              encoding="utf-8") as f:
        cfg = {**json.load(f), **fam.REHEARSAL["serve"]["cfg"]}
    conf = fam.serving(cfg)["manifest"]["config"]
    assert conf["lanes"] == LANES
    gen = PagedLMGenerator(**conf)
    gen.load_weights(weights.make(
        fam.param_shapes(cfg, cfg["param_prefix"]), 41, kind_of=fam.leaf_kind))
    yield gen
    # an instance attribute a test left behind would step the next one
    # through it
    assert "lane_step" not in vars(gen) and "_collect" not in vars(gen)


def traffic(n=14):
    rng = np.random.default_rng(7)
    return [(rng.integers(2, 64, int(rng.integers(2, 30))).tolist(),
             int(rng.integers(1, 12))) for _ in range(n)]


def served(gen, requests, cancel=()):
    """Every request's tokens through one scheduler, driven inline;
    ``cancel``: indices cancelled once their second token was seen."""
    sched = ContinuousBatchingScheduler(gen, n_slots=LANES,
                                        max_new_tokens=16)
    reqs = []
    for i, (prompt, cap) in enumerate(requests):
        def on_token(req, tok, i=i):
            if i in cancel and tok is not None and len(req.tokens) == 2:
                req.cancel()
        reqs.append(sched.submit(prompt, cap, on_token=on_token))
    assert sched.run_until_idle(max_steps=2000) < 2000
    assert all(r.done for r in reqs)
    return sched, reqs


def test_every_request_gets_its_own_tokens_and_the_loop_ends_empty(
        engine, monkeypatch):
    """14 requests over 4 lanes, so every slot is taken again and again:
    requests that run to their caps, requests that meet the end of
    sequence (a token the tiny model likes), requests cancelled with a
    step in flight.  Each gets the tokens the synchronous engine gives it
    (the same scheduler over an instance whose ``lane_step`` is wrapped,
    which the loop must then call), none of its slot's last holder; after
    the last token nothing is in flight."""
    requests, cancel = traffic(), (3, 8)
    # the reference: the engine stepped synchronously, behind a wrapper
    monkeypatch.setattr(engine, "end_id", 64)
    calls, real = [], engine.lane_step
    engine.lane_step = lambda: calls.append(1) or real()
    try:
        _, free = served(engine, requests)
    finally:
        del engine.lane_step
    assert calls and engine.counters()["steps_ahead"] == 0
    assert [len(r.tokens) for r in free] == [cap for _, cap in requests]
    # an end of sequence that several requests meet mid-stream
    flat = [t for r in free for t in r.tokens[:-1]]
    eos = max(set(flat), key=flat.count)
    monkeypatch.setattr(engine, "end_id", eos)

    def cut(tokens, i):
        tokens = list(tokens)
        if eos in tokens:
            tokens = tokens[:tokens.index(eos) + 1]
        return tokens[:2] if i in cancel and len(tokens) >= 2 else tokens

    want = [cut(r.tokens, i) for i, r in enumerate(free)]
    ended_early = sum(len(w) < cap for w, (_, cap) in zip(want, requests))
    assert ended_early >= 4, (eos, want)

    before = engine.counters()
    sched, reqs = served(engine, requests, cancel)
    assert [list(r.tokens) for r in reqs] == want
    gone = 0
    for i, r in enumerate(reqs):
        cancelled = i in cancel and len(want[i]) == 2 \
            and want[i][-1] != eos and requests[i][1] > 2
        assert isinstance(r.error, RequestCancelled) if cancelled \
            else r.error is None, i
        gone += cancelled
    after = sched.stats()["engine"]
    steps = after["steps"] - before["steps"]
    assert after["steps_ahead"] - before["steps_ahead"] >= steps - 3
    assert after["stray_tokens_dropped"] - before["stray_tokens_dropped"] \
        >= 1
    assert after["tokens_fed_on_device"] > before["tokens_fed_on_device"]
    assert not engine._in_flight
    assert after["global_pages_in_use"] == after["window_pages_in_use"] == 0
    assert gone and sched.stats()["failed"] == gone    # the cancelled


@pytest.mark.parametrize("where", ["_launch", "_collect"])
def test_a_failure_in_either_half_fails_the_group_and_the_loop_goes_on(
        engine, where):
    """An exception out of the fetch reaches the group's requests as one
    out of the launch does (and as ``lane_step``'s always did): every
    request in flight fails with it, the lanes and their pages come back,
    nothing stays in flight, and the next request is served."""
    sched = ContinuousBatchingScheduler(engine, n_slots=LANES,
                                        max_new_tokens=16)
    reqs = [sched.submit([3, 4, 5, 6 + i], 8) for i in range(3)]
    for _ in range(3):
        assert sched.step_once()
    assert len(engine._in_flight) == 1

    def broken(*args, **kwargs):
        raise FloatingPointError(f"{where} broke")

    setattr(engine, where, broken)
    try:
        assert sched.step_once()
    finally:
        delattr(engine, where)
    assert all(r.done and isinstance(r.error, FloatingPointError)
               for r in reqs)
    assert not engine._in_flight
    assert engine.counters()["global_pages_in_use"] == 0
    again = sched.submit([3, 4, 5, 6], 8)
    sched.run_until_idle(max_steps=50)
    assert again.done and again.error is None and len(again.tokens) == 8
    assert list(again.tokens)[:len(reqs[0].tokens)] == list(reqs[0].tokens)


@limited(120)
def test_unload_and_shutdown_do_not_wait_for_a_step_in_flight(engine):
    """``remove_model`` without a drain and a plain ``shutdown`` return
    with a step launched and not fetched; the served requests' last
    tokens had all arrived, and the loop had gone idle with nothing in
    flight, before that."""
    sched = ContinuousBatchingScheduler(engine, n_slots=LANES,
                                        max_new_tokens=16).serve()
    try:
        first = [sched.submit([2 + i, 9, 4], 5 + i) for i in range(6)]
        assert all(r.wait(60) for r in first)
        assert [len(r.tokens) for r in first] == [5 + i for i in range(6)]
        wait_for(lambda: not sched.active_requests())
        assert not engine._in_flight
        long = [sched.submit([7, 8, 9, 10 + i], 16) for i in range(LANES)]
        wait_for(lambda: all(len(r.tokens) >= 2 for r in long), 60)
        sched.remove_model(sched.models()[0], drain=False)
        assert all(r.wait(10) and isinstance(r.error, RuntimeError)
                   and "unloaded" in str(r.error) for r in long)
        assert not engine._in_flight        # the lanes' clearing dropped it
    finally:
        sched.shutdown(timeout=10)
    # the same engine under a new scheduler, stopped mid-stream
    sched = ContinuousBatchingScheduler(engine, n_slots=LANES,
                                        max_new_tokens=16).serve()
    long = [sched.submit([7, 8, 9, 10 + i], 16) for i in range(LANES)]
    wait_for(lambda: all(len(r.tokens) >= 2 for r in long), 60)
    sched.shutdown(timeout=10)
    assert sched._thread is None
    # abandoned lanes, perhaps a step in flight: the next owner's
    # open_slots drops both
    engine.open_slots(LANES)
    assert not engine._in_flight
    assert engine.counters()["global_pages_in_use"] == 0
