"""The per-layer readers that read the program's own spans (ISSUE 25):
each on a hand-made ring (its median, the window's clipping, nothing from
an overflowed ring), ``trace_reduce.reduce`` naming an idle gap by a
program span that covers it, and the tiny rehearsals reading every one."""

import pytest

from paddle_tpu.observability import tracer
from perfbench import manifest, ring, trace_reduce
from perfbench.spans import Spans
from perfbench_helpers import rehearse

T0 = 1000.0                         # the window opens, seconds
MS = 1e3                            # microseconds in a millisecond


def emit(name, at_ms, dur_ms=None, parent=None, **args):
    """One event into the process's ring, ``at_ms`` after the window opens
    (X with a duration, else an instant); returns its id."""
    tr = tracer()
    ev = tr._base(name, "test", "X" if dur_ms is not None else "i",
                  T0 + at_ms / 1e3, dict(args))
    if dur_ms is not None:
        ev["dur"] = dur_ms * MS
    if parent is not None:
        ev["parent"] = parent
    tr._emit(ev)
    return ev["id"]


def serve_layer(seconds=1.0):
    return {"kind": "serve", "t_open": T0, "t_close": T0 + seconds}


def train_layer(seconds=1.0):
    spans = Spans()
    spans.records += [("warm", T0 - 5.0, T0), ("window", T0, T0 + seconds)]
    return {"kind": "train", "spans": spans}


def serve_round(step, at_ms, admit, plan, deliver, maint, feed, prepare,
                writeback, fetch):
    """One serve round as the program spans it, durations in ms."""
    emit("scheduler/admit", at_ms, admit, step=step)
    emit("scheduler/plan", at_ms + 1, plan, step=step)
    top = emit("scheduler/step", at_ms + 2, 50, step=step)
    emit("engine/feed_build", at_ms + 2, feed, parent=top, step=step)
    disp = emit("engine/dispatch", at_ms + 10, 20, parent=top, step=step)
    emit("executor/prepare", at_ms + 10, prepare, parent=disp, step=step)
    emit("executor_step/infer", at_ms + 15, 1, parent=disp, step=step)
    emit("executor/writeback", at_ms + 16, writeback, parent=disp, step=step)
    emit("engine/fetch", at_ms + 30, fetch, parent=top, step=step)
    emit("scheduler/deliver", at_ms + 52, deliver, step=step)
    emit("scheduler/maintenance", at_ms + 56, maint, step=step)


def fill_serve():
    # three rounds in the window, one before it and one after it
    serve_round(1, -200, 9, 9, 9, 9, 9, 9, 9, 99)
    serve_round(2, 100, 1.0, 0.5, 3.0, 0.5, 2.0, 4.0, 0.5, 150)
    serve_round(3, 300, 1.0, 0.5, 5.0, 0.5, 3.0, 5.0, 1.0, 160)
    serve_round(4, 500, 2.0, 0.5, 4.0, 0.5, 4.0, 6.0, 0.5, 170)
    serve_round(5, 1100, 9, 9, 9, 9, 9, 9, 9, 99)
    # a round that found nothing to do shares the count of the round
    # after it (the count only moves with a dispatch): step 5's, here
    # outside the window; and one before round 4, whose sum it joins
    emit("scheduler/admit", 480, 0.25, step=4)
    emit("scheduler/plan", 481, 0.25, step=4)
    emit("scheduler/admit", 700, 0.25, step=5)
    emit("scheduler/plan", 701, 0.25, step=5)
    # an executor run outside any engine dispatch (a warm-up, a load)
    emit("executor/prepare", 800, 77)
    emit("executor/writeback", 801, 77)
    # requests: ingress, first token, first chunk (rid 9's token came
    # before the window, its chunk inside it)
    for rid, ingress, token_at, chunk_at in ((7, 0.4, 150, 152.0),
                                             (8, 0.6, 350, 353.0),
                                             (9, 0.8, -5, 1.0)):
        emit("gateway/ingress", max(token_at - 100, 1), ingress, rid=rid)
        emit("request/token", token_at, rid=rid, index=1)
        emit("request/token", token_at + 200, rid=rid, index=2)
        emit("gateway/first_chunk", chunk_at, rid=rid)
    emit("gateway/first_chunk", 1200.0, rid=7)      # after the close


def fill_train():
    for at, prepare, writeback in ((-300, 9, 9), (0, 4.0, 1.0),
                                   (300, 5.0, 1.5), (600, 6.0, 2.0),
                                   (1100, 9, 9)):
        emit("executor/prepare", at, prepare)
        emit("executor_step/train", at + 7, 3)
        emit("executor/writeback", at + 10, writeback)


SERVE_WANT = {
    "sched_host_ms.serve": 7.0,                 # sums 5, 7, 7.5
    "feed_build_ms.serve": 3.0,
    "executor_host_ms.serve": 6.0,              # 4.5, 6, 6.5
    "fetch_wait_ms.serve": 160.0,
    "gateway_ingress_ms": 0.6,
    "gateway_first_chunk_ms": 3.0,              # 2, 3, 6
}
TRAIN_WANT = {"executor_prepare_ms.train": 5.0,
              "executor_writeback_ms.train": 1.5}


@pytest.fixture
def clean_ring():
    tr = tracer()
    tr.clear()
    yield tr
    tr.clear()


@pytest.mark.parametrize("name", sorted(SERVE_WANT) + sorted(TRAIN_WANT))
def test_reader_on_a_hand_made_ring(clean_ring, name):
    serve = name in SERVE_WANT
    (fill_serve if serve else fill_train)()
    layer, other = (serve_layer(), train_layer()) if serve \
        else (train_layer(), serve_layer())
    read = manifest.load_reader(name)
    want = SERVE_WANT[name] if serve else TRAIN_WANT[name]
    assert read(layer) == pytest.approx(want)
    # the other kind of cell has no such metric
    assert read(other) is None
    # a window that holds none of the spans
    far = dict(layer, t_open=T0 + 50, t_close=T0 + 51) if serve else None
    if far is not None:
        assert read(far) is None
    # an overflowed ring is not read
    clean_ring.dropped = 1
    assert read(layer) is None


def test_a_ring_without_the_spans_reads_nothing(clean_ring):
    """What the parent of PR 25 looks like to the readers."""
    emit("scheduler/step", 100, 50)
    emit("executor_step/infer", 110, 1)
    emit("request/token", 150, rid=1, index=1)
    for name in SERVE_WANT:
        assert manifest.load_reader(name)(serve_layer()) is None
    for name in TRAIN_WANT:
        assert manifest.load_reader(name)(train_layer()) is None
    assert ring.window({"kind": "train", "spans": Spans()}) is None


def gap_trace(host):
    ms = 1e6
    ops = [["fusion.1", 0.0, 40 * ms], ["fusion.1", 60 * ms, 40 * ms]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "serving-scheduler",
                                         "events": host}]}]}, ms


def test_reduce_names_a_gap_by_the_program_span_that_covers_it():
    trace, ms = gap_trace([])
    host = [["pb:window", 0.0, 100 * ms],
            ["scheduler/step", 0.0, 41 * ms],
            ["engine/fetch", 5 * ms, 35.5 * ms],
            ["scheduler/deliver", 41.5 * ms, 14 * ms],
            ["scheduler/step", 57 * ms, 43 * ms],
            ["engine/feed_build", 57 * ms, 2 * ms]]
    trace["planes"][1]["lines"][0]["events"] = host
    r = trace_reduce.reduce(trace)
    assert r["idle_gaps"] == [["scheduler/deliver", pytest.approx(0.020)]]
    read = manifest.load_reader("idle_unattributed_share.serve")
    layer = {"kind": "serve", "trace": r}
    assert read(layer) == 0.0
    # no span over the gap: all of it is unattributed, as on the parent
    bare, _ = gap_trace([["pb:window", 0.0, 100 * ms]])
    r = trace_reduce.reduce(bare)
    assert r["idle_gaps"][0][0] == "(no host span)"
    assert read({"kind": "serve", "trace": r}) == 100.0
    # the runtime's own spans are not the program's
    r["idle_gaps"] = [["scheduler/deliver", 0.6], ["pb:fetch_loss", 0.1],
                      ["np.asarray(jax.Array)", 0.2], ["(no host span)", 0.1]]
    assert read({"kind": "serve", "trace": r}) == pytest.approx(30.0)
    assert read({"kind": "train", "trace": r}) is None
    assert read({"kind": "serve", "trace": {}}) is None


@pytest.mark.parametrize("cell", ["base-serve-steady", "big-train-s256"])
def test_rehearsal_reads_every_ring_reader(capsys, cell):
    tracer().clear()
    rc, result, lines = rehearse(capsys, cell, seed=2**31 + 11, seconds=1.5,
                                 trace=1)
    assert rc == 0 and result["metrics"] == {}
    read = {ln["rehearsal_reader"] for ln in lines
            if "rehearsal_reader" in ln}
    due = {m["name"] for m in manifest.metrics_for(
        manifest.load(), cell, "per_layer")
        if m["source"] == "program_span"}
    assert due and due <= read
    want = SERVE_WANT if cell.startswith("base-serve") else TRAIN_WANT
    assert set(want) == due
