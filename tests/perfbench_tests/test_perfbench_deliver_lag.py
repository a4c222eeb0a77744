"""``deliver_lag_ms.serve`` (ISSUE 36): per step, the end of the delivery
thread's ``scheduler/deliver_out`` less the end of the step loop's
``scheduler/deliver``, on hand-made rings; nothing where a program has no
such span (the parent commit); and the entry that lists it."""

import pytest

from paddle_tpu.observability import tracer
from perfbench import manifest

NAME = "deliver_lag_ms.serve"
T0 = 1000.0                         # the window opens, seconds
MS = 1e3                            # microseconds in a millisecond


def emit(name, at_ms, dur_ms, **args):
    tr = tracer()
    ev = tr._base(name, "test", "X", T0 + at_ms / 1e3, dict(args))
    ev["dur"] = dur_ms * MS
    tr._emit(ev)


def layer(kind="serve", seconds=1.0):
    return {"kind": kind, "t_open": T0, "t_close": T0 + seconds}


@pytest.fixture
def clean_ring():
    tr = tracer()
    tr.clear()
    yield tr
    tr.clear()


def two_steps():
    # step 7: handed off at 102 ms, delivered by 110 ms; step 8: 131, 135
    emit("scheduler/deliver", 100, 2.0, step=7)
    emit("scheduler/deliver_out", 103, 7.0, step=7, tokens=128)
    emit("scheduler/deliver", 130, 1.0, step=8)
    emit("scheduler/deliver_out", 132, 3.0, step=8, tokens=128)


def test_lag_is_the_median_over_the_windows_steps(clean_ring):
    two_steps()
    read = manifest.load_reader(NAME)
    assert read(layer()) == pytest.approx(6.0)          # 8 and 4
    # a record of ends alone (a cancelled lane) carries no step
    emit("scheduler/deliver_out", 140, 50.0, tokens=0, finished=1)
    # steps outside the window are not the window's
    emit("scheduler/deliver", -50, 1.0, step=3)
    emit("scheduler/deliver_out", -48, 90.0, step=3)
    emit("scheduler/deliver", 1100, 1.0, step=40)
    emit("scheduler/deliver_out", 1102, 90.0, step=40)
    assert read(layer()) == pytest.approx(6.0)
    assert read(layer(kind="train")) is None
    assert read(dict(layer(), t_open=T0 + 50, t_close=T0 + 51)) is None
    clean_ring.dropped = 1                      # an overflowed ring
    assert read(layer()) is None


def test_a_step_not_yet_delivered_is_left_out(clean_ring):
    two_steps()
    emit("scheduler/deliver", 160, 1.0, step=9)         # its record waits
    assert manifest.load_reader(NAME)(layer()) == pytest.approx(6.0)


@pytest.mark.parametrize("what", ["the parent's ring", "an empty ring",
                                  "no step delivered"])
def test_reads_nothing_and_does_not_raise(clean_ring, what):
    if what == "the parent's ring":             # tokens out on the loop
        emit("scheduler/deliver", 100, 15.0, step=7)
        emit("scheduler/deliver", 140, 15.0, step=8)
    elif what == "no step delivered":
        emit("scheduler/deliver", 100, 1.0, step=7)
        emit("scheduler/deliver_out", 90, 2.0, step=6)
    assert manifest.load_reader(NAME)(layer()) is None, what
    assert manifest.load_reader(NAME)({"kind": "serve"}) is None


def test_entry_lists_the_four_serve_cells_and_moves_the_gap_tail():
    m = manifest.load()
    entry = next(e for e in m["per_layer"] if e["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "scheduler",
        "moves": "token_gap_p95_ms",
        "workloads": ["base-serve-flood", "base-serve-steady",
                      "mimo-serve-mixed", "moonlight-serve-decode"]}
    serve = [w["name"] for w in m["workloads"] if "serve" in w["name"]]
    assert sorted(entry["workloads"]) == sorted(serve)
    for cell in serve:
        assert entry in manifest.metrics_for(m, cell, "per_layer")
    assert entry not in manifest.metrics_for(m, "big-train-s256",
                                             "per_layer")
