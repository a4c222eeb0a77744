"""The books of a serve step (ISSUE 40): the six readers that make
``step_wall_ms.serve`` a sum of rows, and ``admit_wait_p50_ms``, on
hand-made rings against values worked by hand; nothing where the program
opens no ``scheduler/round`` or its spans carry no ``tdur`` (the parent
commit); the entries that list them; and one CPU walk of a serve cell that
reads them all, in a work directory of its own."""

import pytest

from paddle_tpu.observability import tracer
from perfbench import manifest, run

from perfbench_helpers import rehearse

T0 = 1000.0                         # the window opens, seconds
MS = 1e3                            # microseconds in a millisecond
LOOP, WRITER = 111, 222             # thread ids
SERVE_CELLS = ["base-serve-flood", "base-serve-steady", "mimo-serve-mixed",
               "moonlight-serve-decode"]
BOOKS = {"executor_launch_ms.serve": "executor dispatch",
         "executor_release_ms.serve": "executor dispatch",
         "absorb_ms.serve": "paged engine",
         "loop_serial_ms.serve": "scheduler",
         "loop_off_cpu_ms.serve": "scheduler",
         "loop_unspanned_ms.serve": "scheduler"}


def emit(name, at_ms, dur_ms, tdur_ms=None, parent=None, tid=LOOP, ph="X",
         **args):
    """One event as the tracer would have written it; -> its id."""
    tr = tracer()
    ev = tr._base(name, "test", ph, T0 + at_ms / 1e3, dict(args))
    ev["tid"] = tid
    if ph == "X":
        ev["dur"] = dur_ms * MS
    if tdur_ms is not None:
        ev["tdur"] = tdur_ms * MS
    if parent is not None:
        ev["parent"] = parent
    tr._emit(ev)
    return ev["id"]


def layer(kind="serve", seconds=1.0):
    return {"kind": kind, "t_open": T0, "t_close": T0 + seconds}


def read(name, **over):
    return manifest.load_reader(name)(layer(**over))


@pytest.fixture
def clean_ring():
    tr = tracer()
    tr.clear()
    yield tr
    tr.clear()


def a_round(step, at, dur, tdur, admit, plan, step_dur, feed, dispatch,
            prepare, launch, writeback, release, fetch, absorb, deliver,
            maintenance=None, top=True, thread_time=True):
    """One dispatched round, laid out in order from ``at`` (ms).  Spans
    are (dur, tdur) pairs; ``top=False`` leaves out what the parent commit
    has not (the round's own span, ``executor/release``),
    ``thread_time=False`` every ``tdur``."""
    def span(name, at_ms, pair, parent):
        d, t = pair
        return emit(name, at_ms, d, t if thread_time else None,
                    parent=parent, step=step)

    r = span("scheduler/round", at, (dur, tdur), None) if top else None
    t = at
    span("scheduler/admit", t, admit, r)
    t += admit[0] + 0.1
    span("scheduler/plan", t, plan, r)
    t += plan[0] + 0.1
    s = span("scheduler/step", t, step_dur, r)
    span("engine/feed_build", t, feed, s)
    t += feed[0] + 0.1
    d = span("engine/dispatch", t, dispatch, s)
    span("executor/prepare", t, prepare, d)
    span("executor_step/infer", t + prepare[0] + 0.05, launch, d)
    span("executor/writeback", t + prepare[0] + launch[0] + 0.1, writeback, d)
    if top:
        span("executor/release",
             t + prepare[0] + launch[0] + writeback[0] + 0.15, release, d)
    t += dispatch[0] + 0.1
    span("engine/fetch", t, fetch, s)
    t += fetch[0] + 0.1
    span("engine/absorb", t, absorb, s)
    t = at + admit[0] + plan[0] + step_dur[0] + 0.3
    span("scheduler/deliver", t, deliver, r)
    if maintenance:
        span("scheduler/maintenance", t + deliver[0] + 0.1, maintenance, r)


def three_rounds(**how):
    """Worked by hand, in ms (the round's own time: serial = dur less the
    fetch; off-CPU = (dur - tdur) less the fetch's; unspanned = the self
    times of round + step + dispatch):

    step 7: dur 20, fetch 6      -> serial 14
            off 12, fetch's 5.75 -> off-CPU 6.25
            20 - (1 + .5 + 15 + 1.5) = 2; 15 - (1 + 5 + 6 + 1) = 2;
            5 - (1 + 3 + .25 + .5) = .25 -> unspanned 4.25
            launch 3; release .5; absorb 1
    step 8: dur 30, fetch 9      -> serial 21
            off 20, fetch's 8    -> off-CPU 12
            30 - (2 + 1 + 22 + 2 + 1) = 2; 22 - (2 + 8 + 9 + 3) = 0;
            8 - (1 + 5 + 1 + 1) = 0 -> unspanned 2
            launch 5; release 1; absorb 3
    step 9: dur 10, fetch 2      -> serial 8
            off 5, fetch's 1.5   -> off-CPU 3.5
            10 - (.5 + .5 + 7 + 1) = 1; 7 - (1 + 3 + 2 + .25) = .75;
            3 - (.5 + 2 + .25 + .125) = .125 -> unspanned 1.875
            launch 2; release .125; absorb .25
    medians: serial 14, unspanned 2, launch 3, release .5, absorb 1; the
    off-CPU time is a MEAN (its clock may tick): 21.75 / 3 = 7.25.
    Without the release span (the parent) its time is dispatch's own."""
    a_round(7, 100, 20, 8, (1, 1), (.5, .5), (15, 4), (1, 1), (5, 2.5),
            (1, 1), (3, 1), (.25, .25), (.5, .5), (6, .25), (1, 1),
            (1.5, 1), **how)
    a_round(8, 130, 30, 10, (2, 2), (1, 1), (22, 5), (2, 2), (8, 4),
            (1, 1), (5, 2), (1, 1), (1, 1), (9, 1), (3, 3), (2, 1), (1, 1),
            **how)
    a_round(9, 200, 10, 5, (.5, .5), (.5, .5), (7, 3), (1, 1), (3, 1.5),
            (.5, .5), (2, .5), (.25, .25), (.125, .125), (2, .5),
            (.25, .25), (1, 1), **how)


def what_must_be_ignored(top=True):
    if top:
        # a round that found nothing to do (it shares the next dispatched
        # round's step, as in the program): no scheduler/deliver under it
        r = emit("scheduler/round", 140, 50, 1, step=9)
        emit("scheduler/admit", 140, 1, 1, parent=r, step=9)
        emit("scheduler/plan", 141.5, 40, 1, parent=r, step=9)
        # a round that began before the window opened
        r = emit("scheduler/round", -80, 70, 5, step=3)
        emit("scheduler/deliver", -20, 1, 1, parent=r, step=3)
    # another thread's spans, some with the loop's step on them
    emit("scheduler/deliver_out", 121, 90, 30, tid=WRITER, step=7)
    emit("executor_step/infer", 122, 100, 100, tid=WRITER, step=7)
    emit("engine/absorb", 160, 100, 100, tid=WRITER, step=8)
    emit("engine/fetch", 161, 100, 1, tid=WRITER, step=8)
    # the loop's thread outside any step: load and warm-up
    emit("executor_step/infer", 50, 400, 400)
    emit("engine/absorb", 60, 400, 400)
    # and between rounds
    emit("scheduler/wait", 300, 50, 0.1)


WORKED = {"loop_serial_ms.serve": 14.0, "loop_off_cpu_ms.serve": 7.25,
          "loop_unspanned_ms.serve": 2.0, "executor_launch_ms.serve": 3.0,
          "executor_release_ms.serve": 0.5, "absorb_ms.serve": 1.0}


@pytest.mark.parametrize("name", sorted(WORKED))
def test_reader_against_the_hand_worked_rounds(clean_ring, name):
    three_rounds()
    what_must_be_ignored()
    assert read(name) == pytest.approx(WORKED[name])
    assert read(name, kind="train") is None
    clean_ring.dropped = 1              # an overflowed ring is not a window
    assert read(name) is None


@pytest.mark.parametrize("name", sorted(WORKED))
def test_reader_on_a_program_without_the_round_or_the_thread_time(
        clean_ring, name):
    """The parent commit: the launch and the absorb read as they do on the
    change (their spans were there); the round's three and the release
    are left out."""
    three_rounds(top=False, thread_time=False)
    what_must_be_ignored(top=False)
    got = read(name)
    if name in ("executor_launch_ms.serve", "absorb_ms.serve"):
        assert got == pytest.approx(WORKED[name])
    else:
        assert got is None
    # a round, but spans without tdur: only the off-CPU time needs it
    clean_ring.clear()
    three_rounds(thread_time=False)
    got = read(name)
    if name == "loop_off_cpu_ms.serve":
        assert got is None
    else:
        assert got == pytest.approx(WORKED[name])
    clean_ring.clear()
    assert read(name) is None           # an empty ring: nothing, never 0


def test_off_cpu_time_is_read_off_a_clock_that_ticks(clean_ring):
    """The chip's host counts thread time in ticks of 10 ms: four rounds of
    8 ms with a 2 ms fetch each, 6 ms on the CPU each; three are charged a
    whole tick (tdur over dur), one nothing, the fetches nothing.  Round by
    round the off-CPU time reads -4, -4, -4 and 6 ms; the window's sum
    32 - 8 - 30 = -6 over 4 rounds is the sample: -1.5 ms (a window of
    hundreds of rounds comes out at the true 0 within a few ticks)."""
    for i, ticks in enumerate((10, 10, 0, 10)):
        r = emit("scheduler/round", 100 + 20 * i, 8, ticks, step=i)
        emit("engine/fetch", 103 + 20 * i, 2, 0, parent=r, step=i)
        emit("scheduler/deliver", 106 + 20 * i, 1, 0, parent=r, step=i)
    assert read("loop_off_cpu_ms.serve") == pytest.approx(-1.5)
    assert read("loop_serial_ms.serve") == pytest.approx(6.0)


def test_the_books_close_on_the_hand_worked_round(clean_ring):
    """One round alone: the serial part is the leaves' sum plus what no
    leaf covers, to the microsecond."""
    a_round(7, 100, 20, 8, (1, 1), (.5, .5), (15, 4), (1, 1), (5, 2.5),
            (1, 1), (3, 1), (.25, .25), (.5, .5), (6, .25), (1, 1), (1.5, 1))
    sched_host = manifest.load_reader("sched_host_ms.serve")(layer())
    feed = manifest.load_reader("feed_build_ms.serve")(layer())
    executor = manifest.load_reader("executor_host_ms.serve")(layer())
    assert (sched_host, feed, executor) == pytest.approx((3.0, 1.0, 1.25))
    assert sched_host + feed + executor + read("executor_launch_ms.serve") \
        + read("executor_release_ms.serve") + read("absorb_ms.serve") \
        + read("loop_unspanned_ms.serve") \
        == pytest.approx(read("loop_serial_ms.serve"))
    assert read("loop_off_cpu_ms.serve") <= read("loop_serial_ms.serve")


def test_admit_wait_is_the_windows_own_requests(clean_ring):
    """Per rid, admitted less submitted: 30 (submitted before the window),
    4 and 10 ms; the median 10.  Left out: a request admitted after the
    window closed, a preempted request's second admission, one whose
    submission the ring never saw."""
    def request(rid, submitted, admitted, **args):
        if submitted is not None:
            emit("request/submitted", submitted, 0, ph="i", tid=WRITER,
                 rid=rid)
        emit("request/admitted", admitted, 0, ph="i", rid=rid, **args)

    request(1, -20, 10, resumed=False)
    request(2, 100, 104, resumed=False)
    request(3, 200, 210, resumed=False)
    request(4, 300, 1100, resumed=False)
    request(5, 50, 400, resumed=True)
    request(6, None, 500, resumed=False)
    assert read("admit_wait_p50_ms") == pytest.approx(10.0)
    assert read("admit_wait_p50_ms", kind="train") is None
    clean_ring.clear()
    assert read("admit_wait_p50_ms") is None


def test_entries_list_the_serve_cells_and_their_layers():
    found = manifest.load()
    by = {m["name"]: m for m in found["per_layer"]}
    for name, of_layer in BOOKS.items():
        assert by[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": of_layer,
            "moves": "token_gap_p95_ms", "workloads": SERVE_CELLS}
    assert by["admit_wait_p50_ms"] == {
        "name": "admit_wait_p50_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "scheduler",
        "moves": "ttft_p50_ms", "workloads": ["base-serve-steady"]}
    # the old reader stays until a benchmark issue retires it
    assert "queue_wait_p50_ms" in by


def test_rehearsal_reads_the_books_in_a_work_directory_of_its_own(
        capsys, tmp_path, monkeypatch):
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    tracer().clear()
    rc, result, lines = rehearse(capsys, "base-serve-steady",
                                 seed=2**31 + 40, seconds=1.5, trace=1)
    assert rc == 0 and result["correct"] and result["metrics"] == {}
    read_ = {ln["rehearsal_reader"] for ln in lines
             if "rehearsal_reader" in ln}
    assert set(BOOKS) | {"admit_wait_p50_ms"} <= read_
    assert (tmp_path / "work" / "base-serve-steady").is_dir()
