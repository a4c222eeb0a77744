"""``steps_ahead_share.serve`` (ISSUE 41): a share from two snapshots of
the paged engine's counters, nothing where a program has no such counter
(the parent commit, the other engine), the entry that lists it, and a
rehearsal of a cell it is due in that reads it."""

import pytest

from perfbench import manifest
from perfbench_helpers import rehearse

NAME = "steps_ahead_share.serve"
CELLS = ["mimo-serve-mixed", "moonlight-serve-decode"]


def snapshot(steps, ahead=None):
    engine = {"steps": steps, "moe_pairs_here": 7 * steps}
    if ahead is not None:
        engine["steps_ahead"] = ahead
    return {"steps": steps, "engine": engine}


def layer(before, after, kind="serve"):
    return {"kind": kind, "before": before, "after": after, "steps": 10,
            "window_s": 1.0}


@pytest.mark.parametrize("before, after, share", [
    (snapshot(0, 0), snapshot(200, 199), 99.5),
    # cumulative since load: the window's delta, not the totals
    (snapshot(100, 10), snapshot(300, 210), 100.0),
    (snapshot(40, 39), snapshot(50, 39), 0.0),
])
def test_share_is_the_delta_of_steps_ahead_over_steps(before, after, share):
    assert manifest.load_reader(NAME)(layer(before, after)) == share


@pytest.mark.parametrize("what, value", [
    ("no engine block", layer({"steps": 3}, {"steps": 9})),
    ("an engine without the counter (the parent)",
     layer(snapshot(1), snapshot(9))),
    ("the counter appears only after", layer(snapshot(1), snapshot(9, 8))),
    ("no step in the window", layer(snapshot(5, 4), snapshot(5, 4))),
    ("a train cell", layer(snapshot(0, 0), snapshot(9, 8), kind="train")),
    ("an empty layer", {"kind": "serve"}),
])
def test_reads_nothing_and_does_not_raise(what, value):
    assert manifest.load_reader(NAME)(value) is None, what


def test_entry_lists_the_two_cells_of_the_engine_that_looks_ahead():
    m = manifest.load()
    entry = next(e for e in m["per_layer"] if e["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "paged engine",
        "moves": "serve_tokens_per_s", "workloads": CELLS}
    for cell in CELLS:
        assert entry in manifest.metrics_for(m, cell, "per_layer")
    assert entry not in manifest.metrics_for(m, "base-serve-flood",
                                             "per_layer")


def test_a_rehearsal_reads_it_off_the_engines_counters(capsys, tmp_path):
    """The reader and ``PagedLMGenerator.counters()`` agree on the names,
    through the cell's own path: scheduler, ``sched.stats()["engine"]``,
    the window's two snapshots."""
    def patch(ctx):
        ctx.work_dir = lambda: str(tmp_path)

    rc, result, lines = rehearse(capsys, CELLS[0], seed=2**31 + 41,
                                 seconds=1.5, trace=1, patch=patch)
    assert rc == 0 and result["correct"] is True
    assert NAME in {ln["rehearsal_reader"] for ln in lines
                    if "rehearsal_reader" in ln}
