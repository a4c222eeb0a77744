"""``prefill_rows_live_share.serve`` (ISSUE 29): a share from two snapshots
of the paged engine's counters, nothing where a program has no such
counters (the parent commit), and the entry that lists it."""

import pytest

from perfbench import manifest

NAME = "prefill_rows_live_share.serve"


def snapshot(fed, live, steps=0):
    return {"steps": steps, "engine": {
        "steps": steps, "steps_by_width": {8: steps},
        "tower_rows_fed": fed, "tower_rows_live": live}}


def layer(before, after, kind="serve"):
    return {"kind": kind, "before": before, "after": after, "steps": 10,
            "window_s": 1.0}


@pytest.mark.parametrize("before, after, share", [
    (snapshot(0, 0), snapshot(2560, 640, 10), 25.0),
    # cumulative since load: the window's delta, not the totals
    (snapshot(5120, 5000, 20), snapshot(7680, 5256, 30), 10.0),
    (snapshot(256, 0), snapshot(512, 0, 1), 0.0),
])
def test_share_is_the_delta_of_live_rows_over_rows_fed(before, after, share):
    assert manifest.load_reader(NAME)(layer(before, after)) == share


@pytest.mark.parametrize("what, value", [
    ("no engine block (the parent)", layer({"steps": 3}, {"steps": 9})),
    ("another engine's counters", layer(
        {"engine": {"steps": 1, "moe_pairs_here": 4}},
        {"engine": {"steps": 2, "moe_pairs_here": 8}})),
    ("the counters appear only after", layer({"steps": 0},
                                             snapshot(256, 10, 1))),
    ("no step in the window", layer(snapshot(256, 10), snapshot(256, 10))),
    ("a train cell", layer(snapshot(0, 0), snapshot(256, 10), kind="train")),
    ("an empty layer", {"kind": "serve"}),
])
def test_reads_nothing_and_does_not_raise(what, value):
    assert manifest.load_reader(NAME)(value) is None, what


def test_entry_lists_both_transformer_base_cells_and_moves_the_gap_tail():
    m = manifest.load()
    entry = next(e for e in m["per_layer"] if e["name"] == NAME)
    assert m["per_layer"][-1] is entry, "new entries go last"
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "paged engine",
        "moves": "token_gap_p95_ms",
        "workloads": ["base-serve-flood", "base-serve-steady"]}
    for cell in entry["workloads"]:
        assert entry in manifest.metrics_for(m, cell, "per_layer")
    assert entry not in manifest.metrics_for(m, "mimo-serve-mixed",
                                             "per_layer")


def test_the_engine_it_reads_reports_the_counters():
    """The reader and ``PagedTransformerGenerator.counters()`` agree on
    the names."""
    from paddle_tpu import fluid
    from paddle_tpu.serving import PagedTransformerGenerator

    gen = PagedTransformerGenerator(
        30, 30, n_layer=1, n_head=2, d_key=4, d_value=4, d_model=8,
        d_inner_hid=16, max_length=32, src_len=8, max_out_len=4,
        page_size=4, chunk_size=4, num_pages=32, param_prefix="tfr",
        place=fluid.CPUPlace())
    gen.init_params(seed=1)
    before = {"engine": gen.counters()}
    gen.open_slots(2)
    gen.admit_slot(1, [3, 4, 5, 6, 7], max_new=2)
    while gen._lanes[1].phase == "prefill":
        gen.lane_step()
    after = {"engine": gen.counters()}
    # 5 prompt tokens in two chunks of 4 at width 1: 5 of 8 rows
    assert manifest.load_reader(NAME)(layer(before, after)) == 62.5
