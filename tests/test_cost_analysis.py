"""The static cost analyzer (ISSUE 11): per-op cost-rule goldens
(through the op_test harness), the liveness byte-timeline planner with
exact peak coordinates, donation-aware aliasing, budget gating, the
recompile-hazard lint + bucket enumeration, the sharded comms
estimator, level-keyed preflight counters, and the serving wiring
(registry static costing, scheduler HBM budget, engine bucket set).
"""

import json
import os

import numpy as np
import pytest

from paddle_tpu import fluid
from paddle_tpu.fluid.analysis.cost import (CostEnv, get_chip, op_cost,
                                            plan_program, roofline)
from paddle_tpu.fluid.analysis.dataflow import ProgramView
from paddle_tpu.fluid.core.desc import OpDesc, VarDesc

from op_test import OpTestCase


# ---------------------------------------------------------------------------
# op-level cost-rule goldens (satellite: mul/matmul/conv2d/cache ops)
# ---------------------------------------------------------------------------

def test_mul_cost_golden():
    t = OpTestCase("mul", {
        "X": np.ones((4, 8), np.float32),
        "Y": np.ones((8, 16), np.float32)})
    # 2*M*N*K fused multiply-adds counted as 2 flops each
    t.check_cost(expect_flops=2.0 * 4 * 16 * 8,
                 expect_bytes_read=(4 * 8 + 8 * 16) * 4,
                 expect_bytes_written=4 * 16 * 4)


def test_matmul_cost_golden():
    t = OpTestCase("matmul", {
        "X": np.ones((2, 4, 8), np.float32),
        "Y": np.ones((2, 8, 16), np.float32)})
    t.check_cost(expect_flops=2.0 * (2 * 4 * 16) * 8,
                 expect_bytes_read=(2 * 4 * 8 + 2 * 8 * 16) * 4,
                 expect_bytes_written=2 * 4 * 16 * 4)


def test_conv2d_cost_golden():
    t = OpTestCase("conv2d", {
        "Input": np.ones((2, 3, 8, 8), np.float32),
        "Filter": np.ones((4, 3, 3, 3), np.float32)},
        attrs={"strides": [1, 1], "paddings": [1, 1]})
    out_elems = 2 * 4 * 8 * 8            # SAME-padded spatial extent
    t.check_cost(expect_flops=2.0 * out_elems * 3 * 3 * 3,
                 expect_bytes_read=(2 * 3 * 8 * 8 + 4 * 3 * 3 * 3) * 4,
                 expect_bytes_written=out_elems * 4)


def test_cache_write_cost_golden():
    """Out aliases Cache under donation: only the written slice and the
    index move — the cache tensor itself is free."""
    t = OpTestCase("cache_write", {
        "Cache": np.zeros((2, 16, 2, 4), np.float32),
        "Value": np.ones((2, 1, 2, 4), np.float32),
        "Index": np.zeros(1, np.int32)},
        attrs={"axis": 1})
    t.check_cost(expect_flops=0.0,
                 expect_bytes_read=2 * 1 * 2 * 4 * 4 + 4,
                 expect_bytes_written=2 * 1 * 2 * 4 * 4)


def test_quantized_paged_cache_write_int8_sidecar_golden():
    """The int8 pool write prices the quantize math AND the fp32 block
    scales (2 roles x B*C tokens x 4 bytes) the sidecar stores."""
    n_pages, n_layer, page, h, d = 4, 1, 4, 2, 4
    rows = n_pages * n_layer * 2
    t = OpTestCase("quantized_paged_cache_write", {
        "Pool": np.zeros((rows, page, h * d), np.int8),
        "Scales": np.zeros((1, rows, page), np.float32),
        "K": np.ones((2, 1, h, d), np.float32),
        "V": np.ones((2, 1, h, d), np.float32),
        "Pages": np.ones((2, 1), np.int32),
        "Offsets": np.zeros((2, 1), np.int32)},
        attrs={"layer": 0, "n_layer": n_layer},
        # skip the output-discovery probe: the emitter's functional
        # scatter needs jax arrays, and the cost rule only reads descs
        n_outputs={"Out": 1, "ScalesOut": 1})
    kv_elems = 2 * (2 * 1 * h * d)
    t.check_cost(
        expect_flops=6.0 * kv_elems,
        # K+V fp32 reads + page/offset vectors (never the donated pool)
        expect_bytes_read=kv_elems * 4 + 2 * 1 * 4 * 2,
        # int8 bytes land at 1 byte/elem + 2 fp32 scales per token
        expect_bytes_written=kv_elems * 1 + 2 * (2 * 1) * 4)


def test_ragged_decode_attention_cost_golden():
    """Reads price the page-table-addressable pool span (K+V at the
    pool's int8 itemsize) plus the fp32 scale sidecar rows."""
    n_pages, n_layer, page, h, d = 4, 1, 4, 2, 4
    rows = n_pages * n_layer * 2
    b, c, p = 2, 1, 2
    t = OpTestCase("ragged_decode_attention", {
        "Q": np.ones((b, c, h, d), np.float32),
        "Pool": np.zeros((rows, page, h * d), np.int8),
        "PageTable": np.ones((b, p), np.int32),
        "Lengths": np.ones(b, np.int32),
        "QBase": np.zeros(b, np.int32),
        "Scales": np.zeros((1, rows, page), np.float32)},
        attrs={"layer": 0, "n_layer": n_layer, "causal": True})
    lmax = p * page
    reads = (2.0 * b * p * page * h * d * 1      # int8 K+V pages
             + b * c * h * d * 4                 # Q
             + b * p * 4 + b * 4                 # table + lengths
             + 2.0 * b * p * page * 4)           # fp32 scale blocks
    t.check_cost(expect_flops=4.0 * b * c * h * lmax * d,
                 expect_bytes_read=reads)


def test_unregistered_op_conservative_default():
    main = fluid.Program()
    b = main.global_block().desc
    b.add_var(VarDesc("x", shape=[4, 4]))
    b.add_var(VarDesc("y", shape=[4, 4]))
    b.append_op(OpDesc("mystery_op", {"X": ["x"]}, {"Out": ["y"]}, {}))
    env = CostEnv(ProgramView(main.desc), 0)
    c = op_cost(env, b.ops[0])
    assert not c.registered
    assert c.flops == 16.0 and c.bytes_read == 64 and c.bytes_written == 64
    diag = main.analyze(level="cost", fetch_list=["y"])
    found = diag.by_code("unregistered-cost-rule")
    assert len(found) == 1 and "mystery_op" in found[0].message


def test_grad_rule_derived_from_base():
    """A *_grad op without its own rule prices at 2x the base rule's
    flops (vjp recompute) and counts as registered."""
    main = fluid.Program()
    b = main.global_block().desc
    b.add_var(VarDesc("x", shape=[4, 8]))
    b.add_var(VarDesc("w", shape=[8, 16]))
    b.add_var(VarDesc("out_g", shape=[4, 16]))
    b.add_var(VarDesc("x_g", shape=[4, 8]))
    b.append_op(OpDesc("mul_grad",
                       {"X": ["x"], "Y": ["w"], "Out@GRAD": ["out_g"]},
                       {"X@GRAD": ["x_g"]}, {}))
    env = CostEnv(ProgramView(main.desc), 0)
    c = op_cost(env, b.ops[0])
    assert c.registered
    assert c.flops == 2.0 * (2.0 * 4 * 16 * 8)


# ---------------------------------------------------------------------------
# peak-HBM planner: exact coordinates, aliasing, components
# ---------------------------------------------------------------------------

def _seeded_plan_program():
    """x(feed 512B) -> mul w(2048B persist) -> h(1024B) -> concat ->
    c(2048B) -> relu -> r (aliases c) -> reduce_sum -> out(4B).
    Hand-computed peak: 2048 + h + c = 5120 bytes at op#1."""
    main = fluid.Program()
    b = main.global_block().desc
    b.add_var(VarDesc("x", shape=[8, 16]))
    b.add_var(VarDesc("w", shape=[16, 32], persistable=True))
    b.add_var(VarDesc("h", shape=[8, 32]))
    b.add_var(VarDesc("c", shape=[8, 64]))
    b.add_var(VarDesc("r", shape=[8, 64]))
    b.add_var(VarDesc("out", shape=[1]))
    b.append_op(OpDesc("mul", {"X": ["x"], "Y": ["w"]}, {"Out": ["h"]},
                       {}))
    b.append_op(OpDesc("concat", {"X": ["h", "h"]}, {"Out": ["c"]},
                       {"axis": 1}))
    b.append_op(OpDesc("relu", {"X": ["c"]}, {"Out": ["r"]}, {}))
    b.append_op(OpDesc("reduce_sum", {"X": ["r"]}, {"Out": ["out"]}, {}))
    return main


def test_planner_peak_coordinates_exact():
    plan = plan_program(_seeded_plan_program())
    assert plan.peak_bytes == 5120
    assert (plan.peak_block, plan.peak_op) == (0, 1)
    assert plan.components == {"params": 2048, "kv_pool": 0,
                               "activations": 3072, "feeds": 0}
    # top contributor is the aliased c->r buffer (donation-aware reuse:
    # relu's output reuses concat's dying buffer, counted ONCE)
    top = plan.top(3)
    assert top[0]["var"] == "c→r" and top[0]["bytes"] == 2048
    assert {"var": "w", "bytes": 2048, "kind": "params",
            "live": None} in top
    # the byte timeline matches the hand walk
    bp = plan.blocks[0]
    assert bp.timeline == [1536, 3072, 2048, 2052]
    assert bp.peak_op == 1 and bp.peak_bytes == 3072


def test_planner_persistable_alias_is_free():
    """An output chained off a donated persistable (the cache_write /
    paged-pool idiom) shares the scope buffer — zero transient bytes."""
    main = fluid.Program()
    b = main.global_block().desc
    b.add_var(VarDesc("pool", shape=[4, 4], persistable=True))
    b.add_var(VarDesc("pool2", shape=[4, 4]))
    b.add_var(VarDesc("out", shape=[1]))
    b.append_op(OpDesc("relu", {"X": ["pool"]}, {"Out": ["pool2"]}, {}))
    b.append_op(OpDesc("reduce_sum", {"X": ["pool2"]},
                       {"Out": ["out"]}, {}))
    plan = plan_program(main)
    assert plan.peak_bytes == 64 + 4          # pool + out, pool2 free
    assert plan.components["activations"] == 4


def test_planner_kv_pool_component_and_sidecar():
    """The paged generator's pool AND its int8 fp32-scale sidecar land
    in the kv_pool component, matching kv_page_bytes * num_pages."""
    from paddle_tpu.serving.paged_decoder import (build_unified_program,
                                                  kv_page_bytes)
    from paddle_tpu.serving.decoder import _Cfg

    cfg = _Cfg(30, 30, 2, 2, 4, 4, 16, 32, 64)
    prog, _, _, _ = build_unified_program(
        cfg, src_len=8, max_out_len=8, page_size=4, num_pages=32,
        chunk_size=4, param_prefix="tk", kv_dtype="int8")
    plan = plan_program(prog, assume_batch=2)
    want = kv_page_bytes(2, 2, 4, 4, "int8") * 32
    assert plan.components["kv_pool"] == want
    assert plan.components["params"] > 0


def test_budget_finding_and_plint_exit(tmp_path, capsys):
    from paddle_tpu.tools import plint

    main = _seeded_plan_program()
    diag = main.analyze(level="cost", fetch_list=["out"],
                        options={"budget_bytes": 4096})
    over = diag.by_code("over-budget")
    assert len(over) == 1 and over[0].severity == "error"
    assert "params=2048" in over[0].message

    f = tmp_path / "prog.json"
    f.write_bytes(main.desc.serialize_to_string())
    assert plint.main([str(f), "--cost", "--budget", "4096",
                       "--fetch", "out"]) == 1
    capsys.readouterr()
    assert plint.main([str(f), "--cost", "--budget", "1000000",
                       "--fetch", "out"]) == 0
    capsys.readouterr()
    # --fail-on flips a warning-severity finding into exit 1
    b = main.global_block().desc
    b.add_var(VarDesc("m", shape=[1]))
    b.append_op(OpDesc("mystery_op", {"X": ["out"]}, {"Out": ["m"]}, {}))
    f.write_bytes(main.desc.serialize_to_string())
    assert plint.main([str(f), "--cost", "--fetch", "m"]) == 0
    capsys.readouterr()
    assert plint.main([str(f), "--cost", "--fetch", "m",
                       "--fail-on", "unregistered-cost-rule"]) == 1
    capsys.readouterr()


def test_book_program_cost_level_clean():
    """The mnist book program runs the whole cost family with zero
    errors and zero warnings — every op it uses has a cost rule."""
    from paddle_tpu.models import recognize_digits

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        img = fluid.layers.data("img", [1, 28, 28], "float32")
        label = fluid.layers.data("label", [1], "int64")
        _, avg_cost, acc = recognize_digits.conv_net(img, label)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(avg_cost)
    diag = main.analyze(level="cost", fetch_list=[avg_cost, acc],
                        options={"assume_batch": 64})
    assert not diag.has_errors, diag.render()
    assert not diag.warnings(), diag.render()
    rep = diag.reports["cost"]
    assert rep["memory"]["peak_bytes"] > rep["memory"]["components"][
        "params"]
    assert rep["roofline"]["total_flops"] > 1e8   # ~0.7 GFLOP at bs 64
    assert rep["roofline"]["step_time_s"] > 0


def test_roofline_chip_specs():
    spec = get_chip("v5e")
    assert spec.peak_flops == 197e12 and spec.hbm_bytes == 16 * 2 ** 30
    with pytest.raises(ValueError):
        get_chip("not-a-chip")
    main = _seeded_plan_program()
    fast = roofline(main, get_chip("v6e"))
    slow = roofline(main, get_chip("v2"))
    assert fast.step_time_s < slow.step_time_s
    assert fast.total_flops == slow.total_flops


# ---------------------------------------------------------------------------
# recompile-hazard lint + bucket enumeration
# ---------------------------------------------------------------------------

def test_recompile_value_shape_op_is_error():
    main = fluid.Program()
    b = main.global_block().desc
    for n in ("ids", "scores", "parents", "out_ids", "out_scores"):
        b.add_var(VarDesc(n, shape=[-1, 1]))
    b.append_op(OpDesc("beam_search_decode",
                       {"Ids": ["ids"], "Scores": ["scores"],
                        "ParentIdx": ["parents"]},
                       {"SentenceIds": ["out_ids"],
                        "SentenceScores": ["out_scores"]}, {}))
    diag = main.analyze(level="cost", fetch_list=["out_ids"])
    errs = diag.by_code("value-shape-op")
    assert len(errs) == 1 and errs[0].severity == "error"
    assert not diag.reports["recompile"]["closed"]


def test_recompile_dynamic_inner_dim_and_ragged():
    main = fluid.Program()
    b = main.global_block().desc
    b.add_var(VarDesc("x", shape=[-1, -1, 4]))
    b.add_var(VarDesc("s", shape=[-1, 1], lod_level=1))
    b.add_var(VarDesc("y", shape=[-1, 4]))
    b.append_op(OpDesc("reduce_sum", {"X": ["x"]}, {"Out": ["y"]},
                       {"dim": 1}))
    b.append_op(OpDesc("print", {"X": ["s"]}, {}, {}))
    diag = main.analyze(level="cost", fetch_list=["y"])
    assert diag.by_code("dynamic-inner-dim")
    assert diag.by_code("ragged-feed")


def test_bucket_enumeration_closed_product():
    from paddle_tpu.fluid.analysis.recompile import enumerate_buckets

    main = fluid.Program()
    b = main.global_block().desc
    b.add_var(VarDesc("x", shape=[-1, 8]))
    b.add_var(VarDesc("s", shape=[-1, 1], lod_level=1))
    b.add_var(VarDesc("y", shape=[-1, 8]))
    b.append_op(OpDesc("relu", {"X": ["x"]}, {"Out": ["y"]}, {}))
    b.append_op(OpDesc("print", {"X": ["s"]}, {}, {}))
    view = ProgramView(main.desc)
    buckets = enumerate_buckets(view, batch_buckets=(2, 4),
                                time_buckets=(8, 16))
    assert len(buckets) == 4
    assert all(e["closed"] for e in buckets)
    assert sorted({e["batch"] for e in buckets}) == [2, 4]
    # no declared buckets -> the axis is open
    open_set = enumerate_buckets(view)
    assert not all(e["closed"] for e in open_set)


def test_static_serving_program_single_bucket():
    """The paged decode-step program with a declared lane bucket is the
    zero-recompile steady state: exactly ONE closed signature."""
    from paddle_tpu.serving.paged_decoder import build_unified_program
    from paddle_tpu.serving.decoder import _Cfg

    prog, _, ids, _ = build_unified_program(
        _Cfg(30, 30, 2, 2, 4, 4, 16, 32, 64), src_len=8, max_out_len=8,
        page_size=4, num_pages=32, chunk_size=4, param_prefix="tb")
    diag = prog.analyze(level="cost", fetch_list=[ids],
                        options={"batch_buckets": (4,)})
    rep = diag.reports["recompile"]
    assert rep["closed"] and rep["bucket_count"] == 1
    assert rep["hazards"] == 0


def test_engine_bucket_set_and_static_estimate():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[6], dtype="float32")
        y = fluid.layers.fc(input=x, size=4)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    from paddle_tpu.serving.engine import InferenceEngine

    eng = InferenceEngine(program=fluid.io.prune_program(main, [y]),
                          feed_names=["x"], fetch_vars=[y], scope=scope,
                          place=fluid.CPUPlace(),
                          batch_buckets=(2, 8))
    buckets = eng.bucket_set()
    assert len(buckets) == 2
    assert [e["batch"] for e in buckets] == [2, 8]
    assert all(e["closed"] for e in buckets)
    # estimate scales with the assumed batch, params stay constant
    small = eng.static_hbm_estimate(batch=2)
    big = eng.static_hbm_estimate(batch=256)
    assert big.peak_bytes > small.peak_bytes
    assert big.components["params"] == small.components["params"]


# ---------------------------------------------------------------------------
# comms estimator
# ---------------------------------------------------------------------------

def _sharded_net():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [64], "float32")
        y = fluid.layers.data("y", [1], "float32")
        h = fluid.layers.fc(input=x, size=128, act="relu")
        pred = fluid.layers.fc(
            input=h, size=1,
            param_attr=fluid.ParamAttr(name="w2",
                                       sharding=["mp", None]))
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, loss


def test_comms_partial_sum_and_grad_sync():
    main, loss = _sharded_net()
    diag = main.analyze(level="cost", fetch_list=[loss],
                        options={"assume_batch": 32,
                                 "mesh_axes": {"dp": 8, "mp": 4},
                                 "dcn_axes": ["dp"]})
    rep = diag.reports["comms"]
    kinds = {(c["axis"], c["kind"]) for c in rep["collectives"]}
    # w2 is sharded over its contracted dim -> mp partial-sum allreduce
    assert ("mp", "allreduce(partial-sum)") in kinds
    # every param's gradient syncs over the batch axis, once per param
    grad_syncs = [c for c in rep["collectives"]
                  if c["kind"] == "allreduce(grad-sync)"]
    assert len(grad_syncs) == 4        # w1, b1, w2, b2
    w1 = 64 * 128 * 4
    assert rep["grad_sync_bytes"] == w1 + 128 * 4 + 128 * 1 * 4 + 4
    # dp is declared DCN: ring wire bytes = 2*(n-1)/n * payload
    dp = rep["per_axis"]["dp"]
    assert dp["tier"] == "dcn"
    assert dp["wire_bytes"] == pytest.approx(
        2.0 * 7 / 8 * rep["grad_sync_bytes"])
    assert rep["dcn_bytes"] == pytest.approx(dp["wire_bytes"])
    # the EQuARX framing: int8 payload + 1/32-block fp32 scales
    assert rep["int8_quantized_dcn_bytes"] == pytest.approx(
        rep["dcn_bytes"] / 4.0 * (1 + 4.0 / 32.0))
    assert any(f.code == "dcn-bound" for f in diag.warnings())


def test_comms_silent_on_unsharded_program():
    main = _seeded_plan_program()
    diag = main.analyze(level="cost", fetch_list=["out"])
    assert not [f for f in diag.findings if f.pass_name == "comms"]
    assert diag.reports["comms"]["collectives"] == []


# ---------------------------------------------------------------------------
# executor preflight: counters keyed by level (satellite)
# ---------------------------------------------------------------------------

def test_preflight_counters_key_on_level():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [4], "float32")
        h = fluid.layers.fc(input=x, size=8)
        loss = fluid.layers.mean(h)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.zeros((2, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss],
                validate="structural")
        # a cost run of the SAME program is a fresh analysis, not a
        # cache hit of the prior structural run
        exe.run(main, feed=feed, fetch_list=[loss], validate="cost")
        exe.run(main, feed=feed, fetch_list=[loss], validate="cost")
    st = exe.cache_stats()["validate"]
    assert st["runs"] == 2 and st["cached"] == 1
    assert st["by_level"]["structural"] == {"runs": 1, "cached": 0}
    assert st["by_level"]["cost"] == {"runs": 1, "cached": 1}


# ---------------------------------------------------------------------------
# memory_optimize: thin consumer of the byte timeline (satellite)
# ---------------------------------------------------------------------------

def test_memory_optimize_python_stats_carry_byte_timeline():
    from paddle_tpu.fluid.memory_optimization_transpiler import \
        _python_stats

    main = _seeded_plan_program()
    stats = _python_stats(main)
    # the native-compatible contract keys survive untouched
    for key in ("topo_order", "level", "live_range", "reuse_slot",
                "num_slots"):
        assert key in stats
    assert set(stats["live_range"]) == {"h", "c", "r", "out"}
    # plus the planner's byte view (one shared live-set derivation)
    assert stats["peak_transient_bytes"] == 3072
    assert stats["peak_op"] == 1
    assert stats["byte_timeline"] == [1536, 3072, 2048, 2052]


# ---------------------------------------------------------------------------
# serving wiring: registry static costing + scheduler budget
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_gen():
    from paddle_tpu.serving import PagedTransformerGenerator

    gen = PagedTransformerGenerator(
        30, 30, n_layer=2, n_head=2, d_key=4, d_value=4, d_model=16,
        d_inner_hid=32, max_length=64, src_len=8, max_out_len=8,
        page_size=4, chunk_size=4, num_pages=32, param_prefix="tcost",
        place=fluid.CPUPlace())
    gen.init_params(seed=7)
    return gen


def test_registry_costs_with_static_plan(tmp_path, small_gen):
    from paddle_tpu.serving.gateway import HBMBudgetError, ModelRegistry

    root = str(tmp_path)
    ModelRegistry.save_generator_artifact(small_gen, root, "m", "1")
    cfg = json.load(open(os.path.join(root, "m", "1",
                                      "gateway.json")))["config"]
    art = fluid.io.model_version_dir(root, "m", "1")
    cost = ModelRegistry._estimate_cost("generator", art, cfg)
    # the manifest-built desc and the live generator agree exactly
    from paddle_tpu.serving.paged_decoder import estimate_generator_hbm

    plan = small_gen.static_hbm_estimate()
    assert plan.peak_bytes == \
        estimate_generator_hbm(cfg).peak_bytes == cost
    # …and the plan covers more than the old artifact-byte heuristic:
    # pool + activations, not just weight bytes on disk
    assert plan.components["kv_pool"] == \
        small_gen.page_bytes * small_gen.num_pages
    assert plan.components["activations"] > 0

    reg = ModelRegistry(root=root, hbm_budget_bytes=int(cost * 1.5))
    reg.load("m", "1")
    ModelRegistry.save_generator_artifact(small_gen, root, "m", "2")
    with pytest.raises(HBMBudgetError) as ei:
        reg.load("m", "2")
    # the refusal message carries the per-component breakdown
    msg = str(ei.value)
    assert "params=" in msg and "kv_pool=" in msg


def test_scheduler_budget_consults_static_estimate(small_gen):
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              HBMBudgetError)

    plan = small_gen.static_hbm_estimate(assume_lanes=2)
    sched = ContinuousBatchingScheduler(
        hbm_budget_bytes=plan.peak_bytes + 64)
    sched.add_model("m@1", small_gen, 2)
    st = sched.stats()
    assert st["models"]["m@1"]["static_hbm_bytes"] == plan.peak_bytes
    assert st["hbm"]["committed_bytes"] == plan.peak_bytes
    assert not sched.can_admit_model(plan.peak_bytes)
    with pytest.raises(HBMBudgetError):
        sched.add_model("m@2", small_gen, 2)
