"""shardprop (ISSUE 18): whole-program SPMD sharding inference.

Three bars, mirroring test_analysis.py's structure:

* **seeded defects** — one hand-built fixture per finding code
  (shard/resharding-hazard, shard/partial-sum-unreduced,
  shard/dp-grad-divergence, shard/replicated-giant,
  shard/unregistered-prop-rule), each detected with exact
  block/op#/slot coordinates;
* **differential gate** — the inferred collective graph must match
  ``Executor.collective_analysis`` (compiled-HLO ground truth)
  op-for-op: equal counts AND equal payload bytes per collective kind
  (rel_err 0.0), on 2- and 4-device virtual meshes, for the sharded
  unified decode step, the sharded speculative verify program, and a
  dp-sharded training program;
* **zero errors on real programs** — book-style nets, the transpiler's
  emitted programs, and the registry's manifest-built generators all
  propagate clean.
"""

import json

import numpy as np
import pytest

from paddle_tpu import fluid
from paddle_tpu.fluid.analysis import (LEVELS, ProgramValidationError,
                                       analyze_program)
from paddle_tpu.fluid.analysis.comms import WIRE_RULES, estimate_comms
from paddle_tpu.fluid.analysis.cost import COST_RULES
from paddle_tpu.fluid.analysis.shardprop import (PROP_RULES,
                                                 PROPAGATION_OPAQUE,
                                                 compare_collectives,
                                                 has_prop_rule,
                                                 infer_sharding)
from paddle_tpu.fluid.core.desc import OpDesc, VarDesc
from paddle_tpu.parallel import mesh as pmesh
from paddle_tpu.parallel.transpiler import DistributeTranspiler

KW = dict(src_vocab_size=37, trg_vocab_size=37, n_layer=2, n_head=4,
          d_key=8, d_value=8, d_model=32, d_inner_hid=64, max_length=64,
          src_len=16, max_out_len=10, page_size=4, chunk_size=4)


def _train_net():
    """fc -> fc -> cross_entropy -> mean, SGD-minimized."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=128, act="relu")
        pred = fluid.layers.fc(input=h, size=10, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred, label=y))
        opt_ops, pg = fluid.optimizer.SGD(learning_rate=0.01).minimize(
            loss)
    return main, startup, loss, opt_ops, pg


# ---------------------------------------------------------------------------
# wire-byte rules + per-kind subtotals (satellite: comms.py)
# ---------------------------------------------------------------------------

def test_wire_rules_golden():
    # ring all-reduce moves each byte out and back in: 2(n-1)/n
    assert WIRE_RULES["all-reduce"](1000.0, 4) == 1500.0
    assert WIRE_RULES["all-reduce"](1000.0, 2) == 1000.0
    # one-direction shuffles: (n-1)/n of the payload crosses the wire
    for kind in ("all-gather", "reduce-scatter", "all-to-all"):
        assert WIRE_RULES[kind](1000.0, 4) == 750.0, kind
        assert WIRE_RULES[kind](1000.0, 2) == 500.0, kind
    # an unknown/degenerate extent clamps to the assume-2 fallback the
    # whole estimator uses (shardprop never records extent-1 axes, so
    # the clamp is only ever the unknown-axis default)
    assert WIRE_RULES["all-reduce"](1000.0, 1) == 1000.0
    assert WIRE_RULES["all-gather"](1000.0, 1) == 500.0


def test_comms_report_per_kind_subtotals():
    """estimate_comms prices an inferred collective graph entry-for-entry
    and reports per-hlo-kind subtotals in to_dict()."""
    graph = [
        {"axis": "mp", "hlo_kind": "all-reduce", "payload_bytes": 100.0,
         "at": "block 0 op#1 (mul)", "grad": False},
        {"axis": "mp", "hlo_kind": "all-reduce", "payload_bytes": 100.0,
         "at": "block 0 op#5 (mul)", "grad": False},
        {"axis": "dp", "hlo_kind": "all-reduce", "payload_bytes": 40.0,
         "at": "block 0 op#9 (mul_grad)", "grad": True},
        {"axis": "mp", "hlo_kind": "all-gather", "payload_bytes": 64.0,
         "at": "block 0 op#3 (concat)", "grad": False},
    ]
    prog = fluid.Program()
    rep = estimate_comms(prog, options={
        "mesh_axes": {"mp": 2, "dp": 4}, "collectives": graph})
    d = rep.to_dict()
    assert d["per_kind"]["all-reduce"]["count"] == 3
    assert d["per_kind"]["all-reduce"]["payload_bytes"] == 240.0
    # 2*(100 @ mp=2 -> 100) + (40 @ dp=4 -> 60)
    assert d["per_kind"]["all-reduce"]["wire_bytes"] == 260.0
    assert d["per_kind"]["all-gather"] == {
        "count": 1, "payload_bytes": 64.0, "wire_bytes": 32.0}
    assert rep.grad_sync_bytes == 40.0
    assert len(rep.collectives) == 4


# ---------------------------------------------------------------------------
# seeded defects: exact coordinates per finding code
# ---------------------------------------------------------------------------

def test_resharding_hazard_exact_coordinates():
    """Same dim of an elementwise op's operands sharded over two
    different mesh axes: a forced repartition, priced as an
    all-gather."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8, 8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[8, 8], dtype="float32")
        out = fluid.layers.elementwise_add(x, y)
    b = main.global_block().desc
    b.vars["x"].sharding = [None, "mp", None]
    b.vars["y"].sharding = [None, "np", None]
    res = infer_sharding(main, options={"mesh_axes": {"mp": 2, "np": 2}},
                         fetch=[out.name])
    found = [f for f in res.findings if f.code == "resharding-hazard"]
    assert len(found) == 1, [f.render() for f in res.findings]
    f = found[0]
    assert f.severity == "error"
    assert (f.block, f.op, f.op_type) == (0, 0, "elementwise_add")
    assert f.slot == "Y#0" and f.var == "y"
    # ...and the repartition is on the collective bill
    gathers = [c for c in res.collectives
               if c["hlo_kind"] == "all-gather"]
    assert len(gathers) == 1 and gathers[0]["op"] == 0


def test_partial_sum_unreduced_exact_coordinates():
    """A row-parallel matmul's output is a partial sum over the model
    axis; fetching it without the all-reduce means every shard returns
    a different value."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=4, bias_attr=False)
    b = main.global_block().desc
    w = [n for n in b.vars if n.endswith(".w_0")][0]
    b.vars[w].sharding = ["mp", None]          # contracted dim sharded
    res = infer_sharding(main, options={"mesh_axes": {"mp": 2}},
                         fetch=[h.name])
    found = [f for f in res.findings
             if f.code == "partial-sum-unreduced"]
    assert len(found) == 1, [f.render() for f in res.findings]
    f = found[0]
    assert f.severity == "error"
    assert (f.block, f.op, f.op_type) == (0, 0, "mul")
    assert f.var == h.name
    # un-fetched, the partial is legal: its all-reduce gets priced
    res2 = infer_sharding(main, options={"mesh_axes": {"mp": 2}})
    assert not [f for f in res2.findings if f.severity == "error"]
    assert [c["hlo_kind"] for c in res2.collectives] == ["all-reduce"]
    assert res2.collectives[0]["op"] == 0


def test_dp_grad_divergence_exact_coordinates():
    """A gradient declared to stay dp-sharded reaches the optimizer:
    each replica would apply a different update."""
    main, _, loss, opt_ops, pg = _train_net()
    b = main.global_block().desc
    p = pg[0][0].name
    b.vars[p + "@GRAD"].sharding = ["dp", None]
    res = infer_sharding(main, options={"mesh_axes": {"dp": 2},
                                        "assume_batch": 8},
                         fetch=[loss.name])
    found = [f for f in res.findings if f.code == "dp-grad-divergence"]
    assert len(found) == 1, [f.render() for f in res.findings]
    f = found[0]
    sgd = [i for i, op in enumerate(b.ops)
           if op.type == "sgd" and op.inputs.get("Param") == [p]]
    assert (f.block, f.op, f.op_type) == (0, sgd[0], "sgd")
    assert f.severity == "error" and f.var == p and f.slot == "Grad#0"


def test_replicated_giant_threshold_and_coordinates():
    main, _, loss, _, _ = _train_net()
    res = infer_sharding(main, options={"mesh_axes": {"model": 2},
                                        "replicated_giant_bytes": 10_000},
                         fetch=[loss.name])
    found = [f for f in res.findings if f.code == "replicated-giant"]
    # only fc_0's [64,128] fp32 weight (32 KiB) crosses the threshold
    assert len(found) == 1, [f.render() for f in found]
    f = found[0]
    assert f.severity == "error" and f.block == 0
    assert f.var.endswith(".w_0") and "MiB" in f.message
    # sharding that weight on the model axis clears it
    b = main.global_block().desc
    b.vars[f.var].sharding = [None, "model"]
    res2 = infer_sharding(main, options={
        "mesh_axes": {"model": 2}, "replicated_giant_bytes": 10_000},
        fetch=[loss.name])
    assert not [x for x in res2.findings
                if x.code == "replicated-giant"]
    # threshold None disables the check entirely
    res3 = infer_sharding(main, options={
        "mesh_axes": {"model": 2}, "replicated_giant_bytes": None},
        fetch=[loss.name])
    assert not [x for x in res3.findings
                if x.code == "replicated-giant"]


def test_unregistered_prop_rule_warns_once():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8, 8], dtype="float32")
    b = main.global_block().desc
    b.vars["x"].sharding = [None, "mp", None]
    for i in range(2):
        b.add_var(VarDesc(f"frob_{i}", shape=[-1, 8, 8],
                          dtype="float32"))
        b.append_op(OpDesc("frobnicate", {"X": ["x"]},
                           {"Out": [f"frob_{i}"]}, {}))
    res = infer_sharding(main, options={"mesh_axes": {"mp": 2}})
    found = [f for f in res.findings
             if f.code == "unregistered-prop-rule"]
    assert len(found) == 1                    # once per op type
    assert found[0].severity == "warning"
    assert (found[0].block, found[0].op) == (0, 0)
    assert "frobnicate" in found[0].message
    # outputs degrade to replicated, not to garbage
    assert res.var_specs[(0, "frob_0")] == (None, None, None)


# ---------------------------------------------------------------------------
# propagation-rule sweep: cost-model coverage implies shardprop coverage
# ---------------------------------------------------------------------------

def test_every_cost_ruled_op_has_a_prop_rule():
    """Any op family important enough for a cost rule must either
    propagate shardings or be explicitly listed propagation-opaque —
    an unlisted gap silently drops layouts (the WARNING fixture
    above)."""
    missing = sorted(k for k in COST_RULES if not has_prop_rule(k))
    assert not missing, (
        f"{len(missing)} cost-ruled op type(s) have no sharding "
        f"propagation rule and are not PROPAGATION_OPAQUE: {missing}")
    # the opaque list is for ops whose outputs genuinely carry no
    # layout (metrics); it must not silently swallow compute ops
    assert PROPAGATION_OPAQUE <= {"accuracy"} | set(PROP_RULES) or \
        all(op not in PROP_RULES for op in PROPAGATION_OPAQUE)


def test_grad_ops_covered_by_generic_rule():
    assert has_prop_rule("mul_grad")
    assert has_prop_rule("layer_norm_grad")
    assert not has_prop_rule("frobnicate")


# ---------------------------------------------------------------------------
# sharding_pass: producer+consumer coordinates, deduped (satellite)
# ---------------------------------------------------------------------------

def test_producer_consumer_conflict_names_both_coordinates():
    main, _, loss, _, pg = _train_net()
    b = main.global_block().desc
    p = pg[0][0].name
    b.vars[p].sharding = ["mp", None]
    b.vars[p + "@GRAD"].sharding = [None, "mp"]
    diag = analyze_program(main, passes=("sharding",),
                           level="structural", fetch=[loss.name])
    found = diag.by_code("producer-consumer-conflict")
    assert len(found) == 1
    f = found[0]
    assert "(producer block" in f.message
    assert "(consumer block" in f.message
    assert f"op#{f.op}" in f.message          # consumer op named inline


def test_producer_consumer_conflict_dedupes_repeats():
    main = fluid.Program()
    b = main.global_block().desc
    b.add_var(VarDesc("a", shape=[4, 4], dtype="float32"))
    b.add_var(VarDesc("c", shape=[4, 4], dtype="float32"))
    b.vars["a"].sharding = ["mp", None]
    b.vars["c"].sharding = [None, "mp"]
    for _ in range(3):                        # while bodies clone ops
        b.append_op(OpDesc("assign", {"X": ["a"]}, {"Out": ["c"]}, {}))
    diag = analyze_program(main, passes=("sharding",),
                           level="structural")
    assert len(diag.by_code("producer-consumer-conflict")) == 1


# ---------------------------------------------------------------------------
# end-to-end wiring: LEVELS, comms consumption, plint exit codes
# ---------------------------------------------------------------------------

def test_shard_level_runs_and_comms_prices_inferred_graph():
    assert "shardprop" in LEVELS["shard"] and "comms" in LEVELS["shard"]
    main, _, loss, _, _ = _train_net()
    diag = main.analyze(level="shard", fetch_list=[loss],
                        options={"mesh_axes": {"dp": 2},
                                 "assume_batch": 8})
    assert not diag.has_errors, diag.render()
    sp = diag.reports["shardprop"]
    cm = diag.reports["comms"]
    # the comms pass priced shardprop's graph, not its heuristic scan
    assert cm["per_kind"]["all-reduce"]["count"] == \
        sp["per_kind"]["all-reduce"]["count"]
    assert cm["per_kind"]["all-reduce"]["payload_bytes"] == \
        sp["per_kind"]["all-reduce"]["payload_bytes"]
    assert cm["grad_sync_bytes"] > 0          # dW/db syncs flagged grad


def test_plint_shard_exit_codes(tmp_path, capsys):
    from paddle_tpu.tools import plint

    # clean dp training program -> 0
    main, _, loss, _, _ = _train_net()
    good = tmp_path / "good.json"
    good.write_bytes(main.desc.serialize_to_string())
    rc = plint.main([str(good), "--shard", "--mesh-axis", "dp=2",
                     "--assume-batch", "8", "--fetch", loss.name])
    capsys.readouterr()
    assert rc == 0

    # seeded resharding hazard -> 1, with coordinates in the output
    bad = fluid.Program()
    with fluid.program_guard(bad, fluid.Program()), \
            fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8, 8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[8, 8], dtype="float32")
        fluid.layers.elementwise_add(x, y)
    bb = bad.global_block().desc
    bb.vars["x"].sharding = [None, "mp", None]
    bb.vars["y"].sharding = [None, "np", None]
    badp = tmp_path / "bad.json"
    badp.write_bytes(bad.desc.serialize_to_string())
    rc = plint.main([str(badp), "--shard", "--mesh-axis", "mp=2",
                     "--mesh-axis", "np=2", "--json"])
    out = capsys.readouterr().out
    assert rc == 1
    payload = json.loads(out)
    hits = [f for f in payload["findings"]
            if f["code"] == "resharding-hazard"]
    assert hits and hits[0]["block"] == 0 and hits[0]["op"] == 0

    # giant threshold flag reaches the pass
    gp = tmp_path / "giant.json"
    gp.write_bytes(main.desc.serialize_to_string())
    rc = plint.main([str(gp), "--shard", "--mesh-axis", "model=2",
                     "--replicated-giant-bytes", "10000"])
    capsys.readouterr()
    assert rc == 1


# ---------------------------------------------------------------------------
# transpiler + registry integration
# ---------------------------------------------------------------------------

def test_transpiler_verifies_emitted_programs():
    main, _, loss, opt_ops, pg = _train_net()
    t = DistributeTranspiler()
    t.transpile(optimize_ops=opt_ops, params_grads=pg, trainers=2,
                program=main, mesh_axes={"dp": 2})
    assert t.get_trainer_program() is main
    assert not t.get_pserver_program().global_block().desc.ops


def test_transpiler_refuses_conflicting_plan():
    main, _, loss, opt_ops, pg = _train_net()
    b = main.global_block().desc
    p = pg[0][0].name
    b.vars[p].sharding = ["mp", None]
    b.vars[p + "@GRAD"].sharding = [None, "mp"]
    t = DistributeTranspiler()
    with pytest.raises(ProgramValidationError) as ei:
        t.transpile(optimize_ops=opt_ops, params_grads=pg, trainers=2,
                    program=main, mesh_axes={"dp": 2, "mp": 2})
    assert "producer-consumer-conflict" in str(ei.value)


def test_registry_shard_preflight(monkeypatch, tmp_path):
    from paddle_tpu.serving.gateway import registry as reg

    cfg = dict(KW, mesh_axes={"batch": 1, "model": 2})
    # a well-sharded manifest passes (no exception)
    reg.ModelRegistry._shard_preflight("generator", cfg)
    # engines and unsharded generators skip the preflight entirely
    reg.ModelRegistry._shard_preflight("engine", {"anything": 1})
    reg.ModelRegistry._shard_preflight("generator", dict(KW))

    # a manifest whose program fails propagation is refused
    bad = fluid.Program()
    with fluid.program_guard(bad, fluid.Program()), \
            fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=4, bias_attr=False)
    bb = bad.global_block().desc
    w = [n for n in bb.vars if n.endswith(".w_0")][0]
    bb.vars[w].sharding = ["model", None]
    bb.vars[h.name].persistable = True        # partial lands persistable
    monkeypatch.setattr(reg, "build_manifest_program",
                        lambda config, **kw: (bad, {"model": 2}))
    with pytest.raises(ProgramValidationError) as ei:
        reg.ModelRegistry._shard_preflight("generator", cfg)
    assert "partial-sum-unreduced" in str(ei.value)


# ---------------------------------------------------------------------------
# the differential gate: inferred graph == compiled-HLO ground truth
# ---------------------------------------------------------------------------

def test_collective_scan_reads_tpu_and_tuple_hlo():
    """The measured side must read what real compilers print: TPU
    layouts carry tiling / memory-space annotations, collectives come as
    async -start/-done pairs, and a combined gradient all-reduce is one
    tuple whose long shape XLA numbers with /*index=N*/ comments.  (The
    first four-chip run, PR 21, scanned a dp=4 step and found nothing.)"""
    hlo = """
  %ars.3 = f32[512,2048]{1,0:T(8,128)} all-reduce-start(f32[512,2048]{1,0:T(8,128)} %f), channel_id=5, to_apply=%add
  %ard.3 = f32[512,2048]{1,0:T(8,128)} all-reduce-done(%ars.3)
  %ar.42 = (f32[64,16]{1,0}, f32[9,16]{1,0}, f32[64,16]{1,0}, f32[9,16]{1,0}, f32[16,16]{1,0}, /*index=5*/f32[16,16]{1,0}) all-reduce(%a, %b), channel_id=1
  %gte.1 = f32[64,16]{1,0} get-tuple-element(%ar.42), index=0
  %ag = bf16[4,128]{1,0:T(4,128)(2,1)S(1)} all-gather(bf16[1,128]{1,0:T(2,128)(2,1)} %x), dimensions={0}
  ROOT %t = (f32[2]{0}) tuple(%all-reduce.1)
"""
    per_kind, total = fluid.Executor.collectives_in_hlo(hlo)
    tuple_bytes = 4 * (2 * 64 * 16 + 2 * 9 * 16 + 2 * 16 * 16)
    assert per_kind == {
        "all-reduce": {"count": 2,
                       "payload_bytes": 4.0 * 512 * 2048 + tuple_bytes},
        "all-gather": {"count": 1, "payload_bytes": 2.0 * 4 * 128}}
    assert total == sum(k["payload_bytes"] for k in per_kind.values())


def _assert_differential(tag, prog, mesh_axes, feed, fetch_list, exe,
                         scope, mesh, mode, assume_batch):
    with fluid.scope_guard(scope), pmesh.mesh_guard(mesh):
        meas = exe.collective_analysis(prog, feed=feed,
                                       fetch_list=fetch_list, mode=mode)
    pred = infer_sharding(
        prog, options={"mesh_axes": mesh_axes,
                       "assume_batch": assume_batch},
        fetch=[getattr(v, "name", v) for v in fetch_list])
    errs = [f for f in pred.findings if f.severity == "error"]
    assert not errs, f"{tag}: " + "; ".join(f.render() for f in errs)
    cmp = compare_collectives(pred.per_kind(), meas["per_kind"])
    assert cmp["match"] and cmp["rel_err"] == 0.0, (
        f"{tag}: rel_err={cmp['rel_err']}\n"
        f"  predicted: {json.dumps(pred.per_kind(), sort_keys=True)}\n"
        f"  measured:  {json.dumps(meas['per_kind'], sort_keys=True)}")


@pytest.mark.parametrize("n", [2, 4])
def test_differential_sharded_decode_step(n):
    from paddle_tpu.serving.paged_decoder import PagedTransformerGenerator

    ma = {"batch": 1, "model": n}
    g = PagedTransformerGenerator(**KW, mesh_axes=ma)
    g.init_params(seed=1)
    g.open_slots(2)
    prog, _, next_ids, _ = g._unified
    feed = g._step_feed()      # the tower at its widest: the lane count
    assert feed["pf_word"].shape[0] == feed["trg_word"].shape[0] == 2
    _assert_differential(f"decode model={n}", prog, ma, feed,
                         [next_ids], g.exe, g.scope, g.mesh, "infer", 2)


@pytest.mark.parametrize("n", [2, 4])
def test_differential_speculative_verify(n):
    from paddle_tpu.serving.paged_decoder import PagedTransformerGenerator
    from paddle_tpu.serving.speculative import SpeculativeGenerator

    ma = {"batch": 1, "model": n}
    tgt = PagedTransformerGenerator(**KW, mesh_axes=ma)
    drf = PagedTransformerGenerator(**KW, mesh_axes=ma,
                                    param_prefix="draft")
    sg = SpeculativeGenerator(tgt, drf, k=4)
    sg.init_params(seed=1)
    sg.open_slots(2)
    vprog, _, vnext, _ = sg._verify
    feed = tgt._prefill_arrays(width=2)   # the tower at its widest
    feed.update(tgt._decode_arrays(sg.verify_tokens))
    feed["logit_mask"] = sg._vmask
    _assert_differential(f"verify model={n}", vprog, ma, feed, [vnext],
                         tgt.exe, tgt.scope, tgt.mesh, "infer", 2)


@pytest.mark.parametrize("n", [2, 4])
def test_differential_dp_training(n):
    main, startup, loss, opt_ops, pg = _train_net()
    t = DistributeTranspiler()
    t.transpile(optimize_ops=opt_ops, params_grads=pg, trainers=n,
                program=main, mesh_axes={"dp": n})
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    mesh = pmesh.make_mesh({"dp": n})
    rng = np.random.RandomState(7)
    feed = {"x": rng.rand(8, 64).astype("float32"),
            "y": rng.randint(0, 10, (8, 1)).astype("int64")}
    _assert_differential(f"train dp={n}", t.get_trainer_program(),
                         {"dp": n}, feed, [loss], exe, scope, mesh,
                         "train", 8)
