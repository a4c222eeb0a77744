"""Tensor-parallel sharded serving tests (ISSUE 17): token-for-token
greedy and beam parity of the mesh-sharded paged engine against the
single-chip decoder on 2- and 4-device meshes (conftest forces 8 virtual
CPU devices), fp32 and int8 KV pools, a speculative target+draft pair
with both halves sharded, the zero-recompiles-after-warmup contract,
predicted-vs-measured collective payloads, per-shard HBM admission (a
model the single-chip budgeter refuses is admitted when its static plan
is priced per-shard), the actionable ``HBMBudgetError`` mesh-axis
suggestion, and the ``shard``-labeled serving gauges."""

import re

import numpy as np
import pytest

from paddle_tpu.observability import registry
from paddle_tpu.serving import (ContinuousBatchingScheduler,
                                PagedTransformerGenerator, copy_weights)
from paddle_tpu.serving.paged_decoder import estimate_generator_hbm
from paddle_tpu.serving.scheduler import (HBMBudgetError,
                                          suggest_model_axis)
from paddle_tpu.serving.speculative import SpeculativeGenerator

V, NL, NH, DK, DM, DI = 37, 2, 4, 8, 32, 64
SRC, OUT, PS, CHUNK = 16, 10, 4, 4

KW = dict(src_vocab_size=V, trg_vocab_size=V, n_layer=NL, n_head=NH,
          d_key=DK, d_value=DK, d_model=DM, d_inner_hid=DI,
          max_length=64, src_len=SRC, max_out_len=OUT, page_size=PS,
          chunk_size=CHUNK)


def _sources(seed=3, n=3):
    rng = np.random.RandomState(seed)
    src = rng.randint(2, V, size=(n, SRC)).astype(np.int64)
    lens = rng.randint(SRC // 2, SRC + 1, size=n).astype(np.int32)
    lens[0] = SRC
    return src, lens


@pytest.fixture(scope="module")
def single_chip():
    """The unsharded baseline: generator, weights, and its greedy/beam
    outputs — every mesh variant must reproduce the token streams."""
    src, lens = _sources()
    ref = PagedTransformerGenerator(**KW)
    ref.init_params(seed=7)
    greedy = ref.greedy(src, lens)
    beams, scores = ref.beam(src, lens, beam_size=3)
    return ref, src, lens, greedy, beams, scores


def _sharded(n_model, **extra):
    return PagedTransformerGenerator(
        **dict(KW, **extra), mesh_axes={"batch": 1, "model": n_model})


# -- parity -------------------------------------------------------------------

@pytest.mark.parametrize("n_model", [2, 4])
def test_greedy_token_parity(single_chip, n_model):
    """The acceptance bar: the sharded engine is an implementation
    detail — greedy token streams match the single chip exactly."""
    ref, src, lens, g_ref, _, _ = single_chip
    sh = _sharded(n_model)
    copy_weights(ref.scope, sh.scope)
    assert np.array_equal(sh.greedy(src, lens), g_ref)
    plan = sh.shard_plan()
    assert plan["n_model_shards"] == n_model
    assert plan["pool_bytes_per_shard"] * n_model == \
        ref.shard_plan()["pool_bytes_per_shard"]


@pytest.mark.parametrize("n_model", [2, 4])
def test_beam_parity_and_zero_recompiles(single_chip, n_model):
    """Beam tokens are exact; beam SCORES carry the allreduce's fp32
    summation-order difference (row-sharded matmuls reduce partial sums
    in a different order), so they compare within float tolerance, not
    bitwise.  After the greedy+beam warmup, further decodes hit only
    cached executables: replicated int32 block tables keep every mesh
    shape on the compiled signatures."""
    ref, src, lens, _, b_ref, s_ref = single_chip
    sh = _sharded(n_model)
    copy_weights(ref.scope, sh.scope)
    sh.greedy(src, lens)                                       # warm
    beams, scores = sh.beam(src, lens, beam_size=3)
    assert np.array_equal(np.asarray(beams.data),
                          np.asarray(b_ref.data))
    assert np.allclose(scores, s_ref, rtol=0, atol=1e-4)
    misses0 = sh.cache_stats()["executable"]["misses"]
    sh.greedy(src, lens)
    sh.beam(src, lens, beam_size=3)
    assert sh.cache_stats()["executable"]["misses"] == misses0


def test_int8_kv_parity(single_chip):
    """int8 KV quantization shards bitwise: scales are a max over ALL
    heads, and a sharded max allreduce is exact — the int8 pool bytes
    on each shard equal the single chip's slice."""
    _, src, lens, _, _, _ = single_chip
    ref8 = PagedTransformerGenerator(**KW, kv_dtype="int8")
    ref8.init_params(seed=7)
    sh8 = _sharded(2, kv_dtype="int8")
    copy_weights(ref8.scope, sh8.scope)
    assert np.array_equal(sh8.greedy(src, lens), ref8.greedy(src, lens))


def test_speculative_pair_parity(single_chip):
    """Target AND draft sharded over the same mesh accept/reject the
    identical token prefix as the unsharded pair — the verify program's
    logit comparison is on argmax tokens, immune to low-bit drift."""
    _, src, lens, _, _, _ = single_chip

    def make(mesh_axes=None):
        extra = {} if mesh_axes is None else {"mesh_axes": mesh_axes}
        t = PagedTransformerGenerator(**KW, **extra)
        d = PagedTransformerGenerator(
            **dict(KW, param_prefix="draft"), **extra)
        return SpeculativeGenerator(t, d, k=3)

    sp_ref = make()
    sp_ref.init_params(seed=7)
    sp = make({"batch": 1, "model": 2})
    copy_weights(sp_ref.target.scope, sp.target.scope)
    copy_weights(sp_ref.draft.scope, sp.draft.scope)

    def run(spec):
        b = src.shape[0]
        spec.open_slots(b)
        for i in range(b):
            spec.admit_slot(i, src[i, :lens[i]], max_new=OUT,
                            decode={"draft": True})
        out = [[] for _ in range(b)]
        while any(l.phase not in ("hold", "idle")
                  for l in spec.target._lanes):
            for s, toks in spec.lane_step().items():
                out[s].extend(toks)
            for i, l in enumerate(spec.target._lanes):
                if l.phase == "decode" and len(out[i]) >= OUT:
                    l.phase = "hold"
        for i in range(b):
            spec.clear_slot(i)
        return [row[:OUT] for row in out]

    assert run(sp_ref) == run(sp)
    assert sp.cache_stats()["shard"]["n_model_shards"] == 2
    assert sp.cache_stats()["draft_shard"]["n_model_shards"] == 2


def test_shardability_check_rejects_indivisible():
    """A head count the mesh axis cannot divide fails at construction
    with the offending dimensions named, not inside the partitioner."""
    with pytest.raises(ValueError, match="n_head"):
        PagedTransformerGenerator(
            **dict(KW, n_head=3), mesh_axes={"model": 2})
    with pytest.raises(ValueError, match="d_inner_hid"):
        PagedTransformerGenerator(
            **dict(KW, d_inner_hid=66), mesh_axes={"model": 4})


# -- collectives --------------------------------------------------------------

def test_collective_report_predicted_matches_measured():
    """analysis/comms priced the sharded unified program from the desc;
    the partitioner's compiled HLO is ground truth.  Allreduce payload
    bytes must agree — a drift means the estimator's sharding rules no
    longer describe the real program."""
    g = _sharded(2)
    g.init_params(seed=1)
    g.open_slots(2)
    rep = g.collective_report()
    pred = rep["predicted"]["allreduce_payload_bytes"]
    assert rep["predicted"]["allreduce_count"] > 0
    meas = rep["measured"]["total_payload_bytes"]
    assert meas > 0
    assert rep["measured"]["mesh_axes"]["model"] == 2
    assert pred == pytest.approx(meas, rel=0.25)


def test_collective_report_unsharded_predicts_none():
    g = PagedTransformerGenerator(**KW)
    rep = g.collective_report()
    assert rep["predicted"]["allreduce_payload_bytes"] == 0
    assert rep["measured"] == {}


# -- per-shard HBM admission --------------------------------------------------

def test_suggest_model_axis():
    """Smallest power-of-two axis whose per-shard (params + kv_pool
    sharded, rest replicated) footprint fits; None when nothing shards
    or no considered axis helps."""
    comp = {"params": 1000, "kv_pool": 3000, "activations": 500,
            "feeds": 100}
    assert suggest_model_axis(comp, 2700) == 2
    assert suggest_model_axis(comp, 1650) == 4
    assert suggest_model_axis(comp, 500) is None        # fixed > avail
    assert suggest_model_axis({"activations": 900}, 100) is None
    assert suggest_model_axis({}, 10**9) is None
    # speculative plans prefix components; the suffix is what shards
    spec = {"target.params": 800, "draft.params": 200,
            "target.kv_pool": 2000, "target.activations": 100}
    assert suggest_model_axis(spec, 1600) == 2


def test_sharded_estimate_admits_where_single_chip_refused():
    """The acceptance scenario: a budget between the per-shard and the
    full-model static plan.  The single-chip add_model refuses with the
    mesh-axis hint; the SAME model rebuilt sharded is admitted."""
    full = estimate_generator_hbm(KW, assume_lanes=2).peak_bytes
    per_shard = estimate_generator_hbm(
        dict(KW, mesh_axes={"model": 4}), assume_lanes=2).peak_bytes
    assert per_shard < full
    budget = (full + per_shard) // 2

    sched = ContinuousBatchingScheduler(hbm_budget_bytes=budget)
    ref = PagedTransformerGenerator(**KW)
    ref.init_params(seed=0)
    with pytest.raises(HBMBudgetError) as err:
        sched.add_model("m", ref, n_slots=2)
    assert err.value.suggested_model_axis is not None
    assert "mesh_axes" in str(err.value)

    sh = _sharded(err.value.suggested_model_axis)
    sh.init_params(seed=0)
    sched.add_model("m", sh, n_slots=2)         # fits per-shard
    assert sched.stats()["models"]["m"]["static_hbm_bytes"] <= budget
    sched.run_until_idle()


def test_registry_refusal_carries_mesh_suggestion(tmp_path):
    """The gateway registry's refusal is actionable the same way: the
    error names the smallest mesh model-axis that would fit and records
    it on the exception."""
    from paddle_tpu.serving.gateway.registry import ModelRegistry

    gen = PagedTransformerGenerator(**KW)
    gen.init_params(seed=0)
    d = str(tmp_path / "m1")
    ModelRegistry.save_generator_artifact(gen, str(tmp_path), "m", "1")
    full = estimate_generator_hbm(KW, assume_lanes=4).peak_bytes
    # enough for the replicated activations/feeds plus a few shards of
    # params+pool, but well under the full plan — a shardable refusal
    reg = ModelRegistry(root=str(tmp_path),
                        hbm_budget_bytes=int(full * 0.6))
    with pytest.raises(HBMBudgetError) as err:
        reg.load("m", "1")
    assert err.value.suggested_model_axis is not None
    assert "model-axis" in str(err.value)
    del d


def test_artifact_records_mesh_axes(tmp_path):
    """A sharded generator's saved manifest carries its mesh shape, so
    a registry load rebuilds the same partitioning without a side
    channel."""
    from paddle_tpu.serving.gateway.registry import ModelRegistry

    gen = _sharded(2)
    gen.init_params(seed=0)
    ModelRegistry.save_generator_artifact(gen, str(tmp_path), "sh", "1")
    reg = ModelRegistry(root=str(tmp_path))
    key = reg.load("sh", "1")
    inst = reg.instance(key)
    assert dict(inst.mesh_axes)["model"] == 2
    assert inst.shard_plan()["n_model_shards"] == 2


# -- observability ------------------------------------------------------------

def test_shard_pool_gauge_per_shard_rows(single_chip):
    """A live scheduler serving a sharded model exposes one
    ``paddle_serving_shard_pool_bytes`` sample PER SHARD, each priced
    at the pool slice that shard actually holds."""
    ref, src, lens, _, _, _ = single_chip
    sh = _sharded(2)
    copy_weights(ref.scope, sh.scope)
    sched = ContinuousBatchingScheduler(sh, n_slots=2,
                                        max_new_tokens=4)
    try:
        text = registry().render_prometheus()
        rows = re.findall(
            r'^paddle_serving_shard_pool_bytes\{model="default",'
            r'shard="(\d)"\} (\S+)$', text, re.M)
        got = {s: float(v) for s, v in rows}
        per_shard = float(sh.shard_plan()["pool_bytes_per_shard"])
        assert got["0"] == got["1"] == per_shard
        stats = sched.stats()
        assert stats["kv"]["shard"]["mesh_axes"]["model"] == 2
    finally:
        sched.run_until_idle()
