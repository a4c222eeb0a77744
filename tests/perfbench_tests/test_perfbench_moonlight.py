"""The cell ``moonlight-serve-decode`` (ISSUE 32): its tiny rehearsal on
the CPU walks registry -> gateway -> HTTP -> scheduler ->
``PagedLMGenerator`` with the DeepSeek-V3 block (latent attention, one pool;
a routed-expert layer beside a shared expert) and comes out correct; the
float8 control and a program with the rotary key unrotated, the routed
scale dropped or the shared experts left out do not; the configuration
keeps every published number; the family's counts and the two new readers
do their arithmetic."""

import json
import os

import numpy as np
import pytest

from perfbench import manifest
from perfbench.families import deepseek_v3 as fam
from perfbench_helpers import compared, rehearse

CELL = "moonlight-serve-decode"
CONFIG = "moonlight-16b-a3b-l5"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cell_files():
    m = manifest.load()
    with open(manifest.config_path(m, CONFIG), encoding="utf-8") as f:
        cfg = json.load(f)
    with open(manifest.traffic_path("decode-heavy-flood"),
              encoding="utf-8") as f:
        return m, cfg, json.load(f)


def own_work_dir(tmp_path, more=None):
    """A work directory of this test's own (two rehearsals into one
    directory collide under several workers: PERF.md section 7)."""
    def patch(ctx):
        ctx.work_dir = lambda: str(tmp_path)
        if more is not None:
            more(ctx)
    return patch


def test_rehearsal_is_correct_and_reads_the_new_counters(capsys, tmp_path):
    rc, result, lines = rehearse(capsys, CELL, seed=2**31 + 32, seconds=1.5,
                                 trace=1, patch=own_work_dir(tmp_path))
    assert rc == 0 and result["correct"] is True
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    info = next(ln["info"] for ln in lines if "info" in ln)
    assert info["requests_failed"] == 0 and info["requests_ok"] > 0
    assert info["checked_requests"] >= 2 and info["checked_tokens"] > 0
    assert compared(lines)["logit_gap_max"]["ok"] is True
    routing = next(ln["routing"] for ln in lines if "routing" in ln)
    # every run prints the margin and the share beside what they set aside
    assert routing["set_aside_margin"] == fam.ref.SET_ASIDE
    assert routing["scored"] == 2 * routing["tokens"]   # two expert layers
    assert routing["tokens"] == info["checked_tokens"]
    assert 0 <= routing["set_aside"] < routing["tokens"]
    assert routing["set_aside_exempt"] == 0     # the rehearsal exempts none
    assert max(routing["gap_max_free"], routing["gap_set_aside_judged"]) \
        == compared(lines)["logit_gap_max"]["value"] <= routing["limit"]
    read = {ln["rehearsal_reader"] for ln in lines
            if "rehearsal_reader" in ln}
    due = {m["name"] for m in manifest.metrics_for(manifest.load(), CELL,
                                                   "per_layer")}
    assert {"kv_bytes_per_token.serve", "moe_pairs_per_step.serve",
            "kv_global_pool_fill.serve", "tokens_per_step",
            "step_wall_ms.serve", "feed_build_ms.serve",
            "fetch_wait_ms.serve", "sched_host_ms.serve"} <= read <= due
    assert {"latent_attn_roofline.serve", "expert_kernel_roofline.serve",
            "attn_time_share.serve", "expert_time_share.serve"} <= due


def test_the_float8_control_is_over_the_limit(capsys, tmp_path):
    rc, result, lines = rehearse(capsys, CELL, seed=11, control="float8",
                                 patch=own_work_dir(tmp_path))
    assert rc == 0 and result["correct"] is True
    info = next(ln["info"] for ln in lines if "info" in ln)
    limit = compared(lines)["logit_gap_max"]["limit"]
    assert info["control"]["precision"] == "float8"
    assert info["control"]["logit_gap_max"] > 5 * limit


def _unrotated_key(monkeypatch):
    """The token's one rotary key goes into the cache as it left the
    projection (queries still rotate)."""
    from paddle_tpu.fluid import layers

    real = layers.rotary_embedding
    monkeypatch.setattr(
        layers, "rotary_embedding",
        lambda x, *a, **k: x if x.shape[1] == 1 else real(x, *a, **k))


def _scale_dropped(monkeypatch):
    from paddle_tpu.fluid import layers

    real = layers.routed_experts
    monkeypatch.setattr(
        layers, "routed_experts",
        lambda *a, routed_scale=None, **k: real(*a, **k))


def _no_shared_experts(monkeypatch):
    """The shared experts' weights are loaded and their sum left out."""
    from paddle_tpu.fluid import layers

    real = layers.gated_ffn

    def without(x, d_inner, prefix, scope=None, **k):
        y = real(x, d_inner, prefix, scope=scope, **k)
        return layers.scale(y, scale=0.0) if scope == "moe/shared" else y

    monkeypatch.setattr(layers, "gated_ffn", without)


@pytest.mark.parametrize("fault", [_unrotated_key, _scale_dropped,
                                   _no_shared_experts],
                         ids=["rotary-key-unrotated", "routed-scale-dropped",
                              "shared-experts-left-out"])
def test_a_program_that_leaves_part_of_the_block_out_is_not_correct(
        fault, monkeypatch, capsys, tmp_path):
    fault(monkeypatch)
    rc, result, lines = rehearse(capsys, CELL, seed=7,
                                 patch=own_work_dir(tmp_path))
    assert rc == 0 and result["correct"] is False
    assert result["failed"] == 0        # every request answered, wrongly
    assert compared(lines)["logit_gap_max"]["ok"] is False


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file under the
    same name and value, but ``num_hidden_layers``: depth alone is cut,
    to the leading dense layer and the four expert layers after it."""
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Moonlight-16B-A3B")
    m, cfg, _ = cell_files()
    entry = manifest.config_of(m, CONFIG)
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers"}
    assert cfg["published"] == {"num_hidden_layers": 27}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (5, 1)
    assert fam.ref.sizes(cfg)["moe"] == [False, True, True, True, True]
    # every width as published; every layer whole
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == \
        (2048, 16, 512, 128, 64, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_routed_experts"], cfg["n_shared_experts"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == \
        (11264, 1408, 64, 2, 6, 2.446)
    assert cfg["vocab_size"] == cfg["end_id"] == 163840
    assert cfg["src_len"] + cfg["max_out_len"] == \
        cfg["max_position_embeddings"] == 8192
    for key in ("assumed", "deployment", "precision", "check_readings"):
        assert cfg[key]
    assert manifest.validate(m) == []


def test_this_familys_configuration_keeps_its_published_widths():
    """What ``test_perfbench_mimo.py::test_every_configuration_keeps_its_
    published_widths`` asserts of the families it knows, of this one (that
    test names the families it expects and is red since this one entered;
    PERF.md section 7): ``reduced`` is the entry's and names no width."""
    m, _, _ = cell_files()
    ours = []
    for c in m["configs"]:
        with open(manifest.config_path(m, c["name"]),
                  encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg and not key.endswith(("_dim", "_rank"))
        if cfg["family"] == "deepseek_v3":
            ours.append(c["name"])
    assert ours == [CONFIG]


def test_the_model_is_3093_million_parameters():
    _, cfg, _ = cell_files()
    shapes = fam.param_shapes(cfg, cfg["param_prefix"])
    count = lambda pick: sum(int(np.prod(s)) for n, s in shapes.items()     # noqa: E731
                             if pick(n))
    assert count(lambda n: True) == cfg["parameters"] == 3093455616
    assert count(lambda n: ".l0." in n) == 82973184
    assert count(lambda n: ".l3." in n) == 584847936
    assert count(lambda n: ".l3.moe.experts." in n) == 64 * 8650752
    assert count(lambda n: ".l3.moe.shared." in n) == 17301504
    assert count(lambda n: n.endswith(("emb.w", "head.w"))) == 671088640
    # the program's own parameters are exactly these
    from paddle_tpu.models import deepseek_v3 as M

    model = M.config_from_dict(fam.serving(cfg)["manifest"]["config"]
                               ["model"])
    assert M.param_shapes(model, cfg["param_prefix"]) == shapes
    assert (model.experts_held, model.n_routed_experts,
            model.first_expert) == (64, 64, 0)
    kinds = {n: fam.leaf_kind(n) for n in shapes}
    assert kinds["moon.l1.moe.router.bias"] == "bias"
    assert kinds["moon.l0.attn_norm.w"] == kinds["moon.l2.attn.kv_norm.w"] \
        == kinds["moon.out_norm.w"] == "ln_scale"
    assert kinds["moon.l3.moe.experts.down.w"] == "embedding"
    assert kinds["moon.l0.attn.kvb.w"] is None and kinds["moon.emb.w"] is None


def test_the_traffic_is_the_issues():
    _, cfg, mix = cell_files()
    assert (mix["kind"], mix["loop"], mix["clients"], mix["order"]) == \
        ("serve", "closed", 192, "fixed")
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.7, "min": 64, "max": 7168}
    assert mix["max_new"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["shared_prefix"] == {"share": 0.0, "length": 0}
    assert mix["burst"] == {"factor": 1, "every_s": 0, "for_s": 0}
    assert (mix["population"], mix["population_seed"], mix["ramp_s"],
            mix["check_sample"], mix["trace_seconds"]) == \
        (8192, 32, 20, 12, 6)
    assert mix["clients"] == 1.5 * cfg["n_slots"]
    assert mix["prompt_len"]["max"] <= cfg["src_len"]
    assert mix["max_new"]["max"] <= cfg["max_out_len"]


def test_the_familys_counts():
    _, cfg, _ = cell_files()
    # a decoded token at context 2000: 2000 rows of 576 bfloat16 numbers in
    # each of 5 layers, read once for all 16 heads; a head scores a row
    # over 576 columns and sums its leading 512
    ops, bytes_ = fam.latent_attention_need(cfg, [2000], [])
    assert bytes_ == 5 * 2000 * 576 * 2
    assert ops == 5 * 16 * 2000 * (576 + 512) * 2
    # a 300-token prompt: chunks of 256 and 44, causal; the rows up to a
    # chunk's end are read once a chunk
    ops, bytes_ = fam.latent_attention_need(cfg, [], [300])
    pairs = 256 * 257 / 2 + 44 * (256 + 45 / 2)
    assert ops == pytest.approx(5 * 16 * pairs * (576 + 512) * 2)
    assert bytes_ == 5 * (256 + 300) * 576 * 2
    # the ridge the cell's `why` speaks of: 30 operations a byte read
    ops, bytes_ = fam.latent_attention_need(cfg, [4096], [])
    assert ops / bytes_ == pytest.approx(16 * 1088 * 2 / (576 * 2))
    # expert products: a pair is three products; a touched expert's three
    # matrices are read once
    ops, bytes_ = fam.expert_need(cfg, pairs=10, experts_touched=4)
    assert ops == 10 * 3 * 2 * 2048 * 1408
    assert bytes_ == 4 * 3 * 2048 * 1408 * 2 + 10 * (3 * 2048 + 4 * 1408) * 2
    # all 256 expert matrices of a step: the 4.43 GB the cell's `why` names
    _, bytes_ = fam.expert_need(cfg, pairs=0, experts_touched=4 * 64)
    assert bytes_ == pytest.approx(4.43e9, rel=0.01)


def _layer(cfg, kernels, before=None, after=None):
    return {"kind": "serve", "cfg": cfg, "family": fam, "steps": 100,
            "window_s": 6.0, "trace": {"window_s": 6.0, "busy_s": 4.0,
                                       "kernels": kernels},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "before": {"engine": before} if before else {},
            "after": {"engine": after} if after else {},
            "records": [], "requests": {}, "t_open": 0.0, "t_close": 6.0}


def test_the_readers_on_a_canned_layer():
    _, cfg, _ = cell_files()
    k = lambda dims, s, n=10: {"operands": [("bf16", d) for d in dims],   # noqa: E731
                               "results": [], "calls": n, "seconds": s}
    kernels = [k([(2304, 2048), (64, 2048, 1408)], 0.6),
               k([(2304, 1408), (64, 1408, 2048)], 0.2),
               k([(128, 1, 16, 640), (10245, 256, 640)], 0.3),
               k([(8, 1, 1024, 640), (10245, 256, 640)], 0.1),
               k([(64, 8, 64), (983040, 512)], 9.0)]       # someone else's
    before = {"moe_pairs_here": 0, "experts_touched": 0,
              "kv_bytes_per_token": 6400, "global_pages_in_use": 10,
              "global_pages": 2048}
    after = {"moe_pairs_here": 230400, "experts_touched": 25600,
             "kv_bytes_per_token": 6400, "global_pages_in_use": 512,
             "global_pages": 2048}
    layer = _layer(cfg, kernels, before, after)
    read = lambda name: manifest.load_reader(name)(layer)   # noqa: E731
    assert read("kv_bytes_per_token.serve") == 6400.0
    assert read("expert_time_share.serve") == pytest.approx(20.0)
    assert read("attn_time_share.serve") == pytest.approx(10.0)
    assert read("moe_pairs_per_step.serve") == 2304.0
    assert read("kv_global_pool_fill.serve") == 25.0
    need = 25600 * 3 * 2048 * 1408 * 2 / 819e9         # weight reads bound it
    assert read("expert_kernel_roofline.serve") == pytest.approx(
        100 * need / 0.8, rel=0.05)
    layer["records"] = [{"id": 0, "times": [1.0, 2.0, 3.0], "sent": 0.5}]
    layer["requests"] = {0: {"prompt": [1] * 1000}}
    ops, bytes_ = fam.latent_attention_need(cfg, [1001, 1002], [1000])
    assert read("latent_attn_roofline.serve") == pytest.approx(
        100 * max(ops / 197e12, bytes_ / 819e9) / 0.4)
    # a program without the counters or the kernels (the parent commit),
    # another family's layer, a training cell: nothing, and no error
    bare = _layer(cfg, [])
    from perfbench.families import mimo_v2_flash

    other = dict(_layer(cfg, kernels, before, after), family=mimo_v2_flash)
    for name in ("latent_attn_roofline.serve", "kv_bytes_per_token.serve"):
        assert manifest.load_reader(name)(bare) is None
        assert manifest.load_reader(name)({"kind": "train"}) is None
    assert manifest.load_reader("latent_attn_roofline.serve")(other) is None
