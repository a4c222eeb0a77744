"""The cell ``trinity-serve-long`` (ISSUE 42): its tiny rehearsal on the CPU
walks registry -> gateway -> HTTP -> scheduler -> ``PagedLMGenerator`` with
Trinity-Mini's block (``afmoe``: QK-normed, gated attention with a rotary
window beside a position-free global layer, four norms a layer, routed
experts beside a shared one) and comes out correct, reading the engine's
two prefill counters; the float8 control does not; the configuration keeps
every published number; the traffic is the issue's; the family's counts and
the four new readers do their arithmetic."""

import json
import os

import numpy as np
import pytest

from perfbench import manifest
from perfbench.families import afmoe as fam
from perfbench_helpers import compared, rehearse

CELL = "trinity-serve-long"
CONFIG = "trinity-mini-ep8-l8"
TRAFFIC = "long-prompt-flood"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ("window_attn_roofline.serve", "global_attn_roofline.serve",
               "window_attn_time_share.serve",
               "prefill_tokens_per_step.serve")


def cell_files():
    m = manifest.load()
    with open(manifest.config_path(m, CONFIG), encoding="utf-8") as f:
        cfg = json.load(f)
    with open(manifest.traffic_path(TRAFFIC), encoding="utf-8") as f:
        return m, cfg, json.load(f)


def own_work_dir(tmp_path):
    """A work directory of this test's own (two rehearsals into one
    directory collide under several workers: PERF.md section 7)."""
    def patch(ctx):
        ctx.work_dir = lambda: str(tmp_path)
    return patch


def test_rehearsal_is_correct_and_reads_the_new_counters(capsys, tmp_path):
    rc, result, lines = rehearse(capsys, CELL, seed=2**31 + 42, seconds=1.5,
                                 trace=1, patch=own_work_dir(tmp_path))
    assert rc == 0 and result["correct"] is True
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    info = next(ln["info"] for ln in lines if "info" in ln)
    assert info["requests_failed"] == 0 and info["requests_ok"] > 0
    assert info["checked_requests"] >= 2 and info["checked_tokens"] > 0
    assert compared(lines)["logit_gap_max"]["ok"] is True
    routing = next(ln["routing"] for ln in lines if "routing" in ln)
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
    # the counters: the new one, and those the cell shares with the other
    # two decoder-only cells; the device's readers need a device trace
    assert {"prefill_tokens_per_step.serve", "kv_bytes_per_token.serve",
            "window_pages_recycled_per_step.serve",
            "moe_pairs_per_step.serve", "kv_global_pool_fill.serve",
            "tokens_per_step", "step_wall_ms.serve",
            "fetch_wait_ms.serve", "deliver_lag_ms.serve"} <= read <= due
    assert set(NEW_READERS) | {"mixed_attn_roofline.serve",
                               "attn_time_share.serve",
                               "expert_kernel_roofline.serve"} <= due
    # left as they are, for a `benchmark` PR: the entries whose lists an
    # accepted test pins to the cells they had (the loop's books,
    # test_perfbench_loop_books.py; steps_ahead_share.serve,
    # test_perfbench_steps_ahead.py); the engine counts steps ahead here
    # as everywhere
    assert not {"loop_serial_ms.serve", "steps_ahead_share.serve"} & due


def test_the_float8_control_is_over_the_limit(capsys, tmp_path):
    rc, result, lines = rehearse(capsys, CELL, seed=11, control="float8",
                                 patch=own_work_dir(tmp_path))
    assert rc == 0 and result["correct"] is True
    info = next(ln["info"] for ln in lines if "info" in ln)
    limit = compared(lines)["logit_gap_max"]["limit"]
    assert info["control"]["precision"] == "float8"
    assert info["control"]["logit_gap_max"] > 5 * limit


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file under the
    same name and value, but the two the cut changes: depth (the first
    eight layers) and the experts held (16 of the 128 the router scores)."""
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    m, cfg, _ = cell_files()
    entry = manifest.config_of(m, CONFIG)
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "num_experts"]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "num_experts"}
    assert cfg["published"] == {"num_hidden_layers": 32, "num_experts": 128}
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["first_expert"]) == (8, 2, 16, 0)
    z = fam.ref.sizes(cfg)
    assert z["window"] == [True, True, True, False] * 2
    assert z["moe"] == [False, False] + [True] * 6
    assert (z["experts"], z["held"], z["top_k"], z["shared"]) == \
        (128, 16, 8, 1)
    # every width as published; the vocabulary whole
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"]) == (2048, 32, 4, 128, 2048)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["num_shared_experts"],
            cfg["route_scale"], cfg["rope_theta"], cfg["rms_norm_eps"]) == \
        (6144, 1024, 8, 1, 2.826, 10000, 1e-05)
    assert cfg["vocab_size"] == cfg["end_id"] == 200192
    assert cfg["max_position_embeddings"] == 131072
    assert cfg["src_len"] + cfg["max_out_len"] == 16896
    for key in ("assumed", "deployment", "precision", "check",
                "check_readings"):
        assert cfg[key]
    for key in ("gate", "qk_norm", "global_layers", "rotary",
                "sliding_window", "norms", "embedding", "selection_bias",
                "route_norm", "shared_expert", "load_balance_coeff"):
        assert cfg["assumed"][key]
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size"))
    assert manifest.validate(m) == []


def test_the_share_is_1757_million_parameters():
    _, cfg, _ = cell_files()
    shapes = fam.param_shapes(cfg, cfg["param_prefix"])
    count = lambda pick: sum(int(np.prod(s)) for n, s in shapes.items()     # noqa: E731
                             if pick(n))
    assert count(lambda n: True) == cfg["parameters"] == 1756959488
    assert count(lambda n: ".l0." in n) == 65020160
    assert count(lambda n: ".l5." in n) == 134488448
    assert count(lambda n: ".l5.attn" in n) == 27263232 + 2 * 2048
    assert count(lambda n: ".l5.moe.experts." in n) == 16 * 6291456
    assert count(lambda n: ".l5.moe.shared." in n) == 6291456
    assert count(lambda n: ".l5.moe.router." in n) == 2048 * 128 + 128
    assert count(lambda n: n.endswith(("emb.w", "head.w", "out_norm.w"))) \
        == 819988480
    # the program's own parameters are exactly these
    from paddle_tpu.models import afmoe as M

    model = M.config_from_dict(fam.serving(cfg)["manifest"]["config"]
                               ["model"])
    assert M.param_shapes(model, cfg["param_prefix"]) == shapes
    assert (model.experts_held, model.num_experts, model.first_expert) == \
        (16, 128, 0)
    kinds = {n: fam.leaf_kind(n) for n in shapes}
    assert kinds["trinity.l2.moe.router.bias"] == "bias"
    assert kinds["trinity.l0.attn_norm.w"] == kinds["trinity.out_norm.w"] \
        == kinds["trinity.l3.attn.q_norm.w"] \
        == kinds["trinity.l3.ffn_post_norm.w"] == "ln_scale"
    assert kinds["trinity.l3.moe.experts.down.w"] == "embedding"
    assert kinds["trinity.l0.attn.gate.w"] is None
    assert kinds["trinity.emb.w"] is None


def test_the_traffic_is_the_issues():
    m, cfg, mix = cell_files()
    cell = manifest.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert (mix["kind"], mix["loop"], mix["clients"], mix["order"]) == \
        ("serve", "closed", 96, "fixed")
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 0.8, "min": 256, "max": 16384}
    assert mix["max_new"] == {"dist": "uniform", "min": 128, "max": 512}
    assert mix["shared_prefix"] == {"share": 0.0, "length": 0}
    assert mix["burst"] == {"factor": 1, "every_s": 0, "for_s": 0}
    assert (mix["population"], mix["population_seed"], mix["ramp_s"],
            mix["check_sample"], mix["trace_seconds"]) == \
        (4096, 42, 20, 12, 6)
    assert mix["clients"] == 1.5 * cfg["n_slots"]
    assert mix["prompt_len"]["max"] <= cfg["src_len"]
    assert mix["max_new"]["max"] <= cfg["max_out_len"]
    # every metric the cell reports, end to end and per layer
    assert {x["name"] for x in manifest.metrics_for(m, CELL, "end_to_end")} \
        == {"serve_tokens_per_s", "token_gap_p95_ms", "setup_s"}
    for name in NEW_READERS:
        entry = next(x for x in m["per_layer"] if x["name"] == name)
        assert entry["workloads"] == [CELL]


def test_the_familys_counts():
    _, cfg, _ = cell_files()
    row = 4 * 2 * 128 * 2           # a key and a value on 4 heads, bfloat16
    per_key = 2 * 32 * 2 * 128      # one query, 32 heads, scores and values
    # a decoded token at context 5000: 5000 keys in each of the 2 global
    # layers, the window's 2048 in each of the 6 window layers
    g_ops, g_bytes = fam.global_attention_need(cfg, [5000], [])
    w_ops, w_bytes = fam.window_attention_need(cfg, [5000], [])
    assert (g_bytes, w_bytes) == (2 * 5000 * row, 6 * 2048 * row)
    assert (g_ops, w_ops) == (2 * 5000 * per_key, 6 * 2048 * per_key)
    both = fam.mixed_attention_need(cfg, [5000], [])
    assert both == (g_ops + w_ops, g_bytes + w_bytes)
    # a context under the window reads what it has, in both kinds
    assert fam.window_attention_need(cfg, [100], [])[1] == 6 * 100 * row
    # a 300-token prompt: chunks of 256 and 44, causal; the rows up to a
    # chunk's end are read once a chunk
    ops, bytes_ = fam.global_attention_need(cfg, [], [300])
    pairs = 256 * 257 / 2 + 44 * (256 + 45 / 2)
    assert ops == pytest.approx(2 * pairs * per_key)
    assert bytes_ == 2 * (256 + 300) * row
    # a 4096-token prompt in a window layer: past the first 2048 positions
    # every query sees 2048 keys, and a chunk reads itself and the window
    ops, bytes_ = fam.window_attention_need(cfg, [], [4096])
    pairs = 2048 * 2049 / 2 + 2048 * 2048
    assert ops == pytest.approx(6 * pairs * per_key)
    assert bytes_ == 6 * row * (sum(256 * (c + 1) for c in range(8))
                                + 8 * (256 + 2047))
    # expert products: a pair is three products; a touched expert's three
    # matrices are read once; all 96 held experts are 1.2 GB
    ops, bytes_ = fam.expert_need(cfg, pairs=10, experts_touched=4)
    assert ops == 10 * 3 * 2 * 2048 * 1024
    assert bytes_ == 4 * 3 * 2048 * 1024 * 2 + 10 * (3 * 2048 + 4 * 1024) * 2
    _, bytes_ = fam.expert_need(cfg, pairs=0, experts_touched=6 * 16)
    assert bytes_ == pytest.approx(1.21e9, rel=0.01)
    # the two groups' pools are equally wide and told apart by their rows
    assert fam.pool_shapes(cfg) == {"global": (3073 * 2, 256, 512),
                                    "window": (641 * 6, 256, 512)}


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
    glob, win = (6146, 256, 512), (3846, 256, 512)
    kernels = [k([(1152, 2048), (16, 2048, 1024)], 0.3),
               k([(1152, 1024), (16, 1024, 2048)], 0.1),
               k([(64, 4, 8, 128), glob, glob], 0.5),       # decode rows
               k([(16, 4, 512, 128), glob, glob], 0.3),     # prefill tiles
               k([(64, 4, 8, 128), win, win], 0.4),
               k([(16, 4, 512, 128), win, win], 0.8),
               k([(64, 8, 64), (983040, 512)], 9.0)]       # someone else's
    before = {"moe_pairs_here": 0, "experts_touched": 0,
              "kv_bytes_per_token": 4096, "prompt_tokens_prefilled": 1000,
              "window_pages_recycled": 50, "global_pages_in_use": 10,
              "global_pages": 3072}
    after = {"moe_pairs_here": 115200, "experts_touched": 9600,
             "kv_bytes_per_token": 4096, "prompt_tokens_prefilled": 91000,
             "window_pages_recycled": 400, "global_pages_in_use": 1536,
             "global_pages": 3072}
    layer = _layer(cfg, kernels, before, after)
    read = lambda name: manifest.load_reader(name)(layer)   # noqa: E731
    assert read("prefill_tokens_per_step.serve") == 900.0
    assert read("kv_bytes_per_token.serve") == 4096.0
    assert read("window_pages_recycled_per_step.serve") == 3.5
    assert read("kv_global_pool_fill.serve") == 50.0
    assert read("moe_pairs_per_step.serve") == 1152.0
    assert read("expert_time_share.serve") == pytest.approx(10.0)
    assert read("attn_time_share.serve") == pytest.approx(50.0)
    assert read("window_attn_time_share.serve") == pytest.approx(30.0)
    need = 9600 * 3 * 2048 * 1024 * 2 / 819e9          # weight reads bound it
    assert read("expert_kernel_roofline.serve") == pytest.approx(
        100 * need / 0.4, rel=0.05)
    layer["records"] = [{"id": 0, "times": [1.0, 2.0, 3.0], "sent": 0.5}]
    layer["requests"] = {0: {"prompt": [1] * 5000}}
    least = lambda need: max(need[0] / 197e12, need[1] / 819e9)    # noqa: E731
    tokens = ([5001, 5002], [5000])
    assert read("window_attn_roofline.serve") == pytest.approx(
        100 * least(fam.window_attention_need(cfg, *tokens)) / 1.2)
    assert read("global_attn_roofline.serve") == pytest.approx(
        100 * least(fam.global_attention_need(cfg, *tokens)) / 0.8)
    assert read("mixed_attn_roofline.serve") == pytest.approx(
        100 * least(fam.mixed_attention_need(cfg, *tokens)) / 2.0)
    # a program without the counters or the kernels (the parent commit),
    # another family's layer, a training cell: nothing, and no error
    bare = _layer(cfg, [])
    from perfbench.families import mimo_v2_flash

    other = dict(_layer(cfg, kernels, before, after), family=mimo_v2_flash)
    for name in NEW_READERS:
        assert manifest.load_reader(name)(bare) is None
        assert manifest.load_reader(name)({"kind": "train"}) is None
    for name in NEW_READERS[:3]:
        assert manifest.load_reader(name)(other) is None
