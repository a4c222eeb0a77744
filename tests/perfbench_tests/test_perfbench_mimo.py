"""The cell ``mimo-serve-mixed`` (ISSUE 28): its tiny rehearsal on the CPU
walks registry -> gateway -> HTTP -> scheduler -> ``PagedLMGenerator`` and
comes out correct; altered tokens and the float8 control do not; the
configuration keeps every published number; the family's counts and the
new readers do their arithmetic."""

import json
import os

import numpy as np
import pytest

from perfbench import manifest, serve_cell
from perfbench.families import mimo_v2_flash as fam
from perfbench_helpers import compared, rehearse

CELL = "mimo-serve-mixed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cell_files():
    m = manifest.load()
    with open(manifest.config_path(m, "mimo-v2-flash-ep32"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    with open(manifest.traffic_path("mixed-flood"), encoding="utf-8") as f:
        return m, cfg, json.load(f)


def test_rehearsal_is_correct_and_reads_the_new_counters(capsys):
    rc, result, lines = rehearse(capsys, CELL, seed=2**31 + 28, seconds=1.5,
                                 trace=1)
    assert rc == 0 and result["correct"] is True
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    info = next(ln["info"] for ln in lines if "info" in ln)
    assert info["requests_failed"] == 0 and info["requests_ok"] > 0
    assert info["checked_requests"] >= 2 and info["checked_tokens"] > 0
    assert compared(lines)["logit_gap_max"]["ok"] is True
    routing = next(ln["routing"] for ln in lines if "routing" in ln)
    assert routing["scored"] > 0 and routing["near_ties"] >= 0
    # of the tokens with a routing near-tie that concerns this share, all
    # but the widest few (a stated share) are judged like the rest
    assert routing["tokens"] == info["checked_tokens"]
    assert 0 <= routing["set_aside"] < routing["tokens"] / 2
    # (the rehearsal's float32 flips nothing: its check exempts none)
    assert routing["set_aside_exempt"] == 0
    assert routing["gap_set_aside_judged"] <= routing["gap_max_set_aside"]
    assert max(routing["gap_max_free"], routing["gap_set_aside_judged"]) \
        == compared(lines)["logit_gap_max"]["value"] <= routing["limit"]
    assert max(routing["gap_max_by_margin_under"].values()) == \
        max(routing["gap_max_free"], routing["gap_max_set_aside"])
    read = {ln["rehearsal_reader"] for ln in lines
            if "rehearsal_reader" in ln}
    due = {m["name"] for m in manifest.metrics_for(manifest.load(), CELL,
                                                   "per_layer")}
    assert {"moe_pairs_per_step.serve", "kv_global_pool_fill.serve",
            "window_pages_recycled_per_step.serve", "step_wall_ms.serve",
            "feed_build_ms.serve", "fetch_wait_ms.serve",
            "sched_host_ms.serve"} <= read <= due


def test_all_but_the_widest_hundredth_of_the_set_aside_tokens_are_judged(
        monkeypatch, capsys):
    """The cell's check: flips at near-ties (a handful a run, each as
    wide as a fault) are exempt by NUMBER, a stated share of the set-aside
    tokens; what misses more often than that is over the limit."""
    _, cfg, _ = cell_files()
    assert cfg["check"] == {"logit_gap_max": 0.15,
                            "set_aside_exempt_share": 0.01}

    def fake(found):
        monkeypatch.setattr(
            fam.ref, "served_logit_gaps",
            lambda *a, **k: (found, found, {"set_aside":
                                            len(found["set_aside"])}))
        got, _ = fam.served_logit_gaps(cfg, 1, [[2]], [[3]])
        routing = [json.loads(ln)["routing"] for ln in
                   capsys.readouterr().out.splitlines() if "routing" in ln]
        return got, routing[-1]

    quiet = [0.02] * 840
    # 8 flips among 848 set-aside tokens (9 are exempt): judged 0.02
    got, routing = fake({"free": [0.03, 0.01],
                         "set_aside": [0.44, 0.22] + [0.16] * 6 + quiet})
    assert routing["set_aside_exempt"] == 9
    assert got == [0.03, 0.02] and routing["gap_set_aside_judged"] == 0.02
    # a fault that misses on every tenth such token is not hidden
    got, routing = fake({"free": [0.03, 0.01],
                         "set_aside": [0.3] * 85 + quiet[:763]})
    assert got == [0.3, 0.3] and max(got) > cfg["check"]["logit_gap_max"]


class _AlteredTokens(serve_cell.Served):
    """Every token altered where it is produced."""

    def __init__(self, cfg, seed, work_dir):
        super().__init__(cfg, seed, work_dir)
        real, vocab = self.inst.lane_step, cfg["vocab_size"]
        self.inst.lane_step = lambda: {
            slot: (tok + 1) % vocab for slot, tok in real().items()}


def test_altered_tokens_come_out_not_correct(capsys):
    def patch(ctx):
        ctx.make_served = _AlteredTokens

    rc, result, lines = rehearse(capsys, CELL, patch=patch)
    assert rc == 0 and result["correct"] is False
    assert compared(lines)["logit_gap_max"]["ok"] is False


def test_the_float8_control_is_over_the_limit(capsys):
    rc, result, lines = rehearse(capsys, CELL, seed=11, control="float8")
    assert rc == 0 and result["correct"] is True
    info = next(ln["info"] for ln in lines if "info" in ln)
    limit = compared(lines)["logit_gap_max"]["limit"]
    assert info["control"]["precision"] == "float8"
    assert info["control"]["logit_gap_max"] > 5 * limit


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file under the
    same name and value, but the three in ``reduced``; no width is cut."""
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiMo-V2-Flash")
    m, cfg, _ = cell_files()
    entry = manifest.config_of(m, "mimo-v2-flash-ep32")
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == \
        ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"])
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the guide's floors: a whole period and four layers after the dense
    # one, 8 experts, an eighth of the vocabulary
    n = cfg["num_hidden_layers"]
    assert cfg["hybrid_layer_pattern"][:n] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"][:n] == [0, 1, 1, 1, 1, 1, 1]
    assert cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert cfg["end_id"] == cfg["vocab_size"]


def test_every_configuration_keeps_its_published_widths():
    """What ``test_perfbench_manifest.py::test_configs_keep_published_
    widths_and_list_what_they_changed`` asserts, family by family (that
    test asks every configuration for the Transformer's ``d_key == d_value
    == 64`` and is red since this family entered; PERF.md section 7):
    ``reduced`` is the entry's and names no width; big and base have the
    paper's widths, each by name, this family the published config's."""
    m, _, _ = cell_files()
    seen = set()
    for c in m["configs"]:
        with open(manifest.config_path(m, c["name"]),
                  encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg and not key.endswith(("_dim", "_rank"))
        seen.add(cfg["family"])
        if cfg["family"] == "transformer":
            assert cfg["d_key"] == cfg["d_value"] == 64
            assert cfg["n_layer"] == 6
            assert (cfg["d_model"], cfg["d_inner_hid"], cfg["n_head"]) == \
                {"transformer-big": (1024, 4096, 16),
                 "transformer-base": (512, 2048, 8)}[c["name"]]
        else:
            assert cfg["family"] == "mimo_v2_flash"
            assert (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["head_dim"], cfg["v_head_dim"]) == (4096, 64, 192,
                                                            128)
            assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                    cfg["num_experts_per_tok"], cfg["sliding_window"]) == \
                (16384, 2048, 8, 128)
            assert (cfg["num_key_value_heads"],
                    cfg["swa_num_key_value_heads"]) == (4, 8)
    assert seen == {"transformer", "mimo_v2_flash"}


def test_the_share_is_2222_million_parameters():
    _, cfg, _ = cell_files()
    shapes = fam.param_shapes(cfg, cfg["param_prefix"])
    assert sum(int(np.prod(s)) for s in shapes.values()) == \
        cfg["parameters"] == 2221995840
    # the program's own parameters are exactly these
    from paddle_tpu.models import mimo_v2_flash as M

    model = M.LMConfig.from_dict(fam.serving(cfg)["manifest"]["config"]
                                 ["model"])
    assert M.param_shapes(model, cfg["param_prefix"]) == shapes
    kinds = {n: fam.leaf_kind(n) for n in shapes}
    assert kinds["mimo.l1.attn.sink"] == kinds["mimo.l1.moe.router.bias"] \
        == "bias"
    assert kinds["mimo.l0.attn_norm.w"] == kinds["mimo.out_norm.w"] \
        == "ln_scale"
    assert kinds["mimo.l3.moe.experts.down.w"] == "embedding"
    assert kinds["mimo.l0.attn.q.w"] is None and kinds["mimo.emb.w"] is None


def test_the_traffic_is_the_issues():
    _, cfg, mix = cell_files()
    assert (mix["loop"], mix["clients"], mix["order"]) == \
        ("closed", 128, "fixed")
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 1.1, "min": 32, "max": 8192}
    assert mix["max_new"] == {"dist": "uniform", "min": 64, "max": 256}
    assert (mix["population"], mix["population_seed"], mix["ramp_s"],
            mix["check_sample"], mix["trace_seconds"]) == \
        (8192, 28, 10, 12, 6)
    assert mix["prompt_len"]["max"] <= cfg["src_len"]
    assert mix["max_new"]["max"] <= cfg["max_out_len"]


def test_the_pools_fill_what_the_issue_reckoned():
    from paddle_tpu.serving.paged_lm import lm_pool_layout

    _, cfg, _ = cell_files()
    lay = lm_pool_layout(fam.serving(cfg)["manifest"]["config"])
    size = {k: 2 * (int(np.prod(g["k_shape"])) + int(np.prod(g["v_shape"])))
            for k, g in lay["groups"].items()}
    assert lay["groups"]["global"]["table"] == 33          # 8448 / 256
    assert lay["groups"]["window"]["table"] == 4           # the ring
    assert lay["groups"]["window"]["decode_pages"] == 2
    assert 2.7e9 < size["global"] < 2.8e9 and 0.8e9 < size["window"] < 0.9e9


def test_the_familys_counts():
    _, cfg, _ = cell_files()
    # a decoded token at context 2000: 2000 keys in each of 2 global
    # layers at 2560 B, 128 in each of 5 window layers at 5120 B
    ops, bytes_ = fam.mixed_attention_need(cfg, [2000], [])
    assert bytes_ == 2 * 2000 * 2560 + 5 * 128 * 5120
    assert ops == 2.0 * 64 * 320 * (2 * 2000 + 5 * 128)
    short, _ = fam.mixed_attention_need(cfg, [50], [])
    assert short == 2.0 * 64 * 320 * 7 * 50
    # a 300-token prompt: chunks of 256 and 44, causal
    ops, bytes_ = fam.mixed_attention_need(cfg, [], [300])
    pairs_g = 256 * 257 / 2 + 44 * (256 + 45 / 2)
    pairs_w = sum(min(t + 1, 128) for t in range(300))
    assert ops == pytest.approx(2.0 * 64 * 320 * (2 * pairs_g + 5 * pairs_w))
    assert bytes_ == 2 * (256 + 300) * 2560 + 5 * (256 + 44 + 127) * 5120
    # expert products: a pair is three products; a touched expert's three
    # matrices are read once
    ops, bytes_ = fam.expert_need(cfg, pairs=10, experts_touched=4)
    assert ops == 10 * 3 * 2 * 4096 * 2048
    assert bytes_ == 4 * 3 * 4096 * 2048 * 2 + 10 * (3 * 4096 + 4 * 2048) * 2


def _layer(cfg, kernels, before=None, after=None):
    return {"kind": "serve", "cfg": cfg, "family": fam, "steps": 100,
            "window_s": 6.0, "trace": {"window_s": 6.0, "busy_s": 4.0,
                                       "kernels": kernels},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "before": {"engine": before} if before else {},
            "after": {"engine": after} if after else {},
            "records": [], "requests": {}, "t_open": 0.0, "t_close": 6.0}


def test_the_new_readers_tell_expert_and_attention_kernels_apart():
    _, cfg, _ = cell_files()
    k = lambda dims, s, n=10: {"operands": [("bf16", d) for d in dims],   # noqa: E731
                               "results": [], "calls": n, "seconds": s}
    kernels = [k([(4608, 4096), (8, 4096, 2048)], 0.6),
               k([(4608, 2048), (8, 2048, 4096)], 0.2),
               k([(64, 4, 16, 256), (4226, 256, 768), (4226, 256, 512)], 0.3),
               k([(64, 8, 8, 256), (1285, 128, 1536), (1285, 128, 1024)],
                 0.1),
               k([(64, 8, 64), (983040, 512)], 9.0)]       # someone else's
    before = {"moe_pairs_here": 0, "experts_touched": 0,
              "window_pages_recycled": 5, "global_pages_in_use": 10,
              "global_pages": 2112}
    after = {"moe_pairs_here": 60000, "experts_touched": 4000,
             "window_pages_recycled": 105, "global_pages_in_use": 528,
             "global_pages": 2112}
    layer = _layer(cfg, kernels, before, after)
    read = lambda name: manifest.load_reader(name)(layer)   # noqa: E731
    assert read("expert_time_share.serve") == pytest.approx(20.0)
    assert read("attn_time_share.serve") == pytest.approx(10.0)
    assert read("moe_pairs_per_step.serve") == 600.0
    assert read("window_pages_recycled_per_step.serve") == 1.0
    assert read("kv_global_pool_fill.serve") == 25.0
    need = 4000 * 3 * 4096 * 2048 * 2 / 819e9          # weight reads bound it
    assert read("expert_kernel_roofline.serve") == pytest.approx(
        100 * need / 0.8, rel=0.05)
    layer["records"] = [{"id": 0, "times": [1.0, 2.0, 3.0], "sent": 0.5}]
    layer["requests"] = {0: {"prompt": [1] * 1000}}
    ops, bytes_ = fam.mixed_attention_need(cfg, [1001, 1002], [1000])
    assert read("mixed_attn_roofline.serve") == pytest.approx(
        100 * max(ops / 197e12, bytes_ / 819e9) / 0.4)
    # a program without the counters or the kernels: nothing, no error
    bare = _layer(cfg, [])
    for name in ("expert_kernel_roofline.serve", "mixed_attn_roofline.serve",
                 "expert_time_share.serve", "attn_time_share.serve",
                 "moe_pairs_per_step.serve", "kv_global_pool_fill.serve",
                 "window_pages_recycled_per_step.serve"):
        assert manifest.load_reader(name)(bare) is None
        assert manifest.load_reader(name)({"kind": "train"}) is None
