"""BENCHMARK.json, the files it names, and the rule that a later PR adds
cells and metrics without editing a file that is there."""

import json
import os
import shutil

import pytest

from perfbench import device, manifest, run, traffic


def test_manifest_is_sound():
    m = manifest.load()
    assert manifest.validate(m) == []
    assert m["command"][:3] == ["python3", "-m", "perfbench.run"]
    assert set(m["paths"]) == {"perfbench", "tests/perfbench_tests"}
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            assert len(metric["unit"]) <= 16 and " " not in metric["unit"]
    assert os.path.getsize(os.path.join(device.ROOT, "BENCHMARK.json")) < 65536


def test_every_layer_metric_moves_a_metric_its_cells_report():
    m = manifest.load()
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for metric in m["per_layer"]:
        for w in m["workloads"]:
            if manifest.reports(metric, w["name"], m):
                assert manifest.reports(e2e[metric["moves"]], w["name"], m), \
                    (metric["name"], w["name"])
    for w in m["workloads"]:
        assert manifest.metrics_for(m, w["name"], "per_layer")
        names = [x["name"] for x in manifest.metrics_for(m, w["name"],
                                                         "end_to_end")]
        assert "setup_s" in names and len(names) >= 2


def test_configs_keep_published_widths_and_list_what_they_changed():
    m = manifest.load()
    for c in m["configs"]:
        with open(manifest.config_path(m, c["name"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg and not key.endswith(("_dim", "_rank"))
        assert cfg["d_key"] == cfg["d_value"] == 64
    big = json.load(open(manifest.config_path(m, "transformer-big")))
    base = json.load(open(manifest.config_path(m, "transformer-base")))
    assert (big["d_model"], big["d_inner_hid"], big["n_head"]) == (1024, 4096, 16)
    assert (base["d_model"], base["d_inner_hid"], base["n_head"]) == (512, 2048, 8)
    assert big["n_layer"] == base["n_layer"] == 6


def test_validate_finds_faults():
    m = manifest.load()
    broken = json.loads(json.dumps(m))
    broken["per_layer"][0]["moves"] = "no_such_metric"
    broken["end_to_end"][0]["unit"] = "tokens per second"
    broken["workloads"][0]["chips"] = 2
    found = " ".join(manifest.validate(broken))
    assert "no_such_metric" in found and "bad unit" in found \
        and "chips" in found


def test_a_config_a_mix_and_a_metric_are_added_without_an_edit(tmp_path):
    """A later PR's view: new files and new entries only."""
    root = str(tmp_path)
    shutil.copy(os.path.join(device.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(device.ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    m = manifest.load(root)
    # a configuration: its file of sizes
    cfg = json.load(open(manifest.config_path(m, "transformer-base", root)))
    cfg.update(name="transformer-base-p8", page_size=8)
    with open(os.path.join(root, "perfbench/configs/transformer-base-p8.json"),
              "w") as f:
        json.dump(cfg, f)
    m["configs"].append({"name": "transformer-base-p8", "source": cfg["source"],
                         "file": "perfbench/configs/transformer-base-p8.json",
                         "reduced": [], "why": "pages of 8"})
    # a traffic mix: a data file the one generator reads
    mix = traffic.load(manifest.traffic_path("steady", root))
    mix.update(burst={"factor": 4, "every_s": 5, "for_s": 1},
               shared_prefix={"share": 0.5, "length": 128})
    with open(manifest.traffic_path("burst", root), "w") as f:
        json.dump(mix, f)
    m["workloads"].append({"name": "base-p8-burst",
                           "config": "transformer-base-p8", "traffic": "burst",
                           "chips": 1, "why": "bursts of 4x for 1 s in 5"})
    # a per-layer metric: a small reader of its own
    with open(manifest.layer_metric_path("steps_in_window.serve", root),
              "w") as f:
        f.write("def read(layer):\n"
                "    return layer.get('steps') if layer.get('kind') == "
                "'serve' else None\n")
    m["per_layer"].append({"name": "steps_in_window.serve", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "token_gap_p95_ms"})
    for metric in m["end_to_end"]:
        if metric["name"] in ("ttft_p50_ms", "token_gap_p95_ms"):
            metric["workloads"].append("base-p8-burst")
    assert manifest.validate(m, root) == []
    read = manifest.load_reader("steps_in_window.serve", root)
    assert read({"kind": "serve", "steps": 7}) == 7
    assert read({"kind": "train"}) is None
    # the general generator reads the new mix with no new code
    reqs = traffic.serve_requests(mix, 32768, seed=5, horizon_s=10.0)
    assert len(reqs) == round(mix["rate_per_s"] * 10.0)
    shared = sum(1 for r in reqs if r["prompt"][:128] == reqs[0]["prompt"][:128])
    assert any(len(r["prompt"]) >= 129 for r in reqs) and shared >= 1
    # and no file that was there has changed
    for p, content in before.items():
        assert open(p, "rb").read() == content, p


def test_a_longer_training_mix_is_a_traffic_file_and_no_edit():
    """The position tables follow the mix's ``seq_len``; a mix may carry
    limits of its own, set from its own readings."""
    from perfbench import cells, train_cell

    m = manifest.load()
    shapes = {}
    for name in ("big-train-s256", "big-train-s2048"):
        _, cfg, mix = cells.load_cell(m, name, rehearse=False)
        assert "max_length" not in cfg
        sized = train_cell.sized(cfg, mix)
        assert sized["max_length"] == mix["seq_len"] + 1
        assert mix["batch"] * mix["seq_len"] == 16384
        assert mix["batch"] % mix["reference_block_rows"] == 0
        shapes[name] = train_cell.ref.param_shapes(sized, "p")
    assert shapes["big-train-s256"]["p.src_pos_emb.w"] == (257, 1024)
    assert shapes["big-train-s2048"]["p.trg_pos_emb.w"] == (2049, 1024)
    own = train_cell.sized({"check": {"a": 1.0, "b": 2.0}},
                           {"seq_len": 8, "check": {"b": 3.0}})
    assert own["check"] == {"a": 1.0, "b": 3.0}


def test_unknown_device_kind_is_an_error_not_a_default():
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        device.peaks("TPU v9")
    with pytest.raises(KeyError):
        device.peaks("_source")


@pytest.mark.parametrize("cell", ["big-train-s256", "base-serve-flood"])
def test_without_a_tpu_nothing_is_built_and_no_result_is_printed(
        capsys, cell):
    rc = run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 1
    assert '"correct"' not in out.out and "no TPU" in out.err
