"""BENCHMARK.json and the files it names.  The harness finds a cell's
configuration, its traffic mix and each per-layer metric's reader BY NAME:

    perfbench/configs/<config>.json         (or the path in ``file``)
    perfbench/traffic/<traffic>.json
    perfbench/layer_metrics/<metric>.py     (``read(ctx) -> number | None``)

so a later PR adds any of them as new files plus entries, editing nothing.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Callable, Dict, List

from .device import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def cell(manifest: Dict, name: str) -> Dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: "
                   f"{[w['name'] for w in manifest['workloads']]})")


def config_of(manifest: Dict, name: str) -> Dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def config_path(manifest: Dict, name: str, root: str = ROOT) -> str:
    return os.path.join(root, config_of(manifest, name)["file"])


def traffic_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "perfbench", "traffic", f"{name}.json")


def layer_metric_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "perfbench", "layer_metrics", f"{name}.py")


def reports(metric: Dict, cell_name: str, manifest: Dict) -> bool:
    """Whether ``metric`` is due in ``cell_name``'s result line."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    if "moves" in metric:               # per-layer, everywhere its target is
        target = next(m for m in manifest["end_to_end"]
                      if m["name"] == metric["moves"])
        return reports(target, cell_name, manifest)
    return True


def metrics_for(manifest: Dict, cell_name: str, group: str) -> List[Dict]:
    return [m for m in manifest[group] if reports(m, cell_name, manifest)]


def load_reader(name: str, root: str = ROOT) -> Callable:
    """The ``read`` function of a per-layer metric's file (loaded by path:
    metric names hold dots)."""
    path = layer_metric_path(name, root)
    spec = importlib.util.spec_from_file_location(
        "perfbench_layer_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def validate(manifest: Dict, root: str = ROOT) -> List[str]:
    """Everything wrong with the manifest and the files it names, as
    sentences; empty when sound."""
    bad: List[str] = []
    if set(manifest) != KEYS:
        bad.append(f"keys {sorted(manifest)} are not exactly {sorted(KEYS)}")
        return bad
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest[group]]
        for n in names:
            if not NAME.match(n):
                bad.append(f"{group}: bad name {n!r}")
        if len(set(names)) != len(names):
            bad.append(f"{group}: a name appears twice")
    if len(set(e2e) | {m["name"] for m in manifest["per_layer"]}) != \
            len(e2e) + len(manifest["per_layer"]):
        bad.append("a metric name is used end to end and per layer")
    for c in manifest["configs"]:
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        if not any(w["config"] == c["name"] for w in cells.values()):
            bad.append(f"config {c['name']} is used by no cell")
    pairs = set()
    for w in cells.values():
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']}")
        if not NAME.match(w["traffic"]) or \
                not os.path.isfile(traffic_path(w["traffic"], root)):
            bad.append(f"cell {w['name']}: no traffic file for {w['traffic']}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips must be 1 or 4")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad.append(f"cell {w['name']}: why must be 1..200 characters")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: config x traffic appears twice")
        pairs.add((w["config"], w["traffic"]))
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for m in list(e2e.values()) + manifest["per_layer"]:
        if not UNIT.match(m.get("unit", "")):
            bad.append(f"metric {m['name']}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better must be lower or higher")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m['name']}: unknown source")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"metric {m['name']}: unknown cell {w}")
    for m in e2e.values():
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']}: source must be the "
                       f"benchmark's own clock or trace")
        if not 0 < m.get("bound", 0) <= 0.1:
            bad.append(f"end-to-end {m['name']}: bound outside (0, 0.1]")
    for m in manifest["per_layer"]:
        if m.get("moves") not in e2e:
            bad.append(f"per-layer {m['name']}: moves {m.get('moves')!r} "
                       f"is no end-to-end metric")
            continue
        if not os.path.isfile(layer_metric_path(m["name"], root)):
            bad.append(f"per-layer {m['name']}: no reader file")
        for w in cells:
            if reports(m, w, manifest) and not reports(e2e[m["moves"]], w,
                                                       manifest):
                bad.append(f"per-layer {m['name']} is due in {w}, which "
                           f"does not report {m['moves']}")
    for w in cells:
        got = [m["name"] for m in metrics_for(manifest, w, "end_to_end")]
        if "setup_s" not in got or len(got) < 2:
            bad.append(f"cell {w}: needs setup_s and one more end-to-end "
                       f"metric, has {got}")
        if not metrics_for(manifest, w, "per_layer"):
            bad.append(f"cell {w}: reports no per-layer metric")
    return bad
