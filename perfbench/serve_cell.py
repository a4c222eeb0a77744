"""A serving cell: the configuration's artifact through the front door a
tenant uses — ``ModelRegistry -> Gateway.load_model -> GatewayServer`` —
in the process that holds the chip, with the HTTP streaming clients in a
child process that never imports JAX (``perfbench.loadgen``).

Traffic starts ``ramp_s`` before the window opens, so the window sees the
system in its steady state, and is CUT when it closes (an open loop waits
at most ``grace_s`` for the first tokens of requests that were due inside
it): nothing is drained, because a drain measures nothing and a request of
256 tokens takes most of a minute.  Everything a client can see is
measured on the client's side; the program's own counters
(``sched.stats()``) are read as deltas over the window and go to the
per-layer metrics only.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import subprocess
import sys
import time
import urllib.request
from typing import Dict, List

import numpy as np

from . import stats, traffic, weights
from .device import ROOT
from .reference import transformer as ref

MODEL_KEYS = ("src_vocab_size", "trg_vocab_size", "n_layer", "n_head",
              "d_key", "d_value", "d_model", "d_inner_hid", "max_length")
GENERATOR_KEYS = ("src_len", "max_out_len", "param_prefix", "start_id",
                  "end_id", "page_size", "num_pages", "chunk_size",
                  "prefix_sharing", "kv_dtype")
VERSION = "1"


def write_artifact(cfg: Dict, seed: int, root: str) -> str:
    """The served artifact, from the seed: the benchmark's weights under
    the program's parameter names, and the constructor manifest."""
    from paddle_tpu import fluid
    from paddle_tpu.serving.gateway.registry import MANIFEST_NAME

    shapes = ref.param_shapes(cfg, cfg["param_prefix"])
    host = {k: np.asarray(v) for k, v in weights.make(shapes, seed).items()}
    manifest = {k: cfg[k] for k in MODEL_KEYS + GENERATOR_KEYS}
    manifest["topk_size"] = None

    def writer(staging: str) -> None:
        for name, value in host.items():
            fluid.io.save_tensor(value, os.path.join(staging, name))
        with open(os.path.join(staging, MANIFEST_NAME), "w",
                  encoding="utf-8") as f:
            json.dump({"kind": "generator", "config": manifest}, f)

    shutil.rmtree(root, ignore_errors=True)
    return fluid.io.publish_model_version(root, cfg["param_prefix"], VERSION,
                                          writer)


class Served:
    """The system under test, loaded and listening."""

    def __init__(self, cfg: Dict, seed: int, work_dir: str):
        from paddle_tpu.serving.gateway import (Gateway, GatewayServer,
                                                ModelRegistry)

        self.cfg, self.name = cfg, cfg["param_prefix"]
        root = os.path.join(work_dir, "models")
        write_artifact(cfg, seed, root)
        self.registry = ModelRegistry(root=root)
        self.gw = Gateway(registry=self.registry, n_slots=cfg["n_slots"],
                          max_new_tokens=cfg["max_out_len"])
        self.gw.load_model(self.name, VERSION)       # builds, uploads, warms
        self.inst = self.registry.instance(self.name)
        want = ref.param_shapes(cfg, self.name)
        have = {n: tuple(np.shape(v)) for n, v in self.inst.scope.vars.items()
                if n in want}
        if have != want:
            raise RuntimeError("the served program's parameters are not "
                               "the configuration's")
        self.server = GatewayServer(self.gw, port=0, request_timeout=600.0)
        self.addr = self.server.start()

    def warm_request(self) -> None:
        body = json.dumps({"model": self.name, "prompt": [2, 3, 4, 5],
                           "max_new": 2, "stream": True}).encode()
        req = urllib.request.Request(
            f"http://{self.addr}/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as resp:
            resp.read()

    def counters(self) -> Dict:
        s = self.gw.sched.stats()
        s["executable_misses"] = int(
            self.inst.exe.cache_stats()["executable"]["misses"])
        return s

    def stop(self) -> None:
        self.server.stop(drain=True)

    def free(self) -> None:
        """Give the device back before the reference runs."""
        import jax

        for value in list(self.inst.scope.vars.values()):
            if isinstance(value, jax.Array) and not value.is_deleted():
                value.delete()
        self.inst = self.gw = self.registry = self.server = None
        gc.collect()


def request_ok(rec: Dict, req: Dict) -> bool:
    """Answered in full."""
    return rec["status"] == 200 and rec["error"] is None \
        and rec["done"] is not None and len(rec["tokens"]) == req["max_new"]


def request_failed(rec: Dict, req: Dict) -> bool:
    """Neither answered in full nor cut short by the generator itself when
    the window closed (a cut request still may not have more tokens than
    it asked for)."""
    if rec["cut"]:
        return rec["status"] not in (None, 200) \
            or len(rec["tokens"]) > req["max_new"]
    return not request_ok(rec, req)


def window_numbers(records: List[Dict], requests: List[Dict], open_loop: bool,
                   t0: float, t_open: float, t_close: float) -> Dict:
    """Everything the clients saw, from their own records."""
    by_id = {r["id"]: r for r in requests}
    seconds = t_close - t_open
    tokens_in = 0
    gaps_ms: List[float] = []
    for rec in records:
        times = rec["times"]
        tokens_in += sum(1 for t in times if t_open <= t < t_close)
        gaps_ms += [1e3 * (b - a) for a, b in zip(times, times[1:])
                    if t_open <= b < t_close]
    sent = {rec["id"] for rec in records}
    failed = [rec["id"] for rec in records
              if request_failed(rec, by_id[rec["id"]])]
    if open_loop:                       # planned, due before the stop, unsent
        failed += [r["id"] for r in requests if r["id"] not in sent
                   and t0 + r["due_s"] < t_close]
    ttft_ms, late_ms = [], []
    bad = set(failed)
    for rec in records:
        if not t_open <= rec["due"] < t_close:
            continue
        if rec["cut"] and not rec["times"] and not open_loop:
            continue                    # a closed loop's queue at the close
        if rec["id"] in bad or not rec["times"]:
            # a failure, or no first token ``grace_s`` after the close:
            # it misses by the window
            ttft_ms.append(1e3 * seconds)
        else:
            ttft_ms.append(1e3 * (rec["times"][0] - rec["due"]))
        if rec["sent"] is not None:
            late_ms.append(1e3 * (rec["sent"] - rec["due"]))
    if open_loop:
        ttft_ms += [1e3 * seconds for r in requests if r["id"] not in sent
                    and t_open <= t0 + r["due_s"] < t_close]
    return {"tokens_in_window": tokens_in, "seconds": seconds,
            "attempted": len(sent | bad), "failed": len(failed),
            "gaps_ms": gaps_ms, "ttft_ms": ttft_ms, "late_ms": late_ms}


def run(ctx) -> Dict:
    cfg, mix, spans = ctx.cfg, ctx.mix, ctx.spans
    open_loop = mix["loop"] == "open"
    seconds = ctx.window_seconds()
    ramp, grace = float(mix["ramp_s"]), float(mix.get("grace_s", 0.0))
    work = ctx.work_dir()

    with spans.span("build"):
        served = ctx.make_served(cfg, ctx.seed, work)
    with spans.span("warm"):
        served.warm_request()
        ctx.settle()
    requests = traffic.serve_requests(mix, cfg["src_vocab_size"], ctx.seed,
                                      horizon_s=ramp + seconds)
    plan_path = os.path.join(work, "plan.json")
    out_path = os.path.join(work, "results.json")
    lead = 1.0 + (1.5 if ctx.trace else 0.0)
    t0 = time.monotonic() + lead
    t_open, t_close = t0 + ramp, t0 + ramp + seconds
    plan = {"addr": served.addr, "model": served.name, "loop": mix["loop"],
            "t0": t0, "stop_at": t_close, "grace_s": grace,
            "workers": int(mix.get("workers", 256)), "requests": requests}
    with open(plan_path, "w", encoding="utf-8") as f:
        json.dump(plan, f)
    child = subprocess.Popen(
        [sys.executable, "-m", "perfbench.loadgen", plan_path, out_path],
        cwd=ROOT, stdin=subprocess.DEVNULL)
    try:
        with spans.span("ramp"):
            if ctx.trace:
                time.sleep(max(0.0, t_open - 1.0 - time.monotonic()))
                ctx.start_trace()
            time.sleep(max(0.0, t_open - time.monotonic()))
        with spans.span("window"):
            before = served.counters()
            ctx.setup_done(t_open)
            time.sleep(max(0.0, t_close - time.monotonic()))
            after = served.counters()
        ctx.window_closed()
        with spans.span("close"):
            child.wait(timeout=grace + 30.0)
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
        with spans.span("stop"):
            served.stop()
    if child.returncode != 0:
        raise RuntimeError(f"the load generator exited {child.returncode}")
    with open(out_path, encoding="utf-8") as f:
        result = json.load(f)
    records = result["records"]
    nums = window_numbers(records, requests, open_loop, t0, t_open, t_close)

    steps = after["steps"] - before["steps"]
    e2e = {"serve_tokens_per_s": nums["tokens_in_window"] / seconds}
    if nums["gaps_ms"]:
        e2e["token_gap_p95_ms"] = stats.percentile(nums["gaps_ms"], 95)
    if nums["ttft_ms"]:
        e2e["ttft_p50_ms"] = stats.percentile(nums["ttft_ms"], 50)
    checks = [
        {"name": "failed_or_short_requests", "value": float(nums["failed"]),
         "limit": 0.0},
        {"name": "scheduler_failed",
         "value": float(after["failed"] - before["failed"]), "limit": 0.0},
        {"name": "compiles_in_window",
         "value": float(after["executable_misses"]
                        - before["executable_misses"]), "limit": 0.0},
    ]

    # the reference, once the device is free again: a seeded sample of the
    # finished requests, the longest among them
    by_id = {r["id"]: r for r in requests}
    done = [r for r in records if request_ok(r, by_id[r["id"]])]
    served.free()
    t_ref = time.monotonic()
    gaps: List[float] = []
    control: List[float] = []
    sample: List[Dict] = []
    if done:
        rng = traffic.rng_for(ctx.seed, 4)
        longest = max(done, key=lambda r: len(by_id[r["id"]]["prompt"])
                      + len(r["tokens"]))
        rest = [r for r in done if r is not longest]
        pick = rng.permutation(len(rest))[:int(mix["check_sample"]) - 1]
        sample = [longest] + [rest[i] for i in pick]
        with spans.span("reference"):
            shapes = ref.param_shapes(cfg, cfg["param_prefix"])
            gaps, control = ctx.served_gaps(
                weights.make(shapes, ctx.seed), cfg["param_prefix"], cfg,
                [by_id[r["id"]]["prompt"] for r in sample],
                [r["tokens"] for r in sample], cfg["start_id"],
                cfg["src_len"], cfg["max_out_len"],
                ctx.control_precision or "float32")
    ref_s = time.monotonic() - t_ref
    checks.append({"name": "logit_gap_max",
                   "value": max(gaps) if gaps else float("inf"),
                   "limit": cfg["check"]["logit_gap_max"]})
    info = {
        "requests_sent": len(records), "requests_ok": len(done),
        "requests_failed": nums["failed"], "steps": steps,
        "ttft_ms": stats.summary(nums["ttft_ms"], "ms"),
        "token_gap_ms": stats.summary(nums["gaps_ms"], "ms"),
        "gen_late_ms": stats.summary(nums["late_ms"], "ms"),
        "mean_max_new": float(np.mean([r["max_new"] for r in requests])),
        "checked_requests": len(sample),
        "checked_tokens": sum(len(r["tokens"]) for r in sample),
        "reference_s": ref_s,
        "requests_cut_at_close": sum(1 for r in records if r["cut"]),
        "cut_before_first_token": sum(1 for r in records
                                   if r["cut"] and not r["times"]),
        "generator_threads_stuck": result["threads_stuck"],
        "failures": [{k: (len(r[k]) if k == "tokens" else r[k])
                      for k in ("id", "status", "error", "tokens", "cut")}
                     for r in records
                     if request_failed(r, by_id[r["id"]])][:5],
    }
    if ctx.control_precision and control:
        info["control"] = {"precision": ctx.control_precision,
                           "logit_gap_max": max(control)}
    return {
        "e2e": e2e, "attempted": nums["attempted"], "failed": nums["failed"],
        "checks": checks, "info": info,
        "layer": {"kind": "serve", "before": before, "after": after,
                  "steps": steps, "window_s": seconds, "records": records,
                  "requests": by_id, "t_open": t_open, "t_close": t_close,
                  "numbers": nums},
    }
