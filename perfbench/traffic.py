"""The one general traffic generator.  A traffic mix is a JSON file of
parameters under ``perfbench/traffic/``; nothing here knows a mix by name.

Two kinds:

``train``  ``batch`` rows of ``seq_len`` source and target tokens, on
           ``chips`` devices under ``mesh_axes`` (empty = one device).  A
           pool of ``pool_batches`` host batches is drawn from the seed and
           fed round-robin, from numpy, as a job's input pipeline would.
``serve``  requests against the HTTP front door.  ``loop`` is ``closed``
           (``clients`` callers, each sending its next request when the
           last completes) or ``open`` (arrivals at ``rate_per_s`` from an
           ``arrival`` process, each timed from when it was DUE).  Lengths
           come from ``prompt_len`` / ``max_new``; ``shared_prefix``
           (share of requests, prefix length) and ``burst`` (factor, every
           s, for s) default to none.

Steadiness: the SET of request sizes and of inter-arrival gaps is drawn
once from the mix's own ``population_seed``; the run's ``--seed`` draws the
token ids and, with ``order: seeded`` (the default), permutes sizes and
gaps, so that every seed offers the same work in another order.  Where
even the order moves the metric — a tail over a few hundred arrivals does:
on the chip the steady mix's p95 time to first token ranged 9 % across
seeds and 1 % between two runs of one seed (PR 23) — ``order: fixed``
replays the one schedule of (due time, sizes) for every seed.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List

import numpy as np

TRAIN_FEEDS = ("src_word", "src_pos", "trg_word", "trg_pos", "lbl_word",
               "lbl_weight")


def load(path: str) -> Dict:
    with open(path, encoding="utf-8") as f:
        mix = json.load(f)
    if mix.get("kind") not in ("train", "serve"):
        raise ValueError(f"{path}: traffic kind must be train or serve")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    seed = int(seed)
    return np.random.default_rng([abs(seed) & 0xFFFFFFFF, abs(seed) >> 32,
                                  1 if seed < 0 else 0, stream])


# -- train ------------------------------------------------------------------

def train_batches(mix: Dict, vocab: int, seed: int) -> List[Dict[str, np.ndarray]]:
    """``pool_batches`` feed dicts; every row differs."""
    rng = rng_for(seed, 1)
    b, s = int(mix["batch"]), int(mix["seq_len"])
    pos = np.tile(np.arange(s, dtype=np.int32), (b, 1))
    out = []
    for _ in range(int(mix.get("pool_batches", 16))):
        out.append({
            "src_word": rng.integers(1, vocab, (b, s), dtype=np.int32),
            "src_pos": pos,
            "trg_word": rng.integers(1, vocab, (b, s), dtype=np.int32),
            "trg_pos": pos,
            "lbl_word": rng.integers(1, vocab, (b, s), dtype=np.int32),
            "lbl_weight": np.ones((b, s), np.float32),
        })
    return out


# -- serve ------------------------------------------------------------------

def _lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec.get("dist", "lognormal")
    if dist == "lognormal":
        x = rng.lognormal(math.log(float(spec["median"])),
                          float(spec["sigma"]), n)
    elif dist == "uniform":
        x = rng.uniform(float(spec["min"]), float(spec["max"]) + 1, n)
    elif dist == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(int)


def population(mix: Dict, n: int) -> Dict[str, np.ndarray]:
    """The mix's fixed multiset of ``n`` request sizes (and, for an open
    loop, inter-arrival gaps), from its ``population_seed`` alone."""
    rng = rng_for(int(mix.get("population_seed", 0)), 2)
    plen = _lengths(mix["prompt_len"], n, rng)
    mn = mix["max_new"]
    if "ratio_uniform" in mn:
        lo, hi = mn["ratio_uniform"]
        new = np.rint(plen * rng.uniform(lo, hi, n))
    else:
        new = _lengths(mn, n, rng)
    new = np.clip(new, int(mn["min"]), int(mn["max"])).astype(int)
    out = {"prompt_len": plen, "max_new": new}
    if mix.get("loop") == "open":
        arrival = mix.get("arrival", "poisson")
        rate = float(mix["rate_per_s"])
        if arrival == "poisson":
            gaps = rng.exponential(1.0 / rate, n)
        elif arrival == "uniform":
            gaps = np.full(n, 1.0 / rate)
        else:
            raise ValueError(f"unknown arrival process {arrival!r}")
        out["gaps"] = gaps
    return out


def _apply_bursts(due: np.ndarray, burst: Dict) -> np.ndarray:
    """Keep the mean rate; squeeze arrivals so that for ``for_s`` seconds
    in every ``every_s`` the rate is ``factor`` times the rest's."""
    factor = float(burst.get("factor", 1))
    every, dur = float(burst.get("every_s", 0)), float(burst.get("for_s", 0))
    if factor <= 1 or every <= 0 or dur <= 0:
        return due
    # warp time: a period holds dur*factor + (every-dur) units of load
    load_per = dur * factor + (every - dur)
    u = due * load_per / every               # load units, mean rate kept
    k, r = np.divmod(u, load_per)
    t = np.where(r < dur * factor, r / factor, dur + (r - dur * factor))
    return k * every + t


def serve_requests(mix: Dict, vocab: int, seed: int, horizon_s: float) -> List[Dict]:
    """The run's requests.  Open loop: as many as ``rate_per_s`` x
    ``horizon_s``, each with ``due_s`` from the start of traffic.  Closed
    loop: ``population`` requests dealt round-robin to ``clients``, each
    client working through its own list until it is told to stop."""
    open_loop = mix.get("loop") == "open"
    if open_loop:
        n = max(1, int(round(float(mix["rate_per_s"]) * horizon_s)))
    else:
        n = int(mix.get("population", 4096))
    pop = population(mix, n)
    rng = rng_for(seed, 3)
    fixed = mix.get("order", "seeded") == "fixed"
    order = np.arange(n) if fixed else rng.permutation(n)
    plen, new = pop["prompt_len"][order], pop["max_new"][order]
    shared = mix.get("shared_prefix") or {}
    share, pre_len = float(shared.get("share", 0)), int(shared.get("length", 0))
    prefix = rng.integers(2, vocab, pre_len).tolist() if pre_len else []
    with_prefix = rng.random(n) < share
    reqs = []
    for i in range(n):
        body = rng.integers(2, vocab, int(plen[i])).tolist()
        if with_prefix[i] and pre_len:
            body = (prefix + body)[:max(int(plen[i]), min(
                pre_len + 1, int(mix["prompt_len"]["max"])))]
        reqs.append({"id": i, "prompt": body, "max_new": int(new[i])})
    if open_loop:
        gaps = pop["gaps"] if fixed else pop["gaps"][rng.permutation(n)]
        due = _apply_bursts(np.cumsum(gaps), mix.get("burst") or {})
        for r, d in zip(reqs, due):
            r["due_s"] = float(d)
    else:
        clients = int(mix["clients"])
        for r in reqs:
            r["client"] = r["id"] % clients
    return reqs
