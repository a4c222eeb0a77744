"""Family ``mimo_v2_flash``: MiMo-V2-Flash's block (a decoder-only model
with global and sliding-window attention layers side by side and
sigmoid-routed experts), as ``paddle_tpu.models.mimo_v2_flash`` builds it
and ``PagedLMGenerator`` serves it.  The only file of the harness that
knows this model.  It has a serving half only: at 16 bytes a parameter no
cut inside the guide's floors fits one chip in training.

A configuration is one chip's SHARE of a deployment: ``n_routed_experts``
counts the experts held here (``published`` states the router's width),
``vocab_size`` the rows of the vocabulary's slice.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Optional, Tuple

from perfbench import manifest as mf
from perfbench import weights

ref = mf.load_reference(__file__)       # perfbench/reference/mimo_v2_flash.py
param_shapes = ref.param_shapes

# the published keys the program's model file reads
MODEL_KEYS = ref.KEYS + ("published", "model_type")
ENGINE_KEYS = ("param_prefix", "src_len", "max_out_len", "page_size",
               "window_page_size", "chunk_size", "prefill_slots",
               "kv_dtype", "dtype", "start_id", "end_id", "prefix_sharing")

# --rehearse-cpu: a different, tiny model that keeps every mechanism (both
# kinds of layer, a window of 8 under contexts of up to 56, 32 experts of
# which 8 are held, 8 a token); never a measurement.
REHEARSAL_MODEL = {
    "num_hidden_layers": 3, "hidden_size": 32, "num_attention_heads": 4,
    "head_dim": 24, "v_head_dim": 16, "num_key_value_heads": 1,
    "swa_num_key_value_heads": 2, "sliding_window": 8,
    "intermediate_size": 64, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "vocab_size": 64,
    "published": {"n_routed_experts": 32}}
REHEARSAL = {
    "serve": {
        "cfg": dict(REHEARSAL_MODEL, src_len=40, max_out_len=16,
                    page_size=8, window_page_size=4, chunk_size=8,
                    prefill_slots=2, n_slots=4, end_id=64,
                    kv_dtype="float32", dtype="float32",
                    # CPU float32 is exact to rounding (sound 1e-5 at
                    # most); the float8 control reads 0.05 and more
                    check={"logit_gap_max": 0.005}),
        "mix": {"clients": 6, "workers": 16,
                "prompt_len": {"dist": "lognormal", "median": 12,
                               "sigma": 0.7, "min": 2, "max": 40},
                "max_new": {"dist": "uniform", "min": 3, "max": 16},
                "ramp_s": 0.5, "population": 256, "check_sample": 4,
                "trace_seconds": 1},
    },
}


def leaf_kind(name: str) -> Optional[str]:
    """Which of ``perfbench.weights.KINDS`` a leaf is drawn as.  The
    selection bias and the sink logits are seeded noise (``bias``), so
    that dropping either shows; RMSNorm scales are 1 + noise; the stacked
    expert matrices [held, in, out] take the ``embedding`` rule, whose
    scale is shape[1] ** -0.5 = fan-in ** -0.5; the embedding table takes
    it too (rows of unit norm: the first RMSNorm brings them to order one,
    and a plain lookup has no sqrt(d) scale)."""
    if name.endswith("_norm.w"):
        return "ln_scale"
    if name.endswith(".sink") or name.endswith("router.bias"):
        return "bias"
    if ".experts." in name:
        return "embedding"
    return None


def serving(cfg: Dict) -> Dict:
    """The artifact's manifest (kind ``lm_generator``: the decoder-only
    paged generator), lanes, token limit and vocabulary.  A program
    without that generator fails HERE, before 9 GB of weights are made."""
    try:
        from paddle_tpu.serving import paged_lm  # noqa: F401
    except ImportError as e:
        raise mf.FamilyError(
            "family mimo_v2_flash: the program has no decoder-only paged "
            f"generator (paddle_tpu/serving/paged_lm.py): {e}") from e
    config = {k: cfg[k] for k in ENGINE_KEYS}
    config["model"] = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    config["lanes"] = cfg["n_slots"]
    return {"manifest": {"kind": "lm_generator", "config": config},
            "n_slots": cfg["n_slots"], "max_new_tokens": cfg["max_out_len"],
            "vocab": cfg["vocab_size"]}


def served_logit_gaps(cfg: Dict, seed: int, prompts: List[List[int]],
                      outputs: List[List[int]],
                      control_precision: str = "float32"):
    """Per request, the widest gap by which a served token's reference
    logit lies below the reference's best (full forward, layer by layer,
    that layer's weights made from the seed); and the same for the token
    the control's lower precision puts first.  ``logit_gap_max``
    (``cfg["check"]``) limits every token that has no routing near-tie
    concerning this share, and of the SET-ASIDE tokens, which have one
    (the reference's ``SET_ASIDE`` says why), all but the widest
    ``set_aside_exempt_share``: a flip at a near-tie is no fault and reads
    as wide as one, but a run has a handful of flips, and a fault or lower
    precision misses on every tenth token.  The number the harness
    compares is the larger of a request's widest free gap and the widest
    judged set-aside gap of the sample.  Prints what the sample says of
    routing: near-ties, tokens set aside and exempt, the widest gaps as
    they are and the one judged."""
    def make(shapes):
        return weights.make(shapes, seed, kind_of=leaf_kind)

    gaps, control, routing = ref.served_logit_gaps(
        make, cfg["param_prefix"], cfg, prompts, outputs, control_precision,
        longest=cfg["src_len"] + cfg["max_out_len"])
    exempt = math.ceil(cfg["check"].get("set_aside_exempt_share", 0.0)
                       * len(gaps["set_aside"]))

    def judged(found):
        rest = found["set_aside"][exempt:]      # widest first
        return [max(free, rest[0] if rest else 0.0)
                for free in found["free"]]

    routing.update(set_aside_exempt=exempt,
                   gap_set_aside_judged=max(
                       gaps["set_aside"][exempt:], default=0.0),
                   limit=cfg["check"]["logit_gap_max"])
    print(json.dumps({"routing": routing}), flush=True)
    return judged(gaps), judged(control)


# -- operations and bytes ----------------------------------------------------

def _itemsize(cfg: Dict, key: str) -> int:
    return {"float32": 4, "bfloat16": 2}[cfg[key]]


def expert_shapes(cfg: Dict) -> List[Tuple[int, ...]]:
    """The stacked expert matrices, as a grouped product's operand."""
    e, d, f = (cfg["n_routed_experts"], cfg["hidden_size"],
               cfg["moe_intermediate_size"])
    return [(e, d, f), (e, f, d)]


def pool_widths(cfg: Dict) -> List[int]:
    """The row widths of the four KV pools (keys, values; global, window)."""
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    return [h * w for h in (cfg["num_key_value_heads"],
                            cfg["swa_num_key_value_heads"]) for w in (dk, dv)]


def expert_need(cfg: Dict, pairs: float, experts_touched: float
                ) -> Tuple[float, float]:
    """(operations, bytes) the grouped expert products of a window need:
    a (token, expert) pair is three products of d x f multiply-adds (gate,
    up, down) and moves its rows; an expert that got a pair in a step has
    its three matrices read once in that step.  Pairs for absent experts
    need nothing."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    item = _itemsize(cfg, "dtype")
    ops = pairs * 3 * 2.0 * d * f
    bytes_ = experts_touched * 3.0 * d * f * item \
        + pairs * (2 * d + 2 * f + 2 * f + d) * item
    return ops, bytes_


def mixed_attention_need(cfg: Dict, decoded: List[int],
                         prefilled: List[int]) -> Tuple[float, float]:
    """(operations, bytes) the paged-attention calls of a window need.
    ``decoded``: the context (positions in the cache, its own included) of
    every token decoded in the window: it reads that many keys and values
    in each global layer and min(context, window) in each window layer.
    ``prefilled``: the prompt length of every request prefilled in the
    window, in chunks: chunk c reads the keys up to its own end, causally
    (a window layer: its own tokens and the window before them)."""
    n = cfg["num_hidden_layers"]
    kinds = cfg["hybrid_layer_pattern"][:n]
    n_win = sum(1 for k in kinds if k)
    n_glob = n - n_win
    h, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    item = _itemsize(cfg, "kv_dtype")
    row_g = cfg["num_key_value_heads"] * (dk + dv) * item
    row_w = cfg["swa_num_key_value_heads"] * (dk + dv) * item
    win, chunk = cfg["sliding_window"], cfg["chunk_size"]
    per_key = 2.0 * h * (dk + dv)              # one query against one key
    ops = bytes_ = 0.0
    for ctx in decoded:
        seen = min(ctx, win)
        ops += per_key * (n_glob * ctx + n_win * seen)
        bytes_ += n_glob * ctx * row_g + n_win * seen * row_w
    for plen in prefilled:
        done = 0
        while done < plen:
            m = min(chunk, plen - done)
            pairs_g = m * (done + (m + 1) / 2.0)
            pairs_w = sum(min(done + j + 1, win) for j in range(m))
            ops += per_key * (n_glob * pairs_g + n_win * pairs_w)
            bytes_ += n_glob * (done + m) * row_g \
                + n_win * min(done + m, m + win - 1) * row_w
            done += m
    return ops, bytes_


# -- what the per-layer readers share ----------------------------------------

def _kernels_where(layer: Dict, is_ours) -> List[Dict]:
    """The traced Mosaic kernels one of whose operands' dims ``is_ours``."""
    return [k for k in (layer.get("trace") or {}).get("kernels", [])
            if any(is_ours(tuple(dims)) for _dtype, dims in k["operands"])]


def expert_kernels(layer: Dict) -> List[Dict]:
    """Those that take a stacked expert matrix."""
    stacks = {tuple(s) for s in expert_shapes(layer["cfg"])}
    return _kernels_where(layer, lambda dims: dims in stacks)


def attention_kernels(layer: Dict) -> List[Dict]:
    """Those that take a KV pool ([rows, page, heads x width])."""
    widths = set(pool_widths(layer["cfg"]))
    return _kernels_where(layer,
                          lambda dims: len(dims) == 3 and dims[2] in widths)


def engine_delta(layer: Dict, key: str) -> Optional[float]:
    """A counter of the engine (``sched.stats()["engine"]``) over the
    window; None where the program has no such counter."""
    before = (layer.get("before") or {}).get("engine") or {}
    after = (layer.get("after") or {}).get("engine") or {}
    if key not in before or key not in after:
        return None
    return float(after[key]) - float(before[key])


def decoded_and_prefilled(layer: Dict) -> Tuple[List[int], List[int]]:
    """From the clients' records: the context of every token received in
    the window (token i of a request attends prompt + i positions; the
    first comes out of the prefill's last chunk) and the prompt of every
    request sent in it."""
    lo, hi = layer["t_open"], layer["t_close"]
    decoded, prefilled = [], []
    for rec in layer["records"]:
        plen = len(layer["requests"][rec["id"]]["prompt"])
        decoded += [plen + i for i, when in enumerate(rec["times"])
                    if i > 0 and lo <= when < hi]
        if rec["sent"] is not None and lo <= rec["sent"] < hi:
            prefilled.append(plen)
    return decoded, prefilled
