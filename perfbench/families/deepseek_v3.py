"""Family ``deepseek_v3``: the DeepSeek-V3 block as Moonlight-16B-A3B
configures it (a decoder-only model with latent attention — one 576-wide
cache row a token a layer — and sigmoid-routed experts beside shared
experts), as ``paddle_tpu.models.deepseek_v3`` builds it and
``PagedLMGenerator`` serves it.  The only file of the harness that knows
this model.  It has a serving half only: every op of the block is an
inference op (ROADMAP M10).

A configuration may be one chip's SHARE of a deployment
(``n_routed_experts`` the experts held here where ``published`` states the
router's width, ``vocab_size`` the rows of the vocabulary's slice);
``moonlight-16b-a3b-l5`` holds every layer whole and cuts depth alone.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Optional, Tuple

from perfbench import manifest as mf
from perfbench import weights
# What is no model's own (an engine counter over the window, the clients'
# records) and what this model's expert layer has in common with the other
# decoder-only family's (stacked matrices [experts held, d, f]; a pair's
# three products; the shared experts are no grouped product and are not
# counted) is that family's code, not a second copy of it.
from perfbench.families.mimo_v2_flash import (  # noqa: F401
    _itemsize, _kernels_where, decoded_and_prefilled, engine_delta,
    expert_kernels, expert_need, expert_shapes)

ref = mf.load_reference(__file__)       # perfbench/reference/deepseek_v3.py
param_shapes = ref.param_shapes

# the published keys the program's model file reads
MODEL_KEYS = ref.KEYS + ("published", "model_type")
ENGINE_KEYS = ("param_prefix", "src_len", "max_out_len", "page_size",
               "num_pages", "chunk_size", "prefill_slots", "kv_dtype",
               "dtype", "start_id", "end_id", "prefix_sharing")

# --rehearse-cpu: a different, tiny model that keeps every mechanism (a
# dense layer and two expert layers, 8 experts all held of which 2 a token,
# one shared expert, a latent of 16 beside a rotary key of 8: a cache row
# of 24); never a measurement.
REHEARSAL_MODEL = {
    "num_hidden_layers": 3, "hidden_size": 32, "num_attention_heads": 4,
    "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 8,
    "v_head_dim": 12, "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "vocab_size": 64}
REHEARSAL = {
    "serve": {
        "cfg": dict(REHEARSAL_MODEL, src_len=40, max_out_len=16,
                    page_size=8, num_pages=None, chunk_size=8,
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
    selection bias is seeded noise (``bias``), so that dropping it shows;
    RMSNorm scales (the latent's among them) are 1 + noise; the stacked
    expert matrices [held, in, out] take the ``embedding`` rule, whose
    scale is shape[1] ** -0.5 = fan-in ** -0.5; the embedding table takes
    it too (rows of unit norm: the first RMSNorm brings them to order one,
    and a plain lookup has no sqrt(d) scale)."""
    if name.endswith("_norm.w"):
        return "ln_scale"
    if name.endswith("router.bias"):
        return "bias"
    if ".experts." in name:
        return "embedding"
    return None


def serving(cfg: Dict) -> Dict:
    """The artifact's manifest (kind ``lm_generator``: the decoder-only
    paged generator), lanes, token limit and vocabulary.  A program that
    cannot build this model fails HERE, before 12 GB of weights are made."""
    try:
        from paddle_tpu.models import decoder_lm
        from paddle_tpu.serving import paged_lm  # noqa: F401

        decoder_lm(cfg["model_type"])
    except (ImportError, KeyError) as e:
        raise mf.FamilyError(
            f"family deepseek_v3: the program serves no decoder-only model "
            f"of model_type {cfg['model_type']!r} "
            f"(paddle_tpu/models/deepseek_v3.py): {e}") from e
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
    logit lies below the reference's best (full forward in the expanded
    form, layer by layer, that layer's weights made from the seed); and
    the same for the token the control's lower precision puts first.
    ``logit_gap_max`` (``cfg["check"]``) limits every token that has no
    routing near-tie, and of the SET-ASIDE tokens, which have one (the
    reference's ``SET_ASIDE`` says why), all but the widest
    ``set_aside_exempt_share``: a flip at a near-tie is no fault and reads
    as wide as one, and with every expert held most tokens have a
    near-tie and one in seven flips; lower precision or a fault misses on
    four in five.  The number the harness compares is the larger of a
    request's widest free gap and the widest judged set-aside gap of the
    sample.  Prints what the sample says of routing: near-ties, tokens set
    aside and exempt, the share of the set-aside tokens over the limit
    (the control's too), the widest gaps as they are and the one judged."""
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

    limit = cfg["check"]["logit_gap_max"]

    def over(found):
        return sum(g > limit for g in found["set_aside"]) \
            / max(len(found["set_aside"]), 1)

    if control_precision != "float32":
        routing.update(control_set_aside_over_limit_share=over(control))
    routing.update(set_aside_margin=ref.SET_ASIDE,
                   set_aside_over_limit_share=over(gaps),
                   set_aside_exempt_share=cfg["check"].get(
                       "set_aside_exempt_share", 0.0),
                   set_aside_exempt=exempt,
                   gap_set_aside_judged=max(
                       gaps["set_aside"][exempt:], default=0.0),
                   limit=limit)
    print(json.dumps({"routing": routing}), flush=True)
    return judged(gaps), judged(control)


# -- operations and bytes ----------------------------------------------------

def row_width(cfg: Dict) -> int:
    """A token's row in the latent pool: latent + rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_attention_need(cfg: Dict, decoded: List[int],
                          prefilled: List[int]) -> Tuple[float, float]:
    """(operations, bytes) the latent paged-attention calls of a window
    need, in the absorbed form the kernel computes.  ``decoded``: the
    context (positions in the cache, its own included) of every token
    decoded in the window: in each layer it reads that many rows (once,
    for all heads) and every head scores each row over its whole width and
    sums its leading ``kv_lora_rank`` columns.  ``prefilled``: the prompt
    length of every request prefilled in the window, in chunks: chunk c's
    queries see the rows up to their own, causally, and the rows up to the
    chunk's end are read once."""
    n, h = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    row, rank = row_width(cfg), cfg["kv_lora_rank"]
    item = _itemsize(cfg, "kv_dtype")
    chunk = cfg["chunk_size"]
    per_key = 2.0 * h * (row + rank)           # one query against one row
    ops = bytes_ = 0.0
    for ctx in decoded:
        ops += per_key * n * ctx
        bytes_ += n * ctx * row * item
    for plen in prefilled:
        done = 0
        while done < plen:
            m = min(chunk, plen - done)
            ops += per_key * n * m * (done + (m + 1) / 2.0)
            bytes_ += n * (done + m) * row * item
            done += m
    return ops, bytes_


# -- what the per-layer readers share ----------------------------------------

def attention_kernels(layer: Dict) -> List[Dict]:
    """Those that take the latent pool ([rows, page, a row]): the
    ``paged_attn_latent`` calls.  The program may allocate a row in whole
    lane tiles (576 numbers in 640 columns): any width from the row's own
    to under a tile more is the pool's."""
    width, page = row_width(layer["cfg"]), layer["cfg"]["page_size"]
    return _kernels_where(
        layer, lambda dims: len(dims) == 3 and dims[1] == page
        and width <= dims[2] < width + 128)
