"""Family ``afmoe``: Arcee's Trinity block as Trinity-Mini configures it (a
decoder-only model whose attention is QK-normed and gated, with a rotary
sliding window in three layers of four and a position-free global layer in
the fourth, four norms a layer, and sigmoid-routed experts beside a shared
one), as ``paddle_tpu.models.afmoe`` builds it and ``PagedLMGenerator``
serves it.  The only file of the harness that knows this model.  It has a
serving half only: every op of the block is an inference op (ROADMAP M10).

A configuration may be one chip's SHARE of a deployment: ``num_experts``
counts the experts held here (``published`` states the router's width),
``vocab_size`` the rows of the vocabulary held.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Optional, Tuple

from perfbench import manifest as mf
from perfbench import weights
# What is no model's own (an engine counter over the window, the clients'
# records, which traced kernels take an operand of a given shape) and what
# a (token, expert) pair needs of an expert of d x f (the same keys here;
# the shared expert is no grouped product and is not counted) is the first
# decoder-only family's code, not a second copy of it.
from perfbench.families.mimo_v2_flash import (  # noqa: F401
    _itemsize, _kernels_where, decoded_and_prefilled, engine_delta,
    expert_need)

ref = mf.load_reference(__file__)       # perfbench/reference/afmoe.py
param_shapes = ref.param_shapes

# the published keys the program's model file reads
MODEL_KEYS = ref.KEYS + ("published", "model_type")
ENGINE_KEYS = ("param_prefix", "src_len", "max_out_len", "page_size",
               "window_page_size", "num_pages", "window_pages", "chunk_size",
               "prefill_slots", "kv_dtype", "dtype", "start_id", "end_id",
               "prefix_sharing")

# --rehearse-cpu: a different, tiny model that keeps every mechanism (both
# kinds of layer in the published order, a window of 8 under contexts of up
# to 56, 2 dense layers, 32 experts of which 8 are held and 4 a token, a
# shared expert, the gate, QK-norm, the four norms, the embedding scale);
# never a measurement.
REHEARSAL_MODEL = {
    "num_hidden_layers": 4, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 8,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_experts": 8, "num_experts_per_tok": 4, "vocab_size": 64,
    "published": {"num_experts": 32}}
REHEARSAL = {
    "serve": {
        "cfg": dict(REHEARSAL_MODEL, src_len=40, max_out_len=16,
                    page_size=8, window_page_size=4, num_pages=None,
                    window_pages=None, chunk_size=8, prefill_slots=2,
                    n_slots=4, end_id=64, kv_dtype="float32",
                    dtype="float32",
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
    every RMSNorm scale (the four of a layer, QK-norm's two, the last) is
    1 + noise; the stacked expert matrices [held, in, out] take the
    ``embedding`` rule, whose scale is shape[1] ** -0.5 = fan-in ** -0.5;
    the embedding table takes it too: rows of unit norm, which the
    model's sqrt(d) brings to entries of order one, the size of what each
    sub-block adds after its output norm."""
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
    cannot build this model fails HERE, before 7 GB of weights are made."""
    try:
        from paddle_tpu.models import decoder_lm
        from paddle_tpu.serving import paged_lm  # noqa: F401

        decoder_lm(cfg["model_type"])
    except (ImportError, KeyError) as e:
        raise mf.FamilyError(
            f"family afmoe: the program serves no decoder-only model of "
            f"model_type {cfg['model_type']!r} (paddle_tpu/models/afmoe.py):"
            f" {e}") from e
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
    as wide as one, so their NUMBER over the limit is what is limited.
    The number the harness compares is the larger of a request's widest
    free gap and the widest judged set-aside gap of the sample.  Prints
    what the sample says of routing: near-ties, tokens set aside and
    exempt, the share of the set-aside tokens over the limit (the
    control's too), the widest gaps as they are and the one judged."""
    def make(shapes):
        return weights.make(shapes, seed, kind_of=leaf_kind)

    gaps, control, routing = ref.served_logit_gaps(
        make, cfg["param_prefix"], cfg, prompts, outputs, control_precision,
        longest=cfg["src_len"] + cfg["max_out_len"])
    share = cfg["check"].get("set_aside_exempt_share", 0.0)
    exempt = math.ceil(share * len(gaps["set_aside"]))
    limit = cfg["check"]["logit_gap_max"]

    def judged(found):
        rest = found["set_aside"][exempt:]      # widest first
        return [max(free, rest[0] if rest else 0.0)
                for free in found["free"]]

    def over(found):
        return sum(g > limit for g in found["set_aside"]) \
            / max(len(found["set_aside"]), 1)

    if control_precision != "float32":
        routing.update(control_set_aside_over_limit_share=over(control),
                       control_gap_max_free=max(control["free"],
                                                default=0.0))
    routing.update(set_aside_margin=ref.SET_ASIDE,
                   set_aside_over_limit_share=over(gaps),
                   set_aside_exempt_share=share, set_aside_exempt=exempt,
                   gap_set_aside_judged=max(
                       gaps["set_aside"][exempt:], default=0.0),
                   limit=limit)
    print(json.dumps({"routing": routing}), flush=True)
    return judged(gaps), judged(control)


# -- operations and bytes ----------------------------------------------------

def expert_shapes(cfg: Dict) -> List[Tuple[int, ...]]:
    """The stacked expert matrices, as a grouped product's operand."""
    e, d, f = (cfg["num_experts"], cfg["hidden_size"],
               cfg["moe_intermediate_size"])
    return [(e, d, f), (e, f, d)]


def _layers_of(cfg: Dict) -> Tuple[int, int]:
    """(window layers, global layers) of the layers held."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    n_win = sum(k == "sliding_attention" for k in kinds)
    return n_win, len(kinds) - n_win


def _attention_need(cfg: Dict, decoded: List[int], prefilled: List[int],
                    n_win: int, n_glob: int) -> Tuple[float, float]:
    """(operations, bytes) the paged-attention calls of ``n_win`` window
    layers and ``n_glob`` global layers need.  ``decoded``: the context
    (positions in the cache, its own included) of every token decoded in
    the window: it reads that many keys and values in a global layer and
    min(context, window) in a window layer.  ``prefilled``: the prompt
    length of every request prefilled in the window, in chunks: chunk c
    reads the keys up to its own end, causally (a window layer: its own
    tokens and the window before them)."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    row = hkv * 2 * dh * _itemsize(cfg, "kv_dtype")     # a key and a value
    win, chunk = cfg["sliding_window"], cfg["chunk_size"]
    per_key = 2.0 * h * 2 * dh                 # one query against one key
    pairs_g = pairs_w = rows_g = rows_w = 0.0
    for ctx in decoded:
        pairs_g += ctx
        rows_g += ctx
        pairs_w += min(ctx, win)
        rows_w += min(ctx, win)
    for plen in prefilled:
        done = 0
        while done < plen:
            m = min(chunk, plen - done)
            pairs_g += m * (done + (m + 1) / 2.0)
            pairs_w += sum(min(done + j + 1, win) for j in range(m))
            rows_g += done + m
            rows_w += min(done + m, m + win - 1)
            done += m
    return per_key * (n_glob * pairs_g + n_win * pairs_w), \
        row * (n_glob * rows_g + n_win * rows_w)


def mixed_attention_need(cfg: Dict, decoded: List[int],
                         prefilled: List[int]) -> Tuple[float, float]:
    """Both kinds of layer together (``mixed_attn_roofline.serve``)."""
    return _attention_need(cfg, decoded, prefilled, *_layers_of(cfg))


def window_attention_need(cfg: Dict, decoded: List[int],
                          prefilled: List[int]) -> Tuple[float, float]:
    return _attention_need(cfg, decoded, prefilled, _layers_of(cfg)[0], 0)


def global_attention_need(cfg: Dict, decoded: List[int],
                          prefilled: List[int]) -> Tuple[float, float]:
    return _attention_need(cfg, decoded, prefilled, 0, _layers_of(cfg)[1])


# -- what the per-layer readers share ----------------------------------------

def expert_kernels(layer: Dict) -> List[Dict]:
    """Those that take a stacked expert matrix."""
    stacks = {tuple(s) for s in expert_shapes(layer["cfg"])}
    return _kernels_where(layer, lambda dims: dims in stacks)


def pool_shapes(cfg: Dict) -> Dict[str, Tuple[int, int, int]]:
    """kind -> the shape of its key pool and of its value pool (the same:
    keys and values are equally wide), as the engine lays them out: a row
    a page a layer of the kind, a page's tokens, KV heads x head width;
    the window group's ring is ``ceil((chunk + window - 2) / page) + 1``
    pages a lane.  Both kinds' rows are equally wide here, so the two
    groups are told apart by their pools' FIRST dimension."""
    n_win, n_glob = _layers_of(cfg)
    width = cfg["num_key_value_heads"] * cfg["head_dim"]
    lanes = cfg["n_slots"]
    ps = cfg["page_size"]
    wps = cfg.get("window_page_size") or ps
    pages = cfg.get("num_pages")
    if pages is None:
        pages = lanes * -(-(cfg["src_len"] + cfg["max_out_len"]) // ps) + 1
    wpages = cfg.get("window_pages")
    if wpages is None:
        wpages = lanes * (-(-(cfg["chunk_size"] + cfg["sliding_window"] - 2)
                            // wps) + 1) + 1
    out = {}
    if n_glob:
        out["global"] = (pages * n_glob, ps, width)
    if n_win:
        out["window"] = (wpages * n_win, wps, width)
    return out


def attention_kernels(layer: Dict, kind: Optional[str] = None) -> List[Dict]:
    """Those that take a KV pool: of both groups, or of ``kind`` alone."""
    pools = pool_shapes(layer["cfg"])
    shapes = set(pools.values()) if kind is None else {pools.get(kind)}
    return _kernels_where(layer, lambda dims: dims in shapes)
