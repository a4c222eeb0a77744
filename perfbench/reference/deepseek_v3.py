"""Plain reference: the DeepSeek-V3 block as Moonlight-16B-A3B configures it
(moonshotai/Moonlight-16B-A3B, ``config.json``, ``model_type:
"deepseek_v3"``) in straightforward ``jax.numpy``, float32 with
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
paging, no batching, and attention in the published EXPANDED form (every
position's latent is projected up to per-head keys and values; nothing is
absorbed).  It imports nothing of the program; the weights come from
``perfbench.weights`` (the seed) under the program's parameter names.

The layer equations, ``x`` [T, d] the float32 residual stream, H heads,
``pos`` a token's position:

    u = RMSNorm(x; g_attn, eps)
    q = u Wq -> [T, H, nope + rope] = [q_nope | q_rope];
        q_rope = RoPE(q_rope, pos)   (base rope_theta, all ``rope`` dims)
    [c | k_r] = u Wkva -> rank + rope; c = RMSNorm(c; g_kv, eps);
        k_r = RoPE(k_r, pos): ONE rotary key a token, shared by all heads
    [k_nope_h | v_h] = c Wkvb   (a head: nope + v columns)
    k_h = [k_nope_h | k_r];  a_h(t, j) = q_h(t) . k_h(j) / sqrt(nope + rope)
    p_h(t, .) = softmax_{j <= t} a_h(t, .);  o_h = sum_j p_h(t, j) v_h(j)
    x = x + concat_h(o_h) Wo
    w = RMSNorm(x; g_ffn, eps)
    dense layer (i < first_k_dense_replace):
        y = (silu(w Wg) * (w Wu)) Wd
    expert layer: s = sigmoid(w Wr) over all experts (float32);
        S = the num_experts_per_tok largest of s + b
        (e_score_correction_bias; n_group = topk_group = 1);
        g_e = routed_scaling_factor * s_e / (sum_{e' in S} s_e' + 1e-20)
        (norm_topk_prob; b never enters g);
        y = sum over e in S AND HELD HERE of g_e FFN_e(w)  +  FFN_shared(w)
        FFN_shared ONE gated feed-forward of n_shared_experts x the expert
        width, which every token passes
    x = x + y
    logits = RMSNorm(x; g_out) W_head, untied; the embedding a plain lookup

The SHARE: the configuration holds ``n_routed_experts`` experts from
``first_expert`` of the ``published.n_routed_experts`` the router scores
(all of them, where nothing is published beside it), the shared experts
whole, and ``vocab_size`` rows of the vocabulary.  ``moe(..., shared=
False)`` leaves the shared experts out, for the test that adds the shares
of a divided layer up (the shared experts count once).

Assumed (the config has no key; the family's convention): rotary pairs as
halves (dim i pairs with i + rope/2; the checkpoint's interleaved pairs
are a fixed permutation of Wq's and Wkva's rotary columns); g_kv's epsilon
is ``rms_norm_eps``; no rotary scaling (the config has no
``rope_scaling``), so the softmax scale is (nope + rope) ** -0.5; no
low-rank query projection (``q_lora_rank`` null); no
multi-token-prediction layer.

``precision`` selects the arithmetic of every matrix product:
``"float32"`` is the reference proper; ``"bfloat16"`` and ``"float8"``
(e4m3, one scale per operand tensor) round both operands and exist only
for the control that shows ``correct`` failing in a lower precision than
the configuration states.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512           # queries scored at a time (and the length bucket)


# -- the configuration's sizes ----------------------------------------------

def sizes(cfg: Dict) -> Dict:
    n = int(cfg["num_hidden_layers"])
    dense = int(cfg["first_k_dense_replace"])
    freq = int(cfg.get("moe_layer_freq", 1))
    scale = cfg.get("routed_scaling_factor")
    return {
        "n": n, "d": int(cfg["hidden_size"]),
        "h": int(cfg["num_attention_heads"]),
        "rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]), "vocab": int(cfg["vocab_size"]),
        "moe": [i >= dense and i % freq == 0 for i in range(n)],
        "held": int(cfg["n_routed_experts"]),
        "first": int(cfg.get("first_expert", 0)),
        "experts": int(cfg.get("published", {}).get(
            "n_routed_experts", cfg["n_routed_experts"])),
        "shared": int(cfg.get("n_shared_experts") or 0),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": 1.0 if scale is None else float(scale),
        "f_dense": int(cfg["intermediate_size"]),
        "f_moe": int(cfg["moe_intermediate_size"]),
    }


def layer_shapes(cfg: Dict, prefix: str, i: int) -> Dict[str, Tuple]:
    """name -> shape of layer ``i``'s parameters."""
    z = sizes(cfg)
    p, d, h = f"{prefix}.l{i}", z["d"], z["h"]
    out = {f"{p}.attn_norm.w": (d,),
           f"{p}.attn.q.w": (d, h * (z["nope"] + z["rope"])),
           f"{p}.attn.kva.w": (d, z["rank"] + z["rope"]),
           f"{p}.attn.kv_norm.w": (z["rank"],),
           f"{p}.attn.kvb.w": (z["rank"], h * (z["nope"] + z["dv"])),
           f"{p}.attn.out.w": (h * z["dv"], d), f"{p}.ffn_norm.w": (d,)}
    if z["moe"][i]:
        e, f = z["held"], z["f_moe"]
        out[f"{p}.moe.router.w"] = (d, z["experts"])
        out[f"{p}.moe.router.bias"] = (z["experts"],)
        out[f"{p}.moe.experts.gate.w"] = (e, d, f)
        out[f"{p}.moe.experts.up.w"] = (e, d, f)
        out[f"{p}.moe.experts.down.w"] = (e, f, d)
        if z["shared"]:
            fs = z["shared"] * f
            out[f"{p}.moe.shared.gate.w"] = (d, fs)
            out[f"{p}.moe.shared.up.w"] = (d, fs)
            out[f"{p}.moe.shared.down.w"] = (fs, d)
    else:
        f = z["f_dense"]
        out[f"{p}.ffn.gate.w"] = (d, f)
        out[f"{p}.ffn.up.w"] = (d, f)
        out[f"{p}.ffn.down.w"] = (f, d)
    return out


def outer_shapes(cfg: Dict, prefix: str) -> Dict[str, Tuple]:
    z = sizes(cfg)
    return {f"{prefix}.emb.w": (z["vocab"], z["d"]),
            f"{prefix}.out_norm.w": (z["d"],),
            f"{prefix}.head.w": (z["d"], z["vocab"])}


def param_shapes(cfg: Dict, prefix: str) -> Dict[str, Tuple]:
    out = dict(outer_shapes(cfg, prefix))
    for i in range(sizes(cfg)["n"]):
        out.update(layer_shapes(cfg, prefix, i))
    return out


# -- arithmetic -------------------------------------------------------------

def _fp8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _rounder(precision: str):
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        return _fp8
    raise ValueError(f"unknown precision {precision!r}")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary(x, pos, base: float):
    """x [T, H, D]: every dim rotates, halves paired."""
    half = x.shape[-1] // 2
    inv = jnp.power(jnp.float32(base),
                    -jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(r, W, p, u, cfg, z):
    """Attn(u) of one sequence u [T, d] in the expanded form: full
    forward, queries scored ``Q_BLOCK`` at a time so that [heads, block,
    T] fits."""
    t = u.shape[0]
    h, nope, rope, dv = z["h"], z["nope"], z["rope"], z["dv"]
    base, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    mm = lambda a, b: jnp.matmul(r(a), r(b), precision=_HI)    # noqa: E731
    pos = jnp.arange(t)
    q = mm(u, W[f"{p}.attn.q.w"]).reshape(t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], pos, base)],
                        axis=-1)
    kva = mm(u, W[f"{p}.attn.kva.w"])
    c = rms_norm(kva[:, :z["rank"]], W[f"{p}.attn.kv_norm.w"], eps)
    k_r = rotary(kva[:, None, z["rank"]:], pos, base)       # [T, 1, rope]
    kv = mm(c, W[f"{p}.attn.kvb.w"]).reshape(t, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (t, h, rope))], axis=-1)
    v = kv[..., nope:]

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, 0)
        a = jnp.einsum("qhd,khd->hqk", r(qb), r(k), precision=_HI) \
            * (float(nope + rope) ** -0.5)
        keep = jnp.arange(t)[None, :] <= (q0 + jnp.arange(Q_BLOCK))[:, None]
        a = jnp.where(keep[None], a, -jnp.inf)
        e = jnp.exp(a - jnp.max(a, axis=-1, keepdims=True))
        prob = e / jnp.sum(e, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", r(prob), r(v), precision=_HI)

    # t is a multiple of Q_BLOCK (``forward_logits`` pads)
    o = jax.lax.map(block, jnp.arange(0, t, Q_BLOCK))
    return mm(o.reshape(t, h * dv), W[f"{p}.attn.out.w"])


def gated_ffn(r, x, wg, wu, wd):
    mm = lambda a, b: jnp.matmul(r(a), r(b), precision=_HI)    # noqa: E731
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def route(r, W, p, w, z):
    """(selected [T, k], weights g [T, k], margins [T, 2]): the ``top_k``
    experts of s + b, their g_e, and two margins a token.  First: how far
    the last expert selected lies above the first one left out.  Second:
    how far scores would have to move for THIS SHARE's result to change,
    that is for an expert held here to leave the selection or to enter it
    (with every expert held, the first margin again)."""
    s = jax.nn.sigmoid(jnp.matmul(r(w), r(W[f"{p}.moe.router.w"]),
                                  precision=_HI))
    k = z["top_k"]
    v = s + W[f"{p}.moe.router.bias"]
    top, idx = jax.lax.top_k(v, k + 1)
    last_in, first_out = top[:, k - 1:k], top[:, k:k + 1]
    margin = (last_in - first_out)[:, 0]
    mine = v[:, z["first"]:z["first"] + z["held"]]
    here = jnp.min(jnp.where(mine >= last_in, mine - first_out,
                             last_in - mine), axis=-1)
    idx = idx[:, :k]
    sel = jnp.take_along_axis(s, idx, axis=-1)
    g = z["scale"] * sel / (jnp.sum(sel, axis=-1, keepdims=True) + 1e-20)
    return idx, g, jnp.stack([margin, here], axis=-1)


def moe(r, W, p, w, z, first=None, held=None, shared=True):
    """This share's part of the expert layer on w: experts ``first ..
    first + held - 1`` (every token through every held expert, weighted by
    g_e where the token chose it and by 0 where it did not: the plain
    form), plus the shared experts unless ``shared`` is False."""
    first = z["first"] if first is None else first
    held = z["held"] if held is None else held
    idx, g, margin = route(r, W, p, w, z)

    def one(out, expert):
        j, wg, wu, wd = expert
        weight = jnp.sum(jnp.where(idx == first + j, g, 0.0), axis=-1)
        return out + weight[:, None] * gated_ffn(r, w, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(w), (
        jnp.arange(held), W[f"{p}.moe.experts.gate.w"][:held],
        W[f"{p}.moe.experts.up.w"][:held],
        W[f"{p}.moe.experts.down.w"][:held]))
    if shared and z["shared"]:
        out = out + gated_ffn(r, w, W[f"{p}.moe.shared.gate.w"],
                              W[f"{p}.moe.shared.up.w"],
                              W[f"{p}.moe.shared.down.w"])
    return out, margin


def layer(W, p: str, cfg: Dict, x, experts: bool,
          precision: str = "float32"):
    """(y, routing margins [T, 2] or None) of one layer on x [T, d]; ``p``
    is the prefix of the layer's parameter names in ``W``."""
    z, r = sizes(cfg), _rounder(precision)
    eps = float(cfg["rms_norm_eps"])
    h = x + attention(r, W, p, rms_norm(x, W[f"{p}.attn_norm.w"], eps),
                      cfg, z)
    w = rms_norm(h, W[f"{p}.ffn_norm.w"], eps)
    if experts:
        y, margin = moe(r, W, p, w, z)
        return h + y, margin
    return h + gated_ffn(r, w, W[f"{p}.ffn.gate.w"], W[f"{p}.ffn.up.w"],
                         W[f"{p}.ffn.down.w"]), None


def head(W, prefix: str, cfg: Dict, x, precision: str = "float32"):
    r = _rounder(precision)
    y = rms_norm(x, W[f"{prefix}.out_norm.w"], float(cfg["rms_norm_eps"]))
    return jnp.matmul(r(y), r(W[f"{prefix}.head.w"]), precision=_HI)


def forward_logits(make: Callable[[Dict], Dict], prefix: str, cfg: Dict,
                   sequences: Sequence[np.ndarray], keep_last: Sequence[int],
                   precision: str = "float32", longest: int = 0):
    """Logits of the last ``keep_last[i]`` positions of each sequence
    (full forward, no cache), LAYER BY LAYER: ``make(shapes)`` returns one
    layer's weights at a time (the seed gives a leaf the same values
    whoever else is made beside it), so the model need not fit whole
    beside its float32 activations.  Every sequence is padded to ONE
    length (``_padded`` of the longest, or of ``longest`` if that is
    more; causal: what follows a position never reaches it), so each kind
    of layer compiles once, whatever the sample; the logits are taken of
    the kept rows only, so positions x vocabulary never exist at once.
    Also returns every routing margin of the kept positions."""
    z = sizes(cfg)
    cfg_items = _items(cfg)
    lens = [len(s) for s in sequences]
    W = make(outer_shapes(cfg, prefix))
    xs = []
    width = _padded(max(lens + [int(longest)]))
    for s in sequences:
        ids = np.concatenate([np.asarray(s, np.int32),
                              np.zeros(width - len(s), np.int32)])
        xs.append(W[f"{prefix}.emb.w"][jnp.asarray(ids)])
    margins: List[List[np.ndarray]] = [[] for _ in sequences]
    for i in range(z["n"]):
        # under one name for every layer, so that layers of a kind share
        # a compiled function
        Wl = {name.replace(f"{prefix}.l{i}.", "layer.", 1): value
              for name, value in make(layer_shapes(cfg, prefix, i)).items()}
        for j in range(len(xs)):
            xs[j], margin = _layer_jit(Wl, xs[j], z["moe"][i], cfg_items,
                                       precision)
            if margin is not None:
                margins[j].append(np.asarray(
                    margin[lens[j] - keep_last[j]:lens[j]]))
        del Wl
    out = []
    for j, x in enumerate(xs):
        rows = x[lens[j] - keep_last[j]:lens[j]]
        out.append(_head_jit(W, rows, prefix, cfg_items, precision))
    return out, margins


def _padded(n: int) -> int:
    """``n`` tokens as whole blocks of ``Q_BLOCK`` queries."""
    return -(-n // Q_BLOCK) * Q_BLOCK


# the configuration keys the equations read
KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
        "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "vocab_size",
        "first_k_dense_replace", "moe_layer_freq", "n_routed_experts",
        "n_shared_experts", "first_expert", "num_experts_per_tok",
        "routed_scaling_factor", "norm_topk_prob", "scoring_func",
        "n_group", "topk_group", "intermediate_size",
        "moe_intermediate_size", "rope_theta", "rms_norm_eps")


def _items(cfg: Dict) -> Tuple:
    """What the equations read of the configuration, hashable (a jitted
    function's static argument)."""
    items = [(k, cfg[k]) for k in KEYS if k in cfg]
    experts = cfg.get("published", {}).get("n_routed_experts")
    return tuple(items) + (("published_experts", experts),)


def _thaw(items) -> Dict:
    cfg = dict(items)
    experts = cfg.pop("published_experts")
    if experts is not None:
        cfg["published"] = {"n_routed_experts": experts}
    return cfg


@functools.partial(jax.jit, static_argnames=("experts", "cfg_items",
                                             "precision"))
def _layer_jit(W, x, experts, cfg_items, precision):
    with jax.default_matmul_precision("highest"):
        return layer(W, "layer", _thaw(cfg_items), x, experts, precision)


@functools.partial(jax.jit, static_argnames=("prefix", "cfg_items",
                                             "precision"))
def _head_jit(W, x, prefix, cfg_items, precision):
    with jax.default_matmul_precision("highest"):
        return head(W, prefix, _thaw(cfg_items), x, precision)


# -- serving ----------------------------------------------------------------

NEAR_TIE = 1e-3         # margins below this are counted (``near_ties``)
# A token is SET ASIDE where in some expert layer the last expert selected
# lies within this of the first one left out (``route``'s second margin:
# with every expert held here it is the first).  There the program's
# scores (float32, of activations that bfloat16 products made) and the
# reference's may select differently, both rightly, and the result then
# differs by one expert's part exchanged for another's, which is no
# rounding.  With all 64 experts of four layers held, six a token, that
# is most tokens (the margin is under 0.01 in 46 % of (token, layer)
# pairs), and on the chip about one set-aside token in seven does differ
# so (flips were read at margins up to 0.015 in nine runs, none from there
# on; the margin leaves that much room again).  No
# limit on the WIDEST gap of these tokens tells such a flip from a fault;
# their NUMBER does: lower precision or a fault misses on four tokens in
# five.  The caller judges all but the widest of them (a share it states)
# like the rest.  PERF.md section 2 holds the margins and gaps read on
# the chip.
SET_ASIDE = 2e-2
# the widest gap is reported by margin
BANDS = (1e-3, 3e-3, 1e-2, 1.5e-2, SET_ASIDE)
WIDEST = 16                         # and the widest few, each beside its own


def served_logit_gaps(make: Callable[[Dict], Dict], prefix: str, cfg: Dict,
                      prompts: List[List[int]], outputs: List[List[int]],
                      control_precision: str = "float32", longest: int = 0):
    """Teacher-force each prompt with the tokens the system served for it
    (full forward: no cache, no paging) and return the gaps by which the
    served tokens' reference logits lie below the reference's best at
    their positions, as ``{"free": [per request, the widest over the
    tokens that are not set aside (``SET_ASIDE``)], "set_aside": [every
    set-aside token's gap, widest first]}``; with ``control_precision``
    below float32 the second is the control, the same for the token the
    lower precision puts first.  Third: what the sample says of routing:
    (token, expert layer) pairs scored, margins under ``NEAR_TIE``, tokens
    checked and set aside, the widest gap of each of the two sets, the
    widest gap by margin (``BANDS``) and the ``WIDEST`` gaps, each as
    (gap, margin, request, position).  ``longest`` is the longest
    sequence the traffic can send: padding to it, every run compiles the
    same shapes."""
    seqs = [np.asarray(list(p) + list(o[:-1]), np.int32)
            for p, o in zip(prompts, outputs)]
    keep = [len(o) for o in outputs]
    ref, margins = forward_logits(make, prefix, cfg, seqs, keep,
                                  longest=longest)
    # margins[j]: one [kept, 2] array an expert layer (route()'s two)
    flat = np.concatenate([m[:, 0] for ms in margins for m in ms]) \
        if any(margins) else np.zeros(0)
    routing = {"scored": int(flat.size),
               "near_ties": int(np.sum(flat < NEAR_TIE)),
               "margin_p01": float(np.percentile(flat, 1)) if flat.size
               else None}
    low = ref
    if control_precision != "float32":
        low, _ = forward_logits(make, prefix, cfg, seqs, keep,
                                control_precision, longest)

    def widest(gap, where):
        return float(np.max(gap, where=where, initial=0.0))

    gaps = {"free": [], "set_aside": []}
    control = {"free": [], "set_aside": []}
    tokens = 0
    edges = (0.0,) + BANDS
    by_margin = [0.0] * len(edges)
    wide = []
    for j, (lg, lo, o, ms) in enumerate(zip(ref, low, outputs, margins)):
        lg = np.asarray(lg)
        best = lg.max(axis=-1)
        rows = np.arange(len(o))
        gap = best - lg[rows, np.asarray(o)]
        low_gap = best - lg[rows, np.asarray(lo).argmax(axis=-1)]
        # the narrowest margin that concerns this share, over the layers
        margin = np.min(np.stack([m[:, 1] for m in ms]), axis=0) \
            if ms else np.full(len(o), np.inf)
        free = margin >= SET_ASIDE
        for out, g in ((gaps, gap), (control, low_gap)):
            out["free"].append(widest(g, free))
            out["set_aside"] += g[~free].tolist()
        tokens += len(o)
        for b, lo_edge in enumerate(edges):
            hi_edge = edges[b + 1] if b + 1 < len(edges) else np.inf
            by_margin[b] = max(by_margin[b], widest(
                gap, (margin >= lo_edge) & ((margin < hi_edge)
                                            | (hi_edge == np.inf))))
        wide += [(float(gap[i]), float(min(margin[i], 1.0)), j, int(i))
                 for i in np.argsort(gap)[-WIDEST:]]
    for out in (gaps, control):
        out["set_aside"].sort(reverse=True)
    routing.update(
        tokens=tokens, set_aside=len(gaps["set_aside"]),
        gap_max_free=max(gaps["free"], default=0.0),
        gap_max_set_aside=max(gaps["set_aside"], default=0.0),
        gap_max_by_margin_under=dict(zip(
            [str(e) for e in BANDS] + ["inf"], by_margin)),
        widest=sorted(wide, reverse=True)[:WIDEST])
    return gaps, control, routing
