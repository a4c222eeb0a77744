"""Plain reference: Arcee's Trinity block (arcee-ai/Trinity-Mini,
``config.json``, ``model_type: "afmoe"``) in straightforward ``jax.numpy``,
float32 with ``jax.default_matmul_precision("highest")``: no kernels, no
cache, no paging, no batching.  It imports nothing of the program; the
weights come from ``perfbench.weights`` (the seed) under the program's
parameter names.

The equations, ``x`` [T, d] the float32 residual stream, every matrix
without bias, ``RMS(z; w) = z / sqrt(mean(z^2) + eps) * w`` over the last
axis (kinds by ``layer_types``; layer ``i < num_dense_layers`` is dense):

    x = emb[tok] * sqrt(d)                                   (mup_enabled)
    u = RMS(x; g_attn)
    q = (u Wq) -> [T, H, 128];  k = (u Wk) -> [T, Hkv, 128];
    v = (u Wv) -> [T, Hkv, 128];  g = u Wg -> [T, H * 128]
    q = RMS(q; g_q[128]);  k = RMS(k; g_k[128])     per head, one scale
    sliding_attention: q, k = rotary(q, k; pos, rope_theta, all 128 dims,
                       halves); keys t - sliding_window < j <= t
    full_attention:    no rotary at all; keys j <= t
    a = softmax(q k^T / sqrt(128)) v       query head h reads KV head h // G
    x = x + RMS((a * sigmoid(g)) Wo; g_attn_post)
    w = RMS(x; g_ffn)
    dense:   y = (silu(w Wgate) * (w Wup)) Wdown
    experts: s = sigmoid(w Wr) over all experts (float32);
             S = the num_experts_per_tok largest of s + b  (b selects only)
             p_e = route_scale * s_e / (sum_{e' in S} s_e' + 1e-20)
             y = FFN_shared(w) + sum over e in S AND HELD HERE of
                 p_e FFN_e(w)
    x = x + RMS(y; g_ffn_post)
    logits = RMS(x; g_out) W_head, untied

The SHARE: the configuration holds ``num_experts`` experts from
``first_expert`` of the ``published.num_experts`` the router scores (what
the absent experts would add is left out, here as in the program), the
shared expert whole, and ``vocab_size`` rows of the vocabulary.
``g_ffn_post`` normalises the PARTIAL ``y``: in the deployment the
exchange sits before that norm.  ``moe(..., shared=False)`` leaves the
shared expert out, for the test that adds the shares of a divided layer
up (the shared expert counts once).

Assumed (the config has no key; the published description): the gate is
the sigmoid of a projection of the attention's normed input, elementwise
on the heads' concatenated output; QK-norm per head, before rotary, one
[128] scale; no rotary in ``full_attention`` layers; rotary halves (dim i
pairs with i + 64); the window counts the query's own position; where the
four norms act; the selection bias outside the weights; the 1e-20; the
shared experts as ONE feed-forward of ``num_shared_experts`` x the expert
width; no rotary scaling (``rope_scaling`` null).

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
    held = int(cfg["num_experts"])
    return {
        "n": n, "d": int(cfg["hidden_size"]),
        "h": int(cfg["num_attention_heads"]),
        "hkv": int(cfg["num_key_value_heads"]), "dh": int(cfg["head_dim"]),
        "vocab": int(cfg["vocab_size"]),
        "window": [k == "sliding_attention"
                   for k in cfg["layer_types"][:n]],
        "moe": [i >= int(cfg["num_dense_layers"]) for i in range(n)],
        "held": held, "first": int(cfg.get("first_expert", 0)),
        "experts": int(cfg.get("published", {}).get("num_experts", held)),
        "shared": int(cfg.get("num_shared_experts") or 0),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg.get("route_scale") or 1.0),
        "f_dense": int(cfg["intermediate_size"]),
        "f_moe": int(cfg["moe_intermediate_size"]),
    }


def layer_shapes(cfg: Dict, prefix: str, i: int) -> Dict[str, Tuple]:
    """name -> shape of layer ``i``'s parameters."""
    z = sizes(cfg)
    p, d, h, hkv, dh = f"{prefix}.l{i}", z["d"], z["h"], z["hkv"], z["dh"]
    out = {f"{p}.attn_norm.w": (d,), f"{p}.attn.q.w": (d, h * dh),
           f"{p}.attn.k.w": (d, hkv * dh), f"{p}.attn.v.w": (d, hkv * dh),
           f"{p}.attn.gate.w": (d, h * dh), f"{p}.attn.out.w": (h * dh, d),
           f"{p}.attn.q_norm.w": (dh,), f"{p}.attn.k_norm.w": (dh,),
           f"{p}.attn_post_norm.w": (d,), f"{p}.ffn_norm.w": (d,),
           f"{p}.ffn_post_norm.w": (d,)}
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
    """x [T, H, D]: every dim rotates, halves paired (i with i + D/2)."""
    half = x.shape[-1] // 2
    inv = jnp.power(jnp.float32(base),
                    -jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(r, W, p, u, cfg, z, window: bool):
    """The gated attention of one sequence u [T, d], before the output
    norm: full forward, queries scored ``Q_BLOCK`` at a time so that
    [heads, block, T] fits."""
    t = u.shape[0]
    h, hkv, dh = z["h"], z["hkv"], z["dh"]
    eps = float(cfg["rms_norm_eps"])
    mm = lambda a, b: jnp.matmul(r(a), r(b), precision=_HI)    # noqa: E731
    q = rms_norm(mm(u, W[f"{p}.attn.q.w"]).reshape(t, h, dh),
                 W[f"{p}.attn.q_norm.w"], eps)
    k = rms_norm(mm(u, W[f"{p}.attn.k.w"]).reshape(t, hkv, dh),
                 W[f"{p}.attn.k_norm.w"], eps)
    if window:                  # a full_attention layer has no position
        pos = jnp.arange(t)
        q = rotary(q, pos, float(cfg["rope_theta"]))
        k = rotary(k, pos, float(cfg["rope_theta"]))
    v = mm(u, W[f"{p}.attn.v.w"]).reshape(t, hkv, dh)
    gate = jax.nn.sigmoid(mm(u, W[f"{p}.attn.gate.w"]))
    k = jnp.repeat(k, h // hkv, axis=1)     # head h reads KV head h // G
    v = jnp.repeat(v, h // hkv, axis=1)
    win = int(cfg["sliding_window"])
    # the keys a block of queries can see at all: a window layer's block
    # reads its own positions and the window before them
    span = min(Q_BLOCK + win, t) if window else t

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, 0)
        k0 = jnp.clip(q0 - win, 0, t - span) if window else 0
        kb = jax.lax.dynamic_slice_in_dim(k, k0, span, 0)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, span, 0)
        kpos = (k0 + jnp.arange(span))[None, :]
        a = jnp.einsum("qhd,khd->hqk", r(qb), r(kb), precision=_HI) \
            * (float(dh) ** -0.5)
        qpos = (q0 + jnp.arange(Q_BLOCK))[:, None]
        keep = kpos <= qpos
        if window:
            keep = keep & (kpos > qpos - win)
        a = jnp.where(keep[None], a, -jnp.inf)
        e = jnp.exp(a - jnp.max(a, axis=-1, keepdims=True))
        prob = e / jnp.sum(e, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", r(prob), r(vb), precision=_HI)

    # t is a multiple of Q_BLOCK (``forward_logits`` pads; a shorter
    # sequence is one block of its own length)
    o = jax.lax.map(block, jnp.arange(0, t, Q_BLOCK)).reshape(t, h * dh)
    return mm(o * gate, W[f"{p}.attn.out.w"])


def gated_ffn(r, x, wg, wu, wd):
    mm = lambda a, b: jnp.matmul(r(a), r(b), precision=_HI)    # noqa: E731
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def route(r, W, p, w, z):
    """(selected [T, k], weights [T, k], margins [T, 2]): the ``top_k``
    experts of s + b, their p_e, and two margins a token.  First: how far
    the last expert selected lies above the first one left out.  Second:
    how far scores would have to move for THIS SHARE's result to change,
    that is for an expert held here to leave the selection (it lies that
    far above the first one left out) or to enter it (that far below the
    last one selected)."""
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
    weights = z["scale"] * sel / (jnp.sum(sel, axis=-1, keepdims=True)
                                  + 1e-20)
    return idx, weights, jnp.stack([margin, here], axis=-1)


def moe(r, W, p, w, z, first=None, held=None, shared=True):
    """This share's part of the expert layer on w, BEFORE the output norm:
    experts ``first .. first + held - 1`` (every token through every held
    expert, weighted by p_e where the token chose it and by 0 where it did
    not: the plain form), plus the shared experts unless ``shared`` is
    False."""
    first = z["first"] if first is None else first
    held = z["held"] if held is None else held
    idx, c, margin = route(r, W, p, w, z)

    def one(out, expert):
        j, wg, wu, wd = expert
        weight = jnp.sum(jnp.where(idx == first + j, c, 0.0), axis=-1)
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


def layer(W, p: str, cfg: Dict, x, window: bool, experts: bool,
          precision: str = "float32"):
    """(y, routing margins [T, 2] or None) of one layer on x [T, d]; ``p``
    is the prefix of the layer's parameter names in ``W``."""
    z, r = sizes(cfg), _rounder(precision)
    eps = float(cfg["rms_norm_eps"])
    a = attention(r, W, p, rms_norm(x, W[f"{p}.attn_norm.w"], eps), cfg, z,
                  window)
    h = x + rms_norm(a, W[f"{p}.attn_post_norm.w"], eps)
    w = rms_norm(h, W[f"{p}.ffn_norm.w"], eps)
    margin = None
    if experts:
        y, margin = moe(r, W, p, w, z)
    else:
        y = gated_ffn(r, w, W[f"{p}.ffn.gate.w"], W[f"{p}.ffn.up.w"],
                      W[f"{p}.ffn.down.w"])
    return h + rms_norm(y, W[f"{p}.ffn_post_norm.w"], eps), margin


def embed(W, prefix: str, cfg: Dict, ids):
    scale = float(cfg["hidden_size"]) ** 0.5 if cfg.get("mup_enabled") \
        else 1.0
    return W[f"{prefix}.emb.w"][jnp.asarray(ids)] * scale


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
    whoever else is made beside it), so the model need not fit whole.
    Every sequence is padded to ONE length (``_padded`` of the longest,
    or of ``longest`` if that is more; causal: what follows a position
    never reaches it), so each kind of layer compiles once, whatever the
    sample.  The head runs at the kept positions only.  Also returns
    every routing margin of the kept positions."""
    z = sizes(cfg)
    cfg_items = _items(cfg)
    lens = [len(s) for s in sequences]
    W = make(outer_shapes(cfg, prefix))
    xs = []
    width = _padded(max(lens + [int(longest)]))
    for s in sequences:
        ids = np.concatenate([np.asarray(s, np.int32),
                              np.zeros(width - len(s), np.int32)])
        xs.append(embed(W, prefix, cfg, ids))
    margins: List[List[np.ndarray]] = [[] for _ in sequences]
    for i in range(z["n"]):
        # under one name for every layer, so that layers of a kind share
        # a compiled function
        Wl = {name.replace(f"{prefix}.l{i}.", "layer.", 1): value
              for name, value in make(layer_shapes(cfg, prefix, i)).items()}
        for j in range(len(xs)):
            xs[j], margin = _layer_jit(Wl, xs[j], z["window"][i],
                                       z["moe"][i], cfg_items, precision)
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
        "num_key_value_heads", "head_dim", "vocab_size", "layer_types",
        "num_dense_layers", "num_experts", "first_expert",
        "num_experts_per_tok", "num_shared_experts", "intermediate_size",
        "moe_intermediate_size", "rope_theta", "rope_scaling",
        "sliding_window", "rms_norm_eps", "route_scale", "route_norm",
        "score_func", "mup_enabled", "n_group", "num_expert_groups",
        "num_limited_groups", "topk_group")


def _items(cfg: Dict) -> Tuple:
    """What the equations read of the configuration, hashable (a jitted
    function's static argument)."""
    items = [(k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
             for k in KEYS if k in cfg]
    experts = cfg.get("published", {}).get("num_experts")
    return tuple(items) + (("published_experts", experts),)


def _thaw(items) -> Dict:
    cfg = {k: list(v) if isinstance(v, tuple) else v for k, v in items}
    experts = cfg.pop("published_experts")
    if experts is not None:
        cfg["published"] = {"num_experts": experts}
    return cfg


@functools.partial(jax.jit, static_argnames=("window", "experts",
                                             "cfg_items", "precision"))
def _layer_jit(W, x, window, experts, cfg_items, precision):
    with jax.default_matmul_precision("highest"):
        return layer(W, "layer", _thaw(cfg_items), x, window, experts,
                     precision)


@functools.partial(jax.jit, static_argnames=("prefix", "cfg_items",
                                             "precision"))
def _head_jit(W, x, prefix, cfg_items, precision):
    with jax.default_matmul_precision("highest"):
        return head(W, prefix, _thaw(cfg_items), x, precision)


# -- serving ----------------------------------------------------------------

NEAR_TIE = 1e-3         # margins below this are counted (``near_ties``)
# A token is SET ASIDE where in some expert layer an expert held here lies
# within this of entering or leaving the selection (``route``'s second
# margin): ``perfbench/reference/mimo_v2_flash.py``'s rule, for its
# reason.  There the program's scores (float32, of activations that
# bfloat16 products made) and the reference's may select differently, both
# rightly, and this share's result then differs by a whole expert's part,
# which is no rounding: a token has about one of its eight experts here
# (16 of 128 held), and ``ffn_post_norm`` brings the partial sum, whatever
# its size, back to order one, so a flip moves the layer's output as far
# as a fault would.  No limit on the WIDEST gap of these tokens tells a
# flip from a fault; their NUMBER does.  The caller judges all but the
# widest of them (a share it states) like the rest.  PERF.md section 2
# holds the margins and gaps read on the chip.
SET_ASIDE = 1e-2
BANDS = (1e-3, 3e-3, SET_ASIDE)     # the widest gap is reported by margin
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
