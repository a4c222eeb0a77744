"""Ops of a decoder-only language-model block as today's open models build
it: RMS normalisation (of the residual stream, or of every head of the
queries and keys: QK-norm), partial rotary position embedding, the
SiLU-gated feed-forward (its gate alone, and whole), the sigmoid gate on an
attention output, a routed-expert layer that
computes ITS OWN experts' part of the result, latent attention's
up-projection absorbed into queries and outputs, and the row write of a
paged KV pool (one of a split pair, or a latent kind's only one).

All are inference ops (``no_grad``).  Products take the activations' type
(bfloat16 in serving) with float32 accumulation; normalisation, rotary
angles, the router's product and scores and the expert weights (``c_e``)
are float32.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ..core.registry import primitive
from .cache_ops import _scatter_tokens


@primitive("rms_norm", inputs=["X", "Scale"], outputs=["Out"], no_grad=True)
def rms_norm(ctx, x, scale):
    """``x * rsqrt(mean(x^2) + epsilon) * scale`` over the last axis, in
    float32; attr ``out_dtype`` (default: x's) is what the next product
    reads.  X of any rank: [T, H, D] with Scale [D] normalises every head
    by itself with one scale for all heads (QK-norm).  Attr ``scope``
    (optional) names its device operations in a trace."""
    eps = float(ctx.attr("epsilon", 1e-5))
    scope = ctx.attr("scope", None)
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        y = y * scale.astype(jnp.float32)
        return y.astype(ctx.attr("out_dtype", None) or x.dtype)


@primitive("rotary_embedding", inputs=["X", "Pos"], outputs=["Out"],
           no_grad=True)
def rotary_embedding(ctx, x, pos):
    """Rotate the first ``rotary_dim`` of the last axis of X [T, H, D] by
    the angles of positions Pos [T] (halves rotated: dim i pairs with
    i + rotary_dim/2, frequency ``base ** (-2i / rotary_dim)``); the other
    dims pass."""
    rot = int(ctx.attr("rotary_dim", x.shape[-1]))
    base = float(ctx.attr("base", 10000.0))
    half = rot // 2
    inv = jnp.power(jnp.float32(base),
                    -jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    a, b, rest = xf[..., :half], xf[..., half:rot], xf[..., rot:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                          axis=-1)
    return out.astype(x.dtype)


@primitive("swiglu", inputs=["Gate", "Up"], outputs=["Out"], no_grad=True)
def swiglu(ctx, gate, up):
    """``silu(gate) * up``, in float32, back in the inputs' type."""
    g = gate.astype(jnp.float32)
    return (jax.nn.silu(g) * up.astype(jnp.float32)).astype(gate.dtype)


@primitive("sigmoid_gate", inputs=["X", "Gate"], outputs=["Out"],
           no_grad=True)
def sigmoid_gate(ctx, x, gate):
    """``x * sigmoid(gate)`` elementwise, in float32, back in X's type: the
    gate a block puts on its attention's output before the output
    projection.  Attr ``scope`` names its device operations in a trace."""
    with jax.named_scope(ctx.attr("scope", None) or "gate"):
        return (x.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)


@primitive("paged_row_write", inputs=["Pool", "Value", "Pages", "Offsets"],
           outputs=["Out"], no_grad=True)
def paged_row_write(ctx, pool, value, pages, offsets):
    """Write one row a token into ONE pool: one of a split pair, or the
    only pool of a latent kind
    (``kernels.flash_attention.split_kv_rows``): Value [T, ...] flattens
    to the pool's row width (or less: the rest of the row is zero), Pages
    / Offsets [T] int32 say where (page 0 is the trash page).  Out aliases
    Pool: an in-place row scatter under
    donation, the one ``paged_cache_write`` does for K and V each."""
    from ...kernels.flash_attention import split_kv_rows

    rows = split_kv_rows(pages, int(ctx.attr("layer", 0)),
                         int(ctx.attr("n_layer", 1)))
    width = value.size // value.shape[0]
    if width < pool.shape[2]:
        # a latent row in whole lane tiles: zero past its own columns
        value = jnp.pad(value.reshape(value.shape[0], width),
                        ((0, 0), (0, pool.shape[2] - width)))
    return _scatter_tokens(pool, rows.reshape(-1),
                           jnp.asarray(offsets).astype(jnp.int32).reshape(-1),
                           value)


def route_top_k(x, router_w, router_bias, top_k: int, routed_scale=None):
    """Sigmoid scores over every expert, the ``top_k`` largest of score +
    selection bias, and their weights (scores over the selected scores'
    sum: the bias selects and never weighs; with ``routed_scale`` the
    published ``routed_scaling_factor`` times scores over that sum + 1e-20,
    the published denominator), all in float32 as the
    published gate computes them: ``x`` is the float32 norm output and
    the product is a float32 product (on a TPU the default would round
    both operands to bfloat16; a selection turns on the eighth score
    against the ninth).  -> (experts [T, k] int32, weights [T, k]
    float32)."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + router_bias.astype(jnp.float32), top_k)
    sel = jnp.take_along_axis(scores, idx, axis=-1)
    total = jnp.sum(sel, axis=-1, keepdims=True)
    if routed_scale is None:
        return idx.astype(jnp.int32), sel / total
    return idx.astype(jnp.int32), \
        sel / (total + 1e-20) * jnp.float32(routed_scale)


@primitive("routed_experts",
           inputs=["X", "RouterW", "RouterBias", "WGate", "WUp", "WDown",
                   "Live?"],
           outputs=["Out", "Load"], no_grad=True)
def routed_experts(ctx, x, router_w, router_bias, w_gate, w_up, w_down,
                   live=None):
    """A device's share of a routed-expert layer.  X [T, d], the float32
    norm output; RouterW [d, E] (float32) and RouterBias [E] over ALL E
    experts; WGate / WUp [held, d, f] and WDown [held, f, d] the experts
    held here, which are experts ``first_expert .. first_expert + held -
    1``, in the type the products run in.  Every token picks ``top_k`` of
    the E experts (``route_top_k``); the (token, expert) pairs whose
    expert is held are sorted by expert and run through the grouped
    products; pairs for absent experts cost nothing and nothing stands in
    for them, so Out [T, d] is this device's part of the sum (the 32
    shares of a 32-way layer add up to the whole).  No capacity: no pair
    is ever dropped.  Attr ``routed_scale`` (optional) multiplies the
    normalised weights.  Live [T] (optional; nonzero = a request's token):
    the rows of no request (an idle lane, a chunk's padding) make no
    pair, cost nothing and come out zero.  Load [held] int32 is the
    pairs each held expert got."""
    from ...kernels.grouped_matmul import TILE_M, grouped_matmul

    top_k = int(ctx.attr("top_k", 8))
    first = int(ctx.attr("first_expert", 0))
    impl = ctx.attr("impl", None)
    held = w_gate.shape[0]
    dtype = w_gate.dtype
    t, d = x.shape
    with jax.named_scope("moe/route"):
        experts, weights = route_top_k(x, router_w, router_bias, top_k,
                                       ctx.attr("routed_scale", None))
        local = experts - first
        here = jnp.logical_and(local >= 0, local < held)         # [T, k]
        if live is not None:
            here = jnp.logical_and(here, (live != 0).reshape(t, 1))
        n_flat = t * top_k
        m = -(-n_flat // TILE_M) * TILE_M     # the static worst case
        key = jnp.where(here, local, held).reshape(-1)
        key = jnp.concatenate(
            [key, jnp.full((m - n_flat,), held, jnp.int32)])
        order = jnp.argsort(key, stable=True)       # held pairs first
        load = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        token = jnp.minimum(order // top_k, t - 1)
        # where each (token, choice) pair sits in the sorted order
        place = jnp.zeros((m,), jnp.int32).at[order].set(
            jnp.arange(m, dtype=jnp.int32))[:n_flat]
    with jax.named_scope("moe/experts"):
        xs = x.astype(dtype)[token]                              # [M, d]
        gate = grouped_matmul(xs, w_gate, load, out_dtype=jnp.float32,
                              impl=impl)
        up = grouped_matmul(xs, w_up, load, out_dtype=jnp.float32,
                            impl=impl)
        hid = (jax.nn.silu(gate) * up).astype(dtype)
        y = grouped_matmul(hid, w_down, load, out_dtype=dtype, impl=impl)
        w = jnp.where(here, weights, 0.0).reshape(n_flat, 1)
        out = jnp.sum((y[place].astype(jnp.float32) * w)
                      .reshape(t, top_k, d), axis=1)
    return out.astype(dtype), load


@primitive("gated_ffn", inputs=["X", "WGate", "WUp", "WDown"],
           outputs=["Out"], no_grad=True)
def gated_ffn(ctx, x, w_gate, w_up, w_down):
    """``(silu(x WGate) * (x WUp)) WDown`` in the weights' type with
    float32 accumulation (the gate itself float32): a dense feed-forward,
    or the shared experts every token passes.  Attr ``scope`` names its
    device operations in a trace."""
    dtype = w_gate.dtype
    with jax.named_scope(ctx.attr("scope", None) or "ffn"):
        xs = x.astype(dtype)
        gate = jnp.matmul(xs, w_gate, preferred_element_type=jnp.float32)
        up = jnp.matmul(xs, w_up, preferred_element_type=jnp.float32)
        hid = (jax.nn.silu(gate) * up).astype(dtype)
        return jnp.matmul(hid, w_down,
                          preferred_element_type=jnp.float32).astype(dtype)


@primitive("latent_absorb", inputs=["X", "W"], outputs=["Out"], no_grad=True)
def latent_absorb(ctx, x, w):
    """Latent attention's up-projection absorbed into what surrounds the
    cache, so that attention runs against the latent rows themselves.  W
    [r, H * (d_nope + dv)] maps a token's latent (r wide) to every head's
    keys (its first ``d_nope`` columns of a head: W_UK[h]) and values
    (the rest: W_UV[h]).  Attr ``side``: ``"query"`` takes X [T, H, d_nope]
    to X[h] W_UK[h]^T [T, H, r] (a score against the latent is the score
    against the expanded key); ``"output"`` takes X [T, H, r], the
    probabilities' sum over latents, to X[h] W_UV[h] [T, H * dv].
    Products in X's type, float32 accumulation."""
    d_nope = int(ctx.attr("d_nope"))
    t, h = x.shape[0], x.shape[1]
    wh = w.reshape(w.shape[0], h, -1).astype(x.dtype)
    with jax.named_scope("mla/absorb"):
        if ctx.attr("side") == "query":
            out = jnp.einsum("thd,rhd->thr", x, wh[:, :, :d_nope],
                             preferred_element_type=jnp.float32)
            return out.astype(x.dtype)
        out = jnp.einsum("thr,rhd->thd", x, wh[:, :, d_nope:],
                         preferred_element_type=jnp.float32)
        return out.reshape(t, -1).astype(x.dtype)


@primitive("vocab_logits", inputs=["X", "W"], outputs=["Out"], no_grad=True)
def vocab_logits(ctx, x, w):
    """The output head: X [T, d] x W [d, V] with the logits kept in
    float32 (a bfloat16 logit near 1 is 0.008 coarse: the next token
    would turn on its rounding)."""
    return jnp.matmul(x, w.astype(x.dtype),
                      preferred_element_type=jnp.float32)
