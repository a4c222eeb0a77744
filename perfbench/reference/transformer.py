"""Plain reference: the 2017 encoder-decoder Transformer (Vaswani et al.,
"Attention Is All You Need") in straightforward ``jax.numpy``.

Forward pass, loss, gradients (``jax.grad``) and Adam, in float32 with
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks.  It imports nothing of the program and takes nothing the
program has made; the weights come from ``perfbench.weights`` (the seed),
under the parameter names that are the program's public sharing contract
(``<prefix>.enc0.self.q.w`` ...).

Departures from the paper, all of them the served/trained system's own and
therefore part of what the reference must follow:

* position embeddings are a parameter table (filled with the paper's
  sinusoids by ``perfbench.weights``), trained like any other leaf;
* source embedding, target embedding and output head are three tables;
* ``causal_encoder=True`` (serving): the paged engine encodes the prompt
  causally, so that chunked prefill is exact and a prefix's K/V depend on
  the prefix alone.  Training encodes without a mask, as the paper does;
* no dropout and no label smoothing (see the configuration files).

``precision`` selects the matmul arithmetic: ``"float32"`` is the
reference proper; ``"bfloat16"`` and ``"float8"`` round both operands of
every matrix product (float8: e4m3 with one scale per operand tensor) and
exist only for the controls that show ``correct`` failing in a lower
precision than the configuration states.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5           # the layer_norm op's default
ADAM = {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
_HI = jax.lax.Precision.HIGHEST


# -- shapes -----------------------------------------------------------------

def param_shapes(cfg: Dict, prefix: str) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of every parameter, from the configuration's sizes."""
    d, h = cfg["d_model"], cfg["n_head"]
    dk, dv, di = cfg["d_key"], cfg["d_value"], cfg["d_inner_hid"]
    out: Dict[str, Tuple[int, ...]] = {}

    def attn(p):
        out[f"{p}.q.w"] = (d, h * dk)
        out[f"{p}.k.w"] = (d, h * dk)
        out[f"{p}.v.w"] = (d, h * dv)
        out[f"{p}.out.w"] = (h * dv, d)

    def ln(p):
        out[f"{p}.ln2.w"] = (d,)
        out[f"{p}.ln2.b"] = (d,)

    def ffn(p):
        out[f"{p}.fc1.w"] = (d, di)
        out[f"{p}.fc1.b"] = (di,)
        out[f"{p}.fc2.w"] = (di, d)
        out[f"{p}.fc2.b"] = (d,)

    out[f"{prefix}.src_emb.w"] = (cfg["src_vocab_size"], d)
    out[f"{prefix}.src_pos_emb.w"] = (cfg["max_length"], d)
    out[f"{prefix}.trg_emb.w"] = (cfg["trg_vocab_size"], d)
    out[f"{prefix}.trg_pos_emb.w"] = (cfg["max_length"], d)
    for i in range(cfg["n_layer"]):
        e = f"{prefix}.enc{i}"
        attn(f"{e}.self"), ln(f"{e}.post_self")
        ffn(f"{e}.ffn"), ln(f"{e}.post_ffn")
        dd = f"{prefix}.dec{i}"
        attn(f"{dd}.self"), ln(f"{dd}.post_self")
        attn(f"{dd}.cross"), ln(f"{dd}.post_cross")
        ffn(f"{dd}.ffn"), ln(f"{dd}.post_ffn")
    out[f"{prefix}.vocab_proj.w"] = (d, cfg["trg_vocab_size"])
    return out


# -- arithmetic -------------------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _rounder(precision: str):
    """Values rounded, gradients passed straight through: a cast's own
    gradient would round the cotangent too, unscaled, and float8 flushes
    cotangents of 1e-6 to zero (seen on the chip, PR 23: every matrix's
    gradient exactly zero)."""
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    elif precision == "float8":
        low = _fp8
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return lambda x: x + jax.lax.stop_gradient(low(x) - x)


class _Math:
    """Matrix products in the chosen precision; everything else float32."""

    def __init__(self, precision: str):
        self.r = _rounder(precision)

    def mm(self, a, b):                     # [..., k] x [k, n]
        return jnp.matmul(self.r(a), self.r(b), precision=_HI)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.r(a), self.r(b), precision=_HI)


def _layer_norm(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _attention(m: _Math, P, p, xq, xkv, cfg, mask):
    h, dk, dv = cfg["n_head"], cfg["d_key"], cfg["d_value"]
    b, lq, lk = xq.shape[0], xq.shape[1], xkv.shape[1]
    q = m.mm(xq, P[f"{p}.q.w"]).reshape(b, lq, h, dk)
    k = m.mm(xkv, P[f"{p}.k.w"]).reshape(b, lk, h, dk)
    v = m.mm(xkv, P[f"{p}.v.w"]).reshape(b, lk, h, dv)
    s = m.einsum("bqhd,bkhd->bhqk", q, k) * (float(dk) ** -0.5)
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    ctx = m.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, lq, h * dv)
    return m.mm(ctx, P[f"{p}.out.w"])


def _ffn(m: _Math, P, p, x):
    hid = jax.nn.relu(m.mm(x, P[f"{p}.fc1.w"]) + P[f"{p}.fc1.b"])
    return m.mm(hid, P[f"{p}.fc2.w"]) + P[f"{p}.fc2.b"]


def _post(P, p, residual, out):             # "dan" with dropout 0
    return _layer_norm(out + residual, P[f"{p}.ln2.w"], P[f"{p}.ln2.b"])


def _embed(P, table, pos_table, ids, pos, d_model):
    return P[table][ids] * (float(d_model) ** 0.5) + P[pos_table][pos]


def _causal(lq, lk):
    return (jnp.arange(lk)[None, :] <= jnp.arange(lq)[:, None])[None, None]


def forward_logits(P, prefix, cfg, src, src_pos, trg, trg_pos, src_len=None,
                   causal_encoder=False, precision="float32", remat=False):
    """Logits [b, lt, vocab].  ``src_len`` [b] masks source positions at
    and beyond it (padding); None means every position is real.  With
    ``remat`` a gradient keeps each layer's input only and computes the
    layer again on the way back (same arithmetic, less memory: the score
    matrices of a 2048-token block would not fit otherwise)."""
    m = _Math(precision)
    d = cfg["d_model"]
    ls, lt = src.shape[1], trg.shape[1]
    key_ok = None
    if src_len is not None:
        key_ok = (jnp.arange(ls)[None, :] < src_len[:, None])[:, None, None]
    enc_mask = key_ok
    if causal_encoder:
        enc_mask = _causal(ls, ls) if key_ok is None \
            else jnp.logical_and(_causal(ls, ls), key_ok)
    x = _embed(P, f"{prefix}.src_emb.w", f"{prefix}.src_pos_emb.w", src,
               src_pos, d)

    def enc_layer(P, e, x):
        x = _post(P, f"{e}.post_self", x,
                  _attention(m, P, f"{e}.self", x, x, cfg, enc_mask))
        return _post(P, f"{e}.post_ffn", x, _ffn(m, P, f"{e}.ffn", x))

    def dec_layer(P, dd, y, x):
        y = _post(P, f"{dd}.post_self", y,
                  _attention(m, P, f"{dd}.self", y, y, cfg, _causal(lt, lt)))
        y = _post(P, f"{dd}.post_cross", y,
                  _attention(m, P, f"{dd}.cross", y, x, cfg, key_ok))
        return _post(P, f"{dd}.post_ffn", y, _ffn(m, P, f"{dd}.ffn", y))

    if remat:
        enc_layer = jax.checkpoint(enc_layer, static_argnums=(1,))
        dec_layer = jax.checkpoint(dec_layer, static_argnums=(1,))
    for i in range(cfg["n_layer"]):
        x = enc_layer(P, f"{prefix}.enc{i}", x)
    y = _embed(P, f"{prefix}.trg_emb.w", f"{prefix}.trg_pos_emb.w", trg,
               trg_pos, d)
    for i in range(cfg["n_layer"]):
        y = dec_layer(P, f"{prefix}.dec{i}", y, x)
    return m.mm(y, P[f"{prefix}.vocab_proj.w"])


# -- training ---------------------------------------------------------------

def _block_loss_sum(P, prefix, cfg, blk, precision, remat):
    logits = forward_logits(P, prefix, cfg, blk["src_word"], blk["src_pos"],
                            blk["trg_word"], blk["trg_pos"],
                            precision=precision, remat=remat)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, blk["lbl_word"][..., None], axis=-1)
    return jnp.sum(nll[..., 0] * blk["lbl_weight"]), nll[..., 0]


@functools.partial(jax.jit, static_argnames=("prefix", "cfg_items",
                                             "precision", "remat"),
                   donate_argnums=(1,))
def _accumulate(P, acc, blk, inv_count, prefix, cfg_items, precision, remat):
    cfg = dict(cfg_items)
    (loss, per_token), g = jax.value_and_grad(_block_loss_sum, has_aux=True)(
        P, prefix, cfg, blk, precision, remat)
    acc = jax.tree_util.tree_map(lambda a, x: a + x * inv_count, acc, g)
    return loss * inv_count, per_token, acc


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(P, m1, m2, g, lr, b1p, b2p):
    b1, b2, eps = ADAM["beta1"], ADAM["beta2"], ADAM["epsilon"]
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    m1 = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, m1, g)
    m2 = jax.tree_util.tree_map(lambda m, x: b2 * m + (1 - b2) * x * x,
                                m2, g)
    P = jax.tree_util.tree_map(
        lambda p, a, b: p - lr_t * a / (jnp.sqrt(b) + eps), P, m1, m2)
    return P, m1, m2


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def leaf_diff_norms(a, b):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32)))) for k in a}


def train_steps(make_params, prefix: str, cfg: Dict,
                batches: Sequence[Dict[str, np.ndarray]], learning_rate: float,
                block_rows: int, precision: str = "float32",
                remat: bool = False):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights.  ``make_params()`` returns a fresh name -> array dict.

    Each batch is walked in blocks of ``block_rows`` rows, gradients
    accumulated, so that the reference fits beside nothing at any batch.
    Returns ``(losses, grad_norms, delta_norms, token_losses)``: the loss
    of each step, the per-leaf norm of the FIRST step's gradient, the
    per-leaf norm of the parameters' change after the last step, and the
    first step's loss of every token (rows x positions)."""
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float))))
    P = make_params()
    m1 = jax.tree_util.tree_map(jnp.zeros_like, P)
    m2 = jax.tree_util.tree_map(jnp.zeros_like, P)
    losses: List[float] = []
    token_losses: List[List[float]] = []
    grad_norms = None
    b1p, b2p = ADAM["beta1"], ADAM["beta2"]
    for step, batch in enumerate(batches):
        rows = batch["src_word"].shape[0]
        if rows % block_rows:
            raise ValueError(f"batch of {rows} rows does not divide into "
                             f"blocks of {block_rows}")
        inv = 1.0 / float(np.sum(batch["lbl_weight"]))
        acc = jax.tree_util.tree_map(jnp.zeros_like, P)
        loss = 0.0
        for r in range(0, rows, block_rows):
            blk = {k: jnp.asarray(v[r:r + block_rows])
                   for k, v in batch.items()}
            part, per_token, acc = _accumulate(
                P, acc, blk, jnp.float32(inv), prefix, cfg_items, precision,
                remat)
            loss += float(part)
            if step == 0:
                token_losses += np.asarray(per_token).tolist()
        losses.append(loss)
        if step == 0:
            grad_norms = {k: float(v) for k, v in leaf_norms(acc).items()}
        P, m1, m2 = _adam(P, m1, m2, acc, jnp.float32(learning_rate),
                          jnp.float32(b1p), jnp.float32(b2p))
        b1p *= ADAM["beta1"]
        b2p *= ADAM["beta2"]
    delta = leaf_diff_norms(P, make_params())
    return (losses, grad_norms, {k: float(v) for k, v in delta.items()},
            token_losses)


# -- serving ----------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("prefix", "cfg_items",
                                             "precision"))
def _served_gaps(P, src, src_len, dec_in, served, n_out, prefix, cfg_items,
                 precision):
    cfg = dict(cfg_items)
    b, ls = src.shape
    lt = dec_in.shape[1]
    src_pos = jnp.broadcast_to(jnp.arange(ls)[None], (b, ls))
    trg_pos = jnp.broadcast_to(jnp.arange(lt)[None], (b, lt))
    args = (P, prefix, cfg, src, src_pos, dec_in, trg_pos, src_len)
    ref = forward_logits(*args, causal_encoder=True, precision="float32")
    best = jnp.max(ref, axis=-1)
    live = jnp.arange(lt)[None, :] < n_out[:, None]
    got = jnp.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
    gap_served = jnp.where(live, best - got, 0.0)
    if precision == "float32":
        return gap_served, gap_served
    low = forward_logits(*args, causal_encoder=True, precision=precision)
    first = jnp.argmax(low, axis=-1)
    got_low = jnp.take_along_axis(ref, first[..., None], axis=-1)[..., 0]
    return gap_served, jnp.where(live, best - got_low, 0.0)


def served_logit_gaps(P, prefix: str, cfg: Dict, prompts: List[List[int]],
                      outputs: List[List[int]], start_id: int, src_len: int,
                      out_len: int, control_precision: str = "float32"):
    """Teacher-force each prompt with the tokens the system served for it
    through the reference (causal encoder, as the paged engine encodes),
    once, and return per request the widest gap by which a served token's
    reference logit lies below the reference's best at that position.

    With ``control_precision`` below float32 the second list is the
    control: at each position the gap of the token that the lower
    precision puts first (no decoding needed)."""
    n = len(prompts)
    src = np.zeros((n, src_len), np.int32)
    dec_in = np.zeros((n, out_len), np.int32)
    served = np.zeros((n, out_len), np.int32)
    lens = np.zeros(n, np.int32)
    n_out = np.zeros(n, np.int32)
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        src[i, :len(p)] = p
        lens[i] = len(p)
        n_out[i] = len(o)
        served[i, :len(o)] = o
        dec_in[i, 0] = start_id
        dec_in[i, 1:len(o)] = o[:-1]
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float))))
    g, c = _served_gaps(P, jnp.asarray(src), jnp.asarray(lens),
                        jnp.asarray(dec_in), jnp.asarray(served),
                        jnp.asarray(n_out), prefix, cfg_items,
                        control_precision)
    return (np.asarray(g).max(axis=1).tolist(),
            np.asarray(c).max(axis=1).tolist())
