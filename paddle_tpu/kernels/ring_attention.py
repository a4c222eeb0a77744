"""Ring attention: sequence-parallel attention over an ICI ring.

The sequence axis is sharded across devices on a mesh axis (default 'sp');
each device holds local q/k/v blocks of length L/n.  Attention over the full
sequence is computed in n ring steps: at each step a device attends its local
queries against the k/v block it currently holds, folds the partial result
into a (out, logsumexp) accumulator, and passes the k/v block to its ring
neighbour with `lax.ppermute` — so the k/v transfer rides the ICI and
overlaps with the matmuls, and no device ever materialises more than L/n
keys.

r4: each per-block fold IS the Pallas flash kernel (flash_attention.py) —
the kernels take dynamic global row/col offsets, so the causal mask and the
dropout hash key on true global sequence positions while the tiles stay
local.  The backward is a second ring: per held block, the flash dq/dkv
kernels run against the FINAL merged logsumexp (the flash decomposition
makes per-block gradients exact given the final row stats), with dk/dv
accumulators riding the ring home alongside their blocks.  A bias-carrying
call falls back to the blockwise-XLA fold (dbias needs the dense columns).

This is the modern long-context counterpart of the reference's
variable-length machinery (SURVEY.md §2.4); capability the 2018 reference
lacked entirely.  Pattern follows the public ring-attention recipe
(PAPERS.md); written for jax shard_map + XLA collectives.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .flash_attention import (DEFAULT_MASK_VALUE, LANES, _default_block,
                              _pallas_backward, _pallas_forward,
                              _xla_backward, _xla_forward, bh_grid,
                              default_impl, dropout_carrier, keep_scale,
                              offsets_carrier, seed_to_carrier,
                              shard_dropout_seed)

__all__ = ["ring_attention", "ring_attention_sharded"]


def _chunk_fwd(q, k_blk, v_blk, seed_f, offsets, sm_scale, causal, kv_len,
               block_q, block_k, dropout_rate, impl):
    """(out, lse[b,h,lq]) for one held block; causal masking keys on the
    global offsets.  NOT differentiated: the ring carries its own
    custom_vjp.

    Fully-masked (above-diagonal) blocks: BOTH kernels now detect rows
    whose running max never rose above the finite DEFAULT_MASK_VALUE and
    return out = 0 with lse = +inf (the same convention as true l==0
    kv_len-padded rows).  The isposinf flip below turns that into -inf,
    which _merge treats as weight exactly 0 — so dead blocks may be
    folded in any order and an all-dead row merges to 0."""
    if impl == "pallas":
        out, lse128 = _pallas_forward(
            q, k_blk, v_blk, None, seed_f, offsets, sm_scale, causal,
            kv_len, block_q, block_k, dropout_rate, "bhld",
            interpret=False, need_lse=True)
        lse = lse128[:, :, 0].reshape(q.shape[0], q.shape[1], q.shape[2])
    else:
        out, lse = _xla_forward(q, k_blk, v_blk, None, seed_f, offsets,
                                sm_scale, causal, kv_len, block_k,
                                dropout_rate)
    # kernel convention for l==0 rows (kv_len-padded) is lse=+inf; flip to
    # -inf so such rows weigh 0 in the merge
    lse = jnp.where(jnp.isposinf(lse), -jnp.inf, lse)
    return out.astype(jnp.float32), lse


def _merge(out_a, lse_a, out_b, lse_b):
    """Combine two normalized partials by their logsumexps."""
    m = jnp.maximum(lse_a, lse_b)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    wa = jnp.where(jnp.isneginf(lse_a), 0.0, jnp.exp(lse_a - m_safe))
    wb = jnp.where(jnp.isneginf(lse_b), 0.0, jnp.exp(lse_b - m_safe))
    tot = wa + wb
    lse = jnp.where(tot > 0.0, m_safe + jnp.log(jnp.maximum(tot, 1e-38)),
                    -jnp.inf)
    den = jnp.where(tot > 0.0, tot, 1.0)
    out = (out_a * wa[..., None] + out_b * wb[..., None]) / den[..., None]
    return out, lse


def _ring_geometry(q, k, axis_name):
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return n, my, perm


def _pad_seq(x, mult):
    l = x.shape[2]
    pad = (-l) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ring_core(q, k, v, seed_f, sm_scale, axis_name, dropout_rate, impl,
               causal):
    return _ring_fwd(q, k, v, seed_f, sm_scale, axis_name, dropout_rate,
                     impl, causal)[0]


def _ring_prep(q, k, v, impl):
    """Shared fwd/bwd prologue: block choice, padding, kv_len.  The two
    passes MUST agree bit-for-bit (the backward recomputes the forward's
    masks and dropout hash), so this lives in exactly one place."""
    lq0, lk0 = q.shape[2], k.shape[2]
    block = _default_block(max(lq0, lk0)) if impl == "pallas" else 256
    qp, _ = _pad_seq(q, min(block, max(lq0, 1)))
    kp, _ = _pad_seq(k, min(block, max(lk0, 1)))
    vp, _ = _pad_seq(v, min(block, max(lk0, 1)))
    kv_len = lk0 if kp.shape[2] != lk0 else None
    return (qp, kp, vp, lq0, lk0, kv_len,
            min(block, qp.shape[2]), min(block, kp.shape[2]))


def _ring_fwd(q, k, v, seed_f, sm_scale, axis_name, dropout_rate, impl,
              causal):
    n, my, perm = _ring_geometry(q, k, axis_name)
    qp, kp, vp, lq0, lk0, kv_len, block_q, block_k = _ring_prep(
        q, k, v, impl)
    b, h, lqp, d = qp.shape

    def fold(acc, k_blk, v_blk, t):
        out_acc, lse_acc = acc
        src = (my - t) % n
        offs = offsets_carrier(my * lq0, src * lk0)
        out_t, lse_t = _chunk_fwd(qp, k_blk, v_blk, seed_f, offs, sm_scale,
                                  causal, kv_len, block_q, block_k,
                                  dropout_rate, impl)
        return _merge(out_acc, lse_acc, out_t, lse_t)

    def step(carry, t):
        k_blk, v_blk, out_acc, lse_acc = carry
        out_acc, lse_acc = fold((out_acc, lse_acc), k_blk, v_blk, t)
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, out_acc, lse_acc), None

    out0 = jnp.zeros((b, h, lqp, d), jnp.float32)
    lse0 = jnp.full((b, h, lqp), -jnp.inf, jnp.float32)
    # n-1 fold+rotate steps, then a final fold with NO rotation: the last
    # held block needs no onward ICI transfer
    (k_last, v_last, out, lse), _ = jax.lax.scan(
        step, (kp, vp, out0, lse0), jnp.arange(n - 1))
    out, lse = fold((out, lse), k_last, v_last, n - 1)
    out = out[:, :, :lq0].astype(q.dtype)
    lse = lse[:, :, :lq0]
    return out, (q, k, v, seed_f, out, lse)


def _ring_bwd(sm_scale, axis_name, dropout_rate, impl, causal, res, do):
    q, k, v, seed_f, out, lse = res
    n, my, perm = _ring_geometry(q, k, axis_name)
    qp, kp, vp, lq0, lk0, kv_len, block_q, block_k = _ring_prep(
        q, k, v, impl)
    dop = jnp.pad(do.astype(q.dtype),
                  ((0, 0), (0, 0), (0, qp.shape[2] - lq0), (0, 0)))
    outp = jnp.pad(out, ((0, 0), (0, 0), (0, qp.shape[2] - lq0), (0, 0)))
    b, h, lqp, d = qp.shape

    # bwd convention: p = exp(s - lse); fully-masked rows need +inf so the
    # recomputed probabilities underflow to zero (the merge used -inf)
    lse_b = jnp.where(jnp.isneginf(lse), jnp.inf, lse)
    lse_b = jnp.pad(lse_b, ((0, 0), (0, 0), (0, lqp - lq0)),
                    constant_values=jnp.inf)
    if impl == "pallas":
        lse_arg = jnp.broadcast_to(
            lse_b.reshape(b * h, lqp)[..., None], (b * h, lqp, LANES))
    else:
        lse_arg = lse_b

    def chunk_bwd(k_blk, v_blk, offs):
        if impl == "pallas":
            return _pallas_backward(
                qp, k_blk, v_blk, dop, outp, lse_arg, seed_f, offs,
                sm_scale, causal, kv_len, block_q, block_k, dropout_rate,
                "bhld", interpret=False)
        dq, dk, dv, _ = _xla_backward(
            qp, k_blk, v_blk, None, outp, dop, lse_arg, seed_f, offs,
            sm_scale, causal, kv_len, block_k, dropout_rate)
        return dq, dk, dv

    def accumulate(carry, t):
        k_blk, v_blk, dk_acc, dv_acc, dq_acc = carry
        src = (my - t) % n
        offs = offsets_carrier(my * lq0, src * lk0)
        dq_t, dk_t, dv_t = chunk_bwd(k_blk, v_blk, offs)
        return (k_blk, v_blk, dk_acc + dk_t.astype(jnp.float32),
                dv_acc + dv_t.astype(jnp.float32),
                dq_acc + dq_t.astype(jnp.float32))

    def step(carry, t):
        carry = accumulate(carry, t)
        k_blk, v_blk, dk_acc, dv_acc, dq_acc = carry
        # the block and ITS gradient accumulators ride the ring together;
        # after n total rotations the accumulators are home
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_acc, axis_name, perm)
        return (k_nxt, v_nxt, dk_nxt, dv_nxt, dq_acc), None

    zeros_kv = jnp.zeros(kp.shape, jnp.float32)
    carry, _ = jax.lax.scan(
        step, (kp, vp, zeros_kv, jnp.zeros(vp.shape, jnp.float32),
               jnp.zeros(qp.shape, jnp.float32)), jnp.arange(n - 1))
    # last fold: the k/v blocks need no onward transfer — only the
    # gradient accumulators make the final hop home
    _, _, dk_acc, dv_acc, dq = accumulate(carry, n - 1)
    dk = jax.lax.ppermute(dk_acc, axis_name, perm)
    dv = jax.lax.ppermute(dv_acc, axis_name, perm)
    return (dq[:, :, :lq0].astype(q.dtype), dk[:, :, :lk0].astype(k.dtype),
            dv[:, :, :lk0].astype(v.dtype), jnp.zeros((), jnp.float32))


_ring_core.defvjp(_ring_fwd, _ring_bwd)


def _ring_xla_bias(q, k, v, bias, causal, sm_scale, axis_name, dropout_rate,
                   seed_u):
    """Blockwise-XLA ring fold for bias-carrying calls (dbias needs the
    dense columns; plain differentiable JAX — grad rides the scan and the
    ppermute adjoint)."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, lq, d = q.shape
    lk = k.shape[2]
    qf = q.astype(jnp.float32)
    rows_local = jnp.arange(lq)[:, None]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def fold(state, k_blk, v_blk, t):
        m_prev, l_prev, acc = state
        src = (my - t) % n
        grows = my * lq + rows_local
        gcols = src * lk + jnp.arange(lk)[None, :]
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32))
        s = s * sm_scale
        if bias is not None:
            bs = jax.lax.dynamic_slice_in_dim(bias, src * lk, lk, 3)
            s = s + bs.astype(jnp.float32)
        if causal:
            s = jnp.where(grows >= gcols, s, DEFAULT_MASK_VALUE)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        if dropout_rate > 0.0:
            pd = p * keep_scale(seed_u, bh_grid(b, h), grows[None, None],
                                gcols[None, None], dropout_rate)
        else:
            pd = p
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", pd, v_blk.astype(jnp.float32))
        return m_new, l_new, acc

    def step(carry, t):
        k_blk, v_blk, state = carry
        state = fold(state, k_blk, v_blk, t)
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, state), None

    state0 = (jnp.full((b, h, lq), -jnp.inf, jnp.float32),
              jnp.zeros((b, h, lq), jnp.float32),
              jnp.zeros((b, h, lq, d), jnp.float32))
    (k_last, v_last, state), _ = jax.lax.scan(
        step, (k, v, state0), jnp.arange(n - 1))
    m, l, acc = fold(state, k_last, v_last, n - 1)
    denom = jnp.where(l == 0.0, 1.0, l)
    return (acc / denom[..., None]).astype(q.dtype)


def ring_attention(q, k, v, bias: Optional[jax.Array] = None,
                   causal: bool = False, sm_scale: Optional[float] = None,
                   axis_name: str = "sp", dropout_rate: float = 0.0,
                   dropout_seed=None, impl: Optional[str] = None):
    """Attention with q/k/v sharded on the sequence axis over `axis_name`.

    Must be called inside shard_map/pjit with a mapped `axis_name`.
    q [B,H,Lq/n,D], k/v [B,H,Lk/n,D] (local shards).
    bias: optional additive [B|1, H|1, Lq/n, Lk_global] — rows local,
    columns global (so padding masks survive sharding); a bias call uses
    the blockwise-XLA fold (dbias needs the dense columns), bias-free
    calls run the Pallas flash kernels per held block.

    dropout_rate > 0 applies attention-prob dropout via the same
    global-position hash as flash_attention (the mask depends only on the
    *global* (head, q, k) coordinate, so it is invariant to how the
    sequence is sharded); the backward ring regenerates it under AD.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    dropout_rate = float(dropout_rate)
    seed_u = None
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        seed_u = jax.lax.bitcast_convert_type(
            seed_to_carrier(dropout_seed), jnp.uint32)
    if impl is None:
        impl = default_impl()

    if bias is not None:
        return _ring_xla_bias(q, k, v, bias, causal, float(sm_scale),
                              axis_name, dropout_rate, seed_u)
    seed_f = (seed_to_carrier(dropout_seed) if dropout_rate > 0.0
              else jnp.zeros((), jnp.float32))
    return _ring_core(q, k, v, seed_f, float(sm_scale), axis_name,
                      dropout_rate, impl, bool(causal))


def sp_sharded_call(inner_fn, mesh: Mesh, q, k, v, bias, causal,
                    sm_scale, dp_axis, mp_axis, sp_axis, dropout_rate,
                    dropout_seed, impl):
    """Shared shard_map plumbing for the sequence-parallel strategies
    (ring and Ulysses): resolves the dp/mp/sp axes, carries the dropout
    seed through shard_map as an f32 scalar, decorrelates dp/mp shards
    by folding their axis indices into the seed, and maps ``inner_fn``
    (signature of ring_attention/ulysses_attention) over the mesh."""
    names = mesh.axis_names
    dp = dp_axis if dp_axis in names else None
    mp = mp_axis if (mp_axis and mp_axis in names) else None
    if sp_axis not in names:
        raise ValueError(f"mesh {names} has no sequence axis {sp_axis!r}")
    qkv_spec = P(dp, mp, sp_axis, None)
    dropout_rate = float(dropout_rate)
    seed = dropout_carrier(dropout_rate, dropout_seed)

    fn = functools.partial(inner_fn, causal=causal, sm_scale=sm_scale,
                           axis_name=sp_axis, dropout_rate=dropout_rate,
                           impl=impl)

    def local_seed(s_):
        if dropout_rate == 0.0:
            return None
        return shard_dropout_seed(s_, dp, mp)

    if bias is None:
        mapped = jax.shard_map(
            lambda q_, k_, v_, s_: fn(q_, k_, v_,
                                      dropout_seed=local_seed(s_)),
            mesh=mesh, in_specs=(qkv_spec,) * 3 + (P(),),
            out_specs=qkv_spec, check_vma=False)
        return mapped(q, k, v, seed)
    bias_spec = P(dp if bias.shape[0] > 1 else None,
                  mp if bias.shape[1] > 1 else None,
                  sp_axis, None)
    mapped = jax.shard_map(
        lambda q_, k_, v_, b_, s_: fn(q_, k_, v_, bias=b_,
                                      dropout_seed=local_seed(s_)),
        mesh=mesh, in_specs=(qkv_spec,) * 3 + (bias_spec, P()),
        out_specs=qkv_spec, check_vma=False)
    return mapped(q, k, v, bias, seed)


def ring_attention_sharded(mesh: Mesh, q, k, v,
                           bias: Optional[jax.Array] = None,
                           causal: bool = False,
                           sm_scale: Optional[float] = None,
                           dp_axis: Optional[str] = "dp",
                           mp_axis: Optional[str] = None,
                           sp_axis: str = "sp",
                           dropout_rate: float = 0.0,
                           dropout_seed=None,
                           impl: Optional[str] = None):
    """Convenience wrapper: shard_map ring attention over a mesh.

    q/k/v [B,H,L,D] global; batch sharded on dp_axis, heads on mp_axis
    (tensor parallel), sequence on sp_axis.  Returns [B,H,L,D] with the same
    sharding as q.  Dropout masks are decorrelated across dp/mp shards by
    folding the device's axis indices into the seed (the hash already keys
    on the global sequence position, so sp shards need no special care).
    """
    return sp_sharded_call(ring_attention, mesh, q, k, v, bias, causal,
                           sm_scale, dp_axis, mp_axis, sp_axis,
                           dropout_rate, dropout_seed, impl)
