"""Flash attention: O(L)-memory fused attention for TPU, fwd AND bwd in Pallas.

Forward is a Pallas kernel (MXU matmuls over [block_q, block_k] tiles with an
online-softmax running (max, sum, accumulator) in VMEM scratch) that also
emits the row logsumexp; backward is two more Pallas kernels (dq, and dk/dv)
that recompute the probabilities blockwise from q/k and the saved logsumexp —
no [Lq, Lk] tensor is ever materialised in either direction, and no XLA-side
recompute pass remains (r3's backward ran the whole forward again in XLA,
which is why long-sequence MFU collapsed).

Matmuls run in the input dtype (bf16 inputs hit the MXU's native path; the
old kernel upcast everything to f32, halving throughput), accumulating in
f32 via preferred_element_type.  The row statistics ride in [block, 128]
lane-broadcast tiles — the same layout trick the public TPU flash kernels
use — so no sublane/lane transposes appear anywhere.

This is the TPU-native replacement for what the reference could not do at
all — its attention-era models build [lq, lk] score tensors explicitly
(multi_head_attention in the Transformer config helpers); at long context
that is HBM-quadratic.  Written fresh for Pallas tiling constraints (see
PAPERS.md for the flash-attention recipe).

Shapes: layout='bhld' (default) q [B, H, Lq, D], k/v [B, H, Lk, D];
layout='blhd' accepts q [B, Lq, H, D] etc. so callers skip explicit
split-heads transpose ops (the kernel view is made at the boundary, where
XLA fuses the copy into the adjacent projection matmuls; a true
head-strided BlockSpec is illegal on TPU — d=64 < the 128-lane tile).
Optional additive bias [B|1, H|1, Lq, Lk].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128   # stat tiles are [block, LANES] so no sublane transposes occur

# Below this query length the backward runs as the blockwise XLA scan
# instead of the dq/dkv Pallas kernels: at short L the [bh, lq, 128]
# logsumexp residual costs more HBM than recomputing the row stats, and
# XLA can fuse the scan with the surrounding step (measured at s=256:
# pallas bwd end-to-end was ~12% slower; at L >= 1024 it is 2-4x faster).
# Measured when either backward paid for a second forward kernel call; a
# program's grad op now takes the forward's own results (the two halves
# below) and holds the statistics as [bh, lq], so the crossover is due a
# new sweep (ROADMAP D8).
# Tests monkeypatch this to 0 to exercise the kernels at tiny shapes.
PALLAS_BWD_MIN_L = 1024

__all__ = ["flash_attention", "flash_attention_sharded", "decode_attention",
           "ragged_decode_attention", "ragged_decode_attention_sharded",
           "paged_kv_rows", "split_kv_rows", "default_impl"]


def default_impl() -> str:
    """The kernels' ``impl=None`` choice: the Pallas kernels where Mosaic
    can compile them (a TPU backend), the blockwise-XLA path elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def decode_attention(q, k_cache, v_cache, lengths,
                     sm_scale: Optional[float] = None) -> jax.Array:
    """Decode-step attention against a preallocated KV cache.

    The serving hot path: one (or a few) query tokens per sequence attend
    over that sequence's cache prefix.  Shapes (layout 'blhd', matching
    the interleave-heads convention the fused training path uses):

        q        [B, Lq, H, D]   (Lq is 1 in steady-state decode)
        k_cache  [B, Lmax, H, D] (preallocated; rows >= lengths are junk)
        v_cache  [B, Lmax, H, D]
        lengths  [B] int32       (valid cache rows per sequence)

    Returns ctx [B, Lq, H, D].  Per-step work is O(Lmax) — the length
    mask (additive -1e9 on rows >= lengths[b]) replaces the O(L^2)
    causal-bias re-run of the full decoder.  No Pallas kernel: a
    single-token step is a bandwidth-bound [H, 1, Lmax] matvec pair that
    XLA already emits optimally; scores accumulate in f32 regardless of
    the cache dtype (same rule as the flash kernels)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    lmax = k_cache.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores.astype(jnp.float32) * jnp.float32(sm_scale)
    live = (jnp.arange(lmax, dtype=jnp.int32)[None, :]
            < lengths.astype(jnp.int32)[:, None])          # [B, Lmax]
    scores = jnp.where(live[:, None, None, :], scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return ctx.astype(q.dtype)


# ---------------------------------------------------------------------------
# Ragged paged decode attention (serving paged-KV hot path)
# ---------------------------------------------------------------------------
#
# The paged KV pool is ONE persistable tensor [R, page_size, H*D]:
# token-major, every head of a token side by side in the minor dim, the
# layout the K/V projections produce.  A page is one contiguous
# [page_size, H*D] block (one DMA), a new token is one row of the
# [R*page_size, H*D] view (an in-place row update under donation), and
# the minor dim is lane-dense.  A *logical* page spans every layer and
# both K and V of a page_size-token span: physical row =
# (page * n_layer + layer) * 2 (+1 for V).  Per-request block tables
# hold logical page ids; row 0's logical page 0 is the reserved trash
# page dead lanes write into.


def paged_kv_rows(page_table, layer: int, n_layer: int):
    """Logical page table [B, P] -> (k_rows, v_rows) physical row tables
    for one layer.  Pure index arithmetic — shared by the XLA fallback,
    the Pallas index maps, and the paged write op so the three can never
    disagree on the pool layout."""
    base = (jnp.asarray(page_table).astype(jnp.int32) * n_layer + layer) * 2
    return base, base + 1


def _ragged_mask(scores, lengths_b, base_b, p0, causal, c):
    """Additive mask of a [rows, n_cols] score tile for global key
    positions p0..p0+n_cols against live length ``lengths_b`` and
    (optionally) the causal position ``base_b + query``; row r holds
    query ``r % c`` (several heads' C queries may be stacked)."""
    shape = scores.shape
    cols = p0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    keep = cols < lengths_b
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        if shape[0] != c:
            rows = jax.lax.rem(rows, jnp.int32(c))
        keep = jnp.logical_and(keep, cols <= base_b + rows)
    return jnp.where(keep, scores, -1e9)


def _ragged_xla(q, pool, page_table, lengths, q_base, layer, n_layer,
                causal, sm_scale, scales=None):
    """Gather-based fallback: resolve each lane's pages to pool rows and
    run length/causally-masked attention over the gathered prefix.  An
    int8 pool dequantizes right after the gather (``scales`` holds one
    fp32 scale per (row, slot) block) — HBM moved int8 bytes; the f32
    view exists only as a fused register-level convert."""
    _r, ps, _hd = pool.shape
    b, c, h, d = q.shape
    n_pages = page_table.shape[1]
    k_rows, v_rows = paged_kv_rows(page_table, layer, n_layer)
    k = pool[k_rows].reshape(b, n_pages, ps, h, d)
    v = pool[v_rows].reshape(b, n_pages, ps, h, d)
    if scales is not None:
        sc = scales.reshape(scales.shape[-2], scales.shape[-1])  # [R, ps]
        k = k.astype(jnp.float32) * sc[k_rows][..., None, None]
        v = v.astype(jnp.float32) * sc[v_rows][..., None, None]
    elif k.dtype != q.dtype:          # bf16 pool: upcast like the Pallas
        k = k.astype(q.dtype)         # kernel so probs stay full precision
        v = v.astype(q.dtype)         # (probs.astype(v.dtype) below)
    scores = jnp.einsum("bqhd,bpshd->bhqps", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores.reshape(b, h, c, n_pages * ps).astype(jnp.float32)
    scores = scores * jnp.float32(sm_scale)
    cols = jnp.arange(n_pages * ps, dtype=jnp.int32)
    keep = cols[None, :] < lengths.astype(jnp.int32)[:, None]     # [B, L]
    if causal:
        rows = (q_base.astype(jnp.int32)[:, None]
                + jnp.arange(c, dtype=jnp.int32)[None, :])        # [B, C]
        keep = jnp.logical_and(keep[:, None, :],
                               cols[None, None, :] <= rows[:, :, None])
        keep = keep[:, None]                                      # [B,1,C,L]
    else:
        keep = keep[:, None, None, :]
    scores = jnp.where(keep, scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    # a fully-masked row (dead lane, lengths==0) must return 0, matching
    # the Pallas kernel's dead-row contract — not the garbage mean a
    # uniform softmax over -1e9 scores would produce
    dead = jnp.logical_not(keep.any(axis=-1))                     # [B,?,C]
    probs = jnp.where(dead[..., None], 0.0, probs)
    probs = probs.reshape(b, h, c, n_pages, ps)
    ctx = jnp.einsum("bhqps,bpshd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return ctx.astype(q.dtype)


def _lane_group(h: int, d: int) -> int:
    """Width of the lane slices the ragged kernel cuts a [*, h*d] pool
    row into.  Mosaic slices lanes at multiples of 128 only, so heads
    narrower than that share a group: ``d`` itself when it fills whole
    128-lane tiles, else 128 when the heads pack evenly into it, else
    the whole row (shapes below one tile: the tests' sizes)."""
    if d % LANES == 0:
        return d
    if (h * d) % LANES == 0 and LANES % d == 0:
        return LANES
    return h * d


def _ragged_kernel(krows_ref, vrows_ref, meta_ref, q_ref, k_ref, v_ref,
                   ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, n_groups, rows, c, ps, n_pages, causal, sm_scale):
    """grid (B, P): per lane, walk its page list (scalar-prefetched
    block table drives the k/v index maps) with an online softmax.
    A page block is token-major [ps, h*d] and is only ever cut into
    ``n_groups`` lane groups of the width ``w`` of q's rows.  q rides
    [n_groups*rows, w]: group g's ``rows`` rows stack, head by head, the
    C queries of each head of the group, zero outside the head's own
    lanes — so q·kᵀ against the group is exactly q_j·k_jᵀ, and of p·v
    against the group the caller keeps head j's lanes.  Scratch and
    output rows mirror q's.  ks_ref/vs_ref (present for an int8 pool)
    carry this page-row's [1, ps] fp32 block scales.  They apply to the
    score / probability COLUMNS — (q·k_i8ᵀ)·s == q·(k_i8·s)ᵀ and
    (p·s)·v_i8 == p·(v_i8·s) — where ps already sits on the lane axis,
    so dequant is a [rows, ps] multiply and the scale row never needs a
    lane->sublane relayout.  The page DMA moved int8 bytes,
    halving-again the decode read stream vs bf16."""
    b = pl.program_id(0)
    p = pl.program_id(1)
    w = q_ref.shape[-1]

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = meta_ref[0, b]
    base = meta_ref[1, b]

    @pl.when(p * ps < length)
    def _page():
        q = q_ref[0]                       # [n_groups*rows, w]
        k = k_ref[0]                       # [ps, h*d]
        v = v_ref[0]
        if k.dtype != q.dtype:             # bf16/int8 pool: VMEM-level
            k = k.astype(q.dtype)          # upcast (the DMA moved narrow
            v = v.astype(q.dtype)          # bytes; dot_general won't promote)
        p0 = p * ps
        for g in range(n_groups):          # static lane-group loop
            r = slice(g * rows, (g + 1) * rows)
            lanes = slice(g * w, (g + 1) * w)
            s = jax.lax.dot_general(q[r], k[:, lanes],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * sm_scale
            if ks_ref is not None:
                s = s * ks_ref[0]          # [1, ps] over the key columns
            s = _ragged_mask(s, length, base, p0, causal, c)
            m_prev = m_scr[r]                              # [rows, LANES]
            l_prev = l_scr[r]
            m_cur = jnp.max(s, axis=1)[:, None]
            m_new = jnp.maximum(m_prev,
                                jnp.broadcast_to(m_cur, m_prev.shape))
            alpha = jnp.exp(m_prev - m_new)
            pr = jnp.exp(s - m_new[:, :1])
            l_new = alpha * l_prev + jnp.broadcast_to(
                jnp.sum(pr, axis=1)[:, None], l_prev.shape)
            m_scr[r] = m_new
            l_scr[r] = l_new
            if vs_ref is not None:
                pr = pr * vs_ref[0]
            pv = jax.lax.dot_general(pr.astype(v.dtype), v[:, lanes],
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_scr[r] = acc_scr[r] * alpha[:, :1] + pv

    @pl.when(p == n_pages - 1)
    def _finalize():
        l_fin = l_scr[...]
        dead = l_fin == 0.0                # lane with lengths==0
        denom = jnp.where(dead, 1.0, l_fin)
        out = jnp.where(dead[:, :1], 0.0, acc_scr[...] / denom[:, :1])
        _st(o_ref, out.astype(o_ref.dtype))


def _ragged_pallas(q, pool, page_table, lengths, q_base, layer, n_layer,
                   causal, sm_scale, interpret, scales=None):
    _r, ps, hd = pool.shape
    b, c, h, d = q.shape
    n_pages = page_table.shape[1]
    k_rows, v_rows = paged_kv_rows(page_table, layer, n_layer)
    meta = jnp.stack([jnp.asarray(lengths, jnp.int32).reshape(b),
                      jnp.asarray(q_base, jnp.int32).reshape(b)])
    have_scales = scales is not None
    # heads narrower than a lane group share it (``per`` to a group): a
    # head's queries are padded with zeros to its group, and of the
    # group-wide p·v only the head's own lanes are kept at the end
    w = _lane_group(h, d)
    per, n_groups = w // d, h * d // w
    own = jnp.eye(per, dtype=q.dtype)
    qk = jnp.einsum("bcgkd,jk->bgjckd", q.reshape(b, c, n_groups, per, d),
                    own).reshape(b, h * c, w)

    def q_map(bi, pi, kr, vr, mt):
        return (bi, 0, 0)

    def k_map(bi, pi, kr, vr, mt):
        return (kr[bi, pi], 0, 0)

    def v_map(bi, pi, kr, vr, mt):
        return (vr[bi, pi], 0, 0)

    # one page = one contiguous [ps, h*d] block of the pool
    in_specs = [
        pl.BlockSpec((1, h * c, w), q_map),
        pl.BlockSpec((1, ps, hd), k_map),
        pl.BlockSpec((1, ps, hd), v_map),
    ]
    args = [qk, pool, pool]
    if have_scales:
        # block scales viewed [R, 1, ps]: each grid step DMAs the one
        # [1, ps] scale row matching the k/v page row it just fetched.
        # The unit middle dim makes the block's trailing dims the array's
        # FULL (1, ps); a (1, ps) block of the [R, ps] view has a
        # second-minor extent of 1 — neither R nor a multiple of 8 —
        # which the TPU lowering refuses.
        sc = scales.reshape(scales.shape[-2], 1, scales.shape[-1])
        in_specs.append(pl.BlockSpec((1, 1, ps), k_map))
        in_specs.append(pl.BlockSpec((1, 1, ps), v_map))
        args += [sc, sc]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h * c, w), q_map),
        scratch_shapes=[
            pltpu.VMEM((h * c, LANES), jnp.float32),
            pltpu.VMEM((h * c, LANES), jnp.float32),
            pltpu.VMEM((h * c, w), jnp.float32),
        ],
    )
    base = functools.partial(_ragged_kernel, n_groups=n_groups,
                             rows=per * c, c=c, ps=ps, n_pages=n_pages,
                             causal=causal, sm_scale=sm_scale)

    def kernel(krows_ref, vrows_ref, meta_ref, q_ref, k_ref, v_ref, *rest):
        rest = list(rest)
        ks_ref = rest.pop(0) if have_scales else None
        vs_ref = rest.pop(0) if have_scales else None
        return base(krows_ref, vrows_ref, meta_ref, q_ref, k_ref, v_ref,
                    ks_ref, vs_ref, *rest)

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h * c, w), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ragged_paged_attn",
    )(k_rows, v_rows, meta, *args)
    out = jnp.einsum("bgjckd,jk->bcgjd",
                     out.reshape(b, n_groups, per, c, per, d), own)
    return out.reshape(b, c, h, d)


# ---------------------------------------------------------------------------
# Split pools: grouped KV heads, unequal key/value widths, window, sink;
# and the latent form, ONE pool whose rows hold keys and values both
# ---------------------------------------------------------------------------
#
# A decoder-only model whose layers differ in cache shape declares a pool,
# or a pool pair, per KIND of layer.  A pair: keys in ``[R, page,
# Hkv*Dk]``, values in ``[R, page, Hkv*Dv]``, both token-major, physical
# row = page * n_layer + layer (``split_kv_rows``; page 0 is the trash
# page).  H query heads read Hkv KV heads (head h reads h // (H/Hkv)).
# ONE pool (latent attention, ``latent_values``): a token's row is one KV
# head that all H query heads share, and its leading ``latent_values``
# columns are the values too: the page block is read once, scores contract
# the whole row, no second pool and no second DMA.  The pool is ``[R,
# page, latent_row_width(Dk)]``: the row in whole lane tiles, zero past
# its Dk columns.  (A bfloat16 ``[R, 256, 576]`` array is not token-major
# on the chip at all: the TPU lays it out page-dimension-minor to save
# the padding, and every kernel call then begins with a transposing copy
# of the whole pool.  Token-major tiles hold 640 columns either way.)
# A window layer
# keeps its pages as a RING: the table's slot i holds the newest logical
# page congruent to i, so the walk is as long as the ring, never as the
# context; ``ring_top`` [B] is the logical page of each lane's newest
# written token, from which a slot's key positions follow.
# The kernel's grid is (lane, GROUP of consecutive table slots): how many
# slots a grid step follows from the call's shapes (``split_slot_group``:
# several for a decode row, one for a prefill tile).  The pools stay in
# HBM and the kernel copies the live slots' pages itself, the next live
# group's while it works on this one's: a dead slot costs a scalar test,
# a dead group one empty grid step, and neither reads a byte.


def split_kv_rows(page_table, layer: int, n_layer: int):
    """Logical page table -> physical rows of one layer in a kind's pool,
    or pool pair (the same rows in the key pool and in the value pool)."""
    return jnp.asarray(page_table).astype(jnp.int32) * n_layer + layer


def latent_row_width(d_key: int) -> int:
    """The width a latent kind's pool rows are allocated in: ``d_key``
    in whole lane tiles (576 -> 640), so that the pool is token-major on
    the chip like every other pool."""
    return -(-int(d_key) // LANES) * LANES


def _ring_page(slot, top, n_slots):
    """The logical page ring slot ``slot`` holds when the newest written
    page is ``top``: the largest page <= top congruent to slot."""
    return top - jax.lax.rem(top - slot + n_slots, n_slots)


def _split_xla(q, k_pool, v_pool, page_table, lengths, q_base, ring_top,
               layer, n_layer, sm_scale, window, sink, latent_values=None):
    """Gather form: every addressed page, masked.  The reference for the
    kernel below and the path of a host without Mosaic.  ``v_pool`` None:
    the latent form, values = the key rows' leading ``latent_values``
    columns."""
    b, c, h, dk = q.shape
    _r, ps, kw = k_pool.shape
    n_pages = page_table.shape[1]
    rows = split_kv_rows(page_table, layer, n_layer)
    if v_pool is None:
        hkv, dv = 1, int(latent_values)
        k = k_pool[rows][..., :dk].reshape(b, n_pages * ps, 1, dk) \
            .astype(q.dtype)
        v = k[..., :dv]
    else:
        hkv = kw // dk
        dv = v_pool.shape[2] // hkv
        k = k_pool[rows].reshape(b, n_pages * ps, hkv, dk).astype(q.dtype)
        v = v_pool[rows].reshape(b, n_pages * ps, hkv, dv).astype(q.dtype)
    g = h // hkv
    slot = jnp.arange(n_pages, dtype=jnp.int32)[None, :]
    if ring_top is not None:
        page = _ring_page(slot, ring_top.astype(jnp.int32)[:, None],
                          n_pages)
    else:
        page = jnp.broadcast_to(slot, (b, n_pages))
    kpos = (page[:, :, None] * ps
            + jnp.arange(ps, dtype=jnp.int32)[None, None, :]
            ).reshape(b, 1, n_pages * ps)
    qpos = (q_base.astype(jnp.int32)[:, None]
            + jnp.arange(c, dtype=jnp.int32)[None, :])[:, :, None]
    keep = (kpos >= 0) & (kpos < lengths.astype(jnp.int32)[:, None, None]) \
        & (kpos <= qpos)
    if window is not None:
        keep = keep & (kpos > qpos - int(window))
    s = jnp.einsum("bckgd,blkd->bkgcl", q.reshape(b, c, hkv, g, dk), k,
                   preferred_element_type=jnp.float32) * jnp.float32(sm_scale)
    keep = keep[:, None, None]                               # [B,1,1,C,L]
    s = jnp.where(keep, s, -1e9)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(1, hkv, g, 1, 1)
        m = jnp.maximum(m, sk)
    p = jnp.where(keep, jnp.exp(s - m), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    dead = denom == 0.0
    if sink is not None:
        denom = denom + jnp.exp(sk - m)
    p = jnp.where(dead, 0.0, p / jnp.where(dead, 1.0, denom))
    ctx = jnp.einsum("bkgcl,blkd->bckgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return ctx.reshape(b, c, h, dv).astype(q.dtype)


def _head_slices(n_head: int, d: int):
    """How the kernel cuts head j out of a token-major [*, n_head*d] row:
    (starts, width, offsets).  Mosaic slices lanes at multiples of 128
    only, so head j's slice starts at the 128-multiple at or below j*d
    and is ``width`` wide for every head; its own lanes lie ``offsets[j]``
    into it.  Rows narrower than a tile (the tests' sizes) are taken
    whole."""
    row = n_head * d
    if row % LANES:
        return [0] * n_head, row, [j * d for j in range(n_head)]
    starts = [(j * d // LANES) * LANES for j in range(n_head)]
    width = max(-(-(j * d + d) // LANES) * LANES - starts[j]
                for j in range(n_head))
    starts = [min(st, row - width) for st in starts]
    return starts, width, [j * d - starts[j] for j in range(n_head)]


def _split_kernel(rows_ref, meta_ref, q_ref, k_hbm, v_hbm, sink_ref, o_ref,
                  m_scr, l_scr, acc_scr, k_buf, v_buf, sems, walk, *,
                  k_starts, k_width, v_starts, v_width, c, ps, n_pages,
                  group, window, ring, sm_scale):
    """grid (B, ceil(P / group)): a grid step is a GROUP of consecutive
    table slots of one lane.  q rides [Hkv, G*C, k_width]: KV head j's
    rows stack the C queries of each of its G query heads, zero outside
    the head's own lanes of its slice.  ``v_hbm`` None (the latent form):
    the values are columns of the key page already here.

    The pools stay in HBM.  A live slot's page is copied into its place
    of ``k_buf`` / ``v_buf`` [2, group, page, width]; a dead slot starts
    no copy.  A live group starts the copies of the NEXT live group in
    walk order (this lane's or a later lane's) while it works on its
    own, slot by slot, so the dead groups between two live ones cost a
    grid step each and hide no transfer.  ``walk`` (SMEM) carries that
    next group (lane, group, its half of the buffers) from step to step:
    the grid runs in order, on one core.  A group's slots are folded in
    slot order by one loop, each with the update a grid step made when
    it held one page."""
    b = pl.program_id(0)
    gi = pl.program_id(1)
    n_b = pl.num_programs(0)
    n_g = pl.num_programs(1)
    hkv = len(k_starts)

    def slot_of(lane, slot):
        """(live, first key position) of a lane's table slot."""
        length = meta_ref[0, lane]
        base = meta_ref[1, lane]
        page = _ring_page(slot, meta_ref[2, lane], n_pages) if ring \
            else slot
        p0 = page * ps
        # a slot past the table's width pads the last group: a ring would
        # alias it onto a live page
        live = jnp.logical_and(slot < n_pages, p0 >= 0)
        live = jnp.logical_and(live, p0 < length)
        live = jnp.logical_and(live, p0 <= base + (c - 1))
        if window is not None:
            live = jnp.logical_and(live, p0 + ps > base - (window - 1))
        return live, p0

    def copies(lane, slot, half, i):
        row = rows_ref[lane, slot]
        pools = [(k_hbm, k_buf)] if v_hbm is None \
            else [(k_hbm, k_buf), (v_hbm, v_buf)]
        return [pltpu.make_async_copy(hbm.at[row], buf.at[half, i],
                                      sems.at[n, half, i])
                for n, (hbm, buf) in enumerate(pools)]

    def may_be_live(lane, grp):
        """False only where no slot of the group is live (a group taken
        for live that holds none costs its empty grid step, as a dead
        one does).  Without a ring slot i holds page i and the live
        slots are ONE run; a ring's lie anywhere in it."""
        base = meta_ref[1, lane]
        end = jnp.minimum(meta_ref[0, lane], base + c)
        if ring:
            return end > 0
        live = grp * group * ps < end
        if window is not None:
            last = jnp.minimum((grp + 1) * group, n_pages)
            live = jnp.logical_and(live, last * ps > base - (window - 1))
        return live

    def next_live(lane, grp):
        """The first group that may hold a live slot after (lane, grp) in
        walk order; lane == B where there is none."""
        def step(lane, grp):
            last = grp + 1 == n_g
            return jnp.where(last, lane + 1, lane), jnp.where(last, 0,
                                                              grp + 1)

        return jax.lax.while_loop(
            lambda s: jnp.logical_and(
                s[0] < n_b, jnp.logical_not(may_be_live(
                    jnp.minimum(s[0], n_b - 1), s[1]))),
            lambda s: step(*s), step(lane, grp))

    first = jnp.logical_and(b == 0, gi == 0)

    @pl.when(first)
    def _first():
        # the call's first group is taken for live, and starts its own
        # copies: nobody came before it
        walk[0] = 0
        walk[1] = 0
        walk[2] = 0

    @pl.when(gi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = meta_ref[0, b]
    base = meta_ref[1, b]

    @pl.when(jnp.logical_and(walk[0] == b, walk[1] == gi))
    def _group():
        half = walk[2]
        nxt_b, nxt_g = next_live(b, gi)
        walk[0] = nxt_b
        walk[1] = nxt_g
        walk[2] = 1 - half
        nxt_lane = jnp.minimum(nxt_b, n_b - 1)

        def slot(i, carry):
            ahead = nxt_g * group + i

            @pl.when(jnp.logical_and(nxt_b < n_b,
                                     slot_of(nxt_lane, ahead)[0]))
            def _ahead():
                for cp in copies(nxt_lane, ahead, 1 - half, i):
                    cp.start()

            here = gi * group + i
            live, p0 = slot_of(b, here)

            @pl.when(live)
            def _page():
                own = copies(b, here, half, i)

                @pl.when(first)
                def _cold():
                    for cp in own:
                        cp.start()

                for cp in own:
                    cp.wait()
                k = k_buf[half, i]                  # [ps, Hkv*Dk]
                v = k if v_hbm is None else v_buf[half, i]  # [ps, Hkv*Dv]
                for j in range(hkv):               # static KV-head loop
                    q = q_ref[0, j]                # [G*C, k_width]
                    kj = k[:, k_starts[j]:k_starts[j] + k_width]
                    vj = v[:, v_starts[j]:v_starts[j] + v_width]
                    if kj.dtype != q.dtype:
                        kj = kj.astype(q.dtype)
                        vj = vj.astype(q.dtype)
                    s = jax.lax.dot_general(
                        q, kj, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    s = s * sm_scale
                    cols = p0 + jax.lax.broadcasted_iota(jnp.int32,
                                                         s.shape, 1)
                    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                    if c > 1:
                        qpos = jax.lax.rem(qpos, jnp.int32(c))
                    else:
                        qpos = jnp.zeros_like(qpos)
                    qpos = qpos + base
                    keep = jnp.logical_and(cols < length, cols <= qpos)
                    if window is not None:
                        keep = jnp.logical_and(keep, cols > qpos - window)
                    s = jnp.where(keep, s, -1e30)
                    m_prev = m_scr[j]                      # [rows, LANES]
                    l_prev = l_scr[j]
                    m_cur = jnp.max(s, axis=1)[:, None]
                    m_new = jnp.maximum(
                        m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
                    alpha = jnp.exp(m_prev - m_new)
                    pr = jnp.where(keep, jnp.exp(s - m_new[:, :1]), 0.0)
                    l_scr[j] = alpha * l_prev + jnp.broadcast_to(
                        jnp.sum(pr, axis=1)[:, None], l_prev.shape)
                    m_scr[j] = m_new
                    pv = jax.lax.dot_general(
                        pr.astype(vj.dtype), vj, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    acc_scr[j] = acc_scr[j] * alpha[:, :1] + pv
            return carry

        jax.lax.fori_loop(0, group, slot, 0)

    @pl.when(gi == n_g - 1)
    def _finalize():
        l_fin = l_scr[...]
        dead = l_fin == 0.0                # no key of the lane was live
        if sink_ref is not None:
            # the sink takes mass and gives no value
            l_fin = l_fin + jnp.exp(sink_ref[...] - m_scr[...])
        denom = jnp.where(dead, 1.0, l_fin)
        out = jnp.where(dead[..., :1], 0.0, acc_scr[...] / denom[..., :1])
        o_ref[0] = out.astype(o_ref.dtype)


def split_walk(q, k_pool, v_pool, page_table, latent_values=None):
    """(slots a grid step, grid) of the split kernel on these arrays:
    ``split_slot_group`` of their shapes, lanes x groups of slots."""
    b, c, h, dk = q.shape
    _r, ps, kw = k_pool.shape
    latent = v_pool is None
    hkv = 1 if latent else kw // dk
    dv = int(latent_values) if latent else v_pool.shape[2] // hkv
    n_pages = page_table.shape[1]
    group = split_slot_group(c, h, hkv, kw // hkv, dv, ps,
                             jnp.dtype(k_pool.dtype).itemsize, n_pages,
                             latent=latent)
    return group, (b, -(-n_pages // group))


# One trace for every layer of a kind: ``layer`` is data, and a model's
# builder emits this call once a layer, a program variant and a shape
# inference (dozens a build, each a trace of the kernel's body without
# this)
@functools.partial(jax.jit, inline=True, static_argnames=(
    "n_layer", "sm_scale", "window", "interpret", "name", "latent_values",
    "group"))
def _split_pallas(q, k_pool, v_pool, page_table, lengths, q_base, ring_top,
                  layer, sink, *, n_layer, sm_scale, window, interpret,
                  name, latent_values, group):
    b, c, h, dk = q.shape
    _r, ps, kw = k_pool.shape
    latent = v_pool is None
    hkv = 1 if latent else kw // dk
    dv = int(latent_values) if latent else v_pool.shape[2] // hkv
    g = h // hkv
    n_pages = page_table.shape[1]
    n_groups = -(-n_pages // group)
    # the last group's slots past the table: the trash page's row, dead
    rows = split_kv_rows(jnp.pad(
        page_table, ((0, 0), (0, n_groups * group - n_pages))), layer,
        n_layer)
    ring = ring_top is not None
    top = jnp.asarray(ring_top, jnp.int32).reshape(b) if ring \
        else jnp.zeros(b, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(b)
    meta = jnp.stack([lengths, jnp.asarray(q_base, jnp.int32).reshape(b),
                      top])
    # a latent row is taken whole: q is zero past its own Dk columns
    k_starts, k_width, k_offs = ([0], kw, [0]) if latent \
        else _head_slices(hkv, dk)
    v_starts, v_width, v_offs = _head_slices(hkv, dv)
    # [B, C, Hkv, G, Dk] -> per KV head [G*C, k_width], the head's Dk
    # lanes at their place in its slice
    qh = q.reshape(b, c, hkv, g, dk).transpose(0, 2, 3, 1, 4)
    qh = qh.reshape(b, hkv, g * c, dk)
    qk = jnp.stack([jnp.pad(qh[:, j], ((0, 0), (0, 0),
                                       (k_offs[j],
                                        k_width - dk - k_offs[j])))
                    for j in range(hkv)], axis=1)
    have_sink = sink is not None

    def q_map(bi, pi, rw, mt):
        return (bi, 0, 0, 0)

    # the pools are not blocked: the kernel copies a live page itself
    in_specs = [pl.BlockSpec((1, hkv, g * c, k_width), q_map),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [qk, k_pool]
    scratch = [pltpu.VMEM((hkv, g * c, LANES), jnp.float32),
               pltpu.VMEM((hkv, g * c, LANES), jnp.float32),
               pltpu.VMEM((hkv, g * c, v_width), jnp.float32),
               pltpu.VMEM((2, group, ps, kw), k_pool.dtype)]
    if not latent:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(v_pool)
        scratch.append(pltpu.VMEM((2, group, ps, v_pool.shape[2]),
                                  v_pool.dtype))
    scratch += [pltpu.SemaphoreType.DMA((1 if latent else 2, 2, group)),
                pltpu.SMEM((3,), jnp.int32)]
    if have_sink:
        sk = jnp.broadcast_to(
            jnp.asarray(sink, jnp.float32).reshape(hkv, g, 1, 1),
            (hkv, g, c, LANES)).reshape(hkv, g * c, LANES)
        in_specs.append(pl.BlockSpec((hkv, g * c, LANES),
                                     lambda bi, pi, rw, mt: (0, 0, 0)))
        args.append(sk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_groups),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g * c, v_width), q_map),
        scratch_shapes=scratch,
    )
    base = functools.partial(
        _split_kernel, k_starts=tuple(k_starts), k_width=k_width,
        v_starts=tuple(v_starts), v_width=v_width, c=c, ps=ps,
        n_pages=n_pages, group=group, window=window, ring=ring,
        sm_scale=sm_scale)

    def kernel(rows_ref, meta_ref, q_ref, k_hbm, *rest):
        rest = list(rest)
        v_hbm = None if latent else rest.pop(0)
        sink_ref = rest.pop(0) if have_sink else None
        o_ref, m_scr, l_scr, acc_scr, k_buf, *rest = rest
        v_buf = None if latent else rest.pop(0)
        return base(rows_ref, meta_ref, q_ref, k_hbm, v_hbm, sink_ref, o_ref,
                    m_scr, l_scr, acc_scr, k_buf, v_buf, *rest)

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g * c, v_width), q.dtype),
        # in order, on one core: a step starts the next live group's
        # copies, of this lane or of the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(rows, meta, *args)
    out = jnp.stack([out[:, j, :, v_offs[j]:v_offs[j] + dv]
                     for j in range(hkv)], axis=1)       # [B,Hkv,G*C,Dv]
    out = out.reshape(b, hkv, g, c, dv).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, c, h, dv)


# Mosaic's default scoped VMEM on a v5e: the split kernel asks for no more
SPLIT_VMEM_BYTES = 16 * 1024 * 1024


def _split_vmem_need(queries: int, group: int, n_head: int, kv_heads: int,
                     d_key: int, d_value: int, page_size: int,
                     itemsize: int, latent: bool) -> int:
    """Bytes of fast memory one grid step of the split kernel holds: per
    query and query head, q and the output (double-buffered blocks), the
    running max, sum and float32 accumulator, the sink's block; besides
    ``group`` pages of keys and values (double-buffered; ``latent``: ONE
    row holds both, so pages of key rows alone) and one KV head's scores,
    mask and probabilities against a page."""
    _, k_width, _ = _head_slices(kv_heads, d_key)
    _, v_width, _ = _head_slices(kv_heads, d_value)
    per_row = 2 * (k_width + v_width) * itemsize \
        + (2 * LANES + v_width) * 4 + 2 * LANES * 4
    pages = 2 * page_size * kv_heads \
        * (d_key + (0 if latent else d_value)) * itemsize
    return n_head * queries * per_row + group * pages \
        + 3 * (n_head // kv_heads) * queries * page_size * 4


def split_query_tile(chunk: int, n_head: int, kv_heads: int, d_key: int,
                     d_value: int, page_size: int, itemsize: int,
                     vmem_bytes: Optional[int] = None,
                     latent: bool = False) -> int:
    """How many queries of a prompt chunk one lane of the split kernel
    takes: the largest of chunk, chunk / 2, chunk / 4 ... whose blocks fit
    ``vmem_bytes`` (default ``SPLIT_VMEM_BYTES``) beside one page
    (``_split_vmem_need``)."""
    if vmem_bytes is None:
        vmem_bytes = SPLIT_VMEM_BYTES
    c = int(chunk)
    while c % 2 == 0 and _split_vmem_need(
            c, 1, n_head, kv_heads, d_key, d_value, page_size, itemsize,
            latent) > vmem_bytes:
        c //= 2
    return c


def split_slot_group(queries: int, n_head: int, kv_heads: int, d_key: int,
                     d_value: int, page_size: int, itemsize: int,
                     slots: int, vmem_bytes: Optional[int] = None,
                     latent: bool = False) -> int:
    """How many consecutive table slots one grid step of the split kernel
    walks: the largest power of two, at most the table's ``slots``, at
    which the lane's blocks and the group's pages (``_split_vmem_need``)
    take at most HALF of ``vmem_bytes`` (default ``SPLIT_VMEM_BYTES``).
    The half tells the two kinds of call apart by what they hold.  A
    decode row's blocks are small and its step is bound by the step
    itself and its transfers: it takes 4 to 8 slots (on the chip those
    read within 2 % of the best of 1 .. 16 for every served model's
    decode call: PERF.md section 6, PR 44).  A prefill tile
    (``split_query_tile``) holds more than half already and is bound by
    its products: it takes one slot a step, the grid of a page a step
    (where 2 or 4 slots fit at all they took 1 to 5 % less time)."""
    if vmem_bytes is None:
        vmem_bytes = SPLIT_VMEM_BYTES
    k = 1
    while 2 * k <= int(slots) and 2 * _split_vmem_need(
            queries, 2 * k, n_head, kv_heads, d_key, d_value, page_size,
            itemsize, latent) <= vmem_bytes:
        k *= 2
    return k


def _resolve_q_base(q, q_base, causal: bool):
    if q_base is not None:
        return q_base
    if causal:
        raise ValueError("ragged_decode_attention: causal masking needs "
                         "q_base (global position of the first query)")
    return jnp.zeros(q.shape[0], jnp.int32)


def ragged_decode_attention(q, pool, page_table, lengths, q_base=None,
                            *, layer: int, n_layer: int, causal: bool = True,
                            sm_scale: Optional[float] = None,
                            impl: Optional[str] = None,
                            scales=None, v_pool=None,
                            window: Optional[int] = None, sink=None,
                            ring_top=None,
                            kernel_name: Optional[str] = None,
                            latent_values: Optional[int] = None
                            ) -> jax.Array:
    """Attention of per-lane query blocks against a paged KV pool.

    Shapes:
        q           [B, C, H, D]  (C = 1 steady-state decode; C = chunk
                                   size during chunked prefill)
        pool        [R, page_size, H*D]   (see paged_kv_rows layout)
        page_table  [B, P] int32  logical page ids (trash page 0 pads)
        lengths     [B]    int32  live KV positions per lane
        q_base      [B]    int32  global position of q[:, 0] (required
                                  when causal — masks key > base + j)
        scales      [1, R, page_size] fp32 (int8 pools only): one block
                                  scale per (physical row, slot), written
                                  by quantized_paged_cache_write; K/V
                                  dequantize in-register during the walk

    Returns ctx [B, C, H, D].  Per-lane work is O(P * page_size) with
    the page indirection resolved by the block table — bytes for pages a
    lane never touched are never read on the Pallas path."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    q_base = _resolve_q_base(q, q_base, causal)
    if impl is None:
        impl = default_impl()
    if v_pool is not None or latent_values is not None:
        # split pools (keys in ``pool``, values in ``v_pool``, row =
        # page * n_layer + layer): grouped KV heads (H a multiple of
        # Hkv = pool width / Dk), values of another width than keys,
        # ``window`` (keys q-window < j <= q), ``sink`` [H] (a logit per
        # query head in the softmax's denominator only) and ``ring_top``
        # [B] (the table is a ring of pages: see ``split_kv_rows``);
        # ``kernel_name`` is the Mosaic call's name in a device trace.
        # ``latent_values`` in ``v_pool``'s place: ONE pool of latent
        # rows [R, page, >= Dk] (``latent_row_width``) whose leading
        # ``latent_values`` columns are the values; q [B, C, H, Dk] ->
        # [B, C, H, latent_values]
        if scales is not None or not causal:
            raise ValueError("ragged_decode_attention: split pools are "
                             "causal and take no int8 scales")
        if latent_values is not None and (
                v_pool is not None or pool.shape[2] < q.shape[-1]
                or not 0 < int(latent_values) <= q.shape[-1]):
            raise ValueError(
                "ragged_decode_attention: latent_values is the width of "
                "the values inside ONE pool's rows (no v_pool), which "
                "are at least as wide as the queries")
        if impl in ("pallas", "pallas_interpret"):
            return _split_pallas(
                q, pool, v_pool, page_table, lengths, q_base, ring_top,
                layer, sink, n_layer=n_layer, sm_scale=float(sm_scale),
                window=None if window is None else int(window),
                interpret=(impl == "pallas_interpret"),
                name=kernel_name or "ragged_paged_attn_gqa",
                latent_values=latent_values,
                group=split_walk(q, pool, v_pool, page_table,
                                 latent_values)[0])
        return _split_xla(q, pool, v_pool, page_table, lengths, q_base,
                          ring_top, layer, n_layer, float(sm_scale), window,
                          sink, latent_values=latent_values)
    if window is not None or sink is not None or ring_top is not None:
        raise ValueError("ragged_decode_attention: window, sink and "
                         "ring_top need split pools (v_pool)")
    if impl in ("pallas", "pallas_interpret"):
        return _ragged_pallas(q, pool, page_table, lengths, q_base, layer,
                              n_layer, causal, float(sm_scale),
                              interpret=(impl == "pallas_interpret"),
                              scales=scales)
    return _ragged_xla(q, pool, page_table, lengths, q_base, layer, n_layer,
                       causal, float(sm_scale), scales=scales)


def ragged_decode_attention_sharded(mesh: Mesh, q, pool, page_table,
                                    lengths, q_base=None, *,
                                    batch_axis: Optional[str],
                                    head_axis: Optional[str], layer: int,
                                    n_layer: int, causal: bool = True,
                                    sm_scale: Optional[float] = None,
                                    impl: Optional[str] = None,
                                    scales=None) -> jax.Array:
    """``ragged_decode_attention`` inside a jit that spans ``mesh``.

    XLA partitions the gather path by itself, but a Mosaic kernel
    "cannot be automatically partitioned", so the Pallas impls map the
    call over the mesh: lanes (q, tables, lengths) split on
    ``batch_axis``, heads (q's head dim and the pool's minor dim, whose
    equal slices are whole heads) on ``head_axis``;
    either may be None (that dim stays whole on every device).  The
    int8 scale sidecar is one scale per (row, slot) for ALL heads, so it
    rides replicated — each shard pages its own head slice of the pool
    against the same block tables."""
    impl = impl or default_impl()
    kw = dict(layer=layer, n_layer=n_layer, causal=causal,
              sm_scale=sm_scale, impl=impl)
    if impl == "xla":
        return ragged_decode_attention(q, pool, page_table, lengths, q_base,
                                       scales=scales, **kw)
    q_spec = P(batch_axis, None, head_axis, None)
    args = [q, pool, page_table, lengths,
            _resolve_q_base(q, q_base, causal)]
    specs = [q_spec, P(None, None, head_axis), P(batch_axis, None),
             P(batch_axis), P(batch_axis)]
    if scales is not None:
        args.append(scales)
        specs.append(P(None, None, None))

    def local(q_, pool_, table_, lengths_, base_, *scales_):
        return ragged_decode_attention(
            q_, pool_, table_, lengths_, base_,
            scales=scales_[0] if scales_ else None, **kw)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=q_spec, check_vma=False)(*args)


def keep_scale(seed_u32, bh, rows, cols, rate):
    """Deterministic counter-based dropout mask for attention probabilities.

    A murmur3-style finalizer over the *global* (batch*head, query, key)
    position and a traced uint32 seed, in pure uint32 jnp arithmetic — so the
    identical expression runs inside the Pallas kernels and the XLA fallback,
    and the masks match bit-exactly without ever materialising an [Lq, Lk]
    mask tensor.  Inputs broadcast; returns float32 values in
    {0, 1/(1-rate)} (inverted-dropout scaling).
    """
    u32 = jnp.uint32
    x = (rows.astype(u32) * u32(0x9E3779B1) +
         cols.astype(u32) * u32(0x85EBCA77))
    x = x ^ (jnp.asarray(bh).astype(u32) * u32(0xC2B2AE3D)) ^ seed_u32
    x = x ^ (x >> 16)
    x = x * u32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * u32(0xC2B2AE35)
    x = x ^ (x >> 16)
    # top 24 bits -> uniform [0,1); bitcast through int32 because Mosaic
    # has no uint32->float32 cast (value < 2^24, so the int32 is positive)
    u = jax.lax.bitcast_convert_type(x >> 8, jnp.int32).astype(
        jnp.float32) * (1.0 / (1 << 24))
    return jnp.where(u >= rate, 1.0 / (1.0 - rate), 0.0).astype(jnp.float32)


def seed_to_carrier(bits) -> jax.Array:
    """Pack RNG bits into a float32 scalar (bit-cast) so it can ride through
    custom_vjp as an ordinary differentiable operand with a zero cotangent."""
    arr = jnp.asarray(bits)
    if arr.dtype == jnp.float32:
        return arr
    return jax.lax.bitcast_convert_type(arr.astype(jnp.uint32), jnp.float32)


def _carrier_to_u32(seed_f: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(seed_f, jnp.uint32)


def dropout_carrier(dropout_rate: float, dropout_seed) -> jax.Array:
    """The f32 seed operand of an attention call: the packed seed when
    dropout is on (a seed is then required), a zero placeholder when it
    is off."""
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        return seed_to_carrier(dropout_seed)
    return jnp.zeros((), jnp.float32)


def shard_dropout_seed(seed_f: jax.Array, batch_axis: Optional[str],
                       head_axis: Optional[str]) -> jax.Array:
    """uint32 dropout seed for THIS shard of a shard_map'd attention
    call: the in-kernel hash keys on the shard-LOCAL (batch*head) index,
    so shards that split the batch or the heads would otherwise draw the
    same masks — fold their mesh coordinates into the seed.  (Sequence
    shards need nothing: the hash keys on global positions.)"""
    s = _carrier_to_u32(seed_f)
    if batch_axis:
        s = s ^ (jax.lax.axis_index(batch_axis).astype(jnp.uint32)
                 * jnp.uint32(0x27D4EB2F))
    if head_axis:
        s = s ^ (jax.lax.axis_index(head_axis).astype(jnp.uint32)
                 * jnp.uint32(0x165667B1))
    return s


def offsets_carrier(row_off, col_off) -> jax.Array:
    """(row, col) global block offsets as the f32[2] bit-cast carrier the
    kernels decode (_off_rc / _tile_rc) — the int analog of
    seed_to_carrier."""
    return jax.lax.bitcast_convert_type(
        jnp.stack([jnp.asarray(row_off, jnp.int32),
                   jnp.asarray(col_off, jnp.int32)]), jnp.float32)


def bh_grid(b: int, h: int) -> jax.Array:
    """[b,h,1,1] flattened batch*head index — MUST match the Pallas grid's
    program_id(0) = b_idx*h + h_idx convention so XLA-side masks equal the
    in-kernel ones."""
    return (jnp.arange(b, dtype=jnp.int32)[:, None] * h +
            jnp.arange(h, dtype=jnp.int32)[None, :])[:, :, None, None]


# ---------------------------------------------------------------------------
# in-kernel helpers shared by fwd / bwd kernels
# ---------------------------------------------------------------------------

def _ld(ref):
    """Read a [rows, d] tile from a [1, rows, d] q/k/v/do/o block ref."""
    return ref[0]


def _st(ref, val):
    ref[0] = val


def _tile_rc(off_ref, qi, ki, block_q, block_k):
    """(rows_global, cols_global, cols_local) position grids for this
    [block_q, block_k] tile.  off_ref (optional, [1, 2] i32-as-f32
    carrier) adds DYNAMIC global offsets — how ring attention tells the
    kernel where its local shard and the currently-held k/v block sit in
    the full sequence.  Causal masking and the dropout hash key on the
    GLOBAL positions; key-padding (kv_len) keys on the LOCAL column."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    cols_local = cols
    if off_ref is not None:
        off = jax.lax.bitcast_convert_type(off_ref[...], jnp.int32)
        rows = rows + off[0, 0]
        cols = cols + off[0, 1]
    return rows, cols, cols_local


def _tile_mask(s, rows, cols, cols_local, causal, kv_len):
    """Causal mask on global positions + key-padding mask on the local
    column index of a [block_q, block_k] score tile."""
    if not causal and kv_len is None:
        return s
    keep = None
    if causal:
        keep = rows >= cols
    if kv_len is not None:
        pad_ok = cols_local < kv_len
        keep = pad_ok if keep is None else jnp.logical_and(keep, pad_ok)
    return jnp.where(keep, s, DEFAULT_MASK_VALUE)


def _tile_keep_scale(seed_ref, bh, rows_g, cols_g, rate):
    # vector-shaped bitcast: Mosaic's tpu.bitcast rejects bare scalars.
    # ``bh`` is pl.program_id(0) hoisted to kernel top level: calling
    # program_id INSIDE a pl.when body breaks interpret mode (the
    # interpreter doesn't rewrite the primitive inside cond sub-jaxprs).
    seed_u = jax.lax.bitcast_convert_type(seed_ref[...], jnp.uint32)[0, 0]
    return keep_scale(seed_u, bh, rows_g, cols_g, rate)


def _causal_mask_branches(causal, off_ref, n_serial_blocks, live, qi, ki,
                          block_q, block_k, body):
    """Emit the tile compute under pl.when, with mask-free fully-live
    tiles when profitable: under a STATIC causal mask every tile strictly
    below the diagonal needs no iota/compare/where VPU work.  The runtime
    two-branch structure itself costs ~10% at small grids (measured: NET
    LOSS at 2 serial blocks, 23 vs 26 fwd TF/s at L=2048), so it only
    switches on when >= 3/4 of live tiles take the free path
    (n_serial_blocks >= 4: +9% fwd at L=4096, +8% at 8192).
    ``body(skip_causal_mask)`` emits one full-tile flash/grad update."""
    if causal and off_ref is None and n_serial_blocks >= 4:
        # a live tile needs the causal mask iff its smallest row index is
        # below its largest column index (it straddles the diagonal)
        is_edge = qi * block_q < ki * block_k + block_k - 1

        @pl.when(live & is_edge)
        def _compute_edge():
            body(skip_causal_mask=False)

        @pl.when(live & jnp.logical_not(is_edge))
        def _compute_full():
            body(skip_causal_mask=True)
    else:
        @pl.when(live)
        def _compute():
            body(skip_causal_mask=False)


def _compiler_params():
    """Parallel bh/outer grid dims, serial accumulation dim — and a raised
    scoped-VMEM ceiling: v5e has far more physical VMEM than the default
    16 MiB scope, and 1024-blocks (the measured fwd+bwd winner at L >= 1k)
    need ~17-23 MiB once dropout's keep-mask tile joins s/p/dp."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _qk_live(qi, ki, block_q, block_k, causal, kv_len, num_k_blocks):
    """Static-shape predicate: does tile (qi, ki) contribute at all?
    Causal tiles strictly above the diagonal and tiles entirely inside the
    key padding are skipped (their matmuls never issue)."""
    live = True
    if causal:
        live = qi * block_q + block_q - 1 >= ki * block_k
    if kv_len is not None and kv_len < num_k_blocks * block_k:
        pad_live = ki * block_k < kv_len
        live = pad_live if live is True else jnp.logical_and(live, pad_live)
    return live


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, off_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale, causal, kv_len, block_q, block_k, num_k_blocks,
                dropout_rate):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # dynamic offsets (ring shards) defeat the static diagonal skip; the
    # mask still zeroes dead tiles, they just pay their matmuls
    live = True if off_ref is not None else _qk_live(
        qi, ki, block_q, block_k, causal, kv_len, num_k_blocks)

    def _body(skip_causal_mask):
        q = _ld(q_ref)                                 # [bq, D] input dtype
        k = _ld(k_ref)                                 # [bk, D]
        v = _ld(v_ref)                                 # [bk, D]
        rows, cols, cols_l = _tile_rc(off_ref, qi, ki, block_q, block_k)
        # MXU matmul in the INPUT dtype (bf16 native path), f32 accumulate
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                               # [bq, bk]
        if bias_ref is not None:
            s = s + bias_ref[0, ...].astype(jnp.float32)
        if (not skip_causal_mask) or (kv_len is not None):
            s = _tile_mask(s, rows, cols, cols_l,
                           causal and not skip_causal_mask, kv_len)
        m_prev = m_scr[...]                        # [bq, 128] (bcast lanes)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)[:, None]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                  # [bq, bk] f32
        l_new = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=1)[:, None], l_prev.shape)
        m_scr[...] = m_new
        l_scr[...] = l_new
        if dropout_rate > 0.0:
            # mask the unnormalised probs (l keeps the full softmax sum —
            # dropout acts after normalisation, and /l distributes)
            pd = p * _tile_keep_scale(seed_ref, bh, rows, cols,
                                      dropout_rate)
        else:
            pd = p
        pv = jax.lax.dot_general(pd.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv

    # r5 measured note: triangular in-kernel sub-tiling of the diagonal
    # tile (skipping above-diagonal 256- or 512-wide sub-tiles on
    # VMEM-resident data) was implemented and benchmarked — it LOST
    # (12.7 vs 16.1 fwd TF/s at L=1024): Mosaic pipelines one big tile
    # far better than a chain of sliced scratch updates, so the causal
    # waste inside the diagonal tile is cheaper than the bookkeeping
    # that removes it.  What stays is the free win below (see
    # _causal_mask_branches).
    _causal_mask_branches(causal, off_ref, num_k_blocks, live, qi, ki,
                          block_q, block_k, _body)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l_fin = l_scr[...]
        m_fin = m_scr[...]
        # A row is fully masked when l never accumulated (l==0) OR when
        # its running max never rose above the finite DEFAULT_MASK_VALUE
        # — in that case every p was exp(0)=1 over masked keys and both
        # acc and l are finite garbage (real scores cannot reach
        # MASK/2 ≈ -1.2e38).  Zero the output and poison the lse so the
        # backward's exp(s - lse) underflows to 0 for those rows.
        dead = (l_fin == 0.0) | (m_fin <= DEFAULT_MASK_VALUE * 0.5)
        denom = jnp.where(dead, 1.0, l_fin)
        out = jnp.where(dead[:, :1], 0.0, acc_scr[...] / denom[:, :1])
        _st(o_ref, out.astype(o_ref.dtype))
        if lse_ref is not None:
            lse_ref[0] = jnp.where(dead, jnp.inf,
                                   m_fin + jnp.log(denom))


def _qkv_specs(d, block, which):
    """BlockSpec for one of q/k/v/do/o on the [B*H, L, D] kernel view.
    which='q' blocks follow grid dim 1, 'k' follows grid dim 2.  (A true
    [B, L, H, D]-indexed block spec is illegal on TPU: a one-head block's
    trailing dims would be (1, d) with d < 128 lanes, which Mosaic rejects
    — so 'blhd' transposes at the kernel boundary instead, where XLA fuses
    the copy into the neighbouring projection matmuls.)"""
    if which == "q":
        return pl.BlockSpec((1, block, d), lambda bh, qi, ki: (bh, qi, 0))
    return pl.BlockSpec((1, block, d), lambda bh, qi, ki: (bh, ki, 0))


def _flatten_heads(x, layout):
    """-> [B*H, L, D] kernel view (blhd transposes at this boundary)."""
    if layout == "blhd":
        x = jnp.transpose(x, (0, 2, 1, 3))
    b, h, l, d = x.shape
    return x.reshape(b * h, l, d)


def _bhld_shape(x, layout):
    """(b, h, l, d) independent of layout."""
    if layout == "blhd":
        b, l, h, d = x.shape
        return b, h, l, d
    return x.shape


def _pallas_forward(q, k, v, bias, seed, offsets, sm_scale, causal, kv_len,
                    block_q, block_k, dropout_rate, layout, interpret,
                    need_lse):
    b, h, lq, d = _bhld_shape(q, layout)
    lk = _bhld_shape(k, layout)[2]
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    assert lq % block_q == 0 and lk % block_k == 0, (lq, lk, block_q, block_k)
    nq, nk = lq // block_q, lk // block_k
    grid = (b * h, nq, nk)

    in_specs = [
        _qkv_specs(d, block_q, "q"),
        _qkv_specs(d, block_k, "k"),
        _qkv_specs(d, block_k, "k"),
    ]
    args = [_flatten_heads(q, layout), _flatten_heads(k, layout),
            _flatten_heads(v, layout)]
    have_bias = bias is not None
    if have_bias:
        bb, bh_, _, _ = bias.shape

        def bias_map(bh, qi, ki):
            bidx = (bh // h) % bb if bb > 1 else 0
            hidx = (bh % h) if bh_ > 1 else 0
            return (bidx * bh_ + hidx, qi, ki)

        in_specs.append(pl.BlockSpec((1, block_q, block_k), bias_map))
        args.append(bias.reshape(bb * bh_, lq, lk))
    have_seed = dropout_rate > 0.0
    if have_seed:
        in_specs.append(pl.BlockSpec((1, 1), lambda bh, qi, ki: (0, 0)))
        args.append(jnp.asarray(seed, jnp.float32).reshape(1, 1))
    have_off = offsets is not None
    if have_off:
        in_specs.append(pl.BlockSpec((1, 2), lambda bh, qi, ki: (0, 0)))
        args.append(jnp.asarray(offsets, jnp.float32).reshape(1, 2))

    base = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, kv_len=kv_len,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
        dropout_rate=dropout_rate)

    def kernel(q_ref, k_ref, v_ref, *rest):
        rest = list(rest)
        bias_ref = rest.pop(0) if have_bias else None
        seed_ref = rest.pop(0) if have_seed else None
        off_ref = rest.pop(0) if have_off else None
        if need_lse:
            o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        else:
            o_ref, m_scr, l_scr, acc_scr = rest
            lse_ref = None
        return base(q_ref, k_ref, v_ref, bias_ref, seed_ref, off_ref,
                    o_ref, lse_ref, m_scr, l_scr, acc_scr)

    scratch = [
        pltpu.VMEM((block_q, LANES), jnp.float32),   # running max
        pltpu.VMEM((block_q, LANES), jnp.float32),   # running sum
        pltpu.VMEM((block_q, d), jnp.float32),       # output accumulator
    ]
    out_specs = [_qkv_specs(d, block_q, "q")]
    out_shape = [jax.ShapeDtypeStruct((b * h, lq, d), q.dtype)]
    if need_lse:
        # row stats in lane-broadcast layout: [bh, lq, 128] so the bwd
        # kernels read [block_q, 128] tiles with no transpose anywhere
        out_specs.append(pl.BlockSpec((1, block_q, LANES),
                                      lambda bh, qi, ki: (bh, qi, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b * h, lq, LANES),
                                              jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if need_lse else out_specs[0],
        out_shape=out_shape if need_lse else out_shape[0],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    if need_lse:
        out, lse = res
    else:
        out, lse = res, None
    out = out.reshape(b, h, lq, d)
    if layout == "blhd":
        out = jnp.transpose(out, (0, 2, 1, 3))
    return out, lse


# ---------------------------------------------------------------------------
# Pallas backward kernels (dq, then dk/dv) — bias-free path
# ---------------------------------------------------------------------------

def _delta_tile(o_ref, do_ref):
    """rowsum(o * do) for this q block, [bq, 1] f32 — computed in-kernel
    from the o/do tiles (an XLA-side [bh, lq, 128] delta array would cost
    4x the HBM of re-reading the bf16 o block)."""
    o = _ld(o_ref).astype(jnp.float32)
    do = _ld(do_ref).astype(jnp.float32)
    return jnp.sum(o * do, axis=1)[:, None]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, seed_ref,
               off_ref, dq_ref, dq_scr,
               *, sm_scale, causal, kv_len, block_q, block_k, num_k_blocks,
               dropout_rate):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = True if off_ref is not None else _qk_live(
        qi, ki, block_q, block_k, causal, kv_len, num_k_blocks)

    def _body(skip_causal_mask):
        q = _ld(q_ref)
        k = _ld(k_ref)
        v = _ld(v_ref)
        do = _ld(do_ref)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        rows, cols, cols_l = _tile_rc(off_ref, qi, ki, block_q, block_k)
        s = _tile_mask(s, rows, cols, cols_l,
                       causal and not skip_causal_mask, kv_len)
        p = jnp.exp(s - lse_ref[0][:, :1])             # [bq, bk] f32
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = dp * _tile_keep_scale(seed_ref, bh, rows, cols,
                                       dropout_rate)
        ds = p * (dp - _delta_tile(o_ref, do_ref)) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_mask_branches(causal, off_ref, num_k_blocks, live, qi, ki,
                          block_q, block_k, _body)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        _st(dq_ref, dq_scr[...].astype(dq_ref.dtype))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, seed_ref,
                off_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                *, sm_scale, causal, kv_len, block_q, block_k, num_q_blocks,
                num_k_blocks, dropout_rate):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = True if off_ref is not None else _qk_live(
        qi, ki, block_q, block_k, causal, kv_len, num_k_blocks)

    def _body(skip_causal_mask):
        q = _ld(q_ref)
        k = _ld(k_ref)
        v = _ld(v_ref)
        do = _ld(do_ref)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        rows, cols, cols_l = _tile_rc(off_ref, qi, ki, block_q, block_k)
        s = _tile_mask(s, rows, cols, cols_l,
                       causal and not skip_causal_mask, kv_len)
        p = jnp.exp(s - lse_ref[0][:, :1])             # [bq, bk] f32
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _tile_keep_scale(seed_ref, bh, rows, cols,
                                    dropout_rate)
            pv = p * keep                              # what multiplied v fwd
            dp = dp * keep
        else:
            pv = p
        # dv += pv^T @ do; dk += ds^T @ q  (contract over the q rows)
        dv_scr[...] += jax.lax.dot_general(
            pv.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _delta_tile(o_ref, do_ref)) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # the serial dim here is q, so gate on the q-block count
    _causal_mask_branches(causal, off_ref, num_q_blocks, live, qi, ki,
                          block_q, block_k, _body)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        _st(dk_ref, dk_scr[...].astype(dk_ref.dtype))
        _st(dv_ref, dv_scr[...].astype(dv_ref.dtype))


def _pallas_backward(q, k, v, do, out, lse128, seed, offsets, sm_scale,
                     causal, kv_len, block_q, block_k, dropout_rate, layout,
                     interpret):
    """dq/dk/dv via two Pallas kernels; lse128 is the forward's [bh, lq, 128]
    stat output.  delta = rowsum(o * do) is recomputed per-tile inside the
    kernels from the o/do blocks (cheaper than materialising a lane-broadcast
    delta array in HBM)."""
    b, h, lq, d = _bhld_shape(q, layout)
    lk = _bhld_shape(k, layout)[2]
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    nq, nk = lq // block_q, lk // block_k

    stat_spec_q = pl.BlockSpec((1, block_q, LANES),
                               lambda bh, i, j: (bh, i, 0))
    stat_spec_kq = pl.BlockSpec((1, block_q, LANES),
                                lambda bh, ki, qi: (bh, qi, 0))
    have_seed = dropout_rate > 0.0
    seed_arr = jnp.asarray(seed, jnp.float32).reshape(1, 1)
    have_off = offsets is not None
    off_arr = (jnp.asarray(offsets, jnp.float32).reshape(1, 2)
               if have_off else None)

    q3 = _flatten_heads(q, layout)
    k3 = _flatten_heads(k, layout)
    v3 = _flatten_heads(v, layout)
    do3 = _flatten_heads(do, layout)
    o3 = _flatten_heads(out, layout)

    # ---- dq: grid (bh, nq, nk), k-blocks innermost accumulate into scratch
    dq_specs = [
        _qkv_specs(d, block_q, "q"),
        _qkv_specs(d, block_k, "k"),
        _qkv_specs(d, block_k, "k"),
        _qkv_specs(d, block_q, "q"),
        _qkv_specs(d, block_q, "q"),
        stat_spec_q,
    ]
    dq_args = [q3, k3, v3, do3, o3, lse128]
    if have_seed:
        dq_specs.append(pl.BlockSpec((1, 1), lambda bh, qi, ki: (0, 0)))
        dq_args.append(seed_arr)
    if have_off:
        dq_specs.append(pl.BlockSpec((1, 2), lambda bh, qi, ki: (0, 0)))
        dq_args.append(off_arr)

    dq_base = functools.partial(
        _dq_kernel, sm_scale=sm_scale, causal=causal, kv_len=kv_len,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
        dropout_rate=dropout_rate)

    def dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest):
        rest = list(rest)
        seed_ref = rest.pop(0) if have_seed else None
        off_ref = rest.pop(0) if have_off else None
        dq_ref, dq_scr = rest
        return dq_base(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                       seed_ref, off_ref, dq_ref, dq_scr)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * h, nq, nk),
        in_specs=dq_specs,
        out_specs=_qkv_specs(d, block_q, "q"),
        out_shape=jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*dq_args)

    # ---- dk/dv: grid (bh, nk, nq), q-blocks innermost
    def kv_spec(block):
        return pl.BlockSpec((1, block, d), lambda bh, ki, qi: (bh, ki, 0))

    def qdo_spec(block):
        return pl.BlockSpec((1, block, d), lambda bh, ki, qi: (bh, qi, 0))

    dkv_specs = [qdo_spec(block_q), kv_spec(block_k), kv_spec(block_k),
                 qdo_spec(block_q), qdo_spec(block_q), stat_spec_kq]
    dkv_args = [q3, k3, v3, do3, o3, lse128]
    if have_seed:
        dkv_specs.append(pl.BlockSpec((1, 1), lambda bh, ki, qi: (0, 0)))
        dkv_args.append(seed_arr)
    if have_off:
        dkv_specs.append(pl.BlockSpec((1, 2), lambda bh, ki, qi: (0, 0)))
        dkv_args.append(off_arr)

    dkv_base = functools.partial(
        _dkv_kernel, sm_scale=sm_scale, causal=causal, kv_len=kv_len,
        block_q=block_q, block_k=block_k, num_q_blocks=nq, num_k_blocks=nk,
        dropout_rate=dropout_rate)

    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest):
        rest = list(rest)
        seed_ref = rest.pop(0) if have_seed else None
        off_ref = rest.pop(0) if have_off else None
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        return dkv_base(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                        seed_ref, off_ref, dk_ref, dv_ref, dk_scr, dv_scr)

    kv_shape = jax.ShapeDtypeStruct((b * h, lk, d), k.dtype)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * h, nk, nq),
        in_specs=dkv_specs,
        out_specs=[kv_spec(block_k), kv_spec(block_k)],
        out_shape=[kv_shape, kv_shape],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*dkv_args)
    dq = dq.reshape(b, h, lq, d)
    dk = dk.reshape(b, h, lk, d)
    dv = dv.reshape(b, h, lk, d)
    if layout == "blhd":
        dq, dk, dv = (jnp.transpose(x, (0, 2, 1, 3)) for x in (dq, dk, dv))
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Blockwise XLA path: reference forward (CPU / fallback) and the
# bias-carrying backward (dbias needs the [lq, lk]-shaped output anyway)
# ---------------------------------------------------------------------------

def _block_keep_scale(seed_u, b, h, lq_rows, ki, block_k, rate,
                      col_off=0):
    """[b,h,lq,block_k] inverted-dropout scale for one key block, using the
    same global-position hash as the Pallas kernels (bh = b*h + h index);
    lq_rows are already global, col_off shifts the key positions."""
    bh = bh_grid(b, h)
    rows = lq_rows[None, None, :, None]
    cols = (col_off + ki * block_k +
            jnp.arange(block_k, dtype=jnp.int32))[None, None, None, :]
    return keep_scale(seed_u, bh, rows, cols, rate)


def _off_rc(offsets):
    """(row_off, col_off) traced i32 scalars from the f32[2] carrier."""
    if offsets is None:
        return jnp.int32(0), jnp.int32(0)
    off = jax.lax.bitcast_convert_type(
        jnp.asarray(offsets, jnp.float32).reshape(2), jnp.int32)
    return off[0], off[1]


def _xla_forward(q, k, v, bias, seed, offsets, sm_scale, causal, kv_len,
                 block_k, dropout_rate=0.0):
    """lax.scan over key blocks with online softmax; q/k/v in [b,h,l,d].
    Returns (out, lse) with lse [b,h,lq] (+inf on fully-masked rows)."""
    b, h, lq, d = q.shape
    lk = k.shape[2]
    block_k = min(block_k, lk)
    nk = lk // block_k
    qf = q.astype(jnp.float32)
    row_off, col_off = _off_rc(offsets)
    rows = row_off + jnp.arange(lq)[:, None]
    lq_rows = row_off + jnp.arange(lq, dtype=jnp.int32)
    seed_u = _carrier_to_u32(jnp.asarray(seed, jnp.float32)) \
        if dropout_rate > 0.0 else None

    def step(carry, ki):
        m_prev, l_prev, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k, ki * block_k, block_k, 2)
        vs = jax.lax.dynamic_slice_in_dim(v, ki * block_k, block_k, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, ks.astype(jnp.float32))
        s = s * sm_scale
        if bias is not None:
            bs = jax.lax.dynamic_slice_in_dim(bias, ki * block_k, block_k, 3)
            s = s + bs.astype(jnp.float32)
        cols_l = ki * block_k + jnp.arange(block_k)[None, :]
        cols = col_off + cols_l
        if causal:
            s = jnp.where(rows >= cols, s, DEFAULT_MASK_VALUE)
        if kv_len is not None:
            s = jnp.where(cols_l[None, None] < kv_len, s,
                          DEFAULT_MASK_VALUE)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        if dropout_rate > 0.0:
            pd = p * _block_keep_scale(seed_u, b, h, lq_rows, ki, block_k,
                                       dropout_rate, col_off)
        else:
            pd = p
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", pd, vs.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((b, h, lq), -jnp.inf, jnp.float32),
            jnp.zeros((b, h, lq), jnp.float32),
            jnp.zeros((b, h, lq, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, jnp.arange(nk))
    # same dead-row contract as the Pallas kernel: rows whose max never
    # rose above the finite DEFAULT_MASK_VALUE saw only masked keys —
    # their acc/l are garbage (p=exp(0)=1 over masked scores), so return
    # output 0 / lse +inf instead
    dead = (l == 0.0) | (m <= DEFAULT_MASK_VALUE * 0.5)
    denom = jnp.where(dead, 1.0, l)
    lse = jnp.where(dead, jnp.inf, m + jnp.log(denom))
    out = jnp.where(dead[..., None], 0.0, acc / denom[..., None])
    return out.astype(q.dtype), lse


def _xla_backward(q, k, v, bias, o, do, lse, seed, offsets, sm_scale,
                  causal, kv_len, block_k, dropout_rate=0.0):
    """Recompute p blockwise from the saved lse and accumulate dq/dk/dv
    (+dbias) — the flash-attention backward; no [Lq, Lk] intermediate, only
    the dbias *output* (when bias is given) has that shape."""
    b, h, lq, d = q.shape
    lk = k.shape[2]
    block_k = min(block_k, lk)
    nk = lk // block_k
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    # delta_i = sum_d o_i * do_i  (rowwise), standard flash bwd identity;
    # with dropout, o is the *dropped* output, so delta still equals
    # sum_k p_dropped * dp — the identity survives unchanged.
    delta = jnp.sum(o.astype(jnp.float32) * dof, axis=-1)      # [b,h,lq]
    row_off, col_off = _off_rc(offsets)
    rows = row_off + jnp.arange(lq)[:, None]
    lq_rows = row_off + jnp.arange(lq, dtype=jnp.int32)
    seed_u = _carrier_to_u32(jnp.asarray(seed, jnp.float32)) \
        if dropout_rate > 0.0 else None

    def step(dq_acc, ki):
        ks = jax.lax.dynamic_slice_in_dim(k, ki * block_k, block_k, 2)
        vs = jax.lax.dynamic_slice_in_dim(v, ki * block_k, block_k, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, ks.astype(jnp.float32))
        s = s * sm_scale
        if bias is not None:
            bs = jax.lax.dynamic_slice_in_dim(bias, ki * block_k, block_k, 3)
            s = s + bs.astype(jnp.float32)
        cols_l = ki * block_k + jnp.arange(block_k)[None, :]
        cols = col_off + cols_l
        if causal:
            s = jnp.where(rows >= cols, s, DEFAULT_MASK_VALUE)
        if kv_len is not None:
            s = jnp.where(cols_l[None, None] < kv_len, s,
                          DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse[..., None])                        # [b,h,q,bk]
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vs.astype(jnp.float32))
        if dropout_rate > 0.0:
            dscale = _block_keep_scale(seed_u, b, h, lq_rows, ki, block_k,
                                       dropout_rate, col_off)
            dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p * dscale, dof)
            ds_raw = p * (dscale * dp - delta[..., None])       # dbias block
        else:
            dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
            ds_raw = p * (dp - delta[..., None])                # dbias block
        ds = ds_raw * sm_scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds,
                                     ks.astype(jnp.float32))
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        if bias is None:
            return dq_acc, (dk_blk, dv_blk)
        # reduce over dims the bias broadcasts before stacking
        db_blk = ds_raw
        if bias.shape[0] == 1:
            db_blk = db_blk.sum(axis=0, keepdims=True)
        if bias.shape[1] == 1:
            db_blk = db_blk.sum(axis=1, keepdims=True)
        return dq_acc, (dk_blk, dv_blk, db_blk)

    dq, blocks = jax.lax.scan(
        step, jnp.zeros((b, h, lq, d), jnp.float32), jnp.arange(nk))
    dk = jnp.moveaxis(blocks[0], 0, 2).reshape(b, h, lk, d)
    dv = jnp.moveaxis(blocks[1], 0, 2).reshape(b, h, lk, d)
    dbias = None
    if bias is not None:
        db = jnp.moveaxis(blocks[2], 0, 3)     # [bb,hh,lq,nk,bk]
        dbias = db.reshape(*db.shape[:3], lk).astype(bias.dtype)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias)


# ---------------------------------------------------------------------------
# Public entry with custom VJP
# ---------------------------------------------------------------------------

def _swap_lh(x, layout):
    """blhd <-> bhld (the (0,2,1,3) transpose is its own inverse)."""
    return jnp.transpose(x, (0, 2, 1, 3)) if layout == "blhd" else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11,
                                                    12, 13, 14))
def _flash(q, k, v, bias, seed, offsets, sm_scale, causal, block_q,
           block_k, impl, dropout_rate, kv_len, layout, use_offsets):
    # primal-only path: no lse output (saves its HBM write in inference)
    off = offsets if use_offsets else None
    if impl in ("pallas", "pallas_interpret"):
        out, _ = _pallas_forward(q, k, v, bias, seed, off, sm_scale, causal,
                                 kv_len, block_q, block_k, dropout_rate,
                                 layout, interpret=(impl ==
                                                    "pallas_interpret"),
                                 need_lse=False)
        return out
    out, _ = _xla_forward(_swap_lh(q, layout), _swap_lh(k, layout),
                          _swap_lh(v, layout), bias, seed, off, sm_scale,
                          causal, kv_len, block_k, dropout_rate)
    return _swap_lh(out, layout)


def _use_pallas_bwd(impl, has_bias: bool, lq: int) -> bool:
    """Static routing: the dq/dkv Pallas kernels serve the bias-free path
    at long L; short sequences keep the XLA-scan backward (the [bh,lq,128]
    lse residual costs more than recomputing the stats there, and XLA
    fuses the scan into the surrounding step)."""
    return (impl in ("pallas", "pallas_interpret") and not has_bias
            and lq >= PALLAS_BWD_MIN_L)


def _lq(x, layout) -> int:
    """Sequence length of a q / k / v array in either layout."""
    return x.shape[1] if layout == "blhd" else x.shape[2]


def _flash_fwd(q, k, v, bias, seed, offsets, sm_scale, causal, block_q,
               block_k, impl, dropout_rate, kv_len, layout, use_offsets):
    off = offsets if use_offsets else None
    if impl in ("pallas", "pallas_interpret"):
        # save the lse residual only when the Pallas backward will read it;
        # otherwise the XLA backward recomputes the row stats blockwise
        # (cheaper than the [bh, lq, 128] HBM round-trip at short L)
        need_lse = _use_pallas_bwd(impl, bias is not None,
                                   _lq(q, layout))
        out, lse = _pallas_forward(q, k, v, bias, seed, off, sm_scale,
                                   causal, kv_len, block_q, block_k,
                                   dropout_rate, layout,
                                   interpret=(impl == "pallas_interpret"),
                                   need_lse=need_lse)
    else:
        out, lse = _xla_forward(_swap_lh(q, layout), _swap_lh(k, layout),
                                _swap_lh(v, layout), bias, seed, off,
                                sm_scale, causal, kv_len, block_k,
                                dropout_rate)
        out = _swap_lh(out, layout)
    return out, (q, k, v, bias, seed, offsets, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, impl, dropout_rate,
               kv_len, layout, use_offsets, res, do):
    q, k, v, bias, seed, offsets, out, lse = res
    off = offsets if use_offsets else None
    zero_off = jnp.zeros_like(offsets)   # int-carrier operand: zero cotangent
    if _use_pallas_bwd(impl, bias is not None, _lq(q, layout)):
        dq, dk, dv = _pallas_backward(
            q, k, v, do, out, lse, seed, off, sm_scale, causal, kv_len,
            block_q, block_k, dropout_rate, layout,
            interpret=(impl == "pallas_interpret"))
        return (dq, dk, dv, None, jnp.zeros((), jnp.float32), zero_off)
    if lse is None:
        # pallas fwd that skipped the lse residual: recompute the row stats
        # blockwise (l must be the FULL softmax sum — dropout off)
        _, lse = _xla_forward(_swap_lh(q, layout), _swap_lh(k, layout),
                              _swap_lh(v, layout), bias, seed, off,
                              sm_scale, causal, kv_len, block_k,
                              dropout_rate=0.0)
    dq, dk, dv, dbias = _xla_backward(
        _swap_lh(q, layout), _swap_lh(k, layout), _swap_lh(v, layout), bias,
        _swap_lh(out, layout), _swap_lh(do, layout), lse, seed, off,
        sm_scale, causal, kv_len, block_k, dropout_rate)
    return (_swap_lh(dq, layout), _swap_lh(dk, layout),
            _swap_lh(dv, layout), dbias, jnp.zeros((), jnp.float32),
            zero_off)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _default_block(l: int) -> int:
    """v5e fwd+bwd sweep (BENCH_NOTES §4, r4): 1024-blocks win at every
    L >= 1024 (larger tiles amortise the softmax VPU work against the
    d=64-thin matmuls; 2048 exceeds even the raised VMEM scope).  Short
    sequences keep single-block dispatch."""
    if l >= 1024 and l % 1024 == 0:
        return 1024
    if l >= 1024 and l % 512 == 0:
        return 512
    return 256


def _plan(q, k, bias, causal, sm_scale, block_q, block_k, impl,
          dropout_rate, dropout_seed, layout, block_offsets=None):
    """What ``flash_attention`` and its two halves settle before a kernel
    runs, from the same arguments in the same way: the seed and offset
    carriers, the static arguments of ``_flash`` up to ``dropout_rate``,
    whether offsets are in use, and the rows of padding (queries, keys)
    that a block multiple needs."""
    if layout not in ("bhld", "blhd"):
        raise ValueError(f"unknown layout {layout!r}")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    lq, lk = _lq(q, layout), _lq(k, layout)
    if block_q is None:
        block_q = _default_block(lq)
    if block_k is None:
        block_k = _default_block(lk)
    if impl is None:
        impl = default_impl()
    if bias is not None and bias.ndim != 4:
        raise ValueError(f"bias must be 4-d, got {bias.shape}")
    dropout_rate = float(dropout_rate)
    seed = dropout_carrier(dropout_rate, dropout_seed)
    use_offsets = block_offsets is not None
    if use_offsets:
        offsets = offsets_carrier(*block_offsets)
    else:
        offsets = jnp.zeros(2, jnp.float32)
    static = (float(sm_scale), bool(causal), int(block_q), int(block_k),
              impl, dropout_rate)
    return (seed, offsets, static, use_offsets,
            (-lq) % min(block_q, lq), (-lk) % min(block_k, lk))


def flash_attention(q, k, v, bias: Optional[jax.Array] = None,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    impl: Optional[str] = None,
                    dropout_rate: float = 0.0,
                    dropout_seed=None,
                    layout: str = "bhld",
                    block_offsets=None) -> jax.Array:
    """Fused attention.  layout='bhld': q [B,H,Lq,D], k/v [B,H,Lk,D];
    layout='blhd': q [B,Lq,H,D] etc. (head-interleaved — the kernels index
    it directly, so callers skip the split-heads transposes).  Optional
    additive bias [B|1, H|1, Lq, Lk] (the fluid attn-bias convention).
    impl: 'pallas' (TPU fwd+bwd kernels), 'xla' (any backend),
    'pallas_interpret' (testing); default picks pallas on TPU, xla
    elsewhere.

    dropout_rate > 0 applies attention-probability dropout (inverted
    scaling) inside the kernel via a counter-based hash of the global
    position — no [Lq, Lk] mask tensor exists in either direction.
    dropout_seed: int/uint32 scalar (may be traced), required when
    dropout_rate > 0; same seed ⇒ same mask.

    block_offsets=(row_off, col_off) (ints, MAY BE TRACED) place this
    call's q block and k/v block at global sequence positions — ring
    attention's shards call with (my*Lq_shard, src*Lk_shard) so the
    causal mask and the dropout hash key on true global coordinates.

    Query rows with ZERO live keys in this call (causal=True with
    block_offsets placing the whole k/v block strictly after the row)
    return output 0 and lse +inf — the kernel detects rows whose
    running max never rose above the finite DEFAULT_MASK_VALUE and
    zeroes them, so block-wise combiners (ring attention) may fold
    such calls safely: the +inf lse makes their contribution vanish
    in the merged softmax.
    """
    seed, offsets, static, use_offsets, pq, pk = _plan(
        q, k, bias, causal, sm_scale, block_q, block_k, impl, dropout_rate,
        dropout_seed, layout, block_offsets)
    kv_len = None
    if pq or pk:
        # pad to block multiples: padded KEYS are masked in-kernel by the
        # static kv_len bound (no synthetic bias tensor — r3 built one and
        # paid its HBM reads); padded query rows are sliced off (their
        # cotangent is zero, so they can't contaminate dk/dv)
        lq = _lq(q, layout)
        seq_axis = 1 if layout == "blhd" else 2
        padq = [(0, 0)] * 4
        padq[seq_axis] = (0, pq)
        padk = [(0, 0)] * 4
        padk[seq_axis] = (0, pk)
        if pk:
            kv_len = k.shape[seq_axis]
        q = jnp.pad(q, padq)
        k = jnp.pad(k, padk)
        v = jnp.pad(v, padk)
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pq), (0, pk)))
        out = _flash(q, k, v, bias, seed, offsets, *static, kv_len, layout,
                     use_offsets)
        if layout == "blhd":
            return out[:, :lq]
        return out[:, :, :lq, :]
    return _flash(q, k, v, bias, seed, offsets, *static, kv_len, layout,
                  use_offsets)


# ---------------------------------------------------------------------------
# The two halves, for a caller that keeps the forward's results itself
# ---------------------------------------------------------------------------
#
# ``jax.vjp`` over ``flash_attention`` runs ``_flash_fwd``: a second forward
# kernel call where the caller already ran the primal (a fluid program's
# grad op, lowered apart from its forward op), and XLA's CSE does not merge
# Mosaic custom calls.  A caller that holds the forward's output, and its
# row statistics where the backward kernels read them, calls the backward
# half directly instead.

def backward_half(q_shape, k_shape, has_bias: bool,
                  impl: Optional[str] = None, layout: str = "bhld"):
    """``(applies, wants_lse)`` for ``flash_attention_grad`` at these
    shapes.  It applies unless the lengths need padding to a block
    multiple (that pair goes through ``jax.vjp``); it wants the forward's
    row statistics (``flash_attention_stats``) where the Pallas dq/dkv
    kernels read them, and finds them itself in the XLA scan (any bias,
    short sequences)."""
    seq_axis = 1 if layout == "blhd" else 2
    lq, lk = q_shape[seq_axis], k_shape[seq_axis]
    if lq % min(_default_block(lq), lq) or lk % min(_default_block(lk), lk):
        return False, False
    return True, _use_pallas_bwd(impl or default_impl(), has_bias, lq)


def flash_attention_stats(q, k, v, causal: bool = False,
                          sm_scale: Optional[float] = None,
                          impl: Optional[str] = None,
                          dropout_rate: float = 0.0, dropout_seed=None,
                          layout: str = "bhld"):
    """``flash_attention`` once, bias-free, with its row statistics:
    ``(out, lse)``, ``lse`` float32 ``[B*H, Lq]`` — lane 0 of the
    kernel's lane-broadcast ``[B*H, Lq, 128]`` output, which is what
    lives from the forward to the backward (1 MB where the broadcast
    form is 134 MB).  Only where ``backward_half`` wants them; not
    differentiable (``flash_attention_grad`` is its other half)."""
    seed, offsets, static, use_offsets, pq, pk = _plan(
        q, k, None, causal, sm_scale, None, None, impl, dropout_rate,
        dropout_seed, layout)
    if pq or pk:
        raise ValueError(f"lengths {q.shape}, {k.shape} need padding: "
                         f"backward_half says it does not apply")
    out, res = _flash_fwd(q, k, v, None, seed, offsets, *static, None,
                          layout, use_offsets)
    # tied to ``out``: whatever reads the output waits for the slice, so
    # the broadcast form dies here and not when the backward first asks
    # (left alone, XLA schedules the slice there: +0.8 GB at the peak of
    # a 6+6-layer step at 8 x 2048, by the TPU compiler's own count)
    return jax.lax.optimization_barrier((out, res[-1][:, :, 0]))


def flash_attention_grad(q, k, v, bias, out, lse, do, causal: bool = False,
                         sm_scale: Optional[float] = None,
                         impl: Optional[str] = None,
                         dropout_rate: float = 0.0, dropout_seed=None,
                         layout: str = "bhld"):
    """The backward half of ``flash_attention`` alone: ``(dq, dk, dv,
    dbias)`` from the forward's own ``out`` (and ``lse`` where
    ``backward_half`` wants it, else None) and the cotangent ``do``,
    with the forward's arguments.  The same function the custom vjp
    runs, on the same residuals, so the gradients are those of
    ``jax.vjp`` over ``flash_attention`` — without its second forward."""
    seed, offsets, static, use_offsets, pq, pk = _plan(
        q, k, bias, causal, sm_scale, None, None, impl, dropout_rate,
        dropout_seed, layout)
    if pq or pk:
        raise ValueError(f"lengths {q.shape}, {k.shape} need padding: "
                         f"backward_half says it does not apply")
    if lse is not None:
        # back to the lane-broadcast tiles the dq/dkv kernels read, as
        # short-lived as when the forward kernel wrote them for this call
        lse = jnp.broadcast_to(lse[:, :, None], (*lse.shape, LANES))
    return _flash_bwd(*static, None, layout, use_offsets,
                      (q, k, v, bias, seed, offsets, out, lse), do)[:4]


def flash_attention_sharded(mesh: Mesh, q, k, v,
                            bias: Optional[jax.Array] = None, *,
                            batch_axis: Optional[str],
                            head_axis: Optional[str],
                            causal: bool = False,
                            sm_scale: Optional[float] = None,
                            impl: Optional[str] = None,
                            dropout_rate: float = 0.0, dropout_seed=None,
                            layout: str = "bhld") -> jax.Array:
    """``flash_attention`` inside a jit that spans ``mesh`` (data- or
    tensor-parallel training: no sequence axis — that is
    ring/ulysses_attention_sharded's job).

    The XLA impl is left to the SPMD partitioner.  The Pallas impls are
    Mosaic custom calls, which "cannot be automatically partitioned":
    they map over the mesh with the batch dim split on ``batch_axis``
    and the head dim on ``head_axis`` (either may be None — that dim
    stays whole on every device); attention needs nothing from another
    batch row or head, so no collective appears.  Dropout masks are
    decorrelated across shards (``shard_dropout_seed``)."""
    impl = impl or default_impl()
    dropout_rate = float(dropout_rate)
    kw = dict(causal=causal, sm_scale=sm_scale, impl=impl,
              dropout_rate=dropout_rate, layout=layout)
    if impl == "xla":
        return flash_attention(q, k, v, bias=bias,
                               dropout_seed=dropout_seed, **kw)
    seed = dropout_carrier(dropout_rate, dropout_seed)
    qkv_spec = (P(batch_axis, head_axis, None, None) if layout == "bhld"
                else P(batch_axis, None, head_axis, None))
    args = [q, k, v, seed]
    specs = [qkv_spec] * 3 + [P()]
    if bias is not None:
        args.append(bias)
        specs.append(P(batch_axis if bias.shape[0] > 1 else None,
                       head_axis if bias.shape[1] > 1 else None,
                       None, None))

    def local(q_, k_, v_, seed_, *bias_):
        return flash_attention(
            q_, k_, v_, bias=bias_[0] if bias_ else None,
            dropout_seed=(shard_dropout_seed(seed_, batch_axis, head_axis)
                          if dropout_rate > 0.0 else None), **kw)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=qkv_spec, check_vma=False)(*args)
