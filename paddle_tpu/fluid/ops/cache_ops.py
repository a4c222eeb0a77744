"""KV-cache ops for incremental decoding (the serving hot path).

The reference deploys inference through `paddle/capi` / the inference
library by re-running the pruned forward per emitted token — O(L^2) work
per sequence.  These two ops are the device-side primitives that make
decode O(L) per token instead:

* ``cache_write`` — functional in-place update of a preallocated cache
  tensor (``lax.dynamic_update_slice`` / per-row scatter).  The op's
  output is conventionally the SAME variable as its Cache input (the
  ParamOut-aliasing idiom of sgd_op.cc), so under the executor's buffer
  donation the update is a true in-place HBM write.
* ``decode_attention`` — one decode step's attention against the cache
  with a per-sequence length mask (kernels/flash_attention.py
  decode_attention); replaces the materialised causal-bias re-run.

Both are inference-only (``no_grad``): training never builds them, and
``prune_program``'s backward slice never has to reason about them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import primitive


@primitive("cache_write", inputs=["Cache", "Value", "Index"],
           outputs=["Out"], no_grad=True)
def cache_write(ctx, cache, value, index):
    """Write ``value`` into ``cache`` at ``index`` along ``axis``.

    Index forms (int32, may be traced — a new position never recompiles):
      * scalar / [1]: one offset shared by every batch row
        (``dynamic_update_slice`` along ``axis``) — also how a single
        sequence's lane is admitted into a batched cache (axis=0);
      * [B] with B == cache batch and axis == 1: per-row positions —
        continuous batching writes each slot at its OWN decode position
        (``Value`` must then be [B, k, ...]; rows scatter at index[b]).
    """
    import jax.lax as lax

    axis = int(ctx.attr("axis", 1))
    idx = jnp.asarray(index).reshape(-1).astype(jnp.int32)
    if idx.shape[0] == 1:
        start = [jnp.int32(0)] * cache.ndim
        start[axis] = idx[0]
        return lax.dynamic_update_slice(
            cache, value.astype(cache.dtype), tuple(start))
    if axis != 1:
        raise ValueError(
            f"cache_write: per-row index vectors require axis=1, got "
            f"axis={axis}")
    b = cache.shape[0]
    if idx.shape[0] != b:
        raise ValueError(
            f"cache_write: index vector length {idx.shape[0]} != cache "
            f"batch {b}")
    k = value.shape[1]
    rows = idx[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]  # [B, k]
    batch = jnp.arange(b, dtype=jnp.int32)[:, None]
    return cache.at[batch, rows].set(value.astype(cache.dtype))


@primitive("decode_attention", inputs=["Q", "KCache", "VCache", "Lengths"],
           outputs=["Out"], no_grad=True)
def decode_attention(ctx, q, k_cache, v_cache, lengths):
    """Length-masked attention of a decode-step query block against the
    KV cache — see kernels/flash_attention.decode_attention for the
    layout contract (q [B, Lq, H, D], caches [B, Lmax, H, D])."""
    from ...kernels.flash_attention import decode_attention as _da

    sm_scale = ctx.attr("sm_scale", None)
    return _da(q, k_cache, v_cache, lengths, sm_scale=sm_scale)


# ---------------------------------------------------------------------------
# Paged KV-cache ops (ISSUE 6).  The pool is ONE persistable tensor
# [R, page_size, H*D], token-major with the heads in the minor dim; a
# *logical* page spans every layer and K+V of a page_size-token span
# (physical row = (page*n_layer + layer)*2 (+1 for V) —
# kernels/flash_attention.paged_kv_rows is the single source of truth
# for that arithmetic).  Every op below indexes the pool's LEADING axes
# only, so under donation each is an in-place row update and no
# compiled step holds a second buffer of pool size.  Logical page 0 is
# the reserved trash page dead lanes write into, so one compiled
# program serves any mix of prefilling / decoding / idle lanes without
# recompiling.
# ---------------------------------------------------------------------------


def _token_writes(pages, offsets, k, v):
    """Normalise a write op's feeds to ``pages``/``offsets`` [B, C] and
    ``k``/``v`` [B, C, H, D] (a decode step may pass one token a lane
    without the C axis)."""
    pages = jnp.asarray(pages).astype(jnp.int32)
    offsets = jnp.asarray(offsets).astype(jnp.int32)
    if pages.ndim == 1:               # one token per lane (decode step)
        pages = pages[:, None]
        offsets = offsets[:, None]
        k = k if k.ndim == 4 else k[:, None]
        v = v if v.ndim == 4 else v[:, None]
    return pages, offsets, k, v


def _scatter_tokens(pool, rows, offsets, val):
    """pool[rows[b,c], offsets[b,c], :] <- val[b,c,:,:] as ONE
    leading-axis row scatter on the [R*page_size, H*D] view of the pool
    (a bitcast): the embedding-table update XLA performs in place on a
    donated operand."""
    r, ps, hd = pool.shape
    flat = (rows * ps + offsets).reshape(-1)
    out = pool.reshape(r * ps, hd).at[flat].set(
        val.astype(pool.dtype).reshape(-1, hd))
    return out.reshape(r, ps, hd)


@primitive("paged_cache_write",
           inputs=["Pool", "K", "V", "Pages", "Offsets"], outputs=["Out"],
           no_grad=True)
def paged_cache_write(ctx, pool, k, v, pages, offsets):
    """Scatter one layer's K/V for up to C tokens per lane into the
    paged pool.

    ``k``/``v`` [B, C, H, D] head-interleaved values, ``pages`` [B, C]
    int32 logical page per token, ``offsets`` [B, C] int32 slot within
    the page.  Attrs ``layer``/``n_layer`` resolve logical pages to
    physical rows.  Out aliases Pool (the cache_write ParamOut idiom):
    under donation this is an in-place HBM scatter; a traced page id
    never recompiles."""
    from ...kernels.flash_attention import paged_kv_rows

    pages, offsets, k, v = _token_writes(pages, offsets, k, v)
    k_rows, v_rows = paged_kv_rows(pages, int(ctx.attr("layer", 0)),
                                   int(ctx.attr("n_layer", 1)))
    pool = _scatter_tokens(pool, k_rows, offsets, k)
    return _scatter_tokens(pool, v_rows, offsets, v)


@primitive("quantized_paged_cache_write",
           inputs=["Pool", "Scales", "K", "V", "Pages", "Offsets"],
           outputs=["Out", "ScalesOut"], no_grad=True)
def quantized_paged_cache_write(ctx, pool, scales, k, v, pages, offsets):
    """``paged_cache_write`` for an int8 pool: each token's K (and V)
    [H, D] slab quantizes symmetrically on write — one fp32 max-abs
    scale per (token, layer, role) block, stored in the ``scales``
    sidecar [1, R, page_size] at the SAME (physical row, slot) the int8
    bytes land in — so the block scales ride the exact page indirection
    the pool does (paged_page_copy moves both with the same row math).
    Out/ScalesOut alias Pool/Scales (the cache_write ParamOut idiom)."""
    from ...kernels.flash_attention import paged_kv_rows
    from .quant_ops import abs_max_scale, quantize_array

    pages, offsets, k, v = _token_writes(pages, offsets, k, v)
    k_rows, v_rows = paged_kv_rows(pages, int(ctx.attr("layer", 0)),
                                   int(ctx.attr("n_layer", 1)))

    def tok_quant(val):
        """[B, C, H, D] float -> (int8 [B, C, H, D], scale [B, C]) via
        quant_ops' shared max-abs rule (one block scale per token)."""
        vf = val.astype(jnp.float32)
        sc = abs_max_scale(vf, axis=(0, 1))                 # [B, C]
        return quantize_array(vf, sc, axis=(0, 1)), sc

    kq, ks = tok_quant(k)
    vq, vs = tok_quant(v)
    pool = _scatter_tokens(pool, k_rows, offsets, kq)
    pool = _scatter_tokens(pool, v_rows, offsets, vq)
    scales = scales.at[0, k_rows, offsets].set(ks)
    scales = scales.at[0, v_rows, offsets].set(vs)
    return pool, scales


@primitive("ragged_decode_attention",
           inputs=["Q", "Pool", "PageTable", "Lengths", "QBase?", "Scales?",
                   "VPool?", "Sink?", "RingTop?"],
           outputs=["Out"], no_grad=True)
def ragged_decode_attention(ctx, q, pool, page_table, lengths, q_base,
                            scales, v_pool=None, sink=None, ring_top=None):
    """Per-lane attention over the lane's page list — see
    kernels/flash_attention.ragged_decode_attention (q [B, C, H, D],
    pool [R, page_size, H*D], page_table [B, P] int32 logical pages,
    lengths [B], optional q_base [B] for causal chunk queries, optional
    Scales [1, R, page_size] fp32 block scales for an int8 pool).  Under
    an active mesh (tensor-parallel serving) the kernel maps over the
    mesh's batch and head axes."""
    from ...kernels.flash_attention import (
        default_impl as _default_impl,
        ragged_decode_attention as _ra,
        ragged_decode_attention_sharded as _ra_sharded,
        split_walk as _split_walk)
    from ...parallel import mesh as _pmesh

    kw = dict(layer=int(ctx.attr("layer", 0)),
              n_layer=int(ctx.attr("n_layer", 1)),
              causal=bool(ctx.attr("causal", True)),
              sm_scale=ctx.attr("sm_scale", None),
              impl=ctx.attr("impl", None), scales=scales)
    mesh = _pmesh.current_mesh()
    latent = ctx.attr("latent_values", None)
    if v_pool is not None or latent is not None:
        # a split pool pair (keys in Pool, values in VPool): grouped KV
        # heads, a window, a sink, a ring of pages; attr ``out_scale``
        # multiplies the result (a model's value scale).  Attr
        # ``latent_values`` in VPool's place: Pool holds latent rows whose
        # leading that-many columns are the values (ONE pool)
        if mesh is not None:
            raise NotImplementedError(
                "ragged_decode_attention: split pools are not mapped over "
                "a mesh")
        scope = ctx.attr("scope", None) or "attn/paged"
        kernel = "paged_" + scope.replace("/", "_")
        if latent is not None:
            # the absorbed form: queries and outputs carry the latent's
            # up-projection, attention runs against the rows themselves
            ctx.note("attn_latent", form="absorbed", tile=int(q.shape[1]),
                     row=int(pool.shape[2]), values=int(latent))
        if (kw["impl"] or _default_impl()) != "xla":
            # the page walk the kernel's grid makes of these shapes (the
            # gather form has no grid)
            group, grid = _split_walk(q, pool, v_pool, page_table, latent)
            ctx.note("attn_split", kernel=kernel, queries=int(q.shape[1]),
                     slots=int(page_table.shape[1]), slots_per_step=group,
                     grid_steps=grid[0] * grid[1])
        with jax.named_scope(scope):
            out = _ra(q, pool, page_table, lengths, q_base, v_pool=v_pool,
                      window=ctx.attr("window", None), sink=sink,
                      ring_top=ring_top, latent_values=latent,
                      kernel_name=kernel, **kw)
            scale = ctx.attr("out_scale", None)
            return out if scale is None else \
                (out.astype(jnp.float32) * float(scale)).astype(out.dtype)
    if mesh is not None:
        b_ax, h_ax = _pmesh.kernel_axes(mesh, batch=q.shape[0],
                                        heads=q.shape[2])
        return _ra_sharded(mesh, q, pool, page_table, lengths, q_base,
                           batch_axis=b_ax, head_axis=h_ax, **kw)
    return _ra(q, pool, page_table, lengths, q_base, **kw)


def _page_rows(pages, n_layer):
    """Logical pages [N] -> their physical rows [N, 2L] (all layers, K
    and V: ``paged_kv_rows`` for every layer at once)."""
    pages = jnp.asarray(pages).astype(jnp.int32).reshape(-1)
    span = jnp.arange(2 * n_layer, dtype=jnp.int32)[None, :]
    return pages[:, None] * (2 * n_layer) + span


@primitive("paged_page_copy", inputs=["Pool", "Src", "Dst"],
           outputs=["Out"], no_grad=True)
def paged_page_copy(ctx, pool, src, dst):
    """Copy whole logical pages (all layers, K and V) ``src[b] ->
    dst[b]`` — the device half of copy-on-write: beam lanes that share a
    parent's partially-filled page get their own copy IN the step
    dispatch before writing.  ``src == dst`` rows are identity writes
    (the no-op encoding for lanes that don't need a copy this step)."""
    n_layer = int(ctx.attr("n_layer", 1))
    src_rows, dst_rows = _page_rows(src, n_layer), _page_rows(dst, n_layer)
    return pool.at[dst_rows].set(pool[src_rows])


@primitive("quantized_paged_page_copy",
           inputs=["Pool", "Scales", "Src", "Dst"],
           outputs=["Out", "ScalesOut"], no_grad=True)
def quantized_paged_page_copy(ctx, pool, scales, src, dst):
    """``paged_page_copy`` for an int8 pool: the fp32 block scales ride
    the SAME physical-row move the int8 bytes do — a copied page is
    bit-identical to its parent, scales included, so copy-on-write
    never changes what a beam lane dequantizes."""
    n_layer = int(ctx.attr("n_layer", 1))
    src_rows, dst_rows = _page_rows(src, n_layer), _page_rows(dst, n_layer)
    pool = pool.at[dst_rows].set(pool[src_rows])
    scales = scales.at[:, dst_rows].set(scales[:, src_rows])
    return pool, scales


# ---------------------------------------------------------------------------
# Tiered-KV transfer ops (ISSUE 20).  The device half of host-RAM page
# demotion: gather pulls whole logical pages out of the pool as a dense
# [W*2L, page_size, H*D] slab the host fetches (device->host), scatter
# writes such a slab back into fresh pages (host->device).  W is FIXED
# per compiled program (short transfers pad with the trash page), and
# the page lists are int32 DATA — so the whole tier machinery compiles
# exactly two extra executables and never recompiles after warmup.
# ---------------------------------------------------------------------------


@primitive("paged_page_gather", inputs=["Pool", "Pages"],
           outputs=["Out"], no_grad=True)
def paged_page_gather(ctx, pool, pages):
    """Gather W whole logical pages (all layers, K and V) into a dense
    slab [W*2L, page_size, H*D] for host download.  ``pages`` [W] int32
    logical page ids; trash-page entries gather junk the host side
    ignores (the fixed-width padding encoding)."""
    rows = _page_rows(pages, int(ctx.attr("n_layer", 1))).reshape(-1)
    return pool[rows]


@primitive("paged_page_scatter", inputs=["Pool", "Data", "Pages"],
           outputs=["Out"], no_grad=True)
def paged_page_scatter(ctx, pool, data, pages):
    """Scatter a gathered slab [W*2L, page_size, H*D] back into the
    pool at W logical pages — the host->device upload of a promoted or
    resumed page.  Out aliases Pool (the cache_write ParamOut idiom);
    trash-page entries absorb the padding rows harmlessly."""
    rows = _page_rows(pages, int(ctx.attr("n_layer", 1))).reshape(-1)
    return pool.at[rows].set(data.astype(pool.dtype))


@primitive("quantized_paged_page_gather", inputs=["Pool", "Scales", "Pages"],
           outputs=["Out", "ScalesOut"], no_grad=True)
def quantized_paged_page_gather(ctx, pool, scales, pages):
    """``paged_page_gather`` for an int8 pool: the fp32 block-scale
    sidecar rows travel WITH the int8 bytes (same physical rows), so a
    demoted page carries everything needed to dequantize after resume."""
    rows = _page_rows(pages, int(ctx.attr("n_layer", 1))).reshape(-1)
    return pool[rows], scales[:, rows]


@primitive("quantized_paged_page_scatter",
           inputs=["Pool", "Scales", "Data", "ScaleData", "Pages"],
           outputs=["Out", "ScalesOut"], no_grad=True)
def quantized_paged_page_scatter(ctx, pool, scales, data, scale_data, pages):
    """``paged_page_scatter`` for an int8 pool: re-installs the int8
    bytes AND their fp32 block scales at the same physical rows —
    a promoted chunk dequantizes bit-identically to pre-demotion."""
    rows = _page_rows(pages, int(ctx.attr("n_layer", 1))).reshape(-1)
    pool = pool.at[rows].set(data.astype(pool.dtype))
    scales = scales.at[:, rows].set(scale_data.astype(scales.dtype))
    return pool, scales
