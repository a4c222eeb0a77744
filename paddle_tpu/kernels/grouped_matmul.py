"""Grouped matrix product: rows sorted by group, one weight matrix a group.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G])`` multiplies
rows ``[offset_g, offset_g + size_g)`` by ``rhs[g]``.  The expert layer's
product: the (token, expert) pairs a device holds, sorted by expert,
against its experts' stacked weights.  ``sum(group_sizes)`` may be less
than M (M is the static worst case; no pair is ever dropped): rows past
the last group are not computed and come back as zeros.

On a TPU the product is the Pallas grouped-matmul kernel that ships with
JAX (``jax.experimental.pallas.ops.tpu.megablox.gmm``): it visits only the
row tiles that groups touch, so work and weight traffic follow the pairs
present, not M.  Elsewhere it is ``jax.lax.ragged_dot``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import default_impl

__all__ = ["grouped_matmul", "TILE_M"]

TILE_M = 128        # rows a tile; M must be a multiple of it on the TPU path


def _tile(n: int, want: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``want`` (``n`` itself below one lane tile: the tests' sizes).  Where
    no such divisor reaches half of ``want`` and ``n`` is under twice it,
    ``n`` whole: 1408 = 11 x 128 has no divisor between 128 and itself,
    and one tile of 1408 is one transfer where eleven of 128 are eleven."""
    if n % 128:
        return n
    t = min(want, n) // 128 * 128
    while n % t:
        t -= 128
    return n if 2 * t < min(want, n) and n <= 2 * want else t


def grouped_matmul(lhs, rhs, group_sizes, *, out_dtype=None,
                   impl: Optional[str] = None):
    out_dtype = out_dtype or lhs.dtype
    impl = impl or default_impl()
    group_sizes = group_sizes.astype(jnp.int32)
    # rows past the last group are nobody's: the kernel never writes them
    live = (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None]
    if impl == "xla":
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                 preferred_element_type=jnp.float32)
        return jnp.where(live, out, 0.0).astype(out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    n = rhs.shape[2]
    if m % TILE_M:
        raise ValueError(f"grouped_matmul: {m} rows are no multiple of "
                         f"{TILE_M}")
    # weight tiles of up to 1024 x 1024 (2 MB in bfloat16): the product
    # is bound by reading the weights, so few large transfers
    out = gmm(lhs, rhs, group_sizes, preferred_element_type=out_dtype,
              tiling=(TILE_M, _tile(k, 1024), _tile(n, 1024)),
              interpret=(impl == "pallas_interpret"))
    return jnp.where(live, out, jnp.zeros((), out_dtype))
