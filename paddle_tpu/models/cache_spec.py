"""What one KIND of layer of a decoder-only model keeps of a token in the
paged cache, as a builder module's ``cache_specs`` declares it and
``serving.paged_lm`` allocates it.

A spec says how wide a token's row is, on how many heads, and for how long
it is kept; it says nothing of what the row HOLDS.  That is the builder's,
and may differ per kind of layer in one model: keys rotated over part of a
head (``mimo_v2_flash``), over all of it, or not at all (``afmoe``'s
window and global layers), normalised per head before they are written
(QK-norm), or a latent row with one rotary key (``deepseek_v3``).  Nor
does it say what follows attention (an output gate, a norm on the
sub-block's output): the engine sees pools, tables and lengths."""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

__all__ = ["CacheSpec"]


class CacheSpec(NamedTuple):
    """What one KIND of layer keeps of a token, and for how long."""
    kind: str
    layers: Tuple[int, ...]         # the model's layers of this kind
    q_heads: int
    kv_heads: int
    d_key: int
    d_value: int
    window: Optional[int]           # None: every position is kept
    # the values are the leading ``d_value`` columns of the key row
    # (latent attention): ONE pool, not a pool pair
    latent: bool = False
