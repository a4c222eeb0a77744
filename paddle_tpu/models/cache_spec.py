"""What one KIND of layer of a decoder-only model keeps of a token in the
paged cache, as a builder module's ``cache_specs`` declares it and
``serving.paged_lm`` allocates it."""

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
