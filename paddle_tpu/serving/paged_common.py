"""What every paged generator needs, whatever its model: a pool as a
program variable and as a device array, a page table turned into a step's
write targets, and an artifact's tensors put into a scope.
``PagedTransformerGenerator`` (encoder-decoder, one pool for every layer)
and ``PagedLMGenerator`` (decoder-only, a pool or a pool pair per kind of
layer)
both call these."""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from .. import fluid

__all__ = ["ceil_div", "pool_variable", "zero_pool", "token_slots",
           "load_artifact_tensors"]


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pool_variable(block, name: str, shape: Sequence[int], dtype: str,
                  sharding: Optional[Tuple] = None):
    """A KV pool as a persistable variable of ``block`` (cache state,
    rebuilt empty at load and never part of an artifact)."""
    var = block.create_var(name=name, shape=list(shape), dtype=dtype,
                           persistable=True)
    if sharding:
        var.set_sharding(tuple(sharding))
    return var


def zero_pool(scope, name: str, shape: Sequence[int], dtype: str,
              sharding=None) -> None:
    """An empty pool on the device, under ``name`` in ``scope``; with a
    ``sharding`` it is laid out that way from birth (a pool sized for a
    mesh must never materialise on one device)."""
    import jax
    import jax.numpy as jnp

    pool = jnp.zeros(tuple(shape), dtype)
    if sharding is not None:
        pool = jax.device_put(pool, sharding)
    scope.set_var(name, pool)


def token_slots(table: Sequence[int], positions,
                page_size: int) -> Tuple[np.ndarray, np.ndarray]:
    """(pages, offsets) int32 of the tokens at ``positions``, through a
    request's page table (index = position // page_size)."""
    positions = np.asarray(positions, np.int64)
    pages = np.asarray(table, np.int32)[positions // page_size]
    return pages, (positions % page_size).astype(np.int32)


def load_artifact_tensors(scope, dirname: str, skip: Sequence[str] = (),
                          cast: Optional[Dict[str, str]] = None) -> int:
    """Every tensor file of an artifact directory into ``scope``, ONE AT A
    TIME.  ``cast`` maps a tensor's name to the type it is kept in (what
    the step program declares it in: a model resident in bfloat16 keeps
    its matrices so); a tensor that is cast goes to the device at once
    and its host copy is dropped, so loading never holds the model twice.
    Returns the number of tensors."""
    import jax
    import jax.numpy as jnp

    n = 0
    for name in sorted(os.listdir(dirname)):
        path = os.path.join(dirname, name)
        if name in skip or not os.path.isfile(path):
            continue
        # a view of the file's bytes where it goes straight to the device
        value = fluid.io.load_tensor(path, copy=cast is None)
        want = (cast or {}).get(name)
        if want is not None and str(np.asarray(value).dtype) != want:
            value = jax.device_put(np.asarray(value)).astype(
                jnp.dtype(want))
        scope.set_var(name, value)
        n += 1
    return n
