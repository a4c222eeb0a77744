"""Seeded weights, made on the device in ONE jitted call, in the type they
are trained or served in (float32 masters).

The benchmark owns the weights: the system under test gets them put into
its scope or artifact, the plain reference gets the same dict, and neither
takes anything from the other.  A leaf's values depend on the run's seed,
its NAME and its shape only, so a refactor that reorders parameters does
not change them.

Kinds, by name: ``*.b`` biases (small noise, so that a dropped bias shows),
``*.ln*.w`` layer-norm scales (1 + noise), ``*pos_emb.w`` the paper's
sinusoid table, embeddings N(0, 1/d_model) (scaled by sqrt(d_model) at
lookup), every other matrix N(0, 1/fan_in).
"""

from __future__ import annotations

import zlib
from typing import Dict, Tuple

import numpy as np


def _kind(name: str) -> str:
    if name.endswith("pos_emb.w"):
        return "sinusoid"
    if name.endswith(".b"):
        return "bias"
    if ".ln" in name and name.endswith(".w"):
        return "ln_scale"
    if name.endswith("emb.w"):
        return "embedding"
    return "matrix"


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(abs(seed) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (abs(seed) >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, 1 if seed < 0 else 0)


def _leaf(key, name: str, shape: Tuple[int, ...]):
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    kind = _kind(name)
    if kind == "sinusoid":
        n_pos, d = shape
        pos = jnp.arange(n_pos, dtype=jnp.float32)[:, None]
        dim = jnp.arange(d)[None, :]
        angle = pos / jnp.power(10000.0, (2 * (dim // 2)) / float(d))
        return jnp.where(dim % 2 == 0, jnp.sin(angle),
                         jnp.cos(angle)).astype(jnp.float32)
    noise = jax.random.normal(k, shape, jnp.float32)
    if kind == "bias":
        return 0.02 * noise
    if kind == "ln_scale":
        return 1.0 + 0.1 * noise
    if kind == "embedding":
        return noise * (float(shape[1]) ** -0.5)
    return noise * (float(shape[0]) ** -0.5)


def make(shapes: Dict[str, Tuple[int, ...]], seed: int, sharding=None):
    """name -> float32 device array for every leaf of ``shapes``.
    ``sharding`` (optional) lays every leaf out that way from birth."""
    import jax

    names = sorted(shapes)

    def build(key):
        return {n: _leaf(key, n, tuple(shapes[n])) for n in names}

    fn = jax.jit(build) if sharding is None \
        else jax.jit(build, out_shardings=sharding)
    return fn(seed_key(seed))


def count(shapes: Dict[str, Tuple[int, ...]]) -> int:
    return int(sum(int(np.prod(s)) for s in shapes.values()))
