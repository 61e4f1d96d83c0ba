"""Seeded random weights, made by the benchmark: the streams and draws
every architecture module (``bench/arch/``) shares.

A leaf is drawn from its own key (:func:`_key`: the seed, a stream id
per leaf, the layer), so the served tree and the reference draw the
same values without either taking anything from the other.  Values:
matrices N(0, 1/fan_in), the embedding N(0, 0.02**2), biases
N(0, 0.1**2), RMSNorm scales 1 + N(0, 0.125**2).  Matrices and biases
are rounded to bfloat16 (the served type); norm scales stay float32, as
the program keeps them.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

EMBED_STREAM = 100
FINAL_NORM_STREAM = 101


def _key(seed_lo, seed_hi, stream, layer):
    k = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
    return jax.random.fold_in(jax.random.fold_in(k, stream), layer)


def _draw(key, shape, kind):
    """One leaf as float32, already rounded to the type it is served
    in (bfloat16 for matrices and biases)."""
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        # exact in float32 whatever the fusion: a bfloat16 value times a
        # power of two, plus one
        return 1.0 + z.astype(jnp.bfloat16).astype(jnp.float32) * 0.125
    std = 0.1 if kind == "bias" else shape[0] ** -0.5
    return (z * std).astype(jnp.bfloat16).astype(jnp.float32)


def embedding(c: Dict, seed_lo, seed_hi) -> jax.Array:
    """[vocab_size, hidden] float32 (bfloat16 values)."""
    z = jax.random.normal(_key(seed_lo, seed_hi, EMBED_STREAM, 0),
                          (c["vocab_size"], c["hidden_size"]), jnp.float32)
    return (z * 0.02).astype(jnp.bfloat16).astype(jnp.float32)


def final_norm(c: Dict, seed_lo, seed_hi) -> jax.Array:
    return _draw(_key(seed_lo, seed_hi, FINAL_NORM_STREAM, 0),
                 (c["hidden_size"],), "norm")
