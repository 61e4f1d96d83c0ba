"""Pieces of the plain float32 references that every architecture
module (``bench/arch/``) shares, and the gap that ``correct`` reads.

Matrix products run at whatever precision the caller sets (the modules
set ``highest``).  ``control=True`` computes a linear layer in fp8
(e4m3, scaled per row of the activations and per output column of the
weights): the control that has to fail.  :func:`_attention` is causal
grouped-query attention over query chunks of ``Q_CHUNK`` positions, so
a caller pads its sequences to a multiple of it.  Nothing here imports
the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
Q_CHUNK = 256


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to fp8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _linear(x, w, control: bool):
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [N, S, heads, hd], positions 0..S-1."""
    S, h = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(h, dtype=jnp.float32) / h)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA: q [N, S, H, hd], k/v [N, S, KV, hd]; query chunks
    bound the score memory."""
    N, S, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(N, S, KV, H // KV, hd) / np.sqrt(hd)
    kpos = jnp.arange(S)

    def chunk(i):
        qc = jax.lax.dynamic_slice_in_dim(qg, i * Q_CHUNK, Q_CHUNK, 1)
        s = jnp.einsum("nqkgh,nskh->nkgqs", qc, k)
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nkgqs,nskh->nqkgh", p, v)

    out = jax.lax.map(chunk, jnp.arange(S // Q_CHUNK))
    return jnp.moveaxis(out, 0, 1).reshape(N, S, H, hd)


def gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best at its position (inf for a token outside the
    vocabulary)."""
    tokens = np.asarray(tokens, np.int64)
    out = np.full(len(tokens), np.inf)
    ok = (tokens >= 0) & (tokens < ref.shape[1])
    best = ref.max(axis=1)
    out[ok] = best[ok] - ref[np.arange(len(tokens))[ok], tokens[ok]]
    return out
