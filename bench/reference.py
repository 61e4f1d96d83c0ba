"""Plain float32 Qwen2 forward pass: the reference that decides
``correct``, and its fp8 control.

It follows the published description (arXiv:2407.10671; the Hugging
Face ``Qwen2ForCausalLM``): token embedding; per layer pre-RMSNorm,
q/k/v projections with bias, rotary embedding (half rotation, base
``rope_theta``) at positions 0.., causal grouped-query attention, the
output projection, a residual, pre-RMSNorm, a SwiGLU MLP and a
residual; a final RMSNorm and the head.  The head is the embedding
(tied), as the program serves it.  Nothing here imports the program.

It runs layer by layer, drawing each layer's weights from the seed
(:mod:`bench.weights`), over a block of sequences padded to one length
(causal, so padding never reaches a real position), with every matrix
product at ``highest`` precision.  ``control=True`` computes each
linear layer in fp8 (e4m3, scaled per row of the activations and per
output column of the weights): the control that has to fail.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

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


def _layer(c: Dict, control: bool, w: Dict, x: jax.Array) -> jax.Array:
    N, S, _ = x.shape
    H, KV, d = c["num_attention_heads"], c["num_key_value_heads"], weights.hd(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = _rms(x, w["norm1"], eps)
    q = (_linear(h, w["wq"], control) + w["bq"]).reshape(N, S, H, d)
    k = (_linear(h, w["wk"], control) + w["bk"]).reshape(N, S, KV, d)
    v = (_linear(h, w["wv"], control) + w["bv"]).reshape(N, S, KV, d)
    a = _attention(_rope(q, theta), _rope(k, theta), v)
    x = x + _linear(a.reshape(N, S, H * d), w["wo"], control)
    h = _rms(x, w["norm2"], eps)
    g = jax.nn.silu(_linear(h, w["w_gate"], control))
    return x + _linear(g * _linear(h, w["w_up"], control), w["w_down"],
                       control)


def logits(c: Dict, seed: tuple, seqs: Sequence[np.ndarray],
           starts: Sequence[int], control: bool = False) -> List[np.ndarray]:
    """For each token sequence, the float32 logits at positions
    ``starts[i]`` .. ``len(seqs[i]) - 1`` ([n_i, vocab_size] each)."""
    n = len(seqs)
    S = -(-max(len(s) for s in seqs) // Q_CHUNK) * Q_CHUNK
    toks = np.zeros((n, S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        table = jax.jit(functools.partial(weights.embedding, c))(*seed)
        x = table[jnp.asarray(toks)]
        draw = jax.jit(functools.partial(weights.layer_leaves, c))
        step = jax.jit(functools.partial(_layer, c, control))
        for layer in range(c["num_hidden_layers"]):
            x = step(draw(seed[0], seed[1], layer), x)
        fnorm = weights.final_norm(c, *seed)
        head = jax.jit(functools.partial(_head, c["rms_norm_eps"], control))
        out = []
        for i, s in enumerate(seqs):
            rows = x[i, starts[i]:len(s)]
            out.append(np.asarray(head(rows, fnorm, table)))
        return out


def _head(eps, control, rows, fnorm, table):
    return _linear(_rms(rows, fnorm, eps), table.T, control)


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
