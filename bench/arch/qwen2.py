"""Qwen2: what the benchmark knows of the architecture.

A configuration file names this module with ``"arch": "qwen2"``; the
harness calls its functions and never names Qwen2 itself:

- :func:`stated`: the program's ``ArchConfig`` fields the file states;
- :func:`served_params`: seeded weights in the program's tree;
- :func:`logits`: the plain float32 reference and its fp8 control;
- :func:`decode_token_flops`, :func:`prefill_flops`: model FLOPs;
- :func:`paged_kernel_cost`, :func:`paged_layers`: the paged kernel's
  account, per call and calls per step.

Weights.  :func:`layer_leaves` draws every leaf of one layer from the
seed; the served weights (one jitted call on the device, in the types
the program serves them in) and the reference's (one layer at a time in
float32) both come from it, so the reference takes nothing the program
made.  Values: matrices N(0, 1/fan_in), the embedding N(0, 0.02**2),
q/k/v biases N(0, 0.1**2), RMSNorm scales 1 + N(0, 0.125**2)
(:mod:`bench.weights`).

Reference.  It follows the published description (arXiv:2407.10671;
the Hugging Face ``Qwen2ForCausalLM``): token embedding; per layer
pre-RMSNorm, q/k/v projections with bias, rotary embedding (half
rotation, base ``rope_theta``) at positions 0.., causal grouped-query
attention, the output projection, a residual, pre-RMSNorm, a SwiGLU MLP
and a residual; a final RMSNorm and the head.  The head is the
embedding (tied), as the program serves it.  Nothing here imports the
program.  It runs layer by layer over a block of sequences padded to
one length (causal, so padding never reaches a real position), with
every matrix product at ``highest`` precision.  ``control=True``
computes each linear layer in fp8 (:func:`bench.reference._linear`).

FLOPs count multiply-adds as two and include only the work the model
defines (real tokens, the published vocabulary).  The paged kernel's
account follows its data path instead: it DMAs whole pages (all KV
heads) and computes over every slot of each page it walks.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import Q_CHUNK, _attention, _linear, _rms, _rope
from bench.weights import _draw, _key, embedding, final_norm

# ----------------------------------------------------------------- weights
#: leaf name -> (stream id, shape function of the config, kind)
LEAVES = {
    "wq": (1, lambda c: (c["hidden_size"], c["num_attention_heads"] * hd(c)),
           "matrix"),
    "bq": (2, lambda c: (c["num_attention_heads"] * hd(c),), "bias"),
    "wk": (3, lambda c: (c["hidden_size"], c["num_key_value_heads"] * hd(c)),
           "matrix"),
    "bk": (4, lambda c: (c["num_key_value_heads"] * hd(c),), "bias"),
    "wv": (5, lambda c: (c["hidden_size"], c["num_key_value_heads"] * hd(c)),
           "matrix"),
    "bv": (6, lambda c: (c["num_key_value_heads"] * hd(c),), "bias"),
    "wo": (7, lambda c: (c["num_attention_heads"] * hd(c), c["hidden_size"]),
           "matrix"),
    "w_gate": (8, lambda c: (c["hidden_size"], c["intermediate_size"]),
               "matrix"),
    "w_up": (9, lambda c: (c["hidden_size"], c["intermediate_size"]),
             "matrix"),
    "w_down": (10, lambda c: (c["intermediate_size"], c["hidden_size"]),
               "matrix"),
    "norm1": (11, lambda c: (c["hidden_size"],), "norm"),
    "norm2": (12, lambda c: (c["hidden_size"],), "norm"),
}


def hd(c: Dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def stated(c: Dict) -> Dict:
    """The ``ArchConfig`` fields the configuration file states."""
    return {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "num_layers": c["num_hidden_layers"],
            "vocab_size": c["vocab_size"], "head_dim_": c["head_dim"],
            "rope_theta": c["rope_theta"], "norm_eps": c["rms_norm_eps"],
            "dtype": c["dtype"], "qkv_bias": True}


def layer_leaves(c: Dict, seed_lo, seed_hi, layer) -> Dict[str, jax.Array]:
    """Every leaf of layer ``layer`` (float32 values)."""
    return {name: _draw(_key(seed_lo, seed_hi, sid, layer), shape(c), kind)
            for name, (sid, shape, kind) in LEAVES.items()}


def _served_tree(c: Dict, layers: Dict[str, jax.Array], table, fnorm,
                 padded_vocab: int):
    bf = jnp.bfloat16
    pad = padded_vocab - table.shape[0]
    # rows past the vocabulary (the program pads it) are zero: their
    # logit is 0, below the best real logit of any position
    table = jnp.concatenate(
        [table, jnp.zeros((pad, table.shape[1]), table.dtype)]).astype(bf)
    attn = {name: {"w": layers[name].astype(bf)} for name in
            ("wq", "wk", "wv", "wo")}
    for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")):
        attn[w]["b"] = layers[b].astype(bf)
    return {
        "embed": {"table": table},
        "final_norm": {"scale": fnorm},
        "trunk": {
            "norm1": {"scale": layers["norm1"]},
            "norm2": {"scale": layers["norm2"]},
            "attn": attn,
            "mlp": {name: {"w": layers[name].astype(bf)}
                    for name in ("w_gate", "w_up", "w_down")},
        },
    }


def served_params(c: Dict, seed: tuple, padded_vocab: int):
    """The program's parameter tree, made on the device in one jitted
    call: layers stacked on a leading axis, one layer drawn at a time."""
    def make(seed_lo, seed_hi):
        def one(layer):
            leaves = layer_leaves(c, seed_lo, seed_hi, layer)
            return {k: v.astype(jnp.bfloat16) if LEAVES[k][2] != "norm"
                    else v for k, v in leaves.items()}
        layers = jax.lax.map(one, jnp.arange(c["num_hidden_layers"]))
        return _served_tree(c, layers, embedding(c, seed_lo, seed_hi),
                            final_norm(c, seed_lo, seed_hi), padded_vocab)
    return jax.jit(make)(*seed)


# --------------------------------------------------------------- reference
def _layer(c: Dict, control: bool, w: Dict, x: jax.Array) -> jax.Array:
    N, S, _ = x.shape
    H, KV, d = c["num_attention_heads"], c["num_key_value_heads"], hd(c)
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
        table = jax.jit(functools.partial(embedding, c))(*seed)
        x = table[jnp.asarray(toks)]
        draw = jax.jit(functools.partial(layer_leaves, c))
        step = jax.jit(functools.partial(_layer, c, control))
        for layer in range(c["num_hidden_layers"]):
            x = step(draw(seed[0], seed[1], layer), x)
        fnorm = final_norm(c, *seed)
        head = jax.jit(functools.partial(_head, c["rms_norm_eps"], control))
        out = []
        for i, s in enumerate(seqs):
            rows = x[i, starts[i]:len(s)]
            out.append(np.asarray(head(rows, fnorm, table)))
        return out


def _head(eps, control, rows, fnorm, table):
    return _linear(_rms(rows, fnorm, eps), table.T, control)


# ------------------------------------------------------------------- FLOPs
def _dims(c: Dict):
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return d, f, h, kv, hd(c), c["num_hidden_layers"], c["vocab_size"]


def layer_matmul_params(c: Dict) -> int:
    """Weights of one layer's matrix products (q, k, v, o, gate, up,
    down)."""
    d, f, h, kv, hd_, _, _ = _dims(c)
    return d * h * hd_ + 2 * d * kv * hd_ + h * hd_ * d + 3 * d * f


def decode_token_flops(c: Dict, context: int) -> int:
    """Model FLOPs of one decoded token that attends ``context`` tokens
    (itself included): the projections of every layer, attention
    (scores and weighted sum), and the head over the vocabulary."""
    d, f, h, kv, hd_, L, V = _dims(c)
    return (2 * L * layer_matmul_params(c) + 4 * L * h * hd_ * context
            + 2 * d * V)


def prefill_flops(c: Dict, prompt: int) -> int:
    """Model FLOPs of one prompt pass: every position through every
    layer, causal attention, and the head at the last position only."""
    d, f, h, kv, hd_, L, V = _dims(c)
    causal_pairs = prompt * (prompt + 1) // 2
    return (2 * L * layer_matmul_params(c) * prompt
            + 4 * L * h * hd_ * causal_pairs + 2 * d * V)


def paged_kernel_cost(c: Dict, contexts: Iterable[int], page_tokens: int,
                      kv_bytes: int = 2, q_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of ONE paged-attention call (one layer) over a
    batch whose rows attend ``contexts`` tokens: for each row, whole
    pages of K and V (all KV heads) read, scores and weighted sums over
    every slot of those pages, q read and the output written."""
    d, f, h, kv, hd_, L, V = _dims(c)
    flops = nbytes = 0
    for n in contexts:
        pages = -(-int(n) // page_tokens)
        flops += 4 * h * hd_ * pages * page_tokens
        nbytes += 2 * pages * page_tokens * kv * hd_ * kv_bytes
        nbytes += 2 * h * hd_ * q_bytes
    return flops, nbytes


def paged_layers(c: Dict) -> int:
    """Layers that call the paged kernel in one decode step: every
    one."""
    return c["num_hidden_layers"]
