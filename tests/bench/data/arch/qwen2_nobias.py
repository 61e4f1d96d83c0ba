"""Qwen2 without q/k/v biases: a test-only architecture module.

It shows that a configuration can bring an architecture the harness has
never seen as files alone: this module, a configuration that names it
(``"arch": "qwen2_nobias"``), a limits file and a traffic mix, all in a
bench directory of the test's own.  The program serves it as the
registered ``qwen2-1.5b`` with the override ``"qkv_bias": false``.

Its weights, reference and stated config are its own; the FLOP
accounts are Qwen2's, which count no bias.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.arch import qwen2
from bench.arch.qwen2 import (  # noqa: F401  (Qwen2's accounts)
    decode_token_flops, hd, layer_matmul_params, paged_kernel_cost,
    paged_layers, prefill_flops)
from bench.reference import Q_CHUNK, _attention, _linear, _rms, _rope
from bench.weights import _draw, _key, embedding, final_norm

#: Qwen2's leaves and streams, less the biases
LEAVES = {k: v for k, v in qwen2.LEAVES.items() if v[2] != "bias"}


def stated(c: Dict) -> Dict:
    return dict(qwen2.stated(c), qkv_bias=False)


def layer_leaves(c: Dict, seed_lo, seed_hi, layer) -> Dict[str, jax.Array]:
    return {name: _draw(_key(seed_lo, seed_hi, sid, layer), shape(c), kind)
            for name, (sid, shape, kind) in LEAVES.items()}


def served_params(c: Dict, seed: tuple, padded_vocab: int):
    def make(seed_lo, seed_hi):
        def one(layer):
            leaves = layer_leaves(c, seed_lo, seed_hi, layer)
            return {k: v.astype(jnp.bfloat16) if LEAVES[k][2] != "norm"
                    else v for k, v in leaves.items()}
        layers = jax.lax.map(one, jnp.arange(c["num_hidden_layers"]))
        table = embedding(c, seed_lo, seed_hi)
        pad = jnp.zeros((padded_vocab - table.shape[0], table.shape[1]))
        bf = jnp.bfloat16
        return {
            "embed": {"table": jnp.concatenate([table, pad]).astype(bf)},
            "final_norm": {"scale": final_norm(c, seed_lo, seed_hi)},
            "trunk": {
                "norm1": {"scale": layers["norm1"]},
                "norm2": {"scale": layers["norm2"]},
                "attn": {n: {"w": layers[n]} for n in ("wq", "wk", "wv",
                                                       "wo")},
                "mlp": {n: {"w": layers[n]} for n in ("w_gate", "w_up",
                                                      "w_down")},
            },
        }
    return jax.jit(make)(*seed)


def _layer(c: Dict, control: bool, w: Dict, x: jax.Array) -> jax.Array:
    N, S, _ = x.shape
    H, KV, d = c["num_attention_heads"], c["num_key_value_heads"], hd(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = _rms(x, w["norm1"], eps)
    q = _linear(h, w["wq"], control).reshape(N, S, H, d)
    k = _linear(h, w["wk"], control).reshape(N, S, KV, d)
    v = _linear(h, w["wv"], control).reshape(N, S, KV, d)
    a = _attention(_rope(q, theta), _rope(k, theta), v)
    x = x + _linear(a.reshape(N, S, H * d), w["wo"], control)
    h = _rms(x, w["norm2"], eps)
    g = jax.nn.silu(_linear(h, w["w_gate"], control))
    return x + _linear(g * _linear(h, w["w_up"], control), w["w_down"],
                       control)


def logits(c: Dict, seed: tuple, seqs: Sequence[np.ndarray],
           starts: Sequence[int], control: bool = False) -> List[np.ndarray]:
    S = -(-max(len(s) for s in seqs) // Q_CHUNK) * Q_CHUNK
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        table = embedding(c, *seed)
        x = table[jnp.asarray(toks)]
        step = jax.jit(functools.partial(_layer, c, control))
        for layer in range(c["num_hidden_layers"]):
            x = step(layer_leaves(c, seed[0], seed[1], layer), x)
        h = _rms(x, final_norm(c, *seed), c["rms_norm_eps"])
        return [np.asarray(_linear(h[i, starts[i]:len(s)], table.T, control))
                for i, s in enumerate(seqs)]
