"""Seeded random weights of a Qwen2 model, made by the benchmark.

One function, :func:`layer_leaves`, draws every leaf of one layer from
the seed; the served weights (:func:`served_params`, one jitted call on
the device, in the types the program serves them in) and the
reference's (one layer at a time in float32)
both come from it, so the reference takes nothing the program made.

Values: matrices N(0, 1/fan_in), the embedding N(0, 0.02**2), q/k/v
biases N(0, 0.1**2), RMSNorm scales 1 + N(0, 0.125**2).  Matrices and
biases are rounded to bfloat16 (the served type); norm scales stay
float32, as the program keeps them.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

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
EMBED_STREAM = 100
FINAL_NORM_STREAM = 101


def hd(c: Dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


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


def layer_leaves(c: Dict, seed_lo, seed_hi, layer) -> Dict[str, jax.Array]:
    """Every leaf of layer ``layer`` (float32 values)."""
    return {name: _draw(_key(seed_lo, seed_hi, sid, layer), shape(c), kind)
            for name, (sid, shape, kind) in LEAVES.items()}


def embedding(c: Dict, seed_lo, seed_hi) -> jax.Array:
    """[vocab_size, hidden] float32 (bfloat16 values)."""
    z = jax.random.normal(_key(seed_lo, seed_hi, EMBED_STREAM, 0),
                          (c["vocab_size"], c["hidden_size"]), jnp.float32)
    return (z * 0.02).astype(jnp.bfloat16).astype(jnp.float32)


def final_norm(c: Dict, seed_lo, seed_hi) -> jax.Array:
    return _draw(_key(seed_lo, seed_hi, FINAL_NORM_STREAM, 0),
                 (c["hidden_size"],), "norm")


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


