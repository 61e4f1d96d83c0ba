"""The benchmark's weights and its float32 reference: the served tree
and the reference draw the same values from the seed, and the reference
computes what the program computes (on a tiny Qwen2, on the CPU)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic, weights

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = traffic.seed_parts(2**33 + 99)


def tiny():
    with open(os.path.join(DATA, "tiny.json")) as f:
        return json.load(f)


def test_served_and_reference_weights_are_the_same_values():
    c = tiny()
    tree = weights.served_params(c, SEED, padded_vocab=512)
    for layer in range(c["num_hidden_layers"]):
        ref = weights.layer_leaves(c, SEED[0], SEED[1], layer)
        attn = tree["trunk"]["attn"]
        mlp = tree["trunk"]["mlp"]
        served = {"wq": attn["wq"]["w"], "bq": attn["wq"]["b"],
                  "wk": attn["wk"]["w"], "bk": attn["wk"]["b"],
                  "wv": attn["wv"]["w"], "bv": attn["wv"]["b"],
                  "wo": attn["wo"]["w"], "w_gate": mlp["w_gate"]["w"],
                  "w_up": mlp["w_up"]["w"], "w_down": mlp["w_down"]["w"],
                  "norm1": tree["trunk"]["norm1"]["scale"],
                  "norm2": tree["trunk"]["norm2"]["scale"]}
        for name, arr in served.items():
            got = np.asarray(arr[layer].astype(jnp.float32))
            assert np.array_equal(got, np.asarray(ref[name])), name
    table = np.asarray(tree["embed"]["table"].astype(jnp.float32))
    want = np.asarray(weights.embedding(c, *SEED))
    assert np.array_equal(table[:c["vocab_size"]], want)
    assert not table[c["vocab_size"]:].any()


def test_reference_matches_the_programs_prefill():
    from bench.run import Cell, build_program
    c = tiny()
    cell = Cell("tiny", 1, c, {}, {}, [], [])
    seed = 2**33 + 99
    arch, model, params = build_program(cell, seed)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, c["vocab_size"], n).astype(np.int32)
            for n in (17, 40)]
    ref = reference.logits(c, traffic.seed_parts(seed), seqs,
                           [len(s) - 1 for s in seqs])
    for s, r in zip(seqs, ref):
        cache = model.init_cache(1, 64)
        got, _ = jax.jit(model.prefill)(params,
                                        {"tokens": jnp.asarray(s[None])},
                                        cache)
        got = np.asarray(got[0, :c["vocab_size"]].astype(jnp.float32))
        spread = r[0].max() - r[0].min()
        assert np.max(np.abs(got - r[0])) < 0.05 * spread
        assert np.corrcoef(got, r[0])[0, 1] > 0.999


def test_gaps():
    ref = np.array([[1.0, 3.0, 2.0], [0.0, -1.0, 5.0]])
    assert list(reference.gaps(ref, np.array([1, 0]))) == [0.0, 5.0]
    assert np.isinf(reference.gaps(ref, np.array([3, 2]))[0])


def test_fp8_control_departs_from_the_reference():
    c = tiny()
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, c["vocab_size"], 48).astype(np.int32)]
    ref = reference.logits(c, SEED, seqs, [0])[0]
    ctl = reference.logits(c, SEED, seqs, [0], control=True)[0]
    assert np.max(np.abs(ref - ctl)) > 1e-3
    assert reference.gaps(ref, ref.argmax(1)).max() == 0.0
