"""FLOP and byte functions of the decode step and the paged kernel,
against hand counts at qwen2 widths."""

import json
import os

import pytest

from bench import flops

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench")


#: Qwen2-7B's published widths (hf Qwen/Qwen2-7B), 14 of its 28 layers
QWEN2_7B = {"hidden_size": 3584, "intermediate_size": 18944,
            "num_attention_heads": 28, "num_key_value_heads": 4,
            "head_dim": 128, "num_hidden_layers": 14, "vocab_size": 152064}


def config(name):
    if name == "qwen2-7b":
        return dict(QWEN2_7B)
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_layer_params_qwen2_1_5b():
    c = config("qwen2-1.5b")
    q = 1536 * 12 * 128
    kv = 1536 * 2 * 128
    o = 12 * 128 * 1536
    mlp = 3 * 1536 * 8960
    assert flops.layer_matmul_params(c) == q + 2 * kv + o + mlp == 46792704


def test_decode_token_flops_qwen2_7b():
    c = config("qwen2-7b")
    per_layer = (3584 * 3584 + 2 * 3584 * 512 + 3584 * 3584
                 + 3 * 3584 * 18944)
    ctx = 1000
    want = (2 * 14 * per_layer + 4 * 14 * 28 * 128 * ctx
            + 2 * 3584 * 152064)
    assert flops.decode_token_flops(c, ctx) == want


def test_prefill_flops_counts_causal_pairs_and_one_head_row():
    c = config("qwen2-1.5b")
    S = 128
    want = (2 * 28 * 46792704 * S + 4 * 28 * 12 * 128 * (S * (S + 1) // 2)
            + 2 * 1536 * 151936)
    assert flops.prefill_flops(c, S) == want


@pytest.mark.parametrize("name,kv,heads", [("qwen2-1.5b", 2, 12),
                                           ("qwen2-7b", 4, 28)])
def test_paged_kernel_cost_whole_pages(name, kv, heads):
    c = config(name)
    # rows attend 1, 32 and 33 tokens: 1, 1 and 2 pages of 32 slots
    f, b = flops.paged_kernel_cost(c, [1, 32, 33], 32)
    pages = 4
    assert f == 4 * heads * 128 * pages * 32
    page_bytes = 2 * 32 * kv * 128 * 2           # K and V, bf16
    assert b == pages * page_bytes + 3 * 2 * heads * 128 * 2
    # a page of qwen2-1.5b across its 28 layers is 917,504 bytes
    if name == "qwen2-1.5b":
        assert page_bytes * 28 == 917504


def test_decode_mfu_reads_the_traced_rounds_alone():
    from types import SimpleNamespace

    from bench import run
    c = config("qwen2-1.5b")
    traced = [run.Step(10.0, 10.5, [300, 400], 0),
              run.Step(10.5, 11.0, [301], 1)]
    late = [run.Step(30.0, 31.0, [302] * 8, 0)]   # after the profiler's stop
    data = SimpleNamespace(
        cell=SimpleNamespace(config=c), steps=traced + late,
        traced_steps=traced, window_s=40.0,
        peaks={"bf16_flops_per_s": 197e12})
    got = run.metric_reader("decode_mfu")(data)
    want = sum(flops.decode_token_flops(c, n) for n in (300, 400, 301))
    assert got == pytest.approx(100.0 * want / (1.0 * 197e12))
    assert run.metric_reader("decode_mfu")(
        SimpleNamespace(**(vars(data) | {"traced_steps": []}))) is None
