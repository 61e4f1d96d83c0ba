"""Operations and bytes of the work a Qwen2 serve step must do, from
shapes alone.

Model FLOPs count multiply-adds as two and include only the work the
model defines (real tokens, the published vocabulary).  The paged
kernel's account follows its data path instead: it DMAs whole pages
(all KV heads) and computes over every slot of each page it walks.
"""

from __future__ import annotations

from typing import Dict, Iterable


def _dims(c: Dict):
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return d, f, h, kv, hd, c["num_hidden_layers"], c["vocab_size"]


def layer_matmul_params(c: Dict) -> int:
    """Weights of one layer's matrix products (q, k, v, o, gate, up,
    down)."""
    d, f, h, kv, hd, _, _ = _dims(c)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def decode_token_flops(c: Dict, context: int) -> int:
    """Model FLOPs of one decoded token that attends ``context`` tokens
    (itself included): the projections of every layer, attention
    (scores and weighted sum), and the head over the vocabulary."""
    d, f, h, kv, hd, L, V = _dims(c)
    return (2 * L * layer_matmul_params(c) + 4 * L * h * hd * context
            + 2 * d * V)


def prefill_flops(c: Dict, prompt: int) -> int:
    """Model FLOPs of one prompt pass: every position through every
    layer, causal attention, and the head at the last position only."""
    d, f, h, kv, hd, L, V = _dims(c)
    causal_pairs = prompt * (prompt + 1) // 2
    return (2 * L * layer_matmul_params(c) * prompt
            + 4 * L * h * hd * causal_pairs + 2 * d * V)


def paged_kernel_cost(c: Dict, contexts: Iterable[int], page_tokens: int,
                      kv_bytes: int = 2, q_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of ONE paged-attention call (one layer) over a
    batch whose rows attend ``contexts`` tokens: for each row, whole
    pages of K and V (all KV heads) read, scores and weighted sums over
    every slot of those pages, q read and the output written."""
    d, f, h, kv, hd, L, V = _dims(c)
    flops = nbytes = 0
    for n in contexts:
        pages = -(-int(n) // page_tokens)
        flops += 4 * h * hd * pages * page_tokens
        nbytes += 2 * pages * page_tokens * kv * hd * kv_bytes
        nbytes += 2 * h * hd * q_bytes
    return flops, nbytes
