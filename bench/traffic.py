"""The one traffic generator: requests of a closed loop from a mix's
parameters and the run's seed.

Sizes are the distributions' quantiles at stratified points, in an
order drawn once (``ORDER_SEED``); the run's seed draws the prompts'
tokens.  Every seed thus offers the same work in the same order: the
program's page bursts, and so the programs it builds, follow from the
sizes and their order, and a window of a few tens of requests would
otherwise hold other work for every seed.  Prompt lengths are rounded
up to the mix's ``round_up_to`` (prefill compiles per exact length, so
the rounding bounds the menu of compiled lengths).
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np

#: draws the one order of the request sizes that every seed gets
ORDER_SEED = 0


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The distribution's values at the ``n`` stratified points
    ``(i + 0.5) / n``, clipped to ``[min, max]``, as integers
    (rounded up to ``round_up_to`` when the mix asks for it)."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        vals = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    vals = np.clip(np.round(vals), dist["min"], dist["max"])
    step = dist.get("round_up_to", 1)
    vals = np.ceil(vals / step) * step
    return vals.astype(np.int64)


def seed_parts(seed: int) -> tuple:
    """A seed of any size as two numbers below 2**31, for keys that
    take 32 bits."""
    if seed < 0:
        raise ValueError("seed must be >= 0")
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


@dataclasses.dataclass
class Plan:
    """What one run offers: request sizes in the order the clients send
    them, and the prompts' tokens."""

    prompt_lens: np.ndarray
    output_lens: np.ndarray
    prompts: List[np.ndarray]

    @property
    def menu(self) -> List[int]:
        """The distinct prompt lengths: one prefill compile each."""
        return sorted({int(x) for x in self.prompt_lens})


def _blocks(dist: Dict, block: int, nblocks: int,
            rng: np.random.Generator) -> np.ndarray:
    """``nblocks`` blocks, each the full stratified set of ``block``
    values, each permuted by ``rng``."""
    base = quantiles(dist, block)
    return np.concatenate([rng.permutation(base) for _ in range(nblocks)])


def plan(mix: Dict, seed: int, vocab: int) -> Plan:
    """Build the run's offered work from the mix and the seed: the
    ``clients`` clients of a closed loop draw from one sequence of
    requests made of blocks of ``clients`` sizes (each block the same
    stratified set, in an order that does not depend on the seed),
    enough for ``max_requests``; the seed draws the tokens."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    order = np.random.default_rng(ORDER_SEED)
    block = mix["clients"]
    nblocks = -(-mix["max_requests"] // block)
    plens = _blocks(mix["prompt"], block, nblocks, order)
    olens = _blocks(mix["output"], block, nblocks, order)
    rng = np.random.default_rng(list(seed_parts(seed)))
    prompts = [rng.integers(0, vocab, int(p), dtype=np.int32)
               for p in plens]
    return Plan(plens, olens, prompts)


def working_set_pages(prompt_lens: Sequence[int], output_lens: Sequence[int],
                      page_tokens: int) -> float:
    """Mean pages a decoding request holds, weighted by time (one decode
    round per output token after the first), with prompt and output
    lengths drawn independently from the two lists."""
    prompts = np.asarray(prompt_lens, np.int64)
    outs = np.asarray(output_lens, np.int64)
    k = np.arange(1, int(outs.max()), dtype=np.int64)
    # pages[p, k-1]: pages held while decoding output token k
    pages = -(-(prompts[:, None] + k[None, :]) // page_tokens)
    upto = np.concatenate([np.zeros((len(prompts), 1), np.int64),
                           np.cumsum(pages, axis=1)], axis=1)
    total = upto[:, outs - 1].sum()
    steps = len(prompts) * (outs - 1).sum()
    return float(total) / max(float(steps), 1.0)


def max_request_pages(mix: Dict, page_tokens: int) -> int:
    """Pages of the largest request the mix can draw."""
    return -(-(mix["prompt"]["max"] + mix["output"]["max"]) // page_tokens)


def onboard_pages(mix: Dict, page_tokens: int) -> int:
    """The HBM-tier page budget the mix asks for: a share of the mean
    working set of all slots, or room for the largest one."""
    rule = mix["onboard_pages"]
    slots = mix["decode_slots"]
    if rule["rule"] == "largest_working_set":
        return slots * max_request_pages(mix, page_tokens)
    if rule["rule"] == "mean_working_set":
        ws = working_set_pages(quantiles(mix["prompt"], 512),
                               quantiles(mix["output"], 512), page_tokens)
        return int(round(rule["factor"] * slots * ws))
    raise ValueError(f"unknown onboard rule {rule['rule']!r}")
