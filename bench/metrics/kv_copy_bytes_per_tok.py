"""KV manager layer (``serve/kv_cache.py``): bytes the KV path's array
ops wrote on HBM (the program's ``kv.hbm_copy_bytes`` counter: gathers,
pads, stacks, whole-pool scatters, the pool the decode step returns)
per output token, over the rounds of the traced window.  Each
``serve.round`` span carries the counter's growth over its round
(``hbm_copy_bytes``); the tokens are those the traced steps delivered.
The base, bytes and tokens, goes to standard error."""

import sys

from bench import program


def read(run):
    spans = program.recorded()
    if spans is None:
        return None
    rounds = [s for s in spans if s.name == "serve.round"
              and "hbm_copy_bytes" in s.args]
    if not rounds:
        return None
    if len(rounds) != len(run.traced_steps):
        raise RuntimeError(
            f"{len(rounds)} traced rounds in the program's spans against "
            f"{len(run.traced_steps)} steps in the traced window")
    nbytes = sum(s.args["hbm_copy_bytes"] for s in rounds)
    tokens = sum(len(s.contexts) + s.prefills for s in run.traced_steps)
    if tokens == 0:
        return None
    print(f"kv_copy_bytes_per_tok: {nbytes} bytes over {tokens} tokens "
          f"in {len(rounds)} rounds ({len(spans)} program spans)",
          file=sys.stderr, flush=True)
    return nbytes / tokens
