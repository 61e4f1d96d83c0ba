"""Serving launcher: continuous batching with LMB-backed KV capacity.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --requests 16 --decode-slots 4

serves the published config (random weights); ``--reduced`` swaps in
the tiny same-family config for a CPU run.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core import DeviceSpec, HostSpec, LMBSystem, SystemSpec
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.models.flags import Flags
from repro.serve import EngineConfig, ServeEngine, SubmitSpec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--decode-slots", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--onboard-pages", type=int, default=16)
    ap.add_argument("--pool-gib", type=int, default=4)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config instead of the "
                         "published widths")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, Flags(remat=False))
    params = jax.jit(model.init)(jax.random.key(0))

    spec = SystemSpec(expanders=1, pool_gib=args.pool_gib,
                      hosts=(HostSpec("server", page_bytes=4096),),
                      devices=(DeviceSpec("tpu0"),))
    with LMBSystem(spec) as system:
        eng = ServeEngine(model, params, system, EngineConfig(
            decode_slots=args.decode_slots, max_seq_len=128, page_tokens=16,
            onboard_pages=args.onboard_pages))
        rng = np.random.default_rng(0)
        t0 = time.monotonic()
        for _ in range(args.requests):
            eng.submit(SubmitSpec(
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(4, 48))),
                max_new_tokens=args.max_new_tokens))
        eng.run()
        wall = time.monotonic() - t0
        st = eng.stats()
        st["wall_s"] = wall
        st["tok_per_s"] = args.requests * args.max_new_tokens / wall
        print(json.dumps(st, indent=1, default=str))


if __name__ == "__main__":
    main()
