"""Smoke run of the LMB serve path on one TPU chip.

    python chip_smoke.py

One process, through the normal entry points, at the published widths
of qwen2-1.5b (28 layers, d_model 1536, vocab 151936, bf16; random
weights from a fixed seed):

  A  kernel   the compiled Pallas paged-attention kernel against the
              float32 reference, at qwen2 widths, with ragged lengths,
              an unmapped page and a length-0 row.
  B  HBM      ServeEngine with paged decode and the KV held onboard:
              8 requests, prompts of 128, 256 and 512 tokens, 32 new
              tokens each.  The compiled decode step must hold the kernel
              (``tpu_custom_call``), not the XLA fallback.
  C  spill    the same requests with an onboard budget below the
              working set, so KV pages spill to the LMB tier in pinned
              host memory and fault back.  Tokens must be byte-identical
              to B: page moves are exact copies.

Each check prints one line.  A failed check exits 1 before the last
line; off a TPU the script exits 2 and prints no result.  The last line
of a passing run is one JSON object naming the device.  This is a
smoke run: it times nothing beyond set-up and phase wall clocks.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
ARCH = "qwen2-1.5b"
PAGE_TOKENS = 32
DECODE_SLOTS = 8
MAX_SEQ_LEN = 1024
# one prompt per request; three lengths, so prefill compiles three times
PROMPT_LENS = (512, 128, 512, 256, 512, 512, 512, 512)
NEW_TOKENS = 32
# the working set is 116 pages of 0.9 MB at full width (28 layers x K,V
# x 32 tokens x 2 KV heads x 128 x bf16)
ONBOARD_PAGES_HBM = 160     # holds all of it
ONBOARD_PAGES_SPILL = 48    # holds 40%: the rest rides the LMB tier
# kernel vs float32 reference on bf16 inputs: the kernel rounds q*scale
# and its output to bf16 (relative step 2**-8), outputs are O(1)
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event: str, secs: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += secs


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"  [{'pass' if ok else 'FAIL'}] {name}"
          + (f" ({detail})" if detail else ""), flush=True)
    if not ok:
        sys.exit(1)


class Phase:
    """Prints a phase's wall and compile seconds when it ends."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        print(f"phase {self.name}", flush=True)
        self.t0, self.c0 = time.monotonic(), _compile_s[0]
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            print(f"  phase {self.name}: wall_s="
                  f"{time.monotonic() - self.t0} compile_s="
                  f"{_compile_s[0] - self.c0}", flush=True)


def phase_kernel() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.paged_attention import paged_attention

    B, H, KV, hd, T, P, MP = 8, 12, 2, 128, PAGE_TOKENS, 64, 16
    rng = np.random.default_rng(SEED)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((P, T, KV, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((P, T, KV, hd)), jnp.bfloat16)
    lengths = np.array([0, 1, 31, 32, 33, 200, 300, MP * T], np.int32)
    table = np.full((B, MP), -1, np.int32)
    free = list(rng.permutation(P))
    for b, n in enumerate(lengths):
        for i in range(-(-int(n) // T)):
            table[b, i] = free.pop()
    table[6, 3] = -1                  # an unmapped page mid-sequence
    args = (q, kp, vp, jnp.asarray(table), jnp.asarray(lengths))
    compiled = paged_attention.lower(*args).compile()
    check("kernel lowers to a Mosaic custom call",
          "tpu_custom_call" in compiled.as_text())
    out = np.asarray(compiled(*args).astype(jnp.float32))
    f32 = [a.astype(jnp.float32) for a in args[:3]]
    expect = np.asarray(ref.paged_attention_ref(*f32, *args[3:]))
    err = float(np.max(np.abs(out - expect)))
    check("kernel matches the float32 reference",
          bool(np.allclose(out, expect, atol=KERNEL_ATOL,
                           rtol=KERNEL_RTOL)),
          f"max_abs_err={err} atol={KERNEL_ATOL} rtol={KERNEL_RTOL}")
    check("length-0 row is exactly zero", not np.any(out[0]))
    check("all outputs finite", bool(np.all(np.isfinite(out))))


def make_prompts(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, n, dtype=np.int32) for n in PROMPT_LENS]


def serve(model, params, prompts, onboard_pages: int) -> dict:
    """Serve ``prompts`` through ServeEngine; returns what the checks
    read, taken before the LMB session closes."""
    from repro.core import DeviceSpec, HostSpec, LMBSystem, SystemSpec
    from repro.kernels import ops
    from repro.serve import EngineConfig, ServeEngine, SubmitSpec

    spec = SystemSpec(expanders=1, pool_gib=4,
                      hosts=(HostSpec("server", page_bytes=4096),),
                      devices=(DeviceSpec("tpu0"),))
    traces0 = ops.paged_attention_decode_traces()
    with LMBSystem(spec) as system:
        eng = ServeEngine(model, params, system, EngineConfig(
            decode_slots=DECODE_SLOTS, max_seq_len=MAX_SEQ_LEN,
            page_tokens=PAGE_TOKENS, onboard_pages=onboard_pages))
        rids = [eng.submit(SubmitSpec(prompt=p, max_new_tokens=NEW_TOKENS))
                for p in prompts]
        eng.run()
        buf = eng.kv.buf
        onboard = buf.metrics.tier(buf.name, "onboard")
        st = eng.stats()
        return {
            "states": [eng.requests[r].state for r in rids],
            "tokens": [list(eng.requests[r].out_tokens) for r in rids],
            "decode_path": st["decode_path"],
            "paged_rounds": st["paged_rounds"],
            "cancelled": st["cancelled"],
            "decode_traces": ops.paged_attention_decode_traces() - traces0,
            "onboard_hits": onboard.hits,
            "onboard_misses": onboard.misses,
            "link_bytes": sum(buf.host.fm.op_bytes().values()),
            "lmb_memory_kinds": sorted(buf.lmb_memory_kinds()),
            "lmb_kind": buf.executor.lmb_memory_kind,
        }


def report(r: dict) -> None:
    print(f"  requests={len(r['states'])} paged_rounds={r['paged_rounds']} "
          f"cancelled={r['cancelled']} onboard_hits={r['onboard_hits']} "
          f"onboard_misses={r['onboard_misses']} "
          f"link_bytes={r['link_bytes']} "
          f"lmb_memory_kinds={r['lmb_memory_kinds']}", flush=True)


def decode_step_hlo(model, params, prompts) -> str:
    """HLO of ``Model.decode_step_paged`` as the engine compiles it for
    this traffic's largest round (pool padded to a power of two)."""
    import jax
    import jax.numpy as jnp
    cfg = model.cfg
    pages = sum(-(-(len(p) + NEW_TOKENS) // PAGE_TOKENS) for p in prompts)
    P = 1 << (pages - 1).bit_length()
    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    B, MP = len(prompts), -(-MAX_SEQ_LEN // PAGE_TOKENS)
    sds = jax.ShapeDtypeStruct
    shapes = (jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                     params),
              sds((P, L, 2, PAGE_TOKENS, KV, hd), jnp.dtype(cfg.dtype)),
              sds((B, MP), jnp.int32), sds((B,), jnp.int32),
              sds((B, 1), jnp.int32))
    return jax.jit(model.decode_step_paged).lower(*shapes).compile().as_text()


def phase_serve_hbm(model, params, prompts) -> dict:
    r = serve(model, params, prompts, ONBOARD_PAGES_HBM)
    report(r)
    check("every request done", r["states"] == ["done"] * len(prompts),
          f"states={sorted(set(r['states']))}")
    check("no request cancelled", r["cancelled"] == 0)
    check("decode path is paged", r["decode_path"] == "paged")
    check("paged rounds ran", r["paged_rounds"] >= 1,
          f"paged_rounds={r['paged_rounds']}")
    check("decode dispatcher traced", r["decode_traces"] >= 1)
    hlo = decode_step_hlo(model, params, prompts)
    check("compiled decode step holds the Pallas kernel",
          "tpu_custom_call" in hlo,
          f"tpu_custom_call x{hlo.count('tpu_custom_call')}")
    return r


def phase_serve_spill(model, params, prompts, hbm: dict) -> dict:
    import numpy as np
    r = serve(model, params, prompts, ONBOARD_PAGES_SPILL)
    report(r)
    check("every request done", r["states"] == ["done"] * len(prompts),
          f"states={sorted(set(r['states']))}")
    check("no request cancelled", r["cancelled"] == 0)
    check("LMB tier is pinned_host",
          r["lmb_kind"] == "pinned_host"
          and r["lmb_memory_kinds"] == ["pinned_host"],
          f"executor={r['lmb_kind']} pages={r['lmb_memory_kinds']}")
    check("onboard tier missed", r["onboard_misses"] > 0)
    check("link bytes moved", r["link_bytes"] > 0)
    got, want = (np.asarray(t, np.int32) for t in (r["tokens"],
                                                   hbm["tokens"]))
    check("tokens byte-identical to the HBM run",
          got.shape == want.shape and got.tobytes() == want.tobytes(),
          f"tokens={got.size}")
    return r


def build(cfg):
    import jax
    from repro.models import build_model
    from repro.models.flags import Flags
    model = build_model(cfg, Flags(remat=False))
    params = jax.jit(model.init)(jax.random.key(SEED))
    jax.block_until_ready(params)
    return model, params


def main() -> int:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}; "
              "nothing run", file=sys.stderr)
        return 2
    from repro.configs.base import get_config
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    t0 = time.monotonic()
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)

    with Phase("A kernel"):
        phase_kernel()
    cfg = get_config(ARCH)
    with Phase("setup"):
        model, params = build(cfg)
        n = sum(x.size for x in jax.tree_util.tree_leaves(params))
        print(f"  {ARCH}: layers={cfg.num_layers} d_model={cfg.d_model} "
              f"vocab={cfg.vocab_size} dtype={cfg.dtype} params={n}",
              flush=True)
    prompts = make_prompts(cfg.vocab_size)
    print(f"  prompts={[len(p) for p in prompts]} new_tokens={NEW_TOKENS}",
          flush=True)
    with Phase(f"B serve, onboard_pages={ONBOARD_PAGES_HBM}"):
        hbm = phase_serve_hbm(model, params, prompts)
    with Phase(f"C serve, onboard_pages={ONBOARD_PAGES_SPILL}"):
        phase_serve_spill(model, params, prompts, hbm)
    stats = dev.memory_stats() or {}
    print(f"total wall_s={time.monotonic() - t0} compile_s={_compile_s[0]} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
