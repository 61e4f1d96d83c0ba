import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the right step function (train_4k -> train_step;
prefill_32k -> prefill; decode_32k / long_500k -> serve_step = one-token
decode), jits it with full production shardings, ``.lower().compile()``s
against ShapeDtypeStruct inputs (no allocation), and records:

  * ``memory_analysis()``  — proves the cell fits per-device HBM,
  * ``cost_analysis()``    — FLOPs / bytes for §Roofline,
  * parsed collective bytes, and the three roofline terms.

Results append to a JSON table (``--out``); already-done cells are skipped
so the sweep is resumable.  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
        --shape train_4k --mesh single          # one cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ArchConfig, ShapeConfig, get_config, \
    list_configs
from repro.launch.mesh import make_production_mesh
from repro.models.flags import Flags
from repro.models.zoo import build_model
from repro.optim.adamw import AdamWConfig
from repro.roofline.analysis import model_flops, roofline_terms
from repro.sharding.constraints import activation_mesh
from repro.sharding.partition import (batch_spec, cache_shardings,
                                      param_shardings)
from repro.train.loop import abstract_train_state, make_train_step


def _cost_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()``, or ``{}`` where a backend has none."""
    return compiled.cost_analysis() or {}


def opt_state_shardings(opt_shapes, mesh, cfg, fsdp=False):
    """m/v/master shard like params; scalars replicated."""
    out = {}
    for key, sub in opt_shapes.items():
        if key in ("m", "v", "master", "ef_err"):
            out[key] = param_shardings(sub, mesh, cfg, fsdp=fsdp)
        else:
            out[key] = jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P()), sub)
    return out


def _want_fsdp(cfg, shape) -> bool:
    """ZeRO/FSDP when the per-device state wouldn't fit HBM otherwise.

    train: params/grads/opt = ~16 B/param, sharded 16-way TP -> FSDP when
    that exceeds half of HBM.  serve: bf16 params only."""
    n = cfg.param_count()
    per_dev = (16.0 if shape.kind == "train" else 2.0) * n / 16
    return per_dev > 8e9


def build_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, flags: Flags):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs)."""
    model = build_model(cfg, flags)
    fsdp = _want_fsdp(cfg, shape)
    params_shapes = model.abstract_params()
    p_shard = param_shardings(params_shapes, mesh, cfg, fsdp=fsdp)
    B, S = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        params_shapes, opt_shapes = abstract_train_state(model)
        o_shard = opt_state_shardings(opt_shapes, mesh, cfg, fsdp=fsdp)
        step = make_train_step(model, AdamWConfig())
        specs = model.input_specs(shape)
        b_shard = {
            k: NamedSharding(mesh, batch_spec(mesh, B, len(v.shape) - 1))
            for k, v in specs.items()}
        fn = jax.jit(step,
                     in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, None),
                     donate_argnums=(0, 1))
        return fn, (params_shapes, opt_shapes, specs)

    if shape.kind == "prefill":
        specs = model.input_specs(shape)
        cache_shapes = jax.eval_shape(lambda: model.init_cache(B, S))
        c_shard = cache_shardings(cache_shapes, mesh, cfg, B)
        b_shard = {
            k: NamedSharding(mesh, batch_spec(mesh, B, len(v.shape) - 1))
            for k, v in specs.items()}
        fn = jax.jit(model.prefill,
                     in_shardings=(p_shard, b_shard, c_shard),
                     out_shardings=(NamedSharding(
                         mesh, batch_spec(mesh, B, 1)), c_shard),
                     donate_argnums=(2,))
        return fn, (params_shapes, specs, cache_shapes)

    # serve_step: one new token against a seq_len KV cache
    cache_shapes = jax.eval_shape(lambda: model.init_cache(B, S))
    c_shard = cache_shardings(cache_shapes, mesh, cfg, B)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    t_shard = NamedSharding(mesh, batch_spec(mesh, B, 1))
    fn = jax.jit(model.decode_step,
                 in_shardings=(p_shard, c_shard, t_shard),
                 out_shardings=(NamedSharding(mesh, batch_spec(mesh, B, 1)),
                                c_shard),
                 donate_argnums=(1,))
    return fn, (params_shapes, cache_shapes, tok)


def _measure(cfg, shape, mesh, flags) -> Dict[str, float]:
    """lower+compile one step fn; returns {flops, bytes, coll} (per-device)
    plus memory analysis + compile timings."""
    from repro.roofline.analysis import collective_bytes_per_device
    t0 = time.monotonic()
    fn, args = build_cell(cfg, shape, mesh, flags)
    with activation_mesh(mesh if flags.act_constraints else None):
        lowered = fn.lower(*args)
    t_lower = time.monotonic() - t0
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0 - t_lower
    cost = _cost_dict(compiled)
    hlo = compiled.as_text()
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": collective_bytes_per_device(hlo)["total"],
        "lower_s": t_lower, "compile_s": t_compile,
    }
    try:
        mem = compiled.memory_analysis()
        out["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:  # pragma: no cover
        out["memory"] = {"error": str(e)}
    return out


def _inner_chunk_cost(cfg, shape, mesh, flags) -> Dict[str, float]:
    """Per-chunk {flops, bytes, coll} of the wkv/ssd inner scan, measured
    as cost(2 chunks, unrolled) - cost(1 chunk).  Needed because the inner
    lax.scan body is also counted once by cost_analysis."""
    from repro.configs.base import HYBRID, RWKV6
    from repro.roofline.analysis import collective_bytes_per_device
    from jax.sharding import NamedSharding
    B = shape.global_batch
    T = flags.scan_chunk
    bspec = batch_spec(mesh, B, 3)
    results = []
    for n_chunks in (1, 2):
        S = T * n_chunks
        if cfg.block_type == RWKV6:
            from repro.models.rwkv6 import wkv_chunked
            H = cfg.d_model // cfg.rwkv_head_dim
            N = cfg.rwkv_head_dim
            seq = jax.ShapeDtypeStruct((B, S, H, N), jnp.float32)
            u = jax.ShapeDtypeStruct((H, N), jnp.float32)
            st = jax.ShapeDtypeStruct((B, H, N, N), jnp.float32)
            ms = mesh.shape["model"]
            h_ax = "model" if H % ms == 0 else None
            sh_seq = NamedSharding(mesh, jax.sharding.PartitionSpec(
                bspec[0], None, h_ax, None))
            sh_u = NamedSharding(mesh, jax.sharding.PartitionSpec(h_ax, None))
            sh_st = NamedSharding(mesh, jax.sharding.PartitionSpec(
                bspec[0], h_ax, None, None))
            fn = jax.jit(lambda r, k, v, w, u, s: wkv_chunked(
                r, k, v, w, u, s, chunk=T, unroll=True),
                in_shardings=(sh_seq,) * 4 + (sh_u, sh_st))
            args = (seq, seq, seq, seq, u, st)
        elif cfg.block_type == HYBRID:
            from repro.models.ssm import ssd_chunked
            d_in = cfg.ssm_expand * cfg.d_model
            H = cfg.ssm_heads or max(1, d_in // 64)
            P_ = d_in // H
            N = cfg.ssm_state
            xh = jax.ShapeDtypeStruct((B, S, H, P_), jnp.float32)
            dt = jax.ShapeDtypeStruct((B, S, H), jnp.float32)
            A = jax.ShapeDtypeStruct((H,), jnp.float32)
            Bm = jax.ShapeDtypeStruct((B, S, N), jnp.float32)
            st = jax.ShapeDtypeStruct((B, H, P_, N), jnp.float32)
            sh4 = NamedSharding(mesh, jax.sharding.PartitionSpec(
                bspec[0], None, None, None))
            sh3 = NamedSharding(mesh, jax.sharding.PartitionSpec(
                bspec[0], None, None))
            shA = NamedSharding(mesh, jax.sharding.PartitionSpec(None))
            fn = jax.jit(lambda x, d, a, bm, cm, s: ssd_chunked(
                x, d, a, bm, cm, s, chunk=T, unroll=True),
                in_shardings=(sh4, sh3, shA, sh3, sh3, sh4))
            args = (xh, dt, A, Bm, Bm, st)   # Cm shares Bm's spec
        else:
            return {"flops": 0.0, "bytes": 0.0, "coll": 0.0}
        compiled = fn.lower(*args).compile()
        cost = _cost_dict(compiled)
        results.append({
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": collective_bytes_per_device(
                compiled.as_text())["total"]})
    return {k: max(results[1][k] - results[0][k], 0.0) for k in results[0]}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             flags: Flags = Flags(), verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "flags": dataclasses.asdict(flags), "status": "skipped",
    }
    if shape_name not in cfg.shape_cells():
        rec["reason"] = "long-context N/A for pure full-attention arch"
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size
    try:
        # ---- artifact: full depth, scanned (memory + compile proof) ----
        art = _measure(cfg, shape, mesh, flags)
        rec["memory"] = art.pop("memory")
        # ---- per-layer body: unroll@2 - scan@2 (cost_analysis counts a
        # while body once; scan@L has identical body HLO for any L) ----
        L = cfg.num_layers
        cfg2 = dataclasses.replace(
            cfg, num_layers=2,
            num_encoder_layers=2 if cfg.encoder_decoder else 0)
        scan2 = _measure(cfg2, shape, mesh, flags)
        unroll2 = _measure(cfg2, shape, mesh,
                           dataclasses.replace(flags, unroll_layers=True))
        body = {k: max(unroll2[k] - scan2[k], 0.0)
                for k in ("flops", "bytes", "coll")}
        # ---- inner chunk scans (rwkv/ssd) also count once ----
        corr = {"flops": 0.0, "bytes": 0.0, "coll": 0.0}
        if shape.kind != "decode" and cfg.block_type in ("rwkv6", "hybrid"):
            nc = shape.seq_len // flags.scan_chunk
            chunk_cost = _inner_chunk_cost(cfg, shape, mesh, flags)
            mult = (3.0 if shape.kind == "train" else 1.0)  # fwd+bwd+remat
            corr = {k: L * max(nc - 1, 0) * chunk_cost[k] * mult
                    for k in chunk_cost}
            rec["inner_chunk_cost"] = chunk_cost
        totals = {k: art[k] + (L - 1) * body[k] + corr[k]
                  for k in ("flops", "bytes", "coll")}
        cost = {"flops": totals["flops"], "bytes accessed": totals["bytes"]}
        mf = model_flops(cfg, shape)
        terms = roofline_terms(cost, "", chips, mf)
        terms.collective_s = totals["coll"] / 50e9
        terms.coll_bytes_per_dev = totals["coll"]
        rec.update(status="ok", lower_s=round(art["lower_s"], 2),
                   compile_s=round(art["compile_s"], 2),
                   raw_artifact={k: art[k] for k in ("flops", "bytes", "coll")},
                   body_per_layer=body,
                   roofline=terms.row())
        if verbose:
            r = terms
            print(f"[{arch} × {shape_name} × {mesh_kind}] OK "
                  f"lower={art['lower_s']:.1f}s compile={art['compile_s']:.1f}s "
                  f"compute={r.compute_s*1e3:.2f}ms "
                  f"memory={r.memory_s*1e3:.2f}ms "
                  f"coll={r.collective_s*1e3:.2f}ms "
                  f"dom={r.dominant} "
                  f"MFU@roof={r.roofline_fraction*100:.1f}% "
                  f"useful={r.useful_flops_ratio*100:.0f}%")
            if "memory" in rec and "temp_size_in_bytes" in rec.get("memory", {}):
                m = rec["memory"]
                print(f"    mem/device: args={m['argument_size_in_bytes']/2**30:.2f}GiB "
                      f"temp={m['temp_size_in_bytes']/2**30:.2f}GiB "
                      f"out={m['output_size_in_bytes']/2**30:.2f}GiB")
    except Exception as e:
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_kind}] FAIL {type(e).__name__}: {e}")
    return rec


def load_table(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return {}


def cell_key(arch, shape, mesh, tag="base") -> str:
    return f"{arch}|{shape}|{mesh}|{tag}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--tag", default="base")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--causal-skip", action="store_true")
    ap.add_argument("--attn-chunk", type=int, default=512)
    ap.add_argument("--no-remat", action="store_true")
    args = ap.parse_args()

    flags = Flags(causal_skip=args.causal_skip, attn_chunk=args.attn_chunk,
                  remat=not args.no_remat)
    archs = list_configs() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    table = load_table(args.out)
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                key = cell_key(arch, shape, mesh_kind, args.tag)
                if key in table and table[key]["status"] == "ok" \
                        and not args.force:
                    print(f"[{key}] cached")
                    continue
                rec = run_cell(arch, shape, mesh_kind, flags)
                table[key] = rec
                with open(args.out, "w") as f:
                    json.dump(table, f, indent=1)
    ok = sum(1 for r in table.values() if r["status"] == "ok")
    fail = sum(1 for r in table.values() if r["status"] == "fail")
    skip = sum(1 for r in table.values() if r["status"] == "skipped")
    print(f"== dry-run table: {ok} ok / {fail} fail / {skip} skipped(N/A) ==")


if __name__ == "__main__":
    main()
