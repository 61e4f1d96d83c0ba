"""Benchmark harness — one scenario per paper table/figure or sweep.

Prints ``name,us_per_call,derived`` CSV rows.
Run: ``PYTHONPATH=src python -m benchmarks.run`` (or ``--only fig6``).
``--only`` takes a comma-separated list; ``--json PATH`` additionally
writes the rows as JSON (CI uploads ``BENCH_ci.json`` per PR so the perf
trajectory is tracked).

Scenarios self-register with the :func:`scenario` decorator.  A scenario
that wants CI to gate on its output declares :class:`Gate` rows inline —
``--json`` embeds them in the payload and
``tools/check_bench_regression.py`` enforces them, so adding a gated
sweep never means hand-wiring a new key into the checker.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import platform
import sys
import time
from typing import Callable, Dict, Optional, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

#: rows accumulated for --json output: (name, us_per_call, derived)
_ROWS: list = []


def _row(name: str, us: float, derived: str = "") -> None:
    _ROWS.append({"name": name, "us_per_call": round(us, 3),
                  "derived": derived})
    print(f"{name},{us:.3f},{derived}")


# --------------------------------------------------------------------------
# Scenario registry
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Gate:
    """One regression-gate bound a scenario declares on its own rows.

    ``row`` names an emitted row, ``field`` a ``key=value`` entry in its
    ``derived`` column; the checker fails CI when the value leaves
    ``[min, max]``.  Bounds should be machine-independent (modeled /
    virtual-time / count figures), since they gate every runner.
    """

    row: str
    field: str
    min: Optional[float] = None
    max: Optional[float] = None
    note: str = ""


@dataclasses.dataclass(frozen=True)
class Scenario:
    name: str
    fn: Callable[[], None]
    gates: Tuple[Gate, ...] = ()


#: name -> Scenario, in registration (= declaration) order
SCENARIOS: Dict[str, Scenario] = {}


def scenario(name: str, gate: Tuple[Gate, ...] = ()):
    """Register a benchmark scenario (optionally with its CI gate rows)."""
    def deco(fn: Callable[[], None]) -> Callable[[], None]:
        if name in SCENARIOS:
            raise ValueError(f"duplicate scenario {name!r}")
        SCENARIOS[name] = Scenario(name, fn, tuple(gate))
        return fn
    return deco


# ----------------------------------------------------------- Fig 2: tiers
@scenario("fig2")
def bench_fig2_latency() -> None:
    """Paper Fig 2: estimated access latencies per tier."""
    from repro.core.tiers import paper_tiers
    for kind, spec in paper_tiers().items():
        _row(f"fig2.latency.{kind.value}", spec.added_latency_s * 1e6,
             f"bw={spec.bandwidth_Bps/1e9:.0f}GBps")


# ------------------------------------------------------------- Fig 6: sim
@scenario("fig6")
def bench_fig6() -> None:
    """Paper Fig 6 (a)+(b): Ideal/DFTL/LMB-CXL/LMB-PCIe x 4 workloads."""
    from repro.sim import make_ssd_model, make_workload, simulate
    from repro.sim.ssd import make_schemes
    from repro.sim.workload import ALL_PAPER_WORKLOADS
    for gen in (4, 5):
        spec = make_ssd_model(gen)
        schemes = make_schemes(spec)
        for wl_name in ALL_PAPER_WORKLOADS:
            wl = make_workload(wl_name, n_ios=100_000)
            ideal = simulate(spec, schemes["ideal"], wl).iops
            for sname in ("ideal", "lmb-cxl", "lmb-pcie", "dftl"):
                t0 = time.perf_counter()
                r = simulate(spec, schemes[sname], wl)
                wall = (time.perf_counter() - t0) * 1e6
                _row(f"fig6.gen{gen}.{wl_name}.{sname}", wall,
                     f"kiops={r.iops/1e3:.0f};rel={r.iops/ideal:.3f};"
                     f"p99us={r.p99_lat_us:.1f}")


# --------------------------------------- shared-fabric sweep (repro.qos)
@scenario("fabric_sweep")
def bench_fabric_sweep() -> None:
    """1->16 devices on ONE expander: aggregate throughput saturates at
    link bandwidth, equal-weight devices split it fairly, and a 2:1-weight
    tenant gets ~2x an unweighted one (weighted max-min arbitration)."""
    from repro.sim import (make_ssd_model, make_workload,
                           simulate_shared_fabric)
    from repro.sim.ssd import make_schemes
    spec = make_ssd_model(5)
    scheme = make_schemes(spec)["lmb-cxl"]
    wl = make_workload("randread", n_ios=20_000)
    link = 30e9
    for n in (1, 2, 4, 8, 12, 16):
        t0 = time.perf_counter()
        r = simulate_shared_fabric(spec, scheme, wl, n,
                                   link_bandwidth_Bps=link)
        wall = (time.perf_counter() - t0) * 1e6
        goodputs = [d.iops * wl.io_bytes for d in r.per_device]
        spread = (max(goodputs) - min(goodputs)) / max(goodputs)
        _row(f"fabric_sweep.equal.n{n:02d}", wall,
             f"aggGBps={r.aggregate_goodput_Bps/1e9:.2f};"
             f"rho={r.offered_utilization:.2f};"
             f"jain={r.fairness_jain:.3f};spread={spread:.3f};"
             f"p99us={r.mean_p99_us:.1f}")
    # weighted tenants: dev0 weighs 2x, everyone saturated -> 2x goodput
    n = 16
    r = simulate_shared_fabric(spec, scheme, wl, n,
                               link_bandwidth_Bps=link,
                               weights=[2.0] + [1.0] * (n - 1))
    goodputs = [d.iops * wl.io_bytes for d in r.per_device]
    _row(f"fabric_sweep.weighted2x.n{n:02d}", 0.0,
         f"aggGBps={r.aggregate_goodput_Bps/1e9:.2f};"
         f"ratio={goodputs[0]/goodputs[1]:.2f};"
         f"p99us={r.mean_p99_us:.1f}")


# --------------------------------- multi-expander hot/cold migration sweep
@scenario("migration_sweep")
def bench_migration_sweep() -> None:
    """1 hot expander + 1 cold: every device starts on expander 0; hot-page
    migration rebalances the pool and the hot expander's p99 index latency
    recovers toward the uncontended baseline, at a reported migrated-bytes
    overhead."""
    from repro.sim import (make_ssd_model, make_workload,
                           simulate_multi_expander)
    from repro.sim.ssd import make_schemes
    spec = make_ssd_model(5)
    scheme = make_schemes(spec)["lmb-cxl"]
    wl = make_workload("randread", n_ios=20_000)
    link = 30e9
    for n in (4, 8, 12):
        t0 = time.perf_counter()
        r = simulate_multi_expander(spec, scheme, wl, n, n_expanders=2,
                                    link_bandwidth_Bps=link)
        wall = (time.perf_counter() - t0) * 1e6
        _row(f"migration_sweep.hotcold.n{n:02d}", wall,
             f"p99us_before={r.hot_p99_before_us:.1f};"
             f"p99us_after={r.hot_p99_after_us:.1f};"
             f"p99us_baseline={r.baseline_p99_us:.1f};"
             f"recovery={r.recovery_fraction:.2f};"
             f"migMiB={r.migrated_bytes/2**20:.0f};"
             f"migs={r.migration_wall_s*1e3:.1f}ms;"
             f"rho={r.utilization_before[0]:.2f}->"
             f"{max(r.utilization_after):.2f}")
    # live end-to-end: LinkedBuffer thrash saturates expander 0's link,
    # the MigrationEngine moves the hottest pages to expander 1
    import jax.numpy as jnp
    from repro.core import system_for
    from repro.core.metrics import Metrics
    from repro.qos import MigrationEngine, MigrationPolicy
    system = system_for("d0", host_id="h0", n_expanders=2, pool_gib=1,
                        page_bytes=1 << 16, metrics=Metrics())
    buf = system.buffer(name="mig", device_id="d0",
                        page_shape=(128, 128), dtype=jnp.float32,
                        onboard_pages=4, lmb_chunk_pages=8,
                        metrics=Metrics())
    pages = buf.append_pages(32)
    for p in pages:
        buf.write(p, jnp.ones((128, 128)))
    for _ in range(2):
        for p in pages:
            buf.read(p)                      # thrash: all traffic on exp 0
    eng = MigrationEngine(system, MigrationPolicy(max_pages_per_round=16))
    eng.register(buf)
    t0 = time.perf_counter()
    rep = eng.run_once()
    wall = (time.perf_counter() - t0) * 1e6
    place = buf.lmb_placement()
    _row("migration_sweep.live", wall,
         f"moved={rep.pages_moved};migMiB={rep.bytes_moved/2**20:.1f};"
         f"placement={place.get(0, 0)}:{place.get(1, 0)};"
         f"util0={rep.utilization.get(0, 0.0):.2f};"
         f"util1={rep.utilization.get(1, 0.0):.2f}")


# ------------------------------------------- batched data path (gather)
@scenario("gather_sweep", gate=(
    Gate("gather_sweep.meter_reduction.b064", "ratio", min=5,
         note="batched path must cut arbiter calls >=5x at batch 64"),
))
def bench_gather_sweep() -> None:
    """Batched vs scalar LMB data path, batch 1 -> 256: per-page gather
    latency (us_per_call column) and arbiter round-trips, onboard-hit vs
    LMB-resident working sets.  The LMB-resident cells run a steady-state
    thrash (two working-set halves, onboard holds one): every gather is
    all-miss, so scalar pays 2 arbiter calls per page (fault read +
    eviction write-back) while the batched path coalesces the whole burst
    into one charge per expander link — the >=5x metering reduction the
    batched engine exists for."""
    import jax.numpy as jnp
    from repro.core import system_for
    from repro.core.metrics import Metrics

    shape = (64, 64)                      # 16 KiB pages
    calls_at_64 = {}
    for resident in ("onboard", "lmb"):
        for batch in (1, 2, 8, 32, 64, 128, 256):
            system = system_for("d0", host_id="h0", pool_gib=2,
                                page_bytes=1 << 16, metrics=Metrics())
            onboard = batch if resident == "lmb" else 2 * batch
            buf = system.buffer(
                name=f"gs.{resident}.{batch}", device_id="d0",
                page_shape=shape, dtype=jnp.float32,
                onboard_pages=onboard, lmb_chunk_pages=64,
                metrics=Metrics())
            pages = buf.append_pages(2 * batch)
            for p in pages:
                buf.write(p, jnp.full(shape, float(p)))
            half_a, half_b = pages[:batch], pages[batch:]
            if resident == "onboard":
                buf.read_many(half_a)     # warm: every gather below hits
            iters = min(max(4, 64 // batch), 16)
            for mode in ("scalar", "batched"):
                for it in range(2):       # warmup: compile both halves
                    tgt = (half_a if resident == "onboard" or it % 2 == 0
                           else half_b)
                    (buf.read_many(tgt) if mode == "batched"
                     else [buf.read(p) for p in tgt])
                c0 = system.fm.meter_calls()
                best = float("inf")       # min-of-iters: robust to noise
                for it in range(iters):
                    # lmb case alternates halves -> permanent all-miss
                    tgt = (half_a if resident == "onboard" or it % 2 == 0
                           else half_b)
                    t0 = time.perf_counter()
                    if mode == "scalar":
                        for p in tgt:
                            buf.read(p)
                    else:
                        buf.read_many(tgt)
                    best = min(best, time.perf_counter() - t0)
                calls = system.fm.meter_calls() - c0
                if resident == "lmb" and batch == 64:
                    calls_at_64[mode] = calls
                _row(f"gather_sweep.{resident}.b{batch:03d}.{mode}",
                     best / batch * 1e6,
                     f"meter_calls={calls};pages={iters * batch}")
            system.close()
    ratio = calls_at_64["scalar"] / max(calls_at_64["batched"], 1)
    _row("gather_sweep.meter_reduction.b064", 0.0,
         f"ratio={ratio:.1f};scalar={calls_at_64['scalar']};"
         f"batched={calls_at_64['batched']}")


# ------------------------------------------- burst-aware prefetch sweep
@scenario("prefetch_sweep", gate=(
    Gate("prefetch_sweep.gate.hidden", "hidden", min=0.5,
         note="compute-rich sequential prefetch must hide >=50% of "
              "LMB read latency"),
    Gate("prefetch_sweep.gate.hidden", "speedup", min=1.5,
         note="prefetch must beat demand paging per-page"),
    Gate("prefetch_sweep.gate.hidden", "rand_ratio", max=1.25,
         note="random access must stay at parity (prefetch can't help "
              "but must not hurt)"),
))
def bench_prefetch_sweep() -> None:
    """Burst-aware prefetch + overlap scheduling vs demand-only paging:
    depth x access pattern x compute intensity.  Each cell streams a
    scan over an LMB-resident working set; between reads the device
    computes for a fixed window (virtual link time advances, and the
    overlap scheduler sizes its admission budget to the window).  The
    us_per_call column is the MODELED exposed (demand) link wait per
    page — prefetch traffic admitted behind the compute window accrues
    to the hidden counter instead.  Reported per cell: hidden fraction
    (hidden / (hidden + exposed) link wait), fault count, prefetch
    burst/page/used/wasted/deferred counters, arbiter calls.  The
    ``gate.hidden`` summary row is what CI gates on: in the compute-rich
    sequential configuration prefetch must hide >= 50% of the LMB read
    latency, beat demand-only per-page effective latency, and keep
    random access at parity (prefetch can't help there, so it must not
    hurt)."""
    import jax.numpy as jnp
    from repro.core import system_for
    from repro.core.metrics import Metrics

    shape = (64, 64)                      # 16 KiB fp32 pages
    n_scan, n_warm = 144, 48              # LMB scan set + onboard slots
    n_pages = n_scan + n_warm
    windows = {"rich": 2e-3, "poor": 5e-7}
    rng = np.random.default_rng(0)
    rand_order = [int(p) for p in rng.permutation(n_scan)]
    cells = {}
    for compute, window in windows.items():
        for access in ("stride1", "stride2", "sched", "rand"):
            if access == "rand" and compute == "poor":
                continue                  # parity only needs one regime
            order = {
                "stride1": list(range(n_scan)),
                "stride2": list(range(0, n_scan, 2)),
                "sched": rand_order,      # exact knowledge, no stride
                "rand": rand_order,       # no knowledge at all
            }[access]
            for depth in (0, 16):
                metrics = Metrics()
                system = system_for("d0", host_id="h0", pool_gib=2,
                                    page_bytes=1 << 16, metrics=metrics)
                # the system's own link model (spec bandwidth + CXL
                # added latency), not a hand-built TierSpec
                overlap = (system.overlap_scheduler(compute_window_s=window)
                           if depth else None)
                buf = system.buffer(
                    name="pf", device_id="d0", page_shape=shape,
                    dtype=jnp.float32, onboard_pages=n_warm,
                    lmb_chunk_pages=16, prefetch_depth=depth,
                    overlap=overlap, metrics=metrics)
                pages = buf.append_pages(n_pages)
                for p in pages:
                    buf.write(p, jnp.full(shape, float(p), jnp.float32))
                for p in pages[n_scan:]:
                    buf.release(p)        # scan streams through free slots
                c0 = system.fm.meter_calls()
                w0 = buf.link_wait_s
                miss0 = metrics.tier("pf", "onboard").misses
                t0 = time.perf_counter()
                for i, p in enumerate(order):
                    system.fm.advance_links(window)     # compute runs
                    buf.note_compute_window(window, observed=False)
                    if access == "sched" and depth:
                        buf.schedule_prefetch(order[i:i + depth])
                    buf.read(p)
                    buf.release(p)        # streaming consumer moves on
                wall_us = (time.perf_counter() - t0) / len(order) * 1e6
                exposed = buf.link_wait_s - w0
                hidden = buf.prefetch_hidden_s
                faults = metrics.tier("pf", "onboard").misses - miss0
                calls = system.fm.meter_calls() - c0
                pf = buf.prefetch_stats()
                hf = hidden / (hidden + exposed) if hidden + exposed else 0.0
                cell_us = exposed / len(order) * 1e6
                cells[(compute, access, depth)] = (cell_us, hf)
                _row(f"prefetch_sweep.{compute}.{access}.d{depth:02d}",
                     cell_us,
                     f"hidden={hf:.2f};faults={faults};"
                     f"pf_bursts={pf['bursts']};pf_pages={pf['pages']};"
                     f"used={pf['used']};wasted={pf['wasted']};"
                     f"deferred={pf['deferred']};meter_calls={calls};"
                     f"wall_us={wall_us:.1f}")
                system.close()
    # summary gate row (CI: tools/check_bench_regression.py)
    demand_us, _ = cells[("rich", "stride1", 0)]
    pf_us, hf = cells[("rich", "stride1", 16)]
    speedup = demand_us / max(pf_us, 1e-9)
    rand_ratio = (cells[("rich", "rand", 16)][0]
                  / max(cells[("rich", "rand", 0)][0], 1e-9))
    _row("prefetch_sweep.gate.hidden", 0.0,
         f"hidden={hf:.3f};speedup={speedup:.1f};"
         f"rand_ratio={rand_ratio:.3f}")


# --------------------------------------------------- §4.1.2 locality sweep
@scenario("locality")
def bench_locality_sweep() -> None:
    """Hot-index hit ratio -> throughput recovery (paper §4.1.2 claim)."""
    from repro.sim import make_ssd_model, make_workload, simulate
    from repro.sim.ssd import Scheme, make_schemes
    spec = make_ssd_model(5)
    base = make_schemes(spec)["lmb-pcie"]
    wl = make_workload("randread", n_ios=60_000)
    ideal = simulate(spec, make_schemes(spec)["ideal"], wl).iops
    for hit in (0.0, 0.5, 0.8, 0.9, 0.95, 0.99):
        s = Scheme(base.name, base.t_tier_s, base.write_through_index,
                   onboard_hit_ratio=hit)
        r = simulate(spec, s, wl)
        _row(f"locality.gen5.randread.hit{int(hit*100):02d}", 0.0,
             f"kiops={r.iops/1e3:.0f};rel={r.iops/ideal:.3f}")


# ------------------------------------------------------ allocator (§3.2)
@scenario("allocator")
def bench_allocator() -> None:
    """alloc/free/share microbench on the capability client API."""
    from repro.core import (DeviceSpec, HostSpec, LMBSystem, SystemSpec)
    spec = SystemSpec(expanders=1, pool_gib=8,
                      hosts=(HostSpec("h0", page_bytes=4096),),
                      devices=(DeviceSpec("d0"), DeviceSpec("d1")))
    system = LMBSystem(spec)
    N = 2000
    rng = np.random.default_rng(0)
    sizes = rng.integers(1, 1 << 20, N)
    t0 = time.perf_counter()
    handles = [system.alloc("d0", int(s)) for s in sizes]
    t_alloc = (time.perf_counter() - t0) / N * 1e6
    t0 = time.perf_counter()
    for h in handles[:500]:
        h.share("d1")
    t_share = (time.perf_counter() - t0) / 500 * 1e6
    t0 = time.perf_counter()
    for h in handles:
        h.free()
    t_free = (time.perf_counter() - t0) / N * 1e6
    _row("allocator.alloc", t_alloc, f"n={N}")
    _row("allocator.share", t_share, "n=500")
    _row("allocator.free", t_free,
         f"blocks_left={system.host().allocator.block_count}")


# --------------------------------------- offload overlap (TPU adaptation)
@scenario("offload")
def bench_offload_overlap() -> None:
    """Bytes the LMB tier can page per step hidden behind compute (tier
    model), plus measured LinkedBuffer fault cost on this host."""
    import jax.numpy as jnp
    from repro.core import system_for
    from repro.core.metrics import Metrics
    from repro.core.tiers import TierKind, hideable_page_bytes, tpu_tiers
    host_tier = tpu_tiers()[TierKind.HOST_DRAM]
    for step_ms in (5.0, 20.0, 100.0):
        b = hideable_page_bytes(step_ms / 1e3, host_tier, streams=2)
        _row(f"offload.hideable.step{int(step_ms)}ms", 0.0,
             f"MiB={b/2**20:.0f}")
    system = system_for("d0", host_id="h0", pool_gib=2,
                        page_bytes=1 << 16, metrics=Metrics())
    buf = system.buffer(name="bench", device_id="d0",
                        page_shape=(256, 256), dtype=jnp.float32,
                        onboard_pages=4, metrics=Metrics())
    pages = buf.append_pages(16)
    for p in pages:
        buf.write(p, jnp.ones((256, 256)))
    t0 = time.perf_counter()
    n = 64
    for i in range(n):
        buf.read(pages[i % 16])  # forced paging traffic
    dt = (time.perf_counter() - t0) / n * 1e6
    _row("offload.page_fault", dt, "page=256KiB")


# ---------------------------------------------------- roofline (dry-run)
@scenario("roofline")
def bench_roofline_report() -> None:
    """Summarize dryrun_results.json (run launch/dryrun.py first)."""
    path = os.environ.get("DRYRUN_JSON", "dryrun_results.json")
    if not os.path.exists(path):
        _row("roofline.missing", 0.0, f"run launch/dryrun.py ({path})")
        return
    with open(path) as f:
        table = json.load(f)
    for key, rec in sorted(table.items()):
        if rec.get("status") != "ok":
            continue
        r = rec["roofline"]
        _row(f"roofline.{key}", r["compute_s"] * 1e6,
             f"dom={r['dominant']};mem_s={r['memory_s']:.3f};"
             f"coll_s={r['collective_s']:.3f};"
             f"mfu@roof={r['roofline_fraction']*100:.1f}%")


# ------------------------------------------------------------ serve perf
@scenario("serve")
def bench_serving() -> None:
    """Engine throughput on the reduced model (CPU demo scale)."""
    import jax
    from repro.configs.base import get_config
    from repro.core import system_for
    from repro.models import build_model
    from repro.models.flags import Flags
    from repro.serve import EngineConfig, ServeEngine, SubmitSpec
    cfg = get_config("qwen2-1.5b").reduced()
    model = build_model(cfg, Flags(remat=False))
    params = model.init(jax.random.key(0))
    system = system_for("tpu0", host_id="h0", pool_gib=2, page_bytes=4096)
    eng = ServeEngine(model, params, system, EngineConfig(
        decode_slots=4, max_seq_len=64, page_tokens=8, onboard_pages=8,
        prefill_bucket=16))
    rng = np.random.default_rng(0)
    n_req, n_tok = 8, 8
    for _ in range(n_req):
        eng.submit(SubmitSpec(
            prompt=rng.integers(0, cfg.vocab_size, 12),
            max_new_tokens=n_tok))
    t0 = time.perf_counter()
    eng.run(500)
    wall = time.perf_counter() - t0
    st = eng.stats()
    _row("serve.engine", wall / (n_req * n_tok) * 1e6,
         f"tok_per_s={n_req*n_tok/wall:.1f};"
         f"kv_hit={st['kv']['hit_ratio']:.2f}")


# ---------------------------------------------- trace-driven serve sweep
@scenario("serve_sweep", gate=(
    Gate("serve_sweep.gate.pipeline", "tokens_equal", min=1,
         note="pipelined step must emit byte-identical tokens to the "
              "phased reference order"),
    Gate("serve_sweep.gate.pipeline", "wait_ratio", min=1.2,
         note="pipelining must strictly reduce modeled exposed link "
              "wait vs the phased order"),
    Gate("serve_sweep.tenant.steady", "ttft_p99_ms", max=40,
         note="virtual-time TTFT p99 bound, Poisson tenant"),
    Gate("serve_sweep.tenant.steady", "itl_p99_ms", max=6,
         note="virtual-time inter-token p99 bound, Poisson tenant"),
    Gate("serve_sweep.tenant.bursty", "ttft_p99_ms", max=80,
         note="virtual-time TTFT p99 bound, bursty tenant (queueing "
              "under bursts is expected, but bounded)"),
    Gate("serve_sweep.tenant.bursty", "itl_p99_ms", max=6,
         note="virtual-time inter-token p99 bound, bursty tenant"),
))
def bench_serve_sweep() -> None:
    """Trace-driven multi-tenant load sweep on the serve engine: a
    Poisson tenant and a bursty tenant share one engine whose KV pages
    against the LMB pool.  The engine runs on a VIRTUAL clock with a
    pinned round duration, so every latency row (TTFT / inter-token
    p50/p99, straight from ``ServeEngine.stats()['latency']``) is a
    modeled, machine-independent figure CI can gate on.  A second,
    phased-order twin replays the identical trace to check the
    pipelined step's contract: byte-identical tokens, strictly less
    modeled exposed link wait.  ``SERVE_SWEEP_SCALE=N`` multiplies
    per-tenant request counts for offline full-scale runs."""
    import jax
    from repro.configs.base import get_config
    from repro.core import system_for
    from repro.core.metrics import Metrics
    from repro.models import build_model
    from repro.models.flags import Flags
    from repro.serve import (EngineConfig, ServeEngine, TenantLoad,
                             VirtualClock, build_trace, run_sweep)

    cfg = get_config("qwen2-1.5b").reduced()
    model = build_model(cfg, Flags(remat=False))
    params = model.init(jax.random.key(0))
    round_s = 2e-3

    def make_engine(clock, *, pipeline):
        # per-engine Metrics: the A/B twin must not share histograms
        system = system_for("tpu0", host_id="h0", pool_gib=1,
                            page_bytes=4096, metrics=Metrics())
        return ServeEngine(model, params, system, EngineConfig(
            decode_slots=4, max_seq_len=64, page_tokens=8,
            onboard_pages=6, prefill_bucket=16, pipeline=pipeline,
            round_time_s=round_s), clock=clock)

    scale = int(os.environ.get("SERVE_SWEEP_SCALE", "1"))
    tenants = [
        TenantLoad("steady", rate_rps=150.0, n_requests=12 * scale,
                   prompt_tokens=(12, 28), max_new_tokens=(4, 8)),
        TenantLoad("bursty", rate_rps=150.0, n_requests=12 * scale,
                   process="bursty", burst_size=6,
                   prompt_tokens=(12, 28), max_new_tokens=(4, 8)),
    ]
    trace = build_trace(tenants, vocab_size=cfg.vocab_size, seed=0)
    clock = VirtualClock()
    eng = make_engine(clock, pipeline=True)
    t0 = time.perf_counter()
    report = run_sweep(eng, trace, clock)
    wall_us = (time.perf_counter() - t0) * 1e6
    tot = report.totals
    for name, row in sorted(report.per_tenant.items()):
        _row(f"serve_sweep.tenant.{name}", 0.0,
             f"done={row['done']};shed={row['shed']};"
             f"ttft_p50_ms={row['ttft_p50_s'] * 1e3:.3f};"
             f"ttft_p99_ms={row['ttft_p99_s'] * 1e3:.3f};"
             f"itl_p50_ms={row['itl_p50_s'] * 1e3:.3f};"
             f"itl_p99_ms={row['itl_p99_s'] * 1e3:.3f}")
    _row("serve_sweep.totals", wall_us / max(tot["rounds"], 1),
         f"rounds={tot['rounds']};virtual_s={tot['virtual_s']:.3f};"
         f"done={tot['done']};shed={tot['shed']};"
         f"peak_concurrent={tot['peak_concurrent']};"
         f"peak_lmb_pages={tot['peak_lmb_resident_pages']};"
         f"exposed_us={tot['exposed_link_wait_s'] * 1e6:.2f};"
         f"hidden_us={tot['hidden_link_wait_s'] * 1e6:.2f};"
         f"kv_hit={tot['kv_hit_ratio']:.3f};"
         f"meter_calls={tot['meter_calls']}")
    # phased-order twin on the IDENTICAL trace: the pipelined step's
    # contract is byte-identical tokens with strictly less exposed wait
    clock2 = VirtualClock()
    eng2 = make_engine(clock2, pipeline=False)
    run_sweep(eng2, trace, clock2)
    toks = {r.req_id: tuple(r.out_tokens) for r in eng.requests.values()}
    toks2 = {r.req_id: tuple(r.out_tokens) for r in eng2.requests.values()}
    exposed_pipe = eng.kv.buf.link_wait_s
    exposed_phased = eng2.kv.buf.link_wait_s
    _row("serve_sweep.gate.pipeline", 0.0,
         f"tokens_equal={int(toks == toks2)};"
         f"wait_ratio={exposed_phased / max(exposed_pipe, 1e-12):.2f};"
         f"exposed_pipelined_us={exposed_pipe * 1e6:.2f};"
         f"exposed_phased_us={exposed_phased * 1e6:.2f}")


# ------------------------------------------ paged-decode kernel sweep
@scenario("decode_sweep", gate=(
    Gate("decode_sweep.gate.identity", "tokens_equal", min=1,
         note="paged decode must emit byte-identical token streams to "
              "the dense slot-cache reference engine"),
    Gate("decode_sweep.gate.identity", "paged_rounds", min=1,
         note="the paged pool-direct rounds actually served the decode "
              "(not a silent fallback to the dense path)"),
    Gate("decode_sweep.gate.identity", "kernel_traced", min=1,
         note="the paged-attention decode dispatcher was staged into "
              "the compiled step (call-path proof)"),
    Gate("decode_sweep.gate.traffic", "bytes_reconciled", min=1,
         note="per-class link.xfer span bytes reconcile exactly with "
              "fm.op_bytes() — the DecodeView's page traffic rides the "
              "same metered accounting as every other access"),
    Gate("decode_sweep.cell.b4.s24", "tok_per_s", min=1000,
         note="modeled decode throughput (virtual-time) at batch 4"),
    Gate("decode_sweep.cell.b1.s8", "tok_per_s", min=300,
         note="modeled decode throughput (virtual-time) at batch 1"),
))
def bench_decode_sweep() -> None:
    """Batch x sequence-length sweep of the paged decode path: every
    round is ONE batched paged-attention step straight against the
    paged KV pool (DecodeView), timed on a VIRTUAL clock with a pinned
    round duration so tokens/s is a modeled, machine-independent
    figure.  Two gate rows ride along: an identity cell re-serving the
    largest configuration with ``paged_decode=False`` (byte-identical
    tokens, paged rounds > 0, kernel dispatcher on the call path) and a
    traffic cell reconciling the paged rounds' ``link.xfer`` spans
    against ``fm.op_bytes()`` per accounting class."""
    import jax
    from repro.configs.base import get_config
    from repro.core import system_for
    from repro.core.metrics import Metrics
    from repro.kernels import ops as kops
    from repro.models import build_model
    from repro.models.flags import Flags
    from repro.serve import (EngineConfig, ServeEngine, SubmitSpec,
                             VirtualClock)

    cfg = get_config("qwen2-1.5b").reduced()
    model = build_model(cfg, Flags(remat=False))
    params = model.init(jax.random.key(0))
    round_s = 2e-3
    max_new = 8

    def serve(batch, prompt_len, *, paged, trace=False):
        clock = VirtualClock()
        system = system_for("tpu0", host_id="h0", pool_gib=1,
                            page_bytes=4096, metrics=Metrics())
        eng = ServeEngine(model, params, system, EngineConfig(
            decode_slots=batch, max_seq_len=64, page_tokens=8,
            onboard_pages=6, prefill_bucket=16, round_time_s=round_s,
            paged_decode=paged, trace=trace), clock=clock)
        rng = np.random.default_rng(0)
        rids = [eng.submit(SubmitSpec(
            prompt=rng.integers(0, cfg.vocab_size, prompt_len),
            max_new_tokens=max_new)) for _ in range(batch * 2)]
        it = 0
        while (eng.waiting or eng.active) and it < 500:
            eng.step()
            clock.advance(round_s)
            it += 1
        toks = {r: tuple(eng.requests[r].out_tokens) for r in rids}
        return eng, toks, clock.now

    for batch in (1, 4):
        for plen in (8, 24):
            t0 = time.perf_counter()
            eng, toks, virtual_s = serve(batch, plen, paged=True)
            wall_us = (time.perf_counter() - t0) * 1e6
            n_tok = sum(len(t) for t in toks.values())
            st = eng.stats()
            _row(f"decode_sweep.cell.b{batch}.s{plen}",
                 wall_us / max(n_tok, 1),
                 f"tok_per_s={n_tok / virtual_s:.1f};"
                 f"rounds={st['paged_rounds']};"
                 f"kv_hit={st['kv']['hit_ratio']:.3f};"
                 f"meter_calls={st['fabric']['meter_calls']}")

    # identity + call-path gate: the largest cell, paged vs dense twin
    from repro.obs.trace import GLOBAL_TRACER
    before = kops.paged_attention_decode_traces()
    # under --trace the engine reuses the harness's enabled global
    # tracer, so remember where this run's spans start in the ring
    pre = len(GLOBAL_TRACER.spans()) if GLOBAL_TRACER.enabled else 0
    eng_p, toks_p, _ = serve(4, 24, paged=True, trace=True)
    traced = kops.paged_attention_decode_traces() - before
    # snapshot the paged run's span window BEFORE the dense twin runs
    # (it records into the same shared ring under --trace)
    spans = eng_p.trace.spans()
    if eng_p.trace is GLOBAL_TRACER:
        spans = spans[pre:]
    eng_d, toks_d, _ = serve(4, 24, paged=False)
    _row("decode_sweep.gate.identity", 0.0,
         f"tokens_equal={int(toks_p == toks_d)};"
         f"paged_rounds={eng_p.paged_rounds};"
         f"kernel_traced={traced};"
         f"dense_paged_rounds={eng_d.paged_rounds}")
    # traffic gate: the traced paged run's per-class link bytes
    by_op: Dict[str, int] = {}
    for sp in spans:
        if sp.name == "link.xfer":
            by_op[sp.op] = by_op.get(sp.op, 0) + sp.nbytes
    fm_bytes = eng_p.kv.buf.host.fm.op_bytes()
    reconciled = int(bool(by_op) and by_op == fm_bytes)
    _row("decode_sweep.gate.traffic", 0.0,
         f"bytes_reconciled={reconciled};"
         f"link_bytes={sum(by_op.values())};"
         f"classes={len(by_op)}")


# ------------------------------------------- chaos (repro.core.faults)
@scenario("chaos_sweep", gate=(
    Gate("chaos_sweep.gate.storm", "availability", min=0.99,
         note="with link-level retry enabled, a scripted transient-fault "
              "storm (CRC-error window + brownout + link flap) costs "
              "modeled time only: >=99% of requests still complete"),
    Gate("chaos_sweep.gate.storm", "noretry_lost", min=1,
         note="the identical storm with retries DISABLED escalates to "
              "failover and measurably loses work — proving the retry "
              "path, not storm mildness, earned the availability gate"),
    Gate("chaos_sweep.gate.storm", "retry_reconciled", min=1,
         note="injector retry_bytes reconcile exactly with the FM's "
              "op_bytes()['retry'] accounting class"),
    Gate("chaos_sweep.gate.repair", "recovery", min=0.9,
         note="after fail-stop + repair/re-admission, >=90% of requests "
              "arriving post-repair complete (degraded mode exits)"),
    Gate("chaos_sweep.gate.identity", "identical", min=1,
         note="a zero-fault FaultPlan run is byte-identical (tokens and "
              "per-class fm.op_bytes()) to a run with no injector"),
))
def bench_chaos_sweep() -> None:
    """Chaos drill on the serve engine: the same trace-driven sweep as
    ``serve_sweep``, but with a :class:`~repro.core.faults.FaultInjector`
    scripting fault storms against the (single) expander link.

    Four runs, three gates:

      1. **storm + retries** — transient CRC-error window, a brownout,
         and a link flap land mid-trace; bounded backoff + retransmission
         turns them into modeled time and availability stays >= 0.99.
      2. **storm, retries disabled** — the first CRC error escalates to
         the fail-stop path; the pool dies, KV paging degrades to
         onboard-only, and capacity cancellations lose real work.
      3. **fail-stop + repair** — the expander is killed, then readmitted
         blank; requests arriving after the repair complete (>= 90%),
         pinning the degraded-mode EXIT path.
      4. **zero-fault identity** — an attached-but-empty plan must be
         byte-identical to no injector at all (tokens, op_bytes).

    Everything runs on the virtual clock, so every figure is modeled and
    machine-independent."""
    import jax
    from repro.configs.base import get_config
    from repro.core import FaultEvent, FaultPlan, RetryPolicy, system_for
    from repro.core.metrics import Metrics
    from repro.models import build_model
    from repro.models.flags import Flags
    from repro.serve import (EngineConfig, ServeEngine, TenantLoad,
                             VirtualClock, build_trace, run_sweep)

    cfg = get_config("qwen2-1.5b").reduced()
    model = build_model(cfg, Flags(remat=False))
    params = model.init(jax.random.key(0))
    round_s = 2e-3

    def make_engine(clock, *, plan=None, retry=None):
        system = system_for("tpu0", host_id="h0", pool_gib=1,
                            page_bytes=4096, metrics=Metrics())
        injector = (system.attach_fault_injector(plan, retry=retry, seed=7)
                    if plan is not None else None)
        eng = ServeEngine(model, params, system, EngineConfig(
            decode_slots=4, max_seq_len=64, page_tokens=8,
            onboard_pages=6, prefill_bucket=16, pipeline=True,
            round_time_s=round_s), clock=clock)
        return eng, system, injector

    scale = int(os.environ.get("SERVE_SWEEP_SCALE", "1"))
    tenants = [
        TenantLoad("steady", rate_rps=150.0, n_requests=12 * scale,
                   prompt_tokens=(12, 28), max_new_tokens=(4, 8),
                   deadline_s=5.0),
        TenantLoad("bursty", rate_rps=150.0, n_requests=12 * scale,
                   process="bursty", burst_size=6,
                   prompt_tokens=(12, 28), max_new_tokens=(4, 8),
                   deadline_s=5.0),
    ]
    trace = build_trace(tenants, vocab_size=cfg.vocab_size, seed=0)
    t_end = max(s.arrival_time_s for s in trace)

    # ---- run 1+2: the storm, with and without link-level retry --------
    def storm_plan():
        return FaultPlan((
            FaultEvent(t_s=0.1 * t_end, kind="transient",
                       duration_s=0.8 * t_end, error_rate=0.35,
                       crc_retry_cost_s=2e-6),
            FaultEvent(t_s=0.3 * t_end, kind="brownout",
                       duration_s=0.3 * t_end, latency_factor=4.0),
            FaultEvent(t_s=0.6 * t_end, kind="link_flap",
                       retrain_s=2 * round_s),
        ))

    clock = VirtualClock()
    eng, system, inj = make_engine(
        clock, plan=storm_plan(),
        retry=RetryPolicy(link_retry_budget=100_000))
    t0 = time.perf_counter()
    report = run_sweep(eng, trace, clock, drain_idle_gaps=True)
    wall_us = (time.perf_counter() - t0) * 1e6
    tot = report.totals
    ctr = inj.counters()
    availability = tot["done"] / max(tot["requests"], 1)
    reconciled = int(ctr["retry_bytes"]
                     == system.fm.op_bytes().get("retry", 0))
    _row("chaos_sweep.storm.retry", wall_us / max(tot["rounds"], 1),
         f"done={tot['done']};cancelled={tot['cancelled']};"
         f"shed={tot['shed']};errors={ctr['transient_errors']};"
         f"retries={ctr['retries']};"
         f"retry_delay_us={ctr['retry_delay_s'] * 1e6:.2f};"
         f"brownout_delay_us={ctr['brownout_delay_s'] * 1e6:.2f};"
         f"flap_delay_us={ctr['flap_delay_s'] * 1e6:.2f};"
         f"escalations={ctr['escalations']}")

    clock2 = VirtualClock()
    eng2, system2, inj2 = make_engine(clock2, plan=storm_plan(),
                                      retry=RetryPolicy(max_retries=0))
    report2 = run_sweep(eng2, trace, clock2, drain_idle_gaps=True)
    tot2 = report2.totals
    lost = tot2["requests"] - tot2["done"]
    _row("chaos_sweep.storm.noretry", 0.0,
         f"done={tot2['done']};cancelled={tot2['cancelled']};"
         f"lost={lost};"
         f"escalations={inj2.counters()['escalations']};"
         f"healthy={int(system2.fm.healthy)}")
    _row("chaos_sweep.gate.storm", 0.0,
         f"availability={availability:.4f};noretry_lost={lost};"
         f"retry_reconciled={reconciled}")

    # ---- run 3: fail-stop then repair/re-admission --------------------
    clock3 = VirtualClock()
    # the plan targets the system's own expander id, so build the system
    # first, then the plan, then attach
    system3 = system_for("tpu0", host_id="h0", pool_gib=1,
                         page_bytes=4096, metrics=Metrics())
    eid = sorted(system3.fm.expander_ids)[0]
    t_fail, t_repair = 0.25 * t_end, 0.55 * t_end
    plan3 = FaultPlan((
        FaultEvent(t_s=t_fail, kind="fail_stop", expander_id=eid),
        FaultEvent(t_s=t_repair, kind="repair", expander_id=eid),
    ))
    inj3 = system3.attach_fault_injector(plan3, seed=7)
    eng3 = ServeEngine(model, params, system3, EngineConfig(
        decode_slots=4, max_seq_len=64, page_tokens=8,
        onboard_pages=6, prefill_bucket=16, pipeline=True,
        round_time_s=round_s), clock=clock3)
    report3 = run_sweep(eng3, trace, clock3, drain_idle_gaps=True)
    after = [r for r in eng3.requests.values()
             if r.submitted_at >= t_repair]
    done_after = sum(1 for r in after if r.state == "done")
    recovery = done_after / max(len(after), 1)
    tot3 = report3.totals
    _row("chaos_sweep.repair", 0.0,
         f"done={tot3['done']};cancelled={tot3['cancelled']};"
         f"arrived_after_repair={len(after)};done_after={done_after};"
         f"healthy={int(system3.fm.healthy)}")
    _row("chaos_sweep.gate.repair", 0.0,
         f"recovery={recovery:.4f};repaired={int(system3.fm.healthy)}")

    # ---- run 4: zero-fault plan is byte-identical to no injector ------
    clock4 = VirtualClock()
    eng4, system4, _ = make_engine(clock4, plan=FaultPlan())
    run_sweep(eng4, trace, clock4, drain_idle_gaps=True)
    clock5 = VirtualClock()
    eng5, system5, _ = make_engine(clock5)
    run_sweep(eng5, trace, clock5, drain_idle_gaps=True)
    toks4 = {r.req_id: tuple(r.out_tokens) for r in eng4.requests.values()}
    toks5 = {r.req_id: tuple(r.out_tokens) for r in eng5.requests.values()}
    ob4, ob5 = dict(system4.fm.op_bytes()), dict(system5.fm.op_bytes())
    identical = int(toks4 == toks5 and ob4 == ob5)
    _row("chaos_sweep.gate.identity", 0.0,
         f"identical={identical};tokens_equal={int(toks4 == toks5)};"
         f"op_bytes_equal={int(ob4 == ob5)}")


# ------------------------------------------------ rack-scale (repro.rack)
@scenario("rack_sweep", gate=(
    Gate("rack_sweep.hop.monotone", "monotone", min=1,
         note="p99 must grow (weakly) with fabric path latency: the "
              "topology hop cost feeds the index path end to end"),
    Gate("rack_sweep.placement.gate", "skew_over_pool", min=1.15,
         note="pool-aware placement (near-first, capacity-balanced via "
              "the real FM policy) beats piling every device on one "
              "cross-leaf link by >=15% p99"),
    Gate("rack_sweep.failover.gate", "recovery", min=0.9,
         note="after a domain-wide failure, plan_rebalance(alive=...) "
              "recovers >=90% of the pile-up p99 gap vs the balanced-"
              "survivor baseline"),
    Gate("rack_sweep.failover.gate", "lost", max=0,
         note="domain failover re-grants every block (survivors have "
              "room); losing any means the single-pass re-grant broke"),
    Gate("rack_sweep.failover.gate", "regranted", min=8,
         note="all 8 blocks homed on the dead pd0 domain re-granted"),
    Gate("rack_sweep.scale.d16", "requests", min=1_048_576,
         note="rack-scale reach: 256 devices x 4096 IOs in ONE "
              "vectorized call"),
    Gate("rack_sweep.scale.d16", "wall_s", max=60,
         note="CI wall-clock budget for the 1M-request run (locally "
              "~0.04 s; the bound only catches a vectorization "
              "regression back to per-IO Python)"),
    Gate("rack_sweep.speedup.gate", "speedup", min=20,
         note="vectorized core >=20x the scalar reference engine on the "
              "same 256-lane scenario (a wall-clock RATIO, so it is "
              "machine-independent to first order; measured 23-27x)"),
    Gate("rack_sweep.speedup.gate", "results_agree", min=1,
         note="scalar and vectorized engines produce identical per-lane "
              "p99s (rtol 1e-6) on the speedup scenario"),
))
def bench_rack_sweep() -> None:
    """Rack-scale CXL pool: hop costs, placement, correlated failover,
    and the vectorized event core's scale/speedup envelope."""
    from repro.rack import scenarios as rack

    hops = rack.hop_cost_sweep()
    for r in hops:
        _row(f"rack_sweep.hop.{r['case']}", r["p99_us"],
             f"hops={r['hops']};path_ns={r['path_ns']:.0f};"
             f"kiops={r['kiops']:.0f};mean_us={r['mean_us']:.2f}")
    p99s = [r["p99_us"] for r in hops]
    _row("rack_sweep.hop.monotone", 0.0,
         f"monotone={int(all(a <= b + 1e-9 for a, b in zip(p99s, p99s[1:])))}"
         f";span_us={p99s[-1] - p99s[0]:.2f}")

    face = rack.placement_face_off()
    for name in ("skewed", "spread", "pool_aware"):
        c = face[name]
        _row(f"rack_sweep.placement.{name}", c["p99_us"],
             f"kiops={c['kiops_total']:.0f};rho_max={c['rho_max']:.2f}")
    _row("rack_sweep.placement.gate", 0.0,
         f"skew_over_pool={face['p99_ratio_skew_over_pool']:.3f};"
         f"near_fraction={face['near_fraction_pool_aware']:.2f}")

    fo = rack.failover_recovery()
    _row("rack_sweep.failover.gate", fo["pileup_p99_us"],
         f"recovery={fo['recovery']:.3f};"
         f"baseline_us={fo['baseline_p99_us']:.2f};"
         f"rebalanced_us={fo['rebalanced_p99_us']:.2f};"
         f"regranted={fo['regranted']};lost={fo['lost']};"
         f"moved={fo['moved_devices']}")

    ss = rack.scale_sweep()
    for per, d in sorted(ss["density"].items()):
        _row(f"rack_sweep.scale.d{per}", d["p99_us"],
             f"devices={d['devices']};requests={d['requests']};"
             f"wall_s={d['wall_s']:.3f};rho_max={d['rho_max']:.2f};"
             f"agg_GBps={d['agg_GBps']:.0f}")

    vs = rack.vector_speedup()
    _row("rack_sweep.speedup.gate", vs["vector_s"] * 1e6,
         f"speedup={vs['speedup']:.1f};scalar_s={vs['scalar_s']:.3f};"
         f"vector_s={vs['vector_s']:.3f};"
         f"results_agree={int(vs['results_agree'])};"
         f"requests={vs['requests']}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma-separated subset of {sorted(SCENARIOS)}")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (CI perf artifact)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record spans across all benches and write a "
                    "Chrome-trace JSON (open in ui.perfetto.dev; "
                    "inspect with tools/lmbtrace.py)")
    args, _ = ap.parse_known_args()
    names = (args.only.split(",") if args.only else list(SCENARIOS))
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        ap.error(f"unknown scenario(s) {unknown}; choose from "
                 f"{sorted(SCENARIOS)}")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.trace:
        from repro.obs import enable_tracing
        enable_tracing()
    print("name,us_per_call,derived")
    for n in names:
        SCENARIOS[n].fn()
    if args.trace:
        from repro.obs import GLOBAL_TRACER
        from repro.obs.export import write_chrome_trace
        write_chrome_trace(GLOBAL_TRACER.spans(), args.trace,
                           extra={"benches": names,
                                  "dropped": GLOBAL_TRACER.dropped})
        print(f"# wrote {GLOBAL_TRACER.snapshot()['count']} spans to "
              f"{args.trace}", file=sys.stderr)
    if args.json:
        payload = {
            "benches": names,
            "python": platform.python_version(),
            "platform": platform.platform(),
            "rows": _ROWS,
            # every gate the scenarios that RAN declared — the checker
            # enforces these generically (no hand-wired keys)
            "gates": [dataclasses.asdict(g) for n in names
                      for g in SCENARIOS[n].gates],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(_ROWS)} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
