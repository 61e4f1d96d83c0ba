"""Run one benchmark cell once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``) in ``BENCHMARK.json``.
The run:

1. set-up (``setup_s``, from process start): weights made on the device
   from the seed; the cell's own traffic replayed on an engine of its
   own (:func:`replay`), which builds every program the run will use;
   then a fresh ``ServeEngine`` runs the same traffic from the start
   for ``warmup_s``;
2. the window: ``--seconds`` more of that traffic through
   ``ServeEngine.submit`` / ``step``.  A token is delivered when the
   ``step()`` that made it returns (the step syncs on the logits), and
   its time is read on the host clock then;
3. with ``--trace 1``, the first ``trace_s`` of the window under the
   profiler, reduced to the per-layer metrics by ``bench/metrics/``;
4. the check (:func:`judge`): no request failed, nothing was built in
   the window, and a sample of the requests finished in the window,
   drawn from the seed and holding the longest, lies within the cell's
   limit of the float32 reference of the configuration's architecture
   module (``bench/arch/<arch>.py``), run once the program's state is
   freed.

The last line of standard output is the result; the checks, each
number beside its limit, are the last lines of standard error.  Off a
TPU, or with fewer chips than the cell asks for, the run prints no
result and exits 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from bench import reference, traffic  # noqa: E402
from bench import trace as btrace  # noqa: E402

#: JAX's event for building an executable (a persistent-cache hit too)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: set-up replays the run's traffic for this many times the warm-up and
#: the window (counting only steps that built no program)
REPLAY_MARGIN = 1.2
#: capacity of the LMB tier (pinned host memory): the KV of every cell
LMB_POOL_GIB = 4
#: where a traced run's profile is written, read and deleted
TRACE_DIR = os.path.join(ROOT, ".bench_runs", "trace")
#: what every architecture module (``bench/arch/<arch>.py``) provides
ARCH_API = ("stated", "served_params", "logits", "decode_token_flops",
            "prefill_flops", "paged_kernel_cost", "paged_layers")


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than it needs."""


# ------------------------------------------------------------------ cell
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    arch: ModuleType            # the configuration's architecture module


def _load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _load_module(name: str, path: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_arch(config: Dict, config_file: str,
              bench_dir: str = BENCH) -> ModuleType:
    """The module ``<bench_dir>/arch/<arch>.py`` that the configuration
    (read from ``config_file``) names, with every function of
    ``ARCH_API``."""
    name = config.get("arch")
    if not isinstance(name, str) or not name:
        raise ValueError(f"{config_file}: names no architecture module "
                         "(key \"arch\")")
    path = os.path.join(bench_dir, "arch", name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"{config_file}: architecture {name!r} has no "
                         f"module {path}")
    mod = _load_module(f"bench_arch_{name}", path)
    missing = [f for f in ARCH_API if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"{config_file}: architecture module {path} "
                         f"lacks {missing}")
    return mod


def load_cell(name: str, spec: Optional[Dict] = None,
              bench_dir: str = BENCH) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files."""
    spec = spec or _load_json(ROOT, "BENCHMARK.json")
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    config_file = os.path.join(bench_dir, "configs", w["config"] + ".json")
    config = _load_json(config_file)
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    shown = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in shown]
    return Cell(name=name, chips=w["chips"], config=config,
                mix=_load_json(bench_dir, "traffic", w["traffic"] + ".json"),
                limits=_load_json(bench_dir, "limits", name + ".json"),
                end_to_end=e2e, per_layer=per_layer,
                arch=load_arch(config, config_file, bench_dir))


# --------------------------------------------------------------- program
def build_program(cell: Cell, seed: int):
    """The model as the configuration states it, with weights made on
    the device from the seed; checks that the program's parameter tree
    has the benchmark's shapes and types."""
    import jax
    from repro.configs.base import get_config
    from repro.models import build_model
    from repro.models.flags import Flags

    c = cell.config
    cfg = dataclasses.replace(get_config(c["registered"]),
                              **c.get("overrides", {}))
    wrong = {k: (getattr(cfg, k), v) for k, v in cell.arch.stated(c).items()
             if getattr(cfg, k) != v}
    if wrong:
        raise ValueError(f"{c['name']}: the program's config departs from "
                         f"the stated one: {wrong}")
    model = build_model(cfg, Flags(remat=False))
    params = cell.arch.served_params(c, traffic.seed_parts(seed),
                                     cfg.padded_vocab)
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if (jax.tree_util.tree_structure(want)
            != jax.tree_util.tree_structure(got)
            or jax.tree_util.tree_leaves(want)
            != jax.tree_util.tree_leaves(got)):
        raise ValueError("the program's parameter tree has changed: "
                         f"{want} != {got}")
    jax.block_until_ready(params)
    return cfg, model, params


def engine_config(cell: Cell, plan: traffic.Plan):
    from repro.serve import EngineConfig
    mix = cell.mix
    T = mix["page_tokens"]
    return EngineConfig(
        decode_slots=mix["decode_slots"],
        max_seq_len=int(plan.prompt_lens.max() + plan.output_lens.max()),
        page_tokens=T, onboard_pages=traffic.onboard_pages(mix, T))


# ---------------------------------------------------------------- driver
@dataclasses.dataclass
class Req:
    sent: float                 # host clock: when it was submitted
    prompt_len: int
    times: List[float] = dataclasses.field(default_factory=list)
    state: str = "waiting"


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    contexts: List[int]         # tokens attended by each decoded row
    prefills: int               # requests that left the queue


class Driver:
    """Offers the plan's requests to the engine as a closed loop (each
    client sends its next request when its last one finishes) and
    records when each token was delivered."""

    def __init__(self, eng, plan: traffic.Plan, mix: Dict,
                 clock=time.monotonic):
        self.eng, self.plan, self.clock = eng, plan, clock
        self.idle_clients = mix["clients"]
        self.next = 0
        self.reqs: Dict[int, Req] = {}
        self.live: List[int] = []
        self.steps: List[Step] = []

    def offer(self) -> None:
        from repro.serve import SubmitSpec
        n = len(self.plan.prompts)
        while self.idle_clients > 0:
            i = self.next % n
            prompt = self.plan.prompts[i]
            rid = self.eng.submit(SubmitSpec(
                prompt=prompt, max_new_tokens=int(self.plan.output_lens[i])))
            self.reqs[rid] = Req(self.clock(), len(prompt))
            self.live.append(rid)
            self.next += 1
            self.idle_clients -= 1

    def step(self) -> None:
        self.offer()
        t0 = self.clock()
        self.eng.step()
        t1 = self.clock()
        contexts: List[int] = []
        prefills = 0
        still: List[int] = []
        for rid in self.live:
            req = self.eng.requests[rid]
            rec = self.reqs[rid]
            for k in range(len(rec.times), len(req.out_tokens)):
                rec.times.append(t1)
                if k == 0:
                    prefills += 1
                else:
                    contexts.append(rec.prompt_len + k)
            if req.state in ("done", "cancelled", "shed"):
                rec.state = req.state
                self.idle_clients += 1
            else:
                still.append(rid)
        self.live = still
        self.steps.append(Step(t0, t1, contexts, prefills))

    def run_until(self, t_end: float) -> None:
        """Step until the host clock passes ``t_end``."""
        while self.clock() < t_end:
            self.step()


# ---------------------------------------------------------------- warm-up
def replay(eng, plan: traffic.Plan, mix: Dict, seconds: float,
           compiles: "CompileCounter") -> List[Step]:
    """Set-up's pass over the run's own traffic, on an engine of its
    own, so that every program the warm-up and the window will run is
    built before the window opens.

    The engine's work is a function of the plan and the step count
    alone (a closed loop submits on completion, and the LMB tier's
    paging is deterministic), so the measured engine, started from the
    same empty state, meets the same shapes in the same order: the
    prompt lengths of its prefills, the page bursts of its faults,
    evictions and write-backs, the pool sizes of its decode steps.  The
    pass runs until the steps that built nothing add up to
    ``REPLAY_MARGIN`` times the warm-up and the window, more steps than
    the measured engine can reach in that time."""
    driver = Driver(eng, plan, mix)
    need = REPLAY_MARGIN * (mix["warmup_s"] + seconds)
    clean = 0.0
    while clean < need:
        built = len(compiles.ends)
        driver.step()
        if len(compiles.ends) == built:
            clean += driver.steps[-1].t1 - driver.steps[-1].t0
    return driver.steps


def first_divergence(a: List[Step], b: List[Step]) -> Optional[int]:
    """The first step at which two passes over one plan decoded other
    contexts or prefilled other counts, if any."""
    for k, (x, y) in enumerate(zip(a, b)):
        if x.contexts != y.contexts or x.prefills != y.prefills:
            return k
    return None


# -------------------------------------------------------------- counters
class CompileCounter:
    """Host-clock end times, and the seconds, of every executable JAX
    built."""

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.ends: List[float] = []
        self.seconds = 0.0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.ends.append(self.clock())
            self.seconds += secs

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.ends if a <= t <= b)


class ByteMeter:
    """Counts the bytes the KV store's ``TierExecutor`` moves across the
    host link (its ``meter`` hook: every LMB-tier page read or
    written)."""

    def __init__(self):
        self.total = 0

    def __call__(self, nbytes: int) -> None:
        self.total += int(nbytes)


class HostSpans:
    """Wraps engine calls in profiler annotations and host timers."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.seconds: Dict[str, float] = {}
        self.calls: Dict[str, int] = {}

    def wrap(self, name: str, fn):
        import jax

        def wrapped(*a, **k):
            t = self.clock()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*a, **k)
            self.seconds[name] = self.seconds.get(name, 0.0) + self.clock() - t
            self.calls[name] = self.calls.get(name, 0) + 1
            return out
        return wrapped

    def install(self, eng) -> None:
        eng._admit = self.wrap("engine.admit", eng._admit)
        eng.kv.append_tokens = self.wrap("kv.append_tokens",
                                         eng.kv.append_tokens)
        eng.kv.decode_view = self.wrap("kv.decode_view", eng.kv.decode_view)
        eng.kv.commit_decode = self.wrap("kv.commit_decode",
                                         eng.kv.commit_decode)
        eng._prefill_fn = self.wrap("model.prefill", eng._prefill_fn)
        eng._paged_fn = self.wrap("model.decode_step_paged", eng._paged_fn)


# ------------------------------------------------------------------ data
@dataclasses.dataclass
class RunData:
    """What the per-layer metric readers read."""

    cell: Cell
    steps: List[Step]              # steps of the window
    traced_steps: List[Step]       # steps inside the traced window
    window_s: float
    out_tokens: int
    compiles: int
    link_bytes: int
    spans: Optional[HostSpans]
    red: Optional[Dict]            # trace reduction
    peaks: Optional[Dict]


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation), as numpy gives it."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def sample(driver: Driver, eng, mix_check: Dict, seed: int, w0: float,
           w1: float) -> List[tuple]:
    """(prompt, served tokens) of a sample of the requests finished in
    the window: the one with the most served tokens, then others drawn
    from the seed until the sample holds ``tokens`` served tokens or
    ``requests`` requests."""
    done = [rid for rid, r in driver.reqs.items()
            if r.state == "done" and r.times and w0 < r.times[-1] <= w1]
    done.sort(key=lambda rid: (-len(eng.requests[rid].out_tokens), rid))
    if not done:
        return []
    rng = np.random.default_rng(list(traffic.seed_parts(seed)) + [7])
    rest = [done[i] for i in rng.permutation(len(done) - 1) + 1]
    chosen, tokens = [], 0
    for rid in [done[0]] + rest:
        if tokens >= mix_check["tokens"] or len(chosen) >= mix_check["requests"]:
            break
        req = eng.requests[rid]
        chosen.append((np.asarray(req.prompt, np.int32),
                       np.asarray(req.out_tokens, np.int64)))
        tokens += len(req.out_tokens)
    return chosen


def widest_gaps(cell: Cell, seed: int, chosen: List[tuple],
                control: bool = False) -> Dict[str, float]:
    """The widest gap by which a token lies below the float32
    reference's best logit at its position, over every sampled request:
    for the served tokens (``served``) and, with ``control``, for the
    first choices of the fp8 control at the same positions
    (``control``).  A token outside the vocabulary reads ``inf``."""
    seqs = [np.concatenate([p, t[:-1].astype(np.int32)]) for p, t in chosen]
    starts = [len(p) - 1 for p, _ in chosen]
    arch, c, sp = cell.arch, cell.config, traffic.seed_parts(seed)
    ref = arch.logits(c, sp, seqs, starts)
    out = {"served": max(float(reference.gaps(r, t).max())
                         for r, (_, t) in zip(ref, chosen))}
    if control:
        ctl = arch.logits(c, sp, seqs, starts, control=True)
        out["control"] = max(float(reference.gaps(r, q.argmax(1)).max())
                             for r, q in zip(ref, ctl))
    return out


#: checks whose number has to reach its limit; every other check's
#: number has to stay at or under it
AT_LEAST = ("sampled_tokens",)


def judge(limits: Dict, failed: int, compiles: Optional[int], tokens: int,
          gap: Optional[float]) -> tuple:
    """``correct`` and the checks behind it, each number beside its
    limit: no request failed, nothing was built in the window (not
    judged where ``compiles`` is None), the sample holds enough served
    tokens, and no sampled token lies further below the reference's
    best than the cell's limit."""
    checks = {"failed": {"value": failed, "limit": 0}}
    if compiles is not None:
        checks["compiles_in_window"] = {"value": compiles, "limit": 0}
    checks["sampled_tokens"] = {"value": tokens,
                                "limit": limits["sample"]["min_tokens"]}
    if gap is not None and not np.isfinite(gap):
        gap = None                    # a token outside the vocabulary
    checks["max_logit_gap"] = {"value": gap,
                               "limit": limits["max_logit_gap"]}
    ok = all(c["value"] is not None
             and (c["value"] >= c["limit"] if k in AT_LEAST
                  else c["value"] <= c["limit"])
             for k, c in checks.items())
    return ok, checks


# ------------------------------------------------------------------- run
def load_peaks(kind: str) -> Dict:
    peaks = _load_json(BENCH, "peaks.json")
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, found {len(devs)}")
    return devs


def metric_reader(name: str):
    return _load_module(f"bench_metric_{name}",
                        os.path.join(BENCH, "metrics", name + ".py")).read


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, tracing: bool,
             peaks: Optional[Dict] = None, t_start: float = T_PROCESS,
             control: bool = False, warm: bool = True,
             compile_cache: bool = True) -> Dict:
    """One run of ``cell``; returns the result line's fields and the
    checks.  Needs no chip of its own: the caller checks the devices.

    ``control`` puts the fp8 control's first choices in the served
    tokens' place: ``correct`` and the checks are then the control's,
    and the program's own checks come under ``program_checks``.
    ``warm=False`` skips set-up's replay of the traffic (readings of
    the limit only; the window then builds programs, and that check is
    left out).  ``compile_cache`` turns on JAX's persistent compilation
    cache at the program's fixed path (off in tests, which share a
    process)."""
    import jax
    from repro.launch.compile_cache import use_compile_cache

    if compile_cache:
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(cell, seed, seconds, tracing, peaks, t_start, control,
                    warm, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def _lmb_system():
    from repro.core import DeviceSpec, HostSpec, LMBSystem, SystemSpec
    return LMBSystem(SystemSpec(expanders=1, pool_gib=LMB_POOL_GIB,
                                hosts=(HostSpec("server", page_bytes=4096),),
                                devices=(DeviceSpec("tpu0"),)))


def _run(cell, seed, seconds, tracing, peaks, t_start, control, warm,
         compiles) -> Dict:
    import jax
    from repro.serve import ServeEngine

    mix = cell.mix
    dev = jax.devices()[0]

    _, model, params = build_program(cell, seed)
    plan = traffic.plan(mix, seed, cell.config["vocab_size"])
    ecfg = engine_config(cell, plan)
    log(f"cell {cell.name}: seed={seed} menu={plan.menu} "
        f"onboard_pages={ecfg.onboard_pages} "
        f"max_seq_len={ecfg.max_seq_len} slots={ecfg.decode_slots}")
    fns = None
    replayed: List[Step] = []
    t_replay = time.monotonic()
    if warm:
        with _lmb_system() as system:
            eng = ServeEngine(model, params, system, ecfg)
            replayed = replay(eng, plan, mix, seconds, compiles)
            fns = eng._prefill_fn, eng._paged_fn
            del eng
    log(f"set-up: {t_replay - t_start} s to the replay, "
        f"{time.monotonic() - t_replay} s of replay "
        f"({len(compiles.ends)} programs built in {compiles.seconds} s)")
    with _lmb_system() as system:
        eng = ServeEngine(model, params, system, ecfg)
        if fns is not None:
            # the replay's compiled programs, not a second build of them
            eng._prefill_fn, eng._paged_fn = fns
        meter = ByteMeter()
        eng.kv.buf.executor.meter = meter
        spans = HostSpans() if tracing else None
        driver = Driver(eng, plan, mix)
        driver.run_until(time.monotonic() + mix["warmup_s"])
        if spans is not None:
            spans.install(eng)
        w0 = time.monotonic()
        setup_s = w0 - t_start
        steps0 = len(driver.steps)
        bytes0, fm0 = meter.total, dict(eng.kv.buf.host.fm.op_bytes())
        red = None
        traced: List[Step] = []
        if tracing:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            with jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
                t_trace = w0 + min(mix["trace_s"], seconds)
                while time.monotonic() < t_trace:
                    with jax.profiler.TraceAnnotation("bench.step"):
                        driver.step()
            jax.profiler.stop_trace()
            traced = driver.steps[steps0:]
        driver.run_until(w0 + seconds)
        w1 = driver.steps[-1].t1
        link_bytes = meter.total - bytes0
        fm1 = eng.kv.buf.host.fm.op_bytes()
        window = driver.steps[steps0:]
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        chosen = sample(driver, eng, cell.limits["sample"], seed, w0, w1)
        reqs = list(driver.reqs.values())
        ran = driver.steps
        fm_delta = {k: v - fm0.get(k, 0) for k, v in fm1.items()
                    if v != fm0.get(k, 0)}
        # free the program's state before the reference runs
        driver.eng = None
        del driver, eng, params, model, fns
    gc.collect()

    if tracing:
        red = btrace.reduce(btrace.events_from_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # ------------------------------------------------ end-to-end metrics
    in_window = lambda t: w0 < t <= w1  # noqa: E731
    win_s = w1 - w0
    out_tokens = sum(1 for r in reqs for t in r.times if in_window(t))
    itl = [b - a for r in reqs for a, b in zip(r.times, r.times[1:])
           if in_window(b)]
    attempted = [r for r in reqs if r.sent <= w1 and not (
        r.state != "waiting" and r.times and r.times[-1] <= w0)]
    failed = sum(1 for r in attempted if r.state in ("cancelled", "shed"))
    finished = sum(1 for r in reqs if r.state == "done" and r.times
                   and in_window(r.times[-1]))
    in_window_compiles = compiles.between(w0, w1)
    values = {
        "setup_s": setup_s,
        "out_tok_s": out_tokens / win_s if win_s > 0 else None,
        "itl_p95_ms": percentile(itl, 95) * 1e3 if itl else None,
    }
    log(f"window: {win_s} s, {len(window)} steps, {out_tokens} tokens, "
        f"{len(itl)} inter-token gaps, {finished} requests finished, "
        f"{len(attempted)} attempted, {failed} failed")
    log(f"host link bytes in the window: executor={link_bytes} "
        f"fm.op_bytes={sum(fm_delta.values())} by op {fm_delta}")
    if warm:
        k = first_divergence(replayed, ran)
        log(f"replay: {len(replayed)} steps in set-up, {len(ran)} in the "
            f"warm-up and window; first divergence: {k}")
    log(f"compiles: in window={in_window_compiles} "
        f"in set-up={compiles.between(t_start, w0)}")
    metrics = {}
    if tracing:
        data = RunData(cell, window, traced, win_s, out_tokens,
                       in_window_compiles, link_bytes, spans, red, peaks)
        for m in cell.per_layer:
            v = metric_reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            v = values[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # ----------------------------------------------------------- the check
    t_ref = time.monotonic()
    gaps = widest_gaps(cell, seed, chosen, control) if chosen else {}
    log(f"reference: {time.monotonic() - t_ref} s over {len(chosen)} "
        "requests")
    tokens = int(sum(len(t) for _, t in chosen))
    judged = in_window_compiles if warm else None
    correct, checks = judge(cell.limits, failed, judged, tokens,
                            gaps.get("served"))
    program_checks = None
    if control:
        program_checks = checks
        correct, checks = judge(cell.limits, failed, judged, tokens,
                                gaps.get("control"))
    result = {
        "correct": bool(correct),
        "attempted": len(attempted),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if red is not None:
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = btrace.breakdown(red)
    if program_checks is not None:
        result["program_checks"] = program_checks
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        devs = check_devices(cell.chips)
        peaks = load_peaks(devs[0].device_kind)
    except NoChip as e:
        log(f"bench: {e}; nothing run")
        return 2
    log(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks)
    for name, c in result["checks"].items():
        side = ">=" if name in AT_LEAST else "<="
        log(f"check {name}: {c['value']} (limit {side} {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
