"""Reduction of a profiler trace to device busy time, op and module
totals, and idle gaps by what the host was doing.

The trace is first flattened to events ``[plane, line, name, start_ns,
end_ns]`` (:func:`events_from_xplane`), so the reduction can be tested
on a small recorded list.  Device events are those of planes named
``/device:<platform>:<n>``; ops sit on their ``XLA Ops`` line and
compiled programs on ``XLA Modules``.  Host spans are the benchmark's
own ``TraceAnnotation`` names, on the host plane.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
#: host spans the benchmark sets; idle time no span covers is "host.other"
HOST_PREFIXES = ("bench.", "kv.", "model.", "engine.")

Event = Tuple[str, str, str, float, float]


def events_from_xplane(path: str) -> List[Event]:
    """Flatten an ``.xplane.pb`` file (or the newest one under a profile
    directory) into events."""
    import jax
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = jax.profiler.ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        keep_host = plane.name == HOST_PLANE
        keep_dev = bool(DEVICE_PLANE.match(plane.name))
        if not (keep_host or keep_dev):
            continue
        for line in plane.lines:
            if keep_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if keep_host and not ev.name.startswith(HOST_PREFIXES):
                    continue
                out.append((plane.name, line.name, short_name(ev.name),
                            float(ev.start_ns), float(ev.end_ns)))
    return out


def short_name(name: str) -> str:
    """An op's own name: a TPU trace names each op by its whole HLO
    instruction (``%paged_attention.9 = bf16[...] custom-call(...)``),
    whose operands name other ops."""
    return name.split(" = ", 1)[0]


def merge(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Union of intervals, clipped to [lo, hi], sorted."""
    ivs = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                 if b > lo and a < hi)
    out: List[List[float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def window(events: Sequence[Event], span: str = WINDOW_SPAN
           ) -> Tuple[float, float]:
    """[start, end] ns of the host span that marks the traced window."""
    marks = [(e[3], e[4]) for e in events
             if e[0] == HOST_PLANE and e[2] == span]
    if len(marks) != 1:
        raise ValueError(f"expected one {span!r} span, found {len(marks)}")
    return marks[0]


def _label(spans: List[Tuple[float, float, str]], t: float) -> str:
    """The innermost host span covering instant ``t``."""
    best, best_start = "host.other", None
    for a, b, name in spans:
        if a <= t <= b and name != WINDOW_SPAN and (
                best_start is None or a >= best_start):
            best, best_start = name, a
    return best


def reduce(events: Sequence[Event]) -> Dict:
    """Busy and idle time of the device(s) in the traced window, the ops
    and modules that took most device time, and idle time by the host
    span that was open while the device waited.

    Returns seconds: ``window_s``, ``busy_s`` (the union of op intervals,
    averaged over devices), ``ops`` and ``modules`` ({name: seconds},
    summed over devices, inside the window), ``idle_by_host``
    ({span: seconds}), ``n_devices``, and event counts ``op_counts``
    and ``module_counts``."""
    lo, hi = window(events)
    devices = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})
    if not devices:
        raise ValueError("the trace holds no device plane")
    ops: Dict[str, float] = collections.defaultdict(float)
    counts: Dict[str, int] = collections.defaultdict(int)
    modules: Dict[str, float] = collections.defaultdict(float)
    mod_counts: Dict[str, int] = collections.defaultdict(int)
    busy_total = 0.0
    idle_by: Dict[str, float] = collections.defaultdict(float)
    spans = [(e[3], e[4], e[2]) for e in events if e[0] == HOST_PLANE]
    for dev in devices:
        op_ivs = []
        for plane, line, name, a, b in events:
            if plane != dev or b <= lo or a >= hi:
                continue
            clipped = min(b, hi) - max(a, lo)
            if line == OPS_LINE:
                op_ivs.append((a, b))
                ops[name] += clipped * 1e-9
                counts[name] += 1
            elif line == MODULES_LINE:
                modules[name] += clipped * 1e-9
                mod_counts[name] += 1
        busy = merge(op_ivs, lo, hi)
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle_by[_label(spans, (a + b) / 2)] += (b - a) * 1e-9
    n = len(devices)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total * 1e-9 / n,
        "n_devices": n,
        "ops": dict(ops),
        "op_counts": dict(counts),
        "modules": dict(modules),
        "module_counts": dict(mod_counts),
        "idle_by_host": {k: v / n for k, v in idle_by.items()},
    }


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The result line's ``breakdown``: the device ops that took most
    time and the idle time by host span, largest first."""
    def biggest(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": biggest(red["ops"]),
            "idle_gaps": biggest(red["idle_by_host"])}


def module_time(red: Dict, prefix: str) -> Tuple[float, int]:
    """Device seconds of the compiled programs whose name starts with
    ``prefix`` (``jit_prefill``...)."""
    total = sum(v for k, v in red["modules"].items() if k.startswith(prefix))
    n = sum(c for k, c in red["module_counts"].items()
            if k.startswith(prefix))
    return total, n


def op_time(red: Dict, pattern: str) -> Tuple[float, int]:
    """Device seconds and event count of the ops whose name matches
    ``pattern``."""
    rx = re.compile(pattern)
    t = sum(v for k, v in red["ops"].items() if rx.search(k))
    n = sum(c for k, c in red["op_counts"].items() if rx.search(k))
    return t, n
