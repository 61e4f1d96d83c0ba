"""The reduction from a trace to busy time, op and module totals and
idle gaps by host span, on a small recorded list of events."""

import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV, HOST = "/device:TPU:0", trace.HOST_PLANE


def ev(plane, line, name, a, b):
    return (plane, line, name, float(a), float(b))


EVENTS = [
    ev(HOST, "python", "bench.window", 1000, 11000),
    ev(HOST, "python", "bench.step", 1000, 6000),
    ev(HOST, "python", "kv.decode_view", 1200, 2500),
    ev(HOST, "python", "bench.step", 6000, 11000),
    ev(DEV, "XLA Modules", "jit_prefill(1)", 3000, 5000),
    ev(DEV, "XLA Ops", "fusion.1", 3000, 4000),
    ev(DEV, "XLA Ops", "fusion.2", 3500, 5000),     # overlaps fusion.1
    ev(DEV, "XLA Ops", "paged_attention", 7000, 9000),
    ev(DEV, "XLA Ops", "fusion.1", 500, 1500),      # starts before the window
    ev(DEV, "XLA Ops", "fusion.3", 10500, 12000),   # ends after it
]


def test_busy_idle_and_totals():
    red = trace.reduce(EVENTS)
    assert red["window_s"] == pytest.approx(10000e-9)
    # busy: [1000,1500] + [3000,5000] + [7000,9000] + [10500,11000]
    assert red["busy_s"] == pytest.approx(5000e-9)
    assert red["ops"]["fusion.1"] == pytest.approx(1500e-9)
    assert red["op_counts"]["fusion.1"] == 2
    assert red["ops"]["fusion.3"] == pytest.approx(500e-9)
    assert trace.op_time(red, "paged_attention") == pytest.approx(
        (2000e-9, 1))
    assert trace.module_time(red, "jit_prefill") == pytest.approx(
        (2000e-9, 1))
    idle = red["idle_by_host"]
    # [1500,3000] midpoint 2250 lies in kv.decode_view; [5000,7000] and
    # [9000,10500] in bench.step
    assert idle["kv.decode_view"] == pytest.approx(1500e-9)
    assert idle["bench.step"] == pytest.approx(3500e-9)
    assert sum(idle.values()) == pytest.approx(red["window_s"]
                                               - red["busy_s"])


def test_breakdown_lists_largest_first():
    b = trace.breakdown(trace.reduce(EVENTS), top=2)
    assert [n for n, _ in b["device_ops"]] == ["paged_attention", "fusion.1"]
    assert b["idle_gaps"][0][0] == "bench.step"


def test_merge_clips_and_joins():
    assert trace.merge([(0, 5), (3, 8), (10, 12)], 2, 11) == [(2, 8),
                                                              (10, 11)]


def test_a_trace_needs_one_window_and_a_device():
    with pytest.raises(ValueError):
        trace.reduce([e for e in EVENTS if e[2] != "bench.window"])
    with pytest.raises(ValueError):
        trace.reduce([e for e in EVENTS if e[0] == HOST])


def test_recorded_chip_trace():
    """One decode round of qwen2-1.5b (28 layers) on a v5e: one kernel
    call per layer, and the idle time split by host span adds up."""
    with open(os.path.join(DATA, "chip_trace_events.json")) as f:
        rec = json.load(f)
    red = trace.reduce([tuple(e) for e in rec["events"]])
    for key, want in rec["expect"].items():
        assert red[key] == pytest.approx(want, rel=1e-9), key
    seconds, calls = trace.op_time(red, r"paged_attention")
    assert calls == rec["kernel_events"] == 28
    assert 0 < seconds < red["busy_s"]
    assert sum(red["idle_by_host"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert max(red["idle_by_host"], key=red["idle_by_host"].get) == \
        "kv.decode_view"


def test_short_names():
    assert trace.short_name("%paged_attention.9 = bf16[8] custom-call(%a)") \
        == "%paged_attention.9"
    assert trace.short_name("jit_prefill(123)") == "jit_prefill(123)"
