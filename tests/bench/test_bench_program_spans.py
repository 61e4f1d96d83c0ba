"""The readers of the program's own spans (``bench/program.py``,
``bench/metrics/{kv_append_ms,lmb_host_ms,round_host_ms,
kv_copy_bytes_per_tok}.py``) on a synthetic ring, and the trace
reduction with program spans on the host plane."""

import json
import os

import pytest

from bench import program, run, trace
from repro.obs.trace import GLOBAL_TRACER

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ("kv_append_ms", "lmb_host_ms", "round_host_ms",
           "kv_copy_bytes_per_tok")

#: (id, parent, name, seconds, args): three traced rounds, two decoding
TREE = [
    (1, None, "serve.round", 0.050, {"hbm_copy_bytes": 3000}),
    (2, 1, "engine.prefill", 0.015, {}),
    (3, 2, "kv.append", 0.010, {}),
    (4, 3, "lmb.append_pages", 0.001, {}),
    (5, 3, "lmb.read_many", 0.004, {}),
    (6, 5, "exec.read_pages", 0.001, {}),
    (30, 5, "link.xfer", 5.0, {}),             # modeled seconds, left out
    (7, 3, "lmb.write_many", 0.003, {}),
    (8, 7, "fault.batch", 0.002, {}),
    (9, 8, "exec.write_pages", 0.0015, {}),
    (10, 2, "engine.sync", 0.002, {}),
    (11, 1, "decode.paged", 0.025, {"batch": 2}),
    (12, 11, "kv.view", 0.005, {}),
    (13, 12, "lmb.read_many", 0.004, {}),
    (14, 13, "lmb.read", 0.002, {}),           # an lmb call inside another
    (15, 14, "exec.read_page", 0.0005, {}),
    (16, 11, "engine.sync", 0.010, {}),
    (17, 11, "kv.commit", 0.004, {}),
    (18, 17, "lmb.write_many", 0.003, {}),
    (19, 18, "exec.write_pages", 0.001, {}),
    (20, 1, "engine.tail", 0.002, {}),
    (21, 20, "lmb.note_compute_window", 0.0005, {}),
    (22, None, "serve.round", 0.030, {"hbm_copy_bytes": 1000}),
    (23, 22, "decode.paged", 0.020, {"batch": 2}),
    (24, 23, "engine.sync", 0.012, {}),
    (25, 22, "engine.emit", 0.001, {}),
    (26, 25, "lmb.release", 0.0002, {}),
    (27, None, "serve.round", 0.040, {"hbm_copy_bytes": 0}),
    (28, 27, "engine.prefill", 0.035, {}),
    (29, 28, "kv.append", 0.030, {}),
]
#: tokens of the three traced steps: 2 decoded + 1 prefilled, 2, 1
STEPS = [run.Step(0.0, 1.0, [10, 20], 1), run.Step(1.0, 2.0, [11, 21], 0),
         run.Step(2.0, 3.0, [], 1)]


@pytest.fixture
def ring():
    """The process-wide tracer, empty, restored afterwards."""
    GLOBAL_TRACER.clear()
    GLOBAL_TRACER.enabled = True
    yield GLOBAL_TRACER
    GLOBAL_TRACER.enabled = False
    GLOBAL_TRACER.clear()


def record(tr, tree=TREE):
    for sid, parent, name, dur, args in tree:
        tr.add(name, 0.0, dur, span_id=sid, parent_id=parent, **args)


def data(steps=STEPS):
    return run.RunData(cell=None, steps=steps, traced_steps=steps,
                       window_s=3.0, out_tokens=6, compiles=0,
                       link_bytes=0, spans=None, red=None, peaks=None)


def read(name, run_data):
    return run.metric_reader(name)(run_data)


def test_readers_on_a_synthetic_ring(ring):
    record(ring)
    d = data()
    assert read("kv_append_ms", d) == pytest.approx(20.0)
    # outermost lmb spans less their exec descendants, over 2 decode rounds
    lmb = (0.001 + (0.004 - 0.001) + (0.003 - 0.0015) + (0.004 - 0.0005)
           + (0.003 - 0.001) + 0.0005 + 0.0002)
    assert read("lmb_host_ms", d) == pytest.approx(1e3 * lmb / 2)
    # the two decoding rounds, less every engine.sync inside each
    rounds = (0.050 - 0.002 - 0.010) + (0.030 - 0.012)
    assert read("round_host_ms", d) == pytest.approx(1e3 * rounds / 2)
    assert read("kv_copy_bytes_per_tok", d) == pytest.approx(4000 / 6)


def test_seconds_by_span_inside_the_appends(ring):
    record(ring)
    t = program.Tree(ring.spans())
    split = t.self_seconds(t.named("kv.append"))
    assert split == pytest.approx({
        "kv.append": 0.002 + 0.030, "lmb.read_many": 0.003,
        "exec.write_pages": 0.0015, "lmb.append_pages": 0.001,
        "exec.read_pages": 0.001, "lmb.write_many": 0.001,
        "fault.batch": 0.0005})
    assert list(split)[0] == "kv.append"


def test_a_program_without_spans_gives_nothing(ring):
    for name in READERS:
        assert read(name, data()) is None


def test_a_ring_that_dropped_spans_fails(ring, monkeypatch):
    record(ring)
    monkeypatch.setattr(ring, "dropped", 3)
    for name in READERS:
        with pytest.raises(RuntimeError, match="dropped 3"):
            read(name, data())


def test_copy_bytes_need_the_traced_steps_rounds(ring):
    record(ring)
    with pytest.raises(RuntimeError, match="3 traced rounds"):
        read("kv_copy_bytes_per_tok", data(STEPS[:2]))


def test_recorded_trace_with_program_spans():
    """Program spans on the host plane move no device total, and idle
    time under nested program spans goes to the innermost."""
    with open(os.path.join(DATA, "chip_trace_events.json")) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    base = trace.reduce(events)
    host = [e for e in events if e[0] == trace.HOST_PLANE]
    a, b = next((e[3], e[4]) for e in host if e[2] == "kv.decode_view")
    third = (b - a) / 3
    spans = [(trace.HOST_PLANE, "python3", "kv.view", a + 1, b - 1),
               (trace.HOST_PLANE, "python3", "kv.append",
                a + third, a + 2 * third)]
    red = trace.reduce(events + spans)
    for key in ("busy_s", "window_s", "ops", "op_counts", "modules"):
        assert red[key] == base[key], key
    assert sum(red["idle_by_host"].values()) == pytest.approx(
        sum(base["idle_by_host"].values()))
    assert red["idle_by_host"]["kv.append"] > 0
    assert red["idle_by_host"]["kv.view"] > 0
    assert red["idle_by_host"].get("kv.decode_view", 0.0) < \
        base["idle_by_host"]["kv.decode_view"]
