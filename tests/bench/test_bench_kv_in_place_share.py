"""The reader of ``kv_in_place_share`` (``bench/metrics/
kv_in_place_share.py``) on synthetic ``kv.view`` spans."""

import pytest

from bench import run
from repro.obs.trace import GLOBAL_TRACER


@pytest.fixture
def ring():
    """The process-wide tracer, empty, restored afterwards."""
    GLOBAL_TRACER.clear()
    GLOBAL_TRACER.enabled = True
    yield GLOBAL_TRACER
    GLOBAL_TRACER.enabled = False
    GLOBAL_TRACER.clear()


def share(views, ring):
    """Record one decode round per entry of ``views`` (the view's
    ``in_place`` arg, or None for a view without it) and read."""
    for i, flag in enumerate(views):
        rnd = ring.add("serve.round", float(i), 0.02)
        dec = ring.add("decode.paged", float(i), 0.01, parent_id=rnd)
        args = {} if flag is None else {"in_place": flag}
        ring.add("kv.view", float(i), 0.002, parent_id=dec, batch=8,
                 **args)
        ring.add("kv.commit", float(i) + 0.003, 0.001, parent_id=dec)
    data = run.RunData(cell=None, steps=[], traced_steps=[], window_s=1.0,
                       out_tokens=0, compiles=0, link_bytes=0, spans=None,
                       red=None, peaks=None)
    return run.metric_reader("kv_in_place_share")(data)


@pytest.mark.parametrize("views,want", [
    ([1, 1, 1, 1], 100.0),
    ([1, 0, 1, 1], 75.0),
    ([0, 0], 0.0),
])
def test_share_of_views_in_place(ring, views, want):
    assert share(views, ring) == pytest.approx(want)


@pytest.mark.parametrize("views", [[], [None, None]])
def test_no_view_with_the_arg_gives_nothing(ring, views):
    """No span traced, or views of a program that does not say how they
    ran: nothing to read."""
    assert share(views, ring) is None


def test_a_ring_that_dropped_spans_fails(ring, monkeypatch):
    monkeypatch.setattr(ring, "dropped", 2)
    with pytest.raises(RuntimeError, match="dropped 2"):
        share([1], ring)
