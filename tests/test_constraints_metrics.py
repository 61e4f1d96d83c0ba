"""Activation-constraint helper + metrics accounting."""

import jax.numpy as jnp
import pytest

from repro.core.metrics import Metrics
from repro.launch.mesh import make_host_mesh
from repro.sharding.constraints import activation_mesh, constrain


class TestConstraints:
    def test_noop_without_mesh(self):
        x = jnp.ones((4, 8, 16))
        y = constrain(x, "residual")
        assert y is x

    def test_applies_inside_context(self):
        mesh = make_host_mesh()
        x = jnp.ones((4, 8, 16))
        with activation_mesh(mesh):
            y = constrain(x, "residual")
            z = constrain(x, "ffn_hidden")
        # on a 1x1 mesh the constraint is trivially satisfiable
        assert y.shape == x.shape and z.shape == x.shape

    def test_divisibility_degrades_not_crashes(self):
        mesh = make_host_mesh()
        with activation_mesh(mesh):
            # odd dims that divide nothing still pass through
            out = constrain(jnp.ones((3, 5, 7)), "residual")
        assert out.shape == (3, 5, 7)

    def test_decode_single_token_residual(self):
        mesh = make_host_mesh()
        with activation_mesh(mesh):
            out = constrain(jnp.ones((2, 1, 16)), "residual")
        assert out.shape == (2, 1, 16)


class TestMetrics:
    def test_hit_ratio_and_moves(self):
        m = Metrics()
        m.record_hit("kv", "onboard")
        m.record_hit("kv", "onboard")
        m.record_miss("kv", "onboard")
        m.record_move("kv", "onboard", "lmb", 4096)
        c = m.tier("kv", "onboard")
        assert c.hit_ratio == pytest.approx(2 / 3)
        assert c.bytes_out == 4096
        assert m.tier("kv", "lmb").bytes_in == 4096
        snap = m.snapshot()
        assert snap["tiers"]["kv"]["onboard"]["hits"] == 2
        m.reset()
        assert m.tier("kv", "onboard").accesses == 0

    def test_hit_miss_record_bytes(self):
        """record_hit/record_miss must credit nbytes (regression: the
        arguments used to be accepted and dropped)."""
        m = Metrics()
        m.record_hit("kv", "onboard", nbytes=4096)
        m.record_miss("kv", "onboard", nbytes=512)
        c = m.tier("kv", "onboard")
        assert c.bytes_hit == 4096
        assert c.bytes_missed == 512
        snap = m.snapshot()["tiers"]["kv"]["onboard"]
        assert snap["bytes_hit"] == 4096
        assert snap["bytes_missed"] == 512

    def test_event_ring_is_bounded(self):
        """Regression: the event log used to be an unbounded list."""
        m = Metrics(max_events=8)
        for i in range(100):
            m.event("dev0", f"alloc mmid={i}")
        assert m.snapshot()["events"] == {
            "count": 8, "capacity": 8, "total": 100}
        # the ring keeps the most recent events
        assert m._events[-1][2] == "alloc mmid=99"

    def test_counters_gauges_histograms(self):
        m = Metrics()
        m.inc("faults")
        m.inc("faults", 2)
        m.gauge("depth", 7.0)
        m.observe("wait_s", 1e-3)
        m.observe("wait_s", 2e-3)
        snap = m.snapshot()
        assert snap["counters"]["faults"] == 3
        assert snap["gauges"]["depth"] == 7.0
        h = snap["histograms"]["wait_s"]
        assert h["count"] == 2
        assert h["min"] == pytest.approx(1e-3)

    def test_merge(self):
        a, b = Metrics(), Metrics()
        a.record_hit("kv", "onboard", nbytes=10)
        b.record_hit("kv", "onboard", nbytes=20)
        b.record_miss("kv", "lmb")
        a.inc("n", 1)
        b.inc("n", 2)
        a.observe("w", 1.0)
        b.observe("w", 3.0)
        b.event("d0", "free mmid=1")
        a.merge(b)
        assert a.tier("kv", "onboard").hits == 2
        assert a.tier("kv", "onboard").bytes_hit == 30
        assert a.tier("kv", "lmb").misses == 1
        snap = a.snapshot()
        assert snap["counters"]["n"] == 3
        assert snap["histograms"]["w"]["count"] == 2
        assert snap["events"]["count"] == 1
