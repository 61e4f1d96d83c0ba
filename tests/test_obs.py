"""Observability layer: span tracer, log-bucket histograms, trace
export round-trips, and span/counter reconciliation on a live system."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ObsSpec, system_for
from repro.core.metrics import Metrics
from repro.obs import Histogram, SpanTracer
from repro.obs.export import (chrome_trace_events, load_trace, read_jsonl,
                              span_from_dict, span_to_dict,
                              write_chrome_trace, write_jsonl)
from repro.obs.hist import merge_all
from repro.obs.trace import Span


# ------------------------------------------------------------- histogram
class TestHistogram:
    def test_percentiles_vs_numpy(self):
        rng = np.random.default_rng(0)
        vals = rng.lognormal(mean=-6.0, sigma=1.2, size=8000)
        h = Histogram()
        h.record_many(vals)
        # bounded relative error: at most ~the bucket width (15-20%
        # at 8 buckets/decade), far tighter than a mean-only summary
        for q in (10, 50, 90, 99):
            est = h.percentile(q)
            ref = float(np.percentile(vals, q))
            assert est == pytest.approx(ref, rel=0.20), q

    def test_extremes_are_exact(self):
        h = Histogram()
        h.record_many([3e-6, 5e-4, 0.9])
        assert h.percentile(0) == 3e-6
        assert h.percentile(100) == 0.9
        assert h.min == 3e-6 and h.max == 0.9

    def test_single_value(self):
        h = Histogram()
        h.record(2.5e-3)
        for q in (0, 50, 99, 100):
            assert h.percentile(q) == pytest.approx(2.5e-3, rel=0.2)
        assert h.mean == pytest.approx(2.5e-3)

    def test_under_and_overflow(self):
        h = Histogram(lo=1e-3, hi=1e3)
        h.record(0.0)          # underflow
        h.record(1e9)          # overflow
        assert h.count == 2
        assert h.percentile(1) == 0.0       # clamped to observed min
        assert h.percentile(100) == 1e9     # exact observed max

    def test_merge_equals_combined(self):
        rng = np.random.default_rng(1)
        a_vals = rng.lognormal(-5, 1, 500)
        b_vals = rng.lognormal(-4, 1, 700)
        a, b, both = Histogram(), Histogram(), Histogram()
        a.record_many(a_vals)
        b.record_many(b_vals)
        both.record_many(np.concatenate([a_vals, b_vals]))
        merged = merge_all([a, b])
        assert merged.count == both.count
        assert np.array_equal(merged.counts, both.counts)
        assert merged.percentile(99) == both.percentile(99)

    def test_merge_layout_mismatch_raises(self):
        with pytest.raises(ValueError, match="layout"):
            Histogram().merge(Histogram(lo=1e-6, hi=1e6))

    def test_empty_snapshot(self):
        assert Histogram().snapshot()["count"] == 0
        assert Histogram().percentile(50) == 0.0


# ---------------------------------------------------------------- tracer
class TestSpanTracer:
    def test_ring_bounds_and_drop_count(self):
        tr = SpanTracer(capacity=4)
        for i in range(10):
            tr.add(f"s{i}", float(i), 1.0)
        assert len(tr) == 4
        assert tr.dropped == 6
        names = [s.name for s in tr.spans()]
        assert names == ["s6", "s7", "s8", "s9"]  # oldest-first window
        assert tr.snapshot() == {"enabled": True, "capacity": 4,
                                 "count": 4, "dropped": 6}

    def test_disabled_is_noop(self):
        tr = SpanTracer(enabled=False)
        assert tr.add("x", 0.0, 1.0) == 0
        assert tr.event("y") == 0
        cm = tr.span("z")
        with cm:
            pass
        # the disabled span() returns one shared no-op object
        assert tr.span("w") is cm
        assert len(tr) == 0 and tr.dropped == 0

    def test_parenting_via_stack(self):
        tr = SpanTracer()
        with tr.span("outer"):
            tr.event("leaf")
            with tr.span("inner"):
                tr.event("deep")
        by_name = {s.name: s for s in tr.spans()}
        assert by_name["outer"].parent_id is None
        assert by_name["leaf"].parent_id == by_name["outer"].span_id
        assert by_name["inner"].parent_id == by_name["outer"].span_id
        assert by_name["deep"].parent_id == by_name["inner"].span_id
        # nesting is reflected in time containment too
        assert by_name["outer"].t0 <= by_name["inner"].t0
        assert (by_name["inner"].t0 + by_name["inner"].dur
                <= by_name["outer"].t0 + by_name["outer"].dur + 1e-9)

    def test_tags_flow_through(self):
        tr = SpanTracer()
        tr.add("link.xfer", 0.5, 0.25, op="prefetch", tenant="t0",
               expander=3, nbytes=4096, device="d0")
        (s,) = tr.spans()
        assert (s.op, s.tenant, s.expander, s.nbytes) == (
            "prefetch", "t0", 3, 4096)
        assert s.args == {"device": "d0"}

    def test_clear_resets_epoch_and_ids(self):
        tr = SpanTracer(capacity=2)
        tr.add("a", 0.0, 1.0)
        tr.clear()
        assert len(tr) == 0 and tr.dropped == 0
        tr.add("b", 0.0, 1.0)
        assert [s.name for s in tr.spans()] == ["b"]

    def test_span_block_adds_args_before_close(self):
        tr = SpanTracer()
        with tr.span("decode.paged", op="serve") as args:
            args.update(batch=3, nbytes=64)
        (s,) = tr.spans()
        assert (s.op, s.nbytes, s.args) == ("serve", 64, {"batch": 3})
        with SpanTracer(enabled=False).span("decode.paged") as args:
            assert args is None

    def test_spans_land_in_the_profiler_trace(self, tmp_path):
        """An enabled span is a profiler annotation: the JAX profile's
        host plane holds it by name, as ``bench.trace`` flattens it.  A
        disabled tracer records nothing and opens no annotation."""
        import jax

        from bench.trace import HOST_PLANE, events_from_xplane
        from repro.obs import profiling

        on, off = SpanTracer(), SpanTracer(enabled=False)
        assert not profiling()
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert profiling()
            with on.span("engine.sync"):
                with on.span("kv.view"):
                    jnp.ones(4).block_until_ready()
            with off.span("engine.emit"):
                pass
        finally:
            jax.profiler.stop_trace()
        assert not profiling()
        host = {e[2]: e for e in events_from_xplane(str(tmp_path))
                if e[0] == HOST_PLANE}
        assert "engine.sync" in host and "kv.view" in host
        assert "engine.emit" not in host
        outer, inner = host["engine.sync"], host["kv.view"]
        assert outer[3] <= inner[3] and inner[4] <= outer[4]
        assert [s.name for s in on.spans()] == ["kv.view", "engine.sync"]
        assert len(off) == 0


# --------------------------------------------------------------- export
def _sample_spans():
    return [
        Span("serve.round", 0.0, 1e-3, op="serve", span_id=1),
        Span("link.xfer", 1e-4, 5e-5, op="demand", tenant="tA",
             expander=0, nbytes=8192, span_id=2, parent_id=1,
             args={"device": "d0"}),
        Span("link.xfer", 2e-4, 7e-5, op="prefetch", expander=1,
             nbytes=4096, span_id=3, parent_id=1),
        Span("ttft", 9e-4, 0.0, op="serve", tenant="tA", span_id=4,
             parent_id=1, args={"ttft_s": 0.01}),
    ]


class TestExport:
    def test_jsonl_round_trip(self, tmp_path):
        spans = _sample_spans()
        p = tmp_path / "t.jsonl"
        write_jsonl(spans, str(p))
        back = read_jsonl(str(p))
        assert [span_to_dict(s) for s in back] == [
            span_to_dict(s) for s in spans]
        assert span_from_dict(span_to_dict(spans[1])) == spans[1]

    def test_chrome_trace_round_trip_dedupes_tracks(self, tmp_path):
        spans = _sample_spans()
        p = tmp_path / "t.json"
        write_chrome_trace(spans, str(p), extra={"note": "test"})
        with open(p) as f:
            doc = json.load(f)
        assert doc["otherData"]["note"] == "test"
        # span 2 has tenant AND expander -> emitted on both tracks
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == len(spans) + 1
        # ...but load_trace returns each logical span exactly once
        back = load_trace(str(p))
        assert sorted(s.span_id for s in back) == [1, 2, 3, 4]
        by_id = {s.span_id: s for s in back}
        assert by_id[2].tenant == "tA" and by_id[2].expander == 0
        assert by_id[2].args == {"device": "d0"}
        assert by_id[2].dur == pytest.approx(5e-5)
        assert by_id[4].parent_id == 1

    def test_track_metadata(self):
        evs = chrome_trace_events(_sample_spans())
        meta = {(e["pid"], e["tid"], e["args"]["name"])
                for e in evs if e["ph"] == "M"}
        assert (1, 0, "expander 0 link") in meta
        assert (1, 1, "expander 1 link") in meta
        assert (2, 0, "tenant tA") in meta

    def test_load_trace_sniffs_jsonl(self, tmp_path):
        p = tmp_path / "one.jsonl"
        write_jsonl(_sample_spans()[:1], str(p))
        assert load_trace(str(p))[0].name == "serve.round"


# ----------------------------------------------- live-system reconciliation
def _traced_system(**kw):
    return system_for("d0", host_id="h0", pool_gib=1, page_bytes=1 << 16,
                      metrics=Metrics(), obs=ObsSpec(trace=True), **kw)


class TestReconciliation:
    def test_link_span_bytes_match_fabric_op_bytes(self):
        system = _traced_system()
        buf = system.buffer(name="kv", device_id="d0",
                            page_shape=(64, 64), dtype=jnp.float32,
                            onboard_pages=4, metrics=Metrics())
        pages = buf.append_pages(16)
        for p in pages:
            buf.write(p, jnp.full((64, 64), float(p)))
        buf.read_many(pages)                      # coalesced misses
        for p in pages[:6]:
            buf.read(p)                           # scalar faults
        by_op = {}
        for s in system.trace_spans():
            if s.name == "link.xfer":
                by_op[s.op] = by_op.get(s.op, 0) + s.nbytes
        assert by_op  # traffic definitely crossed the link
        assert by_op == system.fm.op_bytes()
        system.close()

    def test_hidden_fraction_matches_prefetch_counters(self):
        system = _traced_system()
        overlap = system.overlap_scheduler(compute_window_s=2e-3)
        n_scan, n_warm = 36, 12
        buf = system.buffer(name="pf", device_id="d0",
                            page_shape=(64, 64), dtype=jnp.float32,
                            onboard_pages=n_warm, prefetch_depth=8,
                            lmb_chunk_pages=16, overlap=overlap,
                            metrics=Metrics())
        pages = buf.append_pages(n_scan + n_warm)
        for p in pages:
            buf.write(p, jnp.full((64, 64), float(p)))
        for p in pages[n_scan:]:
            buf.release(p)              # scan streams through free slots
        w0 = buf.link_wait_s
        for p in pages[:n_scan]:        # sequential scan: prefetch hides
            system.fm.advance_links(2e-3)
            buf.note_compute_window(2e-3, observed=False)
            buf.read(p)
            buf.release(p)
        hidden = buf.prefetch_hidden_s
        exposed = buf.link_wait_s - w0
        assert hidden > 0               # the prefetcher actually ran
        pf_s = sum(s.dur for s in system.trace_spans()
                   if s.name == "link.xfer" and s.op == "prefetch")
        dm_s = sum(s.dur for s in system.trace_spans()
                   if s.name == "link.xfer" and s.op == "demand")
        # span durations ARE the modeled grant delays, so the trace
        # reproduces the buffer's hidden/exposed accounting exactly
        assert pf_s == pytest.approx(hidden, rel=1e-9)
        assert dm_s == pytest.approx(exposed + w0, rel=1e-9)
        system.close()

    def test_disabled_by_default_and_functionally_identical(self):
        def run(obs):
            system = system_for("d0", host_id="h0", pool_gib=1,
                                page_bytes=1 << 16, metrics=Metrics(),
                                obs=obs)
            buf = system.buffer(name="kv", device_id="d0",
                                page_shape=(32, 32), dtype=jnp.float32,
                                onboard_pages=4, metrics=Metrics())
            pages = buf.append_pages(12)
            for p in pages:
                buf.write(p, jnp.full((32, 32), float(p)))
            out = np.asarray(buf.read_many(pages))
            st = (system.fm.op_bytes(), system.fm.meter_calls(),
                  len(system.trace_spans()))
            system.close()
            return out, st

        out_off, (ob_off, mc_off, n_off) = run(ObsSpec())
        out_on, (ob_on, mc_on, n_on) = run(ObsSpec(trace=True))
        assert n_off == 0               # default tracer records nothing
        assert n_on > 0
        np.testing.assert_array_equal(out_off, out_on)
        assert ob_off == ob_on and mc_off == mc_on

    def test_trace_in_system_snapshot_and_export(self, tmp_path):
        system = _traced_system()
        buf = system.buffer(name="kv", device_id="d0",
                            page_shape=(32, 32), dtype=jnp.float32,
                            onboard_pages=2, metrics=Metrics())
        pages = buf.append_pages(8)
        for p in pages:
            buf.write(p, jnp.zeros((32, 32)))
        snap = system.snapshot()
        assert snap["trace"]["enabled"] is True
        assert snap["trace"]["count"] == len(system.trace_spans())
        gauges = system.metrics.snapshot()["gauges"]
        assert gauges["fm.journal_len"] == snap["journal"]["len"]
        assert gauges["fm.journal.grant"] == (
            snap["journal"]["by_op"]["grant"])
        p = tmp_path / "sys.json"
        system.export_trace(str(p))
        assert len(load_trace(str(p))) == len(system.trace_spans())
        system.close()


# ------------------------------------------------------- journal compaction
class TestJournalCompaction:
    def _held(self, fm):
        """Replay the journal into a held-block set per host."""
        held = {}
        for e in fm.journal:
            if e.op in ("grant", "regrant"):
                held.setdefault(e.host_id, set()).add(e.block_id)
            elif e.op == "release":
                held.get(e.host_id, set()).discard(e.block_id)
        return {h: s for h, s in held.items() if s}

    def test_compact_conserves_replayed_state(self):
        system = system_for("d0", host_id="h0", pool_gib=1,
                            page_bytes=4096, metrics=Metrics())
        # near-block-sized allocations: each one grants its own 256 MB
        # block, and freeing empties the block -> a release entry
        keep = [system.alloc("d0", 200 << 20) for _ in range(3)]
        for _ in range(40):             # churn: superseded grant pairs
            system.alloc("d0", 200 << 20).free()
        fm = system.fm
        before_len = fm.journal_stats()["len"]
        held_before = self._held(fm)
        removed = fm.compact()
        assert removed > 0
        assert fm.journal_stats()["len"] == before_len - removed
        assert self._held(fm) == held_before
        # the live allocations' grants survived compaction
        live_blocks = {b for s in self._held(fm).values() for b in s}
        assert live_blocks                  # `keep` still journaled
        assert fm.journal_stats()["by_op"].get("release", 0) == 0
        for h in keep:
            h.free()
        system.close()

    def test_compact_idempotent_and_stats_shape(self):
        system = system_for("d0", host_id="h0", pool_gib=1,
                            page_bytes=4096, metrics=Metrics())
        system.alloc("d0", 200 << 20).free()
        fm = system.fm
        assert fm.compact() >= 2
        assert fm.compact() == 0            # nothing left to fold
        st = fm.journal_stats()
        assert set(st) == {"len", "by_op"}
        assert st["len"] == sum(st["by_op"].values())
        system.close()
