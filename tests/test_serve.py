"""Serving engine: continuous batching, KV paging, preemption, prefix
sharing, capacity exceeding HBM (the LMB thesis applied to serving)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import system_for
from repro.models import build_model
from repro.models.flags import Flags
from repro.serve import EngineConfig, ServeEngine, SubmitSpec
from repro.serve.kv_cache import PagedKVStore


def fresh_system(pool_gib=1):
    """The serve stack is constructed through the client API."""
    return system_for("tpu0", host_id="h0", pool_gib=pool_gib,
                      page_bytes=4096)


@pytest.fixture(scope="module")
def served():
    cfg = get_config("qwen2-1.5b").reduced()
    model = build_model(cfg, Flags(remat=False))
    params = model.init(jax.random.key(0))
    return cfg, model, params


def make_engine(served, **kw):
    cfg, model, params = served
    qos = kw.pop("qos", None)
    clock = kw.pop("clock", None)
    defaults = dict(decode_slots=2, max_seq_len=64, page_tokens=8,
                    onboard_pages=8, prefill_bucket=16)
    defaults.update(kw)
    return ServeEngine(model, params, fresh_system(), EngineConfig(
        **defaults), qos=qos, clock=clock)


def test_requests_complete(served):
    eng = make_engine(served)
    rng = np.random.default_rng(0)
    rids = [eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 12),
                               max_new_tokens=4))
            for _ in range(5)]
    eng.run(200)
    assert all(eng.requests[r].state == "done" for r in rids)
    assert all(len(eng.requests[r].out_tokens) == 4 for r in rids)
    # pooled-fabric placement surfaces in the engine snapshot
    fab = eng.stats()["fabric"]
    assert set(fab) == {"block_placement", "kv_page_placement",
                        "link_utilization", "meter_calls"}
    assert 0 in fab["block_placement"]         # every pool expander listed
    assert all(0.0 <= u <= 1.0 for u in fab["link_utilization"].values())
    assert fab["meter_calls"] >= 0             # arbitration round-trips


def test_deterministic_outputs_vs_direct_decode(served):
    """Engine output == direct prefill+argmax-decode of the same model."""
    cfg, model, params = served
    prompt = np.arange(1, 11, dtype=np.int32)
    eng = make_engine(served)
    rid = eng.submit(SubmitSpec(prompt=prompt, max_new_tokens=4))
    eng.run(100)
    got = eng.requests[rid].out_tokens

    cache = model.init_cache(1, 64)
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(prompt[None])}, cache)
    expect = [int(jnp.argmax(logits[0]))]
    for _ in range(3):
        tok = jnp.asarray([[expect[-1]]], jnp.int32)
        logits, cache = jax.jit(model.decode_step)(params, cache, tok)
        expect.append(int(jnp.argmax(logits[0])))
    assert got == expect


def test_kv_capacity_exceeds_onboard(served):
    """More concurrent KV state than onboard pages: pages spill to the
    LMB tier and requests still complete (paper's capacity thesis)."""
    eng = make_engine(served, decode_slots=4, onboard_pages=4)
    rng = np.random.default_rng(1)
    rids = [eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 20),
                               max_new_tokens=6))
            for _ in range(6)]
    eng.run(400)
    assert all(eng.requests[r].state == "done" for r in rids)
    c = eng.kv.buf.metrics.tier(eng.kv.buf.name, "onboard")
    assert c.misses > 0          # spill traffic actually happened


def test_preemption_and_resume(served):
    eng = make_engine(served, decode_slots=2)
    rng = np.random.default_rng(2)
    r1 = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 10),
                               max_new_tokens=8))
    r2 = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 10),
                               max_new_tokens=8))
    eng.step()
    assert eng.requests[r1].state == "active"
    slot = next(s for s, r in eng.active.items() if r.req_id == r1)
    eng.preempt(slot)
    assert eng.requests[r1].state == "preempted"
    eng.run(300)
    assert eng.requests[r1].state == "done"
    assert eng.requests[r2].state == "done"


def test_prefix_fork_zero_copy(served):
    cfg, model, params = served
    system = fresh_system()
    kv = PagedKVStore(cfg=cfg, system=system, device_id="tpu0",
                      page_tokens=4, onboard_pages=4)
    sid = kv.new_seq()
    L, KV_, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    kvdata = jnp.ones((L, 2, 8, KV_, hd), jnp.dtype(cfg.dtype))
    kv.append_tokens(sid, kvdata)
    held = system.host().owned_bytes("tpu0")
    fork = kv.fork(sid)
    assert system.host().owned_bytes("tpu0") == held   # no new LMB bytes
    assert kv.seq(fork).length == kv.seq(sid).length
    # writing to the fork triggers COW, original unchanged
    kv.append_tokens(fork, kvdata * 2)
    a = np.asarray(kv.gather_seq(sid), np.float32)
    assert a.max() == 1.0
    kv.free_seq(fork)
    kv.free_seq(sid)
    kv.buf.check_invariants()


def test_page_table_export(served):
    cfg, *_ = served
    kv = PagedKVStore(cfg=cfg, system=fresh_system(), device_id="tpu0",
                      page_tokens=4, onboard_pages=4)
    sid = kv.new_seq()
    L, KV_, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    kv.append_tokens(sid, jnp.ones((L, 2, 10, KV_, hd),
                                   jnp.dtype(cfg.dtype)))
    pt = kv.page_table(sid, 8)
    assert (pt >= 0).sum() == 3          # ceil(10/4)
    assert (pt[3:] == -1).all()


def test_qos_admission_shed_and_slo_feedback(served):
    """A tenant whose demand blows its own SLO on the shared link is shed;
    a well-provisioned tenant completes and feeds its latency tracker."""
    from repro.qos import AdmissionController, SLOTarget

    ctrl = AdmissionController(link_bandwidth_Bps=10e9)
    ctrl.register("gold", target=SLOTarget(p99_latency_s=10.0),
                  demand_Bps=1e9, base_latency_s=0.01)
    ctrl.register("abuser",
                  target=SLOTarget(p99_latency_s=0.005, shed_factor=1.5),
                  demand_Bps=9.5e9, base_latency_s=0.01)
    eng = make_engine(served, qos=ctrl)
    rng = np.random.default_rng(0)
    gold = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 8),
                                 max_new_tokens=3, tenant="gold"))
    abuser = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 8),
                                   max_new_tokens=3, tenant="abuser"))
    eng.run(100)
    assert eng.requests[gold].state == "done"
    assert eng.requests[abuser].state == "shed"
    st = eng.stats()
    assert st["shed"] == 1
    t = st["qos"]["tenants"]
    assert t["abuser"]["shed_count"] == 1
    assert t["gold"]["observed_p99_s"] is not None   # latency fed back
    assert not t["gold"]["admitted"]                 # released on drain


def test_per_tenant_latency_attribution(served):
    """Engine-level tracing: every tenant gets its own TTFT and
    inter-token histograms, and ttft/token spans carry the tenant tag."""
    eng = make_engine(served, trace=True)
    rng = np.random.default_rng(0)
    rids = {}
    for i in range(4):
        tenant = f"t{i % 2}"
        rids.setdefault(tenant, []).append(
            eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 12),
                                  max_new_tokens=4, tenant=tenant)))
    eng.run(200)
    st = eng.stats()
    for tenant, ids in rids.items():
        assert all(eng.requests[r].state == "done" for r in ids)
        ttft = st["latency"][f"serve.ttft.{tenant}"]
        itl = st["latency"][f"serve.itl.{tenant}"]
        assert ttft["count"] == len(ids)          # one TTFT per request
        # 4 new tokens -> first is TTFT, the other 3 are gaps
        assert itl["count"] == 3 * len(ids)
        assert 0 < ttft["p50"] <= ttft["p99"]
        assert 0 < itl["p50"] <= itl["p99"]
    # the span stream attributes the same events per tenant
    spans = eng.trace.spans()
    assert any(s.name == "serve.round" for s in spans)
    for tenant, ids in rids.items():
        ttft_spans = [s for s in spans
                      if s.name == "ttft" and s.tenant == tenant]
        tok_spans = [s for s in spans
                     if s.name == "token" and s.tenant == tenant]
        assert len(ttft_spans) == len(ids)
        assert len(tok_spans) == 3 * len(ids)
        assert {s.args["req"] for s in ttft_spans} == set(ids)
    assert st["trace"]["enabled"] and st["trace"]["count"] == len(spans)


def test_deadline_expires_waiting_request(served):
    """A queued request whose deadline passes is cancelled in place —
    never seated, never prefilled, counted in engine stats."""
    from repro.serve import VirtualClock

    clock = VirtualClock()
    eng = make_engine(served, decode_slots=1, clock=clock)
    rng = np.random.default_rng(0)
    r1 = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 10),
                               max_new_tokens=8))
    r2 = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 10),
                               max_new_tokens=4, deadline_s=0.5))
    eng.step()                       # r1 takes the only slot
    assert eng.requests[r2].state == "waiting"
    clock.advance(1.0)               # past r2's deadline
    eng.step()
    req = eng.requests[r2]
    assert req.state == "cancelled" and req.cancel_reason == "deadline"
    assert req.seq_id is None        # nothing was ever allocated for it
    eng.run(200)
    assert eng.requests[r1].state == "done"
    st = eng.stats()
    assert st["cancelled"] == 1 and st["done"] == 1


def test_deadline_cancels_active_mid_flight(served):
    """An ACTIVE request past its deadline is pulled out of its decode
    slot and its KV sequence freed mid-flight."""
    from repro.serve import VirtualClock

    clock = VirtualClock()
    eng = make_engine(served, decode_slots=1, clock=clock)
    rng = np.random.default_rng(1)
    rid = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 10),
                                max_new_tokens=64, deadline_s=0.5))
    eng.step()
    req = eng.requests[rid]
    assert req.state == "active" and req.seq_id is not None
    clock.advance(1.0)
    eng.step()
    assert req.state == "cancelled" and req.cancel_reason == "deadline"
    assert req.seq_id is None        # KV freed mid-flight
    assert not eng.active            # slot returned
    assert len(eng._slot_free) == 1
    eng.kv.buf.check_invariants()


def test_cancellation_counted_per_tenant_slo(served):
    """Deadline cancellations land in the tenant's SLO record."""
    from repro.qos import AdmissionController, SLOTarget
    from repro.serve import VirtualClock

    ctrl = AdmissionController(link_bandwidth_Bps=10e9)
    ctrl.register("gold", target=SLOTarget(p99_latency_s=100.0),
                  demand_Bps=1e6, base_latency_s=0.01)
    clock = VirtualClock()
    eng = make_engine(served, decode_slots=1, qos=ctrl, clock=clock)
    rng = np.random.default_rng(2)
    blocker = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 10),
                                    max_new_tokens=8, tenant="gold"))
    doomed = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 10),
                                   max_new_tokens=4, tenant="gold",
                                   deadline_s=0.25))
    eng.step()
    clock.advance(1.0)
    eng.run(200)
    assert eng.requests[blocker].state == "done"
    assert eng.requests[doomed].state == "cancelled"
    snap = eng.stats()["qos"]["tenants"]["gold"]
    assert snap["cancelled_count"] == 1
    assert not snap["admitted"]      # demand released after the cancel


def test_throttle_preserves_fifo_and_cannot_starve(served):
    """Satellite regression: a throttled request returns to the FRONT of
    the queue in arrival order (no tail-requeue reordering), and a
    permanently-throttled tenant cannot starve later arrivals — its
    deadline bounds the retries."""
    from repro.qos.slo import Decision
    from repro.serve import VirtualClock

    class AlwaysThrottle:
        """Throttles one tenant forever, admits everyone else."""

        def __init__(self, victim):
            self.victim = victim

        def decide(self, tenant):
            return (Decision.THROTTLE if tenant == self.victim
                    else Decision.ADMIT)

        def observe(self, tenant, latency_s):
            pass

        def release(self, tenant):
            pass

        def record_cancel(self, tenant):
            pass

        def snapshot(self):
            return {}

    clock = VirtualClock()
    eng = make_engine(served, decode_slots=1,
                      qos=AlwaysThrottle("starved"), clock=clock)
    rng = np.random.default_rng(3)
    bad = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 10),
                                max_new_tokens=4, tenant="starved",
                                deadline_s=2.0))
    g1 = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 10),
                               max_new_tokens=4, tenant="good"))
    g2 = eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 10),
                               max_new_tokens=4, tenant="good"))
    eng.step()
    # bad was throttled, g1 took the slot; FIFO arrival order holds in
    # the queue: the throttled request is still AHEAD of g2
    assert [r.req_id for r in eng.waiting] == [bad, g2]
    for _ in range(30):
        if not (eng.waiting or eng.active):
            break
        eng.step()
        clock.advance(0.1)
    # both good requests completed despite the ever-throttled head-of-line
    assert eng.requests[g1].state == "done"
    assert eng.requests[g2].state == "done"
    # and the starved tenant's request died at its deadline, not forever
    assert eng.requests[bad].state == "cancelled"
    assert eng.requests[bad].cancel_reason == "deadline"


def test_capacity_cancel_when_pool_degrades_mid_run(served):
    """Expander failure mid-run: the engine cancels what no longer fits
    (reason='capacity') instead of crashing, and still drains."""
    cfg, model, params = served
    system = fresh_system()
    eng = ServeEngine(model, params, system, EngineConfig(
        decode_slots=4, max_seq_len=64, page_tokens=8,
        onboard_pages=4, prefill_bucket=16))
    rng = np.random.default_rng(4)
    rids = [eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 20),
                                  max_new_tokens=8))
            for _ in range(6)]
    eng.step()
    system.inject_failure()          # the only expander dies, no spare
    eng.run(400)                     # must not raise
    states = {eng.requests[r].state for r in rids}
    assert states <= {"done", "cancelled"}
    cancelled = [r for r in rids
                 if eng.requests[r].state == "cancelled"]
    assert cancelled                 # the degraded pool lost real work
    assert all(eng.requests[r].cancel_reason == "capacity"
               for r in cancelled)
    assert eng.stats()["cancelled"] == len(cancelled)


def test_tracing_off_by_default(served):
    """EngineConfig.trace=False must leave the engine on the disabled
    global tracer and record nothing."""
    eng = make_engine(served)
    rng = np.random.default_rng(0)
    eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 8),
                          max_new_tokens=2))
    eng.run(50)
    assert not eng.trace.enabled
    assert len(eng.trace.spans()) == 0
    # per-tenant histograms still collect (cheap, always on)
    assert eng.stats()["latency"]["serve.ttft.default"]["count"] == 1


def test_page_table_overflow_raises(served):
    """Regression: a sequence outgrowing its page table used to be
    silently truncated (numpy slice clamping dropped the tail pages) —
    attention would read garbage for every token past the table edge.
    Both the scalar and the batched export must raise instead."""
    cfg, *_ = served
    kv = PagedKVStore(cfg=cfg, system=fresh_system(), device_id="tpu0",
                      page_tokens=4, onboard_pages=4)
    sid = kv.new_seq()
    L, KV_, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    kv.append_tokens(sid, jnp.ones((L, 2, 10, KV_, hd),
                                   jnp.dtype(cfg.dtype)))   # 3 pages
    with pytest.raises(ValueError, match="exceed"):
        kv.page_table(sid, 2)
    with pytest.raises(ValueError, match="exceed"):
        kv.page_tables([sid], 2)
    # exact fit and slack are both fine
    assert (kv.page_table(sid, 3) >= 0).all()
    tables, lengths = kv.page_tables([sid], 5)
    assert tables.shape == (1, 5)
    assert (tables[0, :3] >= 0).all() and (tables[0, 3:] == -1).all()
    assert lengths[0] == 10


def test_gather_seq_trims_to_length(served):
    """Regression: gather_seq used to return n_pages*page_tokens tokens
    with an uninitialized tail and no valid-length signal; it must trim
    to the sequence's true length."""
    cfg, *_ = served
    kv = PagedKVStore(cfg=cfg, system=fresh_system(), device_id="tpu0",
                      page_tokens=4, onboard_pages=4)
    sid = kv.new_seq()
    L, KV_, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    data = jnp.arange(L * 2 * 10 * KV_ * hd, dtype=jnp.dtype(cfg.dtype)) \
        .reshape(L, 2, 10, KV_, hd)
    kv.append_tokens(sid, data)
    got = kv.gather_seq(sid)
    assert got.shape == (L, 2, 10, KV_, hd)      # not padded to 12
    np.testing.assert_array_equal(np.asarray(got), np.asarray(data))


def test_paged_decode_serves_identical_tokens(served):
    """The tentpole contract: with paged_decode on (the default), every
    decode round runs ONE batched paged-attention step against the
    paged pool, and the emitted token streams are byte-identical to the
    dense slot-cache path."""
    from repro.kernels import ops as kops

    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 100, n).astype(np.int32)
               for n in (5, 13, 20, 9, 17)]

    def serve(paged):
        eng = make_engine(served, paged_decode=paged, trace=paged)
        rids = [eng.submit(SubmitSpec(prompt=p, max_new_tokens=6))
                for p in prompts]
        eng.run(300)
        toks = [eng.requests[r].out_tokens for r in rids]
        assert all(eng.requests[r].state == "done" for r in rids)
        return toks, eng

    dense_toks, dense_eng = serve(False)
    before = kops.paged_attention_decode_traces()
    paged_toks, paged_eng = serve(True)
    assert paged_toks == dense_toks              # byte-identical streams
    # the paged kernel path actually served the rounds
    assert dense_eng.paged_rounds == 0
    assert paged_eng.paged_rounds > 0
    assert kops.paged_attention_decode_traces() > before
    assert paged_eng.stats()["decode_path"] == "paged"
    assert dense_eng.stats()["decode_path"] == "dense"
    # ...and left its span in the trace
    names = [s.name for s in paged_eng.trace.spans()]
    assert "decode.paged" in names
    # the dense handoff cache is retired on the paged path
    assert all(r._cache is None for r in paged_eng.requests.values())


def test_paged_round_spans_nest_and_count_hbm_copies(served):
    """With tracing on, a paged round's spans nest as the chip path
    runs: decode.paged under serve.round, kv.view under it, the view's
    read_many burst under that, and every executor op under a
    LinkedBuffer call.  The round's kv.hbm_copy_bytes is the bytes of
    its HBM array ops, by hand from the shapes."""
    from repro.serve.kv_cache import HBM_COPY_BYTES

    eng = make_engine(served, trace=True)       # 2 slots, 8 onboard pages
    for n in (5, 13):                           # 1 and 2 pages of 8 tokens
        eng.submit(SubmitSpec(prompt=np.arange(1, n + 1), max_new_tokens=6))
    eng.step()                                  # both prefills, a round
    mark = len(eng.trace.spans())
    before = eng.metrics.counter(HBM_COPY_BYTES)
    eng.step()               # lengths 6 and 14: no new page, no fault
    copied = eng.metrics.counter(HBM_COPY_BYTES) - before

    spans = eng.trace.spans()
    by_id = {s.span_id: s for s in spans}
    parent = lambda s: by_id[s.parent_id].name  # noqa: E731
    (rnd,) = [s for s in spans[mark:] if s.name == "serve.round"]
    (dec,) = [s for s in spans[mark:] if s.name == "decode.paged"]
    assert dec.parent_id == rnd.span_id
    assert dec.args == {"batch": 2, "pages": 3, "pool": 8}
    (view,) = [s for s in spans[mark:] if s.name == "kv.view"]
    assert view.parent_id == dec.span_id
    assert [parent(s) for s in spans[mark:]
            if s.name == "lmb.read_many"] == ["kv.view"]
    for name in ("engine.sync", "kv.commit"):
        assert [parent(s) for s in spans[mark:] if s.name == name] == [
            "decode.paged"]
    assert {parent(s) for s in spans[mark:]
            if s.name in ("engine.emit", "engine.tail")} == {"serve.round"}

    def ancestors(s):
        while s.parent_id is not None:
            s = by_id[s.parent_id]
            yield s.name

    execs = [s for s in spans if s.name.startswith("exec.")]
    assert execs
    assert all(any(n.startswith("lmb.") for n in ancestors(s))
               for s in execs)

    # gather of the 3-page union, 5 zero pages and the padded pool of 8,
    # the 8-page pool the step returns, the 2 tail rows and their
    # gather in write_many, then the scatter over all 8 onboard pages
    pb = eng.kv.buf.page_bytes
    assert copied == pb * (3 + 5 + 8 + 8 + 2 + 2 + 8)
    assert rnd.args["hbm_copy_bytes"] == copied


def test_rounds_under_a_profiler_session_are_traced(served, tmp_path):
    """With tracing off, the rounds that run while a JAX profiler
    session records are traced into the engine's tracer, which is off
    again after each; rounds outside the session record nothing."""
    eng = make_engine(served)
    tr = eng.trace
    assert not tr.enabled
    tr.clear()
    eng.submit(SubmitSpec(prompt=np.arange(1, 10), max_new_tokens=4))
    eng.step()
    assert len(tr) == 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
        assert not tr.enabled
    finally:
        jax.profiler.stop_trace()
        traced = tr.spans()
        tr.clear()
    eng.run(50)
    assert len(tr) == 0
    assert [s.name for s in traced if s.parent_id is None] == ["serve.round"]
    assert "decode.paged" in {s.name for s in traced}


def test_paged_decode_spills_past_onboard(served):
    """Paged decode with a working set far beyond the onboard tier: the
    DecodeView's coalesced read bursts wave through onboard capacity and
    requests still complete (the capacity thesis on the new data path)."""
    eng = make_engine(served, decode_slots=4, onboard_pages=4)
    assert eng._use_paged
    rng = np.random.default_rng(8)
    rids = [eng.submit(SubmitSpec(prompt=rng.integers(0, 100, 20),
                                  max_new_tokens=6))
            for _ in range(6)]
    eng.run(400)
    assert all(eng.requests[r].state == "done" for r in rids)
    c = eng.kv.buf.metrics.tier(eng.kv.buf.name, "onboard")
    assert c.misses > 0              # spill traffic actually happened
