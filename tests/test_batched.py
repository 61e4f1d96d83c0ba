"""Batched data path: scalar/batched equivalence + bulk machinery.

The batched engine (read_many/write_many, _evict_many, coalesced chunk
runs, meter_transfer_many) must be a pure performance transform: same
bytes over the same links, bit-identical page contents, and the same
LOGICAL page-table state as the scalar loop.  Physical LMB slot numbers
are not part of the logical state (a burst may recycle its own sources'
slots in a different order than the scalar interleave), so equivalence
here is: per-page tier, per-page onboard slot, LMB placement counts,
owned LMB bytes, metrics counters, metered link bytes — and strictly
FEWER arbiter round-trips.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import system_for
from repro.core.metrics import Metrics
from repro.core.policy import LRU, Clock, CostAwareLRU
from repro.core.pool import OutOfMemory

PAGE = (4, 4)


def make_pair(policy="lru", compress=False, n_pages=24, onboard=8,
              chunk=32, n_expanders=1):
    """Two identically-prepared (system, buffer) twins: every page
    written once, cold pages spilled to the LMB tier."""
    out = []
    for _ in range(2):
        metrics = Metrics()
        system = system_for("d0", host_id="h0", pool_gib=1,
                            page_bytes=4096, n_expanders=n_expanders,
                            metrics=metrics)
        buf = system.buffer(name="eq", device_id="d0", page_shape=PAGE,
                            dtype=jnp.float32, onboard_pages=onboard,
                            lmb_chunk_pages=chunk, policy=policy,
                            compress_lmb=compress, metrics=metrics)
        pages = buf.append_pages(n_pages)
        for p in pages:
            buf.write(p, jnp.full(PAGE, 1.0 + p, jnp.float32))
        out.append((system, buf, metrics))
    return out


def arbiter_bytes(system):
    snap = system.fm.arbiter.snapshot()["tenants"]
    return snap.get("d0", {}).get("bytes_total", 0)


def assert_logical_state_equal(sysA, bufA, mA, sysB, bufB, mB):
    for p, (ea, eb) in enumerate(zip(bufA._pages, bufB._pages)):
        assert ea.tier == eb.tier, f"page {p} tier {ea.tier}!={eb.tier}"
        if ea.tier == "onboard":
            assert ea.slot == eb.slot, f"page {p} onboard slot"
    assert bufA.lmb_placement() == bufB.lmb_placement()
    assert (sysA.host().owned_bytes("d0")
            == sysB.host().owned_bytes("d0"))
    ca, cb = mA.tier("eq", "onboard"), mB.tier("eq", "onboard")
    assert (ca.hits, ca.misses) == (cb.hits, cb.misses)
    la, lb = mA.tier("eq", "lmb"), mB.tier("eq", "lmb")
    assert (la.bytes_in, la.bytes_out) == (lb.bytes_in, lb.bytes_out)
    assert arbiter_bytes(sysA) == arbiter_bytes(sysB)
    bufA.check_invariants()
    bufB.check_invariants()


@pytest.mark.parametrize("policy", ["lru"])
@pytest.mark.parametrize("compress", [False, True])
def test_read_many_equivalence(policy, compress):
    """gather(batch) == [read(p) for p in batch]: contents bit-identical,
    metered bytes identical, logical page table identical, fewer arbiter
    calls — including eviction traffic and duplicate pages.  (LRU only:
    cost-aware's clean-page preference makes the SCALAR interleave evict
    pages faulted earlier in the same gather — see the anti-self-thrash
    test below for that deliberate batched improvement.)"""
    (sysA, bufA, mA), (sysB, bufB, mB) = make_pair(policy, compress)
    batch = list(range(8)) + [2, 0]          # LMB-resident + dups
    calls0 = (sysA.fm.meter_calls(), sysB.fm.meter_calls())
    scalar = jnp.stack([bufA.read(p) for p in batch])
    batched = bufB.read_many(batch)
    scalar_calls = sysA.fm.meter_calls() - calls0[0]
    batched_calls = sysB.fm.meter_calls() - calls0[1]
    assert np.array_equal(np.asarray(scalar), np.asarray(batched))
    assert_logical_state_equal(sysA, bufA, mA, sysB, bufB, mB)
    assert batched_calls < scalar_calls
    # follow-up reads see the same world
    assert np.array_equal(np.asarray(bufA.read(20)),
                          np.asarray(bufB.read(20)))


@pytest.mark.parametrize("compress", [False, True])
def test_write_many_equivalence(compress):
    """write_many == scalar write loop (mixed onboard/LMB/fresh targets,
    duplicate page: last write wins)."""
    (sysA, bufA, mA), (sysB, bufB, mB) = make_pair(compress=compress)
    fresh = bufA.append_pages(2), bufB.append_pages(2)
    targets = [0, 1, 20, fresh[0][0], 0]      # dup of page 0
    datas = [jnp.full(PAGE, 100.0 + i, jnp.float32)
             for i in range(len(targets))]
    for p, d in zip(targets, datas):
        bufA.write(p, d)
    bufB.write_many(targets, jnp.stack(datas))
    assert_logical_state_equal(sysA, bufA, mA, sysB, bufB, mB)
    for p in dict.fromkeys(targets):
        assert np.array_equal(np.asarray(bufA.read(p)),
                              np.asarray(bufB.read(p))), p
    # dup semantics: page 0 holds the LAST value
    assert float(np.asarray(bufB.read(0))[0, 0]) == 100.0 + 4


def test_batched_gather_does_not_self_thrash_cost_policy():
    """Seed misbehavior the batched path fixes: under CostAwareLRU the
    scalar gather interleave prefers CLEAN victims, i.e. the pages it
    just faulted in — a K-page gather could demote its own members
    mid-loop.  Batch victims come from the pre-batch resident set, so a
    gather that fits onboard ends with every member onboard."""
    (sysA, bufA, _), (sysB, bufB, _) = make_pair("cost")
    batch = list(range(8))                    # LMB-resident, == onboard cap
    scalar = jnp.stack([bufA.read(p) for p in batch])
    batched = bufB.read_many(batch)
    assert np.array_equal(np.asarray(scalar), np.asarray(batched))
    assert all(bufB._pages[p].tier == "onboard" for p in batch)
    # the scalar loop re-demoted at least one just-faulted batch member
    assert any(bufA._pages[p].tier == "lmb" for p in batch)
    bufA.check_invariants()
    bufB.check_invariants()


def test_read_many_wave_exceeding_onboard_capacity():
    """A batch larger than the onboard tier thrashes in waves but returns
    every page's correct contents."""
    (_, bufA, _), (sysB, bufB, _) = make_pair(n_pages=24, onboard=4)
    batch = list(range(24))
    scalar = jnp.stack([bufA.read(p) for p in batch])
    batched = bufB.read_many(batch)
    assert np.array_equal(np.asarray(scalar), np.asarray(batched))
    bufB.check_invariants()
    assert sum(1 for e in bufB._pages if e.tier == "onboard") <= 4


def test_write_many_wave_exceeding_onboard_keeps_scalar_dirty_state():
    """Multi-wave write_many: pages evicted by a later wave must end
    (tier='lmb', dirty=False) exactly like the scalar loop — dirty bits
    are applied per wave, before the next wave can evict."""
    (sysA, bufA, mA), (sysB, bufB, mB) = make_pair(
        "cost", n_pages=8, onboard=4, chunk=32)
    datas = [jnp.full(PAGE, 50.0 + p, jnp.float32) for p in range(8)]
    for p in range(8):
        bufA.write(p, datas[p])
    bufB.write_many(list(range(8)), jnp.stack(datas))
    for p in range(8):
        ea, eb = bufA._pages[p], bufB._pages[p]
        assert (ea.tier, ea.dirty) == (eb.tier, eb.dirty), p
        if hasattr(bufB.policy, "_dirty"):
            assert (p in bufA.policy._dirty) == (p in bufB.policy._dirty)
        assert np.array_equal(np.asarray(bufA.read(p)),
                              np.asarray(bufB.read(p))), p
    bufA.check_invariants()
    bufB.check_invariants()


def test_bulk_eviction_one_policy_call_coalesced_writeback():
    """_evict_many(k) demotes k pages with coalesced write-back: arbiter
    sees ONE call for the whole burst, contents survive."""
    metrics = Metrics()
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=metrics)
    buf = system.buffer(name="bulk", device_id="d0", page_shape=PAGE,
                        onboard_pages=8, lmb_chunk_pages=32,
                        metrics=metrics)
    pages = buf.append_pages(8)
    for p in pages:
        buf.write(p, jnp.full(PAGE, 7.0 + p, jnp.float32))
    calls0 = system.fm.meter_calls()
    freed = buf._evict_many(6)
    assert len(freed) == len(set(freed)) == 6
    assert system.fm.meter_calls() - calls0 == 1      # one burst charge
    assert sum(1 for e in buf._pages if e.tier == "lmb") == 6
    buf._onboard_free.extend(freed)   # what the batch-fault caller does
    buf.check_invariants()
    for p in pages:                                   # contents intact
        assert float(np.asarray(buf.read(p))[0, 0]) == 7.0 + p


@pytest.mark.parametrize("policy_cls", [LRU, Clock, CostAwareLRU])
def test_victims_matches_sequential_selection(policy_cls):
    """policy.victims(k) == k successive victim()+on_remove() picks."""
    a, b = policy_cls(), policy_cls()
    for pol in (a, b):
        for key in range(10):
            pol.on_insert(key)
        pol.on_access(3)
        pol.pin(0)
        if hasattr(pol, "mark_dirty"):
            pol.mark_dirty(1)
            pol.mark_dirty(4)
    bulk = a.victims(5)
    seq = []
    for _ in range(5):
        v = b.victim()
        seq.append(v)
        b.on_remove(v)
    assert bulk == seq
    if policy_cls is not Clock:
        # non-mutating for ordered policies: same picks again.  (Clock's
        # selection legitimately advances ref bits — exactly what the
        # equivalent sequential victim() calls would do.)
        assert a.victims(5) == bulk


def test_evict_many_raises_when_pinned_blocks_batch():
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=Metrics())
    buf = system.buffer(name="pin", device_id="d0", page_shape=PAGE,
                        onboard_pages=4, lmb_chunk_pages=8,
                        metrics=Metrics())
    pages = buf.append_pages(4)
    for p in pages:
        buf.write(p, jnp.ones(PAGE, jnp.float32))
    for p in pages[:3]:
        buf.pin(p)
    with pytest.raises(OutOfMemory):
        buf._evict_many(2)
    buf.check_invariants()                    # failed batch left no debris


def test_heat_epsilon_flushes_cold_pages():
    """Decayed-cold heat entries are zeroed during batch updates, so
    hottest_pages stops nominating pages that went quiet long ago."""
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=Metrics())
    buf = system.buffer(name="heat", device_id="d0", page_shape=PAGE,
                        onboard_pages=2, lmb_chunk_pages=8,
                        metrics=Metrics())
    pages = buf.append_pages(10)
    for p in pages:
        buf.write(p, jnp.ones(PAGE, jnp.float32))
    buf.read(0)
    assert buf.page_heat(0) > 0
    # hammer other pages: page 0's heat decays below epsilon and is
    # flushed to EXACTLY zero by the vectorized batch update
    for _ in range(40):
        buf.read_many([4, 5, 6, 7])
    assert buf.page_heat(0) == 0.0
    assert 0 not in buf.hottest_pages(10, min_heat=buf.heat_epsilon)
    hot = buf.hottest_pages(2, min_heat=buf.heat_epsilon)
    assert all(buf.page_heat(h) > 0 for h in hot)


def test_per_expander_free_lists():
    """Free slots are kept per expander: placement-restricted allocation
    pops O(1) from the right list and never crosses homes."""
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        n_expanders=2, metrics=Metrics())
    buf = system.buffer(name="fl", device_id="d0", page_shape=PAGE,
                        onboard_pages=2, lmb_chunk_pages=4,
                        metrics=Metrics())
    pages = buf.append_pages(10)
    for p in pages:
        buf.write(p, jnp.full(PAGE, float(p), jnp.float32))
    lmb_pages = [p for p in pages if buf._pages[p].tier == "lmb"]
    other = 1 if buf.page_expander(lmb_pages[0]) == 0 else 0
    moved = buf.migrate_pages(lmb_pages[:3], other)
    assert moved == 3
    for eid, lst in buf._lmb_free.items():
        for s in lst:
            assert buf._lmb_homes[s // buf._lmb_chunk_pages] == eid
    slot = buf._lmb_slot_alloc(expander_id=other)
    assert buf._lmb_homes[slot // buf._lmb_chunk_pages] == other
    buf._lmb_slot_free(slot)
    buf.check_invariants()
    for p in lmb_pages[:3]:                   # contents survived the move
        assert float(np.asarray(buf.read(p))[0, 0]) == p


def test_migrate_pages_batched_meters_both_links():
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        n_expanders=2, metrics=Metrics())
    buf = system.buffer(name="mig", device_id="d0", page_shape=PAGE,
                        onboard_pages=2, lmb_chunk_pages=4,
                        metrics=Metrics())
    pages = buf.append_pages(10)
    for p in pages:
        buf.write(p, jnp.ones(PAGE, jnp.float32))
    lmb_pages = [p for p in pages if buf._pages[p].tier == "lmb"][:4]
    src = buf.page_expander(lmb_pages[0])
    dst = 1 - src
    calls0 = system.fm.meter_calls()
    before = {e: system.fm._arbiters[e].snapshot()["tenants"]
              .get("d0", {}).get("bytes_total", 0) for e in (0, 1)}
    moved = buf.migrate_pages(lmb_pages, dst)
    after = {e: system.fm._arbiters[e].snapshot()["tenants"]
             .get("d0", {}).get("bytes_total", 0) for e in (0, 1)}
    assert moved == len(lmb_pages)
    assert after[src] - before[src] == moved * buf.lmb_page_bytes
    assert after[dst] - before[dst] == moved * buf.lmb_page_bytes
    # one arbiter round-trip per touched link, not per page
    assert system.fm.meter_calls() - calls0 <= 2


def test_degraded_mode_batched_paths():
    """After total expander loss: never-written pages still batch-read as
    zeros onboard; a batch that would need the LMB tier raises."""
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=Metrics())
    buf = system.buffer(name="deg", device_id="d0", page_shape=PAGE,
                        onboard_pages=4, lmb_chunk_pages=8,
                        metrics=Metrics())
    pages = buf.append_pages(8)
    for p in pages:
        buf.write(p, jnp.full(PAGE, float(p), jnp.float32))
    system.inject_failure()
    assert buf.degraded
    # pages 4..7 survived onboard; 0..3 were LMB-resident and are gone
    got = buf.read_many(pages[4:])            # pure onboard hits
    assert np.asarray(got)[:, 0, 0].tolist() == [4.0, 5.0, 6.0, 7.0]
    buf.check_invariants()
    with pytest.raises(OutOfMemory):
        buf.read_many(pages[:4])              # needs eviction to dead LMB
    buf.check_invariants()


def test_batch_hits_guarded_from_same_batch_eviction():
    """A batch's onboard hits must survive the batch's own evictions:
    under CostAwareLRU a clean hit page was the preferred victim, and
    read_many returned another page's contents through its stale slot."""
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=Metrics())
    buf = system.buffer(name="guard", device_id="d0", page_shape=PAGE,
                        onboard_pages=4, policy="cost",
                        lmb_chunk_pages=8, metrics=Metrics())
    pages = buf.append_pages(8)
    for p in pages:
        buf.write(p, jnp.full(PAGE, float(p), jnp.float32))
    # page 0: onboard + CLEAN (re-read), pages 5,6,7 onboard + dirty
    buf.read(0)
    onboard = [p for p in pages if buf._pages[p].tier == "onboard"]
    assert 0 in onboard
    lmb_page = next(p for p in pages if buf._pages[p].tier == "lmb")
    got = buf.read_many([0, lmb_page])
    assert float(np.asarray(got)[0, 0, 0]) == 0.0          # not corrupted
    assert float(np.asarray(got)[1, 0, 0]) == lmb_page
    assert buf._pages[0].tier == "onboard"                 # hit survived
    # the guard is transient: page 0 is evictable again afterwards
    assert 0 not in buf.policy._pinned()
    buf.check_invariants()


def test_migrate_pages_duplicate_ids():
    """Duplicate page ids in one migrate batch move once (the scalar
    loop skipped the repeat because its home had already changed)."""
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        n_expanders=2, metrics=Metrics())
    buf = system.buffer(name="dup", device_id="d0", page_shape=PAGE,
                        onboard_pages=2, lmb_chunk_pages=4,
                        metrics=Metrics())
    pages = buf.append_pages(8)
    for p in pages:
        buf.write(p, jnp.full(PAGE, float(p), jnp.float32))
    lmb_page = next(p for p in pages if buf._pages[p].tier == "lmb")
    dst = 1 - buf.page_expander(lmb_page)
    moved = buf.migrate_pages([lmb_page, lmb_page, lmb_page], dst)
    assert moved == 1
    assert buf.page_expander(lmb_page) == dst
    buf.check_invariants()
    assert float(np.asarray(buf.read(lmb_page))[0, 0]) == lmb_page


def test_pin_many_overflow_raises():
    """pin_many of more pages than the onboard tier raises (the scalar
    loop did too) instead of silently 'pinning' LMB-resident pages."""
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=Metrics())
    buf = system.buffer(name="pov", device_id="d0", page_shape=PAGE,
                        onboard_pages=2, lmb_chunk_pages=8,
                        metrics=Metrics())
    pages = buf.append_pages(4)
    for p in pages:
        buf.write(p, jnp.ones(PAGE, jnp.float32))
    with pytest.raises(OutOfMemory):
        buf.pin_many(pages)
    buf.check_invariants()
    buf.pin_many(pages[:2])                   # exactly capacity is fine
    assert all(buf._pages[p].tier == "onboard" for p in pages[:2])
    buf.unpin_many(pages[:2])


def test_read_many_under_pin_pressure_waves_through_remainder():
    """Pins shrink the batch-usable capacity, they must not make gather
    raise: the scalar loop thrashed a working set through the unpinned
    remainder one page at a time, so read_many waves at that size."""
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=Metrics())
    buf = system.buffer(name="pp", device_id="d0", page_shape=PAGE,
                        onboard_pages=4, lmb_chunk_pages=8,
                        metrics=Metrics())
    pages = buf.append_pages(8)
    for p in pages:
        buf.write(p, jnp.full(PAGE, float(p), jnp.float32))
    onboard = [p for p in pages if buf._pages[p].tier == "onboard"]
    lmb = [p for p in pages if buf._pages[p].tier == "lmb"]
    buf.pin_many(onboard[:3])                 # 1 unpinned slot remains
    got = buf.read_many(lmb[:2])              # scalar could; batch must
    assert np.asarray(got)[:, 0, 0].tolist() == [float(p) for p in lmb[:2]]
    buf.check_invariants()
    assert all(buf._pages[p].tier == "onboard" for p in onboard[:3])
    buf.unpin_many(onboard[:3])


def test_read_many_with_pinned_members_in_large_batch():
    """Pinned pages that are MEMBERS of an oversized batch: they hold
    their slots through every wave (the scalar loop read them as plain
    hits), so the gather must succeed and return correct contents."""
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=Metrics())
    buf = system.buffer(name="pm", device_id="d0", page_shape=PAGE,
                        onboard_pages=10, lmb_chunk_pages=16,
                        metrics=Metrics())
    pages = buf.append_pages(15)
    for p in pages:
        buf.write(p, jnp.full(PAGE, float(p), jnp.float32))
    onboard = [p for p in pages if buf._pages[p].tier == "onboard"]
    buf.pin_many(onboard[:5])
    got = buf.read_many(pages)                # scalar loop succeeded too
    assert np.asarray(got)[:, 0, 0].tolist() == [float(p) for p in pages]
    assert all(buf._pages[p].tier == "onboard" for p in onboard[:5])
    buf.check_invariants()
    buf.unpin_many(onboard[:5])


def test_duplicate_occurrence_recency_matches_scalar():
    """read_many([a, b, a]): the repeat of `a` must bump its recency
    AFTER insertion (scalar order insert-insert-access), so the next
    eviction victim is `b`, not `a`."""
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=Metrics())
    buf = system.buffer(name="rec", device_id="d0", page_shape=PAGE,
                        onboard_pages=2, lmb_chunk_pages=8,
                        metrics=Metrics())
    pages = buf.append_pages(5)
    for p in pages:
        buf.write(p, jnp.full(PAGE, float(p), jnp.float32))
    a, b = [p for p in pages if buf._pages[p].tier == "lmb"][:2]
    buf.read_many([a, b, a])                  # fills both onboard slots
    buf.read(next(p for p in pages
                  if buf._pages[p].tier == "lmb"))   # forces one eviction
    assert buf._pages[b].tier == "lmb"        # LRU victim was b
    assert buf._pages[a].tier == "onboard"    # the dup access kept a hot
    buf.check_invariants()


def test_kv_append_empty_slab_is_noop():
    from repro.configs.base import get_config
    from repro.serve.kv_cache import PagedKVStore
    cfg = get_config("qwen2-1.5b").reduced()
    system = system_for("tpu0", host_id="h0", pool_gib=1,
                        page_bytes=4096, metrics=Metrics())
    store = PagedKVStore(cfg=cfg, system=system, device_id="tpu0",
                         page_tokens=4, onboard_pages=4)
    sid = store.new_seq()
    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    empty = jnp.zeros((L, 2, 0, KV, hd), jnp.dtype(cfg.dtype))
    store.append_tokens(sid, empty)
    assert store.seq(sid).length == 0 and store.seq(sid).pages == []


def test_share_many_and_pin_many():
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=Metrics())
    buf = system.buffer(name="sp", device_id="d0", page_shape=PAGE,
                        onboard_pages=4, lmb_chunk_pages=8,
                        metrics=Metrics())
    pages = buf.append_pages(6)
    for p in pages:
        buf.write(p, jnp.full(PAGE, float(p), jnp.float32))
    shared = buf.share_many(pages[:3])
    assert shared == pages[:3]
    assert all(buf._pages[p].refcount == 2 for p in shared)
    buf.pin_many(pages[:4])
    assert all(buf._pages[p].tier == "onboard" for p in pages[:4])
    with pytest.raises(OutOfMemory):          # everything onboard pinned
        buf.read(pages[4])
    buf.unpin_many(pages[:4])
    buf.read(pages[4])                        # eviction possible again
    buf.check_invariants()


def test_meter_transfer_many_merges_per_link():
    """LMBHost.meter_transfer_many: one arbiter call per backing
    expander, byte totals unchanged."""
    system = system_for("d0", host_id="h0", pool_gib=1, page_bytes=4096,
                        metrics=Metrics())
    host = system.host()
    a = host.alloc("d0", 1 << 16)
    b = host.alloc("d0", 1 << 16)
    calls0 = system.fm.meter_calls()
    bytes0 = arbiter_bytes(system)
    host.meter_transfer_many("d0", [(4096, a.mmid), (8192, b.mmid)])
    assert system.fm.meter_calls() - calls0 == 1      # single expander
    assert arbiter_bytes(system) - bytes0 == 4096 + 8192
    # unattributed charges (mmid=None) ride the fallback link as their
    # own group; zero-byte charges are dropped
    calls0 = system.fm.meter_calls()
    host.meter_transfer_many("d0", [(4096, None), (0, a.mmid),
                                    (4096, a.mmid)])
    assert system.fm.meter_calls() - calls0 == 2


PT = 4                                        # tokens per KV page


def kv_store(onboard_pages=8, tracer=None, page_tokens=PT):
    """A reduced-width PagedKVStore on a fresh system; ``tracer``
    becomes the fabric's (and so the store's) span tracer."""
    from repro.configs.base import get_config
    from repro.serve.kv_cache import PagedKVStore
    cfg = get_config("qwen2-1.5b").reduced()
    system = system_for("tpu0", host_id="h0", pool_gib=1,
                        page_bytes=4096, metrics=Metrics())
    if tracer is not None:
        system.host().fm.tracer = tracer
    return PagedKVStore(cfg=cfg, system=system, device_id="tpu0",
                        page_tokens=page_tokens,
                        onboard_pages=onboard_pages)


def kv_tokens(store, rng, n):
    L, _, _, KV, hd = store.page_shape
    return jnp.asarray(rng.standard_normal((L, 2, n, KV, hd)),
                       store.buf.dtype)


def gathered(store, sid):
    return np.asarray(store.gather_seq(sid))


@pytest.mark.parametrize("slab", [1, PT - 1, PT, PT + 1, 3 * PT + 3])
@pytest.mark.parametrize("start", [0, 1, PT - 1, PT])
@pytest.mark.parametrize("kind", ["onboard", "spill", "fork"])
def test_kv_append_slab_equals_token_loop(kind, start, slab):
    """One slab appended to a sequence of ``start`` tokens == the same
    tokens appended one by one: every token lands in the same page cell.
    ``spill``: 2 onboard pages, and a filler sequence pushes the
    sequence's pages to the LMB tier first, so a mid-page slab faults
    its first page in.  ``fork``: the slab goes to a fork of the
    sequence (its partial tail copied, not written through the share),
    and the parent is unchanged by it, and the fork by the parent's next
    token."""
    rng = np.random.default_rng(1000 * start + slab)
    onboard = 2 if kind == "spill" else 8
    stores = [kv_store(onboard) for _ in range(2)]
    prefix = kv_tokens(stores[0], rng, start)
    filler = kv_tokens(stores[0], rng, 3 * PT)
    kv = kv_tokens(stores[0], rng, slab)
    more = kv_tokens(stores[0], rng, 1)
    sids, parents = [], []
    for store in stores:
        sid = store.new_seq()
        store.append_tokens(sid, prefix)
        if kind == "spill":
            store.append_tokens(store.new_seq(), filler)
            if start % PT:
                assert store.buf.tier_of(store.seq(sid).pages[-1]) == "lmb"
        if kind == "fork":
            parents.append((sid, gathered(store, sid)))
            sid = store.fork(sid)
        sids.append(sid)
    stores[0].append_tokens(sids[0], kv)              # one slab
    for t in range(slab):                             # token loop
        stores[1].append_tokens(sids[1], kv[:, :, t:t + 1])
    for store, sid in zip(stores, sids):
        assert store.seq(sid).length == start + slab
        assert len(store.seq(sid).pages) == -(-(start + slab) // PT)
    want = gathered(stores[1], sids[1])
    assert np.array_equal(gathered(stores[0], sids[0]), want)
    for store, sid, (parent, before) in zip(stores, sids, parents):
        assert np.array_equal(gathered(store, parent), before)
        store.append_tokens(parent, more)
        assert np.array_equal(gathered(store, sid), want)
    for store in stores:
        store.buf.check_invariants()


def test_kv_append_counts_packed_pages_reads_and_hbm_bytes():
    """A prefill slab over n pages: the packing call builds n pages and
    nothing is read when the slab starts a sequence; one page is read
    when it starts mid-page.  The counters and the ``kv.append`` span's
    args say so, and ``kv.hbm_copy_bytes`` is the bytes of the append's
    HBM array ops, by hand from the shapes."""
    from repro.obs.trace import SpanTracer
    from repro.serve.kv_cache import (APPEND_PAGES_PACKED,
                                      APPEND_PAGES_READ, HBM_COPY_BYTES)
    tr = SpanTracer()
    P = 16                                    # onboard pages: the pool
    store = kv_store(P, tracer=tr)
    m, pb = store.metrics, store.buf.page_bytes
    rng = np.random.default_rng(0)

    def append(sid, n_tokens):
        before = (m.counter(HBM_COPY_BYTES), m.counter(APPEND_PAGES_PACKED),
                  m.counter(APPEND_PAGES_READ))
        store.append_tokens(sid, kv_tokens(store, rng, n_tokens))
        after = (m.counter(HBM_COPY_BYTES), m.counter(APPEND_PAGES_PACKED),
                 m.counter(APPEND_PAGES_READ))
        (span,) = [s for s in tr.spans() if s.name == "kv.append"][-1:]
        return [b - a for a, b in zip(before, after)], span.args

    # fresh: 3 pages.  The packed pages; in write_many the fault of the
    # 3 fresh pages (a zero page, their stack, the scatter over the
    # pool), the gather of the rows, the scatter over the pool
    sid = store.new_seq()
    (copied, packed, read), args = append(sid, 3 * PT - 1)
    assert (packed, read) == (3, 0)
    assert args == {"tokens": 3 * PT - 1, "pages": 3, "read": 0}
    assert copied == pb * (3 + (1 + 3 + P) + 3 + P)
    # mid-page: 1 + 2 * PT tokens into the third page's last slot, 3
    # pages.  The read of that page; the packed pages; in write_many the
    # fault of the 2 fresh pages, the gather of the 3 rows, the scatter
    (copied, packed, read), args = append(sid, 1 + 2 * PT)
    assert (packed, read) == (3, 1)
    assert args == {"tokens": 1 + 2 * PT, "pages": 3, "read": 1}
    assert copied == pb * (1 + 3 + (1 + 2 + P) + 3 + P)
    assert store.seq(sid).length == 5 * PT


def test_kv_append_builds_one_pack_program_per_slab_shape():
    """Slabs of one length appended to new sequences, and at two
    offsets inside a page: :func:`pack_slab` builds a program for the
    first slab of each shape and page count, and none after (the offset
    is traced, not static)."""
    import jax
    from repro.serve.kv_cache import pack_slab
    built = []

    def on_compile(event, secs, fun_name=None, **_):
        if (event == "/jax/core/compile/backend_compile_duration"
                and fun_name == f"jit({pack_slab.__name__})"):
            built.append(fun_name)

    pt = 5                  # a page size no other test packs with
    store = kv_store(64, page_tokens=pt)
    rng = np.random.default_rng(0)
    T = 2 * pt + 2                            # 3 pages from offsets 0-3
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        counts = []
        for start in (0, 0, 0, 1, 3, 1):
            sid = store.new_seq()
            if start:
                store.append_tokens(sid, kv_tokens(store, rng, start))
            store.append_tokens(sid, kv_tokens(store, rng, T))
            counts.append(len(built))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    # one build for the fresh slab, one for the slab with a page to merge
    assert counts == [1, 1, 1, 2, 2, 2]
