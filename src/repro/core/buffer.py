"""LinkedBuffer: a logical paged array spanning onboard memory and the LMB.

This is the consumer-facing realization of the paper's idea: a device whose
working set exceeds onboard memory sees one flat buffer; hot pages live in
the **onboard tier** (a bounded device pool — HBM on TPU), cold pages live in
the **LMB tier** (expander-backed, allocated through the Table-2 API).  The
page table plays the role the L2P table plays in the SSD: every access
resolves logical page → (tier, slot) host-side (allocator metadata stays in
host memory, §3.2), then the data path touches exactly one tier.

Capabilities:
  * demand paging with pluggable eviction (LRU/CLOCK/cost-aware) + prefetch
  * **batched data path** — :meth:`read_many` / :meth:`write_many` resolve
    the page table up front, group the pages into per-(chunk, expander)
    runs, and move each run as ONE coalesced transfer with ONE link-arbiter
    charge for the burst (real CXL/PCIe stacks amortize doorbells and
    arbitration over bursts; the scalar path pays them per page).  Bulk
    eviction (:meth:`_evict_many`) frees K onboard slots with one policy
    call and coalesced per-chunk write-back bursts.
  * dirty tracking with write-back (single-writer "uncached" semantics — the
    paper's PCIe devices don't participate in coherence, and neither do we:
    ownership transfer is explicit)
  * pin/unpin for pages a compiled step will touch (DMA in flight)
  * **in-place access** — :meth:`onboard_slots` faults a batch onboard and
    returns its slots in :attr:`onboard_array`, which a compiled step
    then reads and writes in place (donating it); :meth:`replace_onboard`
    installs the array the step returns and marks the written pages
    dirty — no gather, no scatter
  * refcounted page sharing + copy-on-write (zero-copy prefix sharing, the
    paper's SSD→accelerator shared-buffer scenario)
  * degraded mode on expander failure (availability: fall back to
    onboard-only, shedding capacity rather than dying); on a pooled
    fabric a partial failure only invalidates the pages homed on the
    dead expander
  * optional **int8 page compression on demotion** (``compress_lmb``) —
    beyond-paper: cold pages cost 1/4 the pool bytes and PCIe traffic
    (per-page absmax scale kept in HOST metadata, like all LMB metadata);
    lossy (~1e-2 relative) — suited to KV caches, not optimizer state
  * **spans at the tier's boundary** — with the FM's tracer on, every
    public data-path call (:meth:`read`, :meth:`write`, :meth:`read_many`,
    :meth:`write_many`, :meth:`onboard_slots`, :meth:`replace_onboard`,
    :meth:`append_pages`, :meth:`release`, :meth:`schedule_prefetch`,
    :meth:`note_compute_window`) runs under an
    ``lmb.<call>`` span; ``fault``/``fault.batch``, ``evict.batch``,
    ``prefetch.burst`` and the executor's ``exec.*`` spans nest inside
  * **per-page access heat** (exponentially-decayed touch counters fed by
    the link-metering path, numpy-backed so batch updates are one
    vectorized decay instead of a dict walk; decayed-cold entries are
    flushed to zero so long-lived buffers don't accumulate stale heat)
    + :meth:`migrate_pages`, the mechanism the MigrationEngine
    (repro.qos.migration) uses to move hot LMB pages off a saturated
    expander link onto a cooler one

Batched-vs-scalar equivalence: the batched paths move the same bytes over
the same links, produce bit-identical page contents, and leave the same
logical page-table state as the scalar loop.  Two deliberate improvements:
(1) a batch frees its fault sources *before* allocating eviction
destinations, so a burst can recycle its own sources' slots — the batch
never grows more LMB chunks than the scalar interleave, occasionally
fewer; (2) eviction victims are chosen from the PRE-batch resident set
(one ``policy.victims(k)`` call), so a gather can never demote its own
just-faulted members — the scalar interleave could, and under
CostAwareLRU's clean-page preference routinely did (self-thrash).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import LMBHost
from repro.core.client import MemoryHandle
from repro.core.metrics import Metrics, GLOBAL_METRICS
from repro.core.offload import (HostPool, TierExecutor, stack_pages,
                                tier_of as array_tier)
from repro.core.overlap import OverlapScheduler
from repro.core.policy import EvictionPolicy, Prefetcher, make_policy
from repro.core.pool import OutOfMemory
from repro.obs.trace import SpanTracer

ONBOARD = "onboard"
LMB = "lmb"


def _lmb_span(fn):
    """Run a public LinkedBuffer call under an ``lmb.<name>`` span when
    the FM's tracer is on."""
    name = "lmb." + fn.__name__

    @functools.wraps(fn)
    def traced(self, *args, **kwargs):
        tr = self.host.fm.tracer
        if not tr.enabled:
            return fn(self, *args, **kwargs)
        with tr.span(name):
            return fn(self, *args, **kwargs)
    return traced


@dataclasses.dataclass
class PageEntry:
    tier: Optional[str] = None   # None = never written (implicit zeros)
    slot: int = -1
    dirty: bool = False
    refcount: int = 1


class LinkedBuffer:
    """A paged logical buffer over (onboard pool, LMB pool)."""

    def __init__(self, *,
                 name: str,
                 device_id: str,
                 host: LMBHost,
                 executor: Optional[TierExecutor] = None,
                 page_shape: Tuple[int, ...],
                 dtype=jnp.float32,
                 onboard_pages: int,
                 policy: str | EvictionPolicy = "lru",
                 prefetch_depth: int = 0,
                 prefetch_backlog_factor: int = 8,
                 prefetch_min_burst: Optional[int] = None,
                 overlap: Optional[OverlapScheduler] = None,
                 lmb_chunk_pages: int = 64,
                 compress_lmb: bool = False,
                 metrics: Optional[Metrics] = None):
        self.name = name
        self.device_id = device_id
        self.host = host
        self.executor = executor or TierExecutor(trace=host.fm.tracer)
        self.page_shape = tuple(page_shape)
        self.dtype = dtype
        self.onboard_pages = int(onboard_pages)
        self.compress_lmb = compress_lmb
        self.page_bytes = int(np.prod(self.page_shape)) * jnp.dtype(dtype).itemsize
        #: bytes a page occupies in the LMB tier (int8 + host-side scale)
        self.lmb_page_bytes = (int(np.prod(self.page_shape))
                               if compress_lmb else self.page_bytes)
        self.metrics = metrics or GLOBAL_METRICS
        self.policy: EvictionPolicy = (
            make_policy(policy) if isinstance(policy, str) else policy)
        self.prefetcher = (Prefetcher(prefetch_depth,
                                      prefetch_backlog_factor)
                           if prefetch_depth else None)
        #: hysteresis for STRIDE-source runs: heuristic guesses are held
        #: back until at least this many pages accumulate, so steady-state
        #: lookahead moves chunk-sized bursts (one arbiter charge each)
        #: instead of advancing the frontier one page per access.  Exact
        #: scheduled knowledge is never held back.
        self.prefetch_min_burst = (max(prefetch_depth // 4, 1)
                                   if prefetch_min_burst is None
                                   else max(prefetch_min_burst, 1))
        #: overlap scheduler gating prefetch bursts behind compute; when
        #: set, admitted prefetch wait accrues to ``prefetch_hidden_s``
        #: (it rides under the compute window) instead of link_wait_s
        self.overlap = overlap
        #: pages brought onboard by prefetch, not yet demand-read
        self._prefetched: set = set()
        self.prefetch_bursts = 0
        self.prefetch_pages_total = 0
        self.prefetch_used = 0
        self.prefetch_wasted = 0
        self.prefetch_deferred = 0
        self.prefetch_hidden_s = 0.0
        self.degraded = False
        self._closed = False
        host.fm.on_failover(self._on_failover)
        host.fm.on_repair(self._on_repair)
        # QoS link metering: every byte crossing to/from the LMB tier is
        # charged to this device's share of the expander link.  If the
        # caller's executor carries a meter hook, defer to it to avoid
        # double-charging the same page move.  On a POOLED fabric the
        # buffer always meters itself: only it knows which expander backs
        # the touched chunk, while an executor hook is a bare meter(nbytes)
        # that would dump everything on the fallback link — so don't bind
        # an executor meter over a multi-expander FM.
        pooled = len(host.fm.healthy_expander_ids()) > 1
        if pooled and self.executor.meter is not None:
            raise ValueError(
                f"{name}: an executor-level meter hook cannot attribute "
                "transfers to an expander on a pooled fabric (and the "
                "buffer's own per-block metering would double-charge); "
                "construct the TierExecutor without meter= and let the "
                "buffer meter")
        self._meter_via_executor = self.executor.meter is not None
        self.link_wait_s = 0.0

        # pools
        self._onboard_pool = self.executor.alloc_pool(
            self.onboard_pages, self.page_shape, dtype, tier="onboard")
        self._onboard_free: List[int] = list(range(self.onboard_pages))[::-1]
        self._onboard_owner: Dict[int, int] = {}  # slot -> logical page

        self._lmb_chunk_pages = lmb_chunk_pages
        self._lmb_scales: Dict[int, float] = {}   # slot -> absmax scale
        self._lmb_pools: List[Optional[HostPool]] = []  # None = reclaimed
        #: per-chunk capability for the backing LMB allocation
        self._lmb_allocs: List[Optional[MemoryHandle]] = []
        #: per-expander free lists (LIFO): expander id -> free lmb slots.
        #: Replaces the old flat list whose expander-filtered allocation
        #: was an O(n) scan — migration placement now pops O(1).
        self._lmb_free: Dict[int, List[int]] = {}
        self._lmb_owner: Dict[int, int] = {}
        self._lmb_homes: List[int] = []           # chunk -> expander id
        self._lmb_used: List[int] = []            # chunk -> occupied slots

        # access heat: exponentially-decayed touch counters, bumped on the
        # link-metering path (every byte a page moves over an expander link
        # is a vote for migrating it somewhere cooler).  Numpy-backed
        # structure-of-arrays with lazy decay: store (value, clock-at-touch)
        # per page and age on read; batch touches decay a whole burst in
        # one vectorized update.  Entries whose decayed value drops below
        # ``heat_epsilon`` are flushed to zero during batch updates.
        self.heat_decay = 0.95
        self.heat_epsilon = 1e-4
        self._heat_val = np.zeros(0, np.float64)
        self._heat_at = np.zeros(0, np.int64)
        self._heat_clock = 0

        self._pages: List[PageEntry] = []

    # ----------------------------------------------------------------- tracing
    @property
    def trace(self) -> SpanTracer:
        """The FM's span tracer, read through the host so a tracer
        attached after construction (ServeEngine, benchmarks) is seen.
        Hot paths guard every use with ``tr.enabled`` — the scalar hit
        path never touches this property at all."""
        return self.host.fm.tracer

    # ------------------------------------------------------------------ sizing
    @property
    def num_pages(self) -> int:
        return len(self._pages)

    def logical_bytes(self) -> int:
        return self.num_pages * self.page_bytes

    def onboard_bytes(self) -> int:
        return self.onboard_pages * self.page_bytes

    def tier_of(self, page: int) -> Optional[str]:
        """Which tier currently holds a logical page: ``"onboard"``,
        ``"lmb"``, or ``None`` for a never-materialized page — the
        public residency query (serving stats report how much admitted
        KV the LMB pool, not HBM, is carrying)."""
        return self._pages[page].tier

    def lmb_memory_kinds(self) -> set:
        """Memory kinds that hold the live LMB pools' pages: where the LMB
        tier really sits (``{"pinned_host"}``), as JAX reports it."""
        return {array_tier(p) for pool in self._lmb_pools
                if pool is not None for p in pool.pages}

    # --------------------------------------------------------------- allocation
    @_lmb_span
    def append_pages(self, n: int = 1) -> List[int]:
        """Extend the logical buffer by ``n`` zero pages; returns indices."""
        base = len(self._pages)
        self._pages.extend(PageEntry() for _ in range(n))
        need = len(self._pages)
        if need > len(self._heat_val):
            # geometric growth: decode appends one page at a time, and a
            # copy-per-append would make buffer growth quadratic
            cap = max(need, 2 * len(self._heat_val), 16)
            val = np.zeros(cap, np.float64)
            val[:base] = self._heat_val[:base]
            at = np.full(cap, self._heat_clock, np.int64)
            at[:base] = self._heat_at[:base]
            self._heat_val, self._heat_at = val, at
        else:
            self._heat_at[base:need] = self._heat_clock
        return list(range(base, base + n))

    def _grow_lmb(self, expander_id: Optional[int] = None) -> None:
        if self.degraded:
            raise OutOfMemory(f"{self.name}: LMB tier unavailable (degraded)")
        chunk_bytes = self._lmb_chunk_pages * self.lmb_page_bytes
        # class-agnostic capability alloc: the host dispatches PCIe/CXL
        handle = MemoryHandle.alloc(self.host, self.device_id, chunk_bytes,
                                    expander_id=expander_id)
        pool = self.executor.alloc_pool(
            self._lmb_chunk_pages, self.page_shape,
            jnp.int8 if self.compress_lmb else self.dtype, tier="lmb")
        chunk_idx = len(self._lmb_pools)
        self._lmb_pools.append(pool)
        self._lmb_allocs.append(handle)
        self._lmb_homes.append(handle.expander())
        self._lmb_used.append(0)
        base = chunk_idx * self._lmb_chunk_pages
        self._lmb_free.setdefault(handle.expander(), []).extend(
            range(base, base + self._lmb_chunk_pages))

    def _lmb_slot_alloc(self, expander_id: Optional[int] = None) -> int:
        """Take a free LMB slot; ``expander_id`` restricts the slot to a
        chunk homed on that expander (migration placement).  O(1) pops
        from per-expander free lists."""
        if expander_id is None:
            slot = None
            for lst in self._lmb_free.values():
                if lst:
                    slot = lst.pop()
                    break
            if slot is None:
                self._grow_lmb()
                slot = next(lst.pop() for lst in self._lmb_free.values()
                            if lst)
        else:
            lst = self._lmb_free.get(expander_id)
            if not lst:
                self._grow_lmb(expander_id)
                lst = self._lmb_free[expander_id]
            slot = lst.pop()
        self._lmb_used[slot // self._lmb_chunk_pages] += 1
        return slot

    def _lmb_slot_alloc_many(self, k: int,
                             expander_id: Optional[int] = None) -> List[int]:
        """``k`` free LMB slots as one batch; atomic — on OutOfMemory the
        already-claimed slots are returned before re-raising."""
        slots: List[int] = []
        try:
            for _ in range(k):
                slots.append(self._lmb_slot_alloc(expander_id))
        except OutOfMemory:
            for s in slots:
                self._lmb_slot_free(s)
            raise
        return slots

    def _lmb_slot_free(self, slot: int) -> None:
        home = self._lmb_homes[slot // self._lmb_chunk_pages]
        self._lmb_free.setdefault(home, []).append(slot)
        self._lmb_used[slot // self._lmb_chunk_pages] -= 1
        self._lmb_scales.pop(slot, None)

    # ------------------------------------------------------------------- heat
    def _touch_heat(self, page: int) -> None:
        self._heat_clock += 1
        age = self._heat_clock - self._heat_at[page]
        self._heat_val[page] = (self._heat_val[page]
                                * self.heat_decay ** age + 1.0)
        self._heat_at[page] = self._heat_clock

    def _touch_heat_batch(self, pages: Sequence[int]) -> None:
        """One vectorized decay+bump for a burst of page touches (replaces
        len(pages) dict walks); then flush decayed-cold entries."""
        if not pages:
            return
        u, counts = np.unique(np.asarray(pages, np.int64),
                              return_counts=True)
        self._heat_clock += len(pages)
        age = self._heat_clock - self._heat_at[u]
        self._heat_val[u] = (self._heat_val[u]
                             * self.heat_decay ** age + counts)
        self._heat_at[u] = self._heat_clock
        self._flush_cold_heat()

    def _flush_cold_heat(self) -> None:
        """Zero entries whose decayed heat fell below ``heat_epsilon`` —
        bounds stale-heat noise in long-lived buffers (the dict-era leak:
        every page ever touched kept an entry forever)."""
        n = len(self._pages)
        if self.heat_epsilon <= 0 or not n:
            return
        val, at = self._heat_val[:n], self._heat_at[:n]
        # restrict the decay computation to live entries: the flush runs
        # on every metering burst, and a full-array power over a large,
        # mostly-cold buffer would defeat the lazy-decay design
        (live,) = np.nonzero(val)
        if not len(live):
            return
        dec = val[live] * self.heat_decay ** (self._heat_clock - at[live])
        cold = live[dec < self.heat_epsilon]
        if len(cold):
            val[cold] = 0.0
            at[cold] = self._heat_clock

    def page_heat(self, page: int) -> float:
        """Decayed touch count: how hot this page runs on the LMB link."""
        age = self._heat_clock - self._heat_at[page]
        return float(self._heat_val[page] * self.heat_decay ** age)

    # ---------------------------------------------------------------- metering
    def _meter_link(self, chunk: Optional[int] = None,
                    page: Optional[int] = None) -> None:
        if page is not None:
            self._touch_heat(page)
        if not self._meter_via_executor:
            alloc = (self._lmb_allocs[chunk]
                     if chunk is not None else None)
            self.link_wait_s += self.host.meter_transfer(
                self.device_id, self.lmb_page_bytes,
                mmid=alloc.mmid if alloc is not None else None)

    def _charge_links(self, charges: List[Tuple[int, Optional[int]]],
                      pages: Sequence[int], op: str = "demand") -> None:
        """Flush a batch's accumulated link charges as one burst: one
        vectorized heat update, then ONE arbiter call per backing
        expander (LMBHost.meter_transfer_many merges same-link runs).
        ``op`` tags the traffic class; prefetch bursts admitted under an
        overlap window accrue their modeled wait to ``prefetch_hidden_s``
        (hidden behind compute) instead of the demand-visible
        ``link_wait_s``."""
        if pages:
            self._touch_heat_batch(pages)
        if not self._meter_via_executor and charges:
            delay = self.host.meter_transfer_many(
                self.device_id, charges, op=op)
            if op == "prefetch" and self.overlap is not None:
                self.prefetch_hidden_s += delay
            else:
                self.link_wait_s += delay

    # --------------------------------------------------- coalesced chunk runs
    def _lmb_read_run(self, chunk: int, offs: Sequence[int]) -> jax.Array:
        """Coalesced read of several slots of ONE chunk: one access check,
        one slice gather.  Caller meters (append the run's charge)."""
        self.host.check_access(self.device_id, self._lmb_allocs[chunk].mmid)
        data = self.executor.read_pages(self._lmb_pools[chunk], offs)
        if self.compress_lmb:
            base = chunk * self._lmb_chunk_pages
            scales = jnp.asarray(
                [self._lmb_scales.pop(base + off, 0.0) for off in offs],
                jnp.float32)
            scales = scales.reshape((-1,) + (1,) * len(self.page_shape))
            data = (data.astype(jnp.float32) * scales).astype(self.dtype)
        return data

    def _lmb_write_run(self, chunk: int, offs: Sequence[int],
                       data: jax.Array) -> None:
        """Coalesced write of ``data[i] -> chunk slot offs[i]``: one access
        check, one slice scatter, vectorized compression.  Caller meters."""
        self.host.check_access(self.device_id, self._lmb_allocs[chunk].mmid)
        if self.compress_lmb:
            f = data.astype(jnp.float32)
            axes = tuple(range(1, f.ndim))
            amax = np.asarray(jnp.max(jnp.abs(f), axis=axes),
                              np.float64) + 1e-12
            base = chunk * self._lmb_chunk_pages
            for off, a in zip(offs, amax):
                self._lmb_scales[base + off] = float(a) / 127.0
            inv = jnp.asarray(127.0 / amax, jnp.float32)
            inv = inv.reshape((-1,) + (1,) * len(self.page_shape))
            data = jnp.clip(jnp.round(f * inv), -127, 127).astype(jnp.int8)
        self._lmb_pools[chunk] = self.executor.write_pages(
            self._lmb_pools[chunk], offs, data)

    def _runs_by_chunk(self, slots: Sequence[int]) -> Dict[int, List[int]]:
        """Group batch positions by the chunk their slot lives in."""
        runs: Dict[int, List[int]] = {}
        for i, s in enumerate(slots):
            runs.setdefault(s // self._lmb_chunk_pages, []).append(i)
        return runs

    def _read_runs(self, slots: Sequence[int],
                   charges: List[Tuple[int, Optional[int]]]) -> List:
        """Read arbitrary LMB slots as coalesced per-chunk runs; returns
        page data aligned with ``slots`` and appends one link charge per
        run (the caller flushes the burst)."""
        data: Dict[int, jax.Array] = {}
        for chunk, idxs in self._runs_by_chunk(slots).items():
            offs = [slots[i] % self._lmb_chunk_pages for i in idxs]
            arr = self._lmb_read_run(chunk, offs)
            self.executor.count_hbm(arr.nbytes)        # the rows
            for i, row in zip(idxs, arr):
                data[i] = row
            charges.append((len(idxs) * self.lmb_page_bytes,
                            self._lmb_allocs[chunk].mmid))
        return [data[i] for i in range(len(slots))]

    def _write_runs(self, slots: Sequence[int], rows,
                    charges: List[Tuple[int, Optional[int]]]) -> None:
        """Write ``rows[i] -> slots[i]`` as coalesced per-chunk runs;
        appends one link charge per run.  ``rows`` is a stacked array or
        a list of pages."""
        for chunk, idxs in self._runs_by_chunk(slots).items():
            offs = [slots[i] % self._lmb_chunk_pages for i in idxs]
            sub = (rows[np.asarray(idxs)] if hasattr(rows, "ndim")
                   else stack_pages([rows[i] for i in idxs]))
            self.executor.count_hbm(sub.nbytes)
            self._lmb_write_run(chunk, offs, sub)
            charges.append((len(idxs) * self.lmb_page_bytes,
                            self._lmb_allocs[chunk].mmid))

    def _lmb_read(self, slot: int, page: Optional[int] = None) -> jax.Array:
        chunk, off = divmod(slot, self._lmb_chunk_pages)
        # access-control check on the data path (IOMMU/SAT)
        self.host.check_access(self.device_id, self._lmb_allocs[chunk].mmid)
        self._meter_link(chunk, page)
        page_data = self.executor.read_page(self._lmb_pools[chunk], off)
        if self.compress_lmb:
            scale = self._lmb_scales.pop(slot, 0.0)
            page_data = (page_data.astype(jnp.float32)
                         * scale).astype(self.dtype)
        return page_data

    def _lmb_write(self, slot: int, data: jax.Array,
                   page: Optional[int] = None) -> None:
        chunk, off = divmod(slot, self._lmb_chunk_pages)
        self.host.check_access(self.device_id, self._lmb_allocs[chunk].mmid)
        self._meter_link(chunk, page)
        if self.compress_lmb:
            f = data.astype(jnp.float32)
            amax = float(jnp.max(jnp.abs(f))) + 1e-12
            self._lmb_scales[slot] = amax / 127.0
            data = jnp.clip(jnp.round(f * (127.0 / amax)),
                            -127, 127).astype(jnp.int8)
        self._lmb_pools[chunk] = self.executor.write_page(
            self._lmb_pools[chunk], off, data)

    # ------------------------------------------------------------------ paging
    def _evict_one(self) -> int:
        """Demote one onboard page to the LMB tier; return the freed slot."""
        victim = self.policy.victim()
        if victim is None:
            raise OutOfMemory(
                f"{self.name}: onboard tier full and nothing evictable "
                f"(all {self.onboard_pages} pages pinned)")
        entry = self._pages[victim]
        assert entry.tier == ONBOARD
        slot = entry.slot
        if self.degraded:
            raise OutOfMemory(
                f"{self.name}: degraded mode — working set exceeds onboard "
                "capacity and the LMB tier is gone")
        lmb_slot = self._lmb_slot_alloc()
        page = self.executor.read_page(self._onboard_pool, slot)
        self._lmb_write(lmb_slot, page, victim)
        self.metrics.record_move(self.name, ONBOARD, LMB,
                                 self.lmb_page_bytes)
        entry.tier, entry.slot, entry.dirty = LMB, lmb_slot, False
        self._lmb_owner[lmb_slot] = victim
        self.policy.on_remove(victim)
        self._note_prefetch_evict(victim)
        del self._onboard_owner[slot]
        return slot

    def _evict_many(self, k: int,
                    sink: Optional[Tuple[list, list]] = None) -> List[int]:
        """Bulk eviction: demote ``k`` victims chosen in ONE policy call,
        written back as coalesced per-chunk bursts (one slice scatter +
        one link charge per destination chunk, instead of k round-trips).
        Returns the freed onboard slots in victim order.  ``sink`` is an
        optional ``(charges, heat_pages)`` pair a batch caller passes to
        defer the metering flush to one combined burst."""
        if k <= 0:
            return []
        with self.trace.span("evict.batch", op="demand",
                             nbytes=k * self.lmb_page_bytes, pages=k):
            return self._evict_victims(k, sink)

    def _evict_victims(self, k: int,
                       sink: Optional[Tuple[list, list]]) -> List[int]:
        victims = self.policy.victims(k)
        if len(victims) < k:
            raise OutOfMemory(
                f"{self.name}: onboard tier full and only "
                f"{len(victims)}/{k} evictable pages "
                f"(of {self.onboard_pages}; rest pinned)")
        if self.degraded:
            raise OutOfMemory(
                f"{self.name}: degraded mode — working set exceeds onboard "
                "capacity and the LMB tier is gone")
        dsts = self._lmb_slot_alloc_many(k)
        data = self.executor.read_pages(
            self._onboard_pool, [self._pages[v].slot for v in victims])
        charges, heat = sink if sink is not None else ([], [])
        self._write_runs(dsts, data, charges)
        heat.extend(victims)
        self.metrics.record_move(self.name, ONBOARD, LMB,
                                 k * self.lmb_page_bytes)
        freed: List[int] = []
        for v, dst in zip(victims, dsts):
            entry = self._pages[v]
            slot = entry.slot
            entry.tier, entry.slot, entry.dirty = LMB, dst, False
            self._lmb_owner[dst] = v
            self.policy.on_remove(v)
            self._note_prefetch_evict(v)
            del self._onboard_owner[slot]
            freed.append(slot)
        if sink is None:
            self._charge_links(charges, heat)
        return freed

    def _onboard_slot_alloc(self) -> int:
        if self._onboard_free:
            return self._onboard_free.pop()
        return self._evict_one()

    def _fault_in(self, page: int) -> int:
        """Bring a page onboard; returns the onboard slot."""
        entry = self._pages[page]
        if entry.tier == ONBOARD:
            self.metrics.record_hit(self.name, ONBOARD, self.page_bytes)
            self.policy.on_access(page)
            if self.prefetcher:
                # hits feed the stride detector too — a prefetcher that
                # only learns from misses stalls the moment it succeeds
                # (every access hits, nothing advances the lookahead)
                self._note_prefetch_hit(page)
                self.prefetcher.observe(page)
                self._prefetch_runs()
            return entry.slot
        self.metrics.record_miss(self.name, ONBOARD, self.page_bytes)
        with self.trace.span("fault", op="demand", nbytes=self.page_bytes,
                             page=page):
            slot = self._fault_miss(page, entry)
        if self.prefetcher:
            self.prefetcher.observe(page)
            self._prefetch_runs()
        return slot

    def _fault_miss(self, page: int, entry: PageEntry) -> int:
        slot = self._onboard_slot_alloc()
        if entry.tier == LMB:
            data = self._lmb_read(entry.slot, page)
            self._onboard_pool = self.executor.write_page(
                self._onboard_pool, slot, data)
            self.metrics.record_move(self.name, LMB, ONBOARD,
                                     self.lmb_page_bytes)
            self._lmb_slot_free(entry.slot)
            self._lmb_owner.pop(entry.slot, None)
        else:
            # first touch: zero-fill
            self.executor.count_hbm(self.page_bytes)
            self._onboard_pool = self.executor.write_page(
                self._onboard_pool, slot,
                jnp.zeros(self.page_shape, self.dtype))
        entry.tier, entry.slot, entry.dirty = ONBOARD, slot, False
        self._onboard_owner[slot] = page
        self.policy.on_insert(page)
        return slot

    # --------------------------------------------------------- batched paging
    def _fault_in_many(self, pages: Sequence[int],
                       co_resident: bool = False) -> Dict[int, int]:
        """Batched fault: bring a set of pages onboard with coalesced
        per-chunk transfers, bulk eviction, and one metering burst.
        Returns {page: onboard slot}.  The batch's distinct pages must
        fit the onboard tier at once — every returned slot is live when
        the caller gathers/scatters through it (read_many/write_many
        wave LARGER batches themselves, capturing each wave's data
        before the next may evict it); an oversized fault raises
        OutOfMemory from the eviction shortfall.  Pages already onboard
        are guarded against the batch's own evictions — a burst is one
        access epoch, so its hits must still be resident on return.
        ``co_resident`` additionally pre-checks the whole batch fits
        (the pin contract), raising like the scalar pin loop did when
        it ran out of evictable slots."""
        slots: Dict[int, int] = {}
        faulting: List[int] = []
        hits: List[int] = []
        deferred: List[int] = []
        missed = set()
        for p in pages:
            self._check(p)
            if self.prefetcher:
                self.prefetcher.observe(p)
            entry = self._pages[p]
            if entry.tier == ONBOARD or p in missed:
                # second+ occurrence of a faulting page counts as a hit,
                # exactly like the scalar loop's repeat read would
                self.metrics.record_hit(self.name, ONBOARD, self.page_bytes)
                if p in missed:
                    # recency bump must land AFTER the page is inserted
                    # into the policy (scalar order: insert, then the
                    # repeat read's access) — fired post-wave below
                    deferred.append(p)
                else:
                    self.policy.on_access(p)
                    self._note_prefetch_hit(p)
                    slots[p] = entry.slot
                    hits.append(p)
            else:
                self.metrics.record_miss(self.name, ONBOARD,
                                         self.page_bytes)
                missed.add(p)
                faulting.append(p)
        if co_resident:
            distinct = len(missed) + len(set(hits))
            avail = self._batch_capacity(list(missed) + hits)
            if distinct > avail:
                raise OutOfMemory(
                    f"{self.name}: batch of {distinct} pages cannot "
                    f"co-reside in the onboard tier ({avail} of "
                    f"{self.onboard_pages} slots unpinned)")
        # guard this batch's hit pages against its own evictions: the
        # caller reads/writes through slots[] after we return.  Pin via
        # the public API (a policy may mirror pins into its own
        # structures); _pinned() is only consulted to avoid releasing a
        # caller's pre-existing pin
        guard = [p for p in dict.fromkeys(hits)
                 if p not in self.policy._pinned()]
        for p in guard:
            self.policy.pin(p)
        try:
            if faulting and self.trace.enabled:
                with self.trace.span(
                        "fault.batch", op="demand", pages=len(faulting),
                        nbytes=len(faulting) * self.page_bytes):
                    self._fault_wave(faulting)
            else:
                self._fault_wave(faulting)
        finally:
            for p in guard:
                self.policy.unpin(p)
        for p in deferred:
            self.policy.on_access(p)
        for p in faulting:
            slots[p] = self._pages[p].slot
        if self.prefetcher:
            self._prefetch_runs()
        return slots

    def _fault_wave(self, faulting: List[int]) -> None:
        """One capacity-bounded wave of the batched fault path: coalesced
        LMB reads per source chunk, bulk eviction for the shortfall, one
        coalesced onboard scatter, one metering burst."""
        if not faulting:
            return
        charges: List[Tuple[int, Optional[int]]] = []
        heat: List[int] = []
        # 1. coalesced reads of LMB-resident sources, then free their
        # slots — freeing BEFORE the eviction allocates destinations lets
        # the burst recycle its own sources (never grows more chunks than
        # the scalar interleave would)
        lmb_pages = [p for p in faulting if self._pages[p].tier == LMB]
        src_slots = [self._pages[p].slot for p in lmb_pages]
        # snapshot (page, slot, scale) so a failed eviction below can
        # restore the sources (pool contents stay valid until step 4)
        src_saved = [(p, s, self._lmb_scales.get(s))
                     for p, s in zip(lmb_pages, src_slots)]
        data = dict(zip(lmb_pages, self._read_runs(src_slots, charges)))
        heat.extend(lmb_pages)
        for p in lmb_pages:
            entry = self._pages[p]
            self._lmb_slot_free(entry.slot)
            self._lmb_owner.pop(entry.slot, None)
        # 2. bulk-evict the shortfall (coalesced write-back, shared burst)
        try:
            freed = self._evict_many(
                len(faulting) - len(self._onboard_free),
                sink=(charges, heat))
        except OutOfMemory:
            # eviction failed before any pool write: re-claim the exact
            # source slots so every page keeps its pre-call state — but
            # the source reads DID move bytes over the link, so flush
            # their charges first (the scalar path metered each read
            # before failing too)
            self._charge_links(charges, heat)
            for p, slot, scale in src_saved:
                home = self._lmb_homes[slot // self._lmb_chunk_pages]
                self._lmb_free[home].remove(slot)
                self._lmb_used[slot // self._lmb_chunk_pages] += 1
                if scale is not None:
                    self._lmb_scales[slot] = scale
                self._lmb_owner[slot] = p
            raise
        if lmb_pages:
            self.metrics.record_move(self.name, LMB, ONBOARD,
                                     len(lmb_pages) * self.lmb_page_bytes)
        # 3. assign slots: free list (LIFO, scalar order) first, then the
        # eviction-freed slots in victim order
        assigned = [self._onboard_free.pop() if self._onboard_free
                    else freed.pop(0) for _ in faulting]
        # 4. one coalesced onboard scatter (zeros for first-touch pages)
        zero = jnp.zeros(self.page_shape, self.dtype)
        batch = stack_pages([data.get(p, zero) for p in faulting])
        self.executor.count_hbm(zero.nbytes + batch.nbytes)
        self._onboard_pool = self.executor.write_pages(
            self._onboard_pool, assigned, batch)
        for p, slot in zip(faulting, assigned):
            entry = self._pages[p]
            entry.tier, entry.slot, entry.dirty = ONBOARD, slot, False
            self._onboard_owner[slot] = p
            self.policy.on_insert(p)
        self._charge_links(charges, heat)

    def _batch_capacity(self, batch: Sequence[int] = ()) -> int:
        """Onboard slots a batch can actually occupy: the tier minus
        pages pinned OUTSIDE the batch.  The scalar loop could thrash a
        working set through whatever unpinned remainder existed, one
        page at a time — batch waves must size to the same remainder or
        a gather under pin pressure would spuriously raise."""
        members = set(batch)
        pinned = sum(1 for p in self.policy._pinned()
                     if p not in members and 0 <= p < len(self._pages)
                     and self._pages[p].tier == ONBOARD)
        return max(self.onboard_pages - pinned, 1)

    def _record_dup_hits(self, page: int, n: int) -> None:
        """Account ``n`` duplicate occurrences of a single-page burst as
        onboard hits, like the scalar loop's repeat reads would."""
        for _ in range(n):
            self.metrics.record_hit(self.name, ONBOARD, self.page_bytes)
            self.policy.on_access(page)

    def _single_wave_fits(self, order: Sequence[int]) -> bool:
        """Whether the whole batch can co-reside onboard right now:
        pinned-resident members already hold their slots; the rest must
        fit in the unpinned remainder."""
        pinned = self.policy._pinned()
        member_pins = sum(1 for p in order if p in pinned
                          and self._pages[p].tier == ONBOARD)
        all_pins = sum(1 for p in pinned
                       if 0 <= p < len(self._pages)
                       and self._pages[p].tier == ONBOARD)
        return (len(order) - member_pins
                <= max(self.onboard_pages - all_pins, 0))

    def _iter_waves(self, pages: Sequence[int], order: Sequence[int]):
        """Split a too-large batch into processable waves, yielding
        ``(wave, occ)`` — the wave's distinct pages and their duplicate-
        preserving occurrences.  Pinned-resident members go first (pure
        hits, no eviction needed); the rest waves through the unpinned
        capacity, recomputed each round since a wave may fault a pinned
        page onboard."""
        remaining = list(order)
        while remaining:
            pinned = self.policy._pinned()
            wave = [p for p in remaining if p in pinned
                    and self._pages[p].tier == ONBOARD]
            if not wave:
                wave = remaining[:self._batch_capacity()]
            members = set(wave)
            yield wave, [p for p in pages if p in members]
            remaining = [p for p in remaining if p not in members]

    def _prefetch(self, page: int) -> None:
        self._prefetch_many([page])

    def _note_prefetch_evict(self, page: int) -> None:
        """A prefetched page got demoted before anyone read it: wasted
        link bytes (the fault-rate-delta signal the prefetch_sweep
        benchmark reports)."""
        if page in self._prefetched:
            self._prefetched.discard(page)
            self.prefetch_wasted += 1

    def _note_prefetch_hit(self, page: int) -> None:
        """A demand read landed on a prefetched page: the prefetch was
        useful (its LMB round-trip was paid early, hidden or not)."""
        if page in self._prefetched:
            self._prefetched.discard(page)
            self.prefetch_used += 1

    def _prefetch_runs(self) -> int:
        """One prefetch round: pull chunk-aligned run suggestions, keep
        only LMB-resident pages, cap at the free-slot budget (prefetch
        NEVER evicts a resident page), let the overlap scheduler admit
        what fits behind the current compute window, and hand the
        remainder back to the backlog (deferred, not dropped).  All
        admitted pages move as ONE coalesced burst.  Returns the number
        of pages issued."""
        if not self.prefetcher:
            return 0
        runs = self.prefetcher.suggest_runs(self.num_pages - 1,
                                            self._lmb_chunk_pages)
        if not runs:
            return 0
        live: List[Tuple[str, List[int]]] = []
        seen: set = set()
        for run in runs:
            pages = [p for p in run.pages
                     if p not in seen and 0 <= p < len(self._pages)
                     and self._pages[p].tier == LMB]
            seen.update(pages)
            if pages:
                live.append((run.source, pages))
        #: original priority position of every candidate page — deferred
        #: pages re-queue in THIS order, whichever budget pass cut them
        #: (a free-slot tail must not jump ahead of an overlap-deferred
        #: run that preceded it)
        priority = {p: i for i, p in
                    enumerate(p for _, pages in live for p in pages)}
        deferred: List[Tuple[str, int]] = []   # (source, page)
        issued = 0
        try:
            if not live:
                return 0
            # hard budget first: free onboard slots only.  A run that
            # half-fits is truncated (still one burst); the cut tail and
            # everything after defer.
            free = len(self._onboard_free)
            fitted: List[Tuple[str, List[int]]] = []
            for source, pages in live:
                take = pages[:free]
                free -= len(take)
                if take:
                    fitted.append((source, take))
                deferred.extend((source, p) for p in pages[len(take):])
            # burst hysteresis: stride guesses below the min-burst size
            # wait (regenerated next round, when the frontier has grown)
            # so steady-state lookahead stays burst-shaped; scheduled
            # pages always go now
            stride_pages = sum(len(pages) for source, pages in fitted
                               if source == "stride")
            if 0 < stride_pages < self.prefetch_min_burst:
                fitted = [(s, pages) for s, pages in fitted
                          if s != "stride"]
            # overlap admission: whole runs, in priority order, while
            # they fit behind the compute window
            if self.overlap is not None and fitted:
                n_admit, _ = self.overlap.admit(
                    [len(pages) for _, pages in fitted],
                    self.lmb_page_bytes)
                deferred.extend((source, p)
                                for source, pages in fitted[n_admit:]
                                for p in pages)
                fitted = fitted[:n_admit]
            issue = [p for _, pages in fitted for p in pages]
            if issue:
                self._prefetch_many(issue)
                issued = len(issue)
            return issued
        finally:
            # exact scheduled knowledge is deferred back to the front of
            # the backlog in original priority order; stride guesses are
            # regenerated for free next round, so re-queueing them would
            # only pollute it
            requeue = sorted(
                (p for source, p in deferred if source == "scheduled"),
                key=priority.__getitem__)
            if requeue:
                self.prefetcher.defer(requeue)
                self.prefetch_deferred += len(requeue)
                tr = self.trace
                if tr.enabled:
                    tr.event("prefetch.defer", op="prefetch",
                             pages=len(requeue),
                             nbytes=len(requeue) * self.lmb_page_bytes)

    def _prefetch_many(self, pages: Sequence[int]) -> None:
        """Opportunistic LMB->onboard copies bounded by FREE onboard slots
        (never evicts to prefetch), moved as coalesced per-chunk runs with
        one metering burst tagged ``op="prefetch"`` — prefetch traffic is
        distinguishable from demand on the FM's journal/byte counters and
        never pays per-page arbitration."""
        cands = [p for p in dict.fromkeys(pages)
                 if 0 <= p < len(self._pages)
                 and self._pages[p].tier == LMB]
        cands = cands[:len(self._onboard_free)]
        if not cands:
            return
        with self.trace.span("prefetch.burst", op="prefetch",
                             nbytes=len(cands) * self.lmb_page_bytes,
                             pages=len(cands)):
            self._prefetch_burst(cands)

    def _prefetch_burst(self, cands: List[int]) -> None:
        charges: List[Tuple[int, Optional[int]]] = []
        src_slots = [self._pages[p].slot for p in cands]
        data = self._read_runs(src_slots, charges)
        self.metrics.record_move(self.name, LMB, ONBOARD,
                                 len(cands) * self.lmb_page_bytes)
        assigned = [self._onboard_free.pop() for _ in cands]
        batch = stack_pages(data)
        self.executor.count_hbm(batch.nbytes)
        self._onboard_pool = self.executor.write_pages(
            self._onboard_pool, assigned, batch)
        for p, slot in zip(cands, assigned):
            entry = self._pages[p]
            self._lmb_slot_free(entry.slot)
            self._lmb_owner.pop(entry.slot, None)
            entry.tier, entry.slot, entry.dirty = ONBOARD, slot, False
            self._onboard_owner[slot] = p
            self.policy.on_insert(p)
        self._prefetched.update(cands)
        self.prefetch_bursts += 1
        self.prefetch_pages_total += len(cands)
        self._charge_links(charges, cands, op="prefetch")

    # ------------------------------------------------------------------- API
    @_lmb_span
    def read(self, page: int) -> jax.Array:
        self._check(page)
        slot = self._fault_in(page)
        return self.executor.read_page(self._onboard_pool, slot)

    @_lmb_span
    def write(self, page: int, data) -> None:
        self._check(page)
        entry = self._pages[page]
        if entry.refcount > 1:
            self._cow(page)
            entry = self._pages[page]
        data = jnp.asarray(data, self.dtype)
        if data.shape != self.page_shape:
            raise ValueError(
                f"{self.name}: page shape {data.shape} != {self.page_shape}")
        slot = self._fault_in(page)
        self._onboard_pool = self.executor.write_page(
            self._onboard_pool, slot, data)
        self._pages[page].dirty = True
        if hasattr(self.policy, "mark_dirty"):
            self.policy.mark_dirty(page, True)

    @_lmb_span
    def read_many(self, pages: Sequence[int]) -> jax.Array:
        """Batched :meth:`read`: fault the pages in with coalesced
        per-chunk transfers and bulk eviction, then return them stacked
        ``[len(pages), *page_shape]`` via one gather against the onboard
        pool.  Duplicates allowed.  Batches larger than the onboard tier
        are served in capacity-sized waves."""
        pages = list(pages)
        if not pages:
            return jnp.zeros((0, *self.page_shape), self.dtype)
        order = list(dict.fromkeys(pages))
        if len(order) == 1:
            # a 1-page "burst" IS the scalar path (same bytes, same
            # single-digit arbiter calls) minus the gather machinery;
            # data[None] over jnp.stack keeps the decode path at true
            # scalar dispatch cost
            data = self.read(order[0])
            self._record_dup_hits(order[0], len(pages) - 1)
            self.executor.count_hbm(data.nbytes * len(pages))
            if len(pages) == 1:
                return data[None]
            return jnp.stack([data] * len(pages))
        if self._single_wave_fits(order):
            slotmap = self._fault_in_many(pages)
            return self.executor.read_pages(
                self._onboard_pool, [slotmap[p] for p in pages])
        # batch exceeds the batch-usable onboard capacity: wave through,
        # capturing each wave's data before the next wave may evict it
        datas: Dict[int, jax.Array] = {}
        for wave, occ in self._iter_waves(pages, order):
            slotmap = self._fault_in_many(occ)
            arr = self.executor.read_pages(
                self._onboard_pool, [slotmap[p] for p in wave])
            for p, row in zip(wave, arr):
                datas[p] = row
        # the waves' rows, then their stack
        self.executor.count_hbm(self.page_bytes * (len(datas) + len(pages)))
        return stack_pages([datas[p] for p in pages])

    @_lmb_span
    def write_many(self, pages: Sequence[int], data) -> None:
        """Batched :meth:`write`: ``data[i]`` -> ``pages[i]`` with one
        coalesced onboard scatter after a batched fault (duplicate pages:
        last write wins, like the scalar loop)."""
        pages = list(pages)
        data = jnp.asarray(data, self.dtype)
        if data.shape != (len(pages), *self.page_shape):
            raise ValueError(
                f"{self.name}: batch shape {data.shape} != "
                f"{(len(pages), *self.page_shape)}")
        for p in dict.fromkeys(pages):
            self._check(p)
            if self._pages[p].refcount > 1:
                self._cow(p)
        order = list(dict.fromkeys(pages))
        last = {p: i for i, p in enumerate(pages)}
        if len(order) == 1:
            self.executor.count_hbm(self.page_bytes)
            self.write(order[0], data[last[order[0]]])
            self._record_dup_hits(order[0], len(pages) - 1)
            return
        for wave, occ in self._iter_waves(pages, order):
            slotmap = self._fault_in_many(occ)
            self.executor.count_hbm(self.page_bytes * len(wave))
            self._onboard_pool = self.executor.write_pages(
                self._onboard_pool, [slotmap[p] for p in wave],
                data[np.asarray([last[p] for p in wave])])
            # dirty-mark per wave: a later wave may evict these pages,
            # and eviction must observe (and clear) their dirty state
            # exactly as the scalar interleave would
            for p in wave:
                self._pages[p].dirty = True
                if hasattr(self.policy, "mark_dirty"):
                    self.policy.mark_dirty(p, True)

    # ------------------------------------------------------- in-place access
    @property
    def onboard_array(self) -> jax.Array:
        """The onboard tier's pool ``[onboard_pages, *page_shape]`` as it
        stands: what a compiled step reads and writes in place, at the
        slots :meth:`onboard_slots` returns."""
        return self._onboard_pool

    @_lmb_span
    def onboard_slots(self, pages: Sequence[int],
                      written: Sequence[int] = ()) -> Optional[List[int]]:
        """Fault ``pages`` onboard with one batched fault, as
        :meth:`read_many` does (the same hits, recency and prefetcher
        observations), and return each page's onboard slot, so that a
        compiled step can read them in place in :attr:`onboard_array`.
        Each shared page of ``written``, which the step will write, is
        copied first (copy on write), as :meth:`write_many` would.
        Returns None, and touches nothing, when the distinct pages
        cannot all co-reside onboard: the caller then gathers them with
        :meth:`read_many`'s waves."""
        pages = list(pages)
        if not self._single_wave_fits(list(dict.fromkeys(pages))):
            return None
        for p in dict.fromkeys(written):
            self._check(p)
            if self._pages[p].refcount > 1:
                self._cow(p)
        slotmap = self._fault_in_many(pages)
        return [slotmap[p] for p in pages]

    @_lmb_span
    def replace_onboard(self, pool: jax.Array,
                        written: Sequence[int]) -> None:
        """Install ``pool``, the onboard array a compiled step returned
        after writing the pages ``written`` in place at their
        :meth:`onboard_slots`, as the onboard tier.  The written pages
        are accounted as :meth:`write_many`'s fault pass accounts them
        (onboard hits, recency, prefetcher observations) and marked
        dirty.  Nothing is copied."""
        shape = (self.onboard_pages, *self.page_shape)
        if pool.shape != shape or pool.dtype != self.dtype:
            raise ValueError(
                f"{self.name}: onboard pool {pool.shape} {pool.dtype} != "
                f"{shape} {jnp.dtype(self.dtype)}")
        written = list(written)
        for p in written:
            self._check(p)
            if self._pages[p].tier != ONBOARD:
                raise ValueError(
                    f"{self.name}: page {p} left the onboard tier before "
                    "its in-place write was installed")
        self._onboard_pool = pool
        self._fault_in_many(written)             # every page a hit
        for p in dict.fromkeys(written):
            self._pages[p].dirty = True
            if hasattr(self.policy, "mark_dirty"):
                self.policy.mark_dirty(p, True)

    def gather(self, pages: Sequence[int]) -> jax.Array:
        """Stack several logical pages (faulting them in) — kernel feed.
        Built on :meth:`read_many`: coalesced transfers, bulk eviction,
        one arbiter charge per touched expander link."""
        return self.read_many(pages)

    def pin(self, page: int) -> None:
        self._fault_in(page)
        self.policy.pin(page)

    def unpin(self, page: int) -> None:
        self.policy.unpin(page)

    def pin_many(self, pages: Sequence[int]) -> None:
        """Batched :meth:`pin`: one coalesced fault burst, then pin.
        Raises OutOfMemory when the pages cannot all co-reside onboard
        (the scalar pin loop raised once pins exhausted the tier; a
        silent partial pin would hand the DMA scheduler LMB slots)."""
        self._fault_in_many(pages, co_resident=True)
        for p in dict.fromkeys(pages):
            self.policy.pin(p)

    def unpin_many(self, pages: Sequence[int]) -> None:
        for p in dict.fromkeys(pages):
            self.policy.unpin(p)

    @_lmb_span
    def schedule_prefetch(self, pages: Sequence[int]) -> None:
        """Feed exact future page knowledge (a scheduler's next-round
        access list) to the prefetcher and issue as much of it as fits
        RIGHT NOW — free onboard slots and the overlap window budget —
        as coalesced per-(chunk, expander) bursts.  The seed truncated
        the list to the first ``depth`` pages and silently discarded the
        rest; now the remainder stays in the bounded backlog (or is
        deferred by the overlap scheduler) and issues on later rounds."""
        if not self.prefetcher:
            return
        self.prefetcher.schedule(list(pages))
        while self.prefetcher.pending():
            before = self.prefetcher.pending()
            self._prefetch_runs()
            if self.prefetcher.pending() >= before:
                break       # budgets exhausted (deferred) — later rounds

    @_lmb_span
    def note_compute_window(self, seconds: float,
                            observed: bool = True) -> None:
        """Open a new overlap window sized to the consumer's compute
        step.  ``observed=True`` folds the sample into the scheduler's
        EWMA estimate (the serving engine feeds measured decode-round
        times); ``observed=False`` pins the window exactly (benchmarks
        and simulators declaring a known compute budget).  No-op without
        an overlap scheduler."""
        if self.overlap is None:
            return
        if observed:
            self.overlap.observe_compute(seconds)
            self.overlap.start_window()
        else:
            self.overlap.start_window(seconds)

    # ------------------------------------------------------------- share / COW
    def share(self, page: int) -> int:
        """Refcount++ (zero-copy share). Returns the same logical index."""
        self._check(page)
        self._pages[page].refcount += 1
        return page

    def share_many(self, pages: Sequence[int]) -> List[int]:
        """Batched :meth:`share` (one call for a whole sequence fork)."""
        out = []
        for p in pages:
            self._check(p)
            self._pages[p].refcount += 1
            out.append(p)
        return out

    @_lmb_span
    def release(self, page: int) -> None:
        """Refcount--; frees storage at zero."""
        self._check(page)
        entry = self._pages[page]
        entry.refcount -= 1
        if entry.refcount > 0:
            return
        self._prefetched.discard(page)
        if entry.tier == ONBOARD:
            self.policy.on_remove(page)
            self._onboard_free.append(entry.slot)
            self._onboard_owner.pop(entry.slot, None)
        elif entry.tier == LMB:
            self._lmb_slot_free(entry.slot)
            self._lmb_owner.pop(entry.slot, None)
        entry.tier, entry.slot, entry.dirty = None, -1, False
        entry.refcount = 0

    def _cow(self, page: int) -> None:
        """Copy-on-write: writer gets a private copy of a shared page."""
        entry = self._pages[page]
        data = self.read(page)
        entry.refcount -= 1
        new = PageEntry()
        self._pages[page] = new
        slot = self._onboard_slot_alloc()
        self._onboard_pool = self.executor.write_page(
            self._onboard_pool, slot, data)
        new.tier, new.slot, new.dirty = ONBOARD, slot, True
        self._onboard_owner[slot] = page
        self.policy.on_insert(page)
        # the old physical page stays where it is, now owned by the sharers;
        # bookkeeping for "who else maps it" lives in the serving layer,
        # which tracks logical page ids per request.

    # --------------------------------------------------------- hot-page moves
    def page_expander(self, page: int) -> Optional[int]:
        """Which expander homes this page's LMB slot (None if not in LMB)."""
        entry = self._pages[page]
        if entry.tier != LMB:
            return None
        return self._lmb_homes[entry.slot // self._lmb_chunk_pages]

    def lmb_placement(self) -> Dict[int, int]:
        """LMB-resident page count per home expander."""
        out: Dict[int, int] = {}
        for e in self._pages:
            if e.tier == LMB:
                home = self._lmb_homes[e.slot // self._lmb_chunk_pages]
                out[home] = out.get(home, 0) + 1
        return out

    def hottest_pages(self, limit: int,
                      expander_id: Optional[int] = None,
                      min_heat: float = 0.0) -> List[int]:
        """LMB-resident pages by descending access heat — the migration
        candidates for one saturated expander.  One vectorized decay over
        the heat arrays instead of a per-page dict walk."""
        if not self._pages:
            return []
        n = len(self._pages)
        dec = (self._heat_val[:n]
               * self.heat_decay ** (self._heat_clock - self._heat_at[:n]))
        cands = []
        for p, e in enumerate(self._pages):
            if e.tier != LMB:
                continue
            if (expander_id is not None
                    and self.page_expander(p) != expander_id):
                continue
            h = float(dec[p])
            if h < min_heat:
                continue
            cands.append((h, p))
        cands.sort(reverse=True)
        return [p for _, p in cands[:limit]]

    def migrate_pages(self, pages: Sequence[int], dst_expander: int) -> int:
        """Move LMB-resident pages onto chunks homed on ``dst_expander``.

        Contents are preserved (read from the source chunks, written to
        the destination chunks — coalesced per-chunk runs, one arbiter
        charge per touched link instead of per page); both links are
        metered, so migration traffic is visible as occupancy on each
        side.  Source chunks left empty are reclaimed, which frees their
        allocation and revokes the device's SAT/IOMMU entries on the
        source blocks — the destination grant was authorized when its
        chunk was allocated (the failover re-grant machinery).  Returns
        the number of pages actually moved: when the destination refuses
        growth (quota or pool exhausted) the batch stops early with every
        remaining page intact on its source."""
        movers: List[int] = []
        # dedupe: the scalar loop skipped a repeated page because its
        # home had already changed by the second occurrence
        for page in dict.fromkeys(pages):
            self._check(page)
            entry = self._pages[page]
            if entry.tier != LMB:
                continue
            src_home = self._lmb_homes[entry.slot // self._lmb_chunk_pages]
            if src_home == dst_expander:
                continue
            movers.append(page)
        # claim every destination slot FIRST: an OutOfMemory (quota, full
        # pool) must fire before any source page is touched — with
        # compress_lmb a read pops the source's scale, so failing
        # mid-move would corrupt the page.  A refusal truncates the batch
        # to the prefix that got slots (scalar stop-early semantics).
        dsts: List[int] = []
        for _ in movers:
            try:
                dsts.append(self._lmb_slot_alloc(expander_id=dst_expander))
            except OutOfMemory:
                break
        movers = movers[:len(dsts)]
        if not movers:
            return 0
        charges: List[Tuple[int, Optional[int]]] = []
        src_slots = [self._pages[p].slot for p in movers]
        src_homes = [self._lmb_homes[s // self._lmb_chunk_pages]
                     for s in src_slots]
        data = self._read_runs(src_slots, charges)     # meters source links
        self._write_runs(dsts, data, charges)          # meters dest link
        # scalar parity: migration traffic does NOT bump access heat
        self._charge_links(charges, [])
        moved_by_home: Dict[int, int] = {}
        for i, page in enumerate(movers):
            entry = self._pages[page]
            entry.slot = dsts[i]
            self._lmb_owner[dsts[i]] = page
            self._lmb_owner.pop(src_slots[i], None)
            self._lmb_slot_free(src_slots[i])
            moved_by_home[src_homes[i]] = moved_by_home.get(
                src_homes[i], 0) + 1
        for home, n in moved_by_home.items():
            self.metrics.record_move(self.name, f"{LMB}@{home}",
                                     f"{LMB}@{dst_expander}",
                                     n * self.lmb_page_bytes)
        self._reclaim_empty_chunks()
        tr = self.trace
        if tr.enabled:
            tr.event("migrate.batch", op="migrate",
                     expander=dst_expander, pages=len(movers),
                     nbytes=len(movers) * self.lmb_page_bytes,
                     sources=sorted(moved_by_home))
        return len(movers)

    def _reclaim_empty_chunks(self) -> None:
        """Free fully-empty LMB chunks back through the Table-2 API (which
        revokes this device's SAT/IOMMU entries and may return the 256 MB
        block to the FM)."""
        for chunk, used in enumerate(self._lmb_used):
            if used != 0 or self._lmb_pools[chunk] is None:
                continue
            base = chunk * self._lmb_chunk_pages
            home = self._lmb_homes[chunk]
            if home in self._lmb_free:
                self._lmb_free[home] = [
                    s for s in self._lmb_free[home]
                    if not base <= s < base + self._lmb_chunk_pages]
            self._lmb_allocs[chunk].free()
            self._lmb_pools[chunk] = None
            self._lmb_allocs[chunk] = None
            self._lmb_homes[chunk] = -1

    # ------------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Release the buffer's entire LMB footprint: every live chunk
        capability is freed back through the Table-2 path (revoking
        SAT/IOMMU entries, returning drained blocks to the FM).  LMB-
        resident pages revert to 'never written'; the buffer enters
        degraded (onboard-only) mode so later paging cannot silently
        re-acquire LMB quota, and its failover callback is removed from
        the FM.  Called by LMBSystem.close() so a session cannot leak
        quota through its buffers."""
        self.degraded = True
        self._closed = True
        self.host.fm.off_failover(self._on_failover)
        self.host.fm.off_repair(self._on_repair)
        for chunk, handle in enumerate(self._lmb_allocs):
            if handle is None:
                continue
            if not handle.stale:
                handle.free()
            self._lmb_pools[chunk] = None
            self._lmb_allocs[chunk] = None
            self._lmb_homes[chunk] = -1
            self._lmb_used[chunk] = 0
        for e in self._pages:
            if e.tier == LMB:
                e.tier, e.slot, e.dirty = None, -1, False
        self._lmb_owner.clear()
        self._lmb_scales.clear()
        self._lmb_free = {}

    # ------------------------------------------------------------ failure path
    def _on_failover(self, expander_id: Optional[int] = None) -> None:
        """An expander failed.  Pages homed on it are gone (re-granted
        blocks are blank): they revert to 'never written' (zeros on next
        touch); consumers holding a journal (checkpoint) re-populate.
        Pages homed on surviving pooled expanders are untouched.  With
        nowhere to fail over to we enter degraded mode instead (see
        inject_failure in fabric.py)."""
        if not self.host.fm.healthy:
            # last expander died: the LMB tier is gone for good — shed its
            # pages below, and refuse future growth
            self.degraded = True
        dead = {chunk for chunk, home in enumerate(self._lmb_homes)
                if self._lmb_pools[chunk] is not None
                and (expander_id is None or home == expander_id)}
        if not dead:
            return
        for e in self._pages:
            if e.tier == LMB and e.slot // self._lmb_chunk_pages in dead:
                e.tier, e.slot, e.dirty = None, -1, False
        for slot in [s for s in self._lmb_owner
                     if s // self._lmb_chunk_pages in dead]:
            del self._lmb_owner[slot]
        for slot in [s for s in self._lmb_scales
                     if s // self._lmb_chunk_pages in dead]:
            del self._lmb_scales[slot]
        self._lmb_free = {
            eid: [s for s in lst
                  if s // self._lmb_chunk_pages not in dead]
            for eid, lst in self._lmb_free.items()}
        for chunk in dead:
            # the FM re-granted the underlying blocks blank; the old
            # allocation bookkeeping is unrecoverable, so drop references
            # without freeing (the journal is the recovery source of truth)
            self._lmb_pools[chunk] = None
            self._lmb_allocs[chunk] = None
            self._lmb_homes[chunk] = -1
            self._lmb_used[chunk] = 0
        self.metrics.event(
            self.name, "failover: LMB pages on expander "
                       f"{'*' if expander_id is None else expander_id} "
                       "invalidated")

    def _on_repair(self, expander_id: int) -> None:
        """A failed expander was readmitted (blank).  If the pool is
        healthy again, exit degraded mode: paging may grow fresh LMB
        chunks — with fresh capabilities and fresh SAT/IOMMU mappings —
        on the repaired capacity.  Nothing is restored retroactively:
        pages invalidated at failure stay 'never written', and chunk
        handles freed (or orphaned) while degraded stay stale.  A
        CLOSED buffer never leaves degraded mode — close() means the
        footprint was released for good."""
        if self._closed:
            return
        if self.degraded and self.host.fm.healthy:
            self.degraded = False
            self.metrics.event(
                self.name,
                f"repair: expander {expander_id} readmitted; LMB tier "
                "available again")

    # --------------------------------------------------------------- validation
    def _check(self, page: int) -> None:
        if not 0 <= page < len(self._pages):
            raise IndexError(f"{self.name}: page {page} out of range")

    def check_invariants(self) -> None:
        """Structural invariants (exercised by hypothesis tests)."""
        onboard_slots = [e.slot for e in self._pages if e.tier == ONBOARD]
        assert len(onboard_slots) == len(set(onboard_slots)), "slot aliasing"
        assert len(onboard_slots) + len(self._onboard_free) == \
            self.onboard_pages, "onboard slot leak"
        lmb_slots = [e.slot for e in self._pages if e.tier == LMB]
        assert len(lmb_slots) == len(set(lmb_slots)), "lmb slot aliasing"
        alive = [c for c, p in enumerate(self._lmb_pools) if p is not None]
        total_lmb = len(alive) * self._lmb_chunk_pages
        free_flat = [s for lst in self._lmb_free.values() for s in lst]
        assert len(free_flat) == len(set(free_flat)), "free slot aliasing"
        assert len(lmb_slots) + len(free_flat) == total_lmb, \
            "lmb slot leak"
        for eid, lst in self._lmb_free.items():
            for s in lst:
                assert self._lmb_homes[s // self._lmb_chunk_pages] == eid, \
                    "free-list home drift"
        for slot in lmb_slots + free_flat:
            assert self._lmb_pools[slot // self._lmb_chunk_pages] \
                is not None, "slot points at reclaimed chunk"
        for chunk in alive:
            base = chunk * self._lmb_chunk_pages
            used = sum(1 for s in lmb_slots
                       if base <= s < base + self._lmb_chunk_pages)
            assert used == self._lmb_used[chunk], "chunk occupancy drift"
        for slot, page in self._onboard_owner.items():
            e = self._pages[page]
            assert e.tier == ONBOARD and e.slot == slot, "owner map stale"
        assert len(self._heat_val) >= len(self._pages), "heat array drift"

    def prefetch_stats(self) -> dict:
        """Prefetch-path health: burst counts, usefulness (used vs
        wasted), deferrals, and the wait the overlap window hid."""
        st = {
            "enabled": self.prefetcher is not None,
            "bursts": self.prefetch_bursts,
            "pages": self.prefetch_pages_total,
            "used": self.prefetch_used,
            "wasted": self.prefetch_wasted,
            "unread": len(self._prefetched),
            "deferred": self.prefetch_deferred,
            "hidden_wait_s": self.prefetch_hidden_s,
            "backlog": self.prefetcher.pending() if self.prefetcher else 0,
        }
        if self.overlap is not None:
            st["overlap"] = self.overlap.snapshot()
        return st

    def stats(self) -> dict:
        tiers = {ONBOARD: 0, LMB: 0, "unmaterialized": 0}
        for e in self._pages:
            tiers[e.tier if e.tier else "unmaterialized"] += 1
        c = self.metrics.tier(self.name, ONBOARD)
        return {
            "pages": self.num_pages,
            "resident": tiers,
            "hit_ratio": c.hit_ratio,
            "lmb_bytes_held": self.host.owned_bytes(self.device_id),
            "degraded": self.degraded,
            "link_wait_s": self.link_wait_s,
            "link_utilization": self.host.fm.link_utilization(),
            "lmb_placement": self.lmb_placement(),
            "prefetch": self.prefetch_stats(),
        }
