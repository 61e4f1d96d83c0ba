"""Paged KV storage on a LinkedBuffer — the LMB applied to serving.

A request's KV state is chopped into **KV pages** (``page_tokens`` tokens
of all layers' K+V at once) and stored as LinkedBuffer logical pages:

  * the working set of ACTIVE requests stays in the onboard (HBM) tier;
  * preempted / waiting requests' KV parks in the LMB pool (the paper's
    "exchange time for space"): admission capacity is the POOL size, not
    HBM;
  * prefix sharing = LinkedBuffer.share (zero-copy, copy-on-write) — the
    paper's shared-buffer SSD→accelerator scenario;
  * swap-in cost is predicted with the tier model so the scheduler can
    decide hide-or-stall (repro.core.tiers.hideable_page_bytes).

Layout per logical page: [L, 2, page_tokens, KV, hd] (K and V stacked) —
one DMA per page move, layer-major so a layer-by-layer decode can stream.

A prefill slab becomes its pages in ONE compiled call
(:func:`pack_slab`: pad to whole pages, reshape, move the page axis
first), written with one ``write_many`` burst.  Pages the slab opens
are fresh, so nothing is read for them; only a slab that starts
mid-page reads that one page, whose live tokens the same call keeps.

Every array op the KV path runs on HBM — gathers, pads, stacks, the
packed slab, the onboard pool's scatters (the whole pool: the update is
not donated) and the pool the decode step returns — adds the bytes it
writes to the host registry's ``kv.hbm_copy_bytes`` counter, computed
from shapes at the call.  With tracing on, ``kv.append``, ``kv.view``
and ``kv.commit`` spans cover the store's three data-path calls.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import LMBHost
from repro.core.buffer import LinkedBuffer
from repro.core.client import LMBSystem
from repro.core.offload import TierExecutor
from repro.core.overlap import OverlapScheduler

#: counter of the bytes the KV path's array ops write on HBM
HBM_COPY_BYTES = "kv.hbm_copy_bytes"
#: counters of the multi-page append: pages :func:`pack_slab` built, and
#: pages read first because the slab starts in the middle of one
APPEND_PAGES_PACKED = "kv.append_pages_packed"
APPEND_PAGES_READ = "kv.append_pages_read"


@functools.partial(jax.jit, static_argnames=("n_pages", "page_tokens"))
def pack_slab(kv: jax.Array, off, head: Optional[jax.Array] = None, *,
              n_pages: int, page_tokens: int) -> jax.Array:
    """Lay a slab ``kv [L, 2, T, KV, hd]`` out as its KV pages
    ``[n_pages, L, 2, page_tokens, KV, hd]``, the slab's first token at
    token ``off`` of the first page, in one device program.  Slots the
    slab does not cover are zeros, except that the first page keeps
    ``head``'s tokens before ``off`` when ``head`` (that page's current
    contents) is given.  ``off`` is traced: one program per slab shape
    and page count, whatever the offset."""
    L, two, _, KV, hd = kv.shape
    flat = jnp.zeros((L, two, n_pages * page_tokens, KV, hd), kv.dtype)
    flat = jax.lax.dynamic_update_slice_in_dim(flat, kv, off, axis=2)
    pages = jnp.moveaxis(
        flat.reshape(L, two, n_pages, page_tokens, KV, hd), 2, 0)
    if head is not None:
        live = (jnp.arange(page_tokens) < off)[:, None, None]
        pages = pages.at[0].set(jnp.where(live, head, pages[0]))
    return pages


@dataclasses.dataclass
class SeqPages:
    """Page bookkeeping for one sequence."""

    seq_id: int
    pages: List[int] = dataclasses.field(default_factory=list)
    length: int = 0


@dataclasses.dataclass
class DecodeView:
    """One decode round's batched view over the paged pool.

    ``pool`` is the union of the active sequences' pages materialized
    onboard with ONE coalesced ``read_many`` burst (padded with zero
    pages to a power of two so the compiled step sees few distinct pool
    shapes); ``tables`` indexes INTO THE POOL (not logical page ids), so
    a compiled paged-attention step can consume it directly.  ``pages``
    is the round's touched-page list — exactly what rides the
    schedule_prefetch / meter accounting so modeled link traffic
    reconciles with ``fm.op_bytes()``.
    """

    sids: List[int]
    pool: jax.Array          # [P_pad, L, 2, T, KV, hd]
    tables: np.ndarray       # [B, MP] int32 pool indices (-1 pad)
    lengths: np.ndarray      # [B] int32 tokens stored (pre-step)
    pages: List[int]         # union logical pages backing pool[:n]
    tail_pages: List[int]    # per-sequence logical tail page
    tail_index: List[int]    # per-sequence pool index of the tail page


class PagedKVStore:
    """KV pages over a LinkedBuffer.  Construct with ``system=`` (an
    :class:`~repro.core.client.LMBSystem` session — the client API) or,
    for low-level wiring, a bare ``host=`` LMBHost."""

    def __init__(self, *, cfg, host: Optional[LMBHost] = None,
                 system: Optional[LMBSystem] = None,
                 host_id: Optional[str] = None,
                 device_id: str,
                 page_tokens: int = 64, onboard_pages: int = 64,
                 n_layers: Optional[int] = None,
                 compress_cold: bool = False,
                 prefetch_depth: int = 2,
                 overlap: Optional[OverlapScheduler] = None,
                 executor: Optional[TierExecutor] = None):
        if host is None:
            if system is None:
                raise ValueError("PagedKVStore needs system= or host=")
            host = system.host(host_id)
        self.cfg = cfg
        L = n_layers or cfg.num_layers
        KV, hd = cfg.num_kv_heads, cfg.head_dim_
        self.page_tokens = page_tokens
        self.page_shape = (L, 2, page_tokens, KV, hd)
        self.buf = LinkedBuffer(
            name=f"kv:{device_id}", device_id=device_id, host=host,
            executor=executor, page_shape=self.page_shape,
            dtype=jnp.dtype(cfg.dtype), onboard_pages=onboard_pages,
            policy="cost", prefetch_depth=prefetch_depth,
            overlap=overlap, compress_lmb=compress_cold)
        self.metrics = host.metrics
        self.buf.executor.hbm_meter = self.count_copy
        self._seqs: Dict[int, SeqPages] = {}
        self._next_id = 0

    # ------------------------------------------------------------ lifecycle
    def new_seq(self) -> int:
        sid = self._next_id
        self._next_id += 1
        self._seqs[sid] = SeqPages(sid)
        return sid

    def seq(self, sid: int) -> SeqPages:
        return self._seqs[sid]

    def count_copy(self, nbytes: int) -> None:
        """Count ``nbytes`` written on HBM by an array op of the KV path."""
        self.metrics.inc(HBM_COPY_BYTES, nbytes)

    def copied_bytes(self) -> float:
        """The ``kv.hbm_copy_bytes`` counter (every store of the registry)."""
        return self.metrics.counter(HBM_COPY_BYTES)

    def free_seq(self, sid: int) -> None:
        for p in self._seqs[sid].pages:
            self.buf.release(p)
        del self._seqs[sid]

    def fork(self, sid: int) -> int:
        """Prefix share — the Table-2 ``share`` scenario: the new
        sequence maps the parent's whole pages zero-copy, with one
        batched ``share_many`` call.  A partially filled tail page, which
        the next append of either sequence writes, is copied into a page
        of the fork's own: a write through a shared page would copy it
        under the logical id both sequences map, and each would then see
        the other's tokens."""
        new = self.new_seq()
        src = self._seqs[sid]
        dst = self._seqs[new]
        full = src.length // self.page_tokens
        dst.pages = self.buf.share_many(src.pages[:full])
        if src.length % self.page_tokens:
            tail = self.buf.read(src.pages[full])
            dst.pages.extend(self.buf.append_pages(1))
            self.buf.write(dst.pages[-1], tail)
        dst.length = src.length
        return new

    # ------------------------------------------------------------ data path
    def append_tokens(self, sid: int, kv: jax.Array) -> None:
        """kv [L, 2, T, KV, hd] for T new tokens (one from decode, a
        whole prompt from prefill).  A slab within one page is a plain
        read, update and write of that page.  A slab over several pages
        is laid out as its pages by ONE compiled :func:`pack_slab` call
        and written with ONE ``write_many`` burst (one coalesced
        transfer per LMB chunk).  The pages it opens are fresh, so
        nothing is read for them; a slab that starts mid-page reads that
        first page (faulting it in from the LMB tier if it is there), and
        the same call keeps its live tokens.  The ``kv.append`` span
        carries ``pages`` (written) and ``read`` (read first)."""
        with self.buf.trace.span("kv.append", op="demand",
                                 tokens=kv.shape[2]) as args:
            pages, read = self._append_tokens(sid, kv)
            if args is not None:
                args.update(pages=pages, read=read)

    def _append_tokens(self, sid: int, kv: jax.Array) -> tuple:
        """Append the slab; returns (pages written, pages read)."""
        seq = self._seqs[sid]
        T = kv.shape[2]
        if T == 0:
            return 0, 0                   # empty slab: scalar loop no-op
        pt = self.page_tokens
        off, first = seq.length % pt, seq.length // pt
        n = -(-(off + T) // pt)           # pages the slab touches
        opened = n - 1 if off else n      # each page it enters at token 0
        if opened:
            seq.pages.extend(self.buf.append_pages(opened))
        pages = seq.pages[first:first + n]
        if n == 1:
            # decode path: one page per token — plain scalar read/write,
            # no stack/batch machinery on the hottest per-token path
            cur = self.buf.read(pages[0])
            self.count_copy(cur.nbytes)           # the updated page
            self.buf.write(pages[0], jax.lax.dynamic_update_slice_in_dim(
                cur, kv, off, axis=2))
            seq.length += T
            return 1, 1
        read = int(off > 0)
        head = self.buf.read(pages[0]) if read else None
        packed = pack_slab(kv, off, head, n_pages=n, page_tokens=pt)
        self.count_copy(packed.nbytes)            # the packed pages
        self.metrics.inc(APPEND_PAGES_PACKED, n)
        self.metrics.inc(APPEND_PAGES_READ, read)
        self.buf.write_many(pages, packed)
        seq.length += T
        return n, read

    def gather_seq(self, sid: int) -> jax.Array:
        """Materialize a sequence's KV [L, 2, seq.length, KV, hd] onboard
        (used for swap-in to a dense decode slot).  The token axis is
        trimmed to the sequence's true length — the tail page's unwritten
        slots are allocator garbage and must never reach attention (the
        silent padded return was the PR-10 bug class).  ``gather`` rides
        the batched path: one coalesced transfer per LMB chunk and one
        arbiter charge per expander link for the whole sequence."""
        seq = self._seqs[sid]
        if not seq.pages:
            return jnp.zeros(self.page_shape, self.buf.dtype)[:, :, :0]
        stacked = self.buf.gather(seq.pages)       # [n, L, 2, T, KV, hd]
        n = stacked.shape[0]
        L, _, T, KV, hd = self.page_shape
        full = jnp.moveaxis(stacked, 0, 2).reshape(L, 2, n * T, KV, hd)
        return full[:, :, :seq.length]

    def pin_seq(self, sid: int) -> None:
        """Pin a sequence's pages onboard with ONE batched fault burst
        (a compiled step is about to DMA them)."""
        self.buf.pin_many(self._seqs[sid].pages)

    def unpin_seq(self, sid: int) -> None:
        self.buf.unpin_many(self._seqs[sid].pages)

    def next_decode_pages(self, sid: int) -> List[int]:
        """The KV pages the NEXT decode step of this sequence will touch
        — exact future knowledge for the prefetcher.  A token landing at
        a page boundary opens a fresh page (nothing to fetch); otherwise
        the partially-filled tail page is read-modified-written."""
        seq = self._seqs[sid]
        if seq.length == 0 or seq.length % self.page_tokens == 0:
            return []
        return [seq.pages[seq.length // self.page_tokens]]

    def schedule_prefetch(self, pages: List[int]) -> None:
        """Feed a batch round's worth of scheduled page accesses to the
        buffer's prefetcher: pages move as coalesced per-(chunk,
        expander) bursts, bounded by free slots and the overlap window
        (remainder deferred, not dropped)."""
        self.buf.schedule_prefetch(pages)

    def note_compute_window(self, seconds: float,
                            observed: bool = True) -> None:
        """Report one decode round's compute time so the overlap
        scheduler can size the next prefetch window.  ``observed=False``
        pins the window exactly instead of folding the sample into the
        EWMA estimate (virtual-time sweeps with a declared round
        duration)."""
        self.buf.note_compute_window(seconds, observed=observed)

    def schedule_swap_in(self, sid: int) -> None:
        self.schedule_prefetch(self._seqs[sid].pages)

    # ----------------------------------------------------------- accounting
    def lmb_resident_pages(self) -> int:
        """KV pages currently parked in the LMB pool tier (not onboard)
        — the "concurrent sequences backed by LMB-resident KV" figure a
        load sweep reports alongside its latency table."""
        return self.buf.stats()["resident"].get("lmb", 0)

    def parked_sequences(self) -> int:
        """Sequences whose KV is entirely LMB/unmaterialized-resident —
        admitted work the onboard tier is NOT holding pages for."""
        return sum(1 for s in self._seqs.values()
                   if s.pages and not any(self.buf.tier_of(p) == "onboard"
                                          for p in s.pages))

    def stats(self) -> dict:
        st = self.buf.stats()
        st["sequences"] = len(self._seqs)
        st["page_tokens"] = self.page_tokens
        return st

    def page_table(self, sid: int, max_pages: int) -> np.ndarray:
        """int32 [max_pages] logical page ids (-1 pad) — feeds the Pallas
        paged-attention kernel on TPU.  Raises ``ValueError`` when the
        sequence has outgrown the table: the old behavior silently
        dropped the tail pages (numpy slice clamping), which would make
        attention read garbage for every token past the table edge."""
        seq = self._seqs[sid]
        if len(seq.pages) > max_pages:
            raise ValueError(
                f"seq {sid}: {len(seq.pages)} pages exceed the "
                f"{max_pages}-entry page table (length {seq.length}, "
                f"page_tokens {self.page_tokens}) — the tail KV would be "
                f"silently dropped")
        out = np.full((max_pages,), -1, np.int32)
        out[:len(seq.pages)] = seq.pages
        return out

    def page_tables(self, sids: List[int],
                    max_pages: int) -> tuple:
        """Batched decode view: (tables int32 [B, max_pages] logical page
        ids with -1 pad, lengths int32 [B]) for one engine round's active
        sequences — the host-side half of the kernel's L2P lookup.
        Raises like :meth:`page_table` instead of truncating."""
        tables = np.full((len(sids), max_pages), -1, np.int32)
        lengths = np.zeros((len(sids),), np.int32)
        for i, sid in enumerate(sids):
            tables[i] = self.page_table(sid, max_pages)
            lengths[i] = self._seqs[sid].length
        return tables, lengths

    # ------------------------------------------------------- paged decode
    def ensure_tail_page(self, sid: int) -> int:
        """Guarantee the page the sequence's NEXT token lands in exists
        (a token at a page boundary opens a fresh page); returns its
        logical id.  Allocation is logical-only — the page materializes
        on first touch."""
        seq = self._seqs[sid]
        idx = seq.length // self.page_tokens
        if len(seq.pages) == idx:
            seq.pages.extend(self.buf.append_pages(1))
        return seq.pages[idx]

    def decode_view(self, sids: List[int], max_pages: int) -> DecodeView:
        """Build one round's batched decode view: tail pages guaranteed,
        the union of the active sequences' pages faulted onboard with ONE
        coalesced ``read_many`` burst (metered exactly like any other
        batched access — hits for onboard-resident pages, link charges
        only for LMB misses, waves when the union exceeds onboard
        capacity), and page tables rewritten into pool-index space for
        the compiled step.  Active sequences must not share a tail page
        (the engine never forks a mid-flight sequence)."""
        with self.buf.trace.span("kv.view", op="demand", batch=len(sids)):
            return self._decode_view(sids, max_pages)

    def _decode_view(self, sids: List[int], max_pages: int) -> DecodeView:
        for sid in sids:
            self.ensure_tail_page(sid)
        tables, lengths = self.page_tables(sids, max_pages)
        union: List[int] = []
        index: Dict[int, int] = {}
        for sid in sids:
            for p in self._seqs[sid].pages:
                if p not in index:
                    index[p] = len(union)
                    union.append(p)
        pool = self.buf.read_many(union)       # [n, L, 2, T, KV, hd]
        n = len(union)
        # pad with zero pages to a power of two: the compiled decode step
        # sees O(log) distinct pool shapes instead of one per round
        cap = max(8, 1 << (n - 1).bit_length())
        if cap > n:
            pool = jnp.concatenate(
                [pool, jnp.zeros((cap - n,) + self.page_shape,
                                 pool.dtype)])
            self.count_copy(pool.nbytes // cap * (2 * cap - n))
        pool_tables = np.full_like(tables, -1)
        mapped = tables >= 0
        pool_tables[mapped] = [index[p] for p in tables[mapped].tolist()]
        tail_pages = [
            self._seqs[sid].pages[self._seqs[sid].length //
                                  self.page_tokens]
            for sid in sids]
        tail_index = [index[p] for p in tail_pages]
        return DecodeView(sids=list(sids), pool=pool,
                          tables=pool_tables,
                          lengths=lengths, pages=union,
                          tail_pages=tail_pages, tail_index=tail_index)

    def commit_decode(self, view: DecodeView, pool: jax.Array) -> None:
        """Write one decode round's results back: only the tail pages
        changed (the step scatters the new token's K/V there), so ONE
        ``write_many`` burst covers the whole batch, and each sequence
        advances by the token it just stored."""
        with self.buf.trace.span("kv.commit", op="demand",
                                 batch=len(view.sids)):
            rows = pool[np.asarray(view.tail_index, np.int64)]
            self.count_copy(rows.nbytes)
            self.buf.write_many(view.tail_pages, rows)
            for sid in view.sids:
                self._seqs[sid].length += 1
