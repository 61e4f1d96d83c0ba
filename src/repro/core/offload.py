"""JAX execution of LMB tier moves.

The LMB pool's *live* backing store on a TPU host is pinned host memory —
the byte-addressable, larger, slower tier behind PCIe (DESIGN.md §2).  JAX
exposes it via sharding ``memory_kind``:

  * ``device``       — HBM (the "onboard" tier)
  * ``pinned_host``  — host DRAM reachable by the TPU DMA engines (the "LMB"
                       tier; DMA-able without a bounce buffer = the paper's
                       P2P/CXL.mem path)
  * ``unpinned_host``— pageable host memory (needs a staging copy = the
                       paper's host-forwarded PCIe path)

LMB page moves (:class:`TierExecutor`) are eager ``jax.device_put`` calls
between compiled steps, the same code on every backend: the named pages
cross, nothing else.  With its tracer on, the executor's moves run under
``exec.read_page(s)`` / ``exec.write_page(s)`` spans; its ``hbm_meter``
hook is charged the bytes each of its ops writes on HBM.  Whole-tree moves (:func:`put_tier`) serve training
state; :func:`supports_in_jit_offload` says whether a backend can instead
compile ``memory_kind`` annotations into a step.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.obs.trace import GLOBAL_TRACER, SpanTracer

DEVICE = "device"
PINNED_HOST = "pinned_host"
UNPINNED_HOST = "unpinned_host"

#: ``jnp.stack`` compiled once per (page count, page shape): stacking a
#: burst of pages eagerly dispatches one op per page
stack_pages = jax.jit(jnp.stack)


@functools.cache
def backend_memory_kinds() -> tuple:
    return tuple(m.kind for m in jax.devices()[0].addressable_memories())


@functools.cache
def supports_in_jit_offload() -> bool:
    """Whether ``memory_kind`` annotations survive compile on this backend."""
    dev = jax.devices()[0]
    if PINNED_HOST not in backend_memory_kinds():
        return False
    try:
        s = SingleDeviceSharding(dev, memory_kind=PINNED_HOST)
        jax.jit(lambda a: a * 2, out_shardings=s).lower(
            jax.ShapeDtypeStruct((1,), jnp.float32)).compile()
        return True
    except Exception:
        return False


def with_memory_kind(sharding, memory_kind: str):
    """Rebuild a (Named|SingleDevice)Sharding with a different memory kind."""
    if isinstance(sharding, NamedSharding):
        return NamedSharding(sharding.mesh, sharding.spec,
                             memory_kind=memory_kind)
    if isinstance(sharding, SingleDeviceSharding):
        return SingleDeviceSharding(sharding._device,
                                    memory_kind=memory_kind)
    raise TypeError(f"cannot retier {type(sharding)}")


def _aval_on_host(x: jax.Array) -> bool:
    """True if the array's *aval* carries Host memory space.  The aval is
    authoritative: an eager slice of a host array reports a ``device``
    sharding while its aval (and data) stay in host memory, and ops that
    mix it with device operands are rejected."""
    ms = getattr(x.aval, "memory_space", None)
    return ms is not None and "host" in str(ms).lower()


def put_tier(x: jax.Array, memory_kind: str) -> jax.Array:
    """Eagerly move an array to a tier (whole-tree moves of training
    state)."""
    if tier_of(x) == memory_kind:
        return x
    # may_alias=False: a host slice's sharding already names the device,
    # and only a forced copy lands it in device memory
    return jax.device_put(x, with_memory_kind(x.sharding, memory_kind),
                          may_alias=False)


def tree_put_tier(tree: Any, memory_kind: str) -> Any:
    return jax.tree_util.tree_map(lambda x: put_tier(x, memory_kind), tree)


def tier_of(x: jax.Array) -> str:
    if _aval_on_host(x):
        mk = getattr(x.sharding, "memory_kind", None)
        return mk if mk not in (None, DEVICE) else PINNED_HOST
    return getattr(x.sharding, "memory_kind", None) or DEVICE


def offload_shardings(shardings: Any, memory_kind: str = PINNED_HOST) -> Any:
    """Map a pytree of shardings to the offload tier (for in-jit mode)."""
    return jax.tree_util.tree_map(
        lambda s: with_memory_kind(s, memory_kind), shardings)


def nbytes_of(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
               for l in leaves)


class HostPool:
    """An LMB-tier pool: one array per page, each in pinned host memory.

    JAX gathers and scatters only within one memory space, so a single
    host array cannot be indexed with device-memory indices.  Instead
    every page is an array of its own, and a move hands exactly the named
    pages to one ``jax.device_put``: its cost scales with the page count,
    never with the pool.  A fresh pool shares one zero page across its
    slots (arrays are immutable; a write replaces the slot's entry)."""

    def __init__(self, pages: List[jax.Array]):
        self.pages = pages

    @property
    def page_bytes(self) -> int:
        return self.pages[0].nbytes


class TierExecutor:
    """Executes LinkedBuffer page moves on JAX arrays.

    The onboard pool is one device (HBM) array, read and written with a
    gather or scatter.  The LMB pool is a :class:`HostPool` in
    ``pinned_host`` memory; its pages cross the host link with
    ``jax.device_put``, on every backend alike.  A backend without
    ``pinned_host`` memory has no LMB tier, and construction fails.
    """

    lmb_memory_kind = PINNED_HOST

    def __init__(self, meter: Optional[Callable[[int], float]] = None,
                 trace: Optional[SpanTracer] = None,
                 hbm_meter: Optional[Callable[[int], None]] = None):
        if PINNED_HOST not in backend_memory_kinds():
            raise RuntimeError(
                f"backend {jax.default_backend()!r} has no {PINNED_HOST!r} "
                f"memory (it has {backend_memory_kinds()}): the LMB tier "
                "needs host memory the device can DMA")
        dev = jax.devices()[0]
        self._host = SingleDeviceSharding(dev, memory_kind=PINNED_HOST)
        self._device = SingleDeviceSharding(dev, memory_kind=DEVICE)
        #: span tracer for coalesced pool transfers (wall-clock spans —
        #: the executor runs real JAX ops, unlike the modeled link path)
        self.trace = trace if trace is not None else GLOBAL_TRACER
        #: QoS hook: charged with nbytes for every page crossing the
        #: host<->device boundary (the expander-link analogue on a TPU
        #: host); typically LMBHost.meter_transfer bound to a device id.
        self.meter = meter
        #: charged with the bytes every array op of the data path writes
        #: on HBM: gathers, slices, stacks, and each onboard scatter the
        #: whole pool (the update is not donated)
        self.hbm_meter = hbm_meter

    def count_hbm(self, nbytes: int) -> None:
        if self.hbm_meter is not None:
            self.hbm_meter(nbytes)

    def _meter(self, pool, nbytes: int) -> None:
        if self.meter is not None and isinstance(pool, HostPool):
            self.meter(nbytes)

    @staticmethod
    def _page_bytes(pool) -> int:
        if isinstance(pool, HostPool):
            return pool.page_bytes
        return int(np.prod(pool.shape[1:])) * jnp.dtype(pool.dtype).itemsize

    def alloc_pool(self, npages: int, page_shape: tuple, dtype, tier: str):
        if tier == "lmb":
            zero = jax.device_put(jnp.zeros(page_shape, dtype), self._host)
            return HostPool([zero] * npages)
        return jnp.zeros((npages, *page_shape), dtype=dtype)

    def read_page(self, pool, slot: int) -> jax.Array:
        self._meter(pool, self._page_bytes(pool))
        tr = self.trace
        if tr.enabled:
            with tr.span("exec.read_page", op="demand",
                         nbytes=self._page_bytes(pool),
                         tier=self._tier(pool)):
                return self._read_page(pool, int(slot))
        return self._read_page(pool, int(slot))

    def _read_page(self, pool, slot: int) -> jax.Array:
        if isinstance(pool, HostPool):
            return jax.device_put(pool.pages[slot], self._device)
        self.count_hbm(self._page_bytes(pool))
        return pool[slot]

    def write_page(self, pool, slot: int, page: jax.Array):
        self._meter(pool, self._page_bytes(pool))
        tr = self.trace
        if tr.enabled:
            with tr.span("exec.write_page", op="demand",
                         nbytes=self._page_bytes(pool),
                         tier=self._tier(pool)):
                return self._write_page(pool, int(slot), jnp.asarray(page))
        return self._write_page(pool, int(slot), jnp.asarray(page))

    def _write_page(self, pool, slot: int, page: jax.Array):
        if isinstance(pool, HostPool):
            pool.pages[slot] = jax.device_put(page, self._host)
            return pool
        self.count_hbm(pool.nbytes)
        return pool.at[slot].set(page)

    # ---- coalesced multi-page transfers (the batched data path) ----
    # One gather/scatter against an onboard pool, or one device_put of
    # the named pages of an LMB pool, instead of N single-page moves; the
    # meter hook (when bound) sees ONE charge for the burst's total bytes
    # — the overlap scheduler then has whole runs, not single pages, to
    # hide behind compute.

    def read_pages(self, pool, slots: Sequence[int]) -> jax.Array:
        """Coalesced read: ``[len(slots), *page_shape]`` stacked onboard.
        Duplicate slots are allowed (a gather may repeat pages)."""
        self._meter(pool, self._page_bytes(pool) * len(slots))
        tr = self.trace
        if tr.enabled:
            with tr.span("exec.read_pages", op="demand",
                         nbytes=self._page_bytes(pool) * len(slots),
                         pages=len(slots), tier=self._tier(pool)):
                return self._read_pages(pool, slots)
        return self._read_pages(pool, slots)

    def _read_pages(self, pool, slots: Sequence[int]) -> jax.Array:
        if len(slots) == 1:
            # a one-page burst skips the gather/stack machinery (~10x in
            # eager dispatch) — the decode path (1 page per step) lives here
            self.count_hbm(self._page_bytes(pool))
            return self._read_page(pool, int(slots[0]))[None]
        self.count_hbm(self._page_bytes(pool) * len(slots))
        if isinstance(pool, HostPool):
            return stack_pages(jax.device_put(
                [pool.pages[int(s)] for s in slots], self._device))
        return pool[jnp.asarray(np.asarray(slots, np.int32))]

    def write_pages(self, pool, slots: Sequence[int], pages: jax.Array):
        """Coalesced write of ``pages[i] -> pool[slots[i]]``.  Slots must
        be distinct (scatter order over duplicates is undefined)."""
        self._meter(pool, self._page_bytes(pool) * len(slots))
        tr = self.trace
        if tr.enabled:
            with tr.span("exec.write_pages", op="demand",
                         nbytes=self._page_bytes(pool) * len(slots),
                         pages=len(slots), tier=self._tier(pool)):
                return self._write_pages(pool, slots, pages)
        return self._write_pages(pool, slots, pages)

    def _write_pages(self, pool, slots: Sequence[int], pages: jax.Array):
        pages = jnp.asarray(pages)
        if len(slots) == 1:
            self.count_hbm(pages.nbytes)             # the row
            return self._write_page(pool, int(slots[0]), pages[0])
        if isinstance(pool, HostPool):
            self.count_hbm(pages.nbytes)             # the rows
            rows = jax.device_put(list(pages), self._host)
            for s, row in zip(slots, rows):
                pool.pages[int(s)] = row
            return pool
        self.count_hbm(pool.nbytes)
        return pool.at[jnp.asarray(np.asarray(slots, np.int32))].set(pages)

    @staticmethod
    def _tier(pool) -> str:
        return PINNED_HOST if isinstance(pool, HostPool) else DEVICE
