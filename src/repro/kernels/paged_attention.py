"""Paged decode attention — Pallas TPU kernel (the LMB data path).

This kernel IS the paper's L2P scenario on a TPU: the KV cache lives in a
paged pool (HBM tier of the LinkedBuffer); each request's logical sequence
is scattered across pool pages; the **page table is consulted on every
access** exactly like the SSD firmware consults its L2P table.  The table
rides in SMEM via scalar prefetch — the Pallas equivalent of "allocator
metadata stays host-side / on-board" (§3.2): the lookup never touches the
paged data tier.

Grid (B,): the page walk happens INSIDE the kernel as a fori_loop over
the sequence's live pages, with **double-buffered K/V page loads** — while
page i feeds the softmax/matmul, page i+1's DMA from the HBM pool is
already in flight (the link-layer overlap idea pushed down into the
kernel).  Each DMA moves one whole page, all KV heads at once: the KV
axis is tiled in HBM, so a per-head slice of a page would cut the tile,
which the TPU compiler refuses.  A static loop over the KV heads then
reads each head's
``[T, hd]`` slab from VMEM.  The pool arrays stay in ``pl.ANY`` (HBM) and
only the two in-flight pages ever occupy VMEM, so pool size is bounded by
HBM, not VMEM.

Unmapped pages (table entry -1) are clamped to page 0 for the DMA and
masked out of the softmax — reads are always in-bounds (IOMMU discipline)
and their probability mass is exactly zero.

``paged_attention_xla`` is the byte-compatible decode fallback for
off-TPU runs: it reproduces the dense decode path's einsum/softmax
ordering bit-for-bit (same contraction equation, f32 accumulation, -1e30
masking, post-einsum scaling) so the serve engine's paged decode emits
byte-identical tokens to the retired dense-slot path on CPU CI.
"""

from __future__ import annotations

import functools
import math
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(table_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
               k_buf, v_buf, sem, m_ref, l_ref, acc_ref,
               *, page_tokens: int, kv_heads: int):
    b = pl.program_id(0)
    T = page_tokens
    length = len_ref[b]
    n_pages = (length + T - 1) // T

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def page_dma(slot, ip):
        """Async copies pool page table[b, ip] (all KV heads) into VMEM
        slot: one whole-page DMA each for K and V, so no copy cuts the
        tiled KV axis."""
        page = jnp.maximum(table_ref[b, ip], 0)
        return (pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot],
                                      sem.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot],
                                      sem.at[slot, 1]))

    @pl.when(n_pages > 0)
    def _warmup():
        for cp in page_dma(0, 0):
            cp.start()

    def body(ip, _):
        slot = jax.lax.rem(ip, 2)

        # hide the next page load behind this page's softmax/matmul
        @pl.when(ip + 1 < n_pages)
        def _start_next():
            for cp in page_dma(jax.lax.rem(ip + 1, 2), ip + 1):
                cp.start()

        for cp in page_dma(slot, ip):
            cp.wait()
        for h in range(kv_heads):
            q = q_ref[h].astype(jnp.float32)            # [G, hd]
            k = k_buf[slot, :, h, :].astype(jnp.float32)  # [T, hd]
            v = v_buf[slot, :, h, :].astype(jnp.float32)  # [T, hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())))         # [G, T]
            pos = ip * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = (pos < length) & (table_ref[b, ip] >= 0)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            # masked lanes contribute exactly zero even when the whole
            # page is masked (m stays at NEG_INF, so exp(s - m) would be
            # 1, not 0)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            m_ref[h] = m_new
        return 0

    jax.lax.fori_loop(0, n_pages, body, 0)
    l = jnp.maximum(l_ref[...], 1e-20)              # length-0 rows -> 0
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale_override", "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, lengths: jax.Array,
                    *, scale_override: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """q [B,H,hd]; k/v_pages [P,T,KV,hd]; page_table [B,MP] int32 (-1 =
    unmapped); lengths [B] -> out [B,H,hd]."""
    B, H, hd = q.shape
    P, T, KV, _ = k_pages.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd) if scale_override is None else \
        scale_override
    qs = (q.reshape(B, KV, G, hd) * scale).astype(q.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, KV, G, hd), lambda b, tbl, ln: (b, 0, 0, 0)),
            # the pool stays in HBM; the kernel DMAs pages on demand
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, KV, G, hd),
                               lambda b, tbl, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, T, KV, hd), k_pages.dtype),  # double buffer: K
            pltpu.VMEM((2, T, KV, hd), v_pages.dtype),  # double buffer: V
            pltpu.SemaphoreType.DMA((2, 2)),            # [slot, k/v]
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_pa_kernel, page_tokens=T, kv_heads=KV),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qs, k_pages, v_pages)
    return out.reshape(B, H, hd)


def paged_attention_xla(q, k_pages, v_pages, page_table, lengths,
                        *, scale_override: float | None = None):
    """Decode-shaped XLA fallback, byte-compatible with the dense path.

    Semantics match :func:`paged_attention`; numerics match the dense
    decode attention (`models.attention._scores_softmax_out`) **bitwise**:
    the same einsum contraction (f32 accumulation, scale applied after),
    -1e30 masking before a plain softmax, and the probabilities cast back
    to the V dtype for the output contraction.  Masked lanes underflow to
    exactly 0 after softmax, so clamped-page garbage never leaks — this
    is what lets the serve engine swap its dense slot cache for the paged
    pool without perturbing a single emitted token on CPU CI.
    """
    B, H, hd = q.shape
    P, T, KV, _ = k_pages.shape
    MP = page_table.shape[1]
    G = H // KV
    scale = 1.0 / math.sqrt(hd) if scale_override is None else \
        scale_override
    safe = jnp.maximum(page_table, 0)
    k = k_pages[safe].reshape(B, MP * T, KV, hd)
    v = v_pages[safe].reshape(B, MP * T, KV, hd)
    qg = q.reshape(B, 1, KV, G, hd)
    pos = jnp.arange(MP * T)[None, :]
    valid = (pos < lengths[:, None]) & \
        jnp.repeat(page_table >= 0, T, axis=1)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)
    # all-masked rows (length 0): softmax degenerates to uniform over
    # NEG_INF lanes; zero them like the kernel does
    any_valid = jnp.any(valid, axis=1)[:, None, None, None, None]
    o = jnp.where(any_valid, o, 0.0)
    return o.reshape(B, H, hd).astype(q.dtype)
