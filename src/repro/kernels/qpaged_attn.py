"""Pallas TPU kernels: attention over a *paged* int8 KV cache.

The serving cache stops being a dense ``(slots, max_len, Hkv, D)`` slab and
becomes a single shared pool ``(num_pages, page_size, Hkv, D)`` plus a
per-slot page table of pool indices (nn/attention.py
``init_paged_kv_cache``).  A slot's logical row ``p`` lives in pool page
``table[slot, p // page_size]`` at row ``p % page_size``; unallocated table
entries are ``-1``.  Both kernels here gather K/V blocks *through* the page
table, which arrives as scalar-prefetch metadata so the BlockSpec index maps
can turn a grid step into a pool-page DMA before the kernel body runs:

* :func:`qpaged_decode_attn_pallas` — the paged generalization of
  ``qdecode_attn``: one query per slot, flash over the slot's pages, per-slot
  live-length masking.  Grid ``(B, max_pages)``; page blocks past the
  slot's last live page clamp onto the last one (the revisit skips the DMA)
  and their accumulation is guarded, so per-slot work is proportional to the
  slot's *live* length, not ``max_pages``.
* :func:`qpaged_chunk_attn_pallas` — the paged generalization of
  ``qchunk_attn``: a C-token prompt chunk attends flash-style over its
  slot's pages with causal-in-chunk masking, and the chunk's K/V rows are
  quantized onto the paper's Qm.n grid and written in place into the slot's
  pages inside the same kernel (``input_output_aliases`` on the pools).

Each block is one whole page over all Hkv heads, viewed as ``(page_size,
Hkv * D)`` (a free reshape of the pool) and walked by static lane slices
per head: the TPU compiler refuses a block that takes one head out of the
second-minor axis, and a dynamic index into it.

Page-size note: blocks are one page, so on real TPU hardware ``page_size``
should be a multiple of the sublane tile (>= 128 ideally) to keep the DMA
engine busy; tests run both kernels in interpret mode where any size works.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import qformat

NEG_INF = -1e30
I8_MIN, I8_MAX = -128, 127
# The one-hot merges below are row gathers and must be exact: at its
# default precision the TPU compiler rounds f32 matmul operands to bf16 (on a
# v5e that moved K/V rows before they were quantized to int8).
EXACT = jax.lax.Precision.HIGHEST


def _quantize_i8(x: jax.Array, inv_scale: jax.Array) -> jax.Array:
    """sat(trunc(x * 2^n)) on the paper grid; inv_scale = 2^n (exact pow2)."""
    xf = x * inv_scale
    xq = jnp.where(xf >= 0, jnp.floor(xf), jnp.ceil(xf))  # trunc toward zero
    return jnp.clip(xq, I8_MIN, I8_MAX).astype(jnp.int8)


def _last_live_page(kv_len, ps: int):
    """Index of the last page holding a live row (0 when the slot is empty)."""
    return jnp.maximum(jax.lax.div(kv_len - 1, ps), 0)


# --------------------------------------------------------------------------
# Paged decode
# --------------------------------------------------------------------------

def _qpaged_decode_kernel(
    table_ref, len_ref, scales_ref, q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref, *, hkv: int, d: int, ps: int, n_pages: int,
    sm_scale: float,
):
    ib, ip = pl.program_id(0), pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[ib]
    last = _last_live_page(kv_len, ps)

    # Page blocks past the slot's last live page clamp onto it in the index
    # maps (no new DMA) and skip the flash update entirely.
    @pl.when(ip <= last)
    def _flash():
        k_scale = scales_ref[0]
        v_scale = scales_ref[1]
        pos = ip * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        # head h of the page block is the static lane slice [h*D, (h+1)*D)
        for h in range(hkv):
            lanes = slice(h * d, (h + 1) * d)
            q = q_ref[0, h]                                       # (G, D)
            k = k_ref[0, :, lanes].astype(jnp.float32) * k_scale  # (ps, D)
            v = v_ref[0, :, lanes].astype(jnp.float32) * v_scale

            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(pos < kv_len, s, NEG_INF)

            m_prev = m_ref[h]                                     # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ip == n_pages - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qpaged_decode_attn_pallas(
    q: jax.Array,           # (B, Hq, D) f32
    k_pool: jax.Array,      # (P, ps, Hkv, D) int8
    v_pool: jax.Array,
    k_n: jax.Array,         # scalar int32 dequant exponents (paper Qm.n grid)
    v_n: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32 pool indices, -1 = unmapped
    kv_len: jax.Array,      # (B,) per-slot live lengths
    *,
    interpret: bool = False,
) -> jax.Array:
    """GQA decode attention gathering the int8 KV cache through a page table.

    Args:
      q: ``(B, Hq, D)`` f32 queries, one token per slot (``Hq = G * Hkv``).
      k_pool / v_pool: ``(num_pages, page_size, Hkv, D)`` int8 shared pools.
      k_n / v_n: scalar int32 pow2 dequant exponents.
      page_table: ``(B, max_pages)`` int32; entry ``j`` of slot ``b`` names
        the pool page holding logical rows ``[j*ps, (j+1)*ps)``; ``-1`` =
        unmapped (only reachable past ``kv_len``, so it is never read live).
      kv_len: ``(B,)`` int32 live lengths (per-slot masking, like the dense
        kernel's vector form).

    Returns:
      ``(B, Hq, D)`` attention output in ``q.dtype``.
    """
    b, hq, d = q.shape
    n_pool, ps, hkv, _ = k_pool.shape
    g = hq // hkv
    max_pages = page_table.shape[1]
    sm_scale = 1.0 / (d ** 0.5)
    flat = (n_pool, ps, hkv * d)
    qg = q.reshape(b, hkv, g, d)
    table = jnp.asarray(page_table, jnp.int32)
    len_arr = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    scales = jnp.stack([qformat.pow2(-k_n), qformat.pow2(-v_n)])

    def _pool_idx(ib, ip, table, lens):
        # clamp past-the-last-live-page steps onto the last live page (the
        # revisit skips the DMA; the kernel guards its accumulation), then
        # translate the logical page slot to a pool page via the table.
        last = _last_live_page(lens[ib], ps)
        page = table[ib, jnp.minimum(ip, last)]
        return (jnp.maximum(page, 0), 0, 0)

    pool_spec = pl.BlockSpec((1, ps, hkv * d), _pool_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # scales
            pl.BlockSpec((1, hkv, g, d), lambda ib, ip, *_: (ib, 0, 0, 0)),
            pool_spec,
            pool_spec,
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d),
                               lambda ib, ip, *_: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_qpaged_decode_kernel, hkv=hkv, d=d, ps=ps,
                          n_pages=max_pages, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table, len_arr, scales, qg, k_pool.reshape(flat),
      v_pool.reshape(flat))
    return out.reshape(b, hq, d)


# --------------------------------------------------------------------------
# Paged chunked prefill
# --------------------------------------------------------------------------

def _qpaged_chunk_kernel(
    row_ref, start_ref, scales_ref, q_ref, kc_ref, vc_ref, k_ref, v_ref,
    o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref,
    *, c: int, g: int, hkv: int, d: int, ps: int, n_pages: int,
    sm_scale: float,
):
    ip = pl.program_id(0)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = start_ref[0]
    k_scale = scales_ref[0]
    v_scale = scales_ref[1]

    # Early termination exactly like the dense qchunk kernel: page blocks
    # entirely past the last visible row (start + c - 1) clamp onto the last
    # needed page (index maps below), revisit the resident block with no new
    # DMA, re-merge idempotently, and skip the flash accumulation.
    last = jnp.minimum((start + c - 1) // ps, n_pages - 1)
    ip_eff = jnp.minimum(ip, last)
    pos = ip_eff * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)[:, 0]
    in_chunk = (pos >= start) & (pos < start + c)
    # one-hot gather of chunk row pos-start (exact 0/1 selection)
    oh = (pos[:, None] == start + jax.lax.broadcasted_iota(
        jnp.int32, (ps, c), 1)).astype(jnp.float32)
    # causal within the chunk; the page positions are built along lanes
    # directly (turning the sublane vector ``pos`` into a row costs Mosaic
    # VMEM in proportion to C)
    qc = jax.lax.broadcasted_iota(jnp.int32, (c * g, ps), 0) // g
    visible = (ip_eff * ps + jax.lax.broadcasted_iota(
        jnp.int32, (c * g, ps), 1)) <= start + qc

    # head h of the page block is the static lane slice [h*D, (h+1)*D)
    for h in range(hkv):
        lanes = slice(h * d, (h + 1) * d)
        # -- fused quantize-on-write: merge the chunk's rows into this page
        k_rows = jnp.dot(oh, kc_ref[h], preferred_element_type=jnp.float32,
                         precision=EXACT)
        v_rows = jnp.dot(oh, vc_ref[h], preferred_element_type=jnp.float32,
                         precision=EXACT)
        k8 = jnp.where(in_chunk[:, None],
                       _quantize_i8(k_rows, scales_ref[2]), k_ref[0, :, lanes])
        v8 = jnp.where(in_chunk[:, None],
                       _quantize_i8(v_rows, scales_ref[3]), v_ref[0, :, lanes])
        ko_ref[0, :, lanes] = k8
        vo_ref[0, :, lanes] = v8

        # -- flash update over the merged page (prefix + just-written chunk)
        @pl.when(ip <= last)
        def _flash():
            kf = k8.astype(jnp.float32) * k_scale
            vf = v8.astype(jnp.float32) * v_scale
            q = q_ref[h]                                   # (C*G, D)
            s_blk = jnp.dot(q, kf.T,
                            preferred_element_type=jnp.float32) * sm_scale
            s_blk = jnp.where(visible, s_blk, NEG_INF)

            m_prev = m_ref[h]                              # (C*G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s_blk - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, vf, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ip == n_pages - 1)
    def _done():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qpaged_chunk_attn_pallas(
    q: jax.Array,          # (C, Hq, D) f32, RoPE'd chunk queries
    k_chunk: jax.Array,    # (C, Hkv, D) f32, RoPE'd chunk keys
    v_chunk: jax.Array,    # (C, Hkv, D) f32
    k_pool: jax.Array,     # (P, ps, Hkv, D) int8
    v_pool: jax.Array,
    k_n: jax.Array,        # scalar int32 dequant exponents
    v_n: jax.Array,
    page_row: jax.Array,   # (max_pages,) int32: the target slot's table row
    start: jax.Array,      # int32: first logical cache row of this chunk
    *,
    interpret: bool = False,
):
    """Chunked-prefill attention + fused quantize-on-write into pool pages.

    The paged generalization of ``qchunk_attn_pallas``: the target slot's
    page-table row arrives as scalar-prefetch metadata, every grid step maps
    one *logical* page of the slot onto its pool page, and logical rows
    ``[start, start+C)`` receive the quantized chunk in place
    (``input_output_aliases`` on the pools).

    Args:
      q / k_chunk / v_chunk: the chunk's f32 queries / keys / values.
      k_pool / v_pool: ``(num_pages, page_size, Hkv, D)`` int8 shared pools.
      k_n / v_n: scalar int32 pow2 dequant exponents.
      page_row: ``(max_pages,)`` int32 pool indices for the target slot; all
        entries covering ``[0, start+C)`` must be allocated (>= 0) — the
        serve allocator guarantees this at admission.
      start: int32 first logical row of the chunk.

    Returns:
      ``(out (C, Hq, D), k_pool', v_pool')`` — pools updated in place; pages
      not owned by the slot pass through untouched via aliasing.
    """
    c, hq, d = q.shape
    n_pool, ps, hkv, _ = k_pool.shape
    g = hq // hkv
    max_pages = page_row.shape[0]
    sm_scale = 1.0 / (d ** 0.5)

    flat = (n_pool, ps, hkv * d)
    qg = q.reshape(c, hkv, g, d).transpose(1, 0, 2, 3).reshape(hkv, c * g, d)
    kc = k_chunk.transpose(1, 0, 2)                 # (Hkv, C, D)
    vc = v_chunk.transpose(1, 0, 2)
    row = jnp.asarray(page_row, jnp.int32)
    start_arr = jnp.asarray(start, jnp.int32).reshape(1)
    scales = jnp.stack([qformat.pow2(-k_n), qformat.pow2(-v_n),
                        qformat.pow2(k_n), qformat.pow2(v_n)])

    def _pool_idx(ip, row, start):
        last = jnp.minimum((start[0] + c - 1) // ps, max_pages - 1)
        page = row[jnp.minimum(ip, last)]
        return (jnp.maximum(page, 0), 0, 0)

    pool_spec = pl.BlockSpec((1, ps, hkv * d), _pool_idx)
    whole = lambda shape: pl.BlockSpec(shape, lambda ip, *_: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(max_pages,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),    # scales
            whole((hkv, c * g, d)),
            whole((hkv, c, d)),
            whole((hkv, c, d)),
            pool_spec,
            pool_spec,
        ],
        out_specs=[
            whole((hkv, c * g, d)),
            pool_spec,
            pool_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, c * g, 1), jnp.float32),
            pltpu.VMEM((hkv, c * g, 1), jnp.float32),
            pltpu.VMEM((hkv, c * g, d), jnp.float32),
        ],
    )
    out, k_new, v_new = pl.pallas_call(
        functools.partial(_qpaged_chunk_kernel, c=c, g=g, hkv=hkv, d=d,
                          ps=ps, n_pages=max_pages, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hkv, c * g, d), q.dtype),
            jax.ShapeDtypeStruct(flat, jnp.int8),
            jax.ShapeDtypeStruct(flat, jnp.int8),
        ],
        # indices count the two scalar-prefetch operands: 6/7 are the pools.
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(row, start_arr, scales, qg, kc, vc, k_pool.reshape(flat),
      v_pool.reshape(flat))
    out = out.reshape(hkv, c, g, d).transpose(1, 0, 2, 3).reshape(c, hq, d)
    return out, k_new.reshape(k_pool.shape), v_new.reshape(v_pool.shape)
