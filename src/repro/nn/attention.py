"""Grouped-query attention with RoPE, blocked (flash-style) softmax, KV cache
and the paper-derived int8 KV-cache quantization.

Three entry modes:
  * train/prefill: blocked online-softmax attention (peak memory ~
    block_q x block_kv per head, so 32k-seq prefill fits per-device HBM),
  * decode: single-token step against a cache; float cache uses the same
    einsum path, int8 cache dispatches to the ``qdecode_attn`` Pallas kernel
    (dequant-in-VMEM, half the HBM bytes — DESIGN.md §2),
  * cross-attention (whisper decoder): kv from encoder output, no causal mask.

Two serving cache geometries share one dict contract (see the KV-cache
section below): dense per-slot slabs and the paged pool + page-table layout
(``init_paged_kv_cache``); update/append/attention dispatch on
``is_paged_cache``, and docs/serving.md diagrams the whole thing.

TP: head dims shard over the `model` mesh axis via sharding constraints on
the (B, S, H, D) activations (heads-per-device = H / tp).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import qformat
from repro.nn.layers import Dense
from repro.nn.module import Context, Params

# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """Inverse rotary frequencies ``1/theta^(2i/d)`` over half the head dim."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Blocked online-softmax attention (pure-JAX flash)
# --------------------------------------------------------------------------

def blocked_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool,
    q_offset: int = 0,
    block_q: int = 1024,
    block_kv: int = 1024,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Online-softmax attention; never materializes the full score matrix."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)

    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    # pad seq dims to block multiples
    pq = (-sq) % bq
    pkv = (-skv) % bkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    sq_p, skv_p = q.shape[1], k.shape[1]
    nq, nkv = sq_p // bq, skv_p // bkv

    qb = q.reshape(b, nq, bq, hkv, g, d).astype(jnp.float32) * scale
    kb = k.reshape(b, nkv, bkv, hkv, d).astype(jnp.float32)
    vb = v.reshape(b, nkv, bkv, hkv, d).astype(jnp.float32)

    valid_kv = skv if kv_len is None else kv_len

    def q_block(carry, iq):
        qi = qb[:, iq]  # (B, bq, Hkv, G, D)
        qpos = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(state, ikv):
            m, l, acc = state
            kj = kb[:, ikv]  # (B, bkv, Hkv, D)
            vj = vb[:, ikv]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj)  # (B,Hkv,G,bq,bkv)
            kpos = ikv * bkv + jnp.arange(bkv)
            mask = kpos[None, :] < valid_kv
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            else:
                mask = jnp.broadcast_to(mask, (bq, bkv))
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vj)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, bq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nkv))
        out = acc / jnp.maximum(l[..., None], 1e-30)  # (B,Hkv,G,bq,D)
        return carry, out.transpose(0, 3, 1, 2, 4)  # (B,bq,Hkv,G,D)

    _, outs = jax.lax.scan(q_block, (), jnp.arange(nq))
    # outs: (nq, B, bq, Hkv, G, D)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq_p, hq, d)
    return out[:, :sq].astype(q.dtype)


# --------------------------------------------------------------------------
# Flash attention with custom VJP (recompute-in-backward)
#
# The naive blocked fwd above, when differentiated, makes lax.scan save every
# per-block probability tensor P (B,Hkv,G,bq,bkv) — ≈8 GiB/layer at 4k seq —
# which defeats the point of never materializing the score matrix.  The
# custom VJP saves only (q, k, v, out, lse) and recomputes P blockwise in the
# backward (the FlashAttention-2 recipe), so residuals are O(B·S·H·D).
# --------------------------------------------------------------------------


def _flash_fwd_inner(q, k, v, q_offset, valid_kv, causal, block_q, block_kv):
    """Returns (out (B,Sq,Hq,D) f32, lse (B,Hkv,G,Sq) f32)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    bq, bkv = min(block_q, sq), min(block_kv, skv)
    pq, pkv = (-sq) % bq, (-skv) % bkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    nq, nkv = q.shape[1] // bq, k.shape[1] // bkv
    qb = q.reshape(b, nq, bq, hkv, g, d).astype(jnp.float32) * scale
    kb = k.reshape(b, nkv, bkv, hkv, d).astype(jnp.float32)
    vb = v.reshape(b, nkv, bkv, hkv, d).astype(jnp.float32)

    def q_block(_, iq):
        qi = qb[:, iq]
        qpos = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(state, ikv):
            m, l, acc = state
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kb[:, ikv])
            kpos = ikv * bkv + jnp.arange(bkv)
            mask = kpos[None, :] < valid_kv
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            else:
                mask = jnp.broadcast_to(mask, (bq, bkv))
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb[:, ikv])
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, bq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nkv))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return _, (out.transpose(0, 3, 1, 2, 4), lse)

    _, (outs, lses) = jax.lax.scan(q_block, None, jnp.arange(nq))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * bq, hq, d)[:, :sq]
    lse = jnp.moveaxis(lses, 0, -2).reshape(b, hkv, g, nq * bq)[..., :sq]
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_attention(q, k, v, q_offset, kv_len, causal: bool,
                    block_q: int = 512, block_kv: int = 1024):
    """Online-softmax attention, O(S) memory in fwd AND bwd.

    q (B,Sq,Hq,D); k/v (B,Skv,Hkv,D); GQA via Hq = G·Hkv.
    q_offset/kv_len: int32 scalars (decode/prefill positioning + cache mask).
    """
    out, _ = _flash_fwd_inner(q, k, v, q_offset, kv_len, causal,
                              block_q, block_kv)
    return out.astype(q.dtype)


def _flash_fwd(q, k, v, q_offset, kv_len, causal, block_q, block_kv):
    out, lse = _flash_fwd_inner(q, k, v, q_offset, kv_len, causal,
                                block_q, block_kv)
    return out.astype(q.dtype), (q, k, v, out, lse, q_offset, kv_len)


def _flash_bwd(causal, block_q, block_kv, res, gout):
    q, k, v, out, lse, q_offset, valid_kv = res
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    bq, bkv = min(block_q, sq), min(block_kv, skv)
    pq, pkv = (-sq) % bq, (-skv) % bkv
    pad_q = lambda t: jnp.pad(t, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else t
    pad_kv = lambda t: jnp.pad(t, ((0, 0), (0, pkv), (0, 0), (0, 0))) if pkv else t
    qs = pad_q(q).astype(jnp.float32) * scale
    kf = pad_kv(k).astype(jnp.float32)
    vf = pad_kv(v).astype(jnp.float32)
    go = pad_q(gout).astype(jnp.float32)
    of = pad_q(out)
    nq, nkv = qs.shape[1] // bq, kf.shape[1] // bkv
    qb = qs.reshape(b, nq, bq, hkv, g, d)
    gb = go.reshape(b, nq, bq, hkv, g, d).transpose(0, 1, 3, 4, 2, 5)
    kb = kf.reshape(b, nkv, bkv, hkv, d)
    vb = vf.reshape(b, nkv, bkv, hkv, d)
    if pq:
        lse = jnp.pad(lse, ((0, 0),) * 3 + ((0, pq),))
    lseb = lse.reshape(b, hkv, g, nq, bq)
    # D_i = rowsum(dout * out)
    Dall = jnp.sum(go * of, axis=-1)                       # (B, Sq+p, Hq)
    Db = Dall.reshape(b, nq, bq, hkv, g).transpose(0, 1, 3, 4, 2)

    def q_block(carry, iq):
        dk, dv = carry
        qi = qb[:, iq]                                     # (B,bq,Hkv,G,D)
        gi = gb[:, iq]                                     # (B,Hkv,G,bq,D)
        lsei = lseb[:, :, :, iq]                           # (B,Hkv,G,bq)
        Di = Db[:, iq]                                     # (B,Hkv,G,bq)
        qpos = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(state, ikv):
            dq_i, dk, dv = state
            kj, vj = kb[:, ikv], vb[:, ikv]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj)
            kpos = ikv * bkv + jnp.arange(bkv)
            mask = kpos[None, :] < valid_kv
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            else:
                mask = jnp.broadcast_to(mask, (bq, bkv))
            s = jnp.where(mask[None, None, None], s, -1e30)
            p = jnp.exp(s - lsei[..., None])               # recomputed P
            dv_j = jnp.einsum("bhgqk,bhgqd->bkhd", p, gi)
            dp = jnp.einsum("bhgqd,bkhd->bhgqk", gi, vj)
            ds = p * (dp - Di[..., None])                  # (B,Hkv,G,bq,bkv)
            dq_i = dq_i + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kj)
            dk_j = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qi)
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, jax.lax.dynamic_slice_in_dim(dk, ikv * bkv, bkv, 1) + dk_j,
                ikv * bkv, axis=1)
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, jax.lax.dynamic_slice_in_dim(dv, ikv * bkv, bkv, 1) + dv_j,
                ikv * bkv, axis=1)
            return (dq_i, dk, dv), None

        dq0 = jnp.zeros((b, bq, hkv, g, d), jnp.float32)
        (dq_i, dk, dv), _ = jax.lax.scan(kv_step, (dq0, dk, dv),
                                         jnp.arange(nkv))
        return (dk, dv), dq_i * scale

    dk0 = jnp.zeros((b, nkv * bkv, hkv, d), jnp.float32)
    dv0 = jnp.zeros_like(dk0)
    (dk, dv), dqs = jax.lax.scan(q_block, (dk0, dv0), jnp.arange(nq))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * bq, hq, d)[:, :sq]
    return (dq.astype(q.dtype), dk[:, :skv].astype(k.dtype),
            dv[:, :skv].astype(v.dtype), None, None)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def decode_attention(
    q: jax.Array,        # (B, 1, Hq, D)
    k: jax.Array,        # (B, Skv, Hkv, D)  float or int8
    v: jax.Array,
    kv_len: jax.Array,
    *,
    k_n=None, v_n=None,  # int8 dequant exponents (paper Qm.n grid)
    sharded: bool = False,
) -> jax.Array:
    """Single-token decode over the full cache.

    int8 caches route to the fused ``qdecode_attn`` kernel by default
    (Pallas on TPU, the jnp oracle elsewhere — kernels/ops.py dispatch):
    dequantization happens in VMEM right before the softmax update, so the
    HBM read is half/quarter the float bytes — the paper's memory win at the
    decode-bound roofline.  The einsum fallback below dequantizes the whole
    cache to f32 first; it is kept for ``sharded=True``, where the XLA
    partitioner shards the cache-length axis over `model` (KV/context
    parallelism) and combines with two tiny all-reduces — the Pallas kernel
    has no SPMD rule.  Float caches always take the einsum path.

    ``kv_len`` may be a scalar (lockstep batch) or a (B,) vector (per-slot
    continuous batching): each slot masks its own live prefix.
    """
    b, _, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if k.dtype == jnp.int8 and not sharded:
        from repro.kernels import ops as kops

        out = kops.qdecode_attn(q[:, 0].astype(jnp.float32), k, v,
                                k_n, v_n, kv_len)
        return out[:, None].astype(q.dtype)
    if k.dtype == jnp.int8:
        kf = k.astype(jnp.float32) * qformat.pow2(-k_n)
        vf = v.astype(jnp.float32) * qformat.pow2(-v_n)
    else:
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    qf = q[:, 0].reshape(b, hkv, g, d).astype(jnp.float32) / math.sqrt(d)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, kf)
    if jnp.ndim(kv_len) == 1:
        kv_len = kv_len[:, None, None, None]
    mask = jnp.arange(skv)[None, None, None, :] < kv_len
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, vf)
    return out.reshape(b, 1, hq, d)


def paged_decode_attention(q: jax.Array, cache: Dict[str, Any],
                           *, sharded: bool = False) -> jax.Array:
    """Single-token decode over a paged cache (q (B, 1, Hq, D)).

    int8 pools route to the ``qpaged_decode_attn`` kernel (Pallas on TPU,
    the gather-dense oracle elsewhere — kernels/ops.py dispatch), which DMAs
    one pool page per grid step through a scalar-prefetched page-table
    lookup.  Float pools — and sharded meshes, where the Pallas kernel has
    no SPMD rule — densify each slot's pages with a table gather and fall
    through to the dense einsum path.
    """
    table, ln = cache["page_table"], cache["len"]
    if cache["k"].dtype == jnp.int8 and not sharded:
        from repro.kernels import ops as kops

        out = kops.qpaged_decode_attn(q[:, 0].astype(jnp.float32),
                                      cache["k"], cache["v"],
                                      cache["k_n"], cache["v_n"], table, ln)
        return out[:, None].astype(q.dtype)
    b = q.shape[0]
    mp, ps = table.shape[1], cache["k"].shape[1]
    sh = (b, mp * ps) + cache["k"].shape[2:]
    kd = jnp.take(cache["k"], jnp.maximum(table, 0), axis=0).reshape(sh)
    vd = jnp.take(cache["v"], jnp.maximum(table, 0), axis=0).reshape(sh)
    return decode_attention(q, kd, vd, ln, k_n=cache.get("k_n"),
                            v_n=cache.get("v_n"), sharded=True)


# --------------------------------------------------------------------------
# KV cache (float or paper-quantized int8; dense slab or paged pool)
# --------------------------------------------------------------------------
#
# Two geometries share one dict-pytree contract (so the scheduler's cache-tree
# walks, scan stacking and jit donation treat them alike):
#
#   dense:  k/v (slots, max_len, Hkv, D); len scalar or (slots,)
#   paged:  k/v (num_pages, page_size, Hkv, D) shared pools,
#           page_table (slots, max_pages) int32 pool indices (-1 = unmapped),
#           len (slots,)
#
# A paged slot's logical row p lives in pool page table[slot, p // page_size]
# at row p % page_size.  The serve-side block allocator (serve/paging.py)
# owns which pool pages belong to which slot; everything here just reads or
# writes *through* the table.  ``is_paged_cache`` is the dispatch predicate
# used by update/append/attention below.


def init_paged_kv_cache(
    slots: int, max_pages: int, page_size: int, num_pages: int,
    n_kv_heads: int, head_dim: int,
    *, quantized: bool, dtype=jnp.bfloat16, cache_n: int = 3,
) -> Dict[str, Any]:
    """The PagedKVCache pytree: a shared K/V page pool plus per-slot tables.

    Args:
      slots: batch slots (page-table rows) — cheap, unlike dense slots.
      max_pages: table width = the per-slot logical length ceiling in pages
        (``ceil(max_len / page_size)``).
      page_size: tokens per page.
      num_pages: pool pages *shared by all slots* — the real capacity knob:
        ``num_pages * page_size`` total resident tokens, vs the dense slab's
        ``slots * max_len`` reserved ones.
      n_kv_heads / head_dim: KV geometry per page row.
      quantized: int8 pool on the paper's Qm.n grid (k_n/v_n exponents) vs
        ``dtype`` float pool.
      dtype: float pool dtype when not quantized.
      cache_n: frozen fractional-bit exponent for the int8 grid.

    Returns:
      dict with ``k``/``v`` pools ``(num_pages, page_size, Hkv, D)``,
      ``page_table`` ``(slots, max_pages)`` int32 initialized to -1
      (unmapped), ``len`` ``(slots,)`` int32, and ``k_n``/``v_n`` when
      quantized — always per-slot (continuous batching is the point).
    """
    shape = (num_pages, page_size, n_kv_heads, head_dim)
    base = {
        "page_table": jnp.full((slots, max_pages), -1, jnp.int32),
        "len": jnp.zeros((slots,), jnp.int32),
    }
    if quantized:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_n": jnp.int32(cache_n), "v_n": jnp.int32(cache_n), **base}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype), **base}


def is_paged_cache(cache: Dict[str, Any]) -> bool:
    """True when ``cache`` is a paged pool dict (has a ``page_table``)."""
    return "page_table" in cache


def gather_kv_pages(cache: Dict[str, Any], slot: jax.Array,
                    ) -> Tuple[jax.Array, jax.Array]:
    """Densify one slot's K/V: pool pages -> (max_pages*page_size, Hkv, D).

    Unmapped (-1) table entries clamp to pool page 0; the junk rows they
    produce sit past the slot's live length, which every consumer masks.
    """
    row = jax.lax.dynamic_index_in_dim(cache["page_table"],
                                       jnp.asarray(slot, jnp.int32),
                                       axis=0, keepdims=False)
    mp = row.shape[0]
    ps = cache["k"].shape[1]
    k = jnp.take(cache["k"], jnp.maximum(row, 0), axis=0)
    v = jnp.take(cache["v"], jnp.maximum(row, 0), axis=0)
    sh = (mp * ps,) + cache["k"].shape[2:]
    return k.reshape(sh), v.reshape(sh)


def paged_flat_index(row: jax.Array, pos: jax.Array, page_size: int,
                     num_pages: int) -> jax.Array:
    """Flat pool row indices for logical positions ``pos`` of one slot.

    ``row``: (max_pages,) int32 page-table row; ``pos``: (N,) int32 logical
    rows.  Position p maps to ``row[p // page_size] * page_size +
    p % page_size``; positions past the table or on unmapped (-1) entries
    map to the out-of-bounds sentinel ``num_pages * page_size``, which
    scatter-with-``mode="drop"`` discards — negative indices would *wrap*,
    so the sentinel must be positive.  The single source of truth for the
    layout (kernels/ref.py mirrors the same contract in its standalone
    oracle).
    """
    mp = row.shape[0]
    pslot = pos // page_size
    page = jnp.take(row, jnp.minimum(pslot, mp - 1))
    valid = (pslot < mp) & (page >= 0)
    return jnp.where(valid, page * page_size + pos % page_size,
                     num_pages * page_size)


def _paged_scatter_rows(pool: jax.Array, rows: jax.Array,
                        flat: jax.Array) -> jax.Array:
    """Scatter (N, Hkv, D) rows into a (P, ps, Hkv, D) pool at flat row
    indices from ``paged_flat_index``; out-of-range indices are dropped."""
    n_pool, ps = pool.shape[0], pool.shape[1]
    flat2 = pool.reshape((n_pool * ps,) + pool.shape[2:])
    return flat2.at[flat].set(rows, mode="drop").reshape(pool.shape)


def copy_kv_page(cache: Dict[str, Any], src: jax.Array, dst: jax.Array,
                 *, layer_axis: bool = False) -> Dict[str, Any]:
    """Copy pool page ``src`` onto pool page ``dst`` (K and V; COW primitive).

    The copy-on-write half of prefix sharing: when an admission would write
    into a page mapped by more than one slot (serve/scheduler.py tracks
    refcounts host-side), it allocates a private page, copies the shared
    page's rows here, and remaps its table row via :func:`set_page_row` —
    the shared original is never written.  ``layer_axis``: pools are
    ``(L, num_pages, page_size, Hkv, D)`` (scan-stacked layers); every layer
    copies the same pool page, mirroring the shared logical assignment.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    axis = 1 if layer_axis else 0

    def cp(pool):
        page = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=axis)
        return jax.lax.dynamic_update_slice_in_dim(pool, page, dst, axis=axis)

    return dict(cache, k=cp(cache["k"]), v=cp(cache["v"]))


def set_page_row(cache: Dict[str, Any], slot: jax.Array, row: jax.Array,
                 *, layer_axis: bool = False) -> Dict[str, Any]:
    """Install a slot's page-table row (the allocator's admission write).

    ``row``: (max_pages,) int32 pool indices, -1 past the allocated extent.
    ``layer_axis``: the table is (L, slots, max_pages) (scan-stacked layers)
    — every layer gets the same logical assignment.
    """
    slot = jnp.asarray(slot, jnp.int32)
    table = cache["page_table"]
    row = jnp.asarray(row, jnp.int32)
    if layer_axis:
        upd = jnp.broadcast_to(row[None, None], (table.shape[0], 1,
                                                 row.shape[0]))
        table = jax.lax.dynamic_update_slice(table, upd,
                                             (jnp.int32(0), slot, jnp.int32(0)))
    else:
        table = jax.lax.dynamic_update_slice(table, row[None],
                                             (slot, jnp.int32(0)))
    return dict(cache, page_table=table)


def set_page_entry(cache: Dict[str, Any], slot: jax.Array, idx: jax.Array,
                   page: jax.Array, *, layer_axis: bool = False,
                   ) -> Dict[str, Any]:
    """``page_table[slot, idx] = page`` — the lazy decode-growth primitive.

    Oversubscribed admission maps only the prompt-covering pages; when a
    slot's live length crosses a page boundary mid-decode the scheduler
    allocates ONE fresh pool page and appends it to the slot's row here
    (serve/scheduler.py growth loop).  All three indices are traced int32
    scalars, so one compile serves every (slot, position, page) triple.
    ``layer_axis``: the table is (L, slots, max_pages) (scan-stacked
    layers) — every layer gets the same logical assignment.
    """
    slot = jnp.asarray(slot, jnp.int32)
    idx = jnp.asarray(idx, jnp.int32)
    table = cache["page_table"]
    upd = jnp.asarray(page, jnp.int32).reshape(1, 1)
    if layer_axis:
        upd = jnp.broadcast_to(upd[None], (table.shape[0], 1, 1))
        table = jax.lax.dynamic_update_slice(table, upd,
                                             (jnp.int32(0), slot, idx))
    else:
        table = jax.lax.dynamic_update_slice(table, upd, (slot, idx))
    return dict(cache, page_table=table)


def gather_pool_pages(cache: Dict[str, Any], pages: jax.Array,
                      *, layer_axis: bool = False) -> Dict[str, Any]:
    """Read whole pool pages out of the K/V pools: the swap-out gather.

    ``pages``: (n,) int32 pool indices (traced — one compile per padded n).
    Returns ``{"k": (n, ps, Hkv, D), "v": ...}`` (a leading layer dim when
    ``layer_axis``), raw pool dtype — int8 pages round-trip bit-exactly, so
    a swap-preempted request resumes with the *identical* quantized rows it
    was evicted with (no re-quantization drift).
    """
    axis = 1 if layer_axis else 0
    pages = jnp.asarray(pages, jnp.int32)
    return {"k": jnp.take(cache["k"], pages, axis=axis),
            "v": jnp.take(cache["v"], pages, axis=axis)}


def scatter_pool_pages(cache: Dict[str, Any], pages: jax.Array,
                       data: Dict[str, Any], *, layer_axis: bool = False,
                       ) -> Dict[str, Any]:
    """Write :func:`gather_pool_pages` data back into pool pages ``pages``:
    the swap-in restore.  Duplicate page indices (the scheduler pads the
    index vector to a power of two to bound compile shapes) are harmless —
    they carry duplicate rows of the same content."""
    pages = jnp.asarray(pages, jnp.int32)
    if layer_axis:
        k = cache["k"].at[:, pages].set(data["k"].astype(cache["k"].dtype))
        v = cache["v"].at[:, pages].set(data["v"].astype(cache["v"].dtype))
    else:
        k = cache["k"].at[pages].set(data["k"].astype(cache["k"].dtype))
        v = cache["v"].at[pages].set(data["v"].astype(cache["v"].dtype))
    return dict(cache, k=k, v=v)


def init_kv_cache(
    batch: int, max_len: int, n_kv_heads: int, head_dim: int,
    *, quantized: bool, dtype=jnp.bfloat16, cache_n: int = 3,
    per_slot_len: bool = False,
) -> Dict[str, Any]:
    """cache_n: frozen fractional-bit exponent for the int8 cache grid
    (Q4.3 => range ±16, resolution 1/8 — post-norm K/V fit comfortably).

    ``per_slot_len=True`` makes ``len`` an int32 (B,) vector so every batch
    slot advances independently — the continuous-batching scheduler's cache
    (serve/scheduler.py): admissions write one slot, decode masks per slot.
    """
    shape = (batch, max_len, n_kv_heads, head_dim)
    ln = jnp.zeros((batch,), jnp.int32) if per_slot_len else jnp.int32(0)
    if quantized:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_n": jnp.int32(cache_n),
            "v_n": jnp.int32(cache_n),
            "len": ln,
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": ln,
    }


def init_cross_cache(slots: int, enc_len: int, n_kv_heads: int, head_dim: int,
                     *, dtype=jnp.float32) -> Dict[str, Any]:
    """Per-slot cross-attention K/V cache for EncDec serving.

    ``xk``/``xv`` hold each slot's encoder K/V rows — projected ONCE at
    admission (``EncDecLM.write_cross_kv``) instead of re-projected from
    ``enc`` every decode step — and ``xlen`` the live encoder length per slot
    (0 = evicted/inert; consumers mask rows past it).  Deliberately NOT the
    ``{"k", "len"}`` shape of a self-attention KV cache, so the scheduler's
    cache-tree walkers (keyed on that pair) never mistake it for one: slot
    length bookkeeping, paged growth and NaN audits all pass it by.
    """
    shape = (slots, enc_len, n_kv_heads, head_dim)
    return {"xk": jnp.zeros(shape, dtype), "xv": jnp.zeros(shape, dtype),
            "xlen": jnp.zeros((slots,), jnp.int32)}


def _insert_rows(buf: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Write (B, S_new, H, D) into (B, S, H, D) at position ``idx`` on axis 1.

    Scalar ``idx``: one shared offset (lockstep batch).  (B,) ``idx``: each
    slot writes at its own offset (per-slot continuous batching).
    """
    if jnp.ndim(idx) == 0:
        return jax.lax.dynamic_update_slice_in_dim(buf, new, idx, axis=1)
    return jax.vmap(
        lambda b, n, i: jax.lax.dynamic_update_slice(b, n, (i, 0, 0))
    )(buf, new, idx)


def update_kv_cache(cache: Dict[str, Any], k_new: jax.Array, v_new: jax.Array):
    """Insert (B, S_new, Hkv, D) at cache['len']; returns updated cache.

    With a per-slot ``len`` vector each slot writes at its own live offset
    (writes past ``max_len`` clamp to the last row — harmless: only inactive
    slots ever run off the end, and their output is masked by the scheduler).
    Paged caches take the single-token scatter path below: each slot's new
    row lands in pool page ``table[slot, len//ps]``; slots whose write
    position maps to an unmapped (-1) page — evicted slots whose ``len``
    keeps ticking under the decode mask — are *dropped*, not clamped, so
    they can never corrupt another slot's pages.
    """
    idx = cache["len"]
    if cache["k"].dtype == jnp.int8:
        k_new = qformat.quantize(k_new, cache["k_n"], 8)
        v_new = qformat.quantize(v_new, cache["v_n"], 8)
    else:
        k_new = k_new.astype(cache["k"].dtype)
        v_new = v_new.astype(cache["v"].dtype)
    if is_paged_cache(cache):
        if k_new.shape[1] != 1:
            raise NotImplementedError(
                "multi-token insert into a paged cache: admission goes "
                "through the chunked path (append_kv_chunk)")
        n_pool, ps = cache["k"].shape[0], cache["k"].shape[1]
        flat = jax.vmap(
            lambda row, ln: paged_flat_index(row, ln[None], ps, n_pool)[0]
        )(cache["page_table"], idx)                    # (B,) per-slot rows
        k = _paged_scatter_rows(cache["k"], k_new[:, 0], flat)
        v = _paged_scatter_rows(cache["v"], v_new[:, 0], flat)
        return dict(cache, k=k, v=v, len=idx + 1)
    k = _insert_rows(cache["k"], k_new, idx)
    v = _insert_rows(cache["v"], v_new, idx)
    return dict(cache, k=k, v=v, len=idx + k_new.shape[1])


def reset_kv_slot(cache: Dict[str, Any], slot: jax.Array,
                  *, layer_axis: bool = False) -> Dict[str, Any]:
    """Free one slot of a per-slot cache: len[slot] = 0.

    The stale K/V rows stay in place — every consumer masks positions
    ``>= len``, and the next admission overwrites them — so eviction is O(1),
    not O(S·H·D).  ``layer_axis``: len is (L, B) (scan-stacked layers).

    Paged caches additionally unmap the slot's page-table row (all entries
    back to -1): the pool pages themselves go back to the host-side
    allocator's free list (serve/paging.py) — the device never touches their
    contents, and decode writes to an unmapped slot are dropped.
    """
    ln = cache["len"]
    ln = ln.at[:, slot].set(0) if layer_axis else ln.at[slot].set(0)
    out = dict(cache, len=ln)
    if is_paged_cache(cache):
        table = cache["page_table"]
        if layer_axis:
            table = table.at[:, slot, :].set(-1)
        else:
            table = table.at[slot, :].set(-1)
        out["page_table"] = table
    return out


def write_kv_slot(big: Dict[str, Any], small: Dict[str, Any], slot: jax.Array,
                  length: jax.Array, *, layer_axis: bool = False,
                  ) -> Dict[str, Any]:
    """Copy a batch-1 prefilled kv dict into slot ``slot`` of a per-slot dict.

    ``small`` comes from a slot-targeted prefill over a fresh batch-1 cache;
    its rows past ``length`` may hold prompt-bucket padding junk — masked by
    setting len[slot] = length (the true prompt length), then progressively
    overwritten by decode.  ``layer_axis``: leaves carry a leading scan-layer
    dim (k (L,B,S,H,D), len (L,B)).
    """
    b_axis = 1 if layer_axis else 0
    k = jax.lax.dynamic_update_slice_in_dim(
        big["k"], small["k"].astype(big["k"].dtype), slot, axis=b_axis)
    v = jax.lax.dynamic_update_slice_in_dim(
        big["v"], small["v"].astype(big["v"].dtype), slot, axis=b_axis)
    ln = big["len"]
    if layer_axis:
        upd = jnp.full((ln.shape[0], 1), length, jnp.int32)
        ln = jax.lax.dynamic_update_slice_in_dim(ln, upd, slot, axis=1)
    else:
        ln = set_kv_slot_len(ln, slot, length)
    return dict(big, k=k, v=v, len=ln)


@dataclasses.dataclass(frozen=True)
class KVChunk:
    """Chunked-prefill target: one prompt chunk headed for rows
    [start, start+C) of batch slot ``slot`` in a per-slot cache.

    ``length`` is the number of valid (non-pad) tokens in the chunk — C for
    every chunk but the last, which may be partial.  All three are traced
    int32 scalars inside the serve engine's jitted mixed step, so one compile
    serves every slot, offset and prompt length (the whole point: no
    per-prompt-length jit buckets).
    """

    slot: Any
    start: Any
    length: Any


def set_kv_slot_len(ln: jax.Array, slot: jax.Array,
                    new_len: jax.Array) -> jax.Array:
    """len[slot] = new_len on a per-slot (B,) length vector, traced indices."""
    return jax.lax.dynamic_update_slice_in_dim(
        ln, jnp.asarray(new_len, jnp.int32).reshape(1), slot, axis=0)


@dataclasses.dataclass(frozen=True)
class RaggedBatch:
    """Per-token addressing for the one-forward-per-tick ragged step.

    The (1, T) token batch flattens every live slot's decode token plus the
    prefill-chunk tokens of several concurrent admission lanes; ``slots`` and
    ``positions`` ((T,) traced int32 vectors) name each token's batch slot
    and logical cache row.  ``positions[t] < 0`` marks an inert pad row:
    nothing is written, the length bump is a no-op, and the output row is
    junk that callers never gather (CausalLM's ``logit_rows`` selects only
    real rows).  Both vectors are traced, so one compile serves every mix of
    decode tokens and lane chunks at a fixed token budget T.

    ``lanes`` and ``chunk`` (static) give the tick's geometry T = B + L*C:
    the first B rows are the decode rows (row j is slot j), then L lanes of
    C contiguous rows, the live rows of a lane all of one slot.  The
    attention kernel attends each lane in blocks of rows that share each
    page's grid step (``kernels.qragged_attn``); a recurrent mixer needs the
    geometry to run each lane's rows in order (nn/ssm.py
    ``Mamba._apply_ragged``).
    """

    slots: Any
    positions: Any
    lanes: int = 0
    chunk: int = 0


def _ragged_flat_rows(table: jax.Array, slots: jax.Array, pos: jax.Array,
                      ps: int, n_pool: int) -> jax.Array:
    """Vectorized :func:`paged_flat_index` over a ragged token batch.

    Token ``t`` maps to pool row ``table[slots[t], pos[t]//ps] * ps +
    pos[t] % ps``; inert rows (pos < 0), positions past the table, and
    unmapped (-1) pages redirect to the positive out-of-bounds sentinel
    ``n_pool * ps`` that scatter-with-``mode="drop"`` discards.
    """
    mp = table.shape[1]
    lp = jnp.clip(pos, 0) // ps
    page = table[slots, jnp.minimum(lp, mp - 1)]
    valid = (pos >= 0) & (lp < mp) & (page >= 0)
    return jnp.where(valid, page * ps + jnp.clip(pos, 0) % ps, n_pool * ps)


def append_kv_ragged(cache: Dict[str, Any], k_new: jax.Array,
                     v_new: jax.Array, ragged: RaggedBatch) -> Dict[str, Any]:
    """Scatter a (1, T, Hkv, D) ragged token batch into a per-slot cache.

    Token ``t``'s K/V row lands at logical row ``ragged.positions[t]`` of
    slot ``ragged.slots[t]`` (int8 caches quantize-on-write onto the paper
    grid); inert rows (position < 0) are dropped.  ``len[slot]`` rises to
    ``max(len[slot], positions+1)`` over the slot's tokens — the scatter-max
    keeps pad rows (slot 0, position -1 -> max with 0) inert.  The pure-jnp
    sibling of ``kernels.qragged_attn.qragged_attn_write``.
    """
    if cache["k"].dtype == jnp.int8:
        k_new = qformat.quantize(k_new, cache["k_n"], 8)
        v_new = qformat.quantize(v_new, cache["v_n"], 8)
    else:
        k_new = k_new.astype(cache["k"].dtype)
        v_new = v_new.astype(cache["v"].dtype)
    slots = jnp.asarray(ragged.slots, jnp.int32)
    pos = jnp.asarray(ragged.positions, jnp.int32)
    if is_paged_cache(cache):
        n_pool, ps = cache["k"].shape[0], cache["k"].shape[1]
        flat = _ragged_flat_rows(cache["page_table"], slots, pos, ps, n_pool)
    else:
        b, s = cache["k"].shape[0], cache["k"].shape[1]
        flat = jnp.where((pos >= 0) & (pos < s), slots * s + jnp.clip(pos, 0),
                         b * s)
    k = _paged_scatter_rows(cache["k"], k_new[0], flat)
    v = _paged_scatter_rows(cache["v"], v_new[0], flat)
    ln = cache["len"].at[slots].max(pos + 1)
    return dict(cache, k=k, v=v, len=ln)


def ragged_attention(q: jax.Array, cache: Dict[str, Any],
                     ragged: RaggedBatch) -> jax.Array:
    """Ragged queries (1, T, Hq, D) over a per-slot cache whose rows already
    hold the batch (``append_kv_ragged``): token ``t`` attends positions
    ``<= ragged.positions[t]`` of slot ``ragged.slots[t]`` — full prefix
    plus the causally visible part of its own chunk.  Densifies each token's
    slot (a per-token gather), so it is the jnp path behind
    ``kernels.ops.qragged_attn``'s Pallas version (float caches, sharded
    runs); int8 caches dequantize on the paper's pow2 grid.  Inert rows
    (position < 0) see nothing and emit exact zeros.
    """
    b, t, hq, d = q.shape
    hkv = cache["k"].shape[2]
    g = hq // hkv
    slots = jnp.asarray(ragged.slots, jnp.int32)
    pos = jnp.asarray(ragged.positions, jnp.int32)
    if is_paged_cache(cache):
        table = cache["page_table"]
        mp, ps = table.shape[1], cache["k"].shape[1]
        rows = jnp.maximum(table[slots], 0)              # (T, max_pages)
        sh = (t, mp * ps) + cache["k"].shape[2:]
        kt = jnp.take(cache["k"], rows, axis=0).reshape(sh)
        vt = jnp.take(cache["v"], rows, axis=0).reshape(sh)
        mapped = jnp.repeat(table[slots] >= 0, ps, axis=1)
    else:
        kt = cache["k"][slots]                           # (T, S, Hkv, D)
        vt = cache["v"][slots]
        mapped = jnp.ones((t, kt.shape[1]), bool)
    if kt.dtype == jnp.int8:
        kt = kt.astype(jnp.float32) * qformat.pow2(-cache["k_n"])
        vt = vt.astype(jnp.float32) * qformat.pow2(-cache["v_n"])
    else:
        kt, vt = kt.astype(jnp.float32), vt.astype(jnp.float32)
    s = kt.shape[1]
    qg = q[0].reshape(t, hkv, g, d).astype(jnp.float32) / math.sqrt(d)
    scores = jnp.einsum("thgd,tshd->thgs", qg, kt)
    vis = (jnp.arange(s)[None, :] <= pos[:, None]) & mapped
    p = jax.nn.softmax(jnp.where(vis[:, None, None, :], scores, -1e30),
                       axis=-1)
    p = jnp.where(jnp.any(vis, axis=-1)[:, None, None, None], p, 0.0)
    out = jnp.einsum("thgs,tshd->thgd", p, vt)
    return out.reshape(1, t, hq, d).astype(q.dtype)


def append_kv_chunk(cache: Dict[str, Any], k_new: jax.Array, v_new: jax.Array,
                    chunk: KVChunk) -> Dict[str, Any]:
    """Write a (1, C, Hkv, D) prompt chunk in place into ``chunk.slot``'s
    cache rows [start, start+C) and set len[slot] = start + chunk.length.

    The pure-jnp sibling of the fused write inside ``kernels.qchunk_attn``
    (int8 caches quantize-on-write onto the paper grid; float caches cast).
    Unlike ``update_kv_cache`` this touches exactly one slot and sets its
    length *absolutely*, so decode steps that bumped the mid-prefill slot's
    length with masked junk rows are simply overwritten — the admission path
    needs no batch-1 scratch cache and no ``write_kv_slot`` copy.
    """
    if cache["k"].dtype == jnp.int8:
        k_new = qformat.quantize(k_new, cache["k_n"], 8)
        v_new = qformat.quantize(v_new, cache["v_n"], 8)
    else:
        k_new = k_new.astype(cache["k"].dtype)
        v_new = v_new.astype(cache["v"].dtype)
    slot = jnp.asarray(chunk.slot, jnp.int32)
    start = jnp.asarray(chunk.start, jnp.int32)
    if is_paged_cache(cache):
        # scatter the chunk's rows through the slot's page-table row; rows
        # landing on unmapped pages redirect to an out-of-bounds sentinel
        # (never the case for admitted slots — the allocator covers the
        # chunk-padded extent — but droppable junk beats silent corruption).
        # Prefix-sharing invariant: every page this write touches must be
        # privately mapped (refcount 1).  Refcounts live host-side, so the
        # scheduler asserts it at the dispatch site (_assert_private_write)
        # after copy-on-write has remapped any shared divergence page
        # (copy_kv_page + set_page_row).
        row = jax.lax.dynamic_index_in_dim(cache["page_table"], slot,
                                           axis=0, keepdims=False)
        n_pool, ps = cache["k"].shape[0], cache["k"].shape[1]
        flat = paged_flat_index(row, start + jnp.arange(k_new.shape[1]),
                                ps, n_pool)
        k = _paged_scatter_rows(cache["k"], k_new[0], flat)
        v = _paged_scatter_rows(cache["v"], v_new[0], flat)
    else:
        zero = jnp.int32(0)
        at = (slot, start, zero, zero)
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, at)
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, at)
    ln = set_kv_slot_len(cache["len"], slot, chunk.start + chunk.length)
    return dict(cache, k=k, v=v, len=ln)


def chunk_attention(q: jax.Array, cache: Dict[str, Any], slot: jax.Array,
                    start: jax.Array, *, block_kv: int = 128) -> jax.Array:
    """Chunk queries (1, C, Hq, D) over slot ``slot`` of a per-slot cache
    whose rows [start, start+C) already hold the chunk (``append_kv_chunk``):
    query c attends positions <= start + c — causal within the chunk, full
    prefix before it.  Reads only the target slot's rows; int8 caches
    dequantize on the paper's pow2 grid.  The jnp path behind
    ``kernels.ops.qchunk_attn``'s fused version (float caches, sharded runs).

    Blocked online softmax with a *dynamic* trip count: only KV blocks up to
    the last visible row (start + C - 1) are visited, so a chunk's attention
    work matches one-shot causal prefill (sums to P²/2 over a prompt)
    instead of rescanning the whole max_len cache every chunk.

    Paged caches densify the target slot first (``gather_kv_pages``) and run
    the same loop over the gathered view — one slot's pages, not the pool.
    """
    b, c, hq, d = q.shape
    hkv = cache["k"].shape[2]
    g = hq // hkv
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    if is_paged_cache(cache):
        kc, vc = gather_kv_pages(cache, slot)
    else:
        kc = jax.lax.dynamic_index_in_dim(cache["k"], slot, axis=0,
                                          keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(cache["v"], slot, axis=0,
                                          keepdims=False)
    s = kc.shape[0]
    quantized = kc.dtype == jnp.int8
    if quantized:
        k_scale = qformat.pow2(-cache["k_n"])
        v_scale = qformat.pow2(-cache["v_n"])
    qg = q[0].reshape(c, hkv, g, d).transpose(1, 2, 0, 3).astype(jnp.float32) \
        / math.sqrt(d)                                   # (Hkv, G, C, D)
    qc_idx = jnp.arange(c)[None, None, :, None]
    bkv = min(block_kv, s)
    n_blocks = (start + c + bkv - 1) // bkv              # dynamic trip count

    def body(state):
        i, m, l, acc = state
        # clamped offset keeps the slice in bounds; the >= i*bkv mask keeps
        # re-read rows from being double-counted on the clamped last block
        off = jnp.minimum(i * bkv, s - bkv)
        kb = jax.lax.dynamic_slice_in_dim(kc, off, bkv, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(vc, off, bkv, axis=0)
        if quantized:
            kb = kb.astype(jnp.float32) * k_scale
            vb = vb.astype(jnp.float32) * v_scale
        else:
            kb, vb = kb.astype(jnp.float32), vb.astype(jnp.float32)
        pos = (off + jnp.arange(bkv))[None, None, None, :]
        sb = jnp.einsum("hgcd,khd->hgck", qg, kb)
        visible = (pos >= i * bkv) & (pos <= start + qc_idx)
        sb = jnp.where(visible, sb, -1e30)
        m_new = jnp.maximum(m, jnp.max(sb, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sb - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("hgck,khd->hgcd", p, vb)
        return i + 1, m_new, l_new, acc_new

    m0 = jnp.full((hkv, g, c, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((hkv, g, c, 1), jnp.float32)
    a0 = jnp.zeros((hkv, g, c, d), jnp.float32)
    _, _, l, acc = jax.lax.while_loop(
        lambda st: st[0] < n_blocks, body, (jnp.int32(0), m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)                    # (Hkv, G, C, D)
    out = out.transpose(2, 0, 1, 3).reshape(1, c, hq, d)
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# The attention layer
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Attention:
    """Multi-head attention: GQA, RoPE, and every serving cache path
    (dense/paged, fp32/int8 Qm.n KV, decode/chunk/ragged) behind one module.
    """
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    use_qkv_bias: bool = False
    use_out_bias: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    causal: bool = True
    dtype: Any = jnp.float32
    name: str = "attn"

    @property
    def _q_dim(self):
        return self.n_heads * self.head_dim

    @property
    def _kv_dim(self):
        return self.n_kv_heads * self.head_dim

    def _projs(self):
        mk = lambda o, nm, bias: Dense(self.d_model, o, use_bias=bias,
                                       dtype=self.dtype, name=nm)
        return {
            "wq": mk(self._q_dim, "wq", self.use_qkv_bias),
            "wk": mk(self._kv_dim, "wk", self.use_qkv_bias),
            "wv": mk(self._kv_dim, "wv", self.use_qkv_bias),
            "wo": Dense(self._q_dim, self.d_model, use_bias=self.use_out_bias,
                        dtype=self.dtype, name="wo"),
        }

    def init(self, key) -> Params:
        """Create the q/k/v/o projection parameters."""
        ks = jax.random.split(key, 4)
        projs = self._projs()
        return {nm: layer.init(k) for (nm, layer), k in zip(projs.items(), ks)}

    def project_kv(self, params: Params, kv_in: jax.Array, ctx: Context,
                   ) -> Tuple[jax.Array, jax.Array]:
        """Project ``kv_in`` (B, S, d_model) to K/V exactly as ``apply`` would.

        The cross-attention cache writer (``EncDecLM.write_cross_kv``) runs
        this once per slot at admission; ``apply(cross_cache=...)`` then reads
        the projected rows every decode step instead of re-projecting ``enc``.
        Shares the module scope with ``apply`` so quant-stat paths line up.
        """
        ctx = ctx.scope(self.name)
        projs = self._projs()
        b, skv, _ = kv_in.shape
        k = projs["wk"].apply(params["wk"], kv_in, ctx).reshape(
            b, skv, self.n_kv_heads, self.head_dim)
        v = projs["wv"].apply(params["wv"], kv_in, ctx).reshape(
            b, skv, self.n_kv_heads, self.head_dim)
        return k, v

    def apply(
        self,
        params: Params,
        x: jax.Array,  # (B, S, d_model)
        ctx: Context,
        *,
        positions: Optional[jax.Array] = None,
        cache: Optional[Dict[str, Any]] = None,
        kv_source: Optional[jax.Array] = None,  # cross-attention
        cross_cache: Optional[Dict[str, Any]] = None,
        decode: bool = False,
        chunk: Optional[KVChunk] = None,
        ragged: Optional[RaggedBatch] = None,
    ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
        """Attend over ``x``; with ``cache`` set, run the decode / chunk /
        ragged serving path selected by the keyword arguments.

        ``cross_cache`` is the cached-cross-attention read path: a dict
        ``{"xk"/"xv": (slots, S_enc, Hkv, D), "xlen": (slots,)}`` whose rows
        were projected once at admission.  Only the query/output projections
        run — the per-step K/V re-projection of ``enc`` (and its RoPE-free
        flash over S_enc) drops out, which is the EncDec serving FLOPs win.
        """
        ctx = ctx.scope(self.name)
        projs = self._projs()
        b, s, _ = x.shape

        if cross_cache is not None:
            q = projs["wq"].apply(params["wq"], x, ctx).reshape(
                b, s, self.n_heads, self.head_dim)
            q = ctx.constrain(q, "batch", None, "heads", None)
            if chunk is not None:
                # one slot's prompt chunk: flash over that slot's cached rows
                # (flash_attention takes a scalar kv_len, so gather first)
                slot = jnp.asarray(chunk.slot, jnp.int32)
                kr = jax.lax.dynamic_index_in_dim(cross_cache["xk"], slot,
                                                  axis=0, keepdims=True)
                vr = jax.lax.dynamic_index_in_dim(cross_cache["xv"], slot,
                                                  axis=0, keepdims=True)
                xl = jax.lax.dynamic_index_in_dim(cross_cache["xlen"], slot,
                                                  axis=0, keepdims=False)
                out = flash_attention(q, kr.astype(q.dtype), vr.astype(q.dtype),
                                      jnp.int32(0), xl, False)
            else:
                # decode / tokens-as-batch: every batch row is one slot's
                # single token; per-row xlen masks each slot's live S_enc
                if s != 1:
                    raise NotImplementedError(
                        "cached cross-attention expects single-token rows "
                        "(decode / tokens-as-batch) or a chunk")
                out = decode_attention(q, cross_cache["xk"], cross_cache["xv"],
                                       cross_cache["xlen"]).astype(q.dtype)
            out = ctx.constrain(out, "batch", None, "heads", None)
            y = projs["wo"].apply(params["wo"],
                                  out.reshape(b, s, self._q_dim), ctx)
            return y, None

        q = projs["wq"].apply(params["wq"], x, ctx).reshape(b, s, self.n_heads, self.head_dim)
        kv_in = x if kv_source is None else kv_source
        skv = kv_in.shape[1]
        k = projs["wk"].apply(params["wk"], kv_in, ctx).reshape(b, skv, self.n_kv_heads, self.head_dim)
        v = projs["wv"].apply(params["wv"], kv_in, ctx).reshape(b, skv, self.n_kv_heads, self.head_dim)

        q = ctx.constrain(q, "batch", None, "heads", None)
        k = ctx.constrain(k, "batch", None, "kv_heads", None)
        v = ctx.constrain(v, "batch", None, "kv_heads", None)

        if positions is None:
            if ragged is not None:         # per-token rows; pads clamp to 0
                positions = jnp.maximum(
                    jnp.asarray(ragged.positions, jnp.int32), 0)[None, :]
            elif chunk is not None:        # chunk rows sit at start..start+C-1
                positions = chunk.start + jnp.arange(s)
            elif cache is not None and decode:
                ln = cache["len"]
                if jnp.ndim(ln) == 1:      # per-slot offsets -> (B, S)
                    positions = ln[:, None] + jnp.arange(s)[None, :]
                else:
                    positions = ln + jnp.arange(s)
            else:
                positions = jnp.arange(s)
        if self.use_rope and kv_source is None:
            q = apply_rope(q, positions, self.rope_theta)
            k = apply_rope(k, positions, self.rope_theta)

        new_cache = None
        if cache is not None and kv_source is None:
            if ragged is not None:
                # one ragged forward: every token writes its own cache row
                # and attends its own slot's prefix — decode tokens and
                # several prefill lanes in a single kernel launch.
                if jnp.ndim(cache["len"]) != 1:
                    raise NotImplementedError(
                        "the ragged step targets a per-slot cache "
                        "(init_cache(per_slot_len=True))")
                from repro.kernels import ops as kops

                slots = jnp.asarray(ragged.slots, jnp.int32)
                posv = jnp.asarray(ragged.positions, jnp.int32)
                if cache["k"].dtype == jnp.int8 and ctx.mesh is None \
                        and kops._mode() != "ref":
                    # Pallas path: quantize-on-write, then the flash over
                    # the updated pools.  One pool geometry serves both
                    # layouts: paged caches pass their pool + table as-is;
                    # a dense slab is
                    # *viewed* as a pool of (B * S/bs) pages under the
                    # identity table (a contiguous reshape, no copy).
                    if is_paged_cache(cache):
                        out, k8, v8 = kops.qragged_attn(
                            q[0].astype(jnp.float32),
                            k[0].astype(jnp.float32),
                            v[0].astype(jnp.float32), cache["k"], cache["v"],
                            cache["k_n"], cache["v_n"], cache["page_table"],
                            slots, posv, lanes=ragged.lanes,
                            chunk=ragged.chunk)
                        new_cache = dict(cache, k=k8, v=v8)
                    else:
                        bsz, smax, hkv, hd = cache["k"].shape
                        bs_ = min(512, smax)
                        while smax % bs_:
                            bs_ -= 1
                        steps = smax // bs_
                        table = jnp.arange(bsz * steps, dtype=jnp.int32
                                           ).reshape(bsz, steps)
                        out, k8, v8 = kops.qragged_attn(
                            q[0].astype(jnp.float32),
                            k[0].astype(jnp.float32),
                            v[0].astype(jnp.float32),
                            cache["k"].reshape(bsz * steps, bs_, hkv, hd),
                            cache["v"].reshape(bsz * steps, bs_, hkv, hd),
                            cache["k_n"], cache["v_n"], table, slots, posv,
                            lanes=ragged.lanes, chunk=ragged.chunk)
                        new_cache = dict(cache,
                                         k=k8.reshape(cache["k"].shape),
                                         v=v8.reshape(cache["v"].shape))
                    out = out[None].astype(q.dtype)
                    new_cache["len"] = cache["len"].at[slots].max(posv + 1)
                else:
                    new_cache = append_kv_ragged(cache, k, v, ragged)
                    out = ragged_attention(q, new_cache, ragged)
            elif chunk is not None:
                # chunked prefill: write the chunk in place into the target
                # slot's rows, then attend over prefix + visible chunk — no
                # batch-1 scratch cache, no write_kv_slot copy.
                if jnp.ndim(cache["len"]) != 1:
                    raise NotImplementedError(
                        "chunked prefill targets a per-slot cache "
                        "(init_cache(per_slot_len=True))")
                from repro.kernels import ops as kops

                if cache["k"].dtype == jnp.int8 and ctx.mesh is None \
                        and kops._mode() != "ref":
                    # fused Pallas path: quantize-on-write + flash in one
                    # kernel; fp32 chunk K/V never reaches HBM.  The "ref"
                    # backend (plain CPU) instead takes the blocked jnp path
                    # below — the *_ref oracles are full-scan correctness
                    # contracts, not serving paths.  Paged caches pass the
                    # target slot's page-table row as kernel metadata.
                    if is_paged_cache(cache):
                        row = jax.lax.dynamic_index_in_dim(
                            cache["page_table"],
                            jnp.asarray(chunk.slot, jnp.int32),
                            axis=0, keepdims=False)
                        out, k8, v8 = kops.qpaged_chunk_attn(
                            q[0].astype(jnp.float32),
                            k[0].astype(jnp.float32),
                            v[0].astype(jnp.float32), cache["k"], cache["v"],
                            cache["k_n"], cache["v_n"], row, chunk.start)
                    else:
                        out, k8, v8 = kops.qchunk_attn(
                            q[0].astype(jnp.float32),
                            k[0].astype(jnp.float32),
                            v[0].astype(jnp.float32), cache["k"], cache["v"],
                            cache["k_n"], cache["v_n"], chunk.slot,
                            chunk.start)
                    out = out[None].astype(q.dtype)
                    new_cache = dict(
                        cache, k=k8, v=v8,
                        len=set_kv_slot_len(cache["len"], chunk.slot,
                                            chunk.start + chunk.length))
                else:
                    new_cache = append_kv_chunk(cache, k, v, chunk)
                    out = chunk_attention(q, new_cache, chunk.slot,
                                          chunk.start)
            elif decode and s == 1:
                new_cache = update_kv_cache(cache, k, v)
                if is_paged_cache(cache):
                    out = paged_decode_attention(
                        q, new_cache, sharded=ctx.mesh is not None,
                    ).astype(q.dtype)
                else:
                    out = decode_attention(
                        q, new_cache["k"], new_cache["v"], new_cache["len"],
                        k_n=new_cache.get("k_n"), v_n=new_cache.get("v_n"),
                        sharded=ctx.mesh is not None,
                    ).astype(q.dtype)
            else:
                if jnp.ndim(cache["len"]) == 1:
                    raise NotImplementedError(
                        "multi-token prefill into a per-slot cache: use the "
                        "chunked path (chunk=KVChunk(...)) or admit via a "
                        "batch-1 prefill + write_kv_slot (serve/scheduler)")
                new_cache = update_kv_cache(cache, k, v)
                kf = new_cache["k"]
                vf = new_cache["v"]
                if kf.dtype == jnp.int8:
                    kf = qformat.dequantize(kf, new_cache["k_n"])
                    vf = qformat.dequantize(vf, new_cache["v_n"])
                # prefill-into-cache: causal relative to the pre-update length
                out = flash_attention(
                    q, kf.astype(q.dtype), vf.astype(q.dtype),
                    cache["len"], new_cache["len"], self.causal)
        else:
            skv_len = jnp.int32(k.shape[1])
            out = flash_attention(q, k, v, jnp.int32(0), skv_len,
                                  self.causal and kv_source is None)

        out = ctx.constrain(out, "batch", None, "heads", None)
        y = projs["wo"].apply(params["wo"], out.reshape(b, s, self._q_dim), ctx)
        return y, new_cache
