"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` is the straightforward XLA expression of the same math; kernel
tests sweep shapes/dtypes and ``assert_allclose`` kernel-vs-oracle (exact for
the integer ops, tight rtol for the float ones).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import qformat


def qmm_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """int (M,K) @ (K,N) with int32 accumulation."""
    return jnp.matmul(x.astype(jnp.int32), w.astype(jnp.int32))


def qmm_requant_ref(x, w, shift, *, width: int = 8):
    """Integer matmul + shift-only requant, saturated to width-bit storage."""
    acc = qmm_ref(x, w)
    shift = jnp.asarray(shift, jnp.int32)
    shifted = jnp.where(
        shift >= 0,
        jnp.right_shift(acc, jnp.maximum(shift, 0)),
        jnp.left_shift(acc, jnp.maximum(-shift, 0)),
    )
    return jnp.clip(shifted, qformat.qmin(width), qformat.qmax(width)).astype(
        qformat.storage_dtype(width)
    )


def wq_matmul_ref(x, wq, scale, out_dtype=jnp.float32):
    """Float x @ dequantized int8 weights (weight-only int8 GEMM oracle)."""
    w = wq.astype(jnp.float32) * jnp.broadcast_to(
        jnp.asarray(scale, jnp.float32), (wq.shape[1],)
    )
    return jnp.matmul(x.astype(jnp.float32), w).astype(out_dtype)


def wq4_matmul_ref(x, wq, scale, *, k, width: int = 4, block_size: int = 0,
                   out_dtype=jnp.float32):
    """Packed sub-int8 weight-only GEMM oracle.

    ``wq`` is the int8 container from :func:`repro.core.qformat.pack_subint8`
    (``width``-bit lanes along K); ``scale`` is ``2^-n`` — per-channel
    (``block_size=0``, broadcastable to ``(1, N)``) or per-block
    (``(ceil(K/block_size), N)``, each row covering ``block_size`` K rows).
    """
    n_out = wq.shape[-1]
    w = qformat.unpack_subint8(wq, width, k, axis=-2).astype(jnp.float32)
    scale = jnp.asarray(scale, jnp.float32)
    if block_size:
        s = jnp.repeat(scale.reshape(-1, n_out), block_size, axis=0)[:k]
    else:
        s = jnp.broadcast_to(jnp.atleast_2d(scale), (1, n_out))
    return jnp.matmul(x.astype(jnp.float32), w * s).astype(out_dtype)


def fake_quant_ref(x, n, *, width: int = 8):
    """Quantize-dequantize on the pow2 grid 2^-n (QAT fake-quant oracle)."""
    return qformat.quantize_dequantize(x, jnp.asarray(n, jnp.int32), width).astype(x.dtype)


def qconv1d_ref(x, w, *, stride: int = 1, padding: str = "SAME"):
    """x (B,W,C) int, w (K,C,F) int -> (B,W',F) int32 via lax.conv."""
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, ("NWC", "WIO", "NWC"))
    return jax.lax.conv_general_dilated(
        x.astype(jnp.int32), w.astype(jnp.int32), (stride,), padding,
        dimension_numbers=dn, preferred_element_type=jnp.int32,
    )


def qchunk_attn_ref(q, k_chunk, v_chunk, k_cache, v_cache, k_n, v_n,
                    slot, start):
    """Chunked-prefill attention oracle: quantize the chunk's K/V onto the
    paper grid, write rows [start, start+C) of ``slot`` in the (B,S,Hkv,D)
    int8 caches, then attend each chunk query c over positions <= start+c
    (the slot's prefix plus the causally visible part of the chunk itself).

    Returns (out (C, Hq, D), k_cache', v_cache') like the Pallas kernel.
    """
    c, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    k_n = jnp.asarray(k_n, jnp.int32)
    v_n = jnp.asarray(v_n, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    k8 = qformat.quantize(k_chunk, k_n, 8)
    v8 = qformat.quantize(v_chunk, v_n, 8)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k8[None], (slot, start, jnp.int32(0), jnp.int32(0)))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v8[None], (slot, start, jnp.int32(0), jnp.int32(0)))
    kf = jax.lax.dynamic_index_in_dim(k_cache, slot, axis=0, keepdims=False)
    vf = jax.lax.dynamic_index_in_dim(v_cache, slot, axis=0, keepdims=False)
    kf = kf.astype(jnp.float32) * qformat.pow2(-k_n)
    vf = vf.astype(jnp.float32) * qformat.pow2(-v_n)
    qg = q.reshape(c, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("chgd,shd->hgcs", qg, kf) / (d ** 0.5)
    pos = jnp.arange(s)[None, None, None, :]
    visible = pos <= (start + jnp.arange(c))[None, None, :, None]
    p = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
    out = jnp.einsum("hgcs,shd->chgd", p, vf)
    return out.reshape(c, hq, d).astype(q.dtype), k_cache, v_cache


def gather_pages_ref(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Densify a paged pool: (P, ps, H, D) + (B, max_pages) -> (B, S', H, D).

    ``S' = max_pages * page_size``; unmapped (-1) table entries clamp to pool
    page 0, whose junk rows every consumer masks via the live length.
    """
    n_pages, ps, h, d = pool.shape
    pages = jnp.take(pool, jnp.maximum(page_table, 0), axis=0)
    return pages.reshape(page_table.shape[0], page_table.shape[1] * ps, h, d)


def qpaged_decode_attn_ref(q, k_pool, v_pool, k_n, v_n, page_table, kv_len):
    """Paged decode-attention oracle: gather each slot's pages into a dense
    (B, S', Hkv, D) view through the page table, then run the dense
    dequantize-everything reference.  Same signature contract as
    ``qpaged_attn.qpaged_decode_attn_pallas``.
    """
    k = gather_pages_ref(k_pool, page_table)
    v = gather_pages_ref(v_pool, page_table)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1),
                            (q.shape[0],))
    return qdecode_attn_ref(q, k, v, k_n, v_n, lens)


def qpaged_chunk_attn_ref(q, k_chunk, v_chunk, k_pool, v_pool, k_n, v_n,
                          page_row, start):
    """Paged chunked-prefill oracle: quantize the chunk onto the paper grid,
    scatter its rows into the pool pages named by the slot's ``page_row``,
    then attend each chunk query c over logical positions <= start + c.

    Returns (out (C, Hq, D), k_pool', v_pool') like the Pallas kernel.
    """
    c, hq, d = q.shape
    n_pages, ps, hkv, _ = k_pool.shape
    g = hq // hkv
    k_n = jnp.asarray(k_n, jnp.int32)
    v_n = jnp.asarray(v_n, jnp.int32)
    row = jnp.asarray(page_row, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    k8 = qformat.quantize(k_chunk, k_n, 8)
    v8 = qformat.quantize(v_chunk, v_n, 8)
    # flat scatter: logical row start+i -> pool row page*ps + (start+i) % ps;
    # unmapped (-1) or out-of-table positions redirect to an out-of-bounds
    # sentinel (dropped) — same contract as nn.attention.paged_flat_index.
    pos = start + jnp.arange(c)
    page = jnp.take(row, jnp.minimum(pos // ps, row.shape[0] - 1), axis=0)
    valid = (pos // ps < row.shape[0]) & (page >= 0)
    flat = jnp.where(valid, page * ps + pos % ps, n_pages * ps)
    k_pool = k_pool.reshape(n_pages * ps, hkv, d).at[flat].set(
        k8, mode="drop").reshape(k_pool.shape)
    v_pool = v_pool.reshape(n_pages * ps, hkv, d).at[flat].set(
        v8, mode="drop").reshape(v_pool.shape)
    kf = gather_pages_ref(k_pool, row[None])[0]          # (S', Hkv, D)
    vf = gather_pages_ref(v_pool, row[None])[0]
    kf = kf.astype(jnp.float32) * qformat.pow2(-k_n)
    vf = vf.astype(jnp.float32) * qformat.pow2(-v_n)
    s = kf.shape[0]
    qg = q.reshape(c, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("chgd,shd->hgcs", qg, kf) / (d ** 0.5)
    vis = jnp.arange(s)[None, None, None, :] \
        <= (start + jnp.arange(c))[None, None, :, None]
    p = jax.nn.softmax(jnp.where(vis, scores, -1e30), axis=-1)
    out = jnp.einsum("hgcs,shd->chgd", p, vf)
    return out.reshape(c, hq, d).astype(q.dtype), k_pool, v_pool


def qragged_attn_ref(q, k_new, v_new, k_pool, v_pool, k_n, v_n, table,
                     slot_ids, positions):
    """Ragged token-batch oracle: per-token scatter + per-token attention.

    Token ``t`` is logical row ``positions[t]`` of slot ``slot_ids[t]``: its
    K/V row is quantized onto the paper grid and scattered through the page
    table (``positions[t] < 0`` or unmapped pages redirect to the
    out-of-bounds sentinel and drop, like ``paged_flat_index``), then its
    query attends over that slot's positions ``<= positions[t]``.

    Returns (out (T, Hq, D), k_pool', v_pool') like the Pallas kernel.
    """
    t, hq, d = q.shape
    n_pages, ps, hkv, _ = k_pool.shape
    g = hq // hkv
    k_n = jnp.asarray(k_n, jnp.int32)
    v_n = jnp.asarray(v_n, jnp.int32)
    table = jnp.asarray(table, jnp.int32)
    slots = jnp.asarray(slot_ids, jnp.int32).reshape(-1)
    pos = jnp.asarray(positions, jnp.int32).reshape(-1)
    max_pages = table.shape[1]

    k8 = qformat.quantize(k_new, k_n, 8)
    v8 = qformat.quantize(v_new, v_n, 8)
    lpage = jnp.clip(pos, 0) // ps
    page = table[slots, jnp.minimum(lpage, max_pages - 1)]
    valid = (pos >= 0) & (lpage < max_pages) & (page >= 0)
    flat = jnp.where(valid, page * ps + jnp.clip(pos, 0) % ps, n_pages * ps)
    k_pool = k_pool.reshape(n_pages * ps, hkv, d).at[flat].set(
        k8, mode="drop").reshape(k_pool.shape)
    v_pool = v_pool.reshape(n_pages * ps, hkv, d).at[flat].set(
        v8, mode="drop").reshape(v_pool.shape)

    # densify each token's slot through the table, then mask to <= positions
    kf = gather_pages_ref(k_pool, table[slots])          # (T, S', Hkv, D)
    vf = gather_pages_ref(v_pool, table[slots])
    kf = kf.astype(jnp.float32) * qformat.pow2(-k_n)
    vf = vf.astype(jnp.float32) * qformat.pow2(-v_n)
    s = kf.shape[1]
    qg = q.reshape(t, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("thgd,tshd->thgs", qg, kf) / (d ** 0.5)
    rows = jnp.arange(s)[None, :]
    mapped = jnp.repeat(table[slots] >= 0, ps, axis=1)   # (T, S')
    vis = (rows <= pos[:, None]) & mapped
    p = jax.nn.softmax(jnp.where(vis[:, None, None, :], scores, -1e30),
                       axis=-1)
    # inert rows (positions < 0) see nothing: zero them instead of the
    # uniform junk a fully-masked softmax yields
    p = jnp.where(jnp.any(vis, axis=-1)[:, None, None, None], p, 0.0)
    out = jnp.einsum("thgs,tshd->thgd", p, vf)
    return out.reshape(t, hq, d).astype(q.dtype), k_pool, v_pool


def qdecode_attn_ref(q, k_cache, v_cache, k_n, v_n, kv_len):
    """Dequantize-everything flash-free reference decode attention.

    ``kv_len``: scalar or (B,) per-slot live lengths (scheduler cache).
    """
    b, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    k = k_cache.astype(jnp.float32) * qformat.pow2(-k_n)
    v = v_cache.astype(jnp.float32) * qformat.pow2(-v_n)
    qg = q.reshape(b, hkv, g, d)
    # scores: (B, Hkv, G, S)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, k) / (d ** 0.5)
    pos = jnp.arange(s)
    if jnp.ndim(kv_len) == 1:
        kv_len = kv_len[:, None, None, None]
    scores = jnp.where(pos[None, None, None, :] < kv_len, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v)
    return out.reshape(b, hq, d).astype(q.dtype)
