"""Pallas TPU kernel: ragged token-batch attention into an int8 KV pool.

The serve path's one-forward-per-tick kernel: a flat batch of T tokens —
decode tokens from every live slot *and* prefill-chunk tokens from several
concurrent admission lanes — attends in a single kernel launch.  Per-token
``slot_ids``/``positions`` vectors replace the mixed step's (scalar slot,
scalar start) chunk metadata: token ``t`` is logical row ``positions[t]`` of
slot ``slot_ids[t]``, its K/V row is quantized onto the paper's Qm.n grid
and written in place into the slot's pages (``input_output_aliases``), and
its query attends flash-style over positions ``<= positions[t]`` of that
slot.  Rows with ``positions[t] < 0`` are inert padding: nothing is written
and the output row is junk (callers gather only the rows they need).

One geometry serves both cache layouts: a paged pool is used as-is with its
page table, and a dense ``(B, S, Hkv, D)`` cache is *viewed* as a pool of
``B * (S // bs)`` pages with the identity table ``arange(B*steps)`` — the
caller (nn/attention.py) reshapes, so this file only ever sees
``(num_pages, page_size, Hkv, D)`` pools.

Correctness of intra-tick visibility (a chunk token attending to earlier
tokens of the *same* chunk, or a later lane row of the same slot) does not
rely on grid-step ordering: every (token, page) grid step re-merges **all**
batch rows of its slot that land in the fetched page in-register (one-hot
matmul, like the chunk kernels), so the pool writes are idempotent and the
flash mask ``pos <= positions[t]`` alone decides visibility.

Each block is one whole page over all Hkv heads, viewed as ``(page_size,
Hkv * D)`` (a free reshape of the pool); the kernel walks the heads as
static lane slices.  The TPU compiler refuses a block that takes one head
out of the second-minor axis, and a dynamic index into it; and in the
flattened view an int8 page row pads to 256 lanes instead of a 32 x 128
tile per row.  As with ``qpaged_attn``, real-TPU runs want ``page_size`` at
sublane-tile granularity; tests run in interpret mode where any size works.
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


def _qragged_kernel(
    table_ref, slots_ref, pos_ref, scales_ref, slv_ref, pvv_ref,
    q_ref, kc_ref, vc_ref, k_ref, v_ref,
    o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref,
    *, hkv: int, d: int, ps: int, n_pages: int, sm_scale: float,
):
    it, ip = pl.program_id(0), pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    my_slot = slots_ref[it]
    my_pos = pos_ref[it]
    k_scale = scales_ref[0]
    v_scale = scales_ref[1]

    # Page blocks past the token's own page clamp onto it in the index maps
    # (no new DMA); the revisit re-merges idempotently and skips the flash.
    # Inert rows (my_pos < 0) degrade to last = 0 with an all-masked flash.
    last = jnp.minimum(jnp.maximum(my_pos, 0) // ps, n_pages - 1)
    ip_eff = jnp.minimum(ip, last)
    pos = ip_eff * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)[:, 0]

    # -- fused quantize-on-write: merge *every* batch row of my slot landing
    # in this logical page (one-hot matmul over the full token batch; pad
    # rows carry position -1 and can never match a page row >= 0).
    sl = slv_ref[:, 0]                                  # (T,) slot per token
    pv = pvv_ref[:, 0]                                  # (T,) position
    oh = (pos[:, None] == pv[None, :]) & (sl[None, :] == my_slot)
    ohf = oh.astype(jnp.float32)
    written = jnp.any(oh, axis=1)

    # The page block holds every KV head, flattened into lanes (see the
    # module docstring); head h is the static lane slice [h*D, (h+1)*D).
    for h in range(hkv):
        k_rows = jnp.dot(ohf, kc_ref[h], preferred_element_type=jnp.float32,
                         precision=EXACT)
        v_rows = jnp.dot(ohf, vc_ref[h], preferred_element_type=jnp.float32,
                         precision=EXACT)
        lanes = slice(h * d, (h + 1) * d)
        k8 = jnp.where(written[:, None],
                       _quantize_i8(k_rows, scales_ref[2]), k_ref[0, :, lanes])
        v8 = jnp.where(written[:, None],
                       _quantize_i8(v_rows, scales_ref[3]), v_ref[0, :, lanes])
        ko_ref[0, :, lanes] = k8
        vo_ref[0, :, lanes] = v8

        # -- flash update over the merged page: token t sees positions
        # <= positions[t] (its own row included — standard causal
        # self-visit).  Inert rows skip the flash outright: a fully-masked
        # block would push p = exp(NEG_INF - NEG_INF) = 1 uniform junk;
        # skipping leaves l = 0 so the guarded division emits exact zeros,
        # matching the oracle.
        @pl.when((ip <= last) & (my_pos >= 0))
        def _flash():
            kf = k8.astype(jnp.float32) * k_scale
            vf = v8.astype(jnp.float32) * v_scale
            q = q_ref[0, h]                             # (G, D)
            s_blk = jnp.dot(q, kf.T,
                            preferred_element_type=jnp.float32) * sm_scale
            s_blk = jnp.where(pos[None, :] <= my_pos, s_blk, NEG_INF)

            m_prev = m_ref[h]                           # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s_blk - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, vf, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ip == n_pages - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qragged_attn_pallas(
    q: jax.Array,          # (T, Hq, D) f32, RoPE'd ragged-batch queries
    k_new: jax.Array,      # (T, Hkv, D) f32, RoPE'd ragged-batch keys
    v_new: jax.Array,      # (T, Hkv, D) f32
    k_pool: jax.Array,     # (P, ps, Hkv, D) int8
    v_pool: jax.Array,
    k_n: jax.Array,        # scalar int32 dequant exponents (paper Qm.n grid)
    v_n: jax.Array,
    table: jax.Array,      # (slots, max_pages) int32 pool indices, -1 unmapped
    slot_ids: jax.Array,   # (T,) int32 target slot per token
    positions: jax.Array,  # (T,) int32 logical cache row per token; -1 = pad
    *,
    interpret: bool = False,
):
    """Ragged-batch attention + fused quantize-on-write into pool pages.

    Token ``t``'s K/V row lands at logical row ``positions[t]`` of slot
    ``slot_ids[t]`` (quantized in place through the page table); its query
    attends over that slot's positions ``<= positions[t]``.  All pages
    covering ``[0, positions[t]]`` must be mapped for active tokens — the
    serve allocator guarantees this at admission.  Rows with
    ``positions[t] < 0`` write nothing and produce junk output rows.

    Returns ``(out (T, Hq, D), k_pool', v_pool')`` — pools updated in place;
    pages holding no batch row pass through untouched via aliasing.
    """
    t, hq, d = q.shape
    n_pool, ps, hkv, _ = k_pool.shape
    g = hq // hkv
    max_pages = table.shape[1]
    sm_scale = 1.0 / (d ** 0.5)

    flat = (n_pool, ps, hkv * d)
    qg = q.reshape(t, hkv, g, d)
    kc = k_new.transpose(1, 0, 2)                        # (Hkv, T, D)
    vc = v_new.transpose(1, 0, 2)
    table = jnp.asarray(table, jnp.int32)
    slots = jnp.asarray(slot_ids, jnp.int32).reshape(-1)
    posv = jnp.asarray(positions, jnp.int32).reshape(-1)
    scales = jnp.stack([qformat.pow2(-k_n), qformat.pow2(-v_n),
                        qformat.pow2(k_n), qformat.pow2(v_n)])

    def _pool_idx(it, ip, table, slots, pos):
        # clamp past-the-token's-page steps onto its page (the revisit skips
        # the DMA), then translate logical page -> pool page via the table;
        # unmapped (-1, only reachable for inert rows) clamps to pool page 0,
        # which the kernel reads and writes back byte-identical.
        last = jnp.minimum(jnp.maximum(pos[it], 0) // ps, max_pages - 1)
        page = table[slots[it], jnp.minimum(ip, last)]
        return (jnp.maximum(page, 0), 0, 0)

    pool_spec = pl.BlockSpec((1, ps, hkv * d), _pool_idx)
    q_spec = pl.BlockSpec((1, hkv, g, d), lambda it, ip, *_: (it, 0, 0, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda it, ip, *_: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t, max_pages),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # scales
            whole((t, 1)),                               # slot vec
            whole((t, 1)),                               # pos vec
            q_spec,
            whole((hkv, t, d)),
            whole((hkv, t, d)),
            pool_spec,
            pool_spec,
        ],
        out_specs=[q_spec, pool_spec, pool_spec],
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    out, k_out, v_out = pl.pallas_call(
        functools.partial(_qragged_kernel, hkv=hkv, d=d, ps=ps,
                          n_pages=max_pages, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((t, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct(flat, jnp.int8),
            jax.ShapeDtypeStruct(flat, jnp.int8),
        ],
        # indices count the three scalar-prefetch operands: 9/10 are pools.
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(table, slots, posv, scales, slots.reshape(t, 1), posv.reshape(t, 1),
      qg, kc, vc, k_pool.reshape(flat), v_pool.reshape(flat))
    return (out.reshape(t, hq, d), k_out.reshape(k_pool.shape),
            v_out.reshape(v_pool.shape))
