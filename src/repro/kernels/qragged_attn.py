"""Pallas TPU kernels: ragged token-batch attention over an int8 KV pool.

The serve path's one-forward-per-tick attention: a flat batch of T tokens —
decode tokens from every live slot *and* prefill-chunk tokens from several
concurrent admission lanes — attends in one write launch followed by one or
two attention launches.  Per-token ``slot_ids``/``positions`` vectors replace
the mixed step's (scalar slot, scalar start) chunk metadata: token ``t`` is
logical row ``positions[t]`` of slot ``slot_ids[t]``, its K/V row is
quantized onto the paper's Qm.n grid and written in place into the slot's
pages (``input_output_aliases``), and its query attends flash-style over
positions ``<= positions[t]`` of that slot.  Rows with ``positions[t] < 0``
are inert padding: nothing is written and the output row is exact zeros.

One geometry serves both cache layouts: a paged pool is used as-is with its
page table, and a dense ``(B, S, Hkv, D)`` cache is *viewed* as a pool of
``B * (S // bs)`` pages with the identity table ``arange(B*steps)`` — the
caller (nn/attention.py) reshapes, so this file only ever sees
``(num_pages, page_size, Hkv, D)`` pools.

Pallas calls, ordered by their data dependency:

1. ``qragged_attn_write`` quantizes each live row once.  It sorts
   the tokens by flat destination (``page * page_size + row``, inert rows
   last), so the rows of one page are consecutive grid steps and no page is
   visited in two runs: a page is fetched and copied to its output block on
   its first step, each step selects its own row in under a ``(page_size,
   1)`` row mask, and the page is written back once, when the run moves on.
   It needs no dynamic sublane index (which the TPU compiler refuses for
   int8) and no matmul.
2. The attention reads the updated pools and writes none back.  One kernel
   body walks a block of ``bq`` rows of one slot over that slot's pages,
   grid ``(blocks, max_pages)``.  Given the tick's geometry (``lanes`` L of
   ``chunk`` C rows after the B decode rows, each lane one slot's), it runs
   twice: ``qragged_attn_decode`` over the decode rows at ``bq = 1``, and
   ``qragged_attn_lanes`` over the lanes in blocks of ``bq`` rows
   (:func:`lane_block`), so a lane's tokens share each page's grid step and
   its DMA.  Without geometry one launch at ``bq = 1`` covers all T rows.

Visibility inside a tick (a chunk token attending to earlier tokens of the
same chunk, or a later lane row of the same slot) comes from the write
finishing before attention reads: the flash mask ``pos <= positions[t]``
alone decides what a token sees.

Each pool block is one whole page over all Hkv heads, viewed as
``(page_size, Hkv * D)`` (a free reshape of the pool); the attention kernel
walks the heads as static lane slices.  The TPU compiler refuses a block
that takes one head out of the second-minor axis, and a dynamic index into
it; and in the flattened view an int8 page row pads to 256 lanes instead of
a 32 x 128 tile per row.  As with ``qpaged_attn``, real-TPU runs want
``page_size`` at sublane-tile granularity; tests run in interpret mode
where any size works.
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


def _quantize_i8(x: jax.Array, inv_scale: jax.Array) -> jax.Array:
    """sat(trunc(x * 2^n)) on the paper grid; inv_scale = 2^n (exact pow2)."""
    xf = x * inv_scale
    xq = jnp.where(xf >= 0, jnp.floor(xf), jnp.ceil(xf))  # trunc toward zero
    return jnp.clip(xq, I8_MIN, I8_MAX).astype(jnp.int8)


def _write_kernel(order_ref, page_ref, row_ref, scales_ref, kn_ref, vn_ref,
                  k_ref, v_ref, ko_ref, vo_ref, *, ps: int):
    i = pl.program_id(0)

    # Output blocks are not fetched: a page's first step copies it in, and
    # later steps of the same run keep the block in place.
    @pl.when((i == 0) | (page_ref[i] != page_ref[jnp.maximum(i - 1, 0)]))
    def _first_visit():
        ko_ref[...] = k_ref[...]
        vo_ref[...] = v_ref[...]

    @pl.when(row_ref[i] >= 0)
    def _write():
        hit = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0) == row_ref[i]
        shape = ko_ref.shape[1:]
        k8 = _quantize_i8(jnp.broadcast_to(kn_ref[0], shape), scales_ref[0])
        v8 = _quantize_i8(jnp.broadcast_to(vn_ref[0], shape), scales_ref[1])
        ko_ref[0] = jnp.where(hit, k8, ko_ref[0])
        vo_ref[0] = jnp.where(hit, v8, vo_ref[0])


def qragged_attn_write(k_new, v_new, kf, vf, k_n, v_n, table, slots, posv, *,
                       interpret: bool = False):
    """Quantize-on-write of a ragged token batch into its pool pages.

    ``kf``/``vf`` are the int8 pools viewed flat, ``(P, ps, Hkv * D)``;
    ``table``, ``slots`` and ``posv`` are int32.  Token ``t``'s K/V row
    (``k_new``/``v_new``, ``(T, Hkv, D)`` f32) lands at logical row
    ``posv[t]`` of slot ``slots[t]`` through the page table.  Rows with
    ``posv[t] < 0``, or whose logical page is unmapped or past the table,
    write nothing, like ``ref.qragged_attn_ref``'s scatter.  Returns the
    flat pools, updated in place: only the pages that receive a row (page 0
    when none does) are fetched and written back.
    """
    t, hkv, d = k_new.shape
    n_pool, ps = kf.shape[:2]
    max_pages = table.shape[1]
    lpage = jnp.maximum(posv, 0) // ps
    page = table[slots, jnp.minimum(lpage, max_pages - 1)]
    live = (posv >= 0) & (lpage < max_pages) & (page >= 0)
    none = n_pool * ps
    dest = jnp.where(live, page * ps + jnp.maximum(posv, 0) % ps, none)
    order = jnp.argsort(dest).astype(jnp.int32)      # stable; inert rows last
    dest = dest[order]
    live = dest < none
    # Inert steps stay on the last live page, so they add no fetch and no
    # write-back; an all-inert batch sits on page 0 and writes it back as it
    # was read.
    step_page = jax.lax.cummax(jnp.where(live, dest // ps, 0))
    step_row = jnp.where(live, dest % ps, -1)
    scales = jnp.stack([qformat.pow2(k_n), qformat.pow2(v_n)])

    row_spec = pl.BlockSpec((1, 1, hkv * d),
                            lambda i, order, page, row: (order[i], 0, 0))
    pool_spec = pl.BlockSpec((1, ps, hkv * d),
                             lambda i, order, page, row: (page[i], 0, 0))
    return pl.pallas_call(
        functools.partial(_write_kernel, ps=ps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(t,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # scales
                      row_spec, row_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(kf.shape, jnp.int8)] * 2,
        # indices count the three scalar-prefetch operands: 6/7 are pools.
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="qragged_attn_write",
    )(order, step_page, step_row, scales,
      k_new.reshape(t, 1, hkv * d), v_new.reshape(t, 1, hkv * d), kf, vf)


# VMEM a lane block may take by :func:`_lane_vmem_bytes`.  The estimate
# runs high: the v5e compiler accepts blocks it puts at 22 MiB and refuses
# one at 24.6 MiB, so this keeps about half of the first refused estimate
# (tests/test_tpu_compile.py compiles the blocks the rule picks).
_VMEM_BUDGET = 12 * 2**20


def _lane_vmem_bytes(bq: int, hkv: int, g: int, d: int, ps: int) -> int:
    """Estimated VMEM of one attention block of ``bq`` tokens.

    The block holds all ``hkv * bq * g`` query rows.  Per row: the f32 q and
    out blocks (double-buffered) and the accumulator at ``d`` padded to 128
    lanes, and the running max and sum, one lane-padded f32 each.  Per row
    of one head (the loop over heads runs one at a time): the position
    column (double-buffered), the scores, mask and probabilities over a
    lane-padded page, and the dot and update temporaries.  Then the two
    int8 pages (double-buffered) and one head's f32 dequantised K and V.
    """
    lane = 128
    dp, kp = -(-d // lane) * lane, -(-ps // lane) * lane
    rows = bq * g
    return (4 * hkv * rows * (5 * dp + 2 * lane)
            + 4 * rows * (2 * lane + 3 * kp + 3 * dp)
            + 4 * ps * (-(-hkv * d // lane) * lane) + 8 * ps * dp)


def lane_block(hkv: int, g: int, d: int, ps: int, chunk: int) -> int:
    """Rows ``bq`` of one lane block: the largest power of two that divides
    the chunk and keeps the block within ``_VMEM_BUDGET``
    (:func:`_lane_vmem_bytes`).  1 means no lane blocks: every row walks the
    pages alone."""
    bq = 1
    while (chunk % (2 * bq) == 0
           and _lane_vmem_bytes(2 * bq, hkv, g, d, ps) <= _VMEM_BUDGET):
        bq *= 2
    return bq


def _attn_kernel(
    table_ref, bslot_ref, blast_ref, scales_ref, qpos_ref, q_ref, k_ref,
    v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, hkv: int, d: int, ps: int, n_pages: int, sm_scale: float,
):
    ib, ip = pl.program_id(0), pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Page steps past the block's last page clamp onto it in the index maps
    # (no new DMA) and skip the flash; an all-inert block (last page -1)
    # skips every step, so l stays 0 and its rows come out exact zeros.
    @pl.when(ip <= blast_ref[ib])
    def _flash():
        # Row r (token r // G of the block) sees key positions <= its own
        # (its own row included: standard causal self-visit); an inert row
        # (position -1) sees none.
        rows = q_ref.shape[2]
        vis = (ip * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
               <= qpos_ref[0])
        # The page block holds every KV head, flattened into lanes (see the
        # module docstring); head h is the static lane slice [h*D, (h+1)*D).
        for h in range(hkv):
            lanes = slice(h * d, (h + 1) * d)
            kf = k_ref[0, :, lanes].astype(jnp.float32) * scales_ref[0]
            vf = v_ref[0, :, lanes].astype(jnp.float32) * scales_ref[1]
            q = q_ref[0, h]                             # (bq * G, D)
            s_blk = jnp.dot(q, kf.T,
                            preferred_element_type=jnp.float32) * sm_scale
            s_blk = jnp.where(vis, s_blk, NEG_INF)

            m_prev = m_ref[h]                           # (bq * G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row with nothing visible yet has m_new = NEG_INF, where
            # exp(s - m_new) would add 1 for every masked key
            p = jnp.where(vis, jnp.exp(s_blk - m_new), 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, vf, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ip == n_pages - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _attend(q, kf, vf, scales, table, slots, posv, *, bq: int, name: str,
            interpret: bool):
    """Attention of ``q`` (N, Hq, D) in blocks of ``bq`` consecutive rows
    over the flat pools; a block's live rows belong to one slot."""
    n, hq, d = q.shape
    ps, hkv = kf.shape[1], kf.shape[2] // d
    g = hq // hkv
    nb, rows = n // bq, bq * g
    max_pages = table.shape[1]
    pos_b = posv.reshape(nb, bq)
    top = jnp.argmax(pos_b, axis=1)
    bpos = jnp.take_along_axis(pos_b, top[:, None], axis=1)[:, 0]
    bslot = jnp.take_along_axis(slots.reshape(nb, bq), top[:, None],
                                axis=1)[:, 0]
    blast = jnp.where(bpos >= 0, jnp.minimum(bpos // ps, max_pages - 1), -1)
    # (blocks, Hkv, bq * G, D): row r of a head is token r // G, group r % G
    qb = q.reshape(nb, bq, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        nb, hkv, rows, d)
    qpos = jnp.repeat(pos_b, g, axis=1).reshape(nb, rows, 1)

    def _pool_idx(ib, ip, table, bslot, blast):
        # clamp past-the-block's-last-page steps onto it (the revisit skips
        # the DMA), then translate logical page -> pool page via the table;
        # unmapped (-1, only reachable for inert blocks) clamps to pool page
        # 0, which the kernel then does not read.
        page = table[bslot[ib], jnp.minimum(ip, jnp.maximum(blast[ib], 0))]
        return (jnp.maximum(page, 0), 0, 0)

    pool_spec = pl.BlockSpec((1, ps, hkv * d), _pool_idx)
    q_spec = pl.BlockSpec((1, hkv, rows, d), lambda ib, ip, *_: (ib, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_attn_kernel, hkv=hkv, d=d, ps=ps,
                          n_pages=max_pages, sm_scale=1.0 / (d ** 0.5)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nb, max_pages),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),   # scales
                pl.BlockSpec((1, rows, 1), lambda ib, ip, *_: (ib, 0, 0)),
                q_spec,
                pool_spec,
                pool_spec,
            ],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((hkv, rows, 1), jnp.float32),
                pltpu.VMEM((hkv, rows, 1), jnp.float32),
                pltpu.VMEM((hkv, rows, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qb.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(table, bslot, blast, scales, qpos, qb, kf, vf)
    return out.reshape(nb, hkv, bq, g, d).transpose(0, 2, 1, 3, 4).reshape(
        n, hq, d)


@functools.partial(jax.jit,
                   static_argnames=("lanes", "chunk", "interpret"))
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
    lanes: int = 0,
    chunk: int = 0,
    interpret: bool = False,
):
    """Ragged-batch attention + quantize-on-write into pool pages.

    Token ``t``'s K/V row lands at logical row ``positions[t]`` of slot
    ``slot_ids[t]`` (quantized in place through the page table by
    ``qragged_attn_write``); its query then attends over that slot's
    positions ``<= positions[t]``.  All pages covering ``[0,
    positions[t]]`` must be mapped for active tokens — the serve allocator
    guarantees this at admission.  Rows with ``positions[t] < 0`` write
    nothing and produce zero output rows.

    ``lanes`` and ``chunk`` (static) give the tick's geometry T = B + L*C
    (``nn.attention.RaggedBatch``): the last L*C rows are L lanes of C rows,
    the live rows of each lane belonging to one slot.  The lanes then
    attend in blocks of :func:`lane_block` rows that share each page's grid
    step.  Without them every row walks its slot's pages alone.

    Returns ``(out (T, Hq, D), k_pool', v_pool')`` — pools updated in place;
    pages holding no batch row pass through untouched via aliasing.
    """
    t, hq, d = q.shape
    n_pool, ps, hkv, _ = k_pool.shape
    if not 0 <= lanes * chunk <= t:
        raise ValueError(f"{lanes} lanes of {chunk} rows do not fit {t} rows")
    flat = (n_pool, ps, hkv * d)
    table = jnp.asarray(table, jnp.int32)
    slots = jnp.asarray(slot_ids, jnp.int32).reshape(-1)
    posv = jnp.asarray(positions, jnp.int32).reshape(-1)

    # Every attention launch reads these same flat pools; the one reshape
    # back to (P, ps, Hkv, D) is the return.
    kf, vf = qragged_attn_write(k_new, v_new, k_pool.reshape(flat),
                                v_pool.reshape(flat), k_n, v_n, table, slots,
                                posv, interpret=interpret)
    scales = jnp.stack([qformat.pow2(-k_n), qformat.pow2(-v_n)])
    attend = functools.partial(_attend, kf=kf, vf=vf, scales=scales,
                               table=table, interpret=interpret)
    bq = lane_block(hkv, hq // hkv, d, ps, chunk) if lanes else 1
    if bq == 1:
        out = attend(q, slots=slots, posv=posv, bq=1, name="qragged_attn")
    else:
        b = t - lanes * chunk
        out = attend(q[b:], slots=slots[b:], posv=posv[b:], bq=bq,
                     name="qragged_attn_lanes")
        if b:
            out = jnp.concatenate([
                attend(q[:b], slots=slots[:b], posv=posv[:b], bq=1,
                       name="qragged_attn_decode"), out])
    return out, kf.reshape(k_pool.shape), vf.reshape(v_pool.shape)
