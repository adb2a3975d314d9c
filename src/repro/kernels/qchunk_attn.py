"""Pallas TPU kernel: chunked-prefill attention into an int8 per-slot cache.

Generalizes ``qdecode_attn`` from one query to a Q-block: one prompt chunk of
C tokens attends flash-style (online softmax) over its slot's int8 prefix,
with causal masking *within* the chunk — and the chunk's own K/V rows are
quantized to the paper's Qm.n grid and written **in place** into the slot's
cache slice inside the same kernel (``input_output_aliases``), so the fp32
chunk K/V never round-trips through HBM and no batch-1 scratch cache exists.
This is the serve path's admission kernel: every scheduler tick runs all live
decode slots *plus* one such chunk (serve/engine.make_mixed_step).

Layout: q (Hkv, C*G, D) f32 (queries grouped per KV head); chunk k/v
(Hkv, C, D) f32; caches (B, S, Hkv, D) int8.  Grid (S/BS,) with running
per-head (m, l, acc) scratch; each cache block spans all Hkv heads, viewed
as ``(BS, Hkv * D)`` lanes and walked by static lane slices (see
``qdecode_attn`` for why).  The target slot and the chunk's start row
arrive as scalar-prefetch metadata so the BlockSpecs only ever touch the
target slot's rows — other slots' cache blocks are neither read nor
written.
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


def _qchunk_kernel(
    meta_ref, scales_ref, q_ref, kc_ref, vc_ref, k_ref, v_ref,
    o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref,
    *, c: int, g: int, hkv: int, d: int, bs: int, s_steps: int,
    sm_scale: float,
):
    isz = pl.program_id(0)

    @pl.when(isz == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = meta_ref[1]
    k_scale = scales_ref[0]
    v_scale = scales_ref[1]

    # Early termination: blocks entirely past the last visible row
    # (start + c - 1) carry no chunk rows and are fully masked.  The cache
    # BlockSpecs clamp their index to ``last_block`` (see the index maps),
    # so those grid steps revisit the already-resident block — no new DMA —
    # and the merge below is idempotent; only the flash accumulation is
    # guarded.  Total work per chunk then matches one-shot causal prefill
    # instead of scanning the whole max_len cache every time.
    last_block = jnp.minimum((start + c - 1) // bs, s_steps - 1)
    isz_eff = jnp.minimum(isz, last_block)
    pos = isz_eff * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)[:, 0]
    in_chunk = (pos >= start) & (pos < start + c)
    # one-hot gather of chunk row pos-start (exact 0/1 selection)
    oh = (pos[:, None] == start + jax.lax.broadcasted_iota(
        jnp.int32, (bs, c), 1)).astype(jnp.float32)
    # causal within the chunk; block positions built along lanes directly
    # (see qpaged_attn: a sublane->lane turn of ``pos`` costs VMEM ~ C)
    qc = jax.lax.broadcasted_iota(jnp.int32, (c * g, bs), 0) // g
    visible = (isz_eff * bs + jax.lax.broadcasted_iota(
        jnp.int32, (c * g, bs), 1)) <= start + qc

    # The block spans every KV head, flattened into lanes; head h is the
    # static lane slice [h*D, (h+1)*D).
    for h in range(hkv):
        lanes = slice(h * d, (h + 1) * d)
        # -- fused quantize-on-write: merge the chunk's rows into the block
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

        # -- flash update over the merged block (prefix + just-written chunk)
        @pl.when(isz <= last_block)
        def _flash():
            kf = k8.astype(jnp.float32) * k_scale
            vf = v8.astype(jnp.float32) * v_scale
            q = q_ref[h]                               # (C*G, D)
            s_blk = jnp.dot(q, kf.T,
                            preferred_element_type=jnp.float32) * sm_scale
            s_blk = jnp.where(visible, s_blk, NEG_INF)

            m_prev = m_ref[h]                          # (C*G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s_blk - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, vf, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(isz == s_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def qchunk_attn_pallas(
    q: jax.Array,        # (C, Hq, D) f32, RoPE'd chunk queries
    k_chunk: jax.Array,  # (C, Hkv, D) f32, RoPE'd chunk keys
    v_chunk: jax.Array,  # (C, Hkv, D) f32
    k_cache: jax.Array,  # (B, S, Hkv, D) int8
    v_cache: jax.Array,
    k_n: jax.Array,      # scalar int32 dequant exponents (paper Qm.n grid)
    v_n: jax.Array,
    slot: jax.Array,     # int32: target batch slot
    start: jax.Array,    # int32: first cache row of this chunk
    *,
    bs: int = 512,
    interpret: bool = False,
):
    """Returns (out (C, Hq, D), k_cache', v_cache') — caches updated in place.

    Rows [start, start+C) of ``slot`` receive the quantized chunk; all other
    rows and slots pass through untouched via input/output aliasing.  Junk
    queries past the chunk's valid length produce junk output rows (callers
    gather only the rows they need); their K/V rows land past the slot's live
    length where the scheduler's masking invariant already ignores them.
    """
    c, hq, d = q.shape
    b, s, hkv, _ = k_cache.shape
    g = hq // hkv
    # the S grid needs bs_ | s: take the largest divisor <= bs (cache
    # max_len is operator-chosen, e.g. 560 = prompt 512 + horizon 48 — a
    # fixed 512 would not divide it).  Fail loudly rather than silently
    # degrade to tiny blocks when max_len has no usable divisor (a prime
    # 521 would otherwise run S grid steps over 1-row blocks).
    bs_ = min(bs, s)
    while s % bs_:
        bs_ -= 1
    if bs_ < min(16, s):
        raise ValueError(
            f"cache max_len {s} has no block divisor in [16, {bs}]; pick a "
            f"max_len that is a multiple of a reasonable power of two "
            f"(qchunk_attn grids the cache length into equal blocks)")
    s_steps = s // bs_
    sm_scale = 1.0 / (d ** 0.5)

    flat = (b, s, hkv * d)
    qg = q.reshape(c, hkv, g, d).transpose(1, 0, 2, 3).reshape(hkv, c * g, d)
    kc = k_chunk.transpose(1, 0, 2)                 # (Hkv, C, D)
    vc = v_chunk.transpose(1, 0, 2)
    meta = jnp.stack([jnp.asarray(slot, jnp.int32),
                      jnp.asarray(start, jnp.int32)])
    scales = jnp.stack([qformat.pow2(-k_n), qformat.pow2(-v_n),
                        qformat.pow2(k_n), qformat.pow2(v_n)])

    def _cache_idx(isz, m):
        # clamp past-the-last-visible-row steps onto the last needed block:
        # the revisit skips the DMA and the kernel guards its accumulation
        last = jnp.minimum((m[1] + c - 1) // bs_, s_steps - 1)
        return (m[0], jnp.minimum(isz, last), 0)

    cache_spec = pl.BlockSpec((1, bs_, hkv * d), _cache_idx)
    whole = lambda shape: pl.BlockSpec(shape, lambda isz, m: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_steps,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # scales
            whole((hkv, c * g, d)),
            whole((hkv, c, d)),
            whole((hkv, c, d)),
            cache_spec,
            cache_spec,
        ],
        out_specs=[
            whole((hkv, c * g, d)),
            cache_spec,
            cache_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, c * g, 1), jnp.float32),
            pltpu.VMEM((hkv, c * g, 1), jnp.float32),
            pltpu.VMEM((hkv, c * g, d), jnp.float32),
        ],
    )
    out, k_new, v_new = pl.pallas_call(
        functools.partial(_qchunk_kernel, c=c, g=g, hkv=hkv, d=d, bs=bs_,
                          s_steps=s_steps, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hkv, c * g, d), q.dtype),
            jax.ShapeDtypeStruct(flat, jnp.int8),
            jax.ShapeDtypeStruct(flat, jnp.int8),
        ],
        # indices count the scalar-prefetch operand: 5/6 are the caches.
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(meta, scales, qg, kc, vc, k_cache.reshape(flat), v_cache.reshape(flat))
    out = out.reshape(hkv, c, g, d).transpose(1, 0, 2, 3).reshape(c, hq, d)
    return out, k_new.reshape(k_cache.shape), v_new.reshape(v_cache.shape)
