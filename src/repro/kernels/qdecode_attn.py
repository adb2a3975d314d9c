"""Pallas TPU kernel: GQA decode attention over an int8-quantized KV cache.

The paper's memory argument (footprint / 2 or / 4) applied to the serving
bottleneck: at decode, attention is a pure HBM-bandwidth problem — every step
streams the whole KV cache.  Quantizing K/V to int8 with per-(head) pow2
exponents halves the bytes vs bf16 (4x vs f32); dequantization happens in
VMEM right before the flash-style online-softmax update.

Layout: q (B, Hq, D) f32; k/v caches (B, S, Hkv, D) int8; Hq = G * Hkv.
Grid: (B, S/BS) with running per-head (m, l, acc) scratch — the classic
flash-decoding split, S innermost.  Each cache block spans all Hkv heads
(the minor two dims of a block must be whole or (8, 128)-aligned, and a
one-head slice of the head axis is neither).  The caches are viewed as
``(B, S, Hkv * D)`` — a free reshape — and the kernel walks the heads as
static lane slices; in this view an int8 row pads to a multiple of 128
lanes rather than a (32, 128) tile per row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import qformat

NEG_INF = -1e30


def _qdecode_kernel(
    scales_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, hkv: int, d: int, s_steps: int, bs: int, sm_scale: float,
):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_scale = scales_ref[0]
    v_scale = scales_ref[1]
    kv_len = len_ref[pl.program_id(0)]     # per-slot live length
    pos = pl.program_id(1) * bs + jax.lax.broadcasted_iota(
        jnp.int32, (1, bs), 1)

    # One block holds every KV head of the slot's rows, flattened into
    # lanes; head h is the static lane slice [h*D, (h+1)*D).
    for h in range(hkv):
        lanes = slice(h * d, (h + 1) * d)
        q = q_ref[0, h]                                       # (G, D) f32
        k = k_ref[0, :, lanes].astype(jnp.float32) * k_scale  # (BS, D)
        v = v_ref[0, :, lanes].astype(jnp.float32) * v_scale  # (BS, D)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(pos < kv_len, s, NEG_INF)   # past the live length

        m_prev = m_ref[h]                                     # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                # (G, BS)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(pl.program_id(1) == s_steps - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def qdecode_attn_pallas(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_n: jax.Array,
    v_n: jax.Array,
    kv_len: jax.Array,
    *,
    bs: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q (B,Hq,D) f32, caches (B,S,Hkv,D) int8, exponents scalar -> (B,Hq,D).

    ``kv_len``: scalar (one shared length) or (B,) per-slot lengths — the
    continuous-batching scheduler's case, each slot masking its own prefix.
    """
    b, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    bs_ = min(bs, s)
    assert s % bs_ == 0, (s, bs_)
    s_steps = s // bs_
    sm_scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, g, d)
    scales = jnp.stack(
        [qformat.pow2(-k_n), qformat.pow2(-v_n)]
    )
    len_arr = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    out = pl.pallas_call(
        functools.partial(_qdecode_kernel, hkv=hkv, d=d, s_steps=s_steps,
                          bs=bs_, sm_scale=sm_scale),
        grid=(b, s_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, hkv, g, d), lambda ib, isz: (ib, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bs_, hkv * d), lambda ib, isz: (ib, isz, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bs_, hkv * d), lambda ib, isz: (ib, isz, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), lambda ib, isz: (ib, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(scales, len_arr, qg, k_cache.reshape(b, s, hkv * d),
      v_cache.reshape(b, s, hkv * d))
    return out.reshape(b, hq, d)
