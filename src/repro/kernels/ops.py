"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: the Pallas path targets TPU (and is validated on CPU in
interpret mode by the kernel tests); everywhere else the pure-jnp oracle from
``ref.py`` runs — it is the same math, so the framework is backend-portable
exactly like the paper's "portable C library" claim for KerasCNN2C.

Debug override — two equivalent spellings:

* in-process: set ``repro.kernels.ops.FORCE`` to ``"pallas"`` / ``"ref"`` /
  ``"interpret"`` (what the kernel tests do);
* from the shell: export ``REPRO_KERNELS_FORCE=interpret`` before launching —
  the canonical way to debug a Pallas kernel end-to-end on a CPU box (the
  interpreter runs the exact kernel logic, DMAs and scalar prefetch
  included, just slowly).  See docs/serving.md "Debugging kernels".
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import qformat
from repro.core.qformat import PackedQTensor, QTensor

from . import ref
from .fake_quant import fake_quant_pallas
from .qchunk_attn import qchunk_attn_pallas
from .qconv1d import qconv1d_pallas
from .qdecode_attn import qdecode_attn_pallas
from .qmm import qmm_pallas, qmm_requant_pallas
from .qpaged_attn import qpaged_chunk_attn_pallas, qpaged_decode_attn_pallas
from .qragged_attn import qragged_attn_pallas
from .wq_matmul import wq4_matmul_pallas, wq_matmul_pallas

# None | "pallas" | "ref" | "interpret"; seeded from the environment so a
# plain `REPRO_KERNELS_FORCE=interpret python -m ...` flips every dispatch.
FORCE: Optional[str] = os.environ.get("REPRO_KERNELS_FORCE") or None


def _mode() -> str:
    if FORCE is not None:
        return FORCE
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def is_hardware_dispatch() -> bool:
    """True when kernels dispatch as *compiled* Pallas (TPU default, or
    ``FORCE="pallas"``) — the regime where per-page DMA size governs HBM
    efficiency.  The interpreter and the jnp oracle return False: they are
    correctness paths, not performance paths.  Callers gate
    hardware-geometry warnings (e.g. the serving page-size guard) on this;
    tests stub it by setting ``FORCE``."""
    return _mode() == "pallas"


def _2d(x):
    """Collapse leading dims to rows for GEMM wrappers."""
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def qmm(x: jax.Array, w: jax.Array) -> jax.Array:
    """Integer matmul with int32 accumulation; x (..., K), w (K, N)."""
    x2, lead = _2d(x)
    mode = _mode()
    if mode == "pallas":
        out = qmm_pallas(x2, w)
    elif mode == "interpret":
        out = qmm_pallas(x2, w, interpret=True)
    else:
        out = ref.qmm_ref(x2, w)
    return out.reshape(*lead, w.shape[-1])


def qmm_requant(x, w, shift, *, width: int = 8):
    """Integer matmul + shift-only requantization to ``width``-bit storage.

    x (..., K) int, w (K, N) int; ``shift`` >= 0 right-shifts the int32
    accumulator (the paper's pow2 rescale), < 0 left-shifts.  Returns
    (..., N) saturated to the Qm.n storage dtype.
    """
    x2, lead = _2d(x)
    mode = _mode()
    if mode == "pallas":
        out = qmm_requant_pallas(x2, w, shift, width=width)
    elif mode == "interpret":
        out = qmm_requant_pallas(x2, w, shift, width=width, interpret=True)
    else:
        out = ref.qmm_requant_ref(x2, w, shift, width=width)
    return out.reshape(*lead, w.shape[-1])


def wq_matmul(x: jax.Array, w: QTensor, *, transpose: bool = False) -> jax.Array:
    """x (..., K) float @ dequant(w) — weight-only int8 path.

    ``transpose=True`` computes x @ w.Tᵀ-style logits against an embedding
    table stored (V, D): returns x @ table.T.
    """
    if transpose:
        # Logits path: dequantize per-row exponents cannot ride the N axis of
        # the kernel (they'd be per-K); fall back to dequant + matmul.
        t = w.dequantize()
        return jnp.matmul(x, t.T.astype(x.dtype))
    x2, lead = _2d(x)
    scale = jnp.squeeze(qformat.pow2(-w.n))
    if scale.ndim > 1:  # exotic multi-axis grids: dequant outside the kernel
        y = jnp.matmul(x2.astype(jnp.float32),
                       w.q.astype(jnp.float32)
                       * qformat.pow2(-w.n)).astype(x.dtype)
        return y.reshape(*lead, w.q.shape[-1])
    mode = _mode()
    if mode == "pallas":
        out = wq_matmul_pallas(x2, w.q, scale, out_dtype=x.dtype)
    elif mode == "interpret":
        out = wq_matmul_pallas(x2, w.q, scale, out_dtype=x.dtype, interpret=True)
    else:
        out = ref.wq_matmul_ref(x2, w.q, scale, out_dtype=x.dtype)
    return out.reshape(*lead, w.q.shape[-1])


def wq4_matmul(x: jax.Array, w: PackedQTensor) -> jax.Array:
    """x (..., K) float @ dequant(w) — packed sub-int8 weight-only path.

    ``w`` stores ``w.width``-bit lanes packed into int8 bytes along K with
    per-channel or per-block (MX-style) pow2 scales.  The Pallas kernel
    covers the serving-critical 2-D int4 case (unpack-in-VMEM, scales
    applied before the dot); width-2 and exotic grids take the pure-JAX
    dequant fallback, which is also what sharded paths trace.
    """
    if w.q.ndim != 2:
        # stacked / sharded layouts: dequantize outside any kernel
        return jnp.matmul(x, w.dequantize().astype(x.dtype))
    x2, lead = _2d(x)
    k = w.k
    n_out = w.q.shape[-1]
    scale = qformat.pow2(-w.n)
    mode = _mode()
    if w.width != 4 or mode not in ("pallas", "interpret", "ref"):
        out = ref.wq4_matmul_ref(x2, w.q, scale, k=k, width=w.width,
                                 block_size=w.block_size or 0,
                                 out_dtype=x.dtype)
        return out.reshape(*lead, n_out)
    bs = w.block_size or 0
    if bs:
        scale = scale.reshape(-1, n_out)
    if mode == "pallas":
        out = wq4_matmul_pallas(x2, w.q, scale, k=k, block_size=bs,
                                out_dtype=x.dtype)
    elif mode == "interpret":
        out = wq4_matmul_pallas(x2, w.q, scale, k=k, block_size=bs,
                                out_dtype=x.dtype, interpret=True)
    else:
        out = ref.wq4_matmul_ref(x2, w.q, scale, k=k, width=4,
                                 block_size=bs, out_dtype=x.dtype)
    return out.reshape(*lead, n_out)


def fake_quant_fused(x, n, *, width: int = 8):
    """Quantize-dequantize ``x`` on the pow2 grid 2^-n (QAT fake-quant).

    One fused kernel instead of XLA's quantize + dequantize pair; shape and
    dtype preserved.
    """
    mode = _mode()
    if mode == "pallas":
        return fake_quant_pallas(x, n, width=width)
    if mode == "interpret":
        return fake_quant_pallas(x, n, width=width, interpret=True)
    return ref.fake_quant_ref(x, n, width=width)


def qconv1d(x, w, *, strides: int = 1, padding: str = "SAME"):
    """Integer 1-D convolution with int32 accumulation.

    x (B, W, C_in) int, w (K, C_in, C_out) int -> (B, W', C_out) int32 —
    the paper's MCU conv path at TPU tile sizes.
    """
    mode = _mode()
    if mode == "pallas":
        return qconv1d_pallas(x, w, stride=strides, padding=padding)
    if mode == "interpret":
        return qconv1d_pallas(x, w, stride=strides, padding=padding, interpret=True)
    return ref.qconv1d_ref(x, w, stride=strides, padding=padding)


def qdecode_attn(q, k_cache, v_cache, k_n, v_n, kv_len):
    """Decode attention over a dense int8 KV cache, dequant-in-VMEM.

    q (B, Hq, D) f32; caches (B, S, Hkv, D) int8; k_n/v_n scalar int32 pow2
    exponents; kv_len scalar or (B,) live lengths.  Returns (B, Hq, D).
    """
    mode = _mode()
    if mode == "pallas":
        return qdecode_attn_pallas(q, k_cache, v_cache, k_n, v_n, kv_len)
    if mode == "interpret":
        return qdecode_attn_pallas(q, k_cache, v_cache, k_n, v_n, kv_len, interpret=True)
    return ref.qdecode_attn_ref(q, k_cache, v_cache, k_n, v_n, kv_len)


def qpaged_decode_attn(q, k_pool, v_pool, k_n, v_n, page_table, kv_len):
    """Paged decode attention: gather int8 K/V pages through a page table.

    q (B, Hq, D) f32; pools (num_pages, page_size, Hkv, D) int8; page_table
    (B, max_pages) int32 (-1 = unmapped); kv_len (B,) live lengths.  Returns
    (B, Hq, D).  The Pallas path DMAs one pool page per grid step via a
    scalar-prefetched table lookup; the ref path densifies per slot first.
    """
    mode = _mode()
    if mode == "pallas":
        return qpaged_decode_attn_pallas(q, k_pool, v_pool, k_n, v_n,
                                         page_table, kv_len)
    if mode == "interpret":
        return qpaged_decode_attn_pallas(q, k_pool, v_pool, k_n, v_n,
                                         page_table, kv_len, interpret=True)
    return ref.qpaged_decode_attn_ref(q, k_pool, v_pool, k_n, v_n,
                                      page_table, kv_len)


def qpaged_chunk_attn(q, k_chunk, v_chunk, k_pool, v_pool, k_n, v_n,
                      page_row, start):
    """Paged chunked-prefill attention + fused int8 quantize-on-write.

    Like :func:`qchunk_attn` but against a paged pool: ``page_row``
    ((max_pages,) int32) is the target slot's page-table row, and logical
    rows [start, start+C) of the slot receive the quantized chunk inside
    their pool pages.  Returns (out (C, Hq, D), k_pool', v_pool'); the
    Pallas path aliases the pool buffers so the write is in place.
    """
    mode = _mode()
    if mode == "pallas":
        return qpaged_chunk_attn_pallas(q, k_chunk, v_chunk, k_pool, v_pool,
                                        k_n, v_n, page_row, start)
    if mode == "interpret":
        return qpaged_chunk_attn_pallas(q, k_chunk, v_chunk, k_pool, v_pool,
                                        k_n, v_n, page_row, start,
                                        interpret=True)
    return ref.qpaged_chunk_attn_ref(q, k_chunk, v_chunk, k_pool, v_pool,
                                     k_n, v_n, page_row, start)


def qragged_attn(q, k_new, v_new, k_pool, v_pool, k_n, v_n, table,
                 slot_ids, positions, *, lanes=0, chunk=0):
    """Ragged token-batch int8 quantize-on-write, then attention.

    The one-forward-per-tick serve kernel: q/k_new/v_new are (T, H*, D) flat
    token batches mixing decode tokens and prefill-chunk tokens from several
    slots; ``slot_ids``/``positions`` ((T,) int32) name each token's logical
    cache row (-1 = inert pad row); ``table`` ((slots, max_pages) int32) maps
    logical pages to pool pages — a dense cache passes the identity table
    over its block-reshaped view (see nn/attention.py).  ``lanes``/``chunk``
    (static) are the tick's geometry (``nn.attention.RaggedBatch``): the
    Pallas path attends each lane in blocks of rows; the oracle ignores
    them.  Returns (out (T, Hq, D), k_pool', v_pool'); the Pallas path
    aliases the pools so the write is in place.
    """
    mode = _mode()
    if mode in ("pallas", "interpret"):
        return qragged_attn_pallas(q, k_new, v_new, k_pool, v_pool,
                                   k_n, v_n, table, slot_ids, positions,
                                   lanes=lanes, chunk=chunk,
                                   interpret=mode == "interpret")
    return ref.qragged_attn_ref(q, k_new, v_new, k_pool, v_pool,
                                k_n, v_n, table, slot_ids, positions)


def qchunk_attn(q, k_chunk, v_chunk, k_cache, v_cache, k_n, v_n, slot, start):
    """Chunked-prefill attention + fused int8 quantize-on-write (serve path).

    Returns (out (C, Hq, D), k_cache', v_cache'): rows [start, start+C) of
    ``slot`` hold the quantized chunk; everything else passes through (the
    Pallas path aliases the cache buffers, so the write is in place).
    """
    mode = _mode()
    if mode == "pallas":
        return qchunk_attn_pallas(q, k_chunk, v_chunk, k_cache, v_cache,
                                  k_n, v_n, slot, start)
    if mode == "interpret":
        return qchunk_attn_pallas(q, k_chunk, v_chunk, k_cache, v_cache,
                                  k_n, v_n, slot, start, interpret=True)
    return ref.qchunk_attn_ref(q, k_chunk, v_chunk, k_cache, v_cache,
                               k_n, v_n, slot, start)
