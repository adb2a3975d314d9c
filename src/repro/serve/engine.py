"""Batched serving engine: prefill + decode steps with quantized options.

The serving path is where the paper's memory claims cash out at TPU scale
(DESIGN.md §2): ``weight_quant`` stores all GEMM weights as int8 QTensors
(HBM ÷4 — the 1T-param kimi-k2 fits a 512×16GiB fleet only this way) and
``quantized_kv`` stores the KV cache as int8 on the paper's Qm.n grid
(cache bytes ÷2 vs bf16; the decode-bound cell's dominant roofline term).

Steps are jit-compiled once per shape; the engine drives a fixed-slot batch
(continuous-batching-lite): finished sequences are replaced host-side while
the device tensors keep their static shapes.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.integerize import integerize_weights_only
from repro.core.policy import QuantPolicy
from repro.nn.module import Context

# The sublane tile below which a per-page DMA stops amortizing on real
# hardware: pages shorter than this make paged attention DMA-bound.
HW_MIN_PAGE_SIZE = 128
_small_page_warned = False


def _warn_small_page(page_size: int) -> None:
    """One explicit warning per process when a paged engine is built with a
    sub-sublane page size while kernels dispatch as compiled Pallas — each
    page is a separate DMA, so tiny pages run silently slow on hardware
    (interpret/ref dispatch is unaffected; tests reset the latch via
    ``engine._small_page_warned``)."""
    global _small_page_warned
    if _small_page_warned:
        return
    _small_page_warned = True
    warnings.warn(
        f"paged KV with page_size={page_size} on a hardware Pallas "
        f"backend: every page is a separate DMA and {page_size} rows is "
        f"below the {HW_MIN_PAGE_SIZE}-row sublane tile — attention will "
        f"be DMA-bound; use page_size >= {HW_MIN_PAGE_SIZE} on hardware",
        RuntimeWarning, stacklevel=3)


def _weight_quant_kwargs(spec: Union[bool, str], weight_block: int) -> dict:
    """Map an engine ``weight_quant`` spec to ``integerize_weights_only``
    kwargs.  ``True``/``"int8"`` keep the historical per-channel int8 path;
    ``"int4"``/``"int2"`` pack sub-int8 per-channel; the ``"-block"``
    suffix switches to per-block scales of ``weight_block`` K rows."""
    if spec is True or spec == "int8":
        return {}
    if isinstance(spec, str):
        base, _, tail = spec.partition("-")
        bits = {"int4": 4, "int2": 2}.get(base)
        if bits is not None and tail in ("", "block"):
            return {"bits": bits,
                    "block_size": weight_block if tail == "block" else None}
    raise ValueError(
        f"weight_quant={spec!r}: expected True, 'int8', 'int4[-block]' "
        f"or 'int2[-block]'")


def mask_vocab_tail(logits: jax.Array, vocab: int) -> jax.Array:
    """-inf the padded-vocab tail so it can never be sampled (pad is purely a
    TP-shardability artifact; see models/lm.py)."""
    v_iota = jax.lax.broadcasted_iota(jnp.int32, (logits.shape[-1],), 0)
    return jnp.where(v_iota >= vocab, -jnp.inf, logits)


def sample_tokens(logits: jax.Array, rng, vocab: int,
                  temperature: float) -> jax.Array:
    """(..., V) masked-tail greedy/categorical sample -> (..., 1) int32.

    ``vocab`` outside (0, V) means "no padded tail" (models that don't
    expose a true vocab size): sample over the full logits width.
    """
    if 0 < vocab < logits.shape[-1]:
        logits = mask_vocab_tail(logits, vocab)
    if temperature > 0.0:
        nxt = jax.random.categorical(rng, logits / temperature, axis=-1)
    else:
        nxt = jnp.argmax(logits, axis=-1)
    return nxt[..., None].astype(jnp.int32)


def make_prefill_step(model, *, mesh=None, axis_rules=None,
                      policy: Optional[QuantPolicy] = None) -> Callable:
    """(params, tokens, cache, [embeds/enc]) -> (logits, cache').

    Default: last-position logits (lockstep generate).  ``logit_pos``
    (runtime arg) instead returns (B, 1, V) at that position, slicing the
    hidden states *before* the LM head — admission prefills sample one
    token, so the head (the dominant term at small batch) runs over 1
    position, not S; a slot-targeted prefill over a padded prompt bucket
    passes its true last-token position (scheduler).
    """

    def prefill(params, tokens, cache, embeds=None, enc=None, logit_pos=None):
        ctx = Context(policy=policy or QuantPolicy.float32(), train=False,
                      mesh=mesh, axis_rules=axis_rules)
        kw: Dict[str, Any] = {}
        if enc is not None:
            kw["enc"] = enc
        if embeds is not None:
            kw["embeds"] = embeds
        logits, new_cache = model.apply(params, tokens, ctx, cache=cache,
                                        decode=True, logit_pos=logit_pos,
                                        **kw)
        if logit_pos is not None:
            return logits, new_cache          # (B, 1, V) at logit_pos
        return logits[:, -1], new_cache

    return prefill


def make_decode_step(model, *, mesh=None, axis_rules=None,
                     policy: Optional[QuantPolicy] = None,
                     temperature: float = 0.0,
                     with_health: bool = False) -> Callable:
    """(params, token (B,1), cache, rng, [enc]) -> (next (B,1), cache').

    ``with_health=True`` (the scheduler's audit mode) adds a per-row logit
    health flag and an additive ``poison`` hook: the step becomes
    ``(params, token, cache, rng, enc, poison (B,) f32) ->
    (next, healthy (B,) bool, cache')`` where ``healthy[b]`` is False iff
    row b's last-position logits hold any NaN/Inf.  ``poison`` is added to
    the logits before sampling — all-zeros is an exact no-op, a NaN entry
    is the fault harness's injection seam (serve/faults.py).
    """

    def decode(params, token, cache, rng, enc=None, poison=None):
        ctx = Context(policy=policy or QuantPolicy.float32(), train=False,
                      mesh=mesh, axis_rules=axis_rules)
        kw = {"enc": enc} if enc is not None else {}
        logits, new_cache = model.apply(params, token, ctx, cache=cache,
                                        decode=True, **kw)
        vocab = getattr(model, "vocab", logits.shape[-1])
        row = logits[:, -1]
        if poison is not None:
            row = row + poison[:, None]
        nxt = sample_tokens(row, rng, vocab, temperature)
        if with_health:
            return nxt, jnp.all(jnp.isfinite(row), axis=-1), new_cache
        return nxt, new_cache

    return decode


def make_mixed_step(model, *, mesh=None, axis_rules=None,
                    policy: Optional[QuantPolicy] = None,
                    temperature: float = 0.0,
                    with_health: bool = False,
                    merge: Optional[Callable] = None) -> Callable:
    """Chunked-prefill mixed step: one fused jitted computation that advances
    *all* live decode slots by one token AND prefills one fixed-size prompt
    chunk in place into a target slot's KV slice (nn KVChunk path — no
    batch-1 scratch cache, no ``write_kv_slot`` copy, and because the chunk
    shape is static there is exactly one compile regardless of prompt length).

    (params, tok (B,1), cache, rng, chunk_tok (1,C), slot, start, length)
      -> (next (B,1), first (1,1), cache')

    ``length`` is the chunk's valid token count (< C only on the last,
    padded chunk); ``first`` samples the logits at position length-1 and is
    only meaningful on that last chunk (the prompt's first generated token).
    The decode half runs first, so its per-slot cache append for the
    mid-prefill slot lands exactly on the row the chunk then overwrites —
    the scheduler's masking invariant (junk only at rows >= len) holds.

    ``enc`` (EncDec serving): per-slot encoder outputs ``(B, S_enc, D)``.
    The decode half cross-attends each slot to its own row; the batch-1
    chunk half slices the target slot's row — handing it the full batch
    would shape-mismatch (and silently decode against the wrong context).

    ``with_health=True`` (audit mode): the step gains a trailing ``poison``
    arg (a (B,) f32 vector added to the decode logits — see
    ``make_decode_step``) and returns
    ``(next, first, dec_healthy (B,), first_healthy (1,), cache')``.

    ``merge`` (recurrent-state models): ``merge(old, new, active) -> cache``
    runs BETWEEN the decode half and the chunk half, with the step's
    trailing ``active`` arg ((B,) bool).  KV caches tolerate the decode
    half's masked junk appends (rows >= ``len`` are dead), but a recurrence
    has no position axis — one junk step through an inactive slot corrupts
    its state, so the merge restores every inactive slot's recurrent rows
    to their pre-step values before the chunk half reads/writes the lane
    slot's row (serve/slot_state.py ``merge_inactive``).
    """
    from repro.nn.attention import KVChunk

    decode = make_decode_step(model, mesh=mesh, axis_rules=axis_rules,
                              policy=policy, temperature=temperature,
                              with_health=with_health)

    def mixed(params, tok, cache, rng, chunk_tok, slot, start, length,
              enc=None, poison=None, active=None):
        old = cache
        rng_d, rng_c = jax.random.split(rng)
        if with_health:
            nxt, dec_ok, cache = decode(params, tok, cache, rng_d, enc,
                                        poison)
        else:
            nxt, cache = decode(params, tok, cache, rng_d, enc)
        if merge is not None and active is not None:
            cache = merge(old, cache, active)
        ctx = Context(policy=policy or QuantPolicy.float32(), train=False,
                      mesh=mesh, axis_rules=axis_rules)
        kw = {}
        if enc is not None:
            kw["enc"] = jax.lax.dynamic_index_in_dim(
                enc, jnp.asarray(slot, jnp.int32), axis=0, keepdims=True)
        logits, cache = model.apply(
            params, chunk_tok, ctx, cache=cache, decode=True,
            chunk=KVChunk(slot=slot, start=start, length=length),
            logit_pos=length - 1, **kw)
        vocab = getattr(model, "vocab", logits.shape[-1])
        first = sample_tokens(logits[:, 0], rng_c, vocab, temperature)
        if with_health:
            first_ok = jnp.all(jnp.isfinite(logits[:, 0]), axis=-1)
            return nxt, first, dec_ok, first_ok, cache
        return nxt, first, cache

    return mixed


def make_ragged_step(model, *, mesh=None, axis_rules=None,
                     policy: Optional[QuantPolicy] = None,
                     temperature: float = 0.0,
                     with_health: bool = False) -> Callable:
    """One ragged forward per tick: decode tokens for *all* live slots and
    prefill-chunk tokens from up to L concurrent admission lanes flatten into
    a single (1, T) token batch, T = B + L*C, so every layer runs exactly one
    GEMM per tick no matter how many lanes are active (vs the mixed step's
    two applies and single chunk).

    (params, tok (B,1), cache, rng, chunk_tok (L,C), slot_ids (T,),
     positions (T,), logit_rows (R,), enc=None) -> (next (R,1), cache')

    Per-token addressing replaces the mixed step's scalar chunk metadata:
    token ``t`` is logical row ``positions[t]`` of slot ``slot_ids[t]``;
    rows with position -1 are inert padding (idle decode slots, lane tail
    past the prompt) — they write nothing and their outputs are junk.
    ``logit_rows`` ((R,) int32, R = B + L) picks the rows that sample: row r
    < B is decode slot r's token, row B+l is lane l's last valid chunk token
    (only meaningful on a lane's final chunk).  The LM head runs over R
    rows, not T — the same before-the-head slicing win as ``logit_pos``.

    Every shape is a function of (B, L, C) alone, so the step compiles once
    per scheduler geometry — O(1) compiles over prompt length, lane count
    in use, and arrival pattern.

    ``enc`` (EncDec serving): per-slot encoder outputs (B, S_enc, D); the
    ragged block gathers each token's own slot row (nn/transformer.py).

    ``with_health=True`` (audit mode): the step gains a trailing ``poison``
    arg ((R,) f32 added to the sampled logit rows — rows < B are decode
    slots, row B+l is lane l) and returns
    ``(next (R,1), healthy (R,) bool, cache')``.
    """
    from repro.nn.attention import RaggedBatch

    def ragged_step(params, tok, cache, rng, chunk_tok, slot_ids, positions,
                    logit_rows, enc=None, poison=None):
        ctx = Context(policy=policy or QuantPolicy.float32(), train=False,
                      mesh=mesh, axis_rules=axis_rules)
        flat = jnp.concatenate(
            [tok[:, 0], jnp.reshape(chunk_tok, (-1,))])[None, :]   # (1, T)
        rb = RaggedBatch(slots=jnp.asarray(slot_ids, jnp.int32),
                         positions=jnp.asarray(positions, jnp.int32),
                         lanes=chunk_tok.shape[0], chunk=chunk_tok.shape[1])
        kw = {"enc": enc} if enc is not None else {}
        logits, new_cache = model.apply(
            params, flat, ctx, cache=cache, decode=True, ragged=rb,
            logit_rows=jnp.asarray(logit_rows, jnp.int32), **kw)
        vocab = getattr(model, "vocab", logits.shape[-1])
        rows = logits[0]                                           # (R, V)
        if poison is not None:
            rows = rows + poison[:, None]
        nxt = sample_tokens(rows, rng, vocab, temperature)         # (R, 1)
        if with_health:
            return nxt, jnp.all(jnp.isfinite(rows), axis=-1), new_cache
        return nxt, new_cache

    return ragged_step


@dataclasses.dataclass
class ServeEngine:
    """Fixed-slot batched generation over a (possibly quantized) model.

    The engine owns the jitted steps and the cache geometry; *batch policy*
    lives elsewhere: ``generate()`` is the legacy lockstep wrapper (every slot
    starts together and runs a fixed horizon — kept as the token-identity
    baseline for tests), ``scheduler()`` hands the same steps to the
    continuous-batching ``Scheduler`` (serve/scheduler.py), which admits
    queued requests into freed slots and evicts on EOS/length per slot.
    """

    model: Any
    params: Any
    max_len: int
    batch_slots: int
    quantized_kv: bool = False
    # Weight format for serving: False = float, True / "int8" = per-channel
    # int8 QTensors, "int4" / "int2" = packed sub-int8 per-channel,
    # "int4-block" / "int2-block" = packed with per-block (MX-style) scales
    # of ``weight_block`` K rows each.
    weight_quant: Union[bool, str] = False
    weight_block: int = 32
    temperature: float = 0.0
    mesh: Any = None
    axis_rules: Any = None
    # -- paged KV cache (serving only; lockstep generate() stays dense) ------
    # paged_kv=True makes new_cache(per_slot=True) a shared page pool + per-
    # slot page tables instead of (slots, max_len) slabs; the Scheduler then
    # block-allocates pages per request (serve/paging.py).  kv_pool_pages is
    # the capacity knob: None = dense parity (slots * ceil(max_len/page_size)
    # pages); smaller pools trade worst-case headroom for more slots at the
    # same bytes — the continuous-batching capacity lever.
    # page_size=None resolves to HW_MIN_PAGE_SIZE under compiled-Pallas
    # dispatch (each page is one DMA on hardware) and to 16 elsewhere;
    # explicit small values are honored but warned about on hardware.
    paged_kv: bool = False
    page_size: Optional[int] = None
    kv_pool_pages: Optional[int] = None
    # -- EncDec cross-attention cache (serving only) -------------------------
    # True (default): per-slot caches carry projected cross-attention K/V
    # rows ("xkv" nodes), written once at admission (EncDecLM.write_cross_kv)
    # instead of re-projecting the encoder output every decode step.  False
    # drops the nodes and recomputes from ``enc`` each tick — the bench
    # baseline the cached path is gated against (benchmarks/serve_bench.py).
    cross_attn_cache: bool = True

    def __post_init__(self):
        from repro.kernels import ops as _kops

        if self.page_size is None:
            self.page_size = (HW_MIN_PAGE_SIZE
                              if self.paged_kv and _kops.is_hardware_dispatch()
                              else 16)
        elif self.paged_kv:
            if self.page_size < 1:
                raise ValueError(
                    f"page_size must be >= 1, got {self.page_size}")
            if self.page_size < HW_MIN_PAGE_SIZE and _kops.is_hardware_dispatch():
                _warn_small_page(self.page_size)
        if self.weight_quant:
            self.params = integerize_weights_only(
                self.params, **_weight_quant_kwargs(self.weight_quant,
                                                    self.weight_block))
        self._prefill = jax.jit(make_prefill_step(
            self.model, mesh=self.mesh, axis_rules=self.axis_rules))
        self._decode = jax.jit(make_decode_step(
            self.model, mesh=self.mesh, axis_rules=self.axis_rules,
            temperature=self.temperature))

    @property
    def vocab(self) -> int:
        """True vocab size for tail masking (0 = no padded tail known)."""
        return getattr(self.model, "vocab",
                       getattr(self.model, "vocab_padded", 0))

    @property
    def kv_max_pages(self) -> int:
        """Page-table width: the per-slot logical length ceiling in pages."""
        return -(-self.max_len // self.page_size)

    @property
    def kv_num_pages(self) -> int:
        """Pool pages actually allocated (kv_pool_pages or dense parity)."""
        if self.kv_pool_pages is not None:
            return self.kv_pool_pages
        return self.batch_slots * self.kv_max_pages

    def new_cache(self, *, per_slot: bool = False, batch: Optional[int] = None):
        """A fresh serving cache tree for this engine's geometry.

        ``per_slot=True`` is the scheduler's cache (per-slot ``len`` vector;
        paged when ``paged_kv``); the default is the lockstep ``generate()``
        slab.  ``batch`` overrides ``batch_slots`` (slot-targeted prefills).
        """
        dt = getattr(self.model, "dtype", jnp.float32)
        kw = {}
        if self.paged_kv and per_slot:
            kw = dict(page_size=self.page_size, num_pages=self.kv_num_pages)
        if hasattr(self.model, "encode"):
            kw["cross_attn_cache"] = self.cross_attn_cache
        return self.model.init_cache(batch or self.batch_slots, self.max_len,
                                     quantized_kv=self.quantized_kv,
                                     kv_dtype=dt, per_slot_len=per_slot, **kw)

    def cache_bytes(self) -> int:
        """Device bytes of one full serving cache (the paper's memory win:
        int8 KV halves/quarters this vs bf16/f32). Shape-only — nothing is
        allocated."""
        shapes = jax.eval_shape(self.new_cache)
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(shapes))

    def scheduler(self, **kwargs):
        """A continuous-batching Scheduler bound to this engine's steps."""
        from repro.serve.scheduler import Scheduler

        return Scheduler(self, **kwargs)

    def generate(self, prompts: jax.Array, max_new_tokens: int,
                 *, seed: int = 0, enc: Optional[jax.Array] = None,
                 ) -> jax.Array:
        """prompts: (batch_slots, S_prompt) int32 → (batch_slots, max_new)."""
        cache = self.new_cache()
        rng = jax.random.PRNGKey(seed)
        last_logits, cache = self._prefill(self.params, prompts, cache,
                                           None, enc)
        rng, sub = jax.random.split(rng)
        tok = sample_tokens(last_logits, sub, self.vocab, self.temperature)
        out = [tok]
        for _ in range(max_new_tokens - 1):
            rng, sub = jax.random.split(rng)
            tok, cache = self._decode(self.params, tok, cache, sub, enc)
            out.append(tok)
        return jnp.concatenate(out, axis=1)
