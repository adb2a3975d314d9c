"""Transformer blocks and the scanned layer Stack.

A :class:`Block` is one residual layer: norm → mixer (attention / Mamba /
RWKV6 time-mix) → residual, norm → FFN (gated / MLP / MoE / RWKV6 channel-mix)
→ residual.  ``parallel=True`` gives the command-r-style parallel block
(mixer and FFN both read the same normed input).

A :class:`Stack` is ``prelude`` (python-applied, e.g. kimi-k2's dense first
layer) + ``body`` (a period of blocks — period 1 for uniform archs, 8 for
jamba's mamba/attn interleave) scanned ``n_periods`` times with stacked
params.  Scanning keeps the HLO size O(period), not O(layers) — 61-layer
kimi-k2 compiles like a 1-layer model — and composes with ``jax.checkpoint``
for activation rematerialization (policy knob, a §Perf lever).

Quant-stat collection under scan uses Context.fork_for_scan/merge_scanned
(stats reduce with max over the layer axis, aux losses with sum).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn.attention import Attention
from repro.nn.layers import LayerNorm, RMSNorm
from repro.nn.mlp import MLP, GatedMLP
from repro.nn.moe import MoE
from repro.nn.module import Context, Params
from repro.nn.ssm import Mamba, RWKV6ChannelMix, RWKV6TimeMix

REMAT_POLICIES = {
    "none": None,
    "dots": "dots_with_no_batch_dims_saveable",
    "full": "nothing_saveable",
}


def _remat(fn, policy_name: str):
    if policy_name == "off":
        return fn
    pol = REMAT_POLICIES[policy_name]
    if pol is None:
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=getattr(jax.checkpoint_policies, pol))


@dataclasses.dataclass(frozen=True)
class Block:
    """One residual block: norm + mixer (attention/Mamba/RWKV) + norm +
    feed-forward (MLP/gated/MoE), with optional cross-attention.
    """
    d_model: int
    # mixer
    mixer: str = "attn"            # attn | mamba | rwkv
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    causal: bool = True
    mamba_d_state: int = 16
    mamba_inner_norms: bool = False
    # ffn
    ffn: str = "gated"             # gated | mlp | moe | rwkv | none
    d_ff: int = 0
    activation: str = "silu"
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    # structure
    norm: str = "rms"              # rms | ln
    parallel: bool = False         # command-r parallel attn+ffn
    cross: bool = False            # whisper decoder cross-attention
    dtype: Any = jnp.float32
    name: str = "block"

    # ---- sub-layer factories ------------------------------------------------
    def _norm(self, name):
        if self.norm == "ln":
            return LayerNorm(self.d_model, name=name)
        return RMSNorm(self.d_model, name=name)

    def _mixer(self):
        if self.mixer == "attn":
            return Attention(self.d_model, self.n_heads, self.n_kv_heads,
                             self.head_dim, use_qkv_bias=self.qkv_bias,
                             rope_theta=self.rope_theta, use_rope=self.use_rope,
                             causal=self.causal, dtype=self.dtype, name="attn")
        if self.mixer == "mamba":
            return Mamba(self.d_model, d_state=self.mamba_d_state,
                         inner_norms=self.mamba_inner_norms,
                         dtype=self.dtype, name="mamba")
        if self.mixer == "rwkv":
            return RWKV6TimeMix(self.d_model, head_dim=self.head_dim or 64,
                                dtype=self.dtype, name="timemix")
        raise ValueError(self.mixer)

    def _ffn(self):
        if self.ffn == "gated":
            return GatedMLP(self.d_model, self.d_ff, activation=self.activation,
                            dtype=self.dtype, name="ffn")
        if self.ffn == "mlp":
            return MLP(self.d_model, self.d_ff, activation=self.activation,
                       dtype=self.dtype, name="ffn")
        if self.ffn == "moe":
            return MoE(self.d_model, self.d_ff, self.n_experts, self.top_k,
                       n_shared_experts=self.n_shared_experts,
                       activation=self.activation, dtype=self.dtype, name="moe")
        if self.ffn == "rwkv":
            return RWKV6ChannelMix(self.d_model, self.d_ff, dtype=self.dtype,
                                   name="chanmix")
        if self.ffn == "none":
            return None
        raise ValueError(self.ffn)

    def _xattn(self):
        return Attention(self.d_model, self.n_heads, self.n_kv_heads,
                         self.head_dim, use_rope=False, causal=False,
                         dtype=self.dtype, name="xattn")

    # ---- params ---------------------------------------------------------------
    def init(self, key) -> Params:
        """Create the block's norm/mixer/FFN (and cross-attn) parameters."""
        ks = jax.random.split(key, 6)
        p: Params = {"norm1": self._norm("norm1").init(ks[0]),
                     "mixer": self._mixer().init(ks[1])}
        ffn = self._ffn()
        if ffn is not None:
            if not self.parallel:
                p["norm2"] = self._norm("norm2").init(ks[2])
            p["ffn"] = ffn.init(ks[3])
        if self.cross:
            p["norm_x"] = self._norm("norm_x").init(ks[4])
            p["xattn"] = self._xattn().init(ks[5])
        return p

    def init_cache(self, batch: int, max_len: int, *, quantized_kv: bool,
                   kv_dtype=jnp.bfloat16, per_slot_len: bool = False,
                   page_size: Optional[int] = None,
                   num_pages: Optional[int] = None,
                   enc_len: Optional[int] = None,
                   ) -> Dict[str, Any]:
        """Per-layer decode cache: KV slab or paged pool, or per-slot
        recurrent state (SSM/RWKV — batch rows ARE slot rows, so the same
        state dict serves lockstep and continuous batching), plus a per-slot
        cross-attention K/V cache when ``cross`` and ``enc_len`` are set.
        """
        c: Dict[str, Any] = {}
        if self.mixer == "attn":
            from repro.nn.attention import init_kv_cache, init_paged_kv_cache

            if page_size is not None:
                if not per_slot_len:
                    raise ValueError(
                        "paged KV caches are per-slot by construction: pass "
                        "per_slot_len=True alongside page_size/num_pages")
                max_pages = -(-max_len // page_size)
                c["kv"] = init_paged_kv_cache(
                    batch, max_pages, page_size,
                    num_pages if num_pages is not None else batch * max_pages,
                    self.n_kv_heads, self.head_dim, quantized=quantized_kv,
                    dtype=kv_dtype)
            else:
                c["kv"] = init_kv_cache(batch, max_len, self.n_kv_heads,
                                        self.head_dim, quantized=quantized_kv,
                                        dtype=kv_dtype,
                                        per_slot_len=per_slot_len)
        elif self.mixer == "mamba":
            c["ssm"] = Mamba(self.d_model, d_state=self.mamba_d_state,
                             dtype=self.dtype).init_state(batch)
        elif self.mixer == "rwkv":
            c["ssm"] = RWKV6TimeMix(self.d_model, head_dim=self.head_dim or 64,
                                    dtype=self.dtype).init_state(batch)
            if self.ffn == "rwkv":
                c["cm"] = {"shift": jnp.zeros((batch, 1, self.d_model),
                                              self.dtype)}
        else:
            raise ValueError(self.mixer)
        if self.cross and per_slot_len and enc_len is not None:
            from repro.nn.attention import init_cross_cache

            c["xkv"] = init_cross_cache(batch, enc_len, self.n_kv_heads,
                                        self.head_dim, dtype=self.dtype)
        return c

    # ---- forward ---------------------------------------------------------------
    def apply(self, params: Params, x, ctx: Context, *,
              cache: Optional[Dict[str, Any]] = None,
              enc: Optional[jax.Array] = None,
              positions: Optional[jax.Array] = None,
              decode: bool = False,
              chunk=None,
              ragged=None) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
        """Run the block; serving paths thread ``cache``/``chunk``/``ragged``
        through the mixer and gather per-token encoder rows for cross-attn.
        """
        ctx = ctx.scope(self.name)
        new_cache: Dict[str, Any] = {}
        h = self._norm("norm1").apply(params["norm1"], x, ctx)

        if self.mixer == "attn":
            mix_out, kv = self._mixer().apply(
                params["mixer"], h, ctx, positions=positions,
                cache=None if cache is None else cache["kv"], decode=decode,
                chunk=chunk, ragged=ragged)
            if kv is not None:
                new_cache["kv"] = kv
        else:
            kw = {"chunk": chunk}
            if ragged is not None:
                if self.mixer != "mamba":
                    raise NotImplementedError(
                        f"the ragged step has no path for the {self.mixer!r} "
                        f"mixer — serve it through the chunked mixed step")
                kw["ragged"] = ragged
            mix_out, st = self._mixer().apply(
                params["mixer"], h, ctx,
                state=None if cache is None else cache["ssm"], **kw)
            if st is not None:
                new_cache["ssm"] = st

        ffn = self._ffn()
        if self.parallel and ffn is not None:
            # command-r: y = x + attn(norm(x)) + ffn(norm(x))
            x = x + mix_out + ffn.apply(params["ffn"], h, ctx)
            return x, (new_cache or None)

        x = x + mix_out
        if self.cross:
            hx = self._norm("norm_x").apply(params["norm_x"], x, ctx)
            xkv = None if cache is None else cache.get("xkv")
            if xkv is not None:
                # cached cross-attention: read the per-slot projected rows;
                # the cache itself is written at admission
                # (EncDecLM.write_cross_kv) and passes through untouched —
                # structure preservation under jit donation.
                if ragged is not None:
                    slots = jnp.clip(jnp.asarray(ragged.slots, jnp.int32),
                                     0, None)
                    sub = {"xk": jnp.take(xkv["xk"], slots, axis=0),
                           "xv": jnp.take(xkv["xv"], slots, axis=0),
                           "xlen": jnp.take(xkv["xlen"], slots, axis=0)}
                    hx_t = jnp.swapaxes(hx, 0, 1)           # (T, 1, d)
                    xo, _ = self._xattn().apply(params["xattn"], hx_t, ctx,
                                                cross_cache=sub)
                    xo = jnp.swapaxes(xo, 0, 1)             # (1, T, d)
                else:
                    xo, _ = self._xattn().apply(params["xattn"], hx, ctx,
                                                cross_cache=xkv, chunk=chunk)
                new_cache["xkv"] = xkv
            elif ragged is not None:
                # Ragged tick: hx is one (1, T, d) token batch mixing tokens
                # from several decode slots, but cross-attention must pair
                # each token with *its own* slot's encoder output.  Gather
                # enc rows per token and run tokens-as-batch (T, 1, d) so
                # every row cross-attends only its own context (pads clamp
                # to slot 0 — their output rows are never sampled).
                slots = jnp.clip(jnp.asarray(ragged.slots, jnp.int32), 0, None)
                enc_g = jnp.take(enc, slots, axis=0)        # (T, S_enc, d)
                hx_t = jnp.swapaxes(hx, 0, 1)               # (T, 1, d)
                xo, _ = self._xattn().apply(params["xattn"], hx_t, ctx,
                                            kv_source=enc_g)
                xo = jnp.swapaxes(xo, 0, 1)                 # (1, T, d)
            else:
                xo, _ = self._xattn().apply(params["xattn"], hx, ctx,
                                            kv_source=enc)
            x = x + xo
        if ffn is not None:
            h2 = self._norm("norm2").apply(params["norm2"], x, ctx)
            if self.ffn == "rwkv":
                f_out, cm = ffn.apply(params["ffn"], h2, ctx,
                                      state=None if cache is None else cache.get("cm"),
                                      chunk=chunk)
                if cm is not None:
                    new_cache["cm"] = cm
            else:
                f_out = ffn.apply(params["ffn"], h2, ctx)
            x = x + f_out
        x = ctx.constrain(x, "batch", "seq", None)
        return x, (new_cache or None)


@dataclasses.dataclass(frozen=True)
class Stack:
    """prelude blocks (python loop) + body period scanned n_periods times."""

    body: Tuple[Block, ...]
    n_periods: int
    prelude: Tuple[Block, ...] = ()
    remat: str = "full"            # off | none | dots | full
    scan_layers: bool = True
    name: str = "stack"

    @property
    def n_layers(self) -> int:
        """Total layer count (prelude + scanned periods)."""
        return len(self.prelude) + len(self.body) * self.n_periods

    def init(self, key) -> Params:
        """Create parameters for every layer (stacked for scanned periods)."""
        kp, kb = jax.random.split(key)
        p: Params = {}
        if self.prelude:
            ks = jax.random.split(kp, len(self.prelude))
            p["prelude"] = [blk.init(k) for blk, k in zip(self.prelude, ks)]
        if self.scan_layers and self.n_periods > 1:
            keys = jax.random.split(kb, self.n_periods)
            body_p = []
            for i, blk in enumerate(self.body):
                per_pos = jax.vmap(lambda k: blk.init(
                    jax.random.fold_in(k, i)))(keys)
                body_p.append(per_pos)
            p["body"] = body_p
        else:
            ks = jax.random.split(kb, self.n_periods * max(1, len(self.body)))
            p["body"] = [self.body[i % len(self.body)].init(ks[i])
                         for i in range(self.n_periods * len(self.body))]
        return p

    def init_cache(self, batch: int, max_len: int, *, quantized_kv: bool,
                   kv_dtype=jnp.bfloat16, per_slot_len: bool = False,
                   page_size: Optional[int] = None,
                   num_pages: Optional[int] = None,
                   enc_len: Optional[int] = None,
                   ) -> Dict[str, Any]:
        """Decode caches for all layers, stacked to match the scan layout."""
        kw = dict(quantized_kv=quantized_kv, kv_dtype=kv_dtype,
                  per_slot_len=per_slot_len, page_size=page_size,
                  num_pages=num_pages, enc_len=enc_len)
        c: Dict[str, Any] = {}
        if self.prelude:
            c["prelude"] = [blk.init_cache(batch, max_len, **kw)
                            for blk in self.prelude]
        if self.scan_layers and self.n_periods > 1:
            c["body"] = [
                jax.tree_util.tree_map(
                    lambda l: jnp.broadcast_to(
                        l[None], (self.n_periods,) + l.shape).copy(),
                    blk.init_cache(batch, max_len, **kw))
                for blk in self.body]
        else:
            c["body"] = [self.body[i % len(self.body)].init_cache(
                batch, max_len, **kw)
                for i in range(self.n_periods * len(self.body))]
        return c

    def apply(self, params: Params, x, ctx: Context, *,
              cache: Optional[Dict[str, Any]] = None,
              enc: Optional[jax.Array] = None,
              positions: Optional[jax.Array] = None,
              decode: bool = False,
              chunk=None,
              ragged=None) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
        """Run all layers (prelude loop + scanned periods), threading the
        serving kwargs and per-layer cache slices through each block.
        """
        ctx = ctx.scope(self.name)
        new_cache: Dict[str, Any] = {} if cache is not None else None

        for i, blk in enumerate(self.prelude):
            bctx = ctx.scope(f"pre{i}")
            x, nc = blk.apply(params["prelude"][i], x, bctx,
                              cache=None if cache is None else cache["prelude"][i],
                              enc=enc, positions=positions, decode=decode,
                              chunk=chunk, ragged=ragged)
            if new_cache is not None:
                new_cache.setdefault("prelude", []).append(nc)

        if not (self.scan_layers and self.n_periods > 1):
            ncs = []
            for i in range(self.n_periods * len(self.body)):
                blk = self.body[i % len(self.body)]

                # stats/aux-losses must cross the jax.checkpoint boundary as
                # outputs (mutating the shared dicts inside the rematerialized
                # region would leak tracers — same discipline as the scan path)
                def layer_fn(p, xc, c, blk=blk, i=i):
                    sctx = ctx.fork_for_scan()
                    bctx = sctx.scope(f"l{i}")
                    x2, nc = blk.apply(p, xc, bctx, cache=c, enc=enc,
                                       positions=positions, decode=decode,
                                       chunk=chunk, ragged=ragged)
                    return x2, nc, sctx.stats, sctx.losses

                if self.remat != "off":
                    layer_fn = _remat(layer_fn, self.remat)
                x, nc, stats, losses = layer_fn(
                    params["body"][i], x,
                    None if cache is None else cache["body"][i])
                ctx.merge_scanned(stats, losses)
                ncs.append(nc)
            if new_cache is not None:
                new_cache["body"] = ncs
            return x, new_cache

        # ---- scanned body ----------------------------------------------------
        def period_body(carry, xs):
            xc = carry
            p_list, c_list = xs
            sctx = ctx.fork_for_scan()
            ncs = []
            for pos, blk in enumerate(self.body):
                bctx = sctx.scope(f"p{pos}")
                xc, nc = blk.apply(
                    p_list[pos], xc, bctx,
                    cache=None if c_list is None else c_list[pos],
                    enc=enc, positions=positions, decode=decode, chunk=chunk,
                    ragged=ragged)
                nc = dict(nc) if nc is not None else {}
                # xkv is read-only here (written only by write_cross_kv, at
                # admission): returning it as a scan output would
                # rematerialize the full per-layer encoder K/V every step —
                # the original stacked buffers are reattached after the scan
                nc.pop("xkv", None)
                ncs.append(nc)
            return xc, (tuple(ncs), sctx.stats, sctx.losses)

        body_fn = _remat(period_body, self.remat)
        xs = (params["body"],
              cache["body"] if cache is not None else None)
        x, (ncs, stats, losses) = jax.lax.scan(body_fn, x, xs)
        ctx.merge_scanned(stats, losses)
        if new_cache is not None:
            ncs = list(ncs)
            for pos in range(len(ncs)):
                cb = cache["body"][pos]
                if isinstance(cb, dict) and "xkv" in cb:
                    # identity passthrough outside the scan: under cache
                    # donation this aliases, so the cached cross-attention
                    # read path pays zero copy per step
                    ncs[pos] = dict(ncs[pos], xkv=cb["xkv"])
            new_cache["body"] = ncs
        return x, new_cache
