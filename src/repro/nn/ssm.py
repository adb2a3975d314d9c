"""State-space sequence mixers: Mamba (jamba hybrid) and RWKV6 "Finch".

Both are attention-free recurrences with O(1) decode state — the reason the
``long_500k`` shape runs only on these families (DESIGN.md §5).

Quantization applicability (DESIGN.md §Arch-applicability): the paper's
technique covers every *projection* GEMM (in/out/x/dt for Mamba; r/k/v/g/o and
the FFN for RWKV) via the shared :class:`Dense` layer.  The recurrent **state
itself stays fp32**: a Qm.n-quantized state re-quantizes every step and the
truncation error compounds over thousands of steps (the paper's engine never
re-quantizes inside max-pool for the same reason — precision lost is never
recovered).  ``ssm_state`` is in ``QuantPolicy.skip_kinds``.

Implementation notes:
  * Train/prefill use a **chunked scan**: an outer ``lax.scan`` over chunks
    carries the (B, ...) state; within a chunk the recurrence is unrolled in
    matrix form where possible.  Chunk size bounds the materialized
    (B, chunk, d_inner, d_state) tensor — VMEM/HBM-friendly.
  * Decode is a single recurrence step against carried state (serve path).
  * TP: d_inner / heads shard over `model`; the state shards with them.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn.layers import Dense, RMSNorm, lecun_normal, normal_init
from repro.nn.module import Context, Params

# --------------------------------------------------------------------------
# Mamba (selective SSM, v1 — as interleaved in Jamba)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Mamba:
    """Mamba selective-SSM mixer with a recurrent decode state."""
    d_model: int
    d_inner: int = 0          # default 2*d_model
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0          # default ceil(d_model/16)
    chunk: int = 128
    # Jamba's mixer: RMSNorms on dt, B and C right after x_proj
    # (HF JambaMambaMixer dt_layernorm / b_layernorm / c_layernorm)
    inner_norms: bool = False
    dtype: Any = jnp.float32
    name: str = "mamba"

    @property
    def _di(self):
        return self.d_inner or 2 * self.d_model

    @property
    def _dtr(self):
        return self.dt_rank or max(1, math.ceil(self.d_model / 16))

    def _projs(self):
        di = self._di
        return {
            "in_proj": Dense(self.d_model, 2 * di, use_bias=False, dtype=self.dtype,
                             name="in_proj"),
            "x_proj": Dense(di, self._dtr + 2 * self.d_state, use_bias=False,
                            dtype=self.dtype, name="x_proj"),
            "dt_proj": Dense(self._dtr, di, use_bias=True, dtype=self.dtype,
                             name="dt_proj"),
            "out_proj": Dense(di, self.d_model, use_bias=False, dtype=self.dtype,
                              name="out_proj"),
        }

    def init(self, key) -> Params:
        """Create projection, conv and SSM parameters."""
        ks = jax.random.split(key, 6)
        di, n = self._di, self.d_state
        p = {nm: l.init(k) for (nm, l), k in zip(self._projs().items(), ks)}
        # depthwise causal conv over time: (d_conv, di)
        p["conv"] = {"kernel": lecun_normal(ks[4], (self.d_conv, 1, di)),
                     "bias": jnp.zeros((di,), jnp.float32)}
        # S4D-real init for A; D skip
        a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None, :], (di, 1))
        p["ssm"] = {"a_log": jnp.log(a), "d_skip": jnp.ones((di,), jnp.float32)}
        if self.inner_norms:
            for nm, width in self._inner_norms().items():
                p[nm] = RMSNorm(width, name=nm).init(None)
        return p

    def _inner_norms(self) -> Dict[str, int]:
        return {"dt_norm": self._dtr, "b_norm": self.d_state,
                "c_norm": self.d_state}

    def _conv1d(self, params, x, conv_state=None):
        """Causal depthwise conv; returns (y, padded_input).

        ``conv_state`` is the trailing (K-1) inputs of the previous call
        (zeros for a fresh sequence), so prefill-with-state and single-token
        decode share one code path.  The second return value is the full
        left-padded input ``xp``; callers slice their own carry window out of
        it (the trailing K-1 rows for dense decode, the K-1 rows ending at
        the chunk's live length for per-slot chunked prefill).
        """
        w = params["conv"]["kernel"]                      # (K, 1, di)
        if hasattr(w, "dequantize"):
            # weight-only int8 serving stores every >=2-dim kernel as a
            # QTensor; the depthwise conv reads its weight directly (no
            # Dense/wq_matmul path), so dequantize here
            w = w.dequantize()
        w = w.astype(self.dtype)
        b = params["conv"]["bias"].astype(self.dtype)
        k = self.d_conv
        if conv_state is not None:
            xp = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
        else:
            xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        y = jax.lax.conv_general_dilated(
            xp, w, (1,), "VALID", dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=self._di) + b
        return y, xp

    def _ssm_inputs(self, params, xc, ctx):
        """Data-dependent dt, B, C from the conv output."""
        projs = self._projs()
        dbc = projs["x_proj"].apply(params["x_proj"], xc, ctx)
        dt, bmat, cmat = jnp.split(
            dbc, [self._dtr, self._dtr + self.d_state], axis=-1)
        if self.inner_norms:
            dt, bmat, cmat = (
                RMSNorm(w, name=nm).apply(params[nm], t, ctx)
                for (nm, w), t in zip(self._inner_norms().items(),
                                      (dt, bmat, cmat)))
        dt = jax.nn.softplus(projs["dt_proj"].apply(params["dt_proj"], dt, ctx)
                             .astype(jnp.float32))                  # (B,L,di)
        return dt, bmat.astype(jnp.float32), cmat.astype(jnp.float32)

    def _scan(self, a_log, d_skip, xc, dt, bmat, cmat, h0):
        """Chunked selective scan. xc/dt (B,L,di); bmat/cmat (B,L,N); h0 (B,di,N)."""
        bsz, L, di = xc.shape
        n = self.d_state
        A = -jnp.exp(a_log)                                          # (di, N)
        ch = min(self.chunk, L)
        pad = (-L) % ch
        if pad:
            z = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            xc, dt, bmat, cmat = z(xc), z(dt), z(bmat), z(cmat)
        nc = xc.shape[1] // ch

        xcf = xc.astype(jnp.float32)

        def chunk_step(h, args):
            xk, dtk, bk, ck = args                                   # (B,ch,·)
            da = jnp.exp(dtk[..., None] * A)                         # (B,ch,di,N)
            dbx = (dtk * xk)[..., None] * bk[:, :, None, :]          # (B,ch,di,N)

            def inner(hc, t):
                hc = da[:, t] * hc + dbx[:, t]
                return hc, jnp.einsum("bdn,bn->bd", hc, ck[:, t])

            h, ys = jax.lax.scan(inner, h, jnp.arange(ch))
            return h, jnp.moveaxis(ys, 0, 1)                         # (B,ch,di)

        args = tuple(t.reshape(bsz, nc, ch, *t.shape[2:]).swapaxes(0, 1)
                     for t in (xcf, dt, bmat, cmat))
        h, ys = jax.lax.scan(chunk_step, h0, args)
        y = ys.swapaxes(0, 1).reshape(bsz, nc * ch, di)[:, :L]
        return y + xcf * d_skip, h

    @staticmethod
    def _step(ssm, xc, dt, bmat, cmat, h0):
        """One recurrence step per row: xc/dt (B,di), bmat/cmat (B,N), h0
        (B,di,N) -> (y (B,di) f32, h (B,di,N))."""
        A = -jnp.exp(ssm["a_log"])
        da = jnp.exp(dt[..., None] * A)
        h = da * h0 + (dt * xc.astype(jnp.float32))[..., None] \
            * bmat[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, cmat)
        return y + xc.astype(jnp.float32) * ssm["d_skip"], h

    def apply(self, params: Params, x, ctx: Context,
              state: Optional[Dict[str, Any]] = None,
              chunk=None, ragged=None,
              ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
        """x: (B, S, D).  state: {'h': (B,di,N) f32, 'conv': (B,K-1,di)} or None.

        With ``chunk`` (a ``KVChunk(slot, start, length)``), x is one (1, S, D)
        prompt chunk of a single serving slot: the slot's state row is
        gathered, advanced over the chunk's live ``length`` positions (the pad
        tail is masked to dt=0, an identity state update), and scattered back.
        With ``ragged`` (a ``RaggedBatch``), x is one ragged tick; see
        :meth:`_apply_ragged`.
        """
        ctx = ctx.scope(self.name)
        if ragged is not None:
            return self._apply_ragged(params, x, ctx, state, ragged)
        projs = self._projs()
        b, s, _ = x.shape
        di, n = self._di, self.d_state
        k = self.d_conv

        xz = projs["in_proj"].apply(params["in_proj"], x, ctx)
        xin, z = jnp.split(xz, 2, axis=-1)
        xin = ctx.constrain(xin, "batch", None, "ff")

        decode = state is not None and chunk is None
        if chunk is not None:
            h0 = jax.lax.dynamic_index_in_dim(state["h"], chunk.slot, 0,
                                              keepdims=True)
            conv_state = jax.lax.dynamic_index_in_dim(state["conv"], chunk.slot,
                                                      0, keepdims=True)
        else:
            h0 = state["h"] if decode else jnp.zeros((b, di, n), jnp.float32)
            conv_state = state["conv"] if decode else None
        xc, xp = self._conv1d(params, xin, conv_state)
        xc = jax.nn.silu(xc)

        dt, bmat, cmat = self._ssm_inputs(params, xc, ctx)
        if chunk is not None:
            # dt=0 on the pad tail: da=exp(0)=1, dbx=0 — identity update, so
            # the scanned state lands exactly at position `length`.
            live = jnp.arange(s)[None, :, None] < chunk.length
            dt = jnp.where(live, dt, 0.0)

        if decode and s == 1:
            y, h = self._step(params["ssm"], xc[:, 0], dt[:, 0], bmat[:, 0],
                              cmat[:, 0], h0)
            y = y[:, None]
        else:
            y, h = self._scan(params["ssm"]["a_log"], params["ssm"]["d_skip"],
                              xc, dt, bmat, cmat, h0)

        y = (y.astype(self.dtype) * jax.nn.silu(z)).astype(self.dtype)
        out = projs["out_proj"].apply(params["out_proj"], y, ctx)
        if chunk is not None:
            # conv carry: the K-1 inputs ending at the live length (xp is the
            # conv-state-prepended input, so row `length` is the first carry row)
            new_state = {"h": jax.lax.dynamic_update_slice_in_dim(
                state["h"], h, chunk.slot, axis=0)}
            if k > 1:
                carry = jax.lax.dynamic_slice_in_dim(xp, chunk.length, k - 1,
                                                     axis=1)
                new_state["conv"] = jax.lax.dynamic_update_slice_in_dim(
                    state["conv"], carry.astype(state["conv"].dtype),
                    chunk.slot, axis=0)
            else:
                new_state["conv"] = state["conv"]
        elif decode:
            new_state = {"h": h,
                         "conv": xp[:, -(k - 1):] if k > 1 else None}
        else:
            new_state = None
        return out, new_state

    def _apply_ragged(self, params, x, ctx, state, ragged):
        """One ragged tick (serve/engine.py ``make_ragged_step``).

        x is the (1, T) token batch, T = B + L*C: rows ``[0, B)`` are the
        decode rows, row j being slot j's one token; then L lanes of C
        contiguous prompt rows, each of one slot.  Projections run once over
        all T rows.  Decode rows take one recurrence step; an inert row
        (position -1) leaves its slot's ``h`` and ``conv`` bit-identical.
        Each lane gathers its slot's state (zeros on a prompt's first chunk,
        position 0: the reset at admission), runs the chunked scan over its
        live prefix (the pad tail is masked to dt=0), and scatters the state
        back; an idle lane (no live row) writes nothing.
        """
        projs = self._projs()
        nl, c = ragged.lanes, ragged.chunk
        t = x.shape[1]
        nb = t - nl * c
        di, k = self._di, self.d_conv
        pos = jnp.asarray(ragged.positions, jnp.int32)
        sids = jnp.asarray(ragged.slots, jnp.int32)
        h_all, conv_all = state["h"], state["conv"]

        xz = projs["in_proj"].apply(params["in_proj"], x, ctx)
        xin, z = jnp.split(xz, 2, axis=-1)
        xin = xin[0]
        with jax.named_scope("mamba.ragged_decode"):
            live = pos[:nb] >= 0
            xcd, xpd = self._conv1d(params, xin[:nb, None], conv_all)
        with jax.named_scope("mamba.ragged_lanes"):
            lpos = pos[nb:].reshape(nl, c)
            llen = jnp.sum(lpos >= 0, axis=1)
            lslot = sids[nb:].reshape(nl, c)[:, 0]
            fresh = (lpos[:, 0] == 0)[:, None, None]
            h0 = jnp.where(fresh, 0.0, h_all[lslot])
            cs = jnp.where(fresh, 0, conv_all[lslot])
            xcl, xpl = self._conv1d(params, xin[nb:].reshape(nl, c, di), cs)
        xc = jax.nn.silu(jnp.concatenate(
            [xcd[:, 0], xcl.reshape(nl * c, di)]))[None]
        dt, bmat, cmat = self._ssm_inputs(params, xc, ctx)
        xc, dt, bmat, cmat = xc[0], dt[0], bmat[0], cmat[0]

        with jax.named_scope("mamba.ragged_decode"):
            y_d, h_d = self._step(params["ssm"], xc[:nb], dt[:nb],
                                  bmat[:nb], cmat[:nb], h_all)
            h_new = jnp.where(live[:, None, None], h_d, h_all)
            conv_new = conv_all
            if k > 1:
                conv_new = jnp.where(live[:, None, None],
                                     xpd[:, -(k - 1):].astype(conv_all.dtype),
                                     conv_all)
        with jax.named_scope("mamba.ragged_lanes"):
            lane = lambda a: a[nb:].reshape(nl, c, *a.shape[1:])
            dtl = jnp.where(jnp.arange(c)[None, :, None] < llen[:, None, None],
                            lane(dt), 0.0)
            y_l, h_l = self._scan(params["ssm"]["a_log"],
                                  params["ssm"]["d_skip"], lane(xc), dtl,
                                  lane(bmat), lane(cmat), h0)
            # an idle lane scatters out of bounds: dropped
            tgt = jnp.where(llen > 0, lslot, h_all.shape[0])
            h_new = h_new.at[tgt].set(h_l, mode="drop")
            if k > 1:
                carry = jax.vmap(lambda xp, n: jax.lax.dynamic_slice_in_dim(
                    xp, n, k - 1, axis=0))(xpl, llen)
                conv_new = conv_new.at[tgt].set(carry.astype(conv_all.dtype),
                                                mode="drop")

        y = jnp.concatenate([y_d, y_l.reshape(nl * c, di)])[None]
        y = (y.astype(self.dtype) * jax.nn.silu(z)).astype(self.dtype)
        out = projs["out_proj"].apply(params["out_proj"], y, ctx)
        return out, {"h": h_new, "conv": conv_new}

    def init_state(self, batch: int) -> Dict[str, Any]:
        """Zeroed per-slot recurrent state (conv window + SSM state)."""
        return {"h": jnp.zeros((batch, self._di, self.d_state), jnp.float32),
                "conv": jnp.zeros((batch, self.d_conv - 1, self._di), self.dtype)}


# --------------------------------------------------------------------------
# RWKV6 "Finch" — data-dependent decay linear attention
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RWKV6TimeMix:
    """RWKV6 time-mixing: S_t = diag(w_t)·S_{t-1} + kᵀv; o = r·(S + diag(u)kᵀv).

    Simplified-faithful Finch: data-dependent per-channel decay w_t through a
    low-rank MLP (the paper's LoRA), token-shift interpolation on the inputs,
    grouped heads with per-head (N×N) fp32 state.
    """

    d_model: int
    head_dim: int = 64
    decay_lora: int = 64
    chunk: int = 128
    dtype: Any = jnp.float32
    name: str = "timemix"

    @property
    def n_heads(self):
        """Number of time-mix heads (``d_model / head_dim``)."""
        return self.d_model // self.head_dim

    def _projs(self):
        d = self.d_model
        mk = lambda nm: Dense(d, d, use_bias=False, dtype=self.dtype, name=nm)
        return {"wr": mk("wr"), "wk": mk("wk"), "wv": mk("wv"),
                "wg": mk("wg"), "wo": mk("wo")}

    def init(self, key) -> Params:
        """Create time-mix interpolation, decay and projection parameters."""
        ks = jax.random.split(key, 9)
        d, h, n = self.d_model, self.n_heads, self.head_dim
        p = {nm: l.init(k) for (nm, l), k in zip(self._projs().items(), ks)}
        p["decay"] = {  # w0 + tanh(x A) B  (the Finch decay LoRA)
            "w0": jnp.full((d,), -6.0, jnp.float32),
            "a": normal_init(ks[5], (d, self.decay_lora), std=0.01),
            "b": normal_init(ks[6], (self.decay_lora, d), std=0.01),
        }
        p["bonus_u"] = normal_init(ks[7], (h, n), std=0.5)
        p["mix"] = {"x": jnp.full((5, d), 0.5, jnp.float32)}  # token-shift lerp
        p["ln_out"] = {"scale": jnp.ones((d,), jnp.float32)}
        return p

    def _token_shift(self, x, last):
        """x_{t-1} per position; `last` is (B,1,D) carry for decode."""
        prev = jnp.concatenate([last, x[:, :-1]], axis=1)
        return prev

    def _scan(self, r, k, v, w, u, s0):
        """Recurrence over time, chunked.  r/k/v (B,L,H,N); w (B,L,H,N) decay
        in (0,1); u (H,N); s0 (B,H,N,N).  Returns (out (B,L,H,N), sT)."""
        bsz, L, h, n = r.shape
        ch = min(self.chunk, L)
        pad = (-L) % ch
        if pad:
            z = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
            r, k, v, w = z(r), z(k), z(v), z(w)
            w = w.at[:, L:].set(1.0)  # identity decay on padding
        nc = r.shape[1] // ch

        def chunk_step(s, args):
            rk, kk, vk, wk = args                                     # (B,ch,H,N)

            def inner(sc, t):
                kv = kk[:, t, :, :, None] * vk[:, t, :, None, :]      # (B,H,N,N)
                o = jnp.einsum("bhn,bhnm->bhm", rk[:, t],
                               sc + u[None, :, :, None] * kv)
                sc = wk[:, t, :, :, None] * sc + kv
                return sc, o

            s, os = jax.lax.scan(inner, s, jnp.arange(ch))
            return s, jnp.moveaxis(os, 0, 1)                          # (B,ch,H,N)

        args = tuple(t.reshape(bsz, nc, ch, h, n).swapaxes(0, 1)
                     for t in (r, k, v, w))
        s, outs = jax.lax.scan(chunk_step, s0, args)
        out = outs.swapaxes(0, 1).reshape(bsz, nc * ch, h, n)[:, :L]
        return out, s

    def apply(self, params: Params, x, ctx: Context,
              state: Optional[Dict[str, Any]] = None,
              chunk=None,
              ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
        """Run the WKV recurrence over ``x``; returns output and new state.

        With ``chunk``, x is one (1, S, D) prompt chunk of a single serving
        slot: the slot's (s, shift) rows are gathered, the pad tail is masked
        to an identity update (decay 1, k 0), and the final state is scattered
        back into the slot row.
        """
        ctx = ctx.scope(self.name)
        projs = self._projs()
        b, s, d = x.shape
        h, n = self.n_heads, self.head_dim

        if chunk is not None:
            last = jax.lax.dynamic_index_in_dim(state["shift"], chunk.slot, 0,
                                                keepdims=True)
            s0 = jax.lax.dynamic_index_in_dim(state["s"], chunk.slot, 0,
                                              keepdims=True)
        else:
            last = state["shift"] if state is not None else jnp.zeros(
                (b, 1, d), x.dtype)
            s0 = state["s"] if state is not None else jnp.zeros(
                (b, h, n, n), jnp.float32)
        prev = self._token_shift(x, last.astype(x.dtype))
        mix = params["mix"]["x"]                                      # (5, D)
        xr, xk, xv, xg, xw = (x + mix[i] * (prev - x) for i in range(5))

        r = projs["wr"].apply(params["wr"], xr, ctx).reshape(b, s, h, n)
        k = projs["wk"].apply(params["wk"], xk, ctx).reshape(b, s, h, n)
        v = projs["wv"].apply(params["wv"], xv, ctx).reshape(b, s, h, n)
        g = jax.nn.silu(projs["wg"].apply(params["wg"], xg, ctx))

        # data-dependent decay (fp32; `decay` path skipped from quantization)
        dk = params["decay"]
        wraw = dk["w0"] + jnp.tanh(xw.astype(jnp.float32) @ dk["a"]) @ dk["b"]
        w = jnp.exp(-jnp.exp(wraw)).reshape(b, s, h, n)               # (0,1)

        r32, k32, v32 = (t.astype(jnp.float32) for t in (r, k, v))
        if chunk is not None:
            # identity update on the pad tail: decay 1 keeps S, k=0 adds nothing
            live = jnp.arange(s)[None, :, None, None] < chunk.length
            w = jnp.where(live, w, 1.0)
            k32 = jnp.where(live, k32, 0.0)
        s0 = ctx.constrain(s0, "batch", "heads", None, None)

        if state is not None and chunk is None and s == 1:
            kv = k32[:, 0, :, :, None] * v32[:, 0, :, None, :]
            o = jnp.einsum("bhn,bhnm->bhm",
                           r32[:, 0], s0 + params["bonus_u"][None, :, :, None] * kv)
            sT = w[:, 0, :, :, None] * s0 + kv
            out = o[:, None]
        else:
            out, sT = self._scan(r32, k32, v32, w, params["bonus_u"], s0)

        # per-head group norm (ln_out), then gate and project
        out = out.reshape(b, s, h, n)
        mu = jnp.mean(out, axis=-1, keepdims=True)
        var = jnp.var(out, axis=-1, keepdims=True)
        out = (out - mu) * jax.lax.rsqrt(var + 1e-5)
        out = out.reshape(b, s, d) * params["ln_out"]["scale"]
        out = (out.astype(self.dtype) * g).astype(self.dtype)
        y = projs["wo"].apply(params["wo"], out, ctx)
        new_state = None
        if chunk is not None:
            tail = jax.lax.dynamic_slice_in_dim(x, chunk.length - 1, 1, axis=1)
            new_state = {
                "s": jax.lax.dynamic_update_slice_in_dim(
                    state["s"], sT, chunk.slot, axis=0),
                "shift": jax.lax.dynamic_update_slice_in_dim(
                    state["shift"], tail.astype(state["shift"].dtype),
                    chunk.slot, axis=0)}
        elif state is not None:
            new_state = {"s": sT, "shift": x[:, -1:, :]}
        return y, new_state

    def init_state(self, batch: int) -> Dict[str, Any]:
        """Zeroed per-slot WKV state (last token + per-head accumulator)."""
        return {"s": jnp.zeros((batch, self.n_heads, self.head_dim, self.head_dim),
                               jnp.float32),
                "shift": jnp.zeros((batch, 1, self.d_model), self.dtype)}


@dataclasses.dataclass(frozen=True)
class RWKV6ChannelMix:
    """RWKV6 channel-mixing FFN: relu²(wk(x̃))·wv with a receptance gate."""

    d_model: int
    d_ff: int
    dtype: Any = jnp.float32
    name: str = "chanmix"

    def _projs(self):
        return {
            "wk": Dense(self.d_model, self.d_ff, use_bias=False, dtype=self.dtype,
                        name="wk"),
            "wv": Dense(self.d_ff, self.d_model, use_bias=False, dtype=self.dtype,
                        name="wv"),
            "wr": Dense(self.d_model, self.d_model, use_bias=False, dtype=self.dtype,
                        name="wr"),
        }

    def init(self, key) -> Params:
        """Create channel-mix interpolation and projection parameters."""
        ks = jax.random.split(key, 3)
        p = {nm: l.init(k) for (nm, l), k in zip(self._projs().items(), ks)}
        p["mix"] = {"x": jnp.full((2, self.d_model), 0.5, jnp.float32)}
        return p

    def apply(self, params: Params, x, ctx: Context,
              state: Optional[Dict[str, Any]] = None,
              chunk=None):
        """Squared-ReLU channel mix; returns output and shifted-token state.

        With ``chunk``, x is a single slot's (1, S, D) prompt chunk; the
        shift carry is gathered from / scattered back to the slot row.
        """
        ctx = ctx.scope(self.name)
        projs = self._projs()
        if chunk is not None:
            last = jax.lax.dynamic_index_in_dim(state["shift"], chunk.slot, 0,
                                                keepdims=True).astype(x.dtype)
        else:
            last = state["shift"] if state is not None else jnp.zeros(
                (x.shape[0], 1, x.shape[-1]), x.dtype)
        prev = jnp.concatenate([last, x[:, :-1]], axis=1)
        mix = params["mix"]["x"]
        xk = x + mix[0] * (prev - x)
        xr = x + mix[1] * (prev - x)
        k = projs["wk"].apply(params["wk"], xk, ctx)
        k = jnp.square(jax.nn.relu(k))
        k = ctx.constrain(k, "batch", None, "ff")
        kv = projs["wv"].apply(params["wv"], k, ctx)
        r = jax.nn.sigmoid(projs["wr"].apply(params["wr"], xr, ctx))
        y = r * kv
        if chunk is not None:
            tail = jax.lax.dynamic_slice_in_dim(x, chunk.length - 1, 1, axis=1)
            new_state = {"shift": jax.lax.dynamic_update_slice_in_dim(
                state["shift"], tail.astype(state["shift"].dtype),
                chunk.slot, axis=0)}
        elif state is not None:
            new_state = {"shift": x[:, -1:, :]}
        else:
            new_state = None
        return y, new_state
