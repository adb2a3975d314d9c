"""ArchConfig: declarative architecture description → model instance + specs.

One instance per assigned architecture lives in ``configs/<id>.py`` with the
exact published numbers.  ``smoke()`` derives the reduced same-family config
used by the CPU smoke tests; ``build()`` assembles the Stack/CausalLM/EncDec;
``input_specs()`` yields ShapeDtypeStruct stand-ins for the dry-run.

Layer layout is a period string over {'a': attention, 'm': mamba, 'r': rwkv6}
repeated ``n_layers/len(layout)`` times (jamba: "mmmammmm").  MoE placement:
``moe_every=k, moe_offset=o`` puts MoE at global layer indices i ≡ o (mod k);
``first_k_dense`` peels leading dense layers out of the scan (kimi-k2).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.lm import CausalLM, EncDecLM
from repro.nn.transformer import Block, Stack

# --------------------------------------------------------------------------
# Shapes (assigned): every LM arch is paired with these four cells.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

# Families with sub-quadratic decode state run long_500k; pure full-attention
# archs skip it (DESIGN.md §5 records the skip rationale).
LONG_CONTEXT_FAMILIES = ("ssm", "hybrid")


def pad_vocab(v: int, multiple: int = 256) -> int:
    return ((v + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    arch_id: str
    family: str                    # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    layout: str = "a"              # period string over {a, m, r}
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_every: int = 0             # 0 = no MoE
    moe_offset: int = 0
    first_k_dense: int = 0
    d_ff_dense: int = 0            # dense-FFN width where it differs (kimi)
    # attention details
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    # Mamba mixer: RMSNorms on dt, B, C after x_proj (HF Jamba's mixer)
    mamba_inner_norms: bool = False
    # block structure
    norm: str = "rms"
    parallel_block: bool = False
    activation: str = "silu"
    ffn_kind: str = "gated"        # gated | mlp | rwkv
    tie_embeddings: bool = True
    # enc-dec (audio)
    enc_layers: int = 0
    enc_seq: int = 1500            # stub frontend output length (whisper frames)
    # vlm
    vis_seq: int = 0               # stub vision-prefix length
    # bookkeeping
    notes: str = ""

    # ----------------------------------------------------------------------
    @property
    def vocab_padded(self) -> int:
        return pad_vocab(self.vocab)

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    def supports(self, shape_name: str) -> bool:
        if shape_name == "long_500k":
            return self.family in LONG_CONTEXT_FAMILIES
        return shape_name in SHAPES

    # ----------------------------------------------------------------------
    def _block(self, layer_idx: int, mixer_ch: str, dtype, causal=True) -> Block:
        is_moe = (self.moe_every > 0
                  and layer_idx >= self.first_k_dense
                  and (layer_idx % self.moe_every) == self.moe_offset)
        mixer = {"a": "attn", "m": "mamba", "r": "rwkv"}[mixer_ch]
        if is_moe:
            ffn, d_ff = "moe", self.d_ff
        else:
            ffn = self.ffn_kind
            d_ff = (self.d_ff_dense or self.d_ff)
        return Block(
            d_model=self.d_model, mixer=mixer,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, qkv_bias=self.qkv_bias,
            rope_theta=self.rope_theta, use_rope=self.use_rope, causal=causal,
            mamba_inner_norms=self.mamba_inner_norms,
            ffn=ffn, d_ff=d_ff, activation=self.activation,
            n_experts=self.n_experts, top_k=self.top_k,
            n_shared_experts=self.n_shared_experts,
            norm=self.norm, parallel=self.parallel_block, dtype=dtype)

    def _stack(self, dtype, remat: str, scan_layers: bool) -> Stack:
        period = len(self.layout)
        assert (self.n_layers - self.first_k_dense) % period == 0, self.arch_id
        prelude = tuple(self._block(i, self.layout[i % period], dtype)
                        for i in range(self.first_k_dense))
        body = tuple(self._block(self.first_k_dense + p, self.layout[p], dtype)
                     for p in range(period))
        return Stack(body=body,
                     n_periods=(self.n_layers - self.first_k_dense) // period,
                     prelude=prelude, remat=remat, scan_layers=scan_layers)

    def build(self, *, dtype=jnp.bfloat16, remat: str = "full",
              scan_layers: bool = True):
        if self.is_encdec:
            enc_block = Block(
                d_model=self.d_model, mixer="attn", n_heads=self.n_heads,
                n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                use_rope=False, causal=False, ffn="mlp", d_ff=self.d_ff,
                activation="gelu", norm=self.norm, dtype=dtype)
            dec_block = Block(
                d_model=self.d_model, mixer="attn", n_heads=self.n_heads,
                n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                use_rope=False, causal=True, cross=True, ffn="mlp",
                d_ff=self.d_ff, activation="gelu", norm=self.norm, dtype=dtype)
            return EncDecLM(
                vocab=self.vocab, vocab_padded=self.vocab_padded,
                d_model=self.d_model,
                encoder=Stack(body=(enc_block,), n_periods=self.enc_layers,
                              remat=remat, scan_layers=scan_layers),
                decoder=Stack(body=(dec_block,), n_periods=self.n_layers,
                              remat=remat, scan_layers=scan_layers),
                max_target_len=SHAPES["decode_32k"].seq_len,
                norm=self.norm, enc_len=self.enc_seq, dtype=dtype)
        return CausalLM(
            vocab=self.vocab, vocab_padded=self.vocab_padded,
            d_model=self.d_model, stack=self._stack(dtype, remat, scan_layers),
            norm=self.norm, tie_embeddings=self.tie_embeddings, dtype=dtype)

    # ----------------------------------------------------------------------
    def smoke(self) -> "ArchConfig":
        """Reduced same-family config for CPU smoke tests."""
        period = len(self.layout)
        d_model = 64
        n_heads = 4
        n_kv = min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else n_heads
        return dataclasses.replace(
            self,
            arch_id=self.arch_id + "-smoke",
            n_layers=self.first_k_dense + period * (2 if period == 1 else 1),
            d_model=d_model, n_heads=n_heads, n_kv_heads=n_kv, head_dim=16,
            d_ff=128, d_ff_dense=128 if self.d_ff_dense else 0, vocab=503,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            enc_layers=min(self.enc_layers, 2) if self.enc_layers else 0,
            vis_seq=min(self.vis_seq, 8) if self.vis_seq else 0,
            enc_seq=16 if self.enc_layers else self.enc_seq,
        )

    # ----------------------------------------------------------------------
    def param_count(self) -> int:
        """Analytic total parameter count (embedding included, true vocab)."""
        d, f = self.d_model, self.d_ff
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        total = emb
        period = len(self.layout)
        for i in range(self.n_layers):
            ch = self.layout[(i - self.first_k_dense) % period] \
                if i >= self.first_k_dense else self.layout[i % period]
            if ch == "a":
                qd = self.n_heads * self.head_dim
                kvd = self.n_kv_heads * self.head_dim
                total += d * (qd + 2 * kvd) + qd * d
            elif ch == "m":
                di = 2 * d
                dtr = max(1, math.ceil(d / 16))
                total += d * 2 * di + di * (dtr + 32) + dtr * di + di * d
            elif ch == "r":
                total += 5 * d * d
            is_moe = (self.moe_every > 0 and i >= self.first_k_dense
                      and (i % self.moe_every) == self.moe_offset)
            if is_moe:
                total += self.n_experts * 3 * d * f
                total += self.n_shared_experts * 3 * d * f
            elif ch == "r":
                total += 2 * d * self.d_ff + d * d
            else:
                ff = self.d_ff_dense or f
                n_mats = 3 if self.ffn_kind == "gated" else 2
                total += n_mats * d * ff
        if self.is_encdec:
            total += self.enc_layers * (4 * d * d + 2 * d * f)
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k + shared of E experts)."""
        if not self.moe_every:
            return self.param_count()
        full = self.param_count()
        n_moe_layers = sum(
            1 for i in range(self.first_k_dense, self.n_layers)
            if (i % self.moe_every) == self.moe_offset)
        inactive = n_moe_layers * (self.n_experts - self.top_k) * 3 \
            * self.d_model * self.d_ff
        return full - inactive

    # ----------------------------------------------------------------------
    def input_specs(self, shape_name: str, *, dtype=jnp.bfloat16,
                    ) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this cell.

        train:   tokens/labels (B, S) (+ embeds stub for audio/vlm)
        prefill: tokens (B, S)
        decode:  tokens (B, 1) + KV/state cache sized for S
        """
        sh = SHAPES[shape_name]
        if not self.supports(shape_name):
            raise ValueError(f"{self.arch_id} skips {shape_name}")
        B, S = sh.global_batch, sh.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct

        if sh.kind == "train":
            if self.is_encdec:
                return {"embeds": sds((B, self.enc_seq, self.d_model), dtype),
                        "tokens": sds((B, S), i32),
                        "labels": sds((B, S), i32)}
            if self.vis_seq:
                return {"embeds": sds((B, self.vis_seq, self.d_model), dtype),
                        "tokens": sds((B, S - self.vis_seq), i32),
                        "labels": sds((B, S - self.vis_seq), i32)}
            return {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}

        if sh.kind == "prefill":
            out = {"tokens": sds((B, S), i32)}
            if self.is_encdec:
                out["embeds"] = sds((B, self.enc_seq, self.d_model), dtype)
            if self.vis_seq:
                out["embeds"] = sds((B, self.vis_seq, self.d_model), dtype)
                out["tokens"] = sds((B, S - self.vis_seq), i32)
            return out

        # decode: one new token against an S-token cache
        model = self.build(dtype=dtype)
        cache = jax.eval_shape(
            lambda: model.init_cache(B, S, quantized_kv=False, kv_dtype=dtype))
        out = {"tokens": sds((B, 1), i32), "cache": cache}
        if self.is_encdec:
            out["enc"] = sds((B, self.enc_seq, self.d_model), dtype)
        return out
