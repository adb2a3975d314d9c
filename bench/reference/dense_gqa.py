"""Plain float32 reference: a llama-style decoder with grouped-query attention.

Written from the published description (SmolLM-135M's ``config.json``, the
llama architecture): token embedding, ``num_hidden_layers`` pre-norm blocks of
RMSNorm -> causal GQA attention with rotary position embedding (half-split
rotation, ``rope_theta``) -> residual, RMSNorm -> SiLU-gated MLP -> residual,
a final RMSNorm and the tied embedding as the output head.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
matmul otherwise runs as one bfloat16 pass.  No kernel, cache or batching:
one sequence, the whole causal attention matrix, one layer after another.

Departures from the published block: none in the mathematics.  The weights
are random (``init_weights``), drawn from the seed; their scales follow
LeCun-normal fan-in init, with RMSNorm gains drawn around 1 so that a path
that ignores a gain shows.  This module imports nothing of the program under
test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    """The sizes the forward needs, read from the published keys."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return {"d": d, "hq": hq, "hkv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // hq,
            "ff": cfg["intermediate_size"], "layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
            "theta": float(cfg["rope_theta"])}


def init_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Random weights from ``key``, stacked over layers, ``x @ W`` layout."""
    z = dims(cfg)
    d, hd, n = z["d"], z["hd"], z["layers"]
    ks = jax.random.split(key, 10)

    def lin(k, fan_in, fan_out):
        return jax.random.normal(k, (n, fan_in, fan_out), dtype) \
            * (fan_in ** -0.5)

    def gain(k, shape):
        return jax.random.uniform(k, shape, dtype, 0.8, 1.2)

    return {
        "embed": jax.random.normal(ks[0], (z["vocab"], d), dtype) * d ** -0.5,
        "attn_norm": gain(ks[1], (n, d)),
        "wq": lin(ks[2], d, z["hq"] * hd),
        "wk": lin(ks[3], d, z["hkv"] * hd),
        "wv": lin(ks[4], d, z["hkv"] * hd),
        "wo": lin(ks[5], z["hq"] * hd, d),
        "mlp_norm": gain(ks[6], (n, d)),
        "w_gate": lin(ks[7], d, z["ff"]),
        "w_up": lin(ks[8], d, z["ff"]),
        "w_down": lin(ks[9], z["ff"], d),
        "final_norm": gain(jax.random.fold_in(key, 99), (d,)),
    }


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """x: (S, H, D); rotate the two halves of each head by position."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(w: dict, tokens, cfg: dict):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    z = dims(cfg)
    hq, hkv, hd, eps = z["hq"], z["hkv"], z["hd"], z["eps"]
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        h = rms_norm(x, lw["attn_norm"], eps)
        q = jnp.matmul(h, lw["wq"], precision=HI).reshape(s, hq, hd)
        k = jnp.matmul(h, lw["wk"], precision=HI).reshape(s, hkv, hd)
        v = jnp.matmul(h, lw["wv"], precision=HI).reshape(s, hkv, hd)
        q, k = rope(q, z["theta"]), rope(k, z["theta"])
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(s, hq * hd)
        x = x + jnp.matmul(o, lw["wo"], precision=HI)
        h = rms_norm(x, lw["mlp_norm"], eps)
        g = jnp.matmul(h, lw["w_gate"], precision=HI)
        u = jnp.matmul(h, lw["w_up"], precision=HI)
        x = x + jnp.matmul(jax.nn.silu(g) * u, lw["w_down"], precision=HI)
        return x, None

    per_layer = {k: v for k, v in w.items() if k not in ("embed", "final_norm")}
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, per_layer)
    return rms_norm(x, w["final_norm"], eps)


def logits(w: dict, h):
    """Tied output head over the true vocabulary: (..., d) -> (..., vocab)."""
    return jnp.matmul(h, w["embed"].T, precision=HI)
