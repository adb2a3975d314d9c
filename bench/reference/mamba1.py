"""Plain float32 reference: the Mamba-1 language model (arXiv:2312.00752).

Written from the published description and the keys of
``state-spaces/mamba-130m-hf``'s ``config.json``: token embedding,
``num_hidden_layers`` residual blocks of RMSNorm -> Mamba mixer, a final
RMSNorm and the tied embedding as the output head.  No MLP: a Mamba-1 block
is the mixer alone.

The mixer, per sequence of length S:

    x, z   = split(in_proj(h))                       (S, di) each
    x      = silu(causal depthwise conv1d(x) + b)    kernel conv_kernel
    dt, B, C = split(x_proj(x))                      (S, R), (S, N), (S, N)
    dt     = softplus(dt_proj(dt) + dt_bias)         (S, di)
    A      = -exp(A_log)                             (di, N)
    h_t    = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t  (di, N), h_0 = 0
    y_t    = h_t C_t + D * x_t
    out    = out_proj(y * silu(z))

computed step by step over time, in float32, with every matrix product at
``Precision.HIGHEST``.  Departures from the published model: the residual
stream is float32 here as in the published ``residual_in_fp32``; the weights
are random (``init_weights``), drawn from the seed with the published init
schemes (S4D-real A, dt bias from a log-uniform step in [time_step_min,
time_step_max], PyTorch-default conv init), and RMSNorm gains drawn around
1.  This module imports nothing of the program under test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    """The sizes the forward needs, read from the published keys."""
    d = cfg["hidden_size"]
    return {"d": d, "di": cfg["intermediate_size"], "n": cfg["state_size"],
            "k": cfg["conv_kernel"], "r": cfg["time_step_rank"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "eps": cfg["layer_norm_epsilon"]}


def init_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Random weights from ``key``, stacked over layers, ``x @ W`` layout."""
    z = dims(cfg)
    d, di, n, k, r, L = z["d"], z["di"], z["n"], z["k"], z["r"], z["layers"]
    ks = jax.random.split(key, 10)

    def lin(kk, fan_in, fan_out):
        return jax.random.normal(kk, (L, fan_in, fan_out), dtype) \
            * (fan_in ** -0.5)

    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    dt0 = jnp.exp(jax.random.uniform(ks[6], (L, di), dtype, lo, hi))
    dt0 = jnp.maximum(dt0, cfg["time_step_floor"])
    bound = r ** -0.5 * cfg["time_step_scale"]
    return {
        "embed": jax.random.normal(ks[0], (z["vocab"], d), dtype) * d ** -0.5,
        "norm": jax.random.uniform(ks[1], (L, d), dtype, 0.8, 1.2),
        "in_proj": lin(ks[2], d, 2 * di),
        "conv_w": jax.random.uniform(ks[3], (L, k, di), dtype,
                                     -k ** -0.5, k ** -0.5),
        "conv_b": jax.random.uniform(ks[4], (L, di), dtype,
                                     -k ** -0.5, k ** -0.5),
        "x_proj": lin(ks[5], di, r + 2 * n),
        "dt_w": jax.random.uniform(ks[7], (L, r, di), dtype, -bound, bound),
        "dt_b": dt0 + jnp.log(-jnp.expm1(-dt0)),     # softplus^-1(dt0)
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=dtype)), (L, di, n)),
        "d_skip": jnp.ones((L, di), dtype),
        "out_proj": lin(ks[8], di, d),
        "final_norm": jax.random.uniform(ks[9], (d,), dtype, 0.8, 1.2),
    }


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def mixer(lw: dict, h, z: dict):
    """One Mamba-1 mixer over one sequence h (S, d) -> (S, d)."""
    s = h.shape[0]
    di, n, k, r = z["di"], z["n"], z["k"], z["r"]
    xz = jnp.matmul(h, lw["in_proj"], precision=HI)
    x, gate = xz[:, :di], xz[:, di:]
    xp = jnp.concatenate([jnp.zeros((k - 1, di), x.dtype), x], axis=0)
    conv = sum(lw["conv_w"][j] * xp[j:j + s] for j in range(k))
    x = jax.nn.silu(conv + lw["conv_b"])
    dbc = jnp.matmul(x, lw["x_proj"], precision=HI)
    dt, bm, cm = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    dt = jax.nn.softplus(jnp.matmul(dt, lw["dt_w"], precision=HI) + lw["dt_b"])
    a = -jnp.exp(lw["a_log"])

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, jnp.matmul(state, c_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32),
                        (dt, x, bm, cm))
    y = (y + lw["d_skip"] * x) * jax.nn.silu(gate)
    return jnp.matmul(y, lw["out_proj"], precision=HI)


def hidden(w: dict, tokens, cfg: dict):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    z = dims(cfg)

    def layer(x, lw):
        return x + mixer(lw, rms_norm(x, lw["norm"], z["eps"]), z), None

    per_layer = {k: v for k, v in w.items() if k not in ("embed", "final_norm")}
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, per_layer)
    return rms_norm(x, w["final_norm"], z["eps"])


def logits(w: dict, h):
    """Tied output head over the true vocabulary: (..., d) -> (..., vocab)."""
    return jnp.matmul(h, w["embed"].T, precision=HI)
