"""Plain float32 reference: the Jamba hybrid language model (AI21 Jamba,
arXiv:2403.19887), as HF ``JambaForCausalLM`` computes it with one expert.

Written from the published description and the keys of
``ai21labs/AI21-Jamba2-3B``'s ``config.json``: token embedding,
``num_hidden_layers`` residual layers, a final RMSNorm and the tied
embedding as the output head.  Layer ``i`` mixes with attention where
``i % attn_layer_period == attn_layer_offset`` and with a Mamba-1 mixer
everywhere else (HF ``JambaConfig.layers_block_type``); every layer then
runs a SiLU-gated MLP of ``intermediate_size`` (``num_experts`` 1: no
router).  Each sub-layer is pre-norm: ``x + mixer(rms(x))``, then
``x + mlp(rms(x))``.

Attention: ``num_attention_heads`` query heads over ``num_key_value_heads``
shared K/V heads of ``hidden_size / num_attention_heads``, causal softmax,
no positional encoding, no bias.  Computed in blocks of ``QUERY_BLOCK``
queries, so a 17k-token sequence never holds its whole score matrix.

The Mamba mixer (HF ``JambaMambaMixer``), per sequence of length S:

    x, z      = split(in_proj(h))                      (S, di) each
    x         = silu(causal depthwise conv1d(x) + b)   kernel mamba_d_conv
    dt, B, C  = split(x_proj(x))                       (S, R), (S, N), (S, N)
    dt, B, C  = rms(dt), rms(B), rms(C)                dt/b/c_layernorm
    dt        = softplus(dt_proj(dt) + dt_bias)        (S, di)
    A         = -exp(A_log)                            (di, N)
    h_t       = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t (di, N), h_0 = 0
    y_t       = h_t C_t + D * x_t
    out       = out_proj(y * silu(z))

with ``di = mamba_expand * hidden_size``, N = ``mamba_d_state``, R =
``mamba_dt_rank``.  The recurrence is a ``lax.scan`` over time that carries
one (di, N) state; no (S, di, N) tensor is ever formed.

Everything is float32 under ``jax.default_matmul_precision("highest")``,
each product also at ``Precision.HIGHEST``: on a TPU a float32 matmul
otherwise runs as one bfloat16 pass.  No kernel, cache or batching.  The
position-wise parts (projections, norms, the MLP, the output head) run
``ROW_BLOCK`` rows at a time (the head ``HEAD_BLOCK``): the same arithmetic,
but the TPU compiler spends seconds on each large float32 product at
``HIGHEST`` (about 7 s for one 1024 x 2560 x 2560; 47 s for the whole
forward unblocked), the comparison compiles once per sequence length, and
the blocks keep a 17k-token forward's temporaries small.

Departures from the published model: none in the mathematics.  The weights
are random (``init_weights``), drawn from the seed: LeCun-normal
projections, embedding std 1/sqrt(hidden_size), the Mamba-1 init for A
(S4D-real), D (ones), dt bias (softplus^-1 of a step log-uniform in
[0.001, 0.1], floor 1e-4) and the conv (PyTorch default), RMSNorm gains
uniform in [0.8, 1.2] so that a path that ignores a gain shows.  The
weights are grouped in runs of consecutive layers of one kind, each run
stacked over its layers and scanned.  This module imports nothing of the
program under test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
ROW_BLOCK = 512
HEAD_BLOCK = 128
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


def dims(cfg: dict) -> dict:
    """The sizes the forward needs, read from the published keys."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    r = cfg["mamba_dt_rank"]
    return {"d": d, "hq": hq, "hkv": cfg["num_key_value_heads"],
            "hd": d // hq, "ff": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "eps": cfg["rms_norm_eps"], "di": cfg["mamba_expand"] * d,
            "n": cfg["mamba_d_state"], "k": cfg["mamba_d_conv"],
            "r": math.ceil(d / 16) if r == "auto" else r}


def kinds(cfg: dict) -> str:
    """One letter per layer: ``a`` attention, ``m`` Mamba."""
    p, o = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return "".join("a" if i % p == o else "m"
                   for i in range(cfg["num_hidden_layers"]))


def runs(cfg: dict) -> list:
    """(kind, first layer, count) of each run of consecutive layers of one
    kind, in layer order."""
    out = []
    for i, c in enumerate(kinds(cfg)):
        if out and out[-1][0] == c:
            out[-1][2] += 1
        else:
            out.append([c, i, 1])
    return [tuple(r) for r in out]


def init_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Random weights from ``key``: ``runs[j]`` holds run j's weights, each
    stacked over the run's layers, ``x @ W`` layout."""
    z = dims(cfg)
    d, di, n, k, r = z["d"], z["di"], z["n"], z["k"], z["r"]

    def run_weights(rk, kind, m):
        ks = jax.random.split(rk, 16)

        def lin(kk, fan_in, fan_out):
            return jax.random.normal(kk, (m, fan_in, fan_out), dtype) \
                * (fan_in ** -0.5)

        def gain(kk, width):
            return jax.random.uniform(kk, (m, width), dtype, 0.8, 1.2)

        w = {"norm1": gain(ks[0], d), "norm2": gain(ks[1], d),
             "w_gate": lin(ks[2], d, z["ff"]), "w_up": lin(ks[3], d, z["ff"]),
             "w_down": lin(ks[4], z["ff"], d)}
        if kind == "a":
            w.update(wq=lin(ks[5], d, z["hq"] * z["hd"]),
                     wk=lin(ks[6], d, z["hkv"] * z["hd"]),
                     wv=lin(ks[7], d, z["hkv"] * z["hd"]),
                     wo=lin(ks[8], z["hq"] * z["hd"], d))
            return w
        dt0 = jnp.exp(jax.random.uniform(ks[9], (m, di), dtype,
                                         math.log(DT_MIN), math.log(DT_MAX)))
        dt0 = jnp.maximum(dt0, DT_FLOOR)
        w.update(
            in_proj=lin(ks[5], d, 2 * di),
            conv_w=jax.random.uniform(ks[6], (m, k, di), dtype,
                                      -k ** -0.5, k ** -0.5),
            conv_b=jax.random.uniform(ks[7], (m, di), dtype,
                                      -k ** -0.5, k ** -0.5),
            x_proj=lin(ks[8], di, r + 2 * n),
            dt_norm=gain(ks[10], r), b_norm=gain(ks[11], n),
            c_norm=gain(ks[12], n),
            dt_w=jax.random.uniform(ks[13], (m, r, di), dtype,
                                    -r ** -0.5, r ** -0.5),
            dt_b=dt0 + jnp.log(-jnp.expm1(-dt0)),       # softplus^-1(dt0)
            a_log=jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=dtype)),
                                   (m, di, n)),
            d_skip=jnp.ones((m, di), dtype),
            out_proj=lin(ks[14], di, d))
        return w

    rs = runs(cfg)
    keys = jax.random.split(key, len(rs) + 2)
    return {
        "embed": jax.random.normal(keys[0], (z["vocab"], d), dtype) * d ** -0.5,
        "final_norm": jax.random.uniform(keys[1], (d,), dtype, 0.8, 1.2),
        "runs": [run_weights(keys[j + 2], kind, m)
                 for j, (kind, _, m) in enumerate(rs)],
    }


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rowwise(f, *xs, rows=ROW_BLOCK):
    """``f`` of row-wise arguments (S, ...), ``rows`` rows at a time."""
    s = xs[0].shape[0]
    if s <= rows:
        return f(*xs)
    nb = -(-s // rows)
    blocks = [jnp.pad(x, ((0, nb * rows - s),) + ((0, 0),) * (x.ndim - 1))
              .reshape(nb, rows, *x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda b: f(*b), blocks)
    return jax.tree_util.tree_map(
        lambda o: o.reshape(nb * rows, *o.shape[2:])[:s], out)


def attention(lw: dict, h, z: dict):
    """Causal grouped-query attention over one sequence h (S, d), no
    positional encoding, ``QUERY_BLOCK`` queries at a time."""
    s = h.shape[0]
    hq, hkv, hd = z["hq"], z["hkv"], z["hd"]
    g = hq // hkv
    q, k, v = rowwise(lambda x: (mm(x, lw["wq"]), mm(x, lw["wk"]),
                                 mm(x, lw["wv"])), h)
    q = q.reshape(s, hkv, g, hd) * hd ** -0.5
    k, v = k.reshape(s, hkv, hd), v.reshape(s, hkv, hd)
    blk = min(QUERY_BLOCK, s)
    nb = -(-s // blk)
    q = jnp.pad(q, ((0, nb * blk - s), (0, 0), (0, 0), (0, 0)))
    keys = jnp.arange(s)

    def block(args):
        qb, first = args
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI)
        seen = keys[None, :] <= first + jnp.arange(blk)[:, None]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    o = jax.lax.map(block, (q.reshape(nb, blk, hkv, g, hd),
                            jnp.arange(nb) * blk))
    return rowwise(lambda x: mm(x, lw["wo"]),
                   o.reshape(nb * blk, hq * hd)[:s])


def mamba(lw: dict, h, z: dict):
    """One Jamba Mamba-1 mixer over one sequence h (S, d) -> (S, d)."""
    s = h.shape[0]
    di, n, k, r, eps = z["di"], z["n"], z["k"], z["r"], z["eps"]
    x = rowwise(lambda hb: mm(hb, lw["in_proj"][:, :di]), h)
    xp = jnp.concatenate([jnp.zeros((k - 1, di), x.dtype), x], axis=0)
    conv = sum(lw["conv_w"][j] * xp[j:j + s] for j in range(k))
    x = jax.nn.silu(conv + lw["conv_b"])

    def ssm_inputs(xb):
        dbc = mm(xb, lw["x_proj"])
        dt = rms_norm(dbc[:, :r], lw["dt_norm"], eps)
        bm = rms_norm(dbc[:, r:r + n], lw["b_norm"], eps)
        cm = rms_norm(dbc[:, r + n:], lw["c_norm"], eps)
        return jax.nn.softplus(mm(dt, lw["dt_w"]) + lw["dt_b"]), bm, cm

    dt, bm, cm = rowwise(ssm_inputs, x)
    a = -jnp.exp(lw["a_log"])

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, mm(state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32),
                        (dt, x, bm, cm))

    def out(yb, xb, hb):
        gate = mm(hb, lw["in_proj"][:, di:])
        return mm((yb + lw["d_skip"] * xb) * jax.nn.silu(gate),
                  lw["out_proj"])

    return rowwise(out, y, x, h)


def hidden(w: dict, tokens, cfg: dict):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    z = dims(cfg)
    eps = z["eps"]

    def mlp(lw, x):
        h = rms_norm(x, lw["norm2"], eps)
        u = jax.nn.silu(mm(h, lw["w_gate"])) * mm(h, lw["w_up"])
        return x + mm(u, lw["w_down"])

    def layer(mixer):
        def f(x, lw):
            x = x + mixer(lw, rms_norm(x, lw["norm1"], eps), z)
            return rowwise(lambda xb: mlp(lw, xb), x), None
        return f

    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        for (kind, _, _), rw in zip(runs(cfg), w["runs"]):
            x, _ = jax.lax.scan(layer(attention if kind == "a" else mamba),
                                x, rw)
        return rms_norm(x, w["final_norm"], eps)


def logits(w: dict, h):
    """Tied output head over the true vocabulary: (S, d) -> (S, vocab)."""
    return rowwise(lambda hb: mm(hb, w["embed"].T), h, rows=HEAD_BLOCK)
