"""The program's side of ``reference/jamba.py``: which registry model serves
this family, where each reference weight goes in its parameter tree, and
the shapes of the work a served token costs (for ``work.py``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.registry import get_config


def _ref():
    import harness

    return harness.load_module(harness.BENCH / "reference" / "jamba.py")


def dims(pub: dict) -> dict:
    return _ref().dims(pub)


def layer_counts(pub: dict) -> dict:
    """Layers of each kind: ``{"a": attention, "m": Mamba}``."""
    kinds = _ref().kinds(pub)
    return {"a": kinds.count("a"), "m": kinds.count("m")}


def arch(cfg: dict):
    """The registry config, checked key by key against the published
    config: the program serves exactly what the file states."""
    pub = cfg["published"]
    a = get_config(cfg["program"]["arch"])
    z = dims(pub)
    n_per = a.n_layers // len(a.layout)
    mixers = [a._block(p, ch, jnp.float32)._mixer()
              for p, ch in enumerate(a.layout) if ch == "m"]
    m = mixers[0]
    got = {
        "model_type": "jamba", "hidden_size": a.d_model,
        "num_hidden_layers": a.n_layers, "num_attention_heads": a.n_heads,
        "num_key_value_heads": a.n_kv_heads, "head_dim": a.head_dim,
        "intermediate_size": a.d_ff, "vocab_size": a.vocab,
        "layer_kinds": a.layout * n_per,
        "mamba_d_state": m.d_state, "mamba_d_conv": m.d_conv,
        "mamba_expand": m._di // a.d_model, "mamba_dt_rank": m._dtr,
        "mamba_inner_norms": m.inner_norms,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "num_experts": max(1, a.n_experts), "positional": a.use_rope,
        "hidden_act": a.activation, "ffn": a.ffn_kind,
        "rms_norm_eps": 1e-6 if a.norm == "rms" else None,
        "tie_word_embeddings": a.tie_embeddings, "sliding_window": None,
    }
    want = {
        "model_type": pub["model_type"], "hidden_size": z["d"],
        "num_hidden_layers": z["layers"], "num_attention_heads": z["hq"],
        "num_key_value_heads": z["hkv"], "head_dim": z["hd"],
        "intermediate_size": z["ff"], "vocab_size": z["vocab"],
        "layer_kinds": _ref().kinds(pub),
        "mamba_d_state": z["n"], "mamba_d_conv": z["k"],
        "mamba_expand": pub["mamba_expand"], "mamba_dt_rank": z["r"],
        "mamba_inner_norms": True,
        "mamba_conv_bias": pub["mamba_conv_bias"],
        "mamba_proj_bias": pub["mamba_proj_bias"],
        "num_experts": pub["num_experts"], "positional": False,
        "hidden_act": pub["hidden_act"], "ffn": "gated",
        "rms_norm_eps": pub["rms_norm_eps"],
        "tie_word_embeddings": pub["tie_word_embeddings"],
        "sliding_window": pub["sliding_window"],
    }
    wrong = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if wrong or any(x != m for x in mixers) \
            or len(a.layout) * n_per != a.n_layers:
        raise ValueError(f"program config {a.arch_id} differs from the "
                         f"published model: {wrong} (program, published)")
    return a


# matrices the program stores as int8: every projection but dt_proj, and
# the embedding (core/integerize.py integerize_weights_only)
_INT8 = ("in_proj", "x_proj", "out_proj", "conv_w", "wq", "wk", "wv", "wo",
         "w_gate", "w_up", "w_down")


def program_params(w: dict, vocab_padded: int) -> dict:
    """Reference weights -> the program's scanned-stack parameter tree.

    The matrices the program quantizes to int8 are handed over in bfloat16,
    as a deployment quantizes its bfloat16 checkpoint; everything it keeps
    in float (norm gains, dt_proj, A, D, biases) stays float32.
    """
    layers = []
    for rw in w["runs"]:
        for j in range(next(iter(rw.values())).shape[0]):
            lw = {k: v[j].astype(jnp.bfloat16) if k in _INT8 else v[j]
                  for k, v in rw.items()}
            layers.append(_block(lw))
    kinds = "".join("a" if "wq" in b["mixer"] else "m" for b in layers)
    period = next(p for p in range(1, len(kinds) + 1)
                  if len(kinds) % p == 0
                  and kinds == kinds[:p] * (len(kinds) // p))
    n_per = len(kinds) // period
    if n_per > 1:
        body = [jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[layers[q * period + p] for q in range(n_per)])
            for p in range(period)]
    else:
        body = layers
    table = jnp.pad(w["embed"], ((0, vocab_padded - w["embed"].shape[0]),
                                 (0, 0))).astype(jnp.bfloat16)
    return {"embed": {"table": table}, "stack": {"body": body},
            "final_norm": {"scale": w["final_norm"]}}


def _block(lw: dict) -> dict:
    """One layer's reference weights -> one program Block's parameters."""
    b = {"norm1": {"scale": lw["norm1"]}, "norm2": {"scale": lw["norm2"]},
         "ffn": {"w_gate": {"kernel": lw["w_gate"]},
                 "w_in": {"kernel": lw["w_up"]},
                 "w_out": {"kernel": lw["w_down"]}}}
    if "wq" in lw:
        b["mixer"] = {k: {"kernel": lw[k]} for k in ("wq", "wk", "wv", "wo")}
        return b
    b["mixer"] = {
        "in_proj": {"kernel": lw["in_proj"]},
        "conv": {"kernel": lw["conv_w"][:, None, :], "bias": lw["conv_b"]},
        "x_proj": {"kernel": lw["x_proj"]},
        "dt_proj": {"kernel": lw["dt_w"], "bias": lw["dt_b"]},
        "ssm": {"a_log": lw["a_log"], "d_skip": lw["d_skip"]},
        "out_proj": {"kernel": lw["out_proj"]},
        "dt_norm": {"scale": lw["dt_norm"]},
        "b_norm": {"scale": lw["b_norm"]},
        "c_norm": {"scale": lw["c_norm"]}}
    return b


def layer_matmuls(pub: dict) -> list:
    """(K, N) of every per-layer projection that runs as ``wq_matmul``, one
    entry per shape of any layer (``wq_matmul_shapes`` gives how many
    layers hold each)."""
    return [kn for kn, _ in wq_matmul_shapes(pub)]


def wq_matmul_shapes(pub: dict) -> list:
    """[((K, N), layers that hold it)] of every int8 projection: the Mamba
    mixer's in_proj, x_proj and out_proj (dt_proj stays float), the
    attention's q, k, v and o, and the MLP of every layer."""
    z = dims(pub)
    n = layer_counts(pub)
    d, di, r, s = z["d"], z["di"], z["r"], z["n"]
    q, kv, ff = z["hq"] * z["hd"], z["hkv"] * z["hd"], z["ff"]
    return [((d, 2 * di), n["m"]), ((di, r + 2 * s), n["m"]),
            ((di, d), n["m"]),
            ((d, q), n["a"]), ((d, kv), n["a"]), ((d, kv), n["a"]),
            ((q, d), n["a"]),
            ((d, ff), n["a"] + n["m"]), ((d, ff), n["a"] + n["m"]),
            ((ff, d), n["a"] + n["m"])]


def attention(pub: dict):
    """(layers, query heads, kv heads, head dim) of the KV-cache attention."""
    z = dims(pub)
    return layer_counts(pub)["a"], z["hq"], z["hkv"], z["hd"]


def token_flops(pub: dict, ctx: int) -> float:
    """Model FLOPs of one token at context length ``ctx`` (head excluded).

    Every projection 2*K*N (dt_proj included); per Mamba layer the
    depthwise conv 2*di*k and the selective scan 7*di*n (dt*A, exp, the
    state multiply and add, dt*x*B, the C contraction as two); per
    attention layer QK^T and PV over ``ctx`` positions, 4*hq*hd*ctx.
    """
    z = dims(pub)
    n = layer_counts(pub)
    di = z["di"]
    mm = sum(k * nn * c for (k, nn), c in wq_matmul_shapes(pub)) \
        + n["m"] * z["r"] * di
    return 2.0 * mm + n["m"] * (2.0 * di * z["k"] + 7.0 * di * z["n"]) \
        + n["a"] * 4.0 * z["hq"] * z["hd"] * ctx
