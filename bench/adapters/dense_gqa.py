"""The program's side of ``reference/dense_gqa.py``: which registry model
serves this family, where each reference weight goes in its parameter tree,
and the shapes of the work a served token costs (for ``work.py``)."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.models.registry import get_config


def dims(pub: dict) -> dict:
    d = pub["hidden_size"]
    hq = pub["num_attention_heads"]
    return {"d": d, "hq": hq, "hkv": pub["num_key_value_heads"],
            "hd": pub.get("head_dim") or d // hq,
            "ff": pub["intermediate_size"], "layers": pub["num_hidden_layers"],
            "vocab": pub["vocab_size"]}


def arch(cfg: dict):
    """The registry config with the file's overrides, checked against the
    published sizes: the program serves exactly what the file states."""
    prog = cfg["program"]
    a = dataclasses.replace(get_config(prog["arch"]),
                            **prog.get("overrides", {}))
    z = dims(cfg["published"])
    got = {"d": a.d_model, "hq": a.n_heads, "hkv": a.n_kv_heads,
           "hd": a.head_dim, "ff": a.d_ff, "layers": a.n_layers,
           "vocab": a.vocab}
    want = {k: z[k] for k in got}
    if got != want or a.layout != "a" or a.ffn_kind != "gated" \
            or a.tie_embeddings != cfg["published"]["tie_word_embeddings"] \
            or a.rope_theta != float(cfg["published"]["rope_theta"]):
        raise ValueError(f"program config {a.arch_id} serves {got}, the "
                         f"published model is {want}")
    return a


def program_params(w: dict, vocab_padded: int) -> dict:
    """Reference weights -> the program's scanned-stack parameter tree."""
    table = jnp.pad(w["embed"], ((0, vocab_padded - w["embed"].shape[0]),
                                 (0, 0)))
    body = {"norm1": {"scale": w["attn_norm"]},
            "mixer": {k: {"kernel": w[k]} for k in ("wq", "wk", "wv", "wo")},
            "norm2": {"scale": w["mlp_norm"]},
            "ffn": {"w_gate": {"kernel": w["w_gate"]},
                    "w_in": {"kernel": w["w_up"]},
                    "w_out": {"kernel": w["w_down"]}}}
    return {"embed": {"table": table}, "stack": {"body": [body]},
            "final_norm": {"scale": w["final_norm"]}}


def layer_matmuls(pub: dict) -> list:
    """(K, N) of every per-layer projection that runs as ``wq_matmul``."""
    z = dims(pub)
    d, q, kv, ff = z["d"], z["hq"] * z["hd"], z["hkv"] * z["hd"], z["ff"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff), (ff, d)]


def attention(pub: dict):
    """(layers, query heads, kv heads, head dim) of the KV-cache attention."""
    z = dims(pub)
    return z["layers"], z["hq"], z["hkv"], z["hd"]


def token_flops(pub: dict, ctx: int) -> float:
    """Model FLOPs of one token at context length ``ctx`` (head excluded)."""
    z = dims(pub)
    mm = sum(k * n for k, n in layer_matmuls(pub))
    return z["layers"] * (2.0 * mm + 4.0 * z["hq"] * z["hd"] * ctx)
