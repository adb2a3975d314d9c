"""The program's side of ``reference/mamba1.py``: which registry model
serves this family, where each reference weight goes in its parameter tree,
and the shapes of the work a served token costs (for ``work.py``)."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.models.registry import get_config
from repro.nn.ssm import Mamba


def dims(pub: dict) -> dict:
    return {"d": pub["hidden_size"], "di": pub["intermediate_size"],
            "n": pub["state_size"], "k": pub["conv_kernel"],
            "r": pub["time_step_rank"], "layers": pub["num_hidden_layers"],
            "vocab": pub["vocab_size"]}


def arch(cfg: dict):
    """The registry config with the file's overrides, checked against the
    published sizes: the program serves exactly what the file states."""
    prog = cfg["program"]
    a = dataclasses.replace(get_config(prog["arch"]),
                            **prog.get("overrides", {}))
    m = Mamba(a.d_model)
    got = {"d": a.d_model, "di": m._di, "n": m.d_state, "k": m.d_conv,
           "r": m._dtr, "layers": a.n_layers, "vocab": a.vocab}
    want = dims(cfg["published"])
    if got != want or set(a.layout) != {"m"} or a.ffn_kind != "none" \
            or a.tie_embeddings != cfg["published"]["tie_word_embeddings"]:
        raise ValueError(f"program config {a.arch_id} serves {got} "
                         f"(ffn {a.ffn_kind}), the published model is {want} "
                         f"with no MLP")
    return a


def program_params(w: dict, vocab_padded: int) -> dict:
    """Reference weights -> the program's scanned-stack parameter tree."""
    table = jnp.pad(w["embed"], ((0, vocab_padded - w["embed"].shape[0]),
                                 (0, 0)))
    mixer = {"in_proj": {"kernel": w["in_proj"]},
             "conv": {"kernel": w["conv_w"][:, :, None, :],
                      "bias": w["conv_b"]},
             "x_proj": {"kernel": w["x_proj"]},
             "dt_proj": {"kernel": w["dt_w"], "bias": w["dt_b"]},
             "ssm": {"a_log": w["a_log"], "d_skip": w["d_skip"]},
             "out_proj": {"kernel": w["out_proj"]}}
    return {"embed": {"table": table},
            "stack": {"body": [{"norm1": {"scale": w["norm"]},
                                "mixer": mixer}]},
            "final_norm": {"scale": w["final_norm"]}}


def layer_matmuls(pub: dict) -> list:
    """(K, N) of every per-layer projection that runs as ``wq_matmul``
    (the program keeps ``dt_proj`` in float: its path is never quantized)."""
    z = dims(pub)
    d, di, r, n = z["d"], z["di"], z["r"], z["n"]
    return [(d, 2 * di), (di, r + 2 * n), (di, d)]


def attention(pub: dict):
    """No KV-cache attention in this family."""
    return None


def token_flops(pub: dict, ctx: int) -> float:
    """Model FLOPs of one token (head excluded; no context dependence).

    Projections 2*K*N each (dt_proj included), the depthwise conv 2*di*k,
    and the selective scan 7*di*n: dt*A, exp, the state multiply and add,
    dt*x*B (two multiplies), and the C contraction's multiply-add counted
    as two.
    """
    z = dims(pub)
    d, di, n, k, r = z["d"], z["di"], z["n"], z["k"], z["r"]
    mm = sum(a * b for a, b in layer_matmuls(pub)) + r * di
    return z["layers"] * (2.0 * mm + 2.0 * di * k + 7.0 * di * n)
