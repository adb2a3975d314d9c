"""AI21 Jamba2-3B — Mamba-1 + attention hybrid, dense (no experts).
[hf: ai21labs/AI21-Jamba2-3B config.json]

28 layers with attention where ``i % attn_layer_period == attn_layer_offset``
(HF ``JambaConfig``: period 14, offset 7), so layers 7 and 21 attend and the
other 26 are Mamba-1 mixers; a gated SiLU MLP of 8192 on every layer
(``num_experts`` 1).  Attention: 20 query heads over 1 KV head of 128, no
positional encoding.  Mamba: d_state 16, d_conv 4, expand 2 (d_inner 5120),
dt_rank 160 = ceil(2560 / 16), with RMSNorms on dt, B and C."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    arch_id="jamba2-3b",
    family="hybrid",
    n_layers=28, d_model=2560, n_heads=20, n_kv_heads=1, head_dim=128,
    d_ff=8192, vocab=65536,
    layout="mmmmmmmammmmmm",       # attention at period position 7 (1:13)
    use_rope=False,                # jamba: no positional encoding in attn
    mamba_inner_norms=True,
    norm="rms", activation="silu", ffn_kind="gated", tie_embeddings=True,
    notes="serves on the ragged tick: Mamba state and the int8 paged KV "
          "pool in one slot",
)
