"""Tiny stand-ins of the cells, for CPU tests: the registry's smoke models
(the same families at d_model 64), a 4-slot scheduler and a short mix."""
from __future__ import annotations

import copy

import harness

SMOLLM = {"hidden_size": 64, "intermediate_size": 128,
          "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 503}
MAMBA = {"hidden_size": 64, "intermediate_size": 128, "state_size": 16,
         "num_hidden_layers": 2, "conv_kernel": 4, "time_step_rank": 4,
         "vocab_size": 503}
SERVING = {"slots": 4, "max_len": 256, "chunk_size": 32, "page_size": 16}
MIX = {"prompt": {"median": 40, "sigma": 0.5, "min": 8, "max": 100},
       "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 24},
       "preroll_ticks": 3, "horizon_ticks": 5000}


def cell(workload: str, limit=None) -> dict:
    """``harness.resolve(workload)`` cut to CPU size."""
    c = copy.deepcopy(harness.resolve(workload))
    cfg = c["config"]
    small = SMOLLM if cfg["reference"] == "dense_gqa" else MAMBA
    cfg["published"].update(small)
    cfg["program"]["arch"] += "-smoke"
    cfg["serving"].update(SERVING)
    if limit is not None:
        cfg["correct"]["logit_gap_max"] = limit
    c["traffic"].update(copy.deepcopy(MIX))
    return c


def fake_device(chips: int) -> dict:
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips,
            "peak": harness.load_json(harness.BENCH / "peaks.json")
            ["devices"]["TPU v5 lite"]}
