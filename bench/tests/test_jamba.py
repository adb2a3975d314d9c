"""The jamba cell at its own tiny size on the CPU: the plain reference
against the program's forward (uncached, and prefill then cached decode),
a whole run of the cell, and its three readers on a hand-built run.

The tiny hybrid is the registry's ``jamba2-3b-smoke``: one whole period of
14 layers (attention at layer 7), d_model 64, 4 query heads over 1 KV head
of 16, d_state 16, dt_rank 4.  Its ``correct`` limit is set for this size
as the cells' limits are for theirs: sound runs read 0.79-1.42, the same
runs with float weights 0.17, the control (int4 weights) 5.68 (CPU), so
3.0 lies between them."""
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import tiny
from repro.core.policy import QuantPolicy
from repro.nn.module import Context

CELL = "jamba2-doc-mooncake"
JAMBA = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 14,
         "num_attention_heads": 4, "num_key_value_heads": 1,
         "mamba_dt_rank": 4, "vocab_size": 503}


LIMIT = 3.0


def cell(limit=LIMIT) -> dict:
    c = copy.deepcopy(harness.resolve(CELL))
    cfg = c["config"]
    cfg["published"].update(JAMBA)
    cfg["program"]["arch"] += "-smoke"
    cfg["serving"].update(tiny.SERVING)
    cfg["correct"]["logit_gap_max"] = limit
    c["traffic"].update(copy.deepcopy(tiny.MIX))
    return c


def as_handed_over(w, adapter):
    """The reference's weights with the matrices the adapter hands over in
    bfloat16 rounded the same way, so both sides read the same values."""
    def rnd(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    return dict(w, embed=rnd(w["embed"]),
                runs=[{k: rnd(v) if k in adapter._INT8 else v
                       for k, v in rw.items()} for rw in w["runs"]])


@pytest.fixture(scope="module")
def parts():
    cfg = cell()["config"]
    pub = cfg["published"]
    ref = harness.load_module(harness.BENCH / "reference" / "jamba.py")
    adapter = harness.load_module(harness.BENCH / "adapters" / "jamba.py")
    model = adapter.arch(cfg).build(dtype=jnp.float32, remat="off")
    w = ref.init_weights(jax.random.PRNGKey(7), pub)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        adapter.program_params(w, model.vocab_padded))
    return pub, ref, adapter, model, w, params


def test_published_copy_matches_the_file_top_level():
    """The file holds the published config twice: every key at the top
    level, as the model's ``config.json`` has it, and under ``published``,
    where the harness reads it.  The two copies agree key for key."""
    cfg = harness.resolve(CELL)["config"]
    pub = cfg["published"]
    assert {k: cfg.get(k, "missing") for k in pub} == pub


def test_reference_layer_order():
    ref = harness.load_module(harness.BENCH / "reference" / "jamba.py")
    pub = harness.resolve(CELL)["config"]["published"]
    kinds = ref.kinds(pub)
    assert [i for i, k in enumerate(kinds) if k == "a"] == [7, 21]
    assert len(kinds) == 28 and kinds.count("m") == 26
    assert [r[0] for r in ref.runs(pub)] == ["m", "a", "m", "a", "m"]


def test_reference_matches_program_forward(parts):
    """Same seeded weights, no cache, float32: the logits agree within
    1e-4 (float32 rounding over 14 layers is about 1e-6)."""
    pub, ref, adapter, model, w, params = parts
    w = as_handed_over(w, adapter)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 503, 40),
                         jnp.int32)
    want = ref.logits(w, ref.hidden(w, tokens, pub))
    ctx = Context(policy=QuantPolicy.float32(), train=False)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(params, tokens[None], ctx)
    np.testing.assert_allclose(np.asarray(got[0, :, :503]),
                               np.asarray(want), atol=1e-4, rtol=1e-4)


def test_prefill_then_cached_decode_matches_reference(parts):
    """Prefill 24 tokens into the cache, then decode 8 one at a time: every
    position's logits agree with the reference's one full forward."""
    pub, ref, adapter, model, w, params = parts
    w = as_handed_over(w, adapter)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 503, 32),
                         jnp.int32)
    want = np.asarray(ref.logits(w, ref.hidden(w, tokens, pub)))
    ctx = Context(policy=QuantPolicy.float32(), train=False)
    cache = model.init_cache(1, 64, quantized_kv=False,
                             kv_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        lg, cache = model.apply(params, tokens[None, :24], ctx, cache=cache,
                                decode=True)
        got = [np.asarray(lg[0, :, :503])]
        for i in range(24, 32):
            lg, cache = model.apply(params, tokens[None, i:i + 1], ctx,
                                    cache=cache, decode=True)
            got.append(np.asarray(lg[0, :, :503]))
    np.testing.assert_allclose(np.concatenate(got), want, atol=1e-4,
                               rtol=1e-4)


def test_tiny_run_of_the_cell_is_correct():
    out = harness.run(CELL, 2 ** 40 + 17, 2.0, False,
                      t_start=time.perf_counter(), check=tiny.fake_device,
                      resolved=cell())
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_readers_on_a_hand_built_run(parts):
    """Each of the cell's readers gives a finite, positive share on a
    synthetic run: two traced ticks, one of decode rows at long contexts
    and one with a prefill chunk."""
    pub, _, adapter, _, _, _ = parts
    cfg = harness.resolve(CELL)["config"]
    rows = [[(s, 8000 + s) for s in range(11)],
            [(s, 9000 + s) for s in range(11)]
            + [(20, p) for p in range(256, 512)]]
    rn = harness.Run(cfg=cfg, adapter=adapter, peak=tiny.fake_device(1)
                     ["peak"], timeline=[], top={0: 0.0}, t_open=0,
                     t_close=2,
                     trace={"window_s": 0.4, "busy_s": 0.38,
                            "kernel_s": {"qragged_attn": 0.05,
                                         "wq_matmul": 0.2}},
                     rows=rows, sampled=[11, 12])
    for m in harness.resolve(CELL)["per_layer"]:
        v = harness.load_module(harness.BENCH / "metrics"
                                / f"{m['name']}.py").read(rn)
        assert v is not None and np.isfinite(v) and 0 < v, m["name"]
