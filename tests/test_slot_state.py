"""Per-slot decode-state adapters (serve/slot_state.py): SSM/RWKV recurrent
state and EncDec cached cross-attention serve through the same
continuous-batching loop as KV caches, token-identical to their lockstep
baselines; the PagedKVState wrap keeps the paged/shared/oversubscribed
workloads byte-identical to the pre-refactor scheduler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_config
from repro.nn.module import eval_context
from repro.serve import (Request, ServeEngine, state_bytes_per_slot,
                         state_kinds)


@pytest.fixture(scope="module")
def mamba_lm():
    cfg = get_config("mamba-130m-smoke")
    model = cfg.build(dtype=jnp.float32, remat="off")
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def rwkv_lm():
    cfg = get_config("rwkv6-7b-smoke")
    model = cfg.build(dtype=jnp.float32, remat="off")
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def whisper():
    cfg = get_config("whisper-tiny-smoke")
    model = cfg.build(dtype=jnp.float32, remat="off")
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, **kw):
    kw.setdefault("max_len", 32)
    kw.setdefault("batch_slots", 2)
    return ServeEngine(model=model, params=params, **kw)


# --------------------------------------------------------------------------
# state_kinds: the adapter factory sees the right cache taxonomy
# --------------------------------------------------------------------------

def test_state_kinds_by_family(mamba_lm, whisper):
    causal = get_config("smollm-135m-smoke").build(dtype=jnp.float32,
                                                   remat="off")
    assert state_kinds(causal) == ("kv",)
    assert state_kinds(mamba_lm[1]) == ("recurrent",)
    assert state_kinds(whisper[1]) == ("kv", "cross")
    hybrid = get_config("jamba-v0.1-52b-smoke").build(dtype=jnp.float32,
                                                      remat="off")
    assert state_kinds(hybrid) == ("kv", "recurrent")


def test_recurrent_bytes_per_slot_constant_in_length(mamba_lm):
    """The paper-motivating property: SSM decode state is O(1) per slot
    while a transformer's KV cache grows linearly with max_len."""
    cfg, model, params = mamba_lm
    short = model.init_cache(2, 32, per_slot_len=True, kv_dtype=jnp.float32)
    long = model.init_cache(2, 64, per_slot_len=True, kv_dtype=jnp.float32)
    b_short = state_bytes_per_slot(short, 2)
    b_long = state_bytes_per_slot(long, 2)
    assert b_short["kv"] == b_long["kv"] == 0
    assert b_short["recurrent"] == b_long["recurrent"] > 0

    tcfg = get_config("smollm-135m-smoke")
    tmodel = tcfg.build(dtype=jnp.float32, remat="off")
    kv_short = state_bytes_per_slot(
        tmodel.init_cache(2, 32, per_slot_len=True, kv_dtype=jnp.float32), 2)
    kv_long = state_bytes_per_slot(
        tmodel.init_cache(2, 64, per_slot_len=True, kv_dtype=jnp.float32), 2)
    # ~2x (the constant per-slot ``len`` word keeps it just shy of exact)
    assert kv_long["kv"] > 1.9 * kv_short["kv"] > 0
    assert kv_short["recurrent"] == 0


# --------------------------------------------------------------------------
# SSM/RWKV serving: token identity with lockstep generate()
# --------------------------------------------------------------------------

@pytest.mark.parametrize("weight_quant", [False, True], ids=["fp32", "int8w"])
def test_ssm_serving_token_identical_to_lockstep(mamba_lm, weight_quant):
    """A mixed mamba workload (staggered arrivals, more requests than slots)
    through the chunked loop equals per-request lockstep generate()."""
    cfg, model, params = mamba_lm
    eng = _engine(model, params, weight_quant=weight_quant)
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, cfg.vocab, size=(4, 8), dtype=np.int32)
    base = np.asarray(
        _engine(model, params, batch_slots=4,
                weight_quant=weight_quant).generate(jnp.asarray(prompts), 6))
    reqs = [Request(rid=i, prompt=prompts[i], max_new=6, arrival=i)
            for i in range(4)]
    results, stats = eng.scheduler(chunk_size=4).run(reqs)
    assert stats.state_kinds == "recurrent"
    for i in range(4):
        assert results[i].status == "ok"
        assert results[i].tokens == [int(x) for x in base[i]], (weight_quant,
                                                                i)


def test_rwkv_serving_token_identical_to_lockstep(rwkv_lm):
    cfg, model, params = rwkv_lm
    eng = _engine(model, params)
    rng = np.random.default_rng(4)
    prompts = rng.integers(0, cfg.vocab, size=(2, 8), dtype=np.int32)
    base = np.asarray(eng.generate(jnp.asarray(prompts), 6))
    reqs = [Request(rid=i, prompt=prompts[i], max_new=6) for i in range(2)]
    results, stats = eng.scheduler(chunk_size=4).run(reqs)
    assert stats.state_kinds == "recurrent"
    for i in range(2):
        assert results[i].tokens == [int(x) for x in base[i]], i


def test_ssm_one_shot_admission_matches_chunked(mamba_lm):
    """One-shot (stop-the-world batch-1 prefill) admission carries the
    recurrence through ``_slot_prefill`` + the scatter-admission walker."""
    cfg, model, params = mamba_lm
    eng = _engine(model, params)
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=6 + i),
                    max_new=5) for i in range(3)]
    chunked, _ = eng.scheduler(chunk_size=3).run(reqs)
    one_shot, _ = eng.scheduler().run(reqs)
    for i in range(3):
        assert one_shot[i].tokens == chunked[i].tokens, i


def test_ssm_eos_evicts_and_readmits(mamba_lm):
    """EOS eviction zeroes the slot's recurrent rows; the readmitted request
    must decode from fresh state, not the dead occupant's."""
    cfg, model, params = mamba_lm
    eng = _engine(model, params, batch_slots=1)
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab
    free_run, _ = eng.scheduler(chunk_size=3).run(
        [Request(rid=0, prompt=prompt, max_new=8)])
    solo, _ = eng.scheduler(chunk_size=3).run(
        [Request(rid=1, prompt=prompt + 1, max_new=3)])
    # an EOS within request 0's first 3 tokens that request 1 never emits,
    # so request 1's stream must come back whole
    eos = next((x for x in free_run[0].tokens[:3]
                if x not in solo[1].tokens), None)
    assert eos is not None, (free_run[0].tokens, solo[1].tokens)

    reqs = [Request(rid=0, prompt=prompt, max_new=8),
            Request(rid=1, prompt=prompt + 1, max_new=3)]
    results, _ = eng.scheduler(eos_id=eos, chunk_size=3, audit=True).run(reqs)
    assert results[0].eos is True and results[0].tokens[-1] == eos
    assert len(results[0].tokens) <= 3
    assert results[1].admitted_at >= results[0].finished_at
    # the slot's state was wiped between occupants: request 1's stream is
    # exactly its solo stream
    assert results[1].tokens == solo[1].tokens


def test_ssm_forced_preemption_recompute_identity(mamba_lm):
    """The ``preempts=`` drill mid-decode: the victim's recurrence is
    discarded, its continuation re-prefills prompt+tokens from zeros, and
    under greedy decoding the stream is unchanged."""
    cfg, model, params = mamba_lm
    eng = _engine(model, params)
    rng = np.random.default_rng(9)
    prompts = rng.integers(0, cfg.vocab, size=(2, 8), dtype=np.int32)
    base = np.asarray(eng.generate(jnp.asarray(prompts), 8))
    reqs = [Request(rid=i, prompt=prompts[i], max_new=8) for i in range(2)]
    results, stats = eng.scheduler(chunk_size=4, audit=True).run(
        reqs, preempts={0: 6})
    assert stats.preemptions >= 1
    assert stats.preempted_rids.get(0, 0) >= 1
    for i in range(2):
        assert results[i].status == "ok"
        assert results[i].tokens == [int(x) for x in base[i]], i
    assert stats.audited_ticks > 0


# --------------------------------------------------------------------------
# Unsupported recurrent combinations fail loudly at construction
# --------------------------------------------------------------------------

def test_recurrent_validation_ladder(mamba_lm, rwkv_lm):
    cfg, model, params = mamba_lm
    eng = _engine(model, params)
    # Mamba rides the ragged tick; RWKV has no ragged path
    assert eng.scheduler(chunk_size=4, ragged=True).ragged
    rwkv = _engine(rwkv_lm[1], rwkv_lm[2])
    with pytest.raises(ValueError, match="ragged"):
        rwkv.scheduler(chunk_size=4, ragged=True)
    with pytest.raises(ValueError, match="prompt_bucket"):
        eng.scheduler(prompt_bucket=8)
    paged = _engine(model, params, paged_kv=True, page_size=8)
    with pytest.raises(ValueError, match="paged"):
        paged.scheduler(chunk_size=4)


# --------------------------------------------------------------------------
# EncDec: cached cross-attention K/V == per-step recomputation
# --------------------------------------------------------------------------

def _encode(model, params, seed, s_enc=6):
    embeds = 0.1 * jax.random.normal(jax.random.PRNGKey(seed),
                                     (1, s_enc, model.d_model), jnp.float32)
    return model.encode(params, embeds, eval_context())


def test_encdec_cached_cross_logits_identical(whisper):
    """Decode-step logits with the admission-time xkv cache equal the
    recompute-from-enc path bit-for-bit shape-for-shape (same projections,
    applied once vs every step)."""
    cfg, model, params = whisper
    ctx = eval_context()
    encs = [_encode(model, params, seed) for seed in (11, 22)]
    enc = jnp.concatenate(encs, axis=0)
    kw = dict(quantized_kv=False, kv_dtype=jnp.float32, per_slot_len=True)
    cached = model.init_cache(2, 16, cross_attn_cache=True, **kw)
    plain = model.init_cache(2, 16, cross_attn_cache=False, **kw)
    for slot in range(2):
        cached = model.write_cross_kv(params, cached, encs[slot],
                                      jnp.int32(slot), ctx)
    toks = (np.arange(2 * 5, dtype=np.int32).reshape(2, 5) * 3) % cfg.vocab
    for i in range(5):
        step = jnp.asarray(toks[:, i:i + 1])
        lg_c, cached = model.apply(params, step, ctx, cache=cached,
                                   decode=True, enc=enc)
        lg_p, plain = model.apply(params, step, ctx, cache=plain,
                                  decode=True, enc=enc)
        np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_p),
                                   rtol=1e-5, atol=1e-5)


def test_encdec_serving_identical_with_and_without_cache(whisper):
    """The served token streams agree across ``cross_attn_cache`` on/off —
    the cache is a FLOPs cut, not a semantics change."""
    cfg, model, params = whisper
    rng = np.random.default_rng(5)
    encs = [_encode(model, params, 30 + i) for i in range(3)]
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=4 + i),
                    max_new=5, arrival=i, enc=encs[i]) for i in range(3)]
    on = _engine(model, params, max_len=24)
    off = _engine(model, params, max_len=24, cross_attn_cache=False)
    got_on, st_on = on.scheduler(chunk_size=4).run(reqs)
    got_off, st_off = off.scheduler(chunk_size=4).run(reqs)
    assert st_on.state_kinds == "kv+cross"
    assert st_off.state_kinds == "kv"
    for i in range(3):
        assert got_on[i].tokens == got_off[i].tokens, i


def test_encdec_cached_audit_clean(whisper):
    """audit=True drives check_cross_lens every tick over live + lane slots."""
    cfg, model, params = whisper
    rng = np.random.default_rng(6)
    encs = [_encode(model, params, 40 + i, s_enc=5) for i in range(3)]
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=5),
                    max_new=4, arrival=i, enc=encs[i]) for i in range(3)]
    eng = _engine(model, params, max_len=24)
    got, stats = eng.scheduler(chunk_size=3, audit=True).run(reqs)
    assert stats.audited_ticks > 0
    assert all(got[i].status == "ok" for i in range(3))


# --------------------------------------------------------------------------
# PagedKVState: the mechanical wrap keeps the paged workloads identical
# --------------------------------------------------------------------------

def test_paged_shared_oversubscribed_identity():
    """Shared-prefix + oversubscribed paged serving (the pre-refactor
    oracle workload) still equals the dense chunked run token-for-token."""
    cfg = get_config("smollm-135m-smoke")
    model = cfg.build(dtype=jnp.float32, remat="off")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab, size=8, dtype=np.int32)
    reqs = []
    for i in range(4):
        tail = rng.integers(0, cfg.vocab, size=4, dtype=np.int32)
        reqs.append(Request(rid=i, prompt=np.concatenate([shared, tail]),
                            max_new=6, arrival=i))
    dense = ServeEngine(model=model, params=params, max_len=32,
                        batch_slots=2)
    base, _ = dense.scheduler(chunk_size=4).run(reqs)
    paged = ServeEngine(model=model, params=params, max_len=32,
                        batch_slots=2, paged_kv=True, page_size=4,
                        kv_pool_pages=12)
    got, stats = paged.scheduler(chunk_size=4, oversubscribe=True,
                                 audit=True).run(reqs)
    assert stats.state_kinds == "kv"
    for i in range(4):
        assert got[i].status == "ok"
        assert got[i].tokens == base[i].tokens, i
    assert stats.prefix_hits > 0
    assert stats.audited_ticks > 0
