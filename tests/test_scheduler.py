"""Continuous-batching scheduler tests: token identity vs the lockstep
baseline, queued-request admission into freed slots, EOS eviction mid-stream,
and the per-slot KV cache primitives underneath (fp32 and int8 KV)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_config
from repro.serve import Request, ServeEngine, run_restart_batching


@pytest.fixture(scope="module")
def smoke_lm():
    cfg = get_config("smollm-135m-smoke")
    model = cfg.build(dtype=jnp.float32, remat="off")
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, **kw):
    kw.setdefault("max_len", 32)
    kw.setdefault("batch_slots", 2)
    return ServeEngine(model=model, params=params, **kw)


# --------------------------------------------------------------------------
# Token identity: simultaneous equal-length arrivals == lockstep generate()
# --------------------------------------------------------------------------

@pytest.mark.parametrize("quantized_kv", [False, True],
                         ids=["fp32", "int8kv"])
def test_scheduler_token_identical_to_lockstep(smoke_lm, quantized_kv):
    cfg, model, params = smoke_lm
    eng = _engine(model, params, quantized_kv=quantized_kv)
    prompts = (jnp.arange(2 * 8, dtype=jnp.int32).reshape(2, 8) * 7) % cfg.vocab
    base = np.asarray(eng.generate(prompts, 10))

    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]), max_new=10)
            for i in range(2)]
    results, stats = eng.scheduler().run(reqs)
    for i in range(2):
        assert results[i].tokens == list(base[i]), (quantized_kv, i)
    assert stats.occupancy == 1.0
    assert stats.tokens_out == 20


def test_scheduler_weight_quant_variant_runs(smoke_lm):
    cfg, model, params = smoke_lm
    eng = _engine(model, params, weight_quant=True, quantized_kv=True)
    results, _ = eng.scheduler().run(
        [Request(rid=0, prompt=np.arange(6), max_new=5)])
    assert len(results[0].tokens) == 5
    assert max(results[0].tokens) < cfg.vocab


# --------------------------------------------------------------------------
# Admission into freed slots
# --------------------------------------------------------------------------

def test_queued_requests_admitted_into_freed_slots(smoke_lm):
    cfg, model, params = smoke_lm
    eng = _engine(model, params)
    rng = np.random.default_rng(0)
    # 5 requests, 2 slots, all at t=0: three must wait for a freed slot.
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=8),
                    max_new=4) for i in range(5)]
    results, stats = eng.scheduler().run(reqs)
    assert sorted(results) == list(range(5))
    assert all(len(results[i].tokens) == 4 for i in range(5))
    # first two admitted immediately; the rest only after an eviction
    assert results[0].admitted_at == 0 and results[1].admitted_at == 0
    for i in (2, 3, 4):
        assert results[i].admitted_at >= min(results[0].finished_at,
                                             results[1].finished_at)
    # never more than batch_slots in flight
    live = [(r.admitted_at, r.finished_at) for r in results.values()]
    for t in range(max(f for _, f in live) + 1):
        assert sum(a <= t < f for a, f in live) <= eng.batch_slots


def test_staggered_arrivals_and_prompt_bucketing(smoke_lm):
    cfg, model, params = smoke_lm
    eng = _engine(model, params)
    rng = np.random.default_rng(1)
    # ragged prompt lengths share compiles via bucket=8; arrivals staggered
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=3 + i),
                    max_new=3, arrival=2 * i) for i in range(4)]
    results, _ = eng.scheduler(prompt_bucket=8).run(reqs)
    assert sorted(results) == list(range(4))
    for i in range(4):
        assert len(results[i].tokens) == 3
        assert results[i].admitted_at >= results[i].arrival


# --------------------------------------------------------------------------
# Chunked-prefill admission (the mixed step)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("quantized_kv", [False, True],
                         ids=["fp32", "int8kv"])
@pytest.mark.parametrize("chunk", [4, 7])
def test_chunked_prefill_token_identity(smoke_lm, quantized_kv, chunk):
    """Chunked admission is token-identical to one-shot prefill admission —
    per-slot prompt lengths, staggered arrivals, readmission into freed
    slots, and chunk sizes that do NOT divide the prompt lengths."""
    cfg, model, params = smoke_lm
    eng = _engine(model, params, max_len=48, quantized_kv=quantized_kv)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=5 + 3 * i),
                    max_new=6, arrival=i) for i in range(4)]
    base, _ = eng.scheduler().run(reqs)
    got, stats = eng.scheduler(chunk_size=chunk).run(reqs)
    for i in range(4):
        assert got[i].tokens == base[i].tokens, (quantized_kv, chunk, i)
    # every prompt was really chunked: sum of per-request ceil(P/C) chunks
    want_chunks = sum(-(-(5 + 3 * i) // chunk) for i in range(4))
    assert stats.prefill_chunks == want_chunks
    assert stats.admission_stalls == 0


def test_chunked_matches_lockstep_generate(smoke_lm):
    """Simultaneous equal-length arrivals through chunked admission still
    reproduce lockstep generate() exactly (the PR 2 identity, now one more
    admission policy deep)."""
    cfg, model, params = smoke_lm
    eng = _engine(model, params)
    prompts = (jnp.arange(2 * 8, dtype=jnp.int32).reshape(2, 8) * 7) % cfg.vocab
    base = np.asarray(eng.generate(prompts, 10))
    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]), max_new=10)
            for i in range(2)]
    results, _ = eng.scheduler(chunk_size=3).run(reqs)
    for i in range(2):
        assert results[i].tokens == list(base[i])


def test_chunked_admission_compiles_o1_shapes(smoke_lm):
    """The bucket-explosion regression PR 2 left open: one-shot admission
    compiles one slot-prefill per distinct prompt length; chunked admission
    compiles O(1) step shapes — the count over 7 distinct lengths equals the
    count over 1 and stays a small constant."""
    if not hasattr(jax.jit(lambda: 0), "_cache_size"):
        pytest.skip("jax version does not expose jit cache sizes")
    cfg, model, params = smoke_lm
    rng = np.random.default_rng(4)

    def reqs_for(lens):
        return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=p),
                        max_new=3) for i, p in enumerate(lens)]

    lens7 = [3, 5, 8, 11, 14, 17, 21]         # 7 distinct lengths

    def chunked_compiles(lens):
        _, st = _engine(model, params, max_len=64).scheduler(
            chunk_size=8).run(reqs_for(lens))
        return st.num_jit_compiles

    n1, n7 = chunked_compiles([11]), chunked_compiles(lens7)
    assert n7 == n1, (n1, n7)                 # O(1) in distinct lengths
    assert n7 <= 8, n7                        # and a small constant

    _, oneshot = _engine(model, params, max_len=64).scheduler().run(
        reqs_for(lens7))
    assert oneshot.num_jit_compiles >= len(lens7)   # one compile per length
    assert n7 < oneshot.num_jit_compiles
    assert oneshot.admission_stalls > 0       # the stop-the-world telltale


def test_chunked_token_budget_defers_chunks(smoke_lm):
    """token_budget below live-decode+chunk defers admission chunks (decode
    tokens are never dropped) and the run still completes correctly."""
    cfg, model, params = smoke_lm
    eng = _engine(model, params, max_len=48, batch_slots=4)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=8),
                    max_new=8) for i in range(6)]
    base, _ = eng.scheduler().run(reqs)
    # budget 4 == chunk_size: a chunk only rides when no slot decodes beside
    # it, so every admission past the first defers at least once
    got, stats = eng.scheduler(chunk_size=4, token_budget=4).run(reqs)
    for i in range(6):
        assert got[i].tokens == base[i].tokens
    assert stats.stalled_chunks > 0

    with pytest.raises(ValueError, match="token_budget"):
        eng.scheduler(chunk_size=8, token_budget=4)
    with pytest.raises(ValueError, match="chunk_size"):
        eng.scheduler(token_budget=4)


def test_chunked_int8_fused_kernel_path_identical(smoke_lm):
    """End-to-end through the fused qchunk_attn Pallas kernel (interpret):
    in-place quantize-on-write admission emits the same tokens as the
    blocked-jnp chunk path."""
    from repro.kernels import ops as kops

    cfg, model, params = smoke_lm
    eng = _engine(model, params, max_len=24, batch_slots=1,
                  quantized_kv=True)
    reqs = [Request(rid=0, prompt=np.arange(6, dtype=np.int32) + 2,
                    max_new=3)]
    base, _ = eng.scheduler(chunk_size=4).run(reqs)
    assert kops.FORCE is None
    kops.FORCE = "interpret"
    try:
        got, _ = eng.scheduler(chunk_size=4).run(reqs)
    finally:
        kops.FORCE = None
    assert got[0].tokens == base[0].tokens


def test_chunked_rejects_overlong_prompt(smoke_lm):
    cfg, model, params = smoke_lm
    eng = _engine(model, params, max_len=16)
    sched = eng.scheduler(chunk_size=6)
    # plen 13 pads to 18 chunk rows > max_len 16 even though 13 + 2 fits
    with pytest.raises(ValueError, match="chunk-padded"):
        sched.run([Request(rid=0, prompt=np.arange(13), max_new=2)])


def test_chunked_eos_evicts_and_readmits(smoke_lm):
    cfg, model, params = smoke_lm
    eng = _engine(model, params, batch_slots=1)
    prompt = np.arange(8, dtype=np.int32)
    free_run, _ = eng.scheduler(chunk_size=3).run(
        [Request(rid=0, prompt=prompt, max_new=8)])
    eos = free_run[0].tokens[2]

    reqs = [Request(rid=0, prompt=prompt, max_new=8),
            Request(rid=1, prompt=prompt + 1, max_new=3)]
    results, _ = eng.scheduler(eos_id=eos, chunk_size=3).run(reqs)
    assert results[0].eos is True
    assert results[0].tokens[-1] == eos
    assert len(results[0].tokens) <= 3
    assert results[1].admitted_at >= results[0].finished_at
    assert len(results[1].tokens) == 3


# --------------------------------------------------------------------------
# EOS eviction mid-stream
# --------------------------------------------------------------------------

def test_eos_evicts_slot_and_readmits(smoke_lm):
    cfg, model, params = smoke_lm
    eng = _engine(model, params, batch_slots=1)
    prompt = np.arange(8, dtype=np.int32)
    # discover what the model will emit, then declare one of request 0's
    # first three tokens EOS — one that request 1's own stream never emits
    free_run, _ = eng.scheduler().run(
        [Request(rid=0, prompt=prompt, max_new=8)])
    solo, _ = eng.scheduler().run(
        [Request(rid=1, prompt=prompt + 1, max_new=3)])
    eos = next((x for x in free_run[0].tokens[:3]
                if x not in solo[1].tokens), None)
    assert eos is not None, (free_run[0].tokens, solo[1].tokens)

    reqs = [Request(rid=0, prompt=prompt, max_new=8),
            Request(rid=1, prompt=prompt + 1, max_new=3)]
    results, _ = eng.scheduler(eos_id=eos).run(reqs)
    # request 0 stops at its first eos (within 3 tokens), not at max_new
    assert results[0].eos is True
    assert results[0].tokens[-1] == eos
    assert len(results[0].tokens) <= 3
    # the freed slot served request 1 afterwards
    assert results[1].status == "ok"
    assert results[1].admitted_at >= results[0].finished_at
    assert results[1].tokens == solo[1].tokens


@pytest.mark.parametrize("chunk_size", [None, 3], ids=["one_shot", "chunked"])
def test_first_token_eos_frees_slot_for_queued_request(smoke_lm, chunk_size):
    """A request whose very first sampled token is EOS finishes in its
    admission tick; the request queued behind it must still be admitted
    into the freed slot and end ``ok`` (not be failed as a deadlock)."""
    cfg, model, params = smoke_lm
    eng = _engine(model, params, batch_slots=1)
    prompt = np.arange(8, dtype=np.int32)
    free_run, _ = eng.scheduler(chunk_size=chunk_size).run(
        [Request(rid=0, prompt=prompt, max_new=4)])
    eos = free_run[0].tokens[0]

    reqs = [Request(rid=0, prompt=prompt, max_new=4),
            Request(rid=1, prompt=prompt + 1, max_new=3)]
    results, stats = eng.scheduler(eos_id=eos, chunk_size=chunk_size).run(
        reqs)
    assert results[0].tokens == [eos] and results[0].eos is True
    assert results[1].status == "ok", results[1]
    assert results[1].admitted_at >= results[0].finished_at
    assert stats.deadlock_failures == 0


# --------------------------------------------------------------------------
# Restart-the-batch baseline semantics (bench comparison point)
# --------------------------------------------------------------------------

def test_restart_batching_matches_lockstep_tokens(smoke_lm):
    cfg, model, params = smoke_lm
    eng = _engine(model, params)
    prompts = (jnp.arange(2 * 8, dtype=jnp.int32).reshape(2, 8) * 3) % cfg.vocab
    base = np.asarray(eng.generate(prompts, 6))
    results, stats = run_restart_batching(
        eng, [Request(rid=i, prompt=np.asarray(prompts[i]), max_new=6)
              for i in range(2)])
    for i in range(2):
        assert results[i].tokens == list(base[i])
    # everyone waits for the longest request: one shared finish tick
    assert results[0].finished_at == results[1].finished_at


# --------------------------------------------------------------------------
# Per-slot cache primitives
# --------------------------------------------------------------------------

@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_per_slot_cache_independent_offsets(quantized):
    from repro.nn.attention import init_kv_cache, update_kv_cache

    cache = init_kv_cache(2, 8, 2, 4, quantized=quantized,
                          dtype=jnp.float32, per_slot_len=True)
    cache["len"] = jnp.asarray([0, 3], jnp.int32)
    k = jnp.ones((2, 1, 2, 4)) * jnp.asarray([1.0, 2.0])[:, None, None, None]
    cache = update_kv_cache(cache, k, k)
    np.testing.assert_array_equal(np.asarray(cache["len"]), [1, 4])
    kf = np.asarray(cache["k"], np.float32)
    assert kf[0, 0, 0, 0] != 0          # slot 0 wrote at its own offset 0
    assert kf[1, 3, 0, 0] != 0          # slot 1 wrote at its own offset 3
    assert kf[1, 0, 0, 0] == 0          # and not at slot 0's offset


def test_per_slot_decode_attention_masks_each_slot():
    from repro.nn.attention import decode_attention

    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (2, 1, 4, 8))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, 6, 2, 8))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, 6, 2, 8))
    lens = jnp.asarray([2, 5], jnp.int32)
    out = decode_attention(q, k, v, lens)
    # per-row scalar-length computation must agree exactly
    for i, ln in enumerate([2, 5]):
        ref = decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                               jnp.int32(ln))
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0]),
                                   rtol=1e-6)


def test_qdecode_kernel_per_slot_lengths():
    """Pallas (interpret) and ref agree on per-slot kv_len masking."""
    from repro.kernels.qdecode_attn import qdecode_attn_pallas
    from repro.kernels.ref import qdecode_attn_ref

    rng = jax.random.PRNGKey(3)
    q = jax.random.normal(rng, (2, 4, 8), jnp.float32)
    kc = jax.random.randint(jax.random.fold_in(rng, 1), (2, 8, 2, 8),
                            -100, 100, jnp.int8)
    vc = jax.random.randint(jax.random.fold_in(rng, 2), (2, 8, 2, 8),
                            -100, 100, jnp.int8)
    lens = jnp.asarray([3, 7], jnp.int32)
    ref = qdecode_attn_ref(q, kc, vc, 3, 3, lens)
    out = qdecode_attn_pallas(q, kc, vc, jnp.int32(3), jnp.int32(3), lens,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # scalar kv_len still broadcasts (lockstep path unchanged)
    ref_s = qdecode_attn_ref(q, kc, vc, 3, 3, jnp.int32(5))
    out_s = qdecode_attn_pallas(q, kc, vc, jnp.int32(3), jnp.int32(3),
                                jnp.int32(5), interpret=True)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(ref_s),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# Observers: calibration range accumulation (core/observers.py)
# --------------------------------------------------------------------------

def test_minmax_observer_permutation_invariant():
    """Shuffling calibration batches cannot change a min-max range."""
    from repro.core.observers import make_observer

    rng = np.random.default_rng(0)
    stream = [{"a": jnp.float32(v), "b": jnp.float32(w)}
              for v, w in rng.uniform(0.1, 9.0, size=(8, 2))]
    fwd, rev = make_observer("minmax"), make_observer("minmax")
    for s in stream:
        fwd.observe(s)
    for s in reversed(stream):
        rev.observe(s)
    for k in ("a", "b"):
        want = max(float(s[k]) for s in stream)
        assert float(fwd.ranges[k]) == pytest.approx(want)
        assert float(fwd.ranges[k]) == float(rev.ranges[k])


def test_ema_observer_converges_to_stream_range():
    """First batch seeds directly; a constant tail pulls the EMA to the
    stream's running range geometrically (decay^t), and one outlier moves
    it by only (1 - decay) of its excess."""
    from repro.core.observers import EMAObserver

    obs = EMAObserver(decay=0.9)
    obs.observe({"x": jnp.float32(100.0)})       # outlier seed
    for _ in range(60):
        obs.observe({"x": jnp.float32(2.0)})
    assert float(obs.ranges["x"]) == pytest.approx(
        2.0 + 0.9 ** 60 * 98.0, rel=1e-5)

    single = EMAObserver(decay=0.9)
    single.observe({"x": jnp.float32(2.0)})
    assert float(single.ranges["x"]) == pytest.approx(2.0)   # direct seed
    single.observe({"x": jnp.float32(100.0)})
    assert float(single.ranges["x"]) == pytest.approx(0.9 * 2.0 + 0.1 * 100.0)


def test_make_observer_rejects_unknown_kind():
    from repro.core import observers

    with pytest.raises(ValueError, match="unknown observer"):
        observers.make_observer("percentile")
    inst = observers.EMAObserver(decay=0.5)
    assert observers.make_observer(inst) is inst   # pass-through


def test_calibrate_qstate_reproduces_observed_ranges():
    """calibrate() through an observer lands on the same frozen exponents as
    hand-folding the stream's max-|x| into frac_bits_for — and the ema
    strategy shrugs off a spike that minmax must honor."""
    from repro.core import qformat
    from repro.core.policy import QMode, QuantPolicy
    from repro.core.ptq import calibrate

    def apply_fn(params, batch, ctx):
        ctx.record("act", batch)

    policy = QuantPolicy(mode=QMode.EVAL, weight_bits=8, act_bits=8)
    batches = [jnp.full((4,), v, jnp.float32)
               for v in (0.5, 0.9, 0.7, 0.6, 0.8)]
    qstate = calibrate(apply_fn, {}, batches, policy)
    (site, n), = qstate.items()
    want = qformat.frac_bits_for(jnp.float32(0.9), policy.act_bits)
    assert int(n) == int(want)

    spiked = batches + [jnp.full((4,), 200.0, jnp.float32)] + batches * 4
    n_minmax = next(iter(calibrate(apply_fn, {}, spiked, policy).values()))
    n_ema = next(iter(calibrate(apply_fn, {}, spiked, policy,
                                observer="ema").values()))
    assert int(n_minmax) == int(
        qformat.frac_bits_for(jnp.float32(200.0), policy.act_bits))
    assert int(n_ema) > int(n_minmax)   # ema keeps a finer grid past a spike


def test_scheduler_int4_weights_token_identical_repeat(smoke_lm):
    """Packed int4-per-block weights serve deterministically: a rebuilt
    engine over the same params replays the exact token stream."""
    cfg, model, params = smoke_lm

    def reqs():
        return [Request(rid=i,
                        prompt=np.asarray((np.arange(8) * 3 + i) % cfg.vocab,
                                          np.int32),
                        max_new=8) for i in range(2)]

    runs = []
    for _ in range(2):
        eng = _engine(model, params, weight_quant="int4-block",
                      weight_block=32)
        results, _ = eng.scheduler().run(reqs())
        runs.append({i: results[i].tokens for i in range(2)})
    assert runs[0] == runs[1]
    assert all(0 <= t < cfg.vocab for toks in runs[0].values() for t in toks)
