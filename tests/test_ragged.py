"""Ragged one-forward-per-tick serving: token identity vs the mixed-step
scheduler across dense/paged/prefix-shared/oversubscribed caches, multi-lane
prefill, the O(1) compile-shape property, the qragged kernel-vs-oracle
contract, and the end-to-end interpret-mode Pallas path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_config
from repro.nn.module import eval_context
from repro.serve import Request, ServeEngine


@pytest.fixture(scope="module")
def smoke_lm():
    cfg = get_config("smollm-135m-smoke")
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
    kw.setdefault("max_len", 48)
    kw.setdefault("batch_slots", 4)
    return ServeEngine(model=model, params=params, **kw)


def _reqs(cfg, n, *, seed=3, base_len=5, stride=3, max_new=6, spacing=1):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=base_len + stride * i),
                    max_new=max_new, arrival=spacing * i) for i in range(n)]


# --------------------------------------------------------------------------
# Token identity vs the mixed step
# --------------------------------------------------------------------------

@pytest.mark.parametrize("quantized_kv", [False, True],
                         ids=["fp32", "int8kv"])
@pytest.mark.parametrize("chunk", [4, 7])
def test_ragged_token_identical_to_mixed(smoke_lm, quantized_kv, chunk):
    """Multi-lane ragged admission emits exactly the mixed step's streams —
    per-request prompt lengths, staggered arrivals, readmission, and chunk
    sizes that do NOT divide the prompt lengths."""
    cfg, model, params = smoke_lm
    eng = _engine(model, params, quantized_kv=quantized_kv)
    reqs = _reqs(cfg, 6)
    base, _ = eng.scheduler(chunk_size=chunk).run(reqs)
    got, stats = eng.scheduler(chunk_size=chunk, ragged=True,
                               prefill_lanes=3).run(reqs)
    for i in range(6):
        assert got[i].tokens == base[i].tokens, (quantized_kv, chunk, i)
    want_chunks = sum(-(-len(r.prompt) // chunk) for r in reqs)
    assert stats.prefill_chunks == want_chunks


def test_ragged_paged_prefix_sharing_identity(smoke_lm):
    """Ragged over the paged pool with prefix sharing live: shared-prefix
    requests map resident pages (hits > 0) and streams stay identical to the
    mixed paged run."""
    cfg, model, params = smoke_lm
    rng = np.random.default_rng(7)
    head = rng.integers(0, cfg.vocab, size=16, dtype=np.int32)
    reqs = []
    for i in range(4):
        tail = rng.integers(0, cfg.vocab, size=4, dtype=np.int32)
        # arrivals staggered so request 0's prefill is resident before the
        # shared-prefix followers are admitted
        reqs.append(Request(rid=i, prompt=np.concatenate([head, tail]),
                            max_new=5, arrival=0 if i == 0 else 8))
    kw = dict(paged_kv=True, page_size=8, quantized_kv=True)
    base, _ = _engine(model, params, **kw).scheduler(chunk_size=8).run(reqs)
    got, stats = _engine(model, params, **kw).scheduler(
        chunk_size=8, ragged=True, prefill_lanes=2).run(reqs)
    for i in range(4):
        assert got[i].tokens == base[i].tokens, i
    assert stats.prefix_hits > 0
    assert stats.shared_pages_mapped > 0


@pytest.mark.parametrize("preempt", ["recompute", "swap"])
def test_ragged_oversubscribed_preemption_identity(smoke_lm, preempt):
    """Oversubscribed pool running dry mid-decode: the ragged scheduler
    preempts and resumes exactly like the mixed one, bit-identical streams
    under both recompute and swap."""
    cfg, model, params = smoke_lm
    rng = np.random.default_rng(9)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=8),
                    max_new=14, arrival=i) for i in range(4)]
    kw = dict(max_len=32, batch_slots=4, paged_kv=True, page_size=8,
              kv_pool_pages=8, quantized_kv=True)
    sk = dict(chunk_size=8, oversubscribe=True, preempt_policy=preempt)
    base, bstats = _engine(model, params, **kw).scheduler(**sk).run(reqs)
    got, rstats = _engine(model, params, **kw).scheduler(
        ragged=True, prefill_lanes=2, **sk).run(reqs)
    for i in range(4):
        assert got[i].tokens == base[i].tokens, (preempt, i)
    # the pool really ran dry in both runs — the identity is not vacuous
    assert bstats.preemptions > 0 and rstats.preemptions > 0
    if preempt == "swap":
        assert rstats.resumes > 0    # recompute re-queues instead


def test_ragged_eos_evicts_and_readmits(smoke_lm):
    cfg, model, params = smoke_lm
    eng = _engine(model, params, batch_slots=1, max_len=32)
    prompt = np.arange(8, dtype=np.int32)
    free_run, _ = eng.scheduler(chunk_size=3, ragged=True).run(
        [Request(rid=0, prompt=prompt, max_new=8)])
    eos = free_run[0].tokens[2]

    reqs = [Request(rid=0, prompt=prompt, max_new=8),
            Request(rid=1, prompt=prompt + 1, max_new=3)]
    results, _ = eng.scheduler(eos_id=eos, chunk_size=3, ragged=True).run(reqs)
    assert results[0].eos is True
    assert results[0].tokens[-1] == eos
    assert len(results[0].tokens) <= 3
    assert results[1].admitted_at >= results[0].finished_at
    assert len(results[1].tokens) == 3


def test_ragged_encdec_matches_mixed(whisper):
    """EncDec ragged ticks gather per-token encoder rows (cross-attention
    sees each lane's own enc): streams equal the mixed chunked run."""
    cfg, model, params = whisper

    def encode(seed, s_enc=6):
        embeds = 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed), (1, s_enc, model.d_model), jnp.float32)
        return model.encode(params, embeds, eval_context())

    rng = np.random.default_rng(11)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=4 + i),
                    max_new=5, arrival=i, enc=encode(10 * (i + 1)))
            for i in range(3)]
    eng = ServeEngine(model=model, params=params, max_len=24, batch_slots=2)
    base, _ = eng.scheduler(chunk_size=4).run(reqs)
    got, _ = eng.scheduler(chunk_size=4, ragged=True,
                           prefill_lanes=2).run(reqs)
    for i in range(3):
        assert got[i].tokens == base[i].tokens, i


# --------------------------------------------------------------------------
# O(1) compile shapes
# --------------------------------------------------------------------------

def test_ragged_compiles_o1_shapes(smoke_lm):
    """One compile shape for the whole run: the jit count is flat across
    distinct prompt-length sets AND across lane counts (pure-decode ticks
    reuse the same ragged shape with inert lane rows)."""
    if not hasattr(jax.jit(lambda: 0), "_cache_size"):
        pytest.skip("jax version does not expose jit cache sizes")
    cfg, model, params = smoke_lm

    def compiles(lanes, lens):
        rng = np.random.default_rng(13)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=p),
                        max_new=3) for i, p in enumerate(lens)]
        _, st = _engine(model, params, max_len=64).scheduler(
            chunk_size=8, ragged=True, prefill_lanes=lanes).run(reqs)
        return st.num_jit_compiles

    n_short = compiles(2, [11])
    n_many = compiles(2, [3, 5, 8, 11, 14, 17, 21])
    assert n_many == n_short, (n_short, n_many)      # O(1) in prompt lengths
    assert n_many <= 8, n_many                       # and a small constant
    assert compiles(1, [11]) == compiles(4, [11]) == n_short


def test_ragged_requires_chunk_size_and_lanes_require_ragged(smoke_lm):
    cfg, model, params = smoke_lm
    eng = _engine(model, params)
    with pytest.raises(ValueError, match="chunk_size"):
        eng.scheduler(ragged=True)
    with pytest.raises(ValueError, match="prefill_lanes"):
        eng.scheduler(chunk_size=4, prefill_lanes=2)
    with pytest.raises(ValueError, match="prefill_lanes"):
        eng.scheduler(chunk_size=4, ragged=True, prefill_lanes=0)


# --------------------------------------------------------------------------
# Kernel vs oracle
# --------------------------------------------------------------------------

# slot 0 owns pages 0,1; slot 1 pages 2,3; slot 2 pages 4,5 (+ unmapped)
_TABLE = [[0, 1, -1, -1], [2, 3, -1, -1], [4, 5, -1, -1]]
# decode rows for slots 0..2, then a 4-token chunk for slot 1 (exercises
# intra-tick visibility: later chunk rows attend to earlier ones), then
# inert pad rows (position -1)
_SLOTS = [0, 1, 2, 1, 1, 1, 1, 0, 0, 0]
_POS = [5, 3, 6, 4, 5, 6, 7, -1, -1, -1]


def _ragged_case(seed, *, slots=_SLOTS, pos=_POS, table=_TABLE, hq=4,
                 hkv=2, d=8, n_pages=6, ps=4):
    rng = jax.random.PRNGKey(seed)
    t = len(slots)
    q = jax.random.normal(rng, (t, hq, d), jnp.float32)
    k_new = jax.random.normal(jax.random.fold_in(rng, 1), (t, hkv, d))
    v_new = jax.random.normal(jax.random.fold_in(rng, 2), (t, hkv, d))
    k_pool = jax.random.randint(jax.random.fold_in(rng, 3),
                                (n_pages, ps, hkv, d), -100, 100, jnp.int8)
    v_pool = jax.random.randint(jax.random.fold_in(rng, 4),
                                (n_pages, ps, hkv, d), -100, 100, jnp.int8)
    return (q, k_new, v_new, k_pool, v_pool, jnp.asarray(table, jnp.int32),
            jnp.asarray(slots, jnp.int32), jnp.asarray(pos, jnp.int32))


_PAD3 = ([0] * 3, [-1] * 3)
_ORACLE_CASES = {
    "mixed-seed0": dict(seed=0),
    "mixed-seed1": dict(seed=1),
    # consecutive rows of one slot inside one page (logical page 1 of slot 1)
    "one-page": dict(seed=3, slots=[1] * 4 + _PAD3[0],
                     pos=[4, 5, 6, 7] + _PAD3[1]),
    # a chunk of slot 2 across its page boundary (pool page 4 -> 5)
    "cross-page": dict(seed=4, slots=[2] * 6 + _PAD3[0],
                       pos=[1, 2, 3, 4, 5, 6] + _PAD3[1]),
    # two slots whose rows interleave in batch order
    "interleave": dict(seed=5, slots=[0, 1, 0, 1, 0, 1, 2] + _PAD3[0],
                       pos=[2, 5, 3, 6, 4, 7, 0] + _PAD3[1]),
    # nothing lands: pad rows, one on a wholly unmapped slot, a live
    # position on an unmapped page, and one past the table
    "inert-unmapped": dict(seed=6, table=[[-1] * 4] + _TABLE[1:],
                           slots=[0, 1, 2, 0, 1], pos=[-1, -1, -1, 9, 20]),
    # a dense (B=2, S=16) slab viewed as 8-row pages under the identity
    # table, as nn/attention.py passes it
    "dense-identity": dict(seed=7, n_pages=4, ps=8, table=[[0, 1], [2, 3]],
                           slots=[0, 1, 1, 1, 1, 1] + _PAD3[0],
                           pos=[11, 5, 6, 7, 8, 9] + _PAD3[1]),
}


def _check_vs_oracle(args, *, lanes=0, chunk=0, atol=1e-5):
    """Run the kernel (interpret mode) and the oracle on one case; compare
    live rows, and the pools bit for bit.  Returns (out, valid rows)."""
    from repro.kernels.qragged_attn import qragged_attn_pallas
    from repro.kernels.ref import qragged_attn_ref

    q, k_new, v_new, k_pool, v_pool, table, slots, pos = args
    k_n = jnp.int32(3)
    v_n = jnp.int32(3)
    ref_o, ref_k, ref_v = qragged_attn_ref(
        q, k_new, v_new, k_pool, v_pool, k_n, v_n, table, slots, pos)
    out, ko, vo = qragged_attn_pallas(
        q, k_new, v_new, k_pool, v_pool, k_n, v_n, table, slots, pos,
        lanes=lanes, chunk=chunk, interpret=True)
    # outputs are defined for live rows whose pages are all mapped
    ps = k_pool.shape[1]
    tab, sl, p = np.asarray(table), np.asarray(slots), np.asarray(pos)
    valid = np.array([0 <= pt < tab.shape[1] * ps
                      and (tab[st, :pt // ps + 1] >= 0).all()
                      for st, pt in zip(sl, p)])
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(ref_o)[valid],
                               rtol=1e-5, atol=atol)
    # pool writes are bit-exact (same paper-grid quantizer) and rows that
    # land nowhere wrote nothing — the whole pools must agree
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(ref_v))
    if not valid.any():
        np.testing.assert_array_equal(np.asarray(ko), np.asarray(k_pool))
        np.testing.assert_array_equal(np.asarray(vo), np.asarray(v_pool))
    return np.asarray(out), valid


@pytest.mark.parametrize("case", sorted(_ORACLE_CASES))
def test_qragged_kernel_matches_oracle(case):
    _check_vs_oracle(_ragged_case(**_ORACLE_CASES[case]))


def _lane_case(seed, *, decode, lanes, chunk, n_slots=3, max_pages=4, ps=4,
               table=None, **kw):
    """A tick laid out as the scheduler lays it (serve/lanes.py): one
    decode row per slot ((slot, position), or None when idle), then one
    ``chunk``-row lane per ``(slot, start, clen)`` (clen 0: an idle lane),
    inert rows at slot 0, position -1.  Slots own their pages in a shuffled
    pool order unless ``table`` is given."""
    sl, ps_ = [], []
    for j, row in enumerate(decode):
        sl.append(j if row else 0)
        ps_.append(row[1] if row else -1)
    for slot, start, clen in lanes:
        sl += [slot] * clen + [0] * (chunk - clen)
        ps_ += list(range(start, start + clen)) + [-1] * (chunk - clen)
    if table is None:
        order = np.random.default_rng(seed).permutation(n_slots * max_pages)
        table = order.reshape(n_slots, max_pages).tolist()
    n_pages = int(np.max(table)) + 1
    return _ragged_case(seed, slots=sl, pos=ps_, table=table,
                        n_pages=n_pages, ps=ps, **kw)


_DEC = [(0, 5), None, (2, 6)]          # slot 1 is the one being prefilled
_LANE_CASES = {
    # a full lane whose rows run across two page boundaries (pages 0-2)
    "full-cross-page": dict(seed=10, decode=_DEC, chunk=8,
                            lanes=[(1, 2, 8), (0, 0, 0)]),
    # a lane starting at position 0, beside a second live lane
    "from-zero": dict(seed=11, decode=[(0, 9), None, None], chunk=8,
                      lanes=[(1, 0, 8), (2, 4, 8)]),
    # the prompt ends inside the chunk: an inert tail in the block
    "partial-tail": dict(seed=12, decode=_DEC, chunk=8,
                         lanes=[(1, 6, 5), (0, 0, 0)]),
    # an idle lane is a block with no live row, first and last
    "idle-lane": dict(seed=13, decode=[(0, 3), (1, 14), None], chunk=8,
                      lanes=[(0, 0, 0), (2, 3, 8)]),
    # the block's last page is the table's last page
    "last-page": dict(seed=14, decode=[(0, 15), None, None], chunk=8,
                      lanes=[(2, 8, 8), (1, 1, 8)]),
    # smollm's head ratio (9 query heads on 3 KV heads)
    "smollm-heads": dict(seed=15, decode=_DEC, chunk=8, hq=9, hkv=3,
                         lanes=[(1, 2, 8), (0, 0, 0)]),
    # Jamba2's (20 on 1): 64-row blocks, two to a lane, the second of the
    # second lane partly inert.  Contexts reach 170 keys: the flash and the
    # oracle's softmax differ by a few 1e-5 in float32 here, the per-token
    # launch as much as the lanes.
    "jamba-heads": dict(seed=16, decode=_DEC, chunk=128, hq=20, hkv=1,
                        ps=16, max_pages=12, atol=1e-4,
                        lanes=[(1, 20, 128), (2, 70, 100)]),
    # a dense (B=2, S=16) slab viewed as 8-row pages under the identity
    # table, as nn/attention.py passes it
    "dense-identity": dict(seed=17, decode=[(0, 11), None], chunk=8, ps=8,
                           table=[[0, 1], [2, 3]], lanes=[(1, 3, 8)]),
}


@pytest.mark.parametrize("case", sorted(_LANE_CASES))
def test_qragged_lane_blocks_match_oracle(case):
    """With the tick's geometry the lanes attend in blocks of rows that
    share each page's grid step: the oracle's outputs, and the per-token
    launch's bit for bit; bit-exact pools; exact zeros on every inert
    row."""
    from repro.kernels.qragged_attn import lane_block

    kw = dict(_LANE_CASES[case])
    atol = kw.pop("atol", 1e-5)
    n_lanes, chunk = len(kw["lanes"]), kw["chunk"]
    args = _lane_case(**kw)
    q, pos = args[0], np.asarray(args[7])
    ps, hkv = args[3].shape[1:3]
    bq = lane_block(hkv, q.shape[1] // hkv, q.shape[2], ps, chunk)
    assert bq > 1 and (case != "jamba-heads" or bq < chunk)
    out, valid = _check_vs_oracle(args, lanes=n_lanes, chunk=chunk,
                                  atol=atol)
    per_token, _ = _check_vs_oracle(args, atol=atol)
    np.testing.assert_array_equal(out, per_token)
    assert valid[pos >= 0].all()
    np.testing.assert_array_equal(out[pos < 0], 0.0)


# Rows per lane block the rule picks at a 256-row chunk and 128-row pages:
# smollm's whole lane, Jamba2's 64; None where only the VMEM bound is held.
_BLOCK_PICKS = {"smollm-135m": 256, "jamba2-3b": 64, "jamba-v0.1-52b": None,
                "qwen2.5-14b": None, "command-r-plus-104b": None,
                "glm4-9b": None}


@pytest.mark.parametrize("arch", sorted(_BLOCK_PICKS))
def test_lane_block_fits_vmem(arch):
    """The lane block holds all KV heads' query rows: the rule sizes it
    from (Hkv, G, D, page) and keeps the estimate within the budget, with
    the next doubling over it unless the chunk stops the doubling first."""
    from repro.kernels.qragged_attn import (_VMEM_BUDGET, _lane_vmem_bytes,
                                            lane_block)
    from repro.models.registry import get_config

    cfg = get_config(arch)
    hkv, g, d = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    bq = lane_block(hkv, g, d, 128, 256)
    assert _BLOCK_PICKS[arch] in (None, bq)
    assert bq >= 16 and 256 % bq == 0
    assert _lane_vmem_bytes(bq, hkv, g, d, 128) <= _VMEM_BUDGET
    assert bq == 256 or _lane_vmem_bytes(2 * bq, hkv, g, d, 128) > _VMEM_BUDGET


def test_qragged_inert_rows_write_nothing():
    from repro.kernels.ref import qragged_attn_ref

    q, k_new, v_new, k_pool, v_pool, table, slots, pos = _ragged_case(2)
    all_pad = jnp.full_like(pos, -1)
    _, ko, vo = qragged_attn_ref(q, k_new, v_new, k_pool, v_pool,
                                 jnp.int32(3), jnp.int32(3), table,
                                 slots, all_pad)
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(k_pool))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(v_pool))


# --------------------------------------------------------------------------
# End-to-end interpret-mode Pallas path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_ragged_interpret_kernel_path_identical(smoke_lm, paged):
    """REPRO_KERNELS_FORCE=interpret drives the real qragged Pallas kernel
    (dense caches viewed as an identity-table pool): same streams as the
    blocked-jnp ragged path."""
    from repro.kernels import ops as kops

    if kops.FORCE is not None:
        pytest.skip("dispatch already forced globally (e.g. the CI "
                    "kernels-interpret lane) — the jnp-vs-interpret "
                    "comparison would be vacuous")
    cfg, model, params = smoke_lm
    kw = dict(max_len=32, batch_slots=2, quantized_kv=True)
    if paged:
        kw.update(paged_kv=True, page_size=8)
    eng = _engine(model, params, **kw)
    rng = np.random.default_rng(17)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=6 + i),
                    max_new=4, arrival=i) for i in range(3)]
    base, _ = eng.scheduler(chunk_size=4, ragged=True,
                            prefill_lanes=2).run(reqs)
    assert kops.FORCE is None
    kops.FORCE = "interpret"
    try:
        got, _ = eng.scheduler(chunk_size=4, ragged=True,
                               prefill_lanes=2).run(reqs)
    finally:
        kops.FORCE = None
    for i in range(3):
        assert got[i].tokens == base[i].tokens, (paged, i)
