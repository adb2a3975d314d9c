"""A Mamba + attention hybrid (jamba2-3b-smoke) on the ragged tick: recurrent
state and the int8 paged KV pool in one slot.  The ragged tick serves what
the mixed step serves, inert decode rows and idle lanes leave the recurrent
state bit-identical, a lane over several chunks ends in the state one pass
over the prompt gives, and prefix sharing stays off under recurrent
state."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_config
from repro.nn.attention import KVChunk, RaggedBatch
from repro.nn.module import eval_context
from repro.nn.ssm import Mamba
from repro.serve import Request, ServeEngine


@pytest.fixture(scope="module")
def hybrid():
    cfg = get_config("jamba2-3b-smoke")
    model = cfg.build(dtype=jnp.float32, remat="off")
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, **kw):
    kw.setdefault("max_len", 64)
    kw.setdefault("batch_slots", 4)
    return ServeEngine(model=model, params=params, quantized_kv=True,
                       paged_kv=True, page_size=8, **kw)


def _reqs(vocab, n, *, seed=3, base_len=5, stride=3, max_new=6):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, vocab,
                                               size=base_len + stride * i),
                    max_new=max_new, arrival=i) for i in range(n)]


def test_hybrid_ragged_tokens_identical_to_mixed(hybrid):
    """Two-lane ragged admission over the int8 paged pool emits exactly the
    mixed step's greedy streams (chunk 4 does not divide most prompts)."""
    cfg, model, params = hybrid
    eng = _engine(model, params)
    reqs = _reqs(cfg.vocab, 6)
    base, _ = eng.scheduler(chunk_size=4).run(reqs)
    got, stats = eng.scheduler(chunk_size=4, ragged=True,
                               prefill_lanes=2).run(reqs)
    assert stats.state_kinds == "kv+recurrent"
    for i in range(6):
        assert got[i].tokens == base[i].tokens, i
    assert stats.prefill_chunks == sum(-(-len(r.prompt) // 4) for r in reqs)


def _ragged_prefill(model, params, cache, prompt, *, slot, nslots, lanes,
                    chunk):
    """Feed ``prompt`` to ``slot`` through lane 0 of ragged ticks (every
    decode row and the other lanes inert); returns the last tick's logits at
    the lane's last live row, and the cache."""
    ctx = eval_context()
    t = nslots + lanes * chunk
    for start in range(0, len(prompt), chunk):
        piece = prompt[start:start + chunk]
        toks = np.zeros((t,), np.int32)
        sids = np.zeros((t,), np.int32)
        poss = np.full((t,), -1, np.int32)
        toks[nslots:nslots + len(piece)] = piece
        sids[nslots:nslots + len(piece)] = slot
        poss[nslots:nslots + len(piece)] = np.arange(start,
                                                     start + len(piece))
        rb = RaggedBatch(slots=jnp.asarray(sids), positions=jnp.asarray(poss),
                         lanes=lanes, chunk=chunk)
        row = nslots + len(piece) - 1
        lg, cache = model.apply(params, jnp.asarray(toks)[None], ctx,
                                cache=cache, decode=True, ragged=rb,
                                logit_rows=jnp.asarray([row], jnp.int32))
    return lg[0, 0], cache


def test_hybrid_ragged_logits_match_the_chunked_path(hybrid):
    """One prompt over three chunks, through ragged lanes or through the
    mixed step's chunked path: the last position's logits agree within
    1e-4 (float32 model, int8 KV quantized on the same grid either way;
    only the row blocking of the projections differs)."""
    cfg, model, params = hybrid
    prompt = np.random.default_rng(9).integers(0, cfg.vocab, 10)
    kw = dict(quantized_kv=True, kv_dtype=jnp.float32, per_slot_len=True,
              page_size=8, num_pages=16)
    cache = model.init_cache(3, 32, **kw)
    cache = _set_rows(cache)
    got, _ = _ragged_prefill(model, params, cache, prompt, slot=2, nslots=3,
                             lanes=2, chunk=4)
    ctx = eval_context()
    cache = _set_rows(model.init_cache(3, 32, **kw))
    for start in range(0, 10, 4):
        n = min(4, 10 - start)
        ctok = np.zeros((1, 4), np.int32)
        ctok[0, :n] = prompt[start:start + n]
        lg, cache = model.apply(
            params, jnp.asarray(ctok), ctx, cache=cache, decode=True,
            chunk=KVChunk(slot=jnp.int32(2), start=jnp.int32(start),
                          length=jnp.int32(n)),
            logit_pos=jnp.int32(n - 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(lg[0, 0]),
                               atol=1e-4, rtol=1e-4)


def _set_rows(cache):
    """Map slot s of every paged layer to pool pages [4s, 4s + 4)."""
    from repro.serve.slot_state import set_cache_page_row

    for s in range(3):
        cache = set_cache_page_row(cache, jnp.int32(s),
                                   jnp.arange(4 * s, 4 * s + 4,
                                              dtype=jnp.int32))
    return cache


def _mixer_and_state(nslots, seed=0):
    m = Mamba(16, inner_norms=True, dtype=jnp.float32)
    params = m.init(jax.random.PRNGKey(seed))
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    state = {"h": jax.random.normal(k1, (nslots, m._di, m.d_state)),
             "conv": jax.random.normal(k2, (nslots, m.d_conv - 1, m._di))}
    return m, params, state


def test_inert_rows_and_idle_lanes_leave_state_bit_identical():
    """Decode rows at position -1 and a lane with no live row (its slot id
    a pad 0, as the scheduler fills it) leave those slots' ``h`` and
    ``conv`` bit-identical; the live decode row and the live lane's slot
    move."""
    nslots, lanes, chunk = 4, 2, 4
    m, params, state = _mixer_and_state(nslots)
    t = nslots + lanes * chunk
    x = jax.random.normal(jax.random.PRNGKey(5), (1, t, 16))
    sids = np.zeros((t,), np.int32)
    poss = np.full((t,), -1, np.int32)
    sids[1], poss[1] = 1, 7                     # slot 1 decodes
    sids[nslots:nslots + 3] = 3                 # lane 0: slot 3, 3 rows
    poss[nslots:nslots + 3] = np.arange(4, 7)
    rb = RaggedBatch(slots=jnp.asarray(sids), positions=jnp.asarray(poss),
                     lanes=lanes, chunk=chunk)
    _, new = m.apply(params, x, eval_context(), state=state, ragged=rb)
    for key in ("h", "conv"):
        for s in (0, 2):
            np.testing.assert_array_equal(np.asarray(new[key][s]),
                                          np.asarray(state[key][s]))
        for s in (1, 3):
            assert not np.array_equal(np.asarray(new[key][s]),
                                      np.asarray(state[key][s])), (key, s)


def test_lane_over_three_chunks_matches_one_pass():
    """A 10-token prompt through one lane in chunks of 4, 4 and 2 ends in
    the state one ``Mamba.apply`` over the whole prompt leaves; the other
    slots keep theirs."""
    nslots, lanes, chunk, plen = 3, 2, 4, 10
    m, params, state = _mixer_and_state(nslots, seed=2)
    xs = jax.random.normal(jax.random.PRNGKey(6), (1, plen, 16))
    ctx = eval_context()
    t = nslots + lanes * chunk
    cur = state
    for start in range(0, plen, chunk):
        n = min(chunk, plen - start)
        x = jnp.zeros((1, t, 16)).at[0, nslots:nslots + n].set(
            xs[0, start:start + n])
        sids = np.zeros((t,), np.int32)
        poss = np.full((t,), -1, np.int32)
        sids[nslots:nslots + n] = 2
        poss[nslots:nslots + n] = np.arange(start, start + n)
        rb = RaggedBatch(slots=jnp.asarray(sids),
                         positions=jnp.asarray(poss), lanes=lanes,
                         chunk=chunk)
        _, cur = m.apply(params, x, ctx, state=cur, ragged=rb)
    _, want = m.apply(params, xs, ctx, state=m.init_state(1))
    for key in ("h", "conv"):
        np.testing.assert_allclose(np.asarray(cur[key][2]),
                                   np.asarray(want[key][0]),
                                   rtol=1e-5, atol=1e-6)
        for s in (0, 1):
            np.testing.assert_array_equal(np.asarray(cur[key][s]),
                                          np.asarray(state[key][s]))


def test_prefix_sharing_off_under_recurrent_state(hybrid):
    """Two requests whose first two full pages agree, served together by
    the hybrid, get the tokens each gets served alone: the second does not
    map the first's pages (that would skip the prefill its Mamba state
    needs)."""
    cfg, model, params = hybrid
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab, 16, dtype=np.int32)
    reqs = [Request(rid=i, prompt=np.concatenate(
        [shared, rng.integers(0, cfg.vocab, 3 + i, dtype=np.int32)]),
        max_new=6, arrival=2 * i) for i in range(2)]
    eng = _engine(model, params)
    sched = eng.scheduler(chunk_size=4, ragged=True, prefill_lanes=2)
    assert not sched.prefix_sharing
    both, stats = sched.run(reqs)
    assert stats.prefix_hits == 0 and stats.shared_pages_mapped == 0
    for r in reqs:
        alone, _ = eng.scheduler(chunk_size=4, ragged=True,
                                 prefill_lanes=2).run(
            [Request(rid=r.rid, prompt=r.prompt, max_new=6, arrival=0)])
        assert both[r.rid].tokens == alone[r.rid].tokens, r.rid
