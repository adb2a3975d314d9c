"""The scheduler's record of its own ticks: ``serve.*`` host spans on the
profiler's clock (serve/trace.py), one ``ServeStats.ticks`` record per
executed step and ``RequestResult.started_at``, on the ragged smollm and the
mixed-step mamba smoke models.  Tracing must change no token and no counter.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_config
from repro.serve import Request, ServeEngine

SLOTS, CHUNK, LANES, EOS = 4, 8, 2, 2
PHASES = ("serve.arrivals", "serve.admit", "serve.assemble",
          "serve.dispatch", "serve.emit")
# wall-clock fields of the summary; everything else is a count
WALL = ("steady_tok_s", "compile_s", "steady_s", "p50_latency_ms",
        "p99_latency_ms")


def _requests(vocab):
    rng = np.random.default_rng(7)
    # a burst that queues, then a late arrival after an idle stretch: the
    # idle tick runs no step but still has its serve.tick
    arrivals = [0, 0, 0, 1, 1, 2, 3, 60]
    return [Request(rid=i, prompt=rng.integers(3, vocab, size=5 + 4 * i),
                    max_new=4 + i % 3, arrival=a)
            for i, a in enumerate(arrivals)]


def _host_spans(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    tick = dict(ev.stats)["tick"]
                    out.append((int(ev.start_ns), int(ev.end_ns), ev.name,
                                int(tick)))
    return sorted(out)


@pytest.fixture(scope="module", params=["smollm-135m-smoke",
                                        "mamba-130m-smoke"])
def served(request, tmp_path_factory):
    """The same requests served without and with an active trace."""
    cfg = get_config(request.param)
    model = cfg.build(dtype=jnp.float32, remat="off")
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model=model, params=params, max_len=96,
                      batch_slots=SLOTS)
    ragged = request.param.startswith("smollm")
    kw = dict(ragged=True, prefill_lanes=LANES) if ragged else {}
    sched = eng.scheduler(eos_id=EOS, chunk_size=CHUNK, **kw)
    reqs = _requests(cfg.vocab)
    sched.warmup([len(r.prompt) for r in reqs], seed=1)
    plain = sched.run(reqs, seed=1, warmup=False)
    log_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(log_dir)):
        traced = sched.run(reqs, seed=1, warmup=False)
    return {"ragged": ragged, "reqs": reqs, "plain": plain,
            "traced": traced, "spans": _host_spans(log_dir)}


def test_one_serve_tick_per_tick_with_its_phases_nested(served):
    spans = served["spans"]
    _, stats = served["traced"]
    ticks = [(s, e, t) for s, e, name, t in spans if name == "serve.tick"]
    assert [t for _, _, t in ticks] == sorted({t for _, _, t in ticks})
    stepped = [r.tick for r in stats.ticks]
    assert len(stepped) == stats.decode_steps
    # every executed step has its serve.tick and exactly one dispatch
    assert [t for s, e, name, t in spans if name == "serve.dispatch"] \
        == stepped
    # the idle tick before the late arrival has a serve.tick but no step
    assert set(stepped) < {t for _, _, t in ticks}
    bounds = {t: (s, e) for s, e, t in ticks}
    seen = set()
    for s, e, name, t in spans:
        if name == "serve.tick":
            continue
        assert name in PHASES + ("serve.readback",), name
        lo, hi = bounds[t]
        assert lo <= s <= e <= hi, (name, t)
        seen.add(name)
    assert seen == set(PHASES) | {"serve.readback"}
    # phases of one tick do not overlap and come in order
    for t in stepped:
        mine = [(s, e, name) for s, e, name, tt in spans
                if tt == t and name in PHASES]
        assert [n for _, _, n in mine] == list(PHASES)
        assert all(a[1] <= b[0] for a, b in zip(mine, mine[1:]))
    # each readback lies inside one phase of its tick, and every step's
    # token readback (eos_id is set) inside its serve.emit
    for s, e, name, t in spans:
        if name == "serve.readback":
            assert any(ps <= s and e <= pe for ps, pe, pn, pt in spans
                       if pt == t and pn in PHASES)
    emit = {t: (s, e) for s, e, name, t in spans if name == "serve.emit"}
    for t in stepped:
        assert any(emit[t][0] <= s and e <= emit[t][1]
                   for s, e, name, tt in spans
                   if tt == t and name == "serve.readback"), t


def test_tick_records_count_the_rows_served(served):
    results, stats = served["traced"]
    reqs = served["reqs"]
    chunk_rows = sum(c for r in stats.ticks for _, _, c in r.chunks)
    assert chunk_rows == sum(len(r.prompt) for r in reqs)
    firsts = sum(bool(res.tokens) for res in results.values())
    assert sum(r.decode_rows for r in stats.ticks) \
        == stats.tokens_out - firsts
    for r in stats.ticks:
        if served["ragged"]:
            assert r.step_rows == SLOTS + LANES * CHUNK
            assert len(r.chunks) <= LANES
        else:
            assert r.step_rows == SLOTS + (CHUNK if r.chunks else 0)
            assert len(r.chunks) <= 1
        assert 0 <= r.decode_rows <= SLOTS and r.queued >= 0
        assert all(0 < c <= CHUNK for _, _, c in r.chunks)
    assert max(r.queued for r in stats.ticks) > 0
    # chunks of one request run in order from its first
    for q in reqs:
        starts = [(r.tick, st, c) for r in stats.ticks
                  for rid, st, c in r.chunks if rid == q.rid]
        assert [st for _, st, _ in starts] \
            == list(range(0, len(q.prompt), CHUNK))
        res = results[q.rid]
        assert starts[0][0] == res.started_at
        assert q.arrival <= res.started_at < res.admitted_at
        # the first token comes with the last chunk
        assert res.admitted_at - 1 == starts[-1][0]


def test_tracing_changes_no_token_and_no_counter(served):
    (plain, ps), (traced, ts) = served["plain"], served["traced"]
    assert {k: v.tokens for k, v in plain.items()} \
        == {k: v.tokens for k, v in traced.items()}
    assert {k: (v.started_at, v.admitted_at, v.finished_at, v.status)
            for k, v in plain.items()} \
        == {k: (v.started_at, v.admitted_at, v.finished_at, v.status)
            for k, v in traced.items()}
    strip = (lambda d: {k: v for k, v in d.items() if k not in WALL})
    assert strip(ps.summary()) == strip(ts.summary())
    assert ps.ticks == ts.ticks
    assert served["spans"] and all(
        not name.startswith("serve.") for name in ps.summary())


def test_no_spans_without_an_active_trace(served, tmp_path):
    """Spans are recorded only while a trace is active: a trace started
    after a run holds none of its spans."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with jax.profiler.trace(str(tmp_path)):
        pass
    assert _host_spans(tmp_path) == []
