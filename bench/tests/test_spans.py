"""The reduction of the program's own spans and tick records
(``bench/spans.py``): on the recorded v5e traces, on hand-built inputs, and
on a CPU trace of the tiny scheduler under ``bench.tick`` spans."""
import gzip
import json
import shutil

import numpy as np
import pytest

import harness
import spans
import trace_reduce

DATA = harness.BENCH / "testdata"


def unpack(name, tmp_path):
    path = tmp_path / name.replace(".gz", "")
    with gzip.open(DATA / name) as src, open(path, "wb") as f:
        shutil.copyfileobj(src, f)
    return str(path)


def test_trace_without_program_spans_reduces_as_before(tmp_path):
    """A program that records no ``serve.*`` span (``trace.xplane.pb.gz``):
    no spans, all idle time under ``bench.tick``, the same gap names."""
    path = unpack("trace.xplane.pb.gz", tmp_path)
    old = trace_reduce.reduce(path)
    got = spans.reduce(path)
    assert got["spans"] == []
    assert list(got["idle_by_span"]) == ["bench.tick"]
    assert got["idle_by_span"]["bench.tick"] == pytest.approx(
        old["window_s"] - old["busy_s"], rel=1e-9)
    assert got["idle_gaps"] == old["idle_gaps"]
    assert spans.host_ms_per_tick(got["spans"]) is None


def test_trace_with_program_spans_reduces_to_recorded_numbers(tmp_path):
    """Two ticks of ``smollm-doc-long`` recorded on a v5e chip with the
    scheduler's spans: every idle gap has a ``serve.*`` name, one per-tick
    entry per ``serve.tick``, and the idle split sums to the idle time."""
    want = json.loads((DATA / "trace_spans.reduced.json").read_text())
    path = unpack("trace_spans.xplane.pb.gz", tmp_path)
    old = trace_reduce.reduce(path, kernels=("qragged_attn", "wq_matmul"))
    got = spans.reduce(path)
    for k in ("window_s", "busy_s"):
        assert old[k] == pytest.approx(want["trace_reduce"][k], rel=1e-9)
    assert old["ticks"] == want["trace_reduce"]["ticks"] == 2
    assert [t for t, _, _ in got["spans"]] \
        == [t for t, _, _ in want["spans"]["spans"]]
    assert [x for _, *x in got["spans"]] == [
        pytest.approx(x, rel=1e-9) for _, *x in want["spans"]["spans"]]
    assert got["idle_by_span"] == pytest.approx(
        want["spans"]["idle_by_span"], rel=1e-9)
    assert [n for n, _ in got["idle_gaps"]] \
        == [n for n, _ in want["spans"]["idle_gaps"]]
    assert len(got["spans"]) == old["ticks"]
    assert all(n.startswith("serve.") for n, _ in got["idle_gaps"])
    assert sum(got["idle_by_span"].values()) == pytest.approx(
        old["window_s"] - old["busy_s"], rel=1e-9)
    for _, dur, wait in got["spans"]:
        assert 0 < wait < dur


def test_idle_goes_to_the_innermost_span():
    # bench.tick 0..100 > serve.tick 10..90 > emit 50..90 > readback 60..70
    pieces = spans._innermost([(10, 90, "serve.tick"),
                               (50, 90, "serve.emit"),
                               (60, 70, "serve.readback")])
    assert pieces == [(10, 50, "serve.tick"), (50, 60, "serve.emit"),
                      (60, 70, "serve.readback"), (70, 90, "serve.emit")]
    out = {}
    spans._attribute([(0, 20), (45, 65), (85, 100)], pieces, out)
    assert out == {"bench.tick": 10 + 10, "serve.tick": 10 + 5,
                   "serve.emit": 10 + 5, "serve.readback": 5}


def test_readers_on_hand_built_inputs():
    from repro.serve import TickRecord

    assert spans.host_ms_per_tick([[4, 0.010, 0.004], [5, 0.012, 0.010]]) \
        == pytest.approx(4.0)
    tl = [{"arrival": 1, "started": 3},      # before the window
          {"arrival": 4, "started": 6},      # waits 2
          {"arrival": 5, "started": -1},     # never started: 10 - 5
          {"arrival": 8, "started": 8}]      # waits 0
    assert spans.queue_wait_ticks_p95(tl, 4, 10) == pytest.approx(
        np.percentile([2, 5, 0], 95))
    assert spans.queue_wait_ticks_p95(
        [{"arrival": 4, "started": None}], 4, 10) is None
    assert spans.queue_wait_ticks_p95([{"arrival": 4}], 4, 10) is None
    recs = [TickRecord(0, 2, ((7, 0, 8), (8, 0, 3)), 20, 1),
            TickRecord(1, 3, (), 20, 0)]
    assert spans.live_row_share(recs) == pytest.approx(100 * 16 / 40)
    assert spans.live_row_share(None) is None
    assert spans.live_row_share([]) is None


def test_cpu_trace_of_the_scheduler_nests_in_bench_tick(tmp_path):
    """Under a hook that brackets each tick in ``bench.tick`` (as the
    harness's clock does), every tick has one ``serve.tick`` and its waits;
    the CPU trace has no device plane, so no idle time is split."""
    import jax
    import jax.numpy as jnp

    from repro.models.registry import get_config
    from repro.serve import Request, ServeEngine

    cfg = get_config("smollm-135m-smoke")
    model = cfg.build(dtype=jnp.float32, remat="off")
    eng = ServeEngine(model=model, params=model.init(jax.random.PRNGKey(0)),
                      max_len=64, batch_slots=4)
    sched = eng.scheduler(eos_id=2, chunk_size=8, ragged=True,
                          prefill_lanes=2)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(3, 400, size=6 + 5 * i),
                    max_new=5, arrival=i) for i in range(5)]
    sched.warmup([len(r.prompt) for r in reqs])
    box = []

    def on_tick(t):
        if box:
            box.pop().__exit__(None, None, None)
        box.append(jax.profiler.TraceAnnotation("bench.tick", tick=t))
        box[-1].__enter__()

    with jax.profiler.trace(str(tmp_path)):
        _, stats = sched.run(reqs, warmup=False, on_tick=on_tick)
        box.pop().__exit__(None, None, None)
    got = spans.reduce(trace_reduce.find_trace(str(tmp_path)))
    assert [t for t, _, _ in got["spans"]] == [r.tick for r in stats.ticks]
    assert all(0 < wait < dur for _, dur, wait in got["spans"])
    assert got["idle_by_span"] == {} and got["idle_gaps"] == []
    assert spans.host_ms_per_tick(got["spans"]) > 0
