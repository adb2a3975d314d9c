"""The benchmark harness: one cell, one seed, one process on one chip.

Everything is found by name from ``BENCHMARK.json`` at the checkout's root:

* the cell's configuration file (``configs[].file``): published sizes, the
  plain reference family, the registry model and its serving options;
* ``bench/reference/<family>.py``: the float32 reference, which also draws
  the weights from the seed;
* ``bench/adapters/<family>.py``: where those weights go in the program's
  parameter tree, and the shapes of the work a token costs;
* ``bench/traffic/<mix>.json``: the mix, read by ``bench/traffic.py``;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A run: check the device, draw the weights on the device from the seed, build
``ServeEngine`` + ``Scheduler``, compile and rehearse every step the window
uses, run the mix's first ``preroll_ticks`` ticks of ``Scheduler.run``
(all of that is set-up), then measure the next ``seconds`` of wall time.
At the close the harness cancels everything still queued, prefilling or
decoding (``Scheduler.cancel``); those cancellations are the harness's and
no failure.  Then it reads the device's peak memory,
frees the program, and compares a sample of the served tokens with the
reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


class Refused(SystemExit):
    """The run cannot be measured here; exits non-zero with no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise Refused(f"no file {path.relative_to(ROOT)}")
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                               .parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, spec: Optional[dict] = None) -> dict:
    """A cell of ``BENCHMARK.json`` with every file it names loaded."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(ROOT / configs[w["config"]]["file"])
    mix = load_json(BENCH / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return workload in m.get("workloads", [workload])

    return {"workload": w, "config": cfg, "traffic": mix,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def check_device(chips: int) -> dict:
    """The chip this run measures, or a refusal: a TPU, as many chips as the
    cell asks for, kernels compiled as Pallas (no forced dispatch), and a
    device kind whose peaks ``bench/peaks.json`` knows."""
    if os.environ.get("REPRO_KERNELS_FORCE"):
        raise Refused("REPRO_KERNELS_FORCE is set: the chip run must "
                      "dispatch compiled Pallas kernels")
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"needs {chips} chips, JAX found {len(devs)}")
    if ops.FORCE is not None or not ops.is_hardware_dispatch():
        raise Refused("kernels would not dispatch as compiled Pallas")
    peaks = load_json(BENCH / "peaks.json")["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs),
            "peak": peaks[kind]}


def seed_key(seed: int):
    """A PRNG key from any whole number, also one wider than 32 bits."""
    import jax

    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@dataclasses.dataclass
class Run:
    """What a run leaves for the metric readers (``bench/metrics``)."""
    cfg: dict
    adapter: Any
    peak: dict
    timeline: List[dict]          # per request, see ``timelines``
    top: Dict[int, float]         # tick -> wall clock at its start
    t_open: int                   # first tick of the window
    t_close: int                  # first tick after the window
    trace: Optional[dict] = None  # trace_reduce.reduce() of a traced run
    rows: Optional[list] = None   # live rows of each traced tick
    sampled: Optional[list] = None  # rows that sampled, per traced tick

    def tick_end(self, k: int) -> float:
        return self.top[k + 1]

    def start_of(self, tick: int) -> float:
        return self.top[min(t for t in self.top if t >= tick)]

    def in_window(self, tick: int) -> bool:
        return self.t_open <= tick < self.t_close

    def window_tokens(self, r: dict) -> int:
        """Tokens of one request emitted inside the window (one a tick from
        its first)."""
        if r["first_tick"] is None:
            return 0
        lo = max(r["first_tick"], self.t_open)
        hi = min(r["first_tick"] + r["n_tokens"], self.t_close)
        return max(0, hi - lo)


TRACE_TICKS = 50


class Clock:
    """``on_tick`` hook: stamps each tick's start, opens the window at the
    first tick from ``open_tick`` on and closes it ``seconds`` later.

    A traced run traces a slice of the window: from the first tick that
    starts after half of it has passed, for ``TRACE_TICKS`` ticks or to the
    close, each tick bracketed in a ``bench.tick`` host span.  (A tick of
    the Mamba cell puts tens of thousands of device events in the trace; a
    slice keeps the trace to tens of MB and its reading to seconds.)
    """

    def __init__(self, sched, rids, seconds: float, trace_dir=None,
                 compiles: Callable[[], int] = lambda: 0, open_tick: int = 0):
        self.sched, self.rids, self.seconds = sched, rids, seconds
        self.open_tick = open_tick
        self.t_open: Optional[int] = None
        self.trace_dir, self.compiles = trace_dir, compiles
        self.compiled_in_window = 0
        self.top: Dict[int, float] = {}
        self.t0: Optional[float] = None
        self.t_close: Optional[int] = None
        self.traced: Optional[list] = None     # [first tick, end tick)
        self.span = None

    def _stop_trace(self, t: int) -> None:
        import jax

        self.traced[1] = t
        jax.profiler.stop_trace()

    def __call__(self, t: int) -> None:
        import jax

        now = time.perf_counter()
        self.top.setdefault(t, now)
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
        if self.t_close is not None:
            return
        if self.t0 is None:
            if t < self.open_tick:
                return
            self.t0, self.t_open = now, t
        elif now - self.t0 >= self.seconds:
            self.t_close = t
            self.compiled_in_window = self.compiles()
            for rid in self.rids:
                self.sched.cancel(rid)
            if self.traced is not None and self.traced[1] is None:
                self._stop_trace(t)
            return
        if self.trace_dir is None:
            return
        if self.traced is None and now - self.t0 >= self.seconds / 2:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.traced = [t, None]
        if self.traced is not None and self.traced[1] is None:
            if t - self.traced[0] >= TRACE_TICKS:
                self._stop_trace(t)
                return
            self.span = jax.profiler.TraceAnnotation("bench.tick", tick=t)
            self.span.__enter__()


def timelines(results: dict, reqs: List[Any], t_close: int) -> List[dict]:
    """Per request: arrival, prompt length, the tick of its first token and
    its tokens, checked against the scheduler's tick semantics (one token
    per tick from the first; ``admitted_at`` and ``finished_at`` count the
    ticks done)."""
    out = []
    for r in reqs:
        res = results[r.rid]
        n = len(res.tokens)
        first = res.admitted_at - 1 if n else None
        if n:
            last = res.finished_at - 1
            if first + n - 1 != last or last >= t_close:
                raise RuntimeError(
                    f"request {r.rid}: {n} tokens from tick {first} to "
                    f"{last} do not run one per tick inside the window "
                    f"(closed at tick {t_close})")
        out.append({"rid": r.rid, "arrival": r.arrival,
                    "plen": int(len(r.prompt)), "first_tick": first,
                    "n_tokens": n, "status": res.status,
                    "tokens": list(res.tokens), "prompt": r.prompt})
    return out


def end_to_end(run: Run) -> Dict[str, float]:
    """``output_tok_s``, ``ttft_p95_ms`` and ``itl_p95_ms`` over the window:
    the tokens emitted in its ticks, the first-token waits of the requests
    that arrived in it, and every gap that ends in it."""
    w0, w1 = run.top[run.t_open], run.top[run.t_close]
    tokens = 0
    ttfts, gaps = [], []
    for r in run.timeline:
        tokens += run.window_tokens(r)
        f = r["first_tick"]
        if run.in_window(r["arrival"]):
            a = run.start_of(r["arrival"])
            ttfts.append(w1 - a if f is None else run.tick_end(f) - a)
        if f is None:
            continue
        gaps += [run.tick_end(k) - run.tick_end(k - 1)
                 for k in range(f + 1, f + r["n_tokens"])
                 if run.in_window(k)]
    return {"output_tok_s": tokens / (w1 - w0),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttfts, 95))
            if ttfts else float("nan"),
            "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95))
            if gaps else float("nan")}


@dataclasses.dataclass
class Cell:
    """A built cell: the program under test and its schedule."""
    cfg: dict
    reference: Any
    adapter: Any
    engine: Any
    sched: Any
    requests: List[Any]


def build(cfg: dict, mix: dict, seed: int,
          overrides: Optional[dict] = None) -> Cell:
    """Weights from the seed (one jitted call, on the device), the served
    model, its scheduler and the request schedule.  ``overrides`` replace
    serving options (the control run switches ``weight_quant``)."""
    import jax
    import jax.numpy as jnp

    from repro.serve import Request, ServeEngine

    import traffic

    fam = cfg["reference"]
    reference = load_module(BENCH / "reference" / f"{fam}.py")
    adapter = load_module(BENCH / "adapters" / f"{fam}.py")
    serving = dict(cfg["serving"], **(overrides or {}))
    model = adapter.arch(cfg).build(dtype=jnp.dtype(serving["dtype"]),
                                    remat="off")
    pub = cfg["published"]
    make = jax.jit(lambda key: adapter.program_params(
        reference.init_weights(key, pub), model.vocab_padded))
    params = make(seed_key(seed))
    engine = ServeEngine(
        model=model, params=params, max_len=serving["max_len"],
        batch_slots=serving["slots"], weight_quant=serving["weight_quant"],
        quantized_kv=serving["quantized_kv"], paged_kv=serving["paged_kv"],
        page_size=serving.get("page_size"))
    del params
    sched = engine.scheduler(
        eos_id=serving["eos_id"], chunk_size=serving["chunk_size"],
        ragged=serving["ragged"],
        prefill_lanes=serving.get("prefill_lanes", 1))
    reqs = [Request(rid=r["rid"], prompt=r["prompt"], max_new=r["max_new"],
                    arrival=r["arrival"])
            for r in traffic.schedule(mix, serving, pub["vocab_size"], seed)]
    return Cell(cfg, reference, adapter, engine, sched, reqs)


def warm(cell: Cell, seed: int) -> None:
    """Compile every step the window uses, then rehearse the loop on one
    request (a prefill chunk, a decode tick, the EOS readback, finish and
    evict), so no eager op compiles inside the window either."""
    import jax

    from repro.serve import Request

    cell.sched.warmup([1], seed=seed)
    cell.sched.run([Request(rid=-1, prompt=np.array([1], np.int32),
                            max_new=2, arrival=0)], seed=seed, warmup=False)
    jax.effects_barrier()


def compiles_counter() -> Callable[[], int]:
    """Counts backend compilations from now on (none may fall in the
    window)."""
    import jax

    box = [0]

    def listen(event, duration, **kw):
        if "backend_compile" in event:
            box[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: box[0]


def memory_peak(chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, check=check_device, resolved: Optional[dict] = None,
        overrides: Optional[dict] = None) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax

    import correct

    c = resolved or resolve(workload)
    w = c["workload"]
    device = check(w["chips"])
    cell = build(c["config"], c["traffic"], seed, overrides)
    warm(cell, seed)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    clock = Clock(cell.sched, [r.rid for r in cell.requests], seconds,
                  trace_dir, compiles_counter(),
                  open_tick=int(c["traffic"].get("preroll_ticks", 0)))
    results, _ = cell.sched.run(cell.requests, seed=seed, warmup=False,
                                    on_tick=clock)
    if clock.t_close is None:
        raise RuntimeError(
            f"the schedule ran out after {max(clock.top)} ticks, before the "
            f"{seconds} s window closed: raise the mix's horizon_ticks")
    setup_s = clock.t0 - t_start
    in_window = clock.compiled_in_window
    peak_bytes = memory_peak(w["chips"])
    t_close = clock.t_close
    tl = timelines(results, cell.requests, t_close)
    rn = Run(cfg=cell.cfg, adapter=cell.adapter, peak=device["peak"],
             timeline=tl, top=clock.top, t_open=clock.t_open,
             t_close=t_close)
    del results, cell.engine, cell.sched
    clock.sched = None
    gc.collect()
    attempted = sum(r["arrival"] < t_close for r in tl)
    failed = sum(r["arrival"] < t_close
                 and r["status"] not in ("ok", "cancelled") for r in tl)
    e2e = end_to_end(rn)
    e2e["setup_s"] = setup_s
    s = cell.cfg["serving"]
    live = sum(r["first_tick"] is not None and r["first_tick"] < rn.t_open
               and rn.window_tokens(r) > 0 for r in tl)
    print(f"[run] {workload} seed {seed}: window ticks {rn.t_open}.."
          f"{t_close} in {rn.top[t_close] - rn.top[rn.t_open]:.3f} s, "
          f"{attempted} requests arrived "
          f"({sum(rn.in_window(r['arrival']) for r in tl)} in the window), "
          f"{sum(map(rn.window_tokens, tl))} tokens in the window "
          f"({sum(r['n_tokens'] for r in tl)} in all), {live} of "
          f"{s['slots']} slots decoding at the open, {in_window} compiles "
          f"in the window, setup {setup_s:.2f} s", file=sys.stderr)

    out_device = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": peak_bytes}
    if trace:
        import trace_reduce
        import work

        path = trace_reduce.find_trace(trace_dir)
        rn.trace = trace_reduce.reduce(path, kernels=("qragged_attn",
                                                      "wq_matmul"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        rows, sampled = work.tick_rows(
            tl, s["chunk_size"], t_close, s.get("prefill_lanes", 1),
            s["slots"])
        k0, k1 = clock.traced
        rn.rows, rn.sampled = rows[k0:k1], sampled[k0:k1]
        out_device["busy_s"] = rn.trace["busy_s"]
        out_device["window_s"] = rn.trace["window_s"]
        metrics = {}
        for m in c["per_layer"]:
            mod = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = mod.read(rn)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"]}

    verdict = correct.check(cell, tl, seed, seed_key(seed),
                            attempted=attempted, failed=failed)
    out = {"correct": verdict["correct"], "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": out_device}
    if trace:
        out["breakdown"] = {"device_ops": rn.trace["device_ops"],
                            "idle_gaps": rn.trace["idle_gaps"]}
    out["checks"] = verdict["checks"]
    jax.effects_barrier()
    return out
