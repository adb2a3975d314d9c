"""The program's own record of its ticks, reduced for the benchmark.

The scheduler marks each tick's host phases with ``serve.*`` spans on the
profiler's clock (``src/repro/serve/trace.py``), keeps one record per step
(``ServeStats.ticks``) and the tick each request's prefill began
(``RequestResult.started_at``).  This module reduces them; a program that
records none gives empty results (no spans, no records), never zeros.

From a trace (``reduce``), inside the window of the harness's ``bench.tick``
spans, as ``trace_reduce`` defines it:

* ``spans``: per tick, ``[tick, serve.tick seconds, seconds of the union of
  its serve.readback spans]`` (the waits on the device);
* ``idle_by_span``: the device's idle seconds, each stretch given to the
  innermost ``serve.*`` span covering it, or to ``bench.tick`` where none
  does; the values sum to the window less the busy time;
* ``idle_gaps``: the longest idle stretches, each named by the innermost host
  span (``serve.*`` or ``bench.tick``) holding its midpoint, with its tick.
"""
from __future__ import annotations

from bisect import bisect_right
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from trace_reduce import DEVICE, HOST_SPAN, _union

PROGRAM = "serve."
Span = Tuple[int, int, str]


def _innermost(spans: List[Span]) -> List[Span]:
    """Cut nested spans into consecutive pieces, each named by the shortest
    span covering it; stretches no span covers are left out."""
    cuts = sorted({x for s, e, _ in spans for x in (s, e)})
    todo = sorted(spans)
    out: List[Span] = []
    active: List[Span] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(todo) and todo[i][0] <= a:
            active.append(todo[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        if active:
            out.append((a, b, min(active, key=lambda sp: sp[1] - sp[0])[2]))
    return out


def _attribute(gaps: List[Tuple[int, int]], pieces: List[Span],
               out: Dict[str, float]) -> None:
    """Add each gap's nanoseconds under the pieces' names; the rest of a gap
    goes to ``bench.tick``."""
    starts = [a for a, _, _ in pieces]
    for s, e in gaps:
        left = e - s
        i = max(bisect_right(starts, s) - 1, 0)
        while i < len(pieces) and pieces[i][0] < e:
            a, b, name = pieces[i]
            part = min(b, e) - max(a, s)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                left -= part
            i += 1
        out[HOST_SPAN] = out.get(HOST_SPAN, 0.0) + left


def reduce(path: str, top: int = 10) -> dict:
    """Reduce one trace file; see the module docstring."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: List[Tuple[int, int, str, str]] = []     # + the tick argument
    devices = []
    for plane in pd.planes:
        if DEVICE.match(plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            devices.append([(int(ev.start_ns), int(ev.end_ns))
                            for ln in ops for ev in ln.events])
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == HOST_SPAN or ev.name.startswith(PROGRAM):
                    tick = next((v for k, v in ev.stats if k == "tick"), "")
                    host.append((int(ev.start_ns), int(ev.end_ns), ev.name,
                                 str(tick)))
    window = [(s, e) for s, e, name, _ in host if name == HOST_SPAN]
    if not window:
        raise ValueError(f"{path}: no {HOST_SPAN} host spans")
    w0 = min(s for s, _ in window)
    w1 = max(e for _, e in window)
    program = sorted(h for h in host if h[2] != HOST_SPAN)
    pieces = _innermost([(s, e, name) for s, e, name, _ in program])
    idle: Dict[str, float] = {}
    gaps: List[Tuple[int, int]] = []
    for events in devices:
        u = _union([(max(s, w0), min(e, w1)) for s, e in events
                    if min(e, w1) > max(s, w0)])
        edges = [w0] + [x for se in u for x in se] + [w1]
        mine = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        _attribute(mine, pieces, idle)
        gaps += mine
    n = max(len(devices), 1)
    named = [(s, e, f"{name} {tick}".strip()) for s, e, name, tick in host]

    def label(mid: float) -> str:
        held = [(e - s, name) for s, e, name in named if s <= mid < e]
        return min(held)[1] if held else "outside bench.tick"

    waits: Dict[str, List[Tuple[int, int]]] = {}
    for s, e, name, tick in program:
        if name == PROGRAM + "readback":
            waits.setdefault(tick, []).append((s, e))
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "spans": [[int(tick), (e - s) * 1e-9,
                   sum(b - a for a, b in _union(waits.get(tick, []))) * 1e-9]
                  for s, e, name, tick in program
                  if name == PROGRAM + "tick"],
        "idle_by_span": {k: v / n * 1e-9 for k, v in
                         sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[label((s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }


def host_ms_per_tick(spans: Sequence[Sequence[float]]) -> Optional[float]:
    """Mean over ticks of ``serve.tick`` less its waits on the device: the
    scheduler's own host time per tick, in ms (None: no spans)."""
    if not spans:
        return None
    return 1e3 * sum(dur - wait for _, dur, wait in spans) / len(spans)


def queue_wait_ticks_p95(timeline: Sequence[dict], t_open: int,
                         t_close: int) -> Optional[float]:
    """p95 over the requests that arrived in ``[t_open, t_close)`` of the
    ticks from arrival to the step that carried their first prefill chunk
    (``started``); one not started by the close counts ``t_close -
    arrival``.  None where the program records no ``started``."""
    mine = [r for r in timeline if t_open <= r["arrival"] < t_close]
    if not mine or any(r.get("started") is None for r in mine):
        return None
    waits = [(r["started"] if r["started"] >= 0 else t_close) - r["arrival"]
             for r in mine]
    return float(np.percentile(waits, 95))


def live_row_share(records: Optional[Sequence]) -> Optional[float]:
    """Share of the rows the compiled steps computed that were live: decode
    rows plus prefill chunk rows, over ``step_rows``, in % (None: no
    records)."""
    if not records:
        return None
    live = sum(r.decode_rows + sum(c[2] for c in r.chunks) for r in records)
    return 100.0 * live / sum(r.step_rows for r in records)
