"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark keeps.

Read with ``jax.profiler.ProfileData`` and nothing else.  Device planes are
the ones named ``/device:TPU:<n>``; their op events are those of the line
named ``XLA Ops`` (or, where a plane has no such line, of every line).  The
window is the span of the harness's own host annotations (``bench.tick``),
so device time outside the measured ticks is left out.

* busy: the union of device op intervals inside the window, averaged over
  the device planes; idle share = 1 - busy / window.
* kernel time: the summed duration of the op events whose name, or any
  string stat of the event (its HLO op, its long name), contains the
  kernel's stable name (``qragged_attn``, ``wq_matmul``).
* top ops: self time (nested events, such as a loop and the ops of its
  body, counted once) by HLO instruction name, largest first.
* idle gaps: the longest stretches inside the window with no device op,
  each named by the host annotation that holds its midpoint
  (``bench.tick`` with its tick number).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE = re.compile(r"^/device:TPU:\d+$")
HOST_SPAN = "bench.tick"


def find_trace(log_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _short(name: str) -> str:
    """``%while.70 = (s32[], ...) while(...)`` -> ``while.70``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _strings(ev) -> str:
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def reduce(path: str, kernels: Sequence[str] = (), top: int = 10) -> dict:
    """Reduce one trace file; see the module docstring."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[int, int, str]] = []
    devices = []
    for plane in pd.planes:
        if DEVICE.match(plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            devices.append([ev for ln in ops for ev in ln.events])
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == HOST_SPAN:
                    tick = next((v for k, v in ev.stats if k == "tick"), "")
                    spans.append((int(ev.start_ns), int(ev.end_ns),
                                  f"{HOST_SPAN} {tick}".strip()))
    if not spans:
        raise ValueError(f"{path}: no {HOST_SPAN} host spans")
    w0 = min(s for s, _, _ in spans)
    w1 = max(e for _, e, _ in spans)
    window_ns = w1 - w0
    busy_ns: List[float] = []
    kernel_ns: Dict[str, float] = {k: 0.0 for k in kernels}
    op_ns: Dict[str, float] = {}
    gaps: List[Tuple[int, int]] = []
    for events in devices:
        iv = []
        for ev in events:
            s, e = max(int(ev.start_ns), w0), min(int(ev.end_ns), w1)
            if e <= s:
                continue
            iv.append((s, e, ev))
            if kernels:
                text = _strings(ev)
                for k in kernels:
                    if k in text:
                        kernel_ns[k] += e - s
        iv.sort(key=lambda x: (x[0], -x[1]))
        stack: List[list] = []          # [end, name, self ns]
        for s, e, ev in iv:
            while stack and stack[-1][0] <= s:
                _, name, own = stack.pop()
                op_ns[name] = op_ns.get(name, 0.0) + own
            if stack:
                stack[-1][2] -= e - s   # a child's time is not its parent's
            stack.append([e, _short(ev.name), e - s])
        for _, name, own in stack:
            op_ns[name] = op_ns.get(name, 0.0) + own
        u = _union([(s, e) for s, e, _ in iv])
        busy_ns.append(sum(e - s for s, e in u))
        edges = [w0] + [x for se in u for x in se] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = max(len(devices), 1)

    def label(mid: float) -> str:
        held = [(e - s, name) for s, e, name in spans if s <= mid < e]
        return min(held)[1] if held else "outside bench.tick"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "devices": len(devices),
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy_ns) / n * 1e-9,
        "kernel_s": {k: v / n * 1e-9 for k, v in kernel_ns.items()},
        "device_ops": [[k, v / n * 1e-9] for k, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label((s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
        "ticks": len(spans),
    }
