"""Operations and bytes that the served tokens need, whatever computes them.

Every count here is of the work the algorithm needs for the rows a tick
really carried: live rows only (decode rows of live slots and the valid rows
of prefill chunks), each slot's live K/V read once per tick and layer, each
weight matrix read once per tick.  Padded rows, inert slots, a kernel's
clamped page steps or its one-hot merges never count, so a count is the
same whatever implements the kernel, and a share of a roofline built on it
cannot pass 100%.

A tick's rows are ``[(slot, position), ...]``: the token at ``position`` of
the request held in ``slot`` (attends positions ``0..position``).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple

Rows = Sequence[Tuple[int, int]]


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline bound: the larger of compute time and memory time (s)."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])


def ragged_attention(rows: Rows, layers: int, hq: int, hkv: int, hd: int,
                     kv_bytes: int = 1, act_bytes: int = 4) -> Tuple[float,
                                                                     float]:
    """(flops, bytes) of one tick of KV-cache attention over all layers.

    Per layer: each row scores and mixes ``position + 1`` cached rows in
    every query head (QK^T and PV, 2 flops per multiply-add each); each
    slot's cached K and V rows ``0..max position`` are read once; each row's
    new K/V is written once, and its q, new k, new v and output move once
    (``act_bytes`` each, float32 at the kernel's interface).
    """
    if not rows:
        return 0.0, 0.0
    flops = sum(4.0 * hq * hd * (p + 1) for _, p in rows)
    deepest: Dict[int, int] = {}
    for s, p in rows:
        deepest[s] = max(deepest.get(s, -1), p)
    kv_read = sum(2.0 * (p + 1) * hkv * hd * kv_bytes
                  for p in deepest.values())
    per_row = (2.0 * hkv * hd * kv_bytes
               + act_bytes * (2 * hq * hd + 2 * hkv * hd))
    return layers * flops, layers * (kv_read + len(rows) * per_row)


def weight_matmuls(m: int, shapes: Iterable[Tuple[int, int]], layers: int,
                   w_bytes: float = 1.0, x_bytes: int = 2,
                   out_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of ``m`` live rows through each (K, N) weight matrix
    of every layer: 2*m*K*N flops; the int8 weights and their per-column
    float32 scales read once, the rows' inputs read and outputs written."""
    if m <= 0:
        return 0.0, 0.0
    flops = nbytes = 0.0
    for k, n in shapes:
        flops += 2.0 * m * k * n
        nbytes += k * n * w_bytes + 4.0 * n + m * (k * x_bytes
                                                   + n * out_bytes)
    return layers * flops, layers * nbytes


def model_flops(rows: Rows, sampled: int, token_flops, d: int,
                vocab: int) -> float:
    """Model FLOPs of one tick: every live row through the whole model
    (``token_flops(context length)``), plus the output head for the rows
    that sampled a token."""
    return sum(token_flops(p + 1) for _, p in rows) + 2.0 * d * vocab * sampled


def tick_rows(timeline: List[dict], chunk: int, t_close: int,
              lanes: int, slots: int) -> Tuple[List[list], List[int]]:
    """Rebuild every tick's live rows from the request timelines.

    ``timeline`` entries: ``{"rid", "arrival", "plen", "first_tick",
    "n_tokens"}`` (``first_tick`` is the tick that emitted the first token,
    None if none came).  A request's prompt runs one ``chunk`` per tick in a
    lane, its last chunk in ``first_tick``; token ``i >= 1`` is computed in
    tick ``first_tick + i`` from the row at ``plen + i - 1``.  A request
    still prefilling when the window closed has no first token: its start
    is recovered from the lanes and slots the others held (first come,
    first served; ``lanes`` prompts at a time, each holding one of ``slots``
    slots from its first chunk to its last token).  Returns (rows per tick,
    sampled rows per tick) for ticks ``0 .. t_close - 1``.
    """
    rows: List[list] = [[] for _ in range(t_close)]
    sampled = [0] * t_close
    busy = [0] * (t_close + 1)
    held = [0] * (t_close + 1)
    waiting = []
    for r in sorted(timeline, key=lambda r: (r["arrival"], r["rid"])):
        if r["arrival"] >= t_close:
            continue
        n_chunks = -(-r["plen"] // chunk)
        if r["first_tick"] is None:
            waiting.append((r, n_chunks))
            continue
        start = r["first_tick"] - n_chunks + 1
        _prefill(rows, busy, r, start, n_chunks, chunk, t_close)
        for k in range(start, min(r["first_tick"] + r["n_tokens"], t_close)):
            held[k] += 1
        sampled[r["first_tick"]] += 1
        for i in range(1, r["n_tokens"]):
            tick = r["first_tick"] + i
            rows[tick].append((r["rid"], r["plen"] + i - 1))
            sampled[tick] += 1
    floor = 0
    for r, n_chunks in waiting:
        full = [k for k in range(t_close) if busy[k] >= lanes]
        start = max(r["arrival"], floor, full[-1] + 1 if full else 0)
        while start < t_close and held[start] >= slots:
            start += 1
        if start >= t_close:
            break                   # still queued when the window closed
        _prefill(rows, busy, r, start, n_chunks, chunk, t_close)
        for k in range(start, t_close):
            held[k] += 1
        floor = start
    return rows, sampled


def _prefill(rows, busy, r, start, n_chunks, chunk, t_close):
    for j in range(n_chunks):
        tick = start + j
        if tick >= t_close:
            return
        busy[tick] += 1
        hi = min(r["plen"], (j + 1) * chunk)
        rows[tick].extend((r["rid"], p) for p in range(j * chunk, hi))
