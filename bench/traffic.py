"""The one traffic generator: a mix's data file plus a configuration's serving
geometry and a seed -> the request schedule, on the scheduler's tick clock.

A mix file (``bench/traffic/<mix>.json``) holds only parameters:

``prompt`` / ``output``
    ``{"median", "sigma", "min", "max"}`` of a clipped lognormal length.
``arrivals``
    ``{"kind": "even"}`` (single arrivals at a constant rate, one every
    ``1 / rate`` ticks) or ``{"kind": "burst", "size": n}`` (``n`` requests
    on one tick).
``load``
    offered load as a share of the configuration's capacity in requests per
    tick (see :func:`capacity`), which fixes the rate: an even mix arrives
    at ``load * capacity`` per tick, a burst mix every
    ``round(size / (load * capacity))`` ticks.
``block``
    requests per block (a burst mix uses its burst size).  The lengths are
    stratified: every block holds the same lengths, the ``block`` quantiles
    ``(i + 0.5) / block`` of each distribution, in an order drawn from the
    run's seed (prompt and output lengths drawn apart).  The seed also draws
    the token ids.  So every seed offers the same work in another order.
``preroll_ticks``
    ticks of the schedule run as set-up before the measured window opens,
    so that the window starts on a loaded server rather than an empty one.
``horizon_ticks``
    how many ticks of arrivals to generate; a run that reaches the end of
    the schedule before its window closes is an error, not a result.

Prompt token ids are uniform over the vocabulary and no two prompts share a
prefix.  The tick clock is the scheduler's own: one tick per batched step,
idle ticks skipped (``Scheduler.run``).
"""
from __future__ import annotations

import itertools
import math
from statistics import NormalDist
from typing import List

import numpy as np


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a clipped lognormal, as whole tokens."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = [spec["median"] * math.exp(spec["sigma"] * zi) for zi in z]
    return np.clip(np.round(vals), spec["min"], spec["max"]).astype(np.int64)


def block_size(mix: dict) -> int:
    arr = mix["arrivals"]
    return int(arr["size"]) if arr["kind"] == "burst" else int(mix["block"])


def capacity(mix: dict, serving: dict) -> dict:
    """Requests per tick the scheduler can take, from its geometry alone.

    Prefill: each lane carries one ``chunk_size`` chunk of one prompt per
    tick, so a prompt holds a lane ``ceil(P / chunk)`` ticks.  Decode: a
    request holds a slot from its first chunk to its last token, about
    ``ceil(P / chunk) + output`` ticks.  Capacity is the lower of the two.
    """
    n = block_size(mix)
    p = quantiles(mix["prompt"], n)
    o = np.minimum(quantiles(mix["output"], n), serving["max_len"] - p)
    chunk = serving["chunk_size"]
    lanes = serving.get("prefill_lanes", 1)
    ticks = np.ceil(p / chunk)
    prefill = lanes / float(np.mean(ticks))
    decode = serving["slots"] / float(np.mean(ticks + o))
    return {"prefill": prefill, "decode": decode,
            "requests_per_tick": min(prefill, decode),
            "mean_prompt": float(np.mean(p)), "mean_output": float(np.mean(o))}


def schedule(mix: dict, serving: dict, vocab: int, seed: int) -> List[dict]:
    """[{rid, arrival, prompt (np.int32), max_new}] in arrival order."""
    rng = np.random.default_rng(seed % (2 ** 63))
    n = block_size(mix)
    plens = quantiles(mix["prompt"], n)
    olens = quantiles(mix["output"], n)
    rate = mix["load"] * capacity(mix, serving)["requests_per_tick"]
    kind = mix["arrivals"]["kind"]
    if kind == "burst":
        every = max(1, round(n / rate))
    elif kind != "even":
        raise ValueError(f"unknown arrival kind {kind!r}")
    horizon = int(mix["horizon_ticks"])
    limit = serving["max_len"]
    reqs: List[dict] = []
    for b in itertools.count():
        p = rng.permutation(plens)
        o = rng.permutation(olens)
        for i in range(n):
            k = b * n + i
            tick = b * every if kind == "burst" else int(k / rate)
            if tick >= horizon:
                return reqs
            plen = int(p[i])
            reqs.append({"rid": k, "arrival": tick,
                         "prompt": rng.integers(0, vocab, plen,
                                                dtype=np.int32),
                         "max_new": int(min(o[i], limit - plen))})
