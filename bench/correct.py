"""How ``correct`` is decided: served tokens against the plain reference.

Once the window has closed and the program is freed, a sample of the
requests that were served tokens is drawn from the seed: the request with
the most tokens, then finished requests, then requests cut at the close,
until it holds ``MIN_TOKENS`` tokens in ``MIN_REQUESTS`` requests or more,
or ``MAX_REQUESTS`` requests.  For each, the float32 reference
(``bench/reference/<family>.py``, weights drawn again from the same seed)
runs once over the prompt followed by the served tokens.  At the position
that produced served token ``i`` it reads the gap by which that token's
logit lies below the reference's best logit there.  Greedy decoding with
exact arithmetic gives gap 0 everywhere; the program's int8 weights, int8
KV cache and bfloat16 activations move it off 0 a little; a lower
precision, a skipped write or a wrong token moves it far.

The number compared is the widest gap over the sample, against the
configuration's ``correct.logit_gap_max``.  A run is also incorrect when a
request fails (any terminal status but ``ok`` or the harness's own
``cancelled`` at the close) or when no token was served to compare.
"""
from __future__ import annotations

import json
import math
import sys
import time
from typing import Dict, List

import numpy as np

MIN_TOKENS = 256
MIN_REQUESTS = 8
MAX_REQUESTS = 16
BUCKET = 512


def sample(timeline: List[dict], seed: int) -> List[dict]:
    """The requests compared: the one with the most served tokens, then
    finished requests in seeded order, then requests cut at the close."""
    served = [r for r in timeline if r["n_tokens"] > 0]
    if not served:
        return []
    longest = max(served, key=lambda r: (r["n_tokens"], -r["rid"]))
    rng = np.random.default_rng(seed % (2 ** 63))
    rest = []
    for done in (True, False):
        group = [r for r in served if r is not longest
                 and (r["status"] == "ok") == done]
        rest += [group[i] for i in rng.permutation(len(group))]
    out, n = [longest], longest["n_tokens"]
    for r in rest:
        if (n >= MIN_TOKENS and len(out) >= MIN_REQUESTS) \
                or len(out) >= MAX_REQUESTS:
            break
        out.append(r)
        n += r["n_tokens"]
    return out


_FNS: Dict[str, object] = {}


def _gap_fn(reference, pub: dict):
    import jax
    import jax.numpy as jnp

    key = reference.__name__ + json.dumps(pub, sort_keys=True)
    if key not in _FNS:
        def gaps(w, tokens, rows, served):
            h = reference.hidden(w, tokens, pub)
            lg = reference.logits(w, jnp.take(h, rows, axis=0))
            got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
            return jnp.max(lg, axis=-1) - got

        _FNS[key] = jax.jit(gaps)
    return _FNS[key]


def token_gaps(reference, w, pub: dict, prompt, tokens) -> np.ndarray:
    """Reference logit gap of every served token of one request."""
    import jax.numpy as jnp

    p, n = len(prompt), len(tokens)
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(tokens[:-1], np.int32)])
    length = -(-len(seq) // BUCKET) * BUCKET
    rows_n = -(-n // 128) * 128
    padded = np.zeros(length, np.int32)
    padded[:len(seq)] = seq
    rows = np.zeros(rows_n, np.int32)
    rows[:n] = np.arange(p - 1, p - 1 + n)
    served = np.zeros(rows_n, np.int32)
    served[:n] = tokens
    g = _gap_fn(reference, pub)(w, jnp.asarray(padded), jnp.asarray(rows),
                                jnp.asarray(served))
    return np.asarray(g)[:n]


def check(cell, timeline: List[dict], seed: int, key, *, attempted: int,
          failed: int) -> dict:
    """The verdict and every number compared beside its limit."""
    import jax

    pub = cell.cfg["published"]
    limit = cell.cfg["correct"]["logit_gap_max"]
    t0 = time.perf_counter()
    picked = sample(timeline, seed)
    gaps: List[float] = []
    if picked:
        w = jax.jit(lambda k: cell.reference.init_weights(k, pub))(key)
        for r in picked:
            gaps.extend(token_gaps(cell.reference, w, pub, r["prompt"],
                                   r["tokens"]).tolist())
        del w
    widest = max(gaps) if gaps else math.nan
    checks = {
        "logit_gap_max": {"value": widest, "limit": limit},
        "tokens_compared": {"value": len(gaps), "limit": 1},
        "failed_requests": {"value": failed, "limit": 0},
    }
    ok = bool(gaps) and limit is not None and widest <= limit \
        and failed == 0
    print(f"[correct] {len(picked)} requests, {len(gaps)} served tokens "
          f"compared in {time.perf_counter() - t0:.2f} s; median gap "
          f"{float(np.median(gaps)) if gaps else 'n/a'}", file=sys.stderr)
    print(f"logit_gap_max {widest!r} (limit <= {limit!r})", file=sys.stderr)
    print(f"tokens_compared {len(gaps)} (limit >= 1)", file=sys.stderr)
    print(f"failed_requests {failed} of {attempted} (limit 0)",
          file=sys.stderr)
    return {"correct": ok, "checks": checks}
