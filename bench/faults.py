"""Faults planted under the timed path, to show that ``correct`` catches
them: ``bench/tests/test_faults.py`` plants them at a tiny size on the CPU,
``bench/calibrate.py --faults`` at a cell's own size on the chip.

``state``
    every step returns the cache it was given: nothing is written.
``half``
    the second half of every batched input of a step is left out (zeroed):
    decode slots ``B/2..B-1`` and the second half of the prefill chunk
    rows (the second lane of a two-lane ragged tick).
``token``
    every sampled token is altered where it is produced (``+1``).
"""
from __future__ import annotations

import contextlib

from repro.serve import engine as serve_engine
from repro.serve import scheduler as serve_scheduler

STEPS = ("make_ragged_step", "make_mixed_step", "make_decode_step")


def _keep_state(step):
    def stepped(params, tok, cache, *rest):
        out = step(params, tok, cache, *rest)
        return out[:-1] + (cache,)
    return stepped


def _half(step):
    def stepped(params, tok, cache, *rest):
        tok = tok.at[tok.shape[0] // 2:].set(0)
        if len(rest) > 1 and getattr(rest[1], "ndim", 0) == 2:
            chunk = rest[1].reshape(-1)
            chunk = chunk.at[chunk.shape[0] // 2:].set(0)
            rest = (rest[0], chunk.reshape(rest[1].shape)) + rest[2:]
        return step(params, tok, cache, *rest)
    return stepped


def _altered(sample):
    def sampled(logits, rng, vocab, temperature):
        return (sample(logits, rng, vocab, temperature) + 1) % vocab
    return sampled


def patches(name: str):
    """[(module, attribute, replacement)] that plant fault ``name``."""
    if name == "token":
        return [(serve_engine, "sample_tokens",
                 _altered(serve_engine.sample_tokens))]
    wrap = {"state": _keep_state, "half": _half}[name]
    out = []
    for attr in STEPS:
        make = getattr(serve_scheduler, attr)
        out.append((serve_scheduler, attr,
                    lambda *a, _m=make, **k: wrap(_m(*a, **k))))
    return out


@contextlib.contextmanager
def planted(name: str):
    """Plant fault ``name`` for the duration of the block."""
    saved = []
    try:
        for mod, attr, new in patches(name):
            saved.append((mod, attr, getattr(mod, attr)))
            setattr(mod, attr, new)
        yield
    finally:
        for mod, attr, old in reversed(saved):
            setattr(mod, attr, old)
