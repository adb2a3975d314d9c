"""Work counts: a hand-worked tick, and the per-tick rows rebuilt from the
request timelines against the rows the scheduler really sent."""
import types

import jax
import numpy as np
import pytest

import harness
import tiny
import work
from repro.serve.lanes import assemble_ragged_tick

# smollm-135m: 30 layers, 9 query / 3 KV heads of 64
L, HQ, HKV, HD = 30, 9, 3, 64


def hand_tick():
    """3 decode rows at lengths 10/200/1500 (slots 0-2) and one 256-row
    chunk at positions 1024..1279 (slot 3), built by the program's own
    tick assembly for 64 slots and 2 lanes of 256 (576 rows, one lane
    inert), as (sids, poss) with -1 on every padded row."""
    slots = [None] * 64
    for j, length in enumerate((10, 200, 1500)):
        # the decode row sits at plen + emitted - 1 = length - 1
        slots[j] = types.SimpleNamespace(plen=length - 1, emitted=1)
    lane = types.SimpleNamespace(prompt=np.zeros(1500, np.int32),
                                 next_start=1024, slot=3)
    rt = assemble_ragged_tick(slots, [lane], nslots=64, n_lanes=2, chunk=256,
                              pad_id=0, token_budget=None, n_active=3)
    return rt.sids, rt.poss


def test_hand_worked_tick_counts_live_rows_only():
    sids, poss = hand_tick()
    assert len(poss) == 576 and int((poss >= 0).sum()) == 259
    rows = [(int(s), int(p)) for s, p in zip(sids, poss) if p >= 0]
    flops, nbytes = work.ragged_attention(rows, L, HQ, HKV, HD)
    ctx = 10 + 200 + 1500 + sum(p + 1 for p in range(1024, 1280))
    assert ctx == 296750
    assert flops == L * 4 * HQ * HD * ctx == 20_511_360_000
    kv_read = 2 * HKV * HD * (10 + 200 + 1500 + 1280)
    per_row = 2 * HKV * HD + 4 * (2 * HQ * HD + 2 * HKV * HD)
    assert nbytes == L * (kv_read + 259 * per_row) == 85_167_360
    # the kernel's grid walks all 576 rows over all 16 pages of 128: that
    # padded work is ~4x what the served rows need, and none of it counts
    grid_flops = L * 4 * HQ * HD * 576 * 16 * 128
    assert grid_flops > 3.9 * flops


def test_weight_matmul_counts_live_rows_and_weights_once():
    f, b = work.weight_matmuls(259, [(576, 1536)], 30)
    assert f == 30 * 2 * 259 * 576 * 1536
    assert b == 30 * (576 * 1536 + 4 * 1536 + 259 * (576 * 2 + 1536 * 2))
    assert work.weight_matmuls(0, [(576, 1536)], 30) == (0.0, 0.0)


@pytest.mark.parametrize("workload", ["smollm-doc-long", "mamba-chat-burst"])
def test_tick_rows_rebuilt_from_timelines_match_the_scheduler(workload):
    """Each tick's live rows as the scheduler sent them to its jitted steps
    (positions for the ragged step, row counts for the mixed and decode
    steps) equal
    the rows ``work.tick_rows`` rebuilds from the timelines afterwards."""
    c = tiny.cell(workload, limit=1.0)
    s = c["config"]["serving"]
    cell = harness.build(c["config"], c["traffic"], 5)
    harness.warm(cell, 5)
    sent = []
    if s["ragged"]:
        step = cell.sched._masked_ragged

        def spy(params, tok, cache, rng, active, ctok, sids, poss, *rest):
            p = np.asarray(poss)
            sent.append(sorted(int(x) for x in p[p >= 0]))
            return step(params, tok, cache, rng, active, ctok, sids, poss,
                        *rest)

        cell.sched._masked_ragged = spy
    else:
        step = cell.sched._masked_mixed

        def spy(params, tok, cache, rng, active, ctok, slot, start, length,
                *rest):
            sent.append(int(np.asarray(active).sum()) + int(length))
            return step(params, tok, cache, rng, active, ctok, slot, start,
                        length, *rest)

        decode = cell.sched._masked_decode

        def spy_decode(params, tok, cache, rng, active, *rest):
            sent.append(int(np.asarray(active).sum()))
            return decode(params, tok, cache, rng, active, *rest)

        cell.sched._masked_mixed = spy
        cell.sched._masked_decode = spy_decode
    clock = harness.Clock(cell.sched, [r.rid for r in cell.requests], 1.5)
    results, _ = cell.sched.run(cell.requests, seed=5, warmup=False,
                                on_tick=clock)
    tl = harness.timelines(results, cell.requests, clock.t_close)
    rows, sampled = work.tick_rows(tl, s["chunk_size"], clock.t_close,
                                   s["prefill_lanes"], s["slots"])
    if s["ragged"]:
        rebuilt = [sorted(p for _, p in r) for r in rows if r]
        sent = [x for x in sent if x]
    else:
        rebuilt = [len(r) for r in rows if r]
        sent = [x for x in sent if x]
    assert len(sent) > 20
    assert rebuilt == sent
    assert sum(sampled) == sum(r["n_tokens"] for r in tl)
    jax.effects_barrier()
