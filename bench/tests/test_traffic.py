"""The traffic generator offers every seed the same work, in another order."""
import collections

import pytest

import harness
import traffic

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")


def shape(reqs, n):
    return [(r["arrival"], len(r["prompt"]), r["max_new"]) for r in reqs[:n]]


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_same_work_for_every_seed(cell):
    c = harness.resolve(cell, SPEC)
    mix, serving = c["traffic"], c["config"]["serving"]
    vocab = c["config"]["published"]["vocab_size"]
    a = traffic.schedule(mix, serving, vocab, 3)
    b = traffic.schedule(mix, serving, vocab, 2 ** 40 + 9)
    n = traffic.block_size(mix)
    assert len(a) == len(b) > 20 * n
    assert [r["arrival"] for r in a] == [r["arrival"] for r in b]
    for i in range(0, 20 * n, n):
        for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
            assert collections.Counter(map(key, a[i:i + n])) \
                == collections.Counter(map(key, b[i:i + n]))
    assert shape(a, 200) != shape(b, 200)
    assert all(r["arrival"] < mix["horizon_ticks"] for r in a)
    assert all(len(r["prompt"]) + r["max_new"] <= serving["max_len"]
               for r in a)
    assert [r["prompt"][:8].tolist() for r in a[:8]] \
        != [r["prompt"][:8].tolist() for r in b[:8]]


def test_rates_follow_capacity():
    mixes = {w["traffic"]: harness.resolve(w["name"], SPEC)
             for w in SPEC["workloads"]}
    doc = mixes["doc-long"]
    cap = traffic.capacity(doc["traffic"], doc["config"]["serving"])
    assert cap["requests_per_tick"] == cap["decode"] < cap["prefill"]
    reqs = traffic.schedule(doc["traffic"], doc["config"]["serving"],
                            49152, 1)
    rate = 0.8 * cap["decode"]
    assert [r["arrival"] for r in reqs[:50]] \
        == [int(i / rate) for i in range(50)]
    burst = mixes["chat-burst"]
    cap = traffic.capacity(burst["traffic"], burst["config"]["serving"])
    assert cap["requests_per_tick"] == cap["prefill"] < cap["decode"]
    reqs = traffic.schedule(burst["traffic"], burst["config"]["serving"],
                            50280, 1)
    assert reqs[16]["arrival"] == round(16 / (0.8 * cap["prefill"])) == 21


@pytest.mark.parametrize("mix, mean_prompt, mean_output",
                         [("chat-burst", 69.5, 214.5)])
def test_block_means_match_the_source(mix, mean_prompt, mean_output):
    """A mix whose source gives means draws blocks with those means."""
    spec = harness.load_json(harness.BENCH / "traffic" / f"{mix}.json")
    n = traffic.block_size(spec)
    assert traffic.quantiles(spec["prompt"], n).mean() \
        == pytest.approx(mean_prompt, rel=0.01)
    assert traffic.quantiles(spec["output"], n).mean() \
        == pytest.approx(mean_output, rel=0.01)


def test_block_medians_match_the_source():
    """doc-long's source gives medians: its block's median is theirs."""
    spec = harness.load_json(harness.BENCH / "traffic" / "doc-long.json")
    n = traffic.block_size(spec)
    for key, median in (("prompt", 1020), ("output", 129)):
        q = traffic.quantiles(spec[key], n)
        assert (q[n // 2 - 1] + q[n // 2]) / 2 == pytest.approx(median,
                                                                 rel=0.05)
