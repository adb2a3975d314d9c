"""``correct`` comes out false when the timed path is broken underneath, and
for the control (the program's own next precision down, int4 weights).

Each test skips the harness's look for a chip and drives the rest of a run
at a tiny size on the CPU.  The limit is set for that size as the cells'
limits are for theirs: sound runs read 0.07–0.44 and the control 2.18–3.46
(CPU, d_model 64), so 1.0 lies between them.  The faults a served
cell can have (``bench/faults.py``): a step that returns its state
unchanged, half of the batch left out, a token altered where it is
produced.  No cell spans chips, so there is no exchange between chips to
leave out.
"""
import time

import pytest

import faults
import harness
import tiny

CELLS = [w["name"] for w in
         harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"]]


LIMIT = 1.0


def run(workload, seed=2 ** 35 + 3, overrides=None):
    c = tiny.cell(workload, limit=LIMIT)
    return harness.run(workload, seed, 2.0, False, t_start=time.perf_counter(),
                       check=tiny.fake_device, resolved=c,
                       overrides=overrides)


def assert_caught(out):
    assert out["correct"] is False
    assert out["checks"]["logit_gap_max"]["value"] \
        > out["checks"]["logit_gap_max"]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = run(workload)
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_state_left_unchanged(workload):
    with faults.planted("state"):
        assert_caught(run(workload))


@pytest.mark.parametrize("workload", CELLS)
def test_half_the_batch_left_out(workload):
    with faults.planted("half"):
        assert_caught(run(workload))


@pytest.mark.parametrize("workload", CELLS)
def test_token_altered_where_produced(workload):
    with faults.planted("token"):
        assert_caught(run(workload))


@pytest.mark.parametrize("workload", CELLS)
def test_control_int4_weights_fails(workload):
    assert_caught(run(workload, overrides={"weight_quant": "int4"}))
