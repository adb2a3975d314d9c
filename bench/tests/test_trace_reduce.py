"""The trace reduction on a small trace recorded on a v5e chip
(``bench/testdata/``: two ticks of ``smollm-doc-long``, gzipped): it must
give the numbers the chip run recorded beside it."""
import gzip
import json
import shutil

import pytest

import harness
import trace_reduce

DATA = harness.BENCH / "testdata"


def test_recorded_trace_reduces_to_recorded_numbers(tmp_path):
    want = json.loads((DATA / "trace.reduced.json").read_text())
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(DATA / "trace.xplane.pb.gz") as src, open(path, "wb") as f:
        shutil.copyfileobj(src, f)
    got = trace_reduce.reduce(str(path), kernels=("qragged_attn",
                                                  "wq_matmul"))
    assert got["devices"] == want["devices"] == 1
    assert got["ticks"] == want["ticks"]
    for k in ("window_s", "busy_s"):
        assert got[k] == pytest.approx(want[k], rel=1e-9)
    for k, v in want["kernel_s"].items():
        assert got["kernel_s"][k] == pytest.approx(v, rel=1e-9)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert sum(got["kernel_s"].values()) <= got["busy_s"]
    assert [n for n, _ in got["device_ops"]] \
        == [n for n, _ in want["device_ops"]]
    assert [n for n, _ in got["idle_gaps"]] \
        == [n for n, _ in want["idle_gaps"]]


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 9)]) \
        == [(0, 3), (5, 9)]
