"""The harness finds every cell's files by name, refuses what is not a chip
run, and drives a whole run (set-up, window, metrics, comparison) on the CPU
at a tiny size."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import harness
import tiny

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_every_name_resolves_to_its_files():
    names = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in names
        c = harness.resolve(w["name"], SPEC)
        fam = c["config"]["reference"]
        for sub in ("reference", "adapters"):
            harness.load_module(harness.BENCH / sub / f"{fam}.py")
        assert (harness.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
        assert c["per_layer"]
    for m in SPEC["per_layer"]:
        mod = harness.load_module(harness.BENCH / "metrics"
                                  / f"{m['name']}.py")
        assert callable(mod.read)
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for c in SPEC["configs"]:
        assert (harness.ROOT / c["file"]).is_file()


def run_cli(env_extra, cwd=None, root=harness.ROOT):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0"],
        env=env, cwd=cwd or root, capture_output=True, text=True,
        timeout=300)


def test_refuses_a_cpu_backend():
    p = run_cli({"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_forced_kernel_dispatch():
    p = run_cli({"JAX_PLATFORMS": "cpu", "REPRO_KERNELS_FORCE": "interpret"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "REPRO_KERNELS_FORCE" in p.stderr


def test_refuses_an_unknown_device_kind(monkeypatch):
    import jax

    from repro.kernels import ops

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 giant")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    monkeypatch.setattr(ops, "FORCE", None)
    monkeypatch.setattr(ops, "is_hardware_dispatch", lambda: True)
    with pytest.raises(harness.Refused, match="not in bench/peaks.json"):
        harness.check_device(1)


def test_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ cannot run."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli({"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}, root=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", CELLS)
def test_tiny_run_end_to_end(workload, trace):
    c = tiny.cell(workload, limit=1.0)
    out = harness.run(workload, 2 ** 40 + 17, 2.0, trace,
                      t_start=__import__("time").perf_counter(),
                      check=tiny.fake_device, resolved=c)
    json.dumps(out)
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = ({m["name"] for m in c["per_layer"]} if trace
            else {m["name"] for m in c["end_to_end"]})
    if trace:
        # on the CPU there is no device plane: the trace-read metrics are
        # left out, never reported as 0
        want -= {"device.idle_share", "qragged_attn_roofline",
                 "wq_matmul_roofline"}
        assert out["device"]["busy_s"] == 0.0
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert m["value"] == m["value"] and m["value"] >= 0


def test_window_counts_only_its_own_ticks():
    """Ticks before the window (the pre-run) add no token, wait or gap."""
    top = {t: float(t) for t in range(12)}
    tl = [  # prefilled before the open, decodes across it
        {"rid": 0, "arrival": 0, "first_tick": 2, "n_tokens": 6},
        # arrives in the window, first token at tick 6, cut at the close
        {"rid": 1, "arrival": 5, "first_tick": 6, "n_tokens": 4},
        # arrives in the window and waits to the close
        {"rid": 2, "arrival": 8, "first_tick": None, "n_tokens": 0}]
    run = harness.Run(cfg={"serving": {"slots": 4}}, adapter=None, peak={},
                      timeline=tl, top=top, t_open=4, t_close=10)
    assert [run.window_tokens(r) for r in tl] == [4, 4, 0]
    e2e = harness.end_to_end(run)
    assert e2e["output_tok_s"] == 8 / 6
    # waits: rid 1 from tick 5 to the end of tick 6, rid 2 from 8 to 10
    assert e2e["ttft_p95_ms"] == 2e3
    assert e2e["itl_p95_ms"] == 1e3
    occ = harness.load_module(harness.BENCH / "metrics"
                              / "sched.slot_occupancy.py")
    assert occ.read(run) == 100.0 * 8 / (6 * 4)
    ttft = harness.load_module(harness.BENCH / "metrics"
                               / "sched.ttft_ticks_p95.py")
    assert ttft.read(run) == 2.0
