#!/usr/bin/env python3
"""Readings that set a cell's ``correct`` limit, in one process on the chip.

    python bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 11,12,... --control-seeds 21,22,23 \\
        [--faults state:31,half:32] [--out <file.jsonl>]

Runs the cell as ``bench/run.py`` does (same pre-run, window and
comparison) once per seed with the configuration as stated, once per
control seed with the program's own next precision down (``weight_quant:
int4``), and once per ``fault:seed`` with that fault of ``bench/faults.py``
planted under the timed path.  It prints one JSON line per run: the widest
reference logit gap, the tokens compared and the end-to-end metrics.  The
lower reading of the limit is the largest sound gap, the upper the smallest
control gap (``PERF.md`` gives both with the limit set between them); each
fault has to read above the limit.  The benchmark's own runs never run
this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sys.path.insert(0, str(ROOT / "bench"))
    import harness  # puts the program's src/ on the path first

    import faults

    plan = [(int(s), None, None) for s in args.seeds.split(",") if s] + \
        [(int(s), {"weight_quant": "int4"}, None)
         for s in args.control_seeds.split(",") if s] + \
        [(int(s), None, f) for f, s in
         (x.split(":") for x in args.faults.split(",") if x)]
    for seed, over, fault in plan:
        t0 = time.perf_counter()
        line = {"workload": args.workload, "seed": seed,
                "control": over is not None, "fault": fault}
        try:
            with faults.planted(fault) if fault else \
                    contextlib.nullcontext():
                out = harness.run(args.workload, seed, args.seconds, False,
                                  t_start=t0, overrides=over)
            checks = out["checks"]
            line.update(
                logit_gap_max=checks["logit_gap_max"]["value"],
                tokens_compared=checks["tokens_compared"]["value"],
                failed=out["failed"], attempted=out["attempted"],
                metrics={k: v["value"] for k, v in out["metrics"].items()})
        except Exception as e:      # a crashed control or fault has failed
            line["error"] = repr(e)[:500]
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
