#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds, and print one line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` at the checkout's root (see ``bench/harness.py``).  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is one JSON object; the numbers
compared to decide ``correct`` are the last lines of standard error.  A run
that finds no TPU, fewer chips than the cell asks for, forced kernel
dispatch or an unknown device kind exits non-zero and prints no result.

JAX's persistent compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR``
if set, else to ``<checkout>/.jax_cache``, so only a cell's first run in a
checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sys.path.insert(0, str(ROOT / "bench"))
    import harness

    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
