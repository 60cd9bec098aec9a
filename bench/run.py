#!/usr/bin/env python3
"""Benchmark of the diffusion server on the chip: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json`` with its plain reference
``<config>.py`` and FLOP count ``bench/flops/<config>.py``) and a traffic
mix (``bench/traffic/<traffic>.json``).  The run builds the server the
way ``repro.launch.serve`` does, with weights drawn from the seed
(``bench/weights.py``), and drives it with closed-loop clients that
stream every denoising step.  Set-up ends at the first streamed step,
which pays the cell's only compilation; the window then runs for
``--seconds``.  Afterwards the run compares a sample of the steps served
in the window with the reference (``bench/check.py``).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window by the
readers in ``bench/metrics/``.  The last line of standard output is one
JSON object; a run off the chip, or on fewer chips than the cell asks
for, prints none and exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def set_environment():
    """Fixed in-checkout compile cache, every program cached, untuned
    dispatch blocks; set before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".jax_compilation_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # A name that never exists: no autotune file steers a plan.
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(
        ROOT, ".bench-untuned.autotune.json")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    ap.add_argument("--control", action="store_true",
                    help="also read the float8 control on the compared "
                         "steps (for setting limits; not a benchmark run)")
    args = ap.parse_args(argv)
    set_environment()
    try:
        from bench import harness
    except ImportError as e:
        print(f"bench: cannot import the benchmark or the program: {e}",
              file=sys.stderr)
        return 2
    try:
        bench, cell, config, traffic = harness.load_cell(args.workload)
        result = harness.run_cell(
            bench, cell, config, traffic, seed=args.seed,
            seconds=args.seconds, trace=bool(args.trace),
            trace_dir=args.trace_dir, control=args.control,
            t_start=T_START, require_chip=True)
    except (harness.BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
