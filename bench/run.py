#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 bench/run.py --workload hbm-easy-open --seed 7 --seconds 10 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled window. The last line of standard output
is the result as one JSON object. Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.

JAX's persistent compilation cache is always ``bench/.jax_cache`` in this
checkout, whatever ``JAX_COMPILATION_CACHE_DIR`` said, and the variable is
set to it for the program: two checkouts never share compiled programs, and
only a cell's first run in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")


def use_own_cache(jax) -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    from bench import harness, registry

    try:
        cell = registry.cell(args.workload)
        devices = harness.require_chips(int(cell["workload"]["chips"]))
    except (LookupError, harness.NoChip) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    use_own_cache(jax)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START, devices=devices)
    harness.emit(result)
    harness.report_checks(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
