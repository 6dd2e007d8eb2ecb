#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, on the chip.

    python3 bench/control.py --workload hbm-easy-open --seed 1 --seed 2 \\
        --seed 3 --seconds 10

For each seed it makes the collection and the requests a run of the cell
would make, answers them with the plain reference computed from bfloat16
inputs (the next precision below the configuration's float32) in the
program's place, and prints the numbers ``bench/run.py`` would compare,
beside the cell's limits. Every seed has to come out not correct. The
benchmark's own runs never run this.
"""
import argparse
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from bench import harness, registry

    cell = registry.cell(args.workload)
    harness.require_chips(int(cell["workload"]["chips"]))
    for seed in args.seed:
        t0 = time.perf_counter()
        checks = control_checks(cell, seed, args.seconds)
        limits = cell["config"]["limits"]
        harness.emit({"workload": args.workload, "seed": seed,
                      "control": "reference from bfloat16 inputs",
                      "checks": checks, "limits": limits,
                      "correct": all(checks[c] <= limits[c]
                                     for c in harness.CHECKS),
                      "seconds": time.perf_counter() - t0})
    return 0


def control_checks(cell: dict, seed: int, seconds: float) -> dict:
    """The run's numbers with the bfloat16 reference as the program."""
    from bench import reference, registry, schedule, synth

    cfg, traffic = cell["config"], cell["traffic"]
    loop = registry.load_module("loops", traffic["loop"])
    data = synth.collection(schedule.prng_key(seed), cfg["num_series"],
                            cfg["series_len"])
    reqs = schedule.make_requests(traffic, seed, seconds, data,
                                  count=loop.count(traffic, seconds))
    rows = list(range(len(reqs)))
    answers = [None] * len(rows)
    ks = [reqs.k[i] for i in rows]
    queries = reqs.queries[rows]
    for k in sorted(set(ks)):
        sel = [j for j, kk in enumerate(ks) if kk == k]
        d, i = reference.knn(data, queries[sel], k, precision="bfloat16")
        for j, dd, ii in zip(sel, d, i):
            answers[j] = (dd, ii)
    return reference.compare(data, queries, ks, answers)


if __name__ == "__main__":
    sys.exit(main())
