"""Cells of BENCHMARK.json cut to a size a CPU test can hold, and the cells
kept out of it for now."""
from __future__ import annotations

import copy

from bench import registry

NUM_SERIES = 8192

# The disk cell, measured on the chip but kept out of BENCHMARK.json until
# its throughput holds steady from seed to seed (PERF.md, Open questions).
# Its files stay under bench/, and the tests keep them running.
DEFERRED = {
    "workloads": [{"name": "disk-easy-batch", "config": "synth256-disk",
                   "traffic": "easy-batch", "chips": 1}],
    "end_to_end": [{"name": "queries_per_s", "unit": "queries/s",
                    "better": "higher", "source": "host_clock",
                    "workloads": ["disk-easy-batch"]}],
    "per_layer": [{"name": name, "unit": unit, "moves": "queries_per_s",
                   "workloads": ["disk-easy-batch"]}
                  for name, unit in (
                      ("plan_ms_per_wave.batch", "ms"),
                      ("window_compiles.batch", "programs"),
                      ("rows_streamed_per_query.batch", "rows"),
                      ("read_wait_ms_per_wave.batch", "ms"),
                      ("device_idle_share.batch", "%"))],
}
DEFERRED_CELLS = [w["name"] for w in DEFERRED["workloads"]]


def cell(name: str) -> dict:
    """A cell of BENCHMARK.json or of ``DEFERRED``, at its own size."""
    bench = registry.benchmark()
    for key, entries in DEFERRED.items():
        bench[key] = bench[key] + entries
    return registry.cell(name, bench=bench)


def tiny_cell(name: str, **traffic) -> dict:
    out = copy.deepcopy(cell(name))
    cfg = out["config"]
    cfg["num_series"] = NUM_SERIES
    if cfg["deployment"] == "disk":
        cfg["memory_budget_mb"] = 2          # 8 MiB collection, 4x the budget
        cfg["build_chunk"] = 4096
        out["traffic"]["rehearse_max_s"] = 2.0
    out["traffic"].update(traffic)
    return out
