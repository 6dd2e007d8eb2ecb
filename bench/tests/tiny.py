"""Cells of BENCHMARK.json cut to a size a CPU test can hold."""
from __future__ import annotations

import copy

from bench import registry

NUM_SERIES = 8192


def tiny_cell(name: str, **traffic) -> dict:
    out = copy.deepcopy(registry.cell(name))
    cfg = out["config"]
    cfg["num_series"] = NUM_SERIES
    if cfg["deployment"] == "disk":
        cfg["memory_budget_mb"] = 2          # 8 MiB collection, 4x the budget
        cfg["build_chunk"] = 4096
    out["traffic"].update(traffic)
    return out
