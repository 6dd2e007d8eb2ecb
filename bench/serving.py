"""The benchmark's side of the serving front end: submit, step, claim.

``Server`` wraps the program's ``KnnServeEngine``: each ``step`` serves one
wave inside a ``bench.step`` span and claims the answers of the requests it
served, and ``counters`` reads the engine's telemetry as flat numbers, so
the loops can keep one reading per wave and the metrics take deltas.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.api import BuildConfig, IndexConfig, SearchConfig

from bench.meter import span


def index_config(cfg: dict) -> IndexConfig:
    """The program's index settings from a configuration's ``build`` and
    ``search`` groups (the program's defaults where a group is empty)."""
    return IndexConfig(build=BuildConfig(**cfg.get("build", {})),
                       search=SearchConfig(**cfg.get("search", {})))


@dataclasses.dataclass
class Wave:
    start: float                  # seconds since the window opened
    end: float
    served: int
    counters: dict                # engine counters after the wave


class Server:
    def __init__(self, server, engine):
        self.server = server
        self.engine = engine
        self._open: dict[int, int] = {}      # request id -> caller's index

    def submit(self, index: int, query: np.ndarray, k: int) -> None:
        with span("bench.submit"):
            rid = self.server.submit(query, k=k)
        self._open[rid] = index

    def outstanding(self) -> int:
        return len(self._open)

    def step(self) -> list:
        """Serve one wave; returns [(caller's index, answer or None)]. A
        failed request's answer is None."""
        with span("bench.step"):
            if not self.server.step():
                return []
        out = []
        for rid in list(self._open):
            ans = self.server.poll(rid)
            if ans is None:
                continue
            index = self._open.pop(rid)
            if hasattr(ans, "ids"):
                out.append((index, (np.asarray(ans.dists),
                                    np.asarray(ans.ids))))
            else:
                out.append((index, None))
        return out

    def counters(self) -> dict:
        t = self.engine.telemetry()
        paths = t.paths
        out = {"calls": t.calls, "queries": t.queries,
               "exec_s": t.latency.total,
               "plan_compiles": t.plan_cache.compiles,
               "known_paths": (paths.scan_eapca + paths.scan_sax
                               + paths.pruned + paths.forced_scan),
               "scan_paths": paths.scan_eapca + paths.scan_sax,
               "sax_mean": t.pruning.sax_mean,
               "eapca_mean": t.pruning.eapca_mean}
        if t.ooc is not None:
            out.update(rows_streamed=t.ooc.rows_streamed,
                       bytes_streamed=t.ooc.bytes_streamed,
                       sax_rows_read=t.ooc.sax_rows_read,
                       read_wait_s=t.ooc.read_wait_seconds,
                       blocks=t.ooc.blocks)
        return out
